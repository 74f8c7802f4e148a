"""Exact-integer validation of the pallas field engine's limb core.

Checks `kernels.core` value ops (run under plain jit on CPU — identical
int32 semantics to the in-kernel path) against exact Python-int mirrors,
including the bound discipline from kernels/layout.py.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lodestar_tpu.kernels import core as C
from lodestar_tpu.kernels import layout as LY

pytestmark = pytest.mark.smoke

random.seed(0xC0DE)
P = LY.P
B = 64


def rand_elems(n):
    return [random.randrange(P) for n_ in range(n)]


def enc(xs):
    return jnp.asarray(LY.encode_batch(xs))


def dec(arr):
    return LY.decode_batch(np.asarray(arr))


def mont(x):
    return x * LY.R_MOD_P % P


def test_codec_roundtrip():
    xs = rand_elems(B) + [0, 1, P - 1]
    assert dec(enc(xs)) == [x % P for x in xs]


@pytest.mark.parametrize("width", [1, 128, 256])
def test_decode_batch_matches_per_lane_decode(width):
    # signed lazy-form limbs at the L- and T-bounds
    rng = np.random.default_rng(width)
    a = rng.integers(-4103, 4104, size=(LY.NL, width)).astype(np.int32)
    a[-1] = rng.integers(-300, 301, size=width)
    a[:-1, 0], a[-1, 0] = -4103, -300  # the most negative value
    want = [LY.from_limbs(a[:, j]) * LY.R_INV % P for j in range(width)]
    assert LY.decode_batch(a) == want
    assert LY.decode_batch(jnp.asarray(a)) == want
    assert all(type(v) is int for v in LY.decode_batch(a))


def test_fold_preserves_value():
    rng = np.random.default_rng(1)
    t = rng.integers(-(1 << 29), 1 << 29, size=(LY.NC, B)).astype(np.int32)
    folded = np.asarray(jax.jit(C.fold)(jnp.asarray(t)))
    for j in range(B):
        assert LY.from_limbs(folded[:, j]) == LY.from_limbs(t[:, j])


def test_mul_cols_exact():
    rng = np.random.default_rng(2)
    a = rng.integers(-4103, 4104, size=(LY.NL, B)).astype(np.int32)
    b = rng.integers(-4103, 4104, size=(LY.NL, B)).astype(np.int32)
    cols = np.asarray(jax.jit(C.mul_cols)(jnp.asarray(a), jnp.asarray(b)))
    for j in range(2):
        va = LY.from_limbs(a[:, j])
        vb = LY.from_limbs(b[:, j])
        assert LY.from_limbs(cols[:, j].astype(object)) == va * vb


def test_mont_mul_matches_field():
    xs, ys = rand_elems(B), rand_elems(B)
    out = jax.jit(C.mont_mul)(enc(xs), enc(ys))
    got = dec(out)
    want = [x * y % P for x, y in zip(xs, ys)]
    assert got == want
    # limb bound (public class)
    o = np.asarray(out)
    assert o.min() >= -2 and o.max() <= 4103


def test_mont_mul_lazy_chains():
    """Chained mul/add/sub keeps values and bounds in class."""
    xs, ys, zs = rand_elems(B), rand_elems(B), rand_elems(B)
    a, b, c = enc(xs), enc(ys), enc(zs)

    @jax.jit
    def f(a, b, c):
        t = C.mont_mul(C.add(a, b), C.sub(b, C.neg(c)))
        u = C.sub(C.mont_mul(t, t), C.add(c, C.add(a, C.mont_mul(b, c))))
        return C.mont_mul(u, C.sub(u, a))

    got = dec(f(a, b, c))
    want = []
    for x, y, z in zip(xs, ys, zs):
        t = (x + y) * (y + z) % P
        u = (t * t - (z + x + y * z)) % P
        want.append(u * (u - x) % P)
    assert got == want


def test_mont_mul_shared():
    xs = rand_elems(B)
    k = 0x1234567890ABCDEF1122334455667788
    w = [int(v) for v in LY.const_mont(k)]
    got = dec(jax.jit(lambda a: C.mont_mul_shared(a, w))(enc(xs)))
    assert got == [x * k % P for x in xs]


def test_mul_small_and_neg():
    xs = rand_elems(B)
    got = dec(jax.jit(lambda a: C.mul_small(a, 7))(enc(xs)))
    assert got == [7 * x % P for x in xs]
    got = dec(jax.jit(lambda a: C.neg(C.mul_small(a, 2)))(enc(xs)))
    assert got == [(-2 * x) % P for x in xs]


def test_is_zero_modp():
    xs = rand_elems(8)
    variants = []
    for x in xs:
        variants += [x, 0]
    a = enc(variants)

    @jax.jit
    def f(a, b):
        # exercise lazy forms: x*1 - x, sums, negs
        d = C.sub(C.add(a, b), C.add(b, a))
        return (
            C.is_zero_modp(a),
            C.is_zero_modp(d),
            C.is_zero_modp(C.sub(a, C.neg(C.neg(a)))),
        )

    za, zd, zs = f(a, enc(rand_elems(len(variants))))
    want = [x % P == 0 for x in variants]
    assert list(np.asarray(za)) == want
    assert bool(np.asarray(zd).all()) and bool(np.asarray(zs).all())


def test_eq_modp_on_lazy_forms():
    xs = rand_elems(B)
    a = enc(xs)

    @jax.jit
    def f(a):
        twice = C.add(a, a)
        other = C.sub(C.mul_small(a, 3), a)
        return C.eq_modp(twice, other), C.eq_modp(twice, a)

    eq1, eq2 = f(a)
    assert bool(np.asarray(eq1).all())
    want2 = [(2 * x - x) % P == 0 for x in xs]
    assert list(np.asarray(eq2)) == want2


def test_redc_bound_stress():
    """Random deep op chains stay within limb bounds (empirical V-bound)."""
    rng = random.Random(7)
    xs = [rand_elems(B) for _ in range(4)]
    args = [enc(x) for x in xs]

    @jax.jit
    def f(a, b, c, d):
        vals = [a, b, c, d]
        for i in range(40):
            x = vals[i % 4]
            y = vals[(i + 1) % 4]
            vals[i % 4] = C.mont_mul(C.sub(C.add(x, y), C.neg(y)), C.sub(x, y))
        return vals

    outs = f(*args)
    mirror = [list(x) for x in xs]
    for i in range(40):
        x = mirror[i % 4]
        y = mirror[(i + 1) % 4]
        mirror[i % 4] = [
            ((xx + 2 * yy) * (xx - yy)) % P for xx, yy in zip(x, y)
        ]
    for got_arr, want in zip(outs, mirror):
        assert dec(got_arr) == want
        o = np.asarray(got_arr)
        assert o.min() >= -2 and o.max() <= 4103
