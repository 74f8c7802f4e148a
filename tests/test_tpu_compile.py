"""The main path's fast kernels compile for a described TPU v5e.

No chip is attached: each program is lowered through the real Mosaic
path (`launch.force_mosaic`) and compiled by the TPU compiler for device
0 of a described v5e:2x2 topology.  That shows what interpret-mode tests
cannot: tiling, VMEM and layout refusals.  Only compiles of a few
seconds live here; the slow stages (scalar muls, Miller loop, final
exponentiation) and the whole programs the node dispatches are in
dev/rehearse_tpu_compile.py.
"""

import jax
import jax.numpy as jnp
import pytest

from lodestar_tpu.kernels import launch
from lodestar_tpu.kernels import sha256 as SH
from lodestar_tpu.kernels import verify as KV

NL, BT = KV.NL, KV.BT
TABLE_ROWS = 1_000_000  # the mainnet registry the node keeps in HBM


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache
    # but never read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    with launch.force_mosaic():
        return jax.jit(fn).lower(*specs).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("width", [4, 8])
def test_montgomery_ingest_compiles(one_chip, width):
    """Plain limbs -> Montgomery form, the first device stage of every
    wire job (`_k_mont4`) and of the decoded path (`_k_mont8`)."""
    kernel = KV._k_mont4 if width == 4 else KV._k_mont8
    compiled = _compile(
        lambda *p: KV._tiled(kernel, p, [NL] * width, [NL] * width, BT),
        [((NL, BT), jnp.int32)] * width,
        one_chip,
    )
    assert _kernels(compiled) == 1


def test_pubkey_gather_compiles_at_registry_size(one_chip):
    """The per-set pubkey gather from the 1M-row device table."""
    compiled = _compile(
        KV._gather_pk,
        [
            ((NL, TABLE_ROWS), jnp.int32),
            ((NL, TABLE_ROWS), jnp.int32),
            ((BT, 1), jnp.int32),
            ((BT, 1), jnp.int32),
        ],
        one_chip,
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * NL * TABLE_ROWS * 4


def test_sha256_hash_pairs_compiles(one_chip):
    """One merkle level of the state-root hash forest."""
    compiled = _compile(
        SH.hash_pairs_device, [((512, 16), jnp.uint32)], one_chip
    )
    assert compiled.memory_analysis().output_size_in_bytes == 512 * 8 * 4
