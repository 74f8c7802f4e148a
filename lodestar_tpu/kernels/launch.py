"""Cached pallas_call constructors.

A `pl.pallas_call(...)` created fresh per invocation RE-TRACES its
kernel body on every call (measured: 3 calls through a rebuilt wrapper
= 3 kernel traces; a wrapper built once = 1).  The verify pipeline's
kernel bodies trace to ~1e5-equation jaxprs, so per-job re-tracing
costs minutes of host time — the wrappers MUST be built once per
(kernel, shape signature) and reused.  Every pallas launch in the
pipeline goes through this module's cache.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
from jax.experimental import pallas as pl

_CACHE: Dict[Tuple, Callable] = {}

# When True, launches lower through the REAL Mosaic path regardless of
# the host backend — the AOT export cache sets this while tracing a
# TPU-platform artifact on a CPU host (kernels/export_cache.py).
_FORCE_MOSAIC = False


class force_mosaic:
    """Context manager: lower pallas launches for the real TPU backend
    even when the process default backend is CPU (cross-platform
    jax.export)."""

    def __enter__(self):
        global _FORCE_MOSAIC
        self._prev = _FORCE_MOSAIC
        _FORCE_MOSAIC = True

    def __exit__(self, *exc):
        global _FORCE_MOSAIC
        _FORCE_MOSAIC = self._prev


def interpret() -> bool:
    if _FORCE_MOSAIC:
        return False
    return jax.default_backend() != "tpu"


def _sds(shape):
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _count_build(kind: str) -> None:
    """Wrapper-construction tally (one per (kernel, shape) signature):
    a rebuilt wrapper means a kernel RE-TRACE, so a climbing counter is
    the named symptom of the per-job re-tracing this module exists to
    prevent.  Process-global registry — lands on /metrics."""
    from ..utils.metrics import global_registry

    global_registry().labeled_counter(
        "lodestar_tpu_pallas_builds_total",
        "pallas_call wrapper constructions (each implies a kernel trace)",
        "kind",
    ).inc(kind, 1.0)


def tiled(kernel, ins, in_rows, out_rows, n: int, bt: int):
    """Lane-tiled launch: operands [rows, n] blocked to [rows, bt]."""
    assert n % bt == 0, n
    interp = interpret()
    key = ("tiled", kernel, tuple(in_rows), tuple(out_rows), n, bt, interp)
    fn = _CACHE.get(key)
    if fn is None:
        _count_build("tiled")
        fn = pl.pallas_call(
            kernel,
            out_shape=[_sds((r, n)) for r in out_rows],
            grid=(n // bt,),
            in_specs=[
                pl.BlockSpec((r, bt), lambda i: (0, i)) for r in in_rows
            ],
            out_specs=[
                pl.BlockSpec((r, bt), lambda i: (0, i)) for r in out_rows
            ],
            interpret=interp,
        )
        _CACHE[key] = fn
    return fn(*ins)


def cached(key: Tuple, builder: Callable[[], Callable]) -> Callable:
    """Generic slot for non-tiled launch shapes (grid accumulations,
    gather/aggregate).  `key` must capture everything the builder
    closes over; the interpret flag is appended automatically."""
    full = key + (interpret(),)
    fn = _CACHE.get(full)
    if fn is None:
        _count_build("cached")
        fn = builder()
        _CACHE[full] = fn
    return fn
