"""Accelerated twins of :func:`repro.core.batch.liveness_peak_batch`.

The liveness assembly reduces every cell's alloc/free event program to a
segmented cummax: an ``(n_events, n_cells)`` int64 delta stack whose
per-cell peak is the max over running event-axis prefix sums.  The numpy
``cumsum(...).max(axis=0)`` in ``core.batch`` stays the reference; this
module evaluates the same reduction on accelerator backends:

* ``backend="jax"``    — a jitted cumsum + max-reduce (one compilation
  per (n_events, n_cells) shape);
* ``backend="pallas"`` — a Pallas kernel tiling the cell axis into VMEM
  blocks; the event axis (a handful of events, static per program) is
  unrolled at trace time into straight-line ``add``/``maximum`` vector
  ops, so each block does one pass over its tile with the running sum
  held in registers.  ``interpret=True`` runs it on CPU with identical
  integer math (pass ``interpret=False`` on TPU).

Exactness: int64 adds and maxes are associativity-free here — the
running sum is evaluated in event order, matching ``liveness.replay``'s
scalar prefix walk element-for-element.  Padding lanes are all-zero
columns whose peak is 0 and are sliced off before returning.

``use_backend("jax"|"pallas")`` installs the accelerated twin as
``core.batch``'s liveness-peak implementation for the dynamic extent of
the context, so full columnar liveness sweeps route the prefix-max
through the kernel; parity with the reference is asserted on real
sweeps in tests/test_segmented_cummax.py.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

I64 = np.int64

_BLOCK = 256


# ---------------------------------------------------------------------------
# jax backend: jitted cumsum + max-reduce
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jax_eval():
    import jax
    import jax.numpy as jnp

    def run(deltas):
        return jnp.cumsum(deltas, axis=0).max(axis=0)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# pallas backend: unrolled running sum on VMEM tiles
# ---------------------------------------------------------------------------


def _pallas_kernel(deltas_ref, peak_ref, *, n_events):
    import jax.numpy as jnp

    run = deltas_ref[0, :]
    peak = run
    for e in range(1, n_events):        # static: unrolls at trace time
        run = run + deltas_ref[e, :]
        peak = jnp.maximum(peak, run)
    peak_ref[...] = peak[None, :]


@functools.lru_cache(maxsize=None)
def _pallas_eval(n_events, n_pad, block, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    grid = (n_pad // block,)
    call = pl.pallas_call(
        functools.partial(_pallas_kernel, n_events=n_events),
        grid=grid,
        in_specs=[pl.BlockSpec((n_events, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int64),
        interpret=interpret,
    )
    return jax.jit(lambda d: call(d)[0])


# ---------------------------------------------------------------------------
# drop-in twin + backend switch
# ---------------------------------------------------------------------------


def segmented_cummax(deltas, backend: str = "jax", block: int = _BLOCK,
                     interpret: bool = False) -> np.ndarray:
    """Drop-in twin of :func:`repro.core.batch.liveness_peak_batch`
    (``backend="numpy"`` delegates to the reference; ``"jax"`` and
    ``"pallas"`` produce byte-identical int64 peaks)."""
    deltas = np.asarray(deltas, I64)
    if backend == "numpy":
        return np.cumsum(deltas, axis=0).max(axis=0)
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown segmented-cummax backend {backend!r}")
    n_events, n = deltas.shape

    import jax

    with jax.enable_x64(True):
        if backend == "jax":
            out = _jax_eval()(deltas)
        else:
            blk = min(block, max(n, 1))
            pad = (-n) % blk
            if pad:                 # all-zero lanes peak at 0, discarded
                deltas = np.pad(deltas, ((0, 0), (0, pad)))
            fn = _pallas_eval(n_events, n + pad, blk, interpret)
            out = fn(deltas)[:n]
        return np.asarray(out, I64)


@contextlib.contextmanager
def use_backend(backend: str = "jax", interpret: bool = False):
    """Route ``core.batch.liveness_peak_batch`` through an accelerated
    backend for the dynamic extent of the context (``"numpy"`` is a
    no-op).  Used by tests to run real columnar liveness sweeps through
    the kernels and assert byte-parity, and by on-device sweeps where
    the prefix-max should stay on the accelerator."""
    from repro.core import batch as B

    if backend == "numpy":
        yield
        return
    impl = functools.partial(segmented_cummax, backend=backend,
                             interpret=interpret)
    prev = B._liveness_peak_impl
    B._liveness_peak_impl = impl
    try:
        yield
    finally:
        B._liveness_peak_impl = prev
