"""Accelerated twins of :func:`repro.core.batch.batch_shard_factor`.

The greedy axis-assignment pass (divisibility masks, one-use-per-axis,
the FSDP/ZeRO ``extra`` sweep) is the inner loop of columnar table
building: every TermSpec resolves its shard denominator through it, a
few hundred times per stage-table group.  The numpy transliteration in
``core.batch`` stays the reference; this module *packs* the greedy
program — the (dim, axis, pass) step sequence that the reference's
Python loops walk — into flat int32 step arrays and evaluates all
elements of the broadcast domain in one fused pass:

* ``backend="jax"``   — a jitted ``lax.fori_loop`` over the packed
  steps (one compilation per (n_dims, n_axes, n_steps, n_cells) shape,
  shared by every program with that shape);
* ``backend="pallas"`` — a Pallas kernel with the step list closed over
  as Python constants, so the body unrolls into straight-line vector
  ops on a (dims+axes, block) VMEM tile; ``interpret=True`` runs it on
  CPU with identical integer math (pass ``interpret=False`` on TPU).

Exactness: the packed form drops the reference's ``live`` size-1 axis
skip — a size-1 axis multiplies every factor by 1 and marking it used
only ever blocks another x1 attempt, so including such steps is
value-identical per element (the reference documents the same argument
for all-ones *columns*; here it holds per cell).  Globally dead axes
are still dropped host-side as a pure optimisation.  Everything is
int64 + floor-division under ``jax.enable_x64(True)`` — parity
with the reference is asserted step-for-step on randomized programs and
on real sweeps in tests/test_shard_factor.py.

``use_backend("jax"|"pallas")`` installs the accelerated twin as
``core.batch``'s shard-factor implementation for the dynamic extent of
the context, so full columnar sweeps (and therefore the jax engine's
table building) route divisibility resolution through the kernel.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from repro.mesh_ctx import PIPE_AXIS

I64 = np.int64

_BLOCK = 256


# ---------------------------------------------------------------------------
# program packing
# ---------------------------------------------------------------------------

# step flags: 0 = rules pass; 1 = extra pass; 2 = extra pass, first step
# of a new extra axis (resets the per-axis `assigned` register)
_RULES, _EXTRA, _EXTRA_FIRST = 0, 1, 2


def pack_program(axes, rules: dict, extra=(), axis_names=()):
    """Flatten the greedy assignment into (dim, axis, flag) step triples.

    ``axis_names`` lists the mesh axes that participate (order defines
    the axis ids of the packed program); axes not in it are skipped,
    mirroring the reference's ``live`` filter.  Returns
    ``(steps, names)`` where ``steps`` is a tuple of int triples and
    ``names`` the axis-id -> name order actually referenced.
    """
    ids: dict[str, int] = {}
    steps: list[tuple[int, int, int]] = []
    allowed = set(axis_names)
    for i, ax in enumerate(axes):
        if not ax:
            continue
        for a in rules.get(ax, ()):
            if a == PIPE_AXIS or a not in allowed:
                continue
            steps.append((i, ids.setdefault(a, len(ids)), _RULES))
    for a in extra:
        if a == PIPE_AXIS or a not in allowed:
            continue
        first = True
        for i in range(len(axes)):
            if axes[i] == "layers":     # never FSDP/ZeRO-shard the stack dim
                continue
            steps.append((i, ids.setdefault(a, len(ids)),
                          _EXTRA_FIRST if first else _EXTRA))
            first = False
    names = [a for a, _ in sorted(ids.items(), key=lambda kv: kv[1])]
    return tuple(steps), names


# ---------------------------------------------------------------------------
# jax backend: jitted fori_loop over packed step arrays
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jax_eval():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(arrs, sizes, dim_i, ax_i, flags):
        n = arrs.shape[1]
        init = (jnp.ones_like(arrs),                    # per-dim totals
                jnp.zeros(sizes.shape, bool),           # per-axis used
                jnp.ones((n,), arrs.dtype),             # denom
                jnp.zeros((n,), bool))                  # extra `assigned`

        def step(k, carry):
            totals, used, denom, assigned = carry
            d, a, fl = dim_i[k], ax_i[k], flags[k]
            assigned = jnp.where(fl == _EXTRA_FIRST, False, assigned)
            sv = sizes[a]
            ok = (arrs[d] % (totals[d] * sv) == 0) & ~used[a]
            ok = ok & jnp.where(fl > 0, ~assigned, True)
            mul = jnp.where(ok, sv, 1)
            return (totals.at[d].multiply(mul), used.at[a].set(used[a] | ok),
                    denom * mul, jnp.where(fl > 0, assigned | ok, assigned))

        return lax.fori_loop(0, dim_i.shape[0], step, init)[2]

    return jax.jit(run)


# ---------------------------------------------------------------------------
# pallas backend: unrolled step program on VMEM tiles
# ---------------------------------------------------------------------------


def _pallas_kernel(arrs_ref, sizes_ref, denom_ref, *, steps):
    import jax.numpy as jnp

    arrs = arrs_ref[...]
    sizes = sizes_ref[...]
    totals = jnp.ones_like(arrs)
    used = jnp.zeros(sizes.shape, bool)
    denom = jnp.ones_like(arrs[0])
    assigned = jnp.zeros_like(denom, bool)
    for d, a, fl in steps:                  # static: unrolls at trace time
        if fl == _EXTRA_FIRST:
            assigned = jnp.zeros_like(assigned)
        ok = (arrs[d] % (totals[d] * sizes[a]) == 0) & ~used[a]
        if fl:
            ok = ok & ~assigned
        mul = jnp.where(ok, sizes[a], 1)
        totals = totals.at[d].multiply(mul)
        denom = denom * mul
        used = used.at[a].set(used[a] | ok)
        if fl:
            assigned = assigned | ok
    denom_ref[...] = denom[None, :]


@functools.lru_cache(maxsize=None)
def _pallas_eval(steps, n_dims, n_axes, n_pad, block, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    grid = (n_pad // block,)
    call = pl.pallas_call(
        functools.partial(_pallas_kernel, steps=steps),
        grid=grid,
        in_specs=[pl.BlockSpec((n_dims, block), lambda i: (0, i)),
                  pl.BlockSpec((n_axes, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int64),
        interpret=interpret,
    )
    return jax.jit(lambda a, s: call(a, s)[0])


# ---------------------------------------------------------------------------
# drop-in twin + backend switch
# ---------------------------------------------------------------------------


def shard_factor(dims, axes, sizes: dict, rules: dict, extra=(),
                 backend: str = "jax", block: int = _BLOCK,
                 interpret: bool = False) -> np.ndarray:
    """Drop-in twin of :func:`repro.core.batch.batch_shard_factor`.

    ``backend="numpy"`` delegates to the reference; ``"jax"`` and
    ``"pallas"`` evaluate the packed program (byte-identical int64).
    """
    if backend == "numpy":
        from repro.core import batch as B
        return B.batch_shard_factor(dims, axes, sizes, rules, extra)
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown shard-factor backend {backend!r}")

    arrs = [np.asarray(d, I64) for d in dims]
    svals = {a: np.asarray(v, I64) for a, v in sizes.items()}
    shape = np.broadcast_shapes(*(a.shape for a in arrs),
                                *(v.shape for v in svals.values()))
    live = [a for a, v in svals.items() if np.any(v > 1)]
    steps, names = pack_program(axes, rules, extra, axis_names=live)
    if not steps or not arrs:
        return np.broadcast_to(np.ones((), I64), shape)

    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    a2 = np.stack([np.broadcast_to(a, shape).reshape(n) for a in arrs])
    s2 = np.stack([np.broadcast_to(svals[a], shape).reshape(n)
                   for a in names])

    import jax

    with jax.enable_x64(True):
        if backend == "jax":
            st = np.asarray(steps, np.int32)
            out = _jax_eval()(a2, s2, st[:, 0], st[:, 1], st[:, 2])
        else:
            blk = min(block, max(n, 1))
            pad = (-n) % blk
            if pad:                         # padded lanes: 1 % 1 == 0, discarded
                a2 = np.pad(a2, ((0, 0), (0, pad)), constant_values=1)
                s2 = np.pad(s2, ((0, 0), (0, pad)), constant_values=1)
            fn = _pallas_eval(steps, a2.shape[0], s2.shape[0], n + pad,
                              blk, interpret)
            out = fn(a2, s2)[:n]
        return np.asarray(out, I64).reshape(shape)


@contextlib.contextmanager
def use_backend(backend: str = "jax", interpret: bool = False):
    """Route ``core.batch.batch_shard_factor`` through an accelerated
    backend for the dynamic extent of the context (``"numpy"`` is a
    no-op).  Used by tests to run real columnar sweeps through the
    kernels and assert byte-parity."""
    from repro.core import batch as B

    if backend == "numpy":
        yield
        return
    impl = functools.partial(shard_factor, backend=backend,
                             interpret=interpret)
    prev = B._shard_factor_impl
    B._shard_factor_impl = impl
    try:
        yield
    finally:
        B._shard_factor_impl = prev
