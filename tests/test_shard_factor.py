"""Packed shard-factor kernels (kernels/shard_factor.py): the jax and
pallas evaluators must reproduce core.batch.batch_shard_factor — the
greedy masked axis assignment — byte for byte on randomized programs
and on real columnar sweeps routed through use_backend().
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import batch as B  # noqa: E402
from repro.core import sweep as SW  # noqa: E402
from repro.kernels import shard_factor as K  # noqa: E402

RNG = np.random.default_rng(20260808)

MESH_AXES = ("data", "model", "expert", "context", "pipe")
LOGICAL = ("batch", "heads", "dmodel", "seq", "experts", "layers")


def random_program(rng, n_cells):
    """One randomized (dims, axes, sizes, rules, extra) instance with
    the reference's edge cases reachable: pipe in rules (never shards),
    the layers stack dim (excluded from the extra pass), multi-axis
    rules, size-1 (dead) axes, and dims with no rule at all."""
    rules = {}
    for name in LOGICAL:
        k = rng.integers(0, 3)
        rules[name] = tuple(
            rng.choice(MESH_AXES, size=k, replace=False)) if k else ()
    n_dims = int(rng.integers(1, 5))
    axes = tuple(rng.choice(LOGICAL + (None,)) for _ in range(n_dims))
    dims = [rng.choice([1, 2, 3, 4, 6, 8, 12, 16, 24, 64],
                       size=n_cells).astype(np.int64)
            for _ in range(n_dims)]
    sizes = {a: rng.choice([1, 1, 2, 4, 8], size=n_cells).astype(np.int64)
             for a in MESH_AXES}
    extra = tuple(rng.choice(MESH_AXES,
                             size=int(rng.integers(0, 3)),
                             replace=False))
    return dims, axes, sizes, rules, extra


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_randomized_program_parity(backend):
    for trial in range(25):
        dims, axes, sizes, rules, extra = random_program(RNG, n_cells=17)
        ref = B.batch_shard_factor(dims, axes, sizes, rules, extra)
        got = K.shard_factor(dims, axes, sizes, rules, extra,
                             backend=backend, interpret=True)
        assert got.dtype == np.int64
        assert np.array_equal(np.asarray(got), ref), \
            f"trial {trial}: {axes} rules={rules} extra={extra}"


def test_scalar_and_broadcast_inputs():
    """Int dims and mixed scalar/array sizes broadcast like the
    reference."""
    dims = [8, np.array([4, 8, 16], dtype=np.int64)]
    axes = ("batch", "heads")
    rules = {"batch": ("data",), "heads": ("model",)}
    sizes = {"data": 2, "model": np.array([1, 2, 4], dtype=np.int64)}
    ref = B.batch_shard_factor(dims, axes, sizes, rules, ())
    got = K.shard_factor(dims, axes, sizes, rules, (), backend="jax")
    assert np.array_equal(np.asarray(got), ref)


def test_pallas_pads_partial_blocks():
    """Lane counts that don't divide the block are padded with neutral
    cells and trimmed — answers unchanged."""
    dims, axes, sizes, rules, extra = random_program(RNG, n_cells=7)
    ref = B.batch_shard_factor(dims, axes, sizes, rules, extra)
    got = K.shard_factor(dims, axes, sizes, rules, extra,
                         backend="pallas", block=4, interpret=True)
    assert np.array_equal(np.asarray(got), ref)


def test_pack_program_shape():
    steps, names = K.pack_program(
        axes=("batch", "heads"),
        rules={"batch": ("data",), "heads": ("model", "data")},
        extra=("data",), axis_names=("data", "model"))
    assert names and set(names) <= {"data", "model"}
    assert all(len(s) == 3 for s in steps)
    # rules steps for both dims, then the extra pass per dim
    flags = [f for (_, _, f) in steps]
    assert 0 in flags and 2 in flags
    # axes outside axis_names are dropped (the dead-axis filter)
    steps2, names2 = K.pack_program(
        axes=("batch",), rules={"batch": ("data",)}, extra=(),
        axis_names=())
    assert not steps2 and not names2


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        K.shard_factor([4], ("batch",), {"data": 2},
                       {"batch": ("data",)}, (), backend="cuda")


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_use_backend_real_sweep_parity(backend):
    """A real columnar sweep with batch_shard_factor routed through the
    kernel: verdicts and peaks byte-identical to the numpy path."""
    grid = SW.SweepGrid(arch="smollm-360m", chips=(2, 4), chip="v5e",
                        global_batches=(8, 16), seq_lens=(512,),
                        microbatches=(1, 2), kind="train")
    ref = SW.SweepEngine().sweep(grid)
    with K.use_backend(backend, interpret=True):
        got = SW.SweepEngine().sweep(grid)
    assert np.array_equal(got.columns.peak_bytes, ref.columns.peak_bytes)
    assert np.array_equal(got.columns.fits, ref.columns.fits)


def test_use_backend_restores_impl():
    assert B._shard_factor_impl is None
    with K.use_backend("jax"):
        assert B._shard_factor_impl is not None
    assert B._shard_factor_impl is None
    with pytest.raises(RuntimeError):
        with K.use_backend("pallas", interpret=True):
            assert B._shard_factor_impl is not None
            raise RuntimeError("boom")
    assert B._shard_factor_impl is None
    # numpy is a no-op route
    with K.use_backend("numpy"):
        assert B._shard_factor_impl is None
