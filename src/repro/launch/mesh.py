"""Production meshes and the sharding policy.

``make_production_mesh`` builds the assigned meshes: (16, 16) single pod
(256 chips) and (2, 16, 16) multi-pod (512 chips; ``pod`` is the
DCN-connected data-parallel axis).  Importing this module never touches
jax device state — everything is a function.

``param_shardings`` / ``opt_shardings`` / ``batch_shardings`` derive
NamedShardings from the spec tree's logical axes through the single
resolution path in ``repro.mesh_ctx`` — the same path the memory predictor
uses arithmetically.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.mesh_ctx import (CONTEXT_AXIS, DEFAULT_RULES, EXPERT_AXIS,
                            PIPE_AXIS, assign_axes, mesh_axis_sizes,
                            resolve_pspec)
from repro.models.registry import Model
from repro.train.optimizer import OptimizerConfig, opt_state_specs


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def divisors(n: int) -> list[int]:
    """Positive divisors of ``n``, ascending."""
    small, large = [], []
    d = 1
    while d * d <= n:
        if n % d == 0:
            small.append(d)
            if d != n // d:
                large.append(n // d)
        d += 1
    return small + large[::-1]


def factorizations(n: int, k: int) -> list[tuple[int, ...]]:
    """All ordered ``k``-tuples of positive ints whose product is ``n``.

    Ordered means (2, 8) and (8, 2) are distinct — mesh axes are named, so
    data=2/model=8 and data=8/model=2 are different parallelism plans.
    """
    if n < 1 or k < 1:
        raise ValueError(f"need n >= 1, k >= 1; got n={n}, k={k}")
    if k == 1:
        return [(n,)]
    out = []
    for d in divisors(n):
        for rest in factorizations(n // d, k - 1):
            out.append((d,) + rest)
    return out


def enumerate_meshes(n_chips: int,
                     axes: tuple[str, ...] = ("data", "model"),
                     max_axis: Optional[dict] = None) -> list[dict]:
    """Every mesh shape that lays ``n_chips`` out over the named ``axes``.

    The capacity-planning sweep feeds each of these to the memory predictor
    to find which parallelism plans fit.  ``max_axis`` caps individual axes
    (e.g. ``{"model": 16}`` — an ICI-connected TP axis rarely exceeds a
    pod's torus dimension; ``{"pipe": 8}`` bounds pipeline depth).
    Results are deduplicated and sorted by descending data-parallel degree
    (the conventional preference: DP is the cheapest axis,
    collectives-wise).  Including :data:`~repro.mesh_ctx.PIPE_AXIS` in
    ``axes`` enumerates pipeline-parallel plans: chips along ``pipe`` hold
    disjoint layer stages (core.stages) and never shard tensors.
    Including :data:`~repro.mesh_ctx.EXPERT_AXIS` /
    :data:`~repro.mesh_ctx.CONTEXT_AXIS` enumerates expert-parallel and
    context-parallel (ring-attention) plans, capped by
    ``{"expert": N}`` / ``{"context": N}`` (CLI ``--max-expert`` /
    ``--max-context``); the planner rejects plans that are invalid for
    the architecture or step kind (``planner.check_parallel``).
    """
    seen: set[tuple[int, ...]] = set()
    out: list[dict] = []
    for f in factorizations(n_chips, len(axes)):
        if f in seen:
            continue
        seen.add(f)
        if max_axis and any(f[i] > max_axis.get(a, f[i])
                            for i, a in enumerate(axes)):
            continue
        out.append(dict(zip(axes, f)))
    out.sort(key=lambda m: tuple(-m[a] for a in axes))
    return out


def mesh_chips(mesh_shape: dict) -> int:
    """Total chip count of a mesh-shape dict."""
    total = 1
    for v in mesh_shape.values():
        total *= v
    return total


def pp_degree(mesh_shape: dict) -> int:
    """Pipeline-stage count of a mesh shape (1 when it has no pipe axis)."""
    return int(mesh_shape.get(PIPE_AXIS, 1))


def ep_degree(mesh_shape: dict) -> int:
    """Expert-parallel degree of a mesh shape (1 without an expert axis)."""
    return int(mesh_shape.get(EXPERT_AXIS, 1))


def cp_degree(mesh_shape: dict) -> int:
    """Context-parallel degree of a mesh shape (1 without a context axis)."""
    return int(mesh_shape.get(CONTEXT_AXIS, 1))


def make_smoke_mesh(data: int = 1, model: int = 1,
                    devices=None) -> Mesh:
    """A (data, model) mesh over the first ``data * model`` of
    ``devices`` (default: the local devices) — the launcher's mesh on a
    chip, a tiny one in CPU tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices)


# ---------------------------------------------------------------------------
# sharding policy
# ---------------------------------------------------------------------------


def arch_rules(cfg, kind: str = "train") -> dict:
    """Per-arch logical->physical rule overrides."""
    rules = dict(DEFAULT_RULES)
    if kind in ("train", "prefill") and cfg.seq_parallel:
        # Sequence parallelism: the residual stream (and therefore the
        # per-layer saved scan carry — the dominant training activation)
        # is sharded over `model` as well as `data`.  Attention math stays
        # global; GSPMD inserts the gather/scatter collectives.  Without
        # this, 30B+ archs cannot fit 16 GiB/chip at train_4k.
        rules["seq"] = ("model",)
    if kind in ("train", "prefill"):
        # Context parallelism (ring attention): the seq dim of every
        # activation shards over `context` FIRST, SP's `model` split on
        # what stays divisible.  Decode is token-at-a-time — no seq dim
        # to split — so cp is rejected there (planner.check_parallel)
        # and the decode `cache_seq` rule below never names `context`.
        rules["seq"] = (CONTEXT_AXIS,) + rules["seq"]
    if kind == "prefill":
        # prefill caches derive from the seq-sharded residual stream, so
        # XLA lays them out seq-sharded over `model` (matches SP) — and,
        # under ring attention, over `context` first: each cp rank
        # computes and holds only its sequence block's KV.  (Decode
        # below is different: cp is rejected there, and its caches
        # shard over `model` only.)
        rules["cache_seq"] = (CONTEXT_AXIS, "model")
    elif kind == "decode":
        # Decode caches shard their sequence dim over `model`: none of the
        # zoo's GQA head counts fill a 16-way axis (8, 5, 16...), so
        # head-sharding strands memory, while seq-sharding divides the one
        # buffer that dominates serving (observed 16x: llama3.2 decode_32k
        # cache 28.4 -> 1.8 GiB/device).  MLA latents have no head dim at
        # all.  XLA turns the per-step attention into a sharded partial
        # softmax + cross-shard reduce.
        rules["cache_seq"] = ("model",)
    return rules


def param_shardings(model: Model, mesh: Mesh) -> Any:
    axes_tree = model.param_axes()
    specs_tree = model.param_specs()
    extra = ("data",) if model.cfg.fsdp else ()

    def leaf(ax, sd):
        return NamedSharding(mesh, resolve_pspec(sd.shape, ax, mesh,
                                                 extra=extra))

    return jax.tree.map(leaf, axes_tree, specs_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def opt_shardings(model: Model, mesh: Mesh, trainable_specs: Any,
                  opt_cfg: OptimizerConfig,
                  trainable_axes: Any) -> Any:
    """ZeRO sharding: optimizer-state leaves inherit the param's logical
    axes where shapes line up, plus an extra `data` shard."""
    state_specs = opt_state_specs(trainable_specs, opt_cfg)

    def leaf_state(pspec_axes, pshape, st):
        if st is None:
            return None
        out = {}
        for name, s in st.items():
            if tuple(s.shape) == tuple(pshape):
                ax = pspec_axes
            elif len(s.shape) == len(pshape) - 1 \
                    and tuple(s.shape) == tuple(pshape[:-1]):
                ax = pspec_axes[:-1]                 # adafactor v_row
            elif len(s.shape) == len(pshape) - 1 \
                    and tuple(s.shape) == tuple(pshape[:-2] + pshape[-1:]):
                ax = pspec_axes[:-2] + pspec_axes[-1:]  # adafactor v_col
            else:
                ax = (None,) * len(s.shape)          # 8-bit blocks etc.
            out[name] = NamedSharding(
                mesh, resolve_pspec(s.shape, ax, mesh, extra=("data",)))
        return out

    # axes leaves are tuples => is_leaf stops descent there; the matching
    # state subtree (a dict of arrays) is passed whole to leaf_state.
    return jax.tree.map(
        lambda ax, sd, st: leaf_state(ax, sd.shape if sd is not None else (),
                                      st),
        trainable_axes, trainable_specs, state_specs,
        is_leaf=lambda x: isinstance(x, tuple) or x is None)


def zero_grad_shardings(mesh: Mesh, trainable_specs: Any,
                        trainable_axes: Any) -> Any:
    """Reduce-scatter target sharding for gradients (param axes + data)."""
    def leaf(ax, sd):
        if sd is None:
            return None
        return NamedSharding(mesh, resolve_pspec(sd.shape, ax, mesh,
                                                 extra=("data",)))
    return jax.tree.map(leaf, trainable_axes, trainable_specs,
                        is_leaf=lambda x: isinstance(x, tuple) or x is None)


def batch_shardings(mesh: Mesh, batch_spec: dict) -> dict:
    return {
        k: NamedSharding(mesh, resolve_pspec(
            v.shape, ("batch",) + (None,) * (len(v.shape) - 1), mesh))
        for k, v in batch_spec.items()}


def cache_shardings(mesh: Mesh, cache_spec: Any, cfg) -> Any:
    """KV/SSM cache shardings: (layers, batch, seq, heads...) with batch
    over data and heads (or cache_seq) over model."""
    rules = arch_rules(cfg, kind="decode")

    def leaf(sd):
        if sd is None:
            return None
        shape = sd.shape
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        if len(shape) == 1:                       # e.g. cache["len"]
            return NamedSharding(mesh, P())
        axes: list = [None] * len(shape)
        axes[0] = "layers"
        if len(shape) >= 2:
            axes[1] = "batch"
        if len(shape) == 5:                       # (L, B, S, Hkv, hd)
            axes[2] = "cache_seq"
            axes[3] = "kv_heads"
        elif len(shape) == 4:                     # (L, B, S, r) or ssm
            axes[2] = "cache_seq"
            axes[3] = "ssm"
        elif len(shape) == 3:
            axes[2] = "ffn"
        return NamedSharding(mesh,
                             resolve_pspec(shape, axes, mesh, rules=rules))

    return jax.tree.map(leaf, cache_spec)
