"""Mixture-of-Experts with production expert parallelism.

Dispatch follows the classic EP pattern (GShard/DeepSpeed-MoE adapted to
TPU-native ``shard_map``):

  1. tokens are (re)sharded over *all* mesh axes (``data`` x ``model``);
  2. each shard routes locally (softmax -> top-k -> capacity with drop);
  3. ``jax.lax.all_to_all`` over the ``model`` axis exchanges fixed-capacity
     per-expert buffers (EP: experts live on model shards);
  4. local grouped expert FFN (SwiGLU per expert);
  5. reverse all_to_all + weighted combine.

When no mesh is active (CPU smoke tests) a mathematically identical dense
fallback runs every expert on every token with combine weights.

For the MEMORY MODEL the spec below carries the expert-parallel metadata:
the routed weight stacks' leading ``E`` dim is the ``experts`` logical
axis (rule: ``mesh_ctx.EXPERT_AXIS`` first, then TP on what stays
divisible) and the dispatch/capacity buffers carry the EP-only
``expert_buf`` axis — so a mesh with an ``expert`` axis divides exactly
the MoE weights and dispatch buffers, never a dense layer's tensors.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.spec import (ActTerm, LayerSpec, ParamSpec,
                             AXIS_EMBED, AXIS_EXPERTS, AXIS_EXPERT_BUF,
                             AXIS_FFN)
from repro.mesh_ctx import current_mesh, mesh_axis_sizes


def moe_spec(name: str, d_model: int, moe, dtype: str = "bfloat16") -> LayerSpec:
    E, F = moe.n_experts, moe.d_expert
    params = {
        "router": ParamSpec((d_model, E), "float32", (AXIS_EMBED, None)),
        "wg": ParamSpec((E, d_model, F), dtype, (AXIS_EXPERTS, AXIS_EMBED, None)),
        "wu": ParamSpec((E, d_model, F), dtype, (AXIS_EXPERTS, AXIS_EMBED, None)),
        "wd": ParamSpec((E, F, d_model), dtype, (AXIS_EXPERTS, None, AXIS_EMBED)),
    }
    if moe.n_shared_experts:
        Fs = F * moe.n_shared_experts
        params.update({
            "shared_wg": ParamSpec((d_model, Fs), dtype, (AXIS_EMBED, AXIS_FFN)),
            "shared_wu": ParamSpec((d_model, Fs), dtype, (AXIS_EMBED, AXIS_FFN)),
            "shared_wd": ParamSpec((Fs, d_model), dtype, (AXIS_FFN, AXIS_EMBED)),
        })
    # active-expert FLOPs per token (top_k routed + shared)
    flops = 2.0 * d_model * E \
        + 2.0 * 3 * d_model * F * (moe.top_k + moe.n_shared_experts)
    cap = moe.capacity_factor
    return LayerSpec(
        name=name, kind="moe", params=params,
        acts=[
            ActTerm(f"{name}.in", ("B", "S", d_model), dtype,
                    ("batch", "seq", AXIS_EMBED)),
            ActTerm(f"{name}.router", ("B", "S", E), "float32",
                    ("batch", "seq", None)),
            # dispatched expert buffers (top_k * capacity_factor copies);
            # the capacity dim carries the EP-only `expert_buf` axis: each
            # expert shard holds its own experts' fixed-capacity blocks
            ActTerm(f"{name}.dispatch",
                    ("B", "S", int(d_model * moe.top_k * cap)), dtype,
                    ("batch", "seq", AXIS_EXPERT_BUF)),
            ActTerm(f"{name}.h",
                    ("B", "S", int(3 * F * moe.top_k * cap)), dtype,
                    ("batch", "seq", AXIS_EXPERT_BUF)),
        ] + ([ActTerm(f"{name}.shared_h",
                      ("B", "S", 3 * F * moe.n_shared_experts), dtype,
                      ("batch", "seq", AXIS_FFN))]
             if moe.n_shared_experts else []),
        flops_per_token=flops,
        meta={"n_experts": E, "top_k": moe.top_k, "d_expert": F,
              "d_model": d_model, "capacity_factor": cap,
              "n_shared_experts": moe.n_shared_experts})


# ---------------------------------------------------------------------------
# routing helpers
# ---------------------------------------------------------------------------


def _route(logits: jax.Array, top_k: int):
    """softmax -> top-k -> renormalize. logits: (T, E) fp32."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)               # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i, probs


def load_balance_loss(probs: jax.Array, top_i: jax.Array, n_experts: int):
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    f = jnp.mean(jax.nn.one_hot(top_i, n_experts,
                                dtype=jnp.float32).sum(-2), axis=0)
    p = probs.mean(0)
    return n_experts * jnp.sum(f * p / max(top_i.shape[-1], 1))


def _expert_ffn(wg, wu, wd, xb):
    """xb: (E_loc, C_tot, D); weights (E_loc, D, F)/(E_loc, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", xb, wg)
    u = jnp.einsum("ecd,edf->ecf", xb, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


def _capacity(t_loc: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(t_loc * top_k * cf / n_experts)
    return max(8, -(-c // 8) * 8)


# ---------------------------------------------------------------------------
# expert-parallel path (shard_map over the live mesh)
# ---------------------------------------------------------------------------


def _ep_local(x, router_w, wg, wu, wd, *, top_k: int, n_experts: int,
              cf: float, ep_axis: str, ep_size: int):
    """Runs per device under shard_map.

    x: (B_loc, S_loc, D) local tokens; wg/wu/wd: (E_loc, ...) local experts.
    The (B*S) flatten happens HERE, on local data: a global reshape across
    a (batch x seq)-sharded layout forces SPMD into full rematerialization
    (observed 16 GiB all-gathers on deepseek train_4k).
    """
    B_loc, S_loc, D = x.shape
    x = x.reshape(B_loc * S_loc, D)
    T = B_loc * S_loc
    E = n_experts
    C = _capacity(T, top_k, E, cf)
    logits = x.astype(jnp.float32) @ router_w            # (T, E)
    top_p, top_i, probs = _route(logits, top_k)

    flat_e = top_i.reshape(-1)                           # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot            # slot before me
    slot = (pos * onehot).sum(-1)                        # (T*k,)
    slot = jnp.where(slot < C, slot, C)                  # C == drop sentinel

    xk = jnp.repeat(x, top_k, axis=0)                    # (T*k, D)
    send = jnp.zeros((E, C, D), x.dtype)
    send = send.at[flat_e, slot].add(xk, mode="drop")

    if ep_size > 1:
        # (E, C, D) -> (E_loc, ep*C, D): each shard keeps its experts,
        # receiving every source shard's capacity block.
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
    else:
        recv = send
    out_b = _expert_ffn(wg, wu, wd, recv)                # (E_loc, ep*C, D)
    if ep_size > 1:
        back = jax.lax.all_to_all(out_b, ep_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
    else:
        back = out_b                                     # (E, C, D)

    gathered = back.at[flat_e, slot].get(mode="fill", fill_value=0)
    y = (gathered.reshape(T, top_k, D).astype(jnp.float32)
         * top_p[..., None]).sum(1)
    return y.astype(x.dtype).reshape(B_loc, S_loc, D)


def moe_forward(p: dict, x: jax.Array, meta: dict) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss)."""
    B, S, D = x.shape
    E, top_k, cf = meta["n_experts"], meta["top_k"], meta["capacity_factor"]
    mesh = current_mesh()
    sizes = mesh_axis_sizes(mesh)

    use_ep = False
    if mesh is not None:
        batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
        nb = 1
        for a in batch_axes:
            nb *= sizes[a]
        ep = sizes.get("model", 1)
        use_ep = (B % max(nb, 1) == 0 and S % max(ep, 1) == 0
                  and E % max(ep, 1) == 0)

    if use_ep:
        ep = sizes.get("model", 1)
        # tokens stay 3-D: batch over data, seq over model (matches SP), so
        # the shard_map boundary never reshapes across shardings.
        fn = jax.shard_map(
            functools.partial(_ep_local, top_k=top_k, n_experts=E, cf=cf,
                              ep_axis="model", ep_size=ep),
            mesh=mesh,
            in_specs=(P(batch_axes, "model", None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(batch_axes, "model", None),
            check_vma=False)
        y = fn(x, p["router"], p["wg"], p["wu"], p["wd"])
        # aux loss from a (cheap, duplicated) global router eval so the
        # scalar is well-defined across shards (3-D einsum: no reshape).
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"])
        _, top_i, probs = _route(logits.reshape(-1, E), top_k)
        aux = load_balance_loss(probs, top_i, E)
    else:
        y, aux = _dense_moe(p, x.reshape(B * S, D), meta)
        y = y.reshape(B, S, D)

    if meta["n_shared_experts"]:
        g = jnp.einsum("bsd,df->bsf", x, p["shared_wg"])
        u = jnp.einsum("bsd,df->bsf", x, p["shared_wu"])
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           p["shared_wd"])
    return y, aux


def _dense_moe(p: dict, tokens: jax.Array, meta: dict):
    """Fallback: every expert on every token (tiny configs / no mesh)."""
    E, top_k = meta["n_experts"], meta["top_k"]
    logits = tokens.astype(jnp.float32) @ p["router"]
    top_p, top_i, probs = _route(logits, top_k)
    w = jnp.zeros_like(probs).at[jnp.arange(tokens.shape[0])[:, None],
                                 top_i].set(top_p)       # (T, E)
    h = jnp.einsum("td,edf->etf", tokens, p["wg"])
    u = jnp.einsum("td,edf->etf", tokens, p["wu"])
    yo = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, p["wd"])
    y = jnp.einsum("etd,te->td", yo.astype(jnp.float32), w)
    return y.astype(tokens.dtype), load_balance_loss(probs, top_i, E)
