"""Attention: GQA (llama/qwen/mistral-style) and MLA (deepseek/minicpm-style).

Three compute paths share one math definition:

* ``chunked_attention`` — flash-equivalent pure-``lax`` path (never
  materializes the S x S score matrix; KV is processed in chunks with a
  running-max online softmax).  Used for training/prefill lowering and as
  the oracle for the Pallas kernel.
* ``repro.kernels.flash_attention`` — the Pallas TPU kernel (hot path on
  real hardware; validated in interpret mode against this module).
* ``decode_attention`` — single-token query against a KV cache.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.spec import (ActTerm, LayerSpec, ParamSpec,
                             AXIS_EMBED, AXIS_HEADS, AXIS_KV_HEADS, AXIS_LORA)
from repro.mesh_ctx import shard
from repro.models.layers import apply_rope, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------


def gqa_spec(name: str, d_model: int, n_heads: int, n_kv_heads: int,
             head_dim: int, qk_norm: bool = False,
             dtype: str = "bfloat16") -> LayerSpec:
    params = {
        "wq": ParamSpec((d_model, n_heads * head_dim), dtype,
                        (AXIS_EMBED, AXIS_HEADS)),
        "wk": ParamSpec((d_model, n_kv_heads * head_dim), dtype,
                        (AXIS_EMBED, AXIS_KV_HEADS)),
        "wv": ParamSpec((d_model, n_kv_heads * head_dim), dtype,
                        (AXIS_EMBED, AXIS_KV_HEADS)),
        "wo": ParamSpec((n_heads * head_dim, d_model), dtype,
                        (AXIS_HEADS, AXIS_EMBED)),
    }
    if qk_norm:
        params["q_norm"] = ParamSpec((head_dim,), dtype, (None,), init="ones")
        params["k_norm"] = ParamSpec((head_dim,), dtype, (None,), init="ones")
    proj_flops = 2.0 * d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    return LayerSpec(
        name=name, kind="attention", params=params,
        acts=[
            # 4-D head layouts mirror the runtime's reshape-then-shard order:
            # a head count that does not divide the mesh axis replicates in
            # BOTH the live code and the prediction (e.g. smollm's 15 heads).
            ActTerm(f"{name}.in", ("B", "S", d_model), dtype,
                    ("batch", "seq", AXIS_EMBED)),
            ActTerm(f"{name}.q", ("B", "S", n_heads, head_dim), dtype,
                    ("batch", "seq", AXIS_HEADS, None)),
            ActTerm(f"{name}.k", ("B", "S", n_kv_heads, head_dim), dtype,
                    ("batch", "seq", AXIS_KV_HEADS, None)),
            ActTerm(f"{name}.v", ("B", "S", n_kv_heads, head_dim), dtype,
                    ("batch", "seq", AXIS_KV_HEADS, None)),
            ActTerm(f"{name}.ctx", ("B", "S", n_heads, head_dim), dtype,
                    ("batch", "seq", AXIS_HEADS, None)),
            # flash softmax statistics (fp32 lse per head per position)
            ActTerm(f"{name}.lse", ("B", n_heads, "S"), "float32",
                    ("batch", "heads", "seq")),
        ],
        flops_per_token=proj_flops,
        meta={"n_heads": n_heads, "n_kv_heads": n_kv_heads,
              "head_dim": head_dim, "qk_norm": qk_norm, "d_model": d_model,
              "kv_bytes_per_token": 2 * n_kv_heads * head_dim,
              "attn_kind": "gqa"})


def mla_spec(name: str, d_model: int, n_heads: int, mla,
             dtype: str = "bfloat16") -> LayerSpec:
    """DeepSeek-V2-style multi-head latent attention.

    Decode caches only (kv_lora + rope_dim) per token — the spec records
    that via ``kv_bytes_per_token`` so cache prediction is exact.
    """
    qk_head = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    params: dict[str, ParamSpec] = {}
    if mla.q_lora_rank:
        params["wq_a"] = ParamSpec((d_model, mla.q_lora_rank), dtype,
                                   (AXIS_EMBED, AXIS_LORA))
        params["q_norm"] = ParamSpec((mla.q_lora_rank,), dtype, (None,),
                                     init="ones")
        params["wq_b"] = ParamSpec((mla.q_lora_rank, n_heads * qk_head),
                                   dtype, (AXIS_LORA, AXIS_HEADS))
        q_flops = 2.0 * d_model * mla.q_lora_rank \
            + 2.0 * mla.q_lora_rank * n_heads * qk_head
    else:
        params["wq"] = ParamSpec((d_model, n_heads * qk_head), dtype,
                                 (AXIS_EMBED, AXIS_HEADS))
        q_flops = 2.0 * d_model * n_heads * qk_head
    params.update({
        "wkv_a": ParamSpec((d_model, mla.kv_lora_rank + mla.qk_rope_head_dim),
                           dtype, (AXIS_EMBED, None)),
        "kv_norm": ParamSpec((mla.kv_lora_rank,), dtype, (None,), init="ones"),
        "wkv_b": ParamSpec((mla.kv_lora_rank,
                            n_heads * (mla.qk_nope_head_dim + mla.v_head_dim)),
                           dtype, (AXIS_LORA, AXIS_HEADS)),
        "wo": ParamSpec((n_heads * mla.v_head_dim, d_model), dtype,
                        (AXIS_HEADS, AXIS_EMBED)),
    })
    flops = (q_flops
             + 2.0 * d_model * (mla.kv_lora_rank + mla.qk_rope_head_dim)
             + 2.0 * mla.kv_lora_rank * n_heads
             * (mla.qk_nope_head_dim + mla.v_head_dim)
             + 2.0 * n_heads * mla.v_head_dim * d_model)
    return LayerSpec(
        name=name, kind="attention", params=params,
        acts=[
            ActTerm(f"{name}.in", ("B", "S", d_model), dtype,
                    ("batch", "seq", AXIS_EMBED)),
            ActTerm(f"{name}.q", ("B", "S", n_heads, qk_head), dtype,
                    ("batch", "seq", AXIS_HEADS, None)),
            ActTerm(f"{name}.kv_latent", ("B", "S",
                                          mla.kv_lora_rank + mla.qk_rope_head_dim),
                    dtype, ("batch", "seq", None)),
            ActTerm(f"{name}.k", ("B", "S", n_heads, qk_head), dtype,
                    ("batch", "seq", AXIS_HEADS, None)),
            ActTerm(f"{name}.v", ("B", "S", n_heads, mla.v_head_dim), dtype,
                    ("batch", "seq", AXIS_HEADS, None)),
            ActTerm(f"{name}.ctx", ("B", "S", n_heads, mla.v_head_dim), dtype,
                    ("batch", "seq", AXIS_HEADS, None)),
            ActTerm(f"{name}.lse", ("B", n_heads, "S"), "float32",
                    ("batch", "heads", "seq")),
        ],
        flops_per_token=flops,
        meta={"n_heads": n_heads, "head_dim": qk_head,
              "v_head_dim": mla.v_head_dim, "mla": mla,
              "d_model": d_model,
              "kv_bytes_per_token": 2 * (mla.kv_lora_rank + mla.qk_rope_head_dim),
              "attn_kind": "mla"})


# ---------------------------------------------------------------------------
# chunked (flash-equivalent) attention core
# ---------------------------------------------------------------------------


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, chunk: int = 1024,
                      q_offset: int = 0,
                      kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Online-softmax attention over KV chunks.

    q: (B, Sq, H, Dq); k: (B, Skv, Hkv, Dq); v: (B, Skv, Hkv, Dv); H % Hkv == 0.
    ``q_offset``: absolute position of q[0] (decode/prefill continuation).
    ``kv_len``: optional dynamic number of valid KV positions (masking).
    Returns (B, Sq, H, Dv).
    """
    B, Sq, H, Dq = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = H // Hkv
    scale = Dq ** -0.5
    qg = (q * scale).reshape(B, Sq, Hkv, G, Dq)

    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, Hkv, Dq)
    vc = v.reshape(B, n_chunks, chunk, Hkv, Dv)

    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inputs):
        m, l, acc = carry
        ci, kci, vci = inputs
        kv_pos = ci * chunk + jnp.arange(chunk)
        # scores: (B, Sq, Hkv, G, chunk); qg dims = (b, s, kv-head h, group g, d)
        s = jnp.einsum("bshgd,bchd->bshgc", qg, kci.astype(qg.dtype),
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((Sq, chunk), jnp.bool_)
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        mask = mask & (kv_pos[None, :] < (kv_len if kv_len is not None
                                          else Skv - 0))
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bshgc,bchd->bshgd", p.astype(vci.dtype), vci,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, Hkv, G, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.arange(n_chunks), kc.swapaxes(0, 1), vc.swapaxes(0, 1)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention (custom_vjp): FA2 memory profile in pure lax.
# Forward saves only (q, k, v, out, lse); backward recomputes scores per KV
# chunk — without this, autodiff through the chunk scan stores every
# per-chunk probability matrix, i.e. the full S^2 tensor.
# ---------------------------------------------------------------------------


def _chunk_layout(x, chunk):
    """(B, S, h, d) -> (n_chunks, B, chunk, h, d) with zero padding."""
    B, S, h, d = x.shape
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.reshape(B, n, chunk, h, d).swapaxes(0, 1), n


def _tile_live(qi, ci, q_chunk, kv_chunk, q_offset):
    """Whether the causal mask of q chunk ``qi`` against KV chunk ``ci``
    has a True entry: the chunk's first key is at or before its last
    query.  A dead tile lies after the diagonal in KV order, so every
    row's running max is already finite and skipping it changes nothing."""
    return ci * kv_chunk <= q_offset + qi * q_chunk + q_chunk - 1


def _flash_fwd_impl(q, k, v, causal, chunk, q_offset):
    """Two-level blocked online-softmax attention.

    Blocks over BOTH the query and the KV sequence dims so the largest live
    score tensor is (B, q_chunk, Hkv, G, kv_chunk) — the lowered-HLO twin of
    the Pallas kernel's VMEM tiling.  Under the causal mask a tile with no
    unmasked entry is skipped (``_tile_live``).  Returns (out, lse) with
    lse: (B, Sq, Hkv, G) fp32.
    """
    B, Sq, H, Dq = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = H // Hkv
    scale = Dq ** -0.5

    kv_chunk = min(chunk, Skv)
    q_chunk = min(chunk, Sq)
    # tiles stay in the input dtype — the fp32 upcast happens per-tile
    # inside the body (a whole-q fp32 copy would be gathered/stored)
    qc, nq = _chunk_layout(q.reshape(B, Sq, Hkv * G, Dq), q_chunk)
    qc = qc.reshape(nq, B, q_chunk, Hkv, G, Dq)
    kc, nk = _chunk_layout(k, kv_chunk)
    vc, _ = _chunk_layout(v, kv_chunk)

    def q_body(_, q_in):
        qi, qci_raw = q_in
        qci = qci_raw.astype(jnp.float32) * scale
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, kv_in):
            ci, kci, vci = kv_in

            @jax.named_scope("tile")
            def tile(carry):
                m, l, acc = carry
                kv_pos = ci * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.einsum("bshgd,bchd->bshgc", qci,
                               kci.astype(jnp.float32))
                mask = kv_pos[None, :] < Skv
                if causal:
                    mask = mask & (kv_pos[None, :] <= q_pos[:, None])
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l_new = l * alpha + p.sum(axis=-1)
                pv = jnp.einsum("bshgc,bchd->bshgd", p,
                                vci.astype(jnp.float32))
                return m_new, l_new, acc * alpha[..., None] + pv

            if not causal:
                return tile(carry), None
            live = _tile_live(qi, ci, q_chunk, kv_chunk, q_offset)
            return jax.lax.cond(live, tile, lambda c: c, carry), None

        m0 = jnp.full((B, q_chunk, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, Hkv, G), jnp.float32)
        acc0 = jnp.zeros((B, q_chunk, Hkv, G, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_body), (m0, l0, acc0),
            (jnp.arange(nk), kc, vc))
        lse_c = m + jnp.log(jnp.maximum(l, 1e-30))
        out_c = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, (out_c, lse_c)

    _, (out_c, lse_c) = jax.lax.scan(
        jax.checkpoint(q_body), None, (jnp.arange(nq), qc))
    out = out_c.swapaxes(0, 1).reshape(B, nq * q_chunk, H, Dv)[:, :Sq]
    lse = lse_c.swapaxes(0, 1).reshape(B, nq * q_chunk, Hkv, G)[:, :Sq]
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, chunk: int = 1024,
                    q_offset: int = 0):
    """q: (B,Sq,H,Dq); k/v: (B,Skv,Hkv,D*); returns (B,Sq,H,Dv)."""
    out, _ = _flash_fwd_impl(q, k, v, causal, chunk, q_offset)
    return out


def _flash_fwd(q, k, v, causal, chunk, q_offset):
    out, lse = _flash_fwd_impl(q, k, v, causal, chunk, q_offset)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, chunk, q_offset, res, dout):
    """FA2-style backward, blocked over BOTH q and kv chunks.

    Outer scan walks q chunks carrying full-KV dk/dv accumulators
    (B, Skv_pad, Hkv, D) fp32; the inner scan walks kv chunks recomputing
    the (q_chunk x kv_chunk) probability tile.  A causally empty tile is
    skipped and gives zero dk/dv parts.
    """
    q, k, v, out, lse = res
    B, Sq, H, Dq = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = H // Hkv
    scale = Dq ** -0.5

    kv_chunk = min(chunk, Skv)
    q_chunk = min(chunk, Sq)
    qc, nq = _chunk_layout(q.reshape(B, Sq, Hkv * G, Dq), q_chunk)
    qc = qc.reshape(nq, B, q_chunk, Hkv, G, Dq)
    kc, nk = _chunk_layout(k, kv_chunk)
    vc, _ = _chunk_layout(v, kv_chunk)
    Skv_pad = nk * kv_chunk

    dog = dout.astype(jnp.float32).reshape(B, Sq, Hkv, G, Dv)
    og = out.astype(jnp.float32).reshape(B, Sq, Hkv, G, Dv)
    delta_full = (dog * og).sum(-1)                       # (B,Sq,Hkv,G)
    dogc, _ = _chunk_layout(dog.reshape(B, Sq, Hkv * G, Dv), q_chunk)
    dogc = dogc.reshape(nq, B, q_chunk, Hkv, G, Dv)
    dc, _ = _chunk_layout(delta_full[..., None].reshape(B, Sq, Hkv * G, 1),
                          q_chunk)
    dc = dc.reshape(nq, B, q_chunk, Hkv, G)
    lc, _ = _chunk_layout(lse[..., None].reshape(B, Sq, Hkv * G, 1), q_chunk)
    lc = lc.reshape(nq, B, q_chunk, Hkv, G)

    def q_body(carry, q_in):
        dk_acc, dv_acc = carry
        qi, qci_raw, doci, deltci, lsec = q_in
        qci = qci_raw.astype(jnp.float32) * scale
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_body(dq_c, kv_in):
            ci, kci, vci = kv_in

            @jax.named_scope("tile")
            def tile(dq_c):
                kv_pos = ci * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.einsum("bshgd,bchd->bshgc", qci,
                               kci.astype(jnp.float32))
                mask = kv_pos[None, :] < Skv
                if causal:
                    mask = mask & (kv_pos[None, :] <= q_pos[:, None])
                s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
                p = jnp.exp(s - lsec[..., None])           # (B,qc,Hkv,G,c)
                dv_c = jnp.einsum("bshgc,bshgd->bchd", p, doci)
                dp = jnp.einsum("bshgd,bchd->bshgc", doci,
                                vci.astype(jnp.float32))
                ds = p * (dp - deltci[..., None])
                dq_c = dq_c + jnp.einsum("bshgc,bchd->bshgd", ds,
                                         kci.astype(jnp.float32))
                dk_c = jnp.einsum("bshgc,bshgd->bchd", ds, qci)
                return dq_c, (dk_c, dv_c)

            def skip(dq_c):
                return dq_c, (jnp.zeros((B, kv_chunk, Hkv, Dq), jnp.float32),
                              jnp.zeros((B, kv_chunk, Hkv, Dv), jnp.float32))

            if not causal:
                return tile(dq_c)
            live = _tile_live(qi, ci, q_chunk, kv_chunk, q_offset)
            return jax.lax.cond(live, tile, skip, dq_c)

        dq0 = jnp.zeros((B, q_chunk, Hkv, G, Dq), jnp.float32)
        dq_c, (dk_parts, dv_parts) = jax.lax.scan(
            jax.checkpoint(kv_body), dq0, (jnp.arange(nk), kc, vc))
        dk_acc = dk_acc + dk_parts.swapaxes(0, 1).reshape(
            B, Skv_pad, Hkv, Dq)
        dv_acc = dv_acc + dv_parts.swapaxes(0, 1).reshape(
            B, Skv_pad, Hkv, Dv)
        return (dk_acc, dv_acc), dq_c

    dk0 = jnp.zeros((B, Skv_pad, Hkv, Dq), jnp.float32)
    dv0 = jnp.zeros((B, Skv_pad, Hkv, Dv), jnp.float32)
    (dk, dv), dq_c = jax.lax.scan(
        jax.checkpoint(q_body), (dk0, dv0), (jnp.arange(nq), qc, dogc, dc, lc))
    dq = (dq_c.swapaxes(0, 1).reshape(B, nq * q_chunk, Hkv, G, Dq)[:, :Sq]
          * scale).reshape(B, Sq, H, Dq).astype(q.dtype)
    return dq, dk[:, :Skv].astype(k.dtype), dv[:, :Skv].astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def reference_attention(q, k, v, causal=True, q_offset=0, kv_len=None):
    """Naive O(S^2)-memory oracle (tests only)."""
    B, Sq, H, Dq = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * Dq ** -0.5
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), jnp.bool_)
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    if kv_len is not None:
        mask = mask & (kv_pos[None, :] < kv_len)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# full layer applies
# ---------------------------------------------------------------------------


def _attn_tile_axes(n_heads: int) -> tuple:
    """Layout for q/ctx INSIDE attention.

    The flash scan runs its full trip count on every device, so a
    seq-sharded q leaves each device computing every head's full-S^2 tile
    work (observed 16x redundant FLOPs on qwen3 prefill).  When the head
    count fills the model axis, force head sharding for the attention body
    — heads then partition the tile loops and SP still shards the residual
    stream outside.  Non-divisible head counts keep the seq layout.
    """
    from repro.mesh_ctx import current_rules, mesh_axis_sizes
    sizes = mesh_axis_sizes()
    rules = current_rules()
    m = 1
    for a in rules.get("heads", ()):
        m *= sizes.get(a, 1)
    if m > 1 and n_heads % m == 0:
        return ("batch", None, "heads", None)
    return ("batch", "seq", "heads", None)


def gqa_forward(p: dict, x: jax.Array, *, n_heads: int, n_kv_heads: int,
                head_dim: int, theta: float, qk_norm: bool = False,
                norm_eps: float = 1e-5, causal: bool = True,
                positions: Optional[jax.Array] = None,
                chunk: int = 1024) -> jax.Array:
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = (x @ p["wq"]).reshape(B, S, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(B, S, n_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(B, S, n_kv_heads, head_dim)
    if qk_norm:
        q = rmsnorm({"scale": p["q_norm"]}, q, norm_eps)
        k = rmsnorm({"scale": p["k_norm"]}, k, norm_eps)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    axes = _attn_tile_axes(n_heads)
    q = shard(q, *axes)
    ctx = flash_attention(q, k, v, causal, chunk)
    ctx = shard(ctx, *axes)
    return ctx.reshape(B, S, n_heads * head_dim) @ p["wo"]


def gqa_decode(p: dict, x: jax.Array, cache: dict, *, n_heads: int,
               n_kv_heads: int, head_dim: int, theta: float,
               qk_norm: bool = False, norm_eps: float = 1e-5) -> tuple:
    """One-token decode: x (B, 1, d); cache {'k','v': (B, S_max, Hkv, D),
    'len': (B,)} -> (out, new_cache)."""
    B = x.shape[0]
    pos = cache["len"][:, None]                                   # (B,1)
    q = (x @ p["wq"]).reshape(B, 1, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(B, 1, n_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(B, 1, n_kv_heads, head_dim)
    if qk_norm:
        q = rmsnorm({"scale": p["q_norm"]}, q, norm_eps)
        k = rmsnorm({"scale": p["k_norm"]}, k, norm_eps)
    q = apply_rope(q, pos, theta)
    k = apply_rope(k, pos, theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), cache["len"][0], axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), cache["len"][0], axis=1)
    ctx = decode_attention(q, k_cache, v_cache, cache["len"] + 1)
    out = ctx.reshape(B, 1, n_heads * head_dim) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache, "len": cache["len"] + 1}


def decode_attention(q, k_cache, v_cache, kv_len):
    """q: (B, 1, H, D); caches: (B, S_max, Hkv, D); kv_len: (B,)."""
    B, _, H, Dq = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    G = H // Hkv
    qg = (q * Dq ** -0.5).reshape(B, 1, Hkv, G, Dq)
    s = jnp.einsum("bshgd,bthd->bshgt", qg, k_cache.astype(qg.dtype),
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(Smax)[None] < kv_len[:, None]              # (B, Smax)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    piv = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bshgt,bthd->bshgd", piv.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA applies
# ---------------------------------------------------------------------------


def _mla_qkv(p: dict, x: jax.Array, mla, n_heads: int, norm_eps: float):
    B, S, _ = x.shape
    qk_head = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    if "wq_a" in p:
        qa = rmsnorm({"scale": p["q_norm"]}, x @ p["wq_a"], norm_eps)
        q = (qa @ p["wq_b"]).reshape(B, S, n_heads, qk_head)
    else:
        q = (x @ p["wq"]).reshape(B, S, n_heads, qk_head)
    kv_a = x @ p["wkv_a"]                                         # (B,S,r+rope)
    latent, k_rope = jnp.split(kv_a, [mla.kv_lora_rank], axis=-1)
    latent = rmsnorm({"scale": p["kv_norm"]}, latent, norm_eps)
    return q, latent, k_rope


def _mla_expand_kv(p: dict, latent: jax.Array, k_rope: jax.Array,
                   positions: jax.Array, mla, n_heads: int):
    B, S, _ = latent.shape
    kv = (latent @ p["wkv_b"]).reshape(
        B, S, n_heads, mla.qk_nope_head_dim + mla.v_head_dim)
    k_nope, v = jnp.split(kv, [mla.qk_nope_head_dim], axis=-1)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, theta=10000.0)
    k_rope = jnp.broadcast_to(k_rope, (B, S, n_heads, mla.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return k, v


def mla_forward(p: dict, x: jax.Array, *, n_heads: int, mla,
                norm_eps: float = 1e-5, causal: bool = True,
                positions: Optional[jax.Array] = None,
                chunk: int = 1024) -> jax.Array:
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, latent, k_rope = _mla_qkv(p, x, mla, n_heads, norm_eps)
    q_nope, q_rope = jnp.split(q, [mla.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, apply_rope(q_rope, positions, 10000.0)],
                        axis=-1)
    k, v = _mla_expand_kv(p, latent, k_rope, positions, mla, n_heads)
    axes = _attn_tile_axes(n_heads)
    q = shard(q, *axes)
    k = shard(k, *axes)
    ctx = flash_attention(q, k, v, causal, chunk)
    ctx = shard(ctx, *axes)
    return ctx.reshape(B, S, n_heads * mla.v_head_dim) @ p["wo"]


def mla_decode(p: dict, x: jax.Array, cache: dict, *, n_heads: int, mla,
               norm_eps: float = 1e-5) -> tuple:
    """MLA decode caches only the latent (+ rope key): cache
    {'latent': (B, S_max, r), 'k_rope': (B, S_max, rope), 'len': (B,)}."""
    B = x.shape[0]
    pos = cache["len"][:, None]
    q, latent, k_rope = _mla_qkv(p, x, mla, n_heads, norm_eps)
    q_nope, q_rope = jnp.split(q, [mla.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, apply_rope(q_rope, pos, 10000.0)], axis=-1)
    lat_c = jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], latent.astype(cache["latent"].dtype),
        cache["len"][0], axis=1)
    kr_c = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
        cache["len"][0], axis=1)
    Smax = lat_c.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Smax), (B, Smax))
    k, v = _mla_expand_kv(p, lat_c, kr_c, positions, mla, n_heads)
    ctx = decode_attention(q, k, v, cache["len"] + 1)
    out = ctx.reshape(B, 1, n_heads * mla.v_head_dim) @ p["wo"]
    return out, {"latent": lat_c, "k_rope": kr_c, "len": cache["len"] + 1}
