"""Decoder-only LM family: llama / qwen (GQA), minicpm / deepseek (MLA),
dense or MoE FFN.  Blocks are scan-stacked (O(1) HLO in depth) with a
selectable remat policy; the loss uses a chunked, vocab-sharded
cross-entropy that never materializes the full (B, S, V) logits.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.core.spec import ActTerm, LayerSpec, ModuleSpec, ParamSpec, AXIS_EMBED
from repro.mesh_ctx import shard
from repro.models import layers as L
from repro.models.attention import (gqa_decode, gqa_forward, mla_decode,
                                    mla_forward, gqa_spec, mla_spec)
from repro.models.moe import moe_forward, moe_spec

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------


def attn_spec_for(cfg: ArchConfig) -> LayerSpec:
    if cfg.mla:
        return mla_spec("attn", cfg.d_model, cfg.n_heads, cfg.mla, cfg.dtype)
    return gqa_spec("attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim, cfg.qk_norm, cfg.dtype)


def _block_layers(cfg: ArchConfig, ffn: str) -> list[LayerSpec]:
    layers = [L.rmsnorm_spec("norm1", cfg.d_model, cfg.dtype),
              attn_spec_for(cfg),
              L.rmsnorm_spec("norm2", cfg.d_model, cfg.dtype)]
    if ffn == "moe":
        layers.append(moe_spec("ffn", cfg.d_model, cfg.moe, cfg.dtype))
        if cfg.moe.dense_residual:
            layers.append(L.mlp_spec("dense_ffn", cfg.d_model, cfg.d_ff,
                                     cfg.dtype))
    else:
        layers.append(L.mlp_spec("ffn", cfg.d_model, cfg.d_ff, cfg.dtype))
    return layers


def lm_spec(cfg: ArchConfig, name: str = "language_model") -> ModuleSpec:
    children = [ModuleSpec(
        name="embed", modality="text",
        layers=[L.embedding_spec("tok", cfg.vocab, cfg.d_model, cfg.dtype,
                                 tied=cfg.tie_embeddings)])]
    n_moe_dense = cfg.moe.n_dense_layers if cfg.moe else 0
    if cfg.moe:
        if n_moe_dense:
            children.append(ModuleSpec(
                name="dense_blocks", modality="text", repeat=n_moe_dense,
                scanned=True, layers=_block_layers(cfg, "mlp")))
        children.append(ModuleSpec(
            name="blocks", modality="text", repeat=cfg.n_layers - n_moe_dense,
            scanned=True, layers=_block_layers(cfg, "moe")))
    else:
        children.append(ModuleSpec(
            name="blocks", modality="text", repeat=cfg.n_layers,
            scanned=True, layers=_block_layers(cfg, "mlp")))
    final = [L.rmsnorm_spec("final_norm", cfg.d_model, cfg.dtype)]
    if not cfg.tie_embeddings:
        final.append(L.lm_head_spec("lm_head", cfg.d_model, cfg.vocab,
                                    cfg.dtype))
    children.append(ModuleSpec(name="head", modality="text", layers=final))
    return ModuleSpec(name=name, modality="text", children=children)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


@jax.named_scope("attn")
def _attn_apply(cfg: ArchConfig, bp: dict, h: jax.Array,
                positions: Optional[jax.Array], chunk: int) -> jax.Array:
    if cfg.mla:
        return mla_forward(bp, h, n_heads=cfg.n_heads, mla=cfg.mla,
                           norm_eps=cfg.norm_eps, positions=positions,
                           chunk=chunk)
    return gqa_forward(bp, h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                       head_dim=cfg.resolved_head_dim, theta=cfg.rope_theta,
                       qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
                       positions=positions, chunk=chunk)


def _block_apply(cfg: ArchConfig, moe_block: bool, bp: dict, x: jax.Array,
                 positions, chunk: int) -> tuple[jax.Array, jax.Array]:
    x = shard(x, "batch", "seq", "embed")
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    x = x + _attn_apply(cfg, bp["attn"], h, positions, chunk)
    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if moe_block:
        y, aux = moe_forward(bp["ffn"], h, _moe_meta(cfg))
        if cfg.moe.dense_residual:
            y = y + L.mlp(bp["dense_ffn"], h)
        x = x + y
    else:
        with jax.named_scope("mlp"):
            y = L.mlp(bp["ffn"], h)
        x = x + y
    return x, aux


def _moe_meta(cfg: ArchConfig) -> dict:
    return moe_spec("ffn", cfg.d_model, cfg.moe, cfg.dtype).meta


@jax.custom_vjp
def _pin(x: jax.Array) -> jax.Array:
    """AD-transparent optimization barrier.

    ``lax.optimization_barrier`` has no differentiation rule in this jax
    version, so wrapping it in a custom VJP keeps the forward barrier
    (which pins the bf16 scan carry — see ``_scan_blocks``) while giving
    the backward pass an explicit rule: barrier the cotangent too, which
    symmetrically stops XLA from hoisting the bwd convert of the carried
    gradient stack out of the loop.
    """
    return jax.lax.optimization_barrier(x)


def _pin_fwd(x):
    return _pin(x), None


def _pin_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_pin.defvjp(_pin_fwd, _pin_bwd)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)              # "block": save carries only


def _scan_blocks(cfg: ArchConfig, moe_block: bool, stack: dict, x: jax.Array,
                 positions, chunk: int, remat: str) -> tuple[jax.Array, jax.Array]:
    def body(carry, bp):
        x, aux = carry
        # Barrier pins the bf16 carry: without it XLA hoists the backward
        # pass's bf16->f32 convert of the saved-carry STACK out of the while
        # loop, materializing an fp32 copy of every layer's residual (2x the
        # dominant activation buffer; observed +7.5 GiB on smollm train_4k).
        x = _pin(x)
        x, a = _block_apply(cfg, moe_block, bp, x, positions, chunk)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(_remat(body, remat), (x, jnp.zeros((), jnp.float32)),
                               stack)
    return x, aux


def lm_backbone(cfg: ArchConfig, p: dict, embeds: jax.Array,
                positions=None, remat: Optional[str] = None,
                chunk: int = 1024) -> tuple[jax.Array, jax.Array]:
    """embeds: (B, S, D) -> (hidden (B, S, D), moe_aux)."""
    remat = remat if remat is not None else cfg.remat
    x = embeds
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe and cfg.moe.n_dense_layers:
        x, a = _scan_blocks(cfg, False, p["dense_blocks"], x, positions,
                            chunk, remat)
        aux += a
    x, a = _scan_blocks(cfg, bool(cfg.moe), p["blocks"], x, positions,
                        chunk, remat)
    aux += a
    return L.rmsnorm(p["head"]["final_norm"], x, cfg.norm_eps), aux


def embed_tokens(cfg: ArchConfig, p: dict, tokens: jax.Array) -> jax.Array:
    return L.embed(p["embed"]["tok"], tokens)


def lm_logits(cfg: ArchConfig, p: dict, hidden: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return L.unembed(p["embed"]["tok"], hidden)
    return L.linear(p["head"]["lm_head"], hidden).astype(jnp.float32)


# ---------------------------------------------------------------------------
# chunked cross-entropy (never materializes (B, S, V))
# ---------------------------------------------------------------------------


@jax.named_scope("loss")
def chunked_xent(cfg: ArchConfig, p: dict, hidden: jax.Array,
                 labels: jax.Array, chunk: int = LOSS_CHUNK):
    """hidden: (B, S, D); labels: (B, S) with -100 = masked.
    Returns (sum_loss, n_tokens)."""
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)
    n_chunks = (S + pad) // chunk
    hc = hidden.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(h, l):
        logits = lm_logits(cfg, p, h)                     # (B, c, V) fp32
        logits = shard(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        mask = l >= 0
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1)[..., 0]
        return (jnp.where(mask, lse - tgt, 0.0).sum(),
                mask.sum().astype(jnp.float32))

    def body(carry, inp):
        h, l = inp
        s, n = chunk_loss(h, l)
        return (carry[0] + s, carry[1] + n), None

    (loss_sum, n_tok), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc))
    return loss_sum, n_tok


def lm_loss(cfg: ArchConfig, params: dict, tokens: jax.Array,
            labels: jax.Array, remat: Optional[str] = None):
    p = params[next(iter(params))] if "language_model" not in params \
        else params["language_model"]
    x = embed_tokens(cfg, p, tokens)
    hidden, aux = lm_backbone(cfg, p, x, remat=remat)
    loss_sum, n_tok = chunked_xent(cfg, p, hidden, labels)
    loss = loss_sum / jnp.maximum(n_tok, 1.0)
    if cfg.moe:
        loss = loss + 0.01 * aux / max(cfg.n_layers, 1)
    return loss, {"xent": loss_sum / jnp.maximum(n_tok, 1.0),
                  "aux": aux, "n_tok": n_tok}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    """Stacked (L-leading) cache pytree for the scanned blocks."""
    n_moe_dense = cfg.moe.n_dense_layers if cfg.moe else 0
    n_scan = cfg.n_layers - n_moe_dense

    def one(n):
        if cfg.mla:
            m = cfg.mla
            return {"latent": jnp.zeros((n, batch, max_len, m.kv_lora_rank),
                                        jnp.bfloat16),
                    "k_rope": jnp.zeros((n, batch, max_len, m.qk_rope_head_dim),
                                        jnp.bfloat16)}
        hd = cfg.resolved_head_dim
        return {"k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd),
                               jnp.bfloat16),
                "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd),
                               jnp.bfloat16)}

    cache = {"blocks": one(n_scan), "len": jnp.zeros((batch,), jnp.int32)}
    if n_moe_dense:
        cache["dense_blocks"] = one(n_moe_dense)
    return cache


def _decode_block(cfg: ArchConfig, moe_block: bool, bp: dict, x: jax.Array,
                  layer_cache: dict, length: jax.Array):
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    cache_in = dict(layer_cache, len=length)
    if cfg.mla:
        a, new_cache = mla_decode(bp["attn"], h, cache_in, n_heads=cfg.n_heads,
                                  mla=cfg.mla, norm_eps=cfg.norm_eps)
    else:
        a, new_cache = gqa_decode(bp["attn"], h, cache_in,
                                  n_heads=cfg.n_heads,
                                  n_kv_heads=cfg.n_kv_heads,
                                  head_dim=cfg.resolved_head_dim,
                                  theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                                  norm_eps=cfg.norm_eps)
    x = x + a
    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    if moe_block:
        y, _ = moe_forward(bp["ffn"], h, _moe_meta(cfg))
        if cfg.moe.dense_residual:
            y = y + L.mlp(bp["dense_ffn"], h)
        x = x + y
    else:
        x = x + L.mlp(bp["ffn"], h)
    new_cache.pop("len")
    return x, new_cache


def lm_decode_step(cfg: ArchConfig, params: dict, token: jax.Array,
                   cache: dict):
    """token: (B, 1) -> (logits (B, 1, V), new cache)."""
    p = params.get("language_model") or params[next(iter(params))]
    x = embed_tokens(cfg, p, token)
    length = cache["len"]

    def scan_stack(x, stack, stack_cache, moe_block):
        def body(x, inp):
            bp, lc = inp
            x, nc = _decode_block(cfg, moe_block, bp, x, lc, length)
            return x, nc
        return jax.lax.scan(body, x, (stack, stack_cache))

    new_cache = {"len": length + 1}
    if cfg.moe and cfg.moe.n_dense_layers:
        x, nc = scan_stack(x, p["dense_blocks"], cache["dense_blocks"], False)
        new_cache["dense_blocks"] = nc
    x, nc = scan_stack(x, p["blocks"], cache["blocks"], bool(cfg.moe))
    new_cache["blocks"] = nc
    x = L.rmsnorm(p["head"]["final_norm"], x, cfg.norm_eps)
    return lm_logits(cfg, p, x), new_cache


def lm_prefill(cfg: ArchConfig, params: dict, tokens: jax.Array,
               remat: Optional[str] = None):
    """Full-sequence prefill: returns last-position logits + populated cache.

    Cache layout matches :func:`init_kv_cache` with max_len == S.
    """
    p = params.get("language_model") or params[next(iter(params))]
    B, S = tokens.shape
    x = embed_tokens(cfg, p, tokens)
    remat = remat if remat is not None else cfg.remat

    def scan_stack(x, stack, moe_block):
        def body(carry, bp):
            x = carry
            h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
            kv = _prefill_kv(cfg, bp["attn"], h)
            x, _ = _block_apply(cfg, moe_block, bp, x, None, 1024)
            return x, kv
        return jax.lax.scan(_remat(body, remat), x, stack)

    caches = {}
    if cfg.moe and cfg.moe.n_dense_layers:
        x, kv = scan_stack(x, p["dense_blocks"], False)
        caches["dense_blocks"] = kv
    x, kv = scan_stack(x, p["blocks"], bool(cfg.moe))
    caches["blocks"] = kv
    caches["len"] = jnp.full((B,), S, jnp.int32)
    x = L.rmsnorm(p["head"]["final_norm"], x[:, -1:], cfg.norm_eps)
    return lm_logits(cfg, p, x), caches


def _prefill_kv(cfg: ArchConfig, ap: dict, h: jax.Array) -> dict:
    """Recompute the cacheable K/V (or MLA latent) for a full sequence."""
    from repro.models.attention import _mla_qkv
    from repro.models.layers import apply_rope
    B, S, _ = h.shape
    if cfg.mla:
        _, latent, k_rope = _mla_qkv(ap, h, cfg.mla, cfg.n_heads, cfg.norm_eps)
        return {"latent": latent.astype(jnp.bfloat16),
                "k_rope": k_rope.astype(jnp.bfloat16)}
    hd = cfg.resolved_head_dim
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    k = (h @ ap["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = L.rmsnorm({"scale": ap["k_norm"]}, k, cfg.norm_eps)
    k = apply_rope(k, positions, cfg.rope_theta)
    v = (h @ ap["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
