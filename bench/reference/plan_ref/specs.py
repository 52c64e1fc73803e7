"""Spec trees of the decoder-only LM family and the mesh helpers the
planner reads: frozen copies of the program's spec builders
(``models/layers.py``, ``attention.py``, ``moe.py``, ``transformer.py``)
and of ``launch/mesh.py``'s mesh enumeration and sharding rules."""

from __future__ import annotations

from typing import Optional

from .arch import ArchConfig
from .mesh_ctx import CONTEXT_AXIS, DEFAULT_RULES, EXPERT_AXIS, PIPE_AXIS
from .spec import (ActTerm, LayerSpec, ModuleSpec, ParamSpec, AXIS_EMBED,
                   AXIS_EXPERTS, AXIS_EXPERT_BUF, AXIS_FFN, AXIS_HEADS,
                   AXIS_KV_HEADS, AXIS_LORA, AXIS_VOCAB)

LOSS_CHUNK = 512


def pool_tokens(seq_len: int, spec) -> int:
    """Pool tokens per sequence, the ``pool_tok`` term variable: the
    sequence length when no serve knobs are set, the only case the
    benchmark's planning cells price."""
    if spec is not None:
        raise NotImplementedError("serve knobs are not in the reference")
    return int(seq_len)


def linear_spec(name: str, d_in: int, d_out: int,
                axes=(AXIS_EMBED, AXIS_FFN), dtype: str = "bfloat16",
                bias: bool = False, out_act_axes=("batch", None, AXIS_FFN),
                init_scale: float = 1.0) -> LayerSpec:
    params = {"w": ParamSpec((d_in, d_out), dtype, axes, init_scale=init_scale)}
    if bias:
        params["b"] = ParamSpec((d_out,), dtype, (axes[1],), init="zeros")
    return LayerSpec(
        name=name, kind="linear", params=params,
        acts=[ActTerm(f"{name}.in", ("B", "S", d_in), dtype,
                      ("batch", "seq", axes[0]))],
        flops_per_token=2.0 * d_in * d_out,
        meta={"d_in": d_in, "d_out": d_out})


def embedding_spec(name: str, vocab: int, d_model: int,
                   dtype: str = "bfloat16", tied: bool = False) -> LayerSpec:
    """Untied tables shard columns (embed_cols -> model): the lookup then
    never gathers the table.  Tied tables must stay vocab-sharded for the
    vocab-parallel loss; the lookup's table all-gather is modelled by the
    predictor (meta['lookup_gather'])."""
    axes = (AXIS_VOCAB, AXIS_EMBED) if tied else (None, "embed_cols")
    return LayerSpec(
        name=name, kind="embedding",
        params={"w": ParamSpec((vocab, d_model), dtype, axes, init="embed")},
        acts=[ActTerm(f"{name}.ids", ("B", "S"), "int32", ("batch", "seq"))],
        flops_per_token=0.0,
        meta={"vocab": vocab, "d_model": d_model, "lookup_gather": tied})


def lm_head_spec(name: str, d_model: int, vocab: int,
                 dtype: str = "bfloat16") -> LayerSpec:
    return LayerSpec(
        name=name, kind="linear",
        params={"w": ParamSpec((d_model, vocab), dtype,
                               (AXIS_EMBED, AXIS_VOCAB))},
        acts=[ActTerm(f"{name}.in", ("B", "S", d_model), dtype,
                      ("batch", "seq", AXIS_EMBED))],
        flops_per_token=2.0 * d_model * vocab,
        meta={"d_in": d_model, "d_out": vocab})


def rmsnorm_spec(name: str, d: int, dtype: str = "bfloat16") -> LayerSpec:
    return LayerSpec(
        name=name, kind="rmsnorm",
        params={"scale": ParamSpec((d,), dtype, (None,), init="ones")},
        acts=[ActTerm(f"{name}.in", ("B", "S", d), dtype,
                      ("batch", "seq", AXIS_EMBED))],
        flops_per_token=5.0 * d,
        meta={"d": d})


def layernorm_spec(name: str, d: int, dtype: str = "bfloat16") -> LayerSpec:
    return LayerSpec(
        name=name, kind="layernorm",
        params={"scale": ParamSpec((d,), dtype, (None,), init="ones"),
                "bias": ParamSpec((d,), dtype, (None,), init="zeros")},
        acts=[ActTerm(f"{name}.in", ("B", "S", d), dtype,
                      ("batch", "seq", AXIS_EMBED))],
        flops_per_token=8.0 * d,
        meta={"d": d})


def mlp_spec(name: str, d_model: int, d_ff: int,
             dtype: str = "bfloat16", gated: bool = True) -> LayerSpec:
    if gated:
        params = {
            "wg": ParamSpec((d_model, d_ff), dtype, (AXIS_EMBED, AXIS_FFN)),
            "wu": ParamSpec((d_model, d_ff), dtype, (AXIS_EMBED, AXIS_FFN)),
            "wd": ParamSpec((d_ff, d_model), dtype, (AXIS_FFN, AXIS_EMBED)),
        }
        flops = 2.0 * d_model * d_ff * 3
        n_ff_acts = 3
    else:
        params = {
            "wu": ParamSpec((d_model, d_ff), dtype, (AXIS_EMBED, AXIS_FFN)),
            "wd": ParamSpec((d_ff, d_model), dtype, (AXIS_FFN, AXIS_EMBED)),
        }
        flops = 2.0 * d_model * d_ff * 2
        n_ff_acts = 2
    return LayerSpec(
        name=name, kind="mlp", params=params,
        acts=[ActTerm(f"{name}.in", ("B", "S", d_model), dtype,
                      ("batch", "seq", AXIS_EMBED))]
             + [ActTerm(f"{name}.h{i}", ("B", "S", d_ff), dtype,
                        ("batch", "seq", AXIS_FFN)) for i in range(n_ff_acts)],
        flops_per_token=flops,
        meta={"d_model": d_model, "d_ff": d_ff, "gated": gated})


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


def attn_spec_for(cfg: ArchConfig) -> LayerSpec:
    if cfg.mla:
        return mla_spec("attn", cfg.d_model, cfg.n_heads, cfg.mla, cfg.dtype)
    return gqa_spec("attn", cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim, cfg.qk_norm, cfg.dtype)


def _block_layers(cfg: ArchConfig, ffn: str) -> list[LayerSpec]:
    layers = [rmsnorm_spec("norm1", cfg.d_model, cfg.dtype),
              attn_spec_for(cfg),
              rmsnorm_spec("norm2", cfg.d_model, cfg.dtype)]
    if ffn == "moe":
        layers.append(moe_spec("ffn", cfg.d_model, cfg.moe, cfg.dtype))
        if cfg.moe.dense_residual:
            layers.append(mlp_spec("dense_ffn", cfg.d_model, cfg.d_ff,
                                     cfg.dtype))
    else:
        layers.append(mlp_spec("ffn", cfg.d_model, cfg.d_ff, cfg.dtype))
    return layers


def lm_spec(cfg: ArchConfig, name: str = "language_model") -> ModuleSpec:
    children = [ModuleSpec(
        name="embed", modality="text",
        layers=[embedding_spec("tok", cfg.vocab, cfg.d_model, cfg.dtype,
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
    final = [rmsnorm_spec("final_norm", cfg.d_model, cfg.dtype)]
    if not cfg.tie_embeddings:
        final.append(lm_head_spec("lm_head", cfg.d_model, cfg.vocab,
                                    cfg.dtype))
    children.append(ModuleSpec(name="head", modality="text", layers=final))
    return ModuleSpec(name=name, modality="text", children=children)


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
