"""Plain reference of a dense decoder-only LM's training step (Llama
layout: RMSNorm, rotary GQA attention, SwiGLU MLP, tied or untied head),
written from the model's published ``config.json`` keys.

Float32 throughout, every matrix product at ``Precision.HIGHEST``, no
kernels, no sharding, no remat policy of the program's: the forward runs
layer by layer under ``jax.checkpoint`` and in blocks of rows, so that it
fits one chip after the program's state is freed.  The loss is the mean
next-token cross-entropy over every token of the batch; the optimizer is
AdamW with bias correction and decoupled weight decay on every leaf, the
update ``x -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * x)``.

``lowp=True`` is the control: every matrix product takes its inputs and
its incoming gradient rounded to float8 (e4m3, one scale per tensor),
the precision below the bfloat16 the configuration states.

Imports nothing of the program.  Weights come from ``seeded.make_params``
keyed by path, so they equal what the program was given.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
ROOT = "language_model"


# ---------------------------------------------------------------------------
# shapes from the configuration file
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "f": int(cfg["intermediate_size"]),
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "tied": bool(cfg["tie_word_embeddings"])}


def param_specs(cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Paths and shapes of the weights, in the layout they are served."""
    m = dims(cfg)
    d, L, hd = m["d"], m["L"], m["hd"]
    S = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    blocks = {
        "norm1": {"scale": S(L, d)},
        "attn": {"wq": S(L, d, m["h"] * hd), "wk": S(L, d, m["kv"] * hd),
                 "wv": S(L, d, m["kv"] * hd), "wo": S(L, m["h"] * hd, d)},
        "norm2": {"scale": S(L, d)},
        "ffn": {"wg": S(L, d, m["f"]), "wu": S(L, d, m["f"]),
                "wd": S(L, m["f"], d)}}
    head = {"final_norm": {"scale": S(d)}}
    if not m["tied"]:
        head["lm_head"] = {"w": S(d, m["V"])}
    return {ROOT: {"embed": {"tok": {"w": S(m["V"], d)}}, "blocks": blocks,
                   "head": head}}


# ---------------------------------------------------------------------------
# matrix products, exact or at float8
# ---------------------------------------------------------------------------


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(eq, a, b):
    return jnp.einsum(eq, _q8(a), _q8(b), precision=HIGHEST)


def _einsum8_fwd(eq, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _einsum8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_q8(g))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def einsum(eq, a, b, lowp: bool):
    if lowp:
        return _einsum8(eq, a, b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x: (b, s, heads, hd); the two halves of each head rotate as pairs."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, chunk, lowp):
    """Causal softmax attention, queries in chunks.  q: (b, s, h, hd);
    k, v: (b, s, kv, hd); query head i reads kv head i // (h // kv)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    c = min(chunk, s)
    n = s // c
    qc = (q * hd ** -0.5).reshape(b, n, c, kv, g, hd).swapaxes(0, 1)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(i, qi):
        sc = einsum("bchgd,bshd->bhgcs", qi, k, lowp)
        qpos = i * c + jnp.arange(c)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return einsum("bhgcs,bshd->bchgd", p, v, lowp)

    _, out = jax.lax.scan(lambda _, x: (None, one(*x)), None,
                          (jnp.arange(n), qc))
    return out.swapaxes(0, 1).reshape(b, s, h * hd)


def layer(m, x, lp, attn_chunk, lowp):
    b, s, _ = x.shape
    a, f = lp["attn"], lp["ffn"]
    h = rms(x, lp["norm1"]["scale"], m["eps"])
    q = einsum("bsd,de->bse", h, a["wq"], lowp).reshape(b, s, m["h"], -1)
    k = einsum("bsd,de->bse", h, a["wk"], lowp).reshape(b, s, m["kv"], -1)
    v = einsum("bsd,de->bse", h, a["wv"], lowp).reshape(b, s, m["kv"], -1)
    ctx = attention(rope(q, m["theta"]), rope(k, m["theta"]), v,
                    attn_chunk, lowp)
    x = x + einsum("bse,ed->bsd", ctx, a["wo"], lowp)
    h = rms(x, lp["norm2"]["scale"], m["eps"])
    u = jax.nn.silu(einsum("bsd,df->bsf", h, f["wg"], lowp)) \
        * einsum("bsd,df->bsf", h, f["wu"], lowp)
    return x + einsum("bsf,fd->bsd", u, f["wd"], lowp)


def loss_sum(m, params, tokens, labels, *, attn_chunk=1024,
             loss_chunk=1024, lowp=False):
    """Sum over the rows' tokens of the next-token cross-entropy."""
    p = params[ROOT]
    table = p["embed"]["tok"]["w"]
    x = jnp.take(table, tokens, axis=0)
    body = jax.checkpoint(
        lambda x, lp: (layer(m, x, lp, attn_chunk, lowp), None))
    x, _ = jax.lax.scan(body, x, p["blocks"])
    x = rms(x, p["head"]["final_norm"]["scale"], m["eps"])
    head = table.T if m["tied"] else p["head"]["lm_head"]["w"]
    b, s, d = x.shape
    c = min(loss_chunk, s)
    xc = x.reshape(b, s // c, c, d).swapaxes(0, 1)
    lc = labels.reshape(b, s // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def one(xi, li):
        logits = einsum("bcd,dv->bcv", xi, head, lowp)
        tgt = jnp.take_along_axis(logits, li[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt)

    _, parts = jax.lax.scan(lambda _, x: (None, one(*x)), None, (xc, lc))
    return jnp.sum(parts)


# ---------------------------------------------------------------------------
# the training step
# ---------------------------------------------------------------------------


def adamw(opt: dict, params, grads, m1, v1, t):
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(x, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                     + opt["eps"])
        return x - opt["lr"] * (upd + opt["weight_decay"] * x), m, v

    out = jax.tree.map(leaf, params, grads, m1, v1)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> dict:
    """Float32 norm of every leaf, keyed by its path."""
    import seeded
    leaves = jax.tree.leaves(tree)
    return dict(zip(seeded.paths(tree),
                    [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                     for x in leaves]))


def run(cfg: dict, opt: dict, seed: int, batches: list, *,
        rows_per_block: int = 2, lowp: bool = False,
        keep_rows: int = 0, device=None) -> dict:
    """Follow the program through ``len(batches)`` steps from the seed's
    weights.  Returns each step's mean loss, the first gradient's norm
    per leaf, and the norm of each leaf's change over all the steps.

    ``keep_rows``: use only the first rows of each batch (a planted
    fault: half the batch left out, the mean taken over the rest).
    """
    import seeded
    m = dims(cfg)
    specs = param_specs(cfg)
    dev = device or jax.devices()[0]
    put = lambda x: jax.device_put(x, dev)

    words = seeded.seed_words(seed)

    @jax.jit
    def init(words):
        return jax.tree.map(lambda x: x.astype(jnp.float32),
                            seeded.make_params(words, specs))

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_sum(m, p, t, l, lowp=lowp)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t))
    update = jax.jit(functools.partial(adamw, opt), donate_argnums=(0, 2, 3))
    change = jax.jit(lambda p, p0: leaf_norms(
        jax.tree.map(jnp.subtract, p, p0)))

    with jax.default_device(dev):
        params = init(words)
        m1 = jax.tree.map(jnp.zeros_like, params)
        v1 = jax.tree.map(jnp.zeros_like, params)
        losses, first_grad = [], None
        for t, batch in enumerate(batches, start=1):
            tokens, labels = batch["tokens"], batch["labels"]
            if keep_rows:
                tokens, labels = tokens[:keep_rows], labels[:keep_rows]
            rows, seq = tokens.shape
            total, acc = 0.0, None
            for r in range(0, rows, rows_per_block):
                l, g = grad_fn(params, put(tokens[r:r + rows_per_block]),
                               put(labels[r:r + rows_per_block]))
                total += float(l)
                acc = g if acc is None else add(acc, g)
                del g
            n_tok = rows * seq
            grads = scale(acc, 1.0 / n_tok)
            del acc
            losses.append(total / n_tok)
            if t == 1:
                first_grad = jax.device_get(dict(zip(seeded.paths(grads),
                                                     jax.tree.leaves(grads))))
            params, m1, v1 = update(params, grads, m1, v1, float(t))
            del grads
        del m1, v1
        moved = {k: float(v) for k, v in change(params, init(words)).items()}
    return {"loss": losses, "grads": first_grad,
            "grad_norms": {k: float(np.linalg.norm(v))
                           for k, v in first_grad.items()},
            "change_norms": moved}
