"""Causal tile skipping in the pure-lax flash attention
(``models.attention.flash_attention``): which (q chunk, KV chunk) tiles
run, and that skipping the rest leaves the output and all three
gradients equal to the plain reference."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (_tile_live, flash_attention,
                                    reference_attention)

KEY = jax.random.PRNGKey(11)


def _brute_live(Sq, Skv, chunk, q_offset, causal):
    """Tile grid -> whether its slice of the full mask has a True entry."""
    q_chunk, kv_chunk = min(chunk, Sq), min(chunk, Skv)
    q_pos = q_offset + np.arange(Sq)
    kv_pos = np.arange(Skv)
    mask = np.ones((Sq, Skv), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    nq, nk = -(-Sq // q_chunk), -(-Skv // kv_chunk)
    return {(qi, ci): bool(mask[qi * q_chunk:(qi + 1) * q_chunk,
                                ci * kv_chunk:(ci + 1) * kv_chunk].any())
            for qi, ci in itertools.product(range(nq), range(nk))}


@pytest.mark.parametrize("Sq,Skv,chunk,q_offset,causal,n_live,n_tiles", [
    (4096, 4096, 1024, 0, True, 10, 16),     # the smollm train step
    (200, 200, 64, 0, True, 10, 16),         # ragged: last chunk padded
    (128, 384, 64, 256, True, 11, 12),       # continuation, Sq < Skv
    (32, 384, 64, 240, True, 5, 6),          # q chunk across a KV boundary
    (4096, 4096, 1024, 0, False, 16, 16),    # non-causal: every tile
])
def test_tile_live_matches_mask(Sq, Skv, chunk, q_offset, causal, n_live,
                                n_tiles):
    brute = _brute_live(Sq, Skv, chunk, q_offset, causal)
    q_chunk, kv_chunk = min(chunk, Sq), min(chunk, Skv)
    mine = {t: (not causal) or bool(_tile_live(*t, q_chunk, kv_chunk,
                                               q_offset))
            for t in brute}
    assert mine == brute
    assert (sum(mine.values()), len(mine)) == (n_live, n_tiles)


SKIP_CASES = [
    # (B, Sq, Skv, H, Hkv, Dq, Dv, causal, chunk, q_offset)
    (1, 256, 256, 6, 2, 64, 64, True, 64, 0),     # GQA G=3 (smollm), 10/16
    (1, 200, 200, 4, 4, 32, 32, True, 64, 0),     # MHA, ragged Sq
    (1, 128, 384, 4, 4, 48, 32, True, 64, 256),   # Dq != Dv (MLA), offset
    (1, 32, 384, 6, 2, 32, 32, True, 64, 240),    # unaligned offset
    (2, 192, 192, 6, 2, 32, 32, False, 64, 0),    # non-causal: no skip
]


def _inputs(case, dtype):
    B, Sq, Skv, H, Hkv, Dq, Dv, *_ = case
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    return (jax.random.normal(k1, (B, Sq, H, Dq), dtype),
            jax.random.normal(k2, (B, Skv, Hkv, Dq), dtype),
            jax.random.normal(k3, (B, Skv, Hkv, Dv), dtype),
            jax.random.normal(k4, (B, Sq, H, Dv), jnp.float32))


def _f32(*xs):
    return [x.astype(jnp.float32) for x in xs]


@pytest.mark.parametrize("case", SKIP_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_skip_forward_matches_reference(case, dtype):
    causal, chunk, q_offset = case[7:]
    q, k, v, _ = _inputs(case, dtype)
    out = flash_attention(q, k, v, causal, chunk, q_offset)
    expect = reference_attention(*_f32(q, k, v), causal, q_offset)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", SKIP_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_skip_grads_match_reference(case, dtype):
    causal, chunk, q_offset = case[7:]
    q, k, v, w = _inputs(case, dtype)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal, chunk, q_offset)
                .astype(jnp.float32) * w).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal, q_offset) * w).sum()

    got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_ref, argnums=(0, 1, 2))(*_f32(q, k, v))
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-4
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("causal,n_cond", [(True, 2), (False, 0)])
def test_skip_is_a_conditional_only_when_causal(causal, n_cond):
    """Causal: one ``cond`` in the forward's KV scan and one in the
    backward's; non-causal callers keep the unconditional body."""
    q, k, v, w = _inputs(SKIP_CASES[0], jnp.float32)

    def f(q, k, v):
        return (flash_attention(q, k, v, causal, 64, 0) * w).sum()

    text = str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(q, k, v))
    assert text.count(" cond[") == n_cond
