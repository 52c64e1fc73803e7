"""Inputs and weights from ``--seed``: the same seed gives the same
tokens and the same weights, to the program and to the reference alike.

Weights are keyed by their path in the parameter tree and their shape,
so the reference, which builds its own list of paths and shapes from the
configuration file, draws exactly the arrays the program is given.
"""

from __future__ import annotations

import zlib

import numpy as np

EMBED_SCALE = 0.02


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size (more than 32 bits) as two 31-bit words: the
    argument of the jitted functions that make weights, so that one
    compiled program serves every seed."""
    seed = int(seed)
    return np.array([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                    np.uint32)


def key_of(words):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def leaf_scale(path: str, shape: tuple) -> tuple:
    """(kind, scale) of a leaf: norm scales start at one, the token
    table at N(0, 0.02), every matrix at N(0, 1/fan_in)."""
    if path.endswith("/scale"):
        return "ones", 1.0
    if "/embed/" in path:
        return "normal", EMBED_SCALE
    return "normal", 1.0 / float(np.sqrt(max(shape[-2], 1)))


def leaf_array(key, path: str, shape: tuple, dtype):
    import jax
    import jax.numpy as jnp
    kind, scale = leaf_scale(path, shape)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def paths(tree) -> list:
    """'a/b/c' paths of a nested dict's leaves, in tree order."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]


def make_params(words, specs, dtype=None):
    """Arrays for a nested dict of ShapeDtypeStructs from the seed's
    ``seed_words`` (call under jit, with the words as an argument)."""
    import jax
    key = key_of(words)
    leaves, treedef = jax.tree_util.tree_flatten(specs)
    out = [leaf_array(key, p, tuple(s.shape), dtype or s.dtype)
           for p, s in zip(paths(specs), leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def token_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> dict:
    """Step ``step``'s rows: uniform token ids, labels the next token.
    Every (seed, step) pair draws its own rows."""
    g = np.random.Generator(np.random.Philox(
        key=[int(seed) & (2 ** 64 - 1), int(step)]))
    tokens = g.integers(0, vocab, (batch, seq), dtype=np.int32)
    return {"tokens": tokens,
            "labels": np.roll(tokens, -1, axis=1).astype(np.int32)}
