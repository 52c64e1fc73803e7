"""Inputs, weights and query orders are the same for the same seed."""

import numpy as np

import harness
import seeded

BIG = 2 ** 33 + 12345


def test_batches_are_deterministic_per_seed():
    a = seeded.token_batch(BIG, 3, 4, 16, 100)
    b = seeded.token_batch(BIG, 3, 4, 16, 100)
    c = seeded.token_batch(BIG, 4, 4, 16, 100)
    d = seeded.token_batch(BIG + 1, 3, 4, 16, 100)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])
    assert np.array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 100


def test_weights_are_keyed_by_seed_and_path():
    import jax
    import jax.numpy as jnp
    specs = {"m": {"w": jax.ShapeDtypeStruct((8, 4), jnp.bfloat16),
                   "scale": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}}
    make = jax.jit(lambda w: seeded.make_params(w, specs))
    a = make(seeded.seed_words(BIG))
    b = make(seeded.seed_words(BIG))
    c = make(seeded.seed_words(BIG + 1))
    assert make._cache_size() == 1          # one program for every seed
    assert np.array_equal(a["m"]["w"], b["m"]["w"])
    assert not np.array_equal(a["m"]["w"], c["m"]["w"])
    assert np.all(np.asarray(a["m"]["scale"]) == 1)


def test_query_order_is_deterministic_per_seed():
    drv = harness.load_module(harness.driver_file("plan_query"))
    assert drv.order(11, BIG, 3) == drv.order(11, BIG, 3)
    assert drv.order(11, BIG, 3) != drv.order(11, BIG + 1, 3)
    assert sorted(drv.order(11, BIG, 1)) == list(range(11))
    t = harness.load_json(harness.traffic_file("plan-query"))
    kinds = [q["kind_of"] for q in t["queries"]]
    assert kinds.count("min_chips") == 9 and kinds.count("frontier") == 2
