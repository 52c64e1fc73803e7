"""A training cell's run, driven without the chip at a CPU size, comes
out correct, and comes out not correct with the timed path broken: a
step that returns its state unchanged, half of the batch left out (the
mean taken over the rest), and the control (the reference at float8 in
the program's place).  The limits are the cell's own."""

import dataclasses

import jax
import pytest

import harness

SEED = 2 ** 33 + 21


@pytest.fixture
def cell(monkeypatch):
    from repro.core import planner
    monkeypatch.setitem(planner.DEVICE_KINDS, "cpu", "v5e")
    traffic = harness.load_json("bench/tests/data/tiny-train.json")
    traffic["limits"] = harness.load_json(
        harness.traffic_file("train-s4096-b8"))["limits"]
    return harness.Cell(
        name="tiny.train", chips=1, config_name="tiny-lm",
        config=harness.load_json("bench/tests/data/tiny-lm.json"), traffic=traffic, end_to_end=[],
        per_layer=[])


def run(cell):
    drv = harness.load_module(harness.driver_file("train"))
    return drv.run(cell, SEED, 0.2, False, jax.devices()[:1])


def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.end_to_end["train_tokens_per_s"] > 0
    assert out.end_to_end["mem_pred_ape"] > 0


def test_state_left_unchanged_is_caught(cell, monkeypatch):
    from repro.train import train_step
    monkeypatch.setattr(train_step, "apply_updates",
                        lambda p, g, s, step, cfg: (p, s))
    assert not run(cell).correct


def test_half_batch_is_caught(cell, monkeypatch):
    import repro.models as models
    build = models.build_model

    def half(cfg):
        m = build(cfg)
        loss = m.loss

        def half_loss(p, b, **kw):
            n = b["tokens"].shape[0] // 2
            return loss(p, {k: v[:n] for k, v in b.items()}, **kw)
        return dataclasses.replace(m, loss=half_loss)

    monkeypatch.setattr(models, "build_model", half)
    assert not run(cell).correct


def test_control_fails(cell, monkeypatch):
    drv = harness.load_module(harness.driver_file("train"))
    ref = harness.load_module(cell.config["reference"])
    t = cell.traffic

    def control(job, steps):
        return ref.run(cell.config, t["optimizer"], SEED,
                       [job.host_batch(i) for i in range(steps)],
                       rows_per_block=int(t["rows_per_block"]), lowp=True)

    monkeypatch.setattr(drv, "program_readings", control)
    assert not run(cell).correct
