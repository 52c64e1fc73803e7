"""The planning cells' runs, driven without the chip on the CPU, come out
correct, and not correct with an answer altered where it is produced
and with the control (byte counts held in int32) in the program's
place."""

import dataclasses

import jax
import numpy as np
import pytest

import harness

SEED = 2 ** 33 + 31
#: long enough for both queries to be asked in the window on the CPU
QUERY_WINDOW = 3.0


def cell(traffic_name: str, **overrides):
    traffic = harness.load_json(harness.traffic_file(traffic_name))
    traffic.update(overrides)
    return harness.Cell(
        name=f"deepseek-v2-lite-16b.{traffic_name}", chips=1,
        config_name="deepseek-v2-lite-16b",
        config=harness.load_json("bench/configs/deepseek-v2-lite-16b.json"), traffic=traffic, end_to_end=[],
        per_layer=[])


def run(c, seconds=0.3):
    drv = harness.load_module(harness.driver_file(c.driver))
    return drv.run(c, SEED, seconds, False, jax.devices()[:1])


@pytest.fixture
def sweep_cell():
    return cell("plan-sweep", sample_cells=150, warm_sweeps=1)


@pytest.fixture
def query_cell():
    t = harness.load_json(harness.traffic_file("plan-query"))
    qs = [dict(t["queries"][0], chips=[8, 16, 32]),
          dict(t["queries"][-1], chips=[16, 32])]
    return cell("plan-query", queries=qs, reference_queries=2)


def test_sweep_sound_run_is_correct(sweep_cell):
    out = run(sweep_cell)
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.end_to_end["sweep_cells_per_s"] > 0


def test_sweep_altered_answer_is_caught(sweep_cell, monkeypatch):
    from repro.core import batch_jax
    sweep = batch_jax.sweep_columnar_jax

    def altered(*a, **kw):
        res = sweep(*a, **kw)
        res.columns.peak_bytes[::97] += 1
        return res

    monkeypatch.setattr(batch_jax, "sweep_columnar_jax", altered)
    assert not run(sweep_cell).correct


def test_sweep_control_fails(sweep_cell, monkeypatch):
    drv = harness.load_module(harness.driver_file("plan_sweep"))
    take = drv.take

    def int32(c, idx):
        cols, tables = take(c, idx)
        peak = cols[9].astype(np.int32).astype(np.int64)
        return cols[:9] + (peak,) + cols[10:], tables

    monkeypatch.setattr(drv, "take", int32)
    assert not run(sweep_cell).correct


def test_query_sound_run_is_correct(query_cell):
    out = run(query_cell, QUERY_WINDOW)
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.end_to_end["query_ms_p90"] > 0


def test_query_altered_answer_is_caught(query_cell, monkeypatch):
    drv = harness.load_module(harness.driver_file("plan_query"))
    ask = drv.ask

    def altered(arch, q, stats=None):
        got = ask(arch, q, stats)
        if q["kind_of"] == "min_chips":
            return dataclasses.replace(got, peak_bytes=got.peak_bytes + 1)
        return got[:-1]

    monkeypatch.setattr(drv, "ask", altered)
    assert not run(query_cell, QUERY_WINDOW).correct


def test_query_control_fails(query_cell, monkeypatch):
    drv = harness.load_module(harness.driver_file("plan_query"))
    ask = drv.ask

    def int32(arch, q, stats=None):
        got = ask(arch, q, stats)
        if q["kind_of"] == "min_chips":
            return dataclasses.replace(
                got, peak_bytes=int(np.int64(got.peak_bytes)
                                    .astype(np.int32)))
        return got

    monkeypatch.setattr(drv, "ask", int32)
    assert not run(query_cell, QUERY_WINDOW).correct
