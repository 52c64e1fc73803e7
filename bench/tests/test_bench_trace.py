"""The reduction from a trace to busy time, idle gaps, collective
exposure and device-op time: on hand-made events with known answers, and
on small traces recorded on a v5e (a ``--trace 1`` run with
``BENCH_KEEP_TRACE=<file>`` saves its events; the fixtures are a slice
of a window of the train and plan-sweep cells)."""

import glob
import os

import pytest

import trace_reduce as T

MS = 1_000_000


def ev(plane, line, name, start_ms, dur_ms):
    return [plane, line, name, int(start_ms * MS), int(dur_ms * MS)]


def test_interval_arithmetic():
    assert T.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert T.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert T.clip([[0, 5], [8, 9]], 2, 8) == [[2, 5]]
    assert T.length([[0, 3], [5, 7]]) == 5


def test_reduce_on_known_events():
    host, d0, d1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
    events = [
        ev(host, "main", "window", 0, 100),
        ev(host, "main", "step", 0, 60),
        ev(host, "main", "metrics_to_host", 60, 40),
        ev(d0, T.OPS_LINE, "fusion.1", 10, 30),
        ev(d0, T.OPS_LINE, "all-reduce.2", 30, 20),     # 10 ms exposed
        ev(d0, T.MODULES_LINE, "jit_step", 10, 40),
        ev(d1, T.OPS_LINE, "fusion.1", 10, 40),
        ev(d1, T.OPS_LINE, "all-gather.3", 20, 10),     # hidden
        ev(d0, T.OPS_LINE, "fusion.9", 150, 10),        # outside the window
    ]
    s = T.reduce(events)
    assert s.window_s == pytest.approx(0.1)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(0.04)              # 40 ms on each
    assert s.idle_share == pytest.approx(0.6)
    assert s.collective_exposed_s == pytest.approx(0.005)
    assert s.gaps[0] == ["metrics_to_host", pytest.approx(0.05)]
    assert s.gaps[1] == ["step", pytest.approx(0.01)]
    assert s.ops[0] == ["fusion.1", pytest.approx(0.035)]
    assert s.module_seconds("jit_step") == pytest.approx(0.02)
    assert s.spans["step"] == 1
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_refuses_a_trace_without_a_window():
    with pytest.raises(ValueError):
        T.reduce([ev("/device:TPU:0", T.OPS_LINE, "fusion", 0, 1)])


RECORDED = sorted(glob.glob(os.path.join(
    os.path.dirname(__file__), "data", "trace_*.json.gz")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_reduce_on_recorded_chip_trace(path):
    s = T.reduce(T.read_saved(path))
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert s.ops and s.ops[0][1] > 0
    assert sum(t for _, t in s.ops) >= s.busy_s * 0.999
    assert all(g[1] > 0 for g in s.gaps)
