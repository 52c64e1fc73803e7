"""The program's spans and named scopes as ``span_reduce`` reads them: on
hand-made events with known answers, on a real profiler trace of a small
sweep on the CPU, on the compiled step of the tiny training cell, and on
a slice of a plan-query window recorded on a v5e
(``tests/data/trace_v5e_query.json.gz``, saved with the ``plan.*`` spans
kept).  The reduction ``trace_reduce`` computes on the two older
recordings is pinned, so that neither module moves a metric that reads
it."""

import glob
import os
import random
import re

import pytest

import harness
import span_reduce as S
import trace_reduce as T

MS = 1_000_000
DATA = os.path.join(os.path.dirname(__file__), "data")
HOST, DEV = ("/host:CPU", "python"), "/device:TPU:0"


def host(name, start_ms, dur_ms, line="python"):
    return [HOST[0], line, name, int(start_ms * MS), int(dur_ms * MS)]


def op(name, start_ms, dur_ms, dev=DEV):
    return [dev, T.OPS_LINE, name, int(start_ms * MS), int(dur_ms * MS)]


def query_events():
    """One query 0-100 ms: a search holding two sweeps, each with its
    columns, tables (a fold inside), composition (the copy back inside)
    and the device op of the composition."""
    return [
        host("window", 0, 100),
        host("query:min_chips", 0, 100),
        host("plan.grid", 0, 10),
        host("plan.search", 10, 90),
        host("plan.sweep", 10, 40),
        host("plan.columns", 10, 5),
        host("plan.tables", 15, 20),
        host("plan.fold", 30, 5),
        host("plan.compose", 35, 10),
        host("plan.to_host", 40, 5),
        op("fusion.1", 36, 2),
        host("plan.sweep", 60, 40),
        host("plan.compose", 90, 10),
        op("fusion.2", 91, 1),
        host("plan.sweep", 200, 5),              # outside the window
    ]


def test_span_seconds_total_and_self():
    got = S.span_seconds(query_events())
    assert got["plan.sweep"]["count"] == 2
    assert got["plan.sweep"]["total_s"] == pytest.approx(0.080)
    # less columns 5, tables 20 and compose 10 in the first, compose 10
    # in the second
    assert got["plan.sweep"]["self_s"] == pytest.approx(0.035)
    assert got["plan.tables"]["self_s"] == pytest.approx(0.015)
    assert got["plan.fold"]["self_s"] == pytest.approx(0.005)
    assert got["plan.compose"]["total_s"] == pytest.approx(0.020)
    assert got["plan.compose"]["self_s"] == pytest.approx(0.015)
    assert got["plan.search"]["self_s"] == pytest.approx(0.010)
    assert got["query:min_chips"]["self_s"] == pytest.approx(0.0)


def test_self_time_stays_on_its_thread_line():
    events = [host("window", 0, 100), host("plan.sweep", 0, 50),
              host("plan.columns", 10, 20, line="worker")]
    got = S.span_seconds(events)
    assert got["plan.sweep"]["self_s"] == pytest.approx(0.050)
    assert got["plan.columns"]["self_s"] == pytest.approx(0.020)


def test_idle_named_by_the_innermost_program_span():
    got = S.idle_by_span(query_events())
    assert sum(got.values()) == pytest.approx(0.097)
    assert got["plan.grid"] == pytest.approx(0.010)
    assert got["plan.search"] == pytest.approx(0.010)
    assert got["plan.columns"] == pytest.approx(0.005)
    assert got["plan.tables"] == pytest.approx(0.015)
    assert got["plan.fold"] == pytest.approx(0.005)
    assert got["plan.compose"] == pytest.approx(0.005 - 0.002 + 0.009)
    assert got["plan.to_host"] == pytest.approx(0.005)
    assert got["plan.sweep"] == pytest.approx(0.005 + 0.030)
    assert "query:min_chips" not in got and "none" not in got


@pytest.mark.parametrize("seed", range(5))
def test_innermost_agrees_with_trace_reduce(seed):
    rng = random.Random(seed)
    spans = []
    for i in range(200):
        s = rng.randrange(0, 10_000)
        name = rng.choice(["window", "sweep", "plan.a", "plan.b"])
        spans.append((f"{name}", s, s + rng.choice([1, 5, 50, 500, 5000])))
    times = [rng.randrange(-10, 15_000) for _ in range(500)]
    want = [T._innermost(spans, t) for t in times]
    assert S.innermost(spans, times) == want


HLO = """\
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(train_step)/jvp()/while/body/attn/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%p), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/checkpoint/rematted_computation/mlp/mul"}
  ROOT %fusion.3 = f32[] fusion(%q), metadata={op_name="jit(train_step)/transpose(jvp(loss))/while/body/log"}
  %fusion.4 = f32[] fusion(%q), metadata={op_name="jit(train_step)/attention/mul"}
  %while.5 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp()/while"}
  %copy.6 = f32[] copy(%q)
"""


def test_op_scopes_bare_and_wrapped():
    assert S.op_scopes(HLO, S.SCOPES) == {
        "fusion.1": "attn", "fusion.2": "mlp", "fusion.3": "loss"}


def test_scope_seconds_counts_leaf_ops_once():
    events = [host("window", 0, 100), host("step", 0, 100),
              op("while.5", 0, 90), op("fusion.1", 0, 40),
              op("fusion.2", 40, 20), op("fusion.3", 60, 10),
              op("copy.6", 70, 20), op("fusion.1", 95, 10)]
    scopes = S.op_scopes(HLO, S.SCOPES)
    got = S.scope_seconds(events, scopes, S.control_ops(HLO))
    assert got["attn"] == pytest.approx(0.045)     # clipped at 100 ms
    assert got["mlp"] == pytest.approx(0.020)
    assert got["loss"] == pytest.approx(0.010)
    assert got["unscoped"] == pytest.approx(0.020)
    assert got["leaf"] == pytest.approx(0.095)


#: a tile skip as the TPU compiler prints it: JAX names the conditional
#: ``cond.N``; its live branch's leaf runs as an event of its own
COND_HLO = """\
  %fusion.8 = f32[8,1024]{1,0:T(8,128)} fusion(%a), kind=kOutput, metadata={op_name="jit(train_step)/jvp()/while/body/attn/while/body/cond/tile/dot_general"}
  %cond.7 = (f32[8,1024]{1,0:T(8,128)}, f32[8]{0:T(128)}) conditional(%p, %t, %u), branch_computations={%region_1.2, %region_3.4}, metadata={op_name="jit(train_step)/jvp()/while/body/attn/while/body/cond"}
  %call.9 = f32[8]{0} call(%x), to_apply=%region_5, metadata={op_name="jit(train_step)/mlp/call"}
  %while.10 = (s32[], /*index=1*/bf16[2,4]{1,0:T(8,128)(2,1)}) while(%t), condition=%c, body=%b, metadata={op_name="jit(train_step)/loss/while"}
  %fusion.11 = f32[8]{0} fusion(%x), metadata={op_name="jit(train_step)/jvp(mlp)/mul"}
"""


def test_op_scopes_drop_control_ops_by_opcode():
    assert S.control_ops(COND_HLO) == {"cond.7", "call.9", "while.10"}
    assert S.control_ops(HLO) == {"while.5"}
    assert S.op_scopes(COND_HLO, S.SCOPES) == {"fusion.8": "attn",
                                               "fusion.11": "mlp"}


def test_scope_seconds_counts_a_live_branch_once():
    events = [host("window", 0, 100), host("step", 0, 100),
              op("cond.7", 0, 50), op("fusion.8", 10, 30),
              op("while.10", 50, 40), op("call.9", 55, 10),
              op("fusion.11", 60, 5)]
    got = S.scope_seconds(events, S.op_scopes(COND_HLO, S.SCOPES),
                          S.control_ops(COND_HLO))
    assert got == {"attn": pytest.approx(0.030),
                   "mlp": pytest.approx(0.005), "unscoped": 0.0,
                   "leaf": pytest.approx(0.035)}


def reader(name):
    return harness.load_module(harness.metric_file(name))


def test_scope_readers_per_step():
    """Leaf time per scope over the window's ``step`` spans, two of them,
    from ops on two devices; control ops and other scopes left out."""
    d1 = "/device:TPU:1"
    events = [host("window", 0, 200), host("step", 0, 1),
              host("step", 100, 1),
              op("cond.7", 0, 50), op("fusion.8", 10, 30),
              op("fusion.8", 110, 30), op("fusion.8", 10, 20, dev=d1),
              op("fusion.11", 60, 4), op("fusion.11", 60, 8, dev=d1),
              op("copy.12", 70, 10)]
    trace = T.reduce(events)
    facts = {"op_scopes": S.op_scopes(COND_HLO, S.SCOPES)}
    # attn: (30 + 30 + 20) / 2 devices / 2 steps; mlp: (4 + 8) / 2 / 2
    assert reader("attn_device_ms.train").read(facts, trace) \
        == pytest.approx(20.0)
    assert reader("mlp_device_ms.train").read(facts, trace) \
        == pytest.approx(3.0)
    assert reader("loss_device_ms.train").read(facts, trace) is None
    assert S.scope_ms_per_step(facts, trace, "moe") is None


@pytest.mark.parametrize("name", ["attn_device_ms.train",
                                  "mlp_device_ms.train",
                                  "loss_device_ms.train"])
def test_scope_readers_read_nothing_without_scopes(name):
    """A compiled step that names no scope (a persistent-cache entry
    compiled before the scopes were added) or an untraced run: the
    reader returns None, and the metric is left out of the line."""
    trace = T.reduce([host("window", 0, 100), host("step", 0, 1),
                      op("fusion.8", 10, 30), op("fusion.11", 50, 5),
                      op("fusion.3", 60, 5)])
    full = {"op_scopes": S.op_scopes(HLO + COND_HLO, S.SCOPES)}
    assert reader(name).read(full, trace) > 0
    assert reader(name).read({"op_scopes": {}}, trace) is None
    assert reader(name).read({}, trace) is None
    assert reader(name).read(full, None) is None


def test_collective_exposed_on_two_devices():
    d1 = "/device:TPU:1"
    events = [host("window", 0, 100), op("fusion.1", 10, 30),
              op("all-reduce.2", 30, 20),            # 10 ms exposed
              op("fusion.1", 10, 40, dev=d1),
              op("all-gather.3", 20, 10, dev=d1),    # hidden
              op("all-gather.3", 60, 6, dev=d1),     # 6 ms exposed
              op("collective-permute-done.4", 90, 20)]   # 10 in window
    # (10 + 10 on device 0, 6 on device 1) / 2 devices
    assert T.reduce(events).collective_exposed_s == pytest.approx(0.013)


def test_collective_inside_a_loop_body_is_exposed():
    """A layer loop's ``while`` event spans its body's ops: where only
    the loop covers a collective, the collective is exposed; a compute
    op of the body beside it hides its part."""
    events = [host("window", 0, 100), op("while.5", 0, 100),
              op("all-gather.6", 10, 30), op("fusion.7", 30, 20),
              op("cond.8", 60, 30), op("all-reduce.9", 70, 10)]
    control = S.control_ops(
        '  %while.5 = (s32[]) while(%t), condition=%c, body=%b\n'
        '  %cond.8 = f32[8]{0} conditional(%p, %x, %y), '
        'branch_computations={%r1, %r2}\n')
    assert T.reduce(events, control).collective_exposed_s \
        == pytest.approx(0.030)                     # 20 + 10 ms
    assert T.reduce(events).collective_exposed_s == 0.0


def test_named_scopes_reach_forward_backward_and_remat(monkeypatch):
    """The tiny training cell's compiled production step: ops of each
    scope in the forward pass, the backward pass and the remat
    recompute."""
    import jax
    from repro.core import planner
    monkeypatch.setitem(planner.DEVICE_KINDS, "cpu", "v5e")
    cell = harness.Cell(
        name="tiny.train", chips=1, config_name="tiny-lm",
        config=harness.load_json("bench/tests/data/tiny-lm.json"),
        traffic=harness.load_json("bench/tests/data/tiny-train.json"),
        end_to_end=[], per_layer=[])
    drv = harness.load_module(harness.driver_file("train"))
    text = drv.Job(cell, 5, jax.devices()[:1]).compiled.as_text()
    scopes = S.op_scopes(text, S.SCOPES)
    names = dict(m.groups() for m in map(S._OP_NAME.match,
                                         text.splitlines()) if m)
    phases = set()
    for inst, scope in scopes.items():
        on = names[inst]
        phase = "remat" if "rematted_computation" in on \
            else "backward" if "transpose(" in on else "forward"
        phases.add((scope, phase))
    dense = set(S.SCOPES) - {"moe"}          # the tiny model has no MoE
    assert phases == {(s, p) for s in dense
                      for p in ("forward", "backward", "remat")}
    assert re.search(r'op_name="[^"]*jvp\(loss\)', text)


def test_load_keeps_program_spans(tmp_path):
    import jax
    from repro.core import sweep as SW
    grid = SW.SweepGrid(arch="llama3.2-3b", chips=(4,), chip="v5e",
                        global_batches=(8,), seq_lens=(1024,),
                        kind="train")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW):
            SW.SweepEngine().sweep(grid, engine="jax")
    finally:
        jax.profiler.stop_trace()
    names = {e[2] for e in S.load(str(tmp_path))}
    assert {"window", "plan.sweep", "plan.columns", "plan.tables",
            "plan.fold", "plan.compose", "plan.to_host",
            "plan.finalize"} <= names


QUERY = os.path.join(DATA, "trace_v5e_query.json.gz")


def test_recorded_query_trace():
    events = T.read_saved(QUERY)
    spans = S.span_seconds(events)
    assert {"plan.search", "plan.sweep", "plan.columns", "plan.tables",
            "plan.fold", "plan.compose", "plan.to_host",
            "plan.finalize"} <= set(spans)
    idle = S.idle_by_span(events)
    program = sum(t for n, t in idle.items() if n.startswith("plan."))
    inside = program + sum(t for n, t in idle.items()
                           if n.startswith("query:"))
    assert program >= 0.9 * inside > 0
    s = T.reduce(events)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_cli_prints_the_breakdown(capsys):
    assert S.main([QUERY]) == 0
    out = capsys.readouterr().out
    assert '"idle_by_span"' in out and '"plan.sweep"' in out


#: trace_reduce's Summary on the two older recordings, as computed
#: before the program had spans
PINNED = {
    "trace_v5e_sweep.json.gz": dict(
        window_s=0.35, busy_s=0.001830478, n_devices=1,
        collective_exposed_s=0.0, spans={"window": 1, "sweep": 5},
        modules={"jit_compose(1478429059675937987)": 0.000387489,
                 "jit_compose(13806422100212199148)": 0.000569213,
                 "jit_compose(2902185385898219176)": 0.000886498},
        n_gaps=721, gaps_s=0.348169522,
        gap0=["sweep", 0.058041448], n_ops=145, ops_s=0.002994271,
        op0=["while.6", 0.001202002]),
    "trace_v5e_train.json.gz": dict(
        window_s=0.6, busy_s=0.59179609, n_devices=1,
        collective_exposed_s=0.0,
        spans={"window": 1, "metrics_to_host": 2, "batch": 1, "step": 1},
        modules={"jit_train_step(3449555078589335657)": 0.591807439},
        n_gaps=149, gaps_s=0.00820391,
        gap0=["metrics_to_host", 0.008196942], n_ops=679,
        ops_s=2.092447739, op0=["while.310", 0.319377511]),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_trace_reduce_pinned(name):
    want = PINNED[name]
    s = T.reduce(T.read_saved(os.path.join(DATA, name)))
    approx = lambda v: pytest.approx(v, rel=1e-12, abs=1e-15)
    assert s.window_s == approx(want["window_s"])
    assert s.busy_s == approx(want["busy_s"])
    assert s.n_devices == want["n_devices"]
    assert s.collective_exposed_s == approx(want["collective_exposed_s"])
    assert s.spans == want["spans"]
    assert s.modules == {k: approx(v) for k, v in want["modules"].items()}
    assert len(s.gaps) == want["n_gaps"]
    assert sum(g[1] for g in s.gaps) == approx(want["gaps_s"])
    assert s.gaps[0] == [want["gap0"][0], approx(want["gap0"][1])]
    assert len(s.ops) == want["n_ops"]
    assert sum(o[1] for o in s.ops) == approx(want["ops_s"])
    assert s.ops[0] == [want["op0"][0], approx(want["op0"][1])]


def test_every_recording_is_pinned_or_has_program_spans():
    for path in glob.glob(os.path.join(DATA, "trace_*.json.gz")):
        name = os.path.basename(path)
        assert name in PINNED or any(
            e[2].startswith(S.PREFIX) for e in T.read_saved(path)), name
