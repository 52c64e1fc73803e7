"""A training cell's run, driven without the chip at a CPU size, comes
out correct, and comes out not correct with the timed path broken: a
step that returns its state unchanged, half of the batch left out (the
mean taken over the rest), and the control (the reference at float8 in
the program's place); on four devices as a {data 2, model 2} mesh too,
where the half batch stands for a data replica's gradient left out of
the exchange.  The limits are the cell's own."""

import json
import os
import subprocess
import sys

import pytest

import harness
import train_faults as F


@pytest.fixture
def cell(monkeypatch):
    from repro.core import planner
    monkeypatch.setitem(planner.DEVICE_KINDS, "cpu", "v5e")
    return F.tiny_cell()


#: what the sound run hands ``harness.measure`` as ``control``
MEASURED: dict = {}


@pytest.fixture(scope="module")
def sound():
    from repro.core import planner
    measure = harness.measure

    def recording(*a, control=frozenset(), **kw):
        MEASURED["control"] = control
        return measure(*a, control=control, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(planner.DEVICE_KINDS, "cpu", "v5e")
        mp.setattr(harness, "measure", recording)
        yield F.run(F.tiny_cell())


def test_sound_run_is_correct(sound):
    out = sound
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.end_to_end["train_tokens_per_s"] > 0
    assert out.end_to_end["mem_pred_ape"] > 0


def test_sound_run_hands_readers_scopes_and_metrics(sound):
    """Set-up maps the compiled step's leaf instructions to the scopes
    the tiny model opens; the check steps' mean of every scalar the step
    reports."""
    scopes = sound.facts["op_scopes"]
    assert set(scopes.values()) == {"attn", "mlp", "loss"}
    m = sound.facts["step_metrics"]
    assert {"loss", "xent", "aux", "grad_norm", "n_tok"} <= set(m)
    assert m["n_tok"] == 4 * 64
    assert 0 < m["loss"] < 10 and m["grad_norm"] > 0


def test_sound_run_names_the_steps_loops_to_the_trace(sound):
    """The window's trace is reduced knowing the compiled step's control
    ops: the layer loops' ``while`` instructions, none of them a leaf
    that a scope reader counts."""
    control = MEASURED["control"]
    assert any(n.startswith("while") for n in control)
    assert not control & set(sound.facts["op_scopes"])


def test_state_left_unchanged_is_caught(cell, monkeypatch):
    F.state_unchanged(monkeypatch, cell)
    assert not F.run(cell).correct


def test_half_batch_is_caught(cell, monkeypatch):
    F.half_batch(monkeypatch, cell)
    assert not F.run(cell).correct


def test_control_fails(cell, monkeypatch):
    F.control(monkeypatch, cell)
    assert not F.run(cell).correct


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_four_devices_sound_and_faults():
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                   "--xla_backend_optimization_level=0")
    p = subprocess.run([sys.executable, F.__file__], cwd=harness.ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "state_unchanged": False,
                   "half_batch": False}, p.stderr[-3000:]


SCOPES_RUN = """
import json, sys
sys.path[:0] = ["bench", "src"]
import harness
harness.enable_compile_cache()
import jax
from repro.core import planner
import train_faults as F
planner.DEVICE_KINDS["cpu"] = "v5e"
hits = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, d, **k: hits.append(e) if "cache_retrieval" in e else None)
drv = harness.load_module(harness.driver_file("train"))
job = drv.Job(F.tiny_cell(), 5, jax.devices()[:1])
scopes = drv.step_scopes(job.compiled.as_text())
print(json.dumps({"scopes": sorted(set(scopes.values())),
                  "hits": len(hits)}))
"""


def test_scopes_from_a_cold_and_a_warm_compile_cache(tmp_path):
    """The step's scopes come through JAX's persistent compilation cache:
    a run that compiles and one that loads the step find the same."""
    env = _cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   PYTHONPATH=os.path.join(harness.BENCH, "tests"))
    got = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", SCOPES_RUN],
                           cwd=harness.ROOT, env=env, capture_output=True,
                           text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-3000:]
        got.append(json.loads(p.stdout.strip().splitlines()[-1]))
    cold, warm = got
    assert cold["hits"] == 0 and warm["hits"] > 0
    assert cold["scopes"] == warm["scopes"] == ["attn", "loss", "mlp"]


def test_step_scopes_logs_a_step_without_scopes(capsys):
    drv = harness.load_module(harness.driver_file("train"))

    stale = ('  %fusion.1 = f32[8]{0} fusion(%p), metadata={'
             'op_name="jit(train_step)/jvp()/dot_general"}\n')
    assert drv.step_scopes(stale) == {}
    assert "names none of" in capsys.readouterr().err
