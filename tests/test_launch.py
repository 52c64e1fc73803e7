"""The launcher (launch/train.py) and the pieces around it that need no
chip: the compile-cache path rule, the device-kind -> chip table, the
guard planning the mesh that is built and the (remat, grad_accum) that
runs, and chip_smoke.py refusing a host without a TPU.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro import configs  # noqa: E402
from repro.configs import ShapeConfig, get_config  # noqa: E402
from repro.core import planner  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import train as T  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def test_compile_cache_env_var_wins():
    env = {compile_cache.ENV_VAR: "/somewhere/else"}
    assert compile_cache.cache_dir(env) == "/somewhere/else"


def test_compile_cache_default_is_fixed_inside_the_checkout():
    a, b = compile_cache.cache_dir({}), compile_cache.cache_dir({})
    assert a == b == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_sets_nothing_when_env_var_is_set(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/from/env")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/from/env"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("kind, chip", [("TPU v5 lite", "v5e"),
                                        ("TPU v5", "v5p"),
                                        ("TPU v6 lite", "v6e")])
def test_device_kind_resolves_to_a_chip(kind, chip):
    assert planner.chip_of_device_kind(kind) == chip
    assert chip in planner.CHIPS


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="unknown device kind"):
        planner.chip_of_device_kind(kind)


def test_mesh_shape_for():
    assert T.mesh_shape_for(1) == {"data": 1, "model": 1}
    assert T.mesh_shape_for(4) == {"data": 4, "model": 1}
    assert T.mesh_shape_for(4, data=2) == {"data": 2, "model": 2}
    with pytest.raises(ValueError):
        T.mesh_shape_for(4, data=3)


SHAPE = ShapeConfig("t", 32, 4, "train")


@pytest.fixture
def tiny_arch(monkeypatch):
    """A registered width-cut smollm on a CPU host that plans as a v5e
    whose HBM fits the job only with gradient accumulation."""
    cfg = get_config("smollm-360m").reduced()
    monkeypatch.setitem(configs._RUNTIME, cfg.name, cfg)
    monkeypatch.setitem(planner.DEVICE_KINDS, "cpu", "v5e")
    mesh = {"data": 1, "model": 1}
    p1, p2 = (planner.check(cfg.name, SHAPE, mesh, grad_accum=a).peak_bytes
              for a in (1, 2))
    assert p2 < p1
    hbm = int((p1 + p2) / 2 / planner.HEADROOM)
    monkeypatch.setitem(planner.CHIPS, "v5e",
                        planner.ChipSpec("v5e", hbm))
    return cfg.name


def test_guard_plans_the_built_mesh_and_runs_its_grad_accum(
        tiny_arch, monkeypatch, tmp_path):
    import repro.train as TR
    seen = {}
    real = TR.make_train_step

    def spy(*args, **kwargs):
        seen.update(kwargs)
        return real(*args, **kwargs)

    monkeypatch.setattr(TR, "make_train_step", spy)
    devices = jax.devices()[:1]
    run = T.launch(tiny_arch, SHAPE, steps=2, ckpt_dir=str(tmp_path),
                   devices=devices)
    assert run.mesh_shape == {"data": 1, "model": 1}
    assert run.chip == "v5e"
    assert run.report.fits and run.report.grad_accum == 2
    assert seen["grad_accum"] == run.report.grad_accum
    assert seen["remat"] == run.report.remat
    leaf = jax.tree.leaves(run.state.params)[0]
    assert dict(leaf.sharding.mesh.shape) == run.mesh_shape
    assert leaf.sharding.device_set == set(devices)
    assert run.restarts == 0 and len(run.history) == 2
    assert len(run.step_seconds) == 2


def test_guard_refuses_a_job_that_cannot_fit(tiny_arch, monkeypatch,
                                             tmp_path):
    monkeypatch.setitem(planner.CHIPS, "v5e", planner.ChipSpec("v5e", 1))
    with pytest.raises(T.GuardRefused, match="OoM guard"):
        T.launch(tiny_arch, SHAPE, steps=1, ckpt_dir=str(tmp_path))
    assert not list(tmp_path.iterdir())


def test_chip_smoke_fails_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
