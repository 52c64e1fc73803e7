"""Shared fixtures.  Tests run on the default 1-CPU-device backend; tests
needing a small multi-device mesh spawn it via the xdist-safe subprocess
helpers or use the 1x1 mesh (same code paths, degenerate sizes).

NOTE: --xla_force_host_platform_device_count is deliberately NOT set here —
only launch/dryrun.py uses placeholder devices (per the brief).  Tests that
need >1 device run in a subprocess (see test_moe_ep / test_distributed).

Session-scoped caches (tier-1 wall-clock): building a reduced model and
``model.init``-ing its params costs ~2s per arch, and the full-size spec
trees / parse tables behind the predictor parity tests are pure functions
of (arch, policy) — both used to be rebuilt per test.  ``reduced_zoo``
and ``sweep_engine`` build each exactly once per session; everything they
hand out is treated as read-only by convention (jax arrays are immutable,
parse tables are frozen dataclass rows).
"""

import os
import subprocess
import sys

# Tier-1 runs on XLA:CPU and only asserts NUMERICS, never executable
# speed — so skip XLA's backend optimization passes, which dominate the
# per-arch train-step compile times (full suite ~142s -> ~100s).  Must
# happen before the first `import jax` of the session (conftest is);
# appended so an explicit user XLA_FLAGS still wins.
_OPT_FLAG = "--xla_backend_optimization_level=0"
if "--xla_backend_optimization_level" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = \
        (os.environ.get("XLA_FLAGS", "") + " " + _OPT_FLAG).strip()

import jax
import jax.numpy as jnp
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Shared hypothesis profile for the property suites (test_batch_property,
# test_mesh_ctx, test_serve_property, test_stages_property,
# test_monotone_property): fixed seed (derandomize), no deadline flakes
# on shared CI runners, explicit example budget.  Local runs without
# hypothesis installed skip those suites via importorskip — the ONLY
# self-skips tier-1 carries — but in CI that skip is a silent coverage
# hole, so with CI=1 a missing hypothesis is a hard session error:
# requirements-dev.txt installs it, and this assert guarantees the
# property suites leave zero self-skips on every CI run.
try:
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile(
        "ci", derandomize=True, deadline=None, max_examples=50,
        print_blob=True)
    _hyp_settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:                                   # pragma: no cover
    if os.environ.get("CI"):
        raise RuntimeError(
            "CI=1 but hypothesis is not importable: the property suites "
            "would self-skip. Install requirements-dev.txt.")


def run_with_devices(code: str, n_devices: int = 4) -> str:
    """Run a python snippet in a subprocess with N fake CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices}"
                        f" {_OPT_FLAG}")
    env["PYTHONPATH"] = os.path.abspath(SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_archs():
    """Each test module starts with no runtime-registered architecture: a
    file run before it on the same xdist worker (the benchmark's tests
    register the configurations they build) must not add to
    ``registered_archs()``, which the golden suite compares with its
    snapshot files."""
    from repro import configs
    saved = dict(configs._RUNTIME)
    configs._RUNTIME.clear()
    yield
    configs._RUNTIME.clear()
    configs._RUNTIME.update(saved)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def tiny_batch(model, shape, key=None):
    """Concrete random batch matching model.batch_spec(shape)."""
    key = key if key is not None else jax.random.PRNGKey(1)
    out = {}
    for name, sd in model.batch_spec(shape).items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(sd.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, sd.shape, 0,
                                           model.cfg.vocab, sd.dtype)
        else:
            out[name] = jax.random.normal(sub, sd.shape, sd.dtype) * 0.3
    return out


# ---------------------------------------------------------------------------
# session-scoped model/engine caches
# ---------------------------------------------------------------------------


class ReducedZoo:
    """Memoized (cfg.reduced(), model, params) per arch — the expensive
    trio behind every per-arch smoke test.  Params are initialized ONCE
    with PRNGKey(0), exactly what each test did individually."""

    def __init__(self):
        self._cache = {}

    def __call__(self, arch: str):
        hit = self._cache.get(arch)
        if hit is None:
            from repro.configs import get_config
            from repro.models import build_model
            cfg = get_config(arch).reduced()
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            hit = self._cache[arch] = (cfg, model, params)
        return hit


@pytest.fixture(scope="session")
def reduced_zoo():
    return ReducedZoo()


@pytest.fixture(scope="session")
def sweep_engine():
    """One shared SweepEngine: memoizes full-size spec trees, parse
    tables, and component groups across every predictor/parity test.
    Cached cells are byte-identical to cold evaluation by construction
    (asserted by test_sweep_cache_hits_are_identical_to_cold)."""
    from repro.core.sweep import SweepEngine
    return SweepEngine()


@pytest.fixture(scope="session")
def zoo_rows(sweep_engine):
    """Memoized full-size (cfg, model, rows) per (arch, policy) — the
    parse tables the partitioner/factor tests walk."""
    from repro.core.spec import FULL_TRAIN

    def get(arch, policy=FULL_TRAIN):
        return sweep_engine._arch_state(arch, policy)

    return get
