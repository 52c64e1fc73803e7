"""Compile-only checks for one described TPU v5e chip: the TPU compiler
(installed without a chip) must accept the programs the system runs on
the device — every variant of the jitted columnar composition at int64
table sizes, and the launcher's full-width smollm-360m train step, whose
compiled footprint must fit the chip's HBM and whose causal attention
must keep its tile skip as a branch.  Nothing runs; a compile that
passes is not a chip measurement.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker
imports this file, so only the worker that runs these tests may load it.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

# the composition's table sizes: stages x meshes x codes, and its inner
# (knob-tuple) cell count
STAGES, MESHES, CODES, INNER = 4, 64, 512, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a described chip's executables cannot be read back from the
        # persistent cache without a chip; keep these compiles out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        from jax.experimental import topologies
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _i64(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int64, sharding=one_chip)


# gathered tables per variant; every table is (STAGES, MESHES, CODES)
# except the per-stage scalars/vectors listed with explicit shapes
_VARIANTS = {
    "legacy": dict(kind="train", serve=False, off=False,
                   tabs=("aff", "b", "base")),
    "serve": dict(kind="decode", serve=True, off=False,
                  tabs=("aff", "b", "base", "pool", "drf", "hit")),
    "offload": dict(kind="train", serve=False, off=True,
                    tabs=("aff", "b", "base", "ho")),
    "liveness": dict(kind="train", serve=False, off=False,
                     tabs=("aff", "b", "inp", "cch", "lss", "bd", "tr",
                           "otr"),
                     extra={"emb": (STAGES,), "ocp": (STAGES, MESHES)}),
}


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_compose_compiles_for_v5e(one_chip, variant):
    from repro.core import batch_jax as BJ
    v = _VARIANTS[variant]
    assembly = "liveness" if variant == "liveness" else "legacy"
    with jax.enable_x64(True):
        tabs = {k: _i64(one_chip, STAGES, MESHES, CODES) for k in v["tabs"]}
        tabs.update({k: _i64(one_chip, *s)
                     for k, s in v.get("extra", {}).items()})
        idx = tuple(_i64(one_chip, INNER) for _ in range(5))
        carry0 = tuple(_i64(one_chip, MESHES, INNER) for _ in range(6))
        compiled = BJ._compose_fn().lower(
            carry0, tabs, idx, has_profile=False, serve=v["serve"],
            off=v["off"], assembly=assembly, kind=v["kind"]).compile()
    # the carry comes back whole: six int64 (meshes, inner) buffers
    out = jax.tree.leaves(compiled.out_info)
    assert [(o.shape, o.dtype) for o in out] \
        == [((MESHES, INNER), jnp.int64)] * 6


def test_smollm_train_step_fits_v5e(topo):
    """The launcher's smollm-360m step at its published widths and full
    depth, seq 4096 x global batch 8, on a one-chip (1, 1) mesh."""
    from jax.sharding import Mesh

    from repro.configs import ShapeConfig, get_config
    from repro.core.planner import CHIPS
    from repro.launch import mesh as M
    from repro.launch.train import train_program
    from repro.mesh_ctx import mesh_context
    from repro.models import build_model

    cfg = get_config("smollm-360m")
    model = build_model(cfg)
    shape = ShapeConfig("train_s4096_b8", 4096, 8, "train")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    with mesh_context(mesh, M.arch_rules(cfg)):
        init, step, _ = train_program(model, shape, mesh,
                                      remat=cfg.remat, grad_accum=1)
        state = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        compiled = step.lower(state, model.batch_spec(shape)).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < CHIPS["v5e"].hbm_bytes
    # the causal tile skip stays a branch in each attention pass (forward,
    # remat forward, backward): a select would run the dead tiles too
    conds = re.findall(r'= .* conditional\(.*op_name="([^"]*)"',
                       compiled.as_text())
    assert len(conds) == 3 and all("/attn/" in n for n in conds), conds
