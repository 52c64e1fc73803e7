"""Training launcher: the paper's workflow end-to-end.

    python -m repro.launch.train --arch smollm-360m --seq-len 4096 \
        --global-batch 8 --steps 5
    python -m repro.launch.train --arch smollm-360m --seq-len 4096 \
        --global-batch 8 --check-only     # OoM guard only, then exit

Flow (:func:`launch`): predict the peak memory of the job that will run —
the (data, model) mesh built from the local devices, the chip those
devices are, the shape, and the (remat, grad_accum) the planner approves
— and refuse a doomed job; build the mesh and the production shardings
(params/optimizer/gradients/batch; ``launch/dryrun.py`` lowers the same
program);
compile the step once; drive it through the fault-tolerant trainer
(async checkpoints, restart, straggler mitigation).  The planned chip
comes from ``jax.devices()[0].device_kind``; a device kind the planner
does not know is an error.  ``--check-only`` plans the local devices
only; to check a target mesh from a host without its chips, use
``python -m repro.core.sweep`` or ``repro.core.planner.check``.
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Optional


class GuardRefused(RuntimeError):
    """The OoM guard predicts the job cannot fit its chip."""


def mesh_shape_for(n_devices: int, data: Optional[int] = None) -> dict:
    """The (data, model) mesh shape :func:`launch` builds over
    ``n_devices`` local devices: ``data`` devices on the data axis (all
    of them by default), the rest on the model axis."""
    data = n_devices if data is None else data
    if data < 1 or n_devices % data:
        raise ValueError(f"data={data} does not divide the {n_devices} "
                         f"local devices")
    return {"data": data, "model": n_devices // data}


def plan_job(arch: str, shape, n_devices: int, device_kind: str,
             data: Optional[int] = None):
    """The guard: ``(mesh_shape, chip, PlanReport)`` for the mesh that
    :func:`launch` builds on these devices.  The report's ``remat`` and
    ``grad_accum`` are the ones the run uses."""
    from repro.core import planner
    chip = planner.chip_of_device_kind(device_kind)
    mesh_shape = mesh_shape_for(n_devices, data)
    return mesh_shape, chip, planner.plan(arch, shape, mesh_shape,
                                          backend="tpu", chip=chip)


@dataclass
class TrainRun:
    """What one :func:`launch` did: the plan it ran, the compiled step,
    the final state and per-step history."""

    mesh_shape: dict
    chip: str
    report: Any                      # planner.PlanReport
    compiled: Any                    # the step's jax.stages.Compiled
    compile_seconds: float
    state: Any
    history: list
    restarts: int
    step_seconds: list               # host clock, per completed step


def train_program(model, shape, mesh, *, remat: str, grad_accum: int):
    """The launcher's programs on ``mesh`` (call under ``mesh_context``):
    ``(init, step, batch_shardings)`` — ``init(key)`` builds the sharded
    TrainState, ``step(state, batch)`` is the jitted, state-donating
    train step with the production param/optimizer/ZeRO-gradient/batch
    shardings.  ``launch/dryrun.py`` compiles this same step for its
    train cells."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.spec import FULL_TRAIN
    from repro.launch import mesh as M
    from repro.models import param as PM
    from repro.train import OptimizerConfig, TrainState, make_train_step
    from repro.train.optimizer import init_opt_state

    cfg = model.cfg
    opt_cfg = OptimizerConfig(name=cfg.optimizer,
                              master_fp32=cfg.optimizer != "adafactor")
    mask = PM.trainable_mask(model.spec, FULL_TRAIN)
    t_axes = jax.tree.map(lambda m, ax: ax if m else None, mask,
                          model.param_axes())
    t_specs, _ = PM.partition_params(model.param_specs(), mask)
    state_sh = TrainState(
        params=M.param_shardings(model, mesh),
        opt=M.opt_shardings(model, mesh, t_specs, opt_cfg, t_axes),
        step=NamedSharding(mesh, P()))
    bsh = M.batch_shardings(mesh, model.batch_spec(shape))

    def init_state(key):
        params = model.init(key)
        trainable, _ = PM.partition_params(params, mask)
        return TrainState(params=params,
                          opt=init_opt_state(trainable, opt_cfg),
                          step=jnp.zeros((), jnp.int32))

    step = jax.jit(
        make_train_step(model, FULL_TRAIN, opt_cfg, grad_accum=grad_accum,
                        zero_shardings=M.zero_grad_shardings(
                            mesh, t_specs, t_axes),
                        remat=remat),
        in_shardings=(state_sh, bsh), out_shardings=(state_sh, None),
        donate_argnums=(0,))
    return jax.jit(init_state, out_shardings=state_sh), step, bsh


def launch(arch: str, shape, *, steps: int, ckpt_dir: str,
           data: Optional[int] = None, devices=None,
           log_every: int = 0) -> TrainRun:
    """Guard -> mesh -> ResilientTrainer for ``steps`` steps of ``arch``
    at ``shape`` (a ShapeConfig or a SHAPES key) on ``devices`` (default:
    every local device).  Raises :class:`GuardRefused` when the planned
    job cannot fit."""
    import jax

    from repro.checkpoint import Checkpointer
    from repro.configs import get_config
    from repro.core import planner
    from repro.data.pipeline import SyntheticPipeline
    from repro.launch import mesh as M
    from repro.mesh_ctx import mesh_context
    from repro.models import build_model, param as PM
    from repro.runtime import FaultConfig, ResilientTrainer

    shape = planner._resolve_shape(shape)
    devices = list(devices or jax.devices())
    mesh_shape, chip, report = plan_job(arch, shape, len(devices),
                                        devices[0].device_kind, data)
    print(f"guard: {chip} x {mesh_shape}: {report}")
    if not report.fits:
        raise GuardRefused(f"OoM guard: refusing to launch a doomed job "
                           f"({report})")

    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = M.make_smoke_mesh(mesh_shape["data"], mesh_shape["model"],
                             devices)
    pipe = SyntheticPipeline(cfg, shape)

    with mesh_context(mesh, M.arch_rules(cfg)):
        init, step_fn, bsh = train_program(model, shape, mesh,
                                           remat=report.remat,
                                           grad_accum=report.grad_accum)
        state = init(jax.random.PRNGKey(0))

        def make_batch(s):
            return jax.device_put(pipe.global_batch(s), bsh)

        t0 = time.perf_counter()
        compiled = step_fn.lower(state, make_batch(0)).compile()
        compile_s = time.perf_counter() - t0
        print(f"launch: {cfg.name} "
              f"({PM.count_params(model.param_specs()) / 1e6:.1f}M params),"
              f" mesh={dict(mesh.shape)}, optimizer={cfg.optimizer}, "
              f"remat={report.remat}, grad_accum={report.grad_accum}, "
              f"compiled in {compile_s:.1f}s")

        trainer = ResilientTrainer(
            train_step=compiled, pipeline=pipe,
            checkpointer=Checkpointer(ckpt_dir, keep=3),
            fault_cfg=FaultConfig(ckpt_every=max(steps // 4, 10)),
            make_batch=make_batch)
        state, history = trainer.run(state, 0, steps, log_every=log_every)
    return TrainRun(mesh_shape=mesh_shape, chip=chip, report=report,
                    compiled=compiled, compile_seconds=compile_s,
                    state=state, history=history,
                    restarts=trainer.restarts,
                    step_seconds=trainer.step_seconds)


def main(argv=None):
    from repro.configs import SHAPES, ShapeConfig

    ap = argparse.ArgumentParser(prog="python -m repro.launch.train")
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--shape", choices=sorted(SHAPES),
                    help="a registered shape (default train_4k unless "
                         "--seq-len/--global-batch name one)")
    ap.add_argument("--seq-len", type=int)
    ap.add_argument("--global-batch", type=int)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--data", type=int, default=None,
                    help="data-axis size (default: every local device); "
                         "the model axis takes the rest")
    ap.add_argument("--check-only", action="store_true",
                    help="run the OoM guard for the local devices' "
                         "mesh and exit (a target mesh: python -m "
                         "repro.core.sweep)")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(),
                                         "repro_train"))
    args = ap.parse_args(argv)

    if (args.seq_len is None) != (args.global_batch is None):
        ap.error("--seq-len and --global-batch go together")
    if args.seq_len is not None:
        if args.shape:
            ap.error("--shape and --seq-len/--global-batch are exclusive")
        shape = ShapeConfig(f"train_s{args.seq_len}_b{args.global_batch}",
                            args.seq_len, args.global_batch, "train")
    else:
        shape = args.shape or "train_4k"

    import jax
    from repro.launch import compile_cache

    if args.check_only:
        devices = jax.devices()
        mesh_shape, chip, report = plan_job(
            args.arch, shape, len(devices), devices[0].device_kind,
            args.data)
        print(f"guard: {chip} x {mesh_shape}: {report}")
        return
    compile_cache.enable()
    try:
        run = launch(args.arch, shape, steps=args.steps,
                     ckpt_dir=args.ckpt_dir, data=args.data,
                     log_every=max(args.steps // 5, 1))
    except GuardRefused as e:
        raise SystemExit(str(e))
    print(f"done: loss {run.history[0]['loss']:.3f} -> "
          f"{run.history[-1]['loss']:.3f} over {args.steps} steps; "
          f"checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
