"""On-chip smoke test of the system's two device paths.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # one host with four chips

One chip, in one process, in this order:

1. Trainer (the predictor's ground truth): ``smollm-360m`` at its
   published config, seq 4096 x global batch 8, a few steps through
   :func:`repro.launch.train.launch` (guard -> mesh -> ResilientTrainer).
   Fails on a restart or a non-finite loss.  Prints the device's
   ``peak_bytes_in_use`` and ``bytes_limit``, the compiled step's
   ``memory_analysis``, the legacy and liveness predictions with their
   APE against the measured peak, and step times (information only).
2. Planning: the jitted columnar engine (``engine="jax"``) on the
   benchmark's ``smoke`` (ep x cp x pp), ``serve`` (paged decode) and
   ``large`` (liveness assembly) grids, plus one jax-engine
   ``min_chips_search``.  Every result column must be byte-identical to
   the numpy engine run on the host, and the composition's outputs must
   sit on a TPU device.

``--chips 4`` runs only the sharded trainer on a {data: 2, model: 2}
mesh and, through the same launcher, the same steps on device 0 alone.
It prints every device's peak against the predicted per-device peak,
fails unless each device holds well under the one-device job's memory
(the state is spread over the mesh), and compares the first loss, the
first gradient norm and the loss after two updates with the one-device
run.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  Without a TPU the script
exits non-zero, naming the platform it found.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

GiB = 1024 ** 3
ARCH = "smollm-360m"
SEQ_LEN, GLOBAL_BATCH = 4096, 8
TRAIN_STEPS = 4

#: sharded vs one-device relative bounds.  The sharded step reorders
#: bf16 reductions, and the per-token differences mostly cancel in the
#: mean over 32,768 tokens.  On four v5e the relative differences were
#: 2.3e-6 (step-1 loss), 8.8e-5 (step-1 gradient norm) and 1.4e-6
#: (step-3 loss).  The bounds sit well above that and below what a wrong
#: batch or a wrong gradient reduction moves: the one-device losses of
#: consecutive steps differ by 2e-4 to 7e-4, their gradient norms by 6%.
LOSS_RTOL = 1e-4
GRAD_NORM_RTOL = 1e-3
#: {data: 2, model: 2} spreads the state four ways (about 0.29 of the
#: one-device job per device on a v5e); state replicated over either
#: axis would hold at least half
MAX_SHARD_SHARE = 0.4


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU devices, found "
                         f"{len(devs)}")
    return devs


def train_shape():
    from repro.configs import ShapeConfig
    return ShapeConfig(f"train_s{SEQ_LEN}_b{GLOBAL_BATCH}", SEQ_LEN,
                       GLOBAL_BATCH, "train")


def run_trainer(steps: int, data=None, devices=None):
    """Launch the trainer; fail on a restart or a non-finite loss."""
    from repro.launch.train import launch
    with tempfile.TemporaryDirectory() as ckpt:
        run = launch(ARCH, train_shape(), steps=steps, ckpt_dir=ckpt,
                     data=data, devices=devices, log_every=1)
    losses = [h["loss"] for h in run.history]
    log(f"trainer: {len(losses)} steps on {run.mesh_shape}, restarts "
        f"{run.restarts}, losses {losses}")
    log(f"trainer: compile {run.compile_seconds:.1f}s, step seconds "
        f"(host clock, to the metrics on the host) {run.step_seconds}")
    if run.restarts or len(losses) != steps \
            or not all(math.isfinite(v) for v in losses):
        raise SystemExit("chip_smoke: trainer restarted or produced a "
                         "non-finite loss")
    return run


def peak_and_limit(dev) -> tuple:
    stats = dev.memory_stats()
    log(f"memory: device {dev.id} memory_stats {stats}")
    return stats["peak_bytes_in_use"], stats["bytes_limit"]


def ape(pred: int, measured: int) -> float:
    return abs(pred - measured) / measured * 100.0


def trainer_phase() -> None:
    import jax
    from repro.core import planner
    from repro.core.xla_metrics import memory_stats

    run = run_trainer(TRAIN_STEPS)
    peak, limit = peak_and_limit(jax.devices()[0])
    ma = memory_stats(run.compiled)
    r = run.report
    live = planner.check(ARCH, train_shape(), run.mesh_shape,
                         chip=run.chip, grad_accum=r.grad_accum,
                         remat=r.remat, assembly="liveness")
    log(f"memory: peak_bytes_in_use {peak} ({peak / GiB:.3f} GiB), "
        f"bytes_limit {limit} ({limit / GiB:.3f} GiB)")
    log(f"memory: compiled step memory_analysis total {ma.total_bytes} "
        f"({ma.total_bytes / GiB:.3f} GiB): arguments {ma.argument_bytes}"
        f", outputs {ma.output_bytes}, temporaries {ma.temp_bytes}, "
        f"aliased {ma.alias_bytes}")
    for name, pred in (("legacy", r.peak_bytes),
                       ("liveness", live.peak_bytes)):
        log(f"memory: predicted {name} peak {pred} "
            f"({pred / GiB:.3f} GiB), APE vs peak_bytes_in_use "
            f"{ape(pred, peak):.2f}%, vs memory_analysis "
            f"{ape(pred, ma.total_bytes):.2f}%")


def columns_mismatch(ref, got) -> int:
    """Cells whose result columns differ in any byte (all cells when the
    per-grid metadata differs)."""
    import numpy as np
    a, b = ref.columns, got.columns
    bad = np.zeros(a.n, bool)
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) and isinstance(y, np.ndarray) \
                and x.shape == y.shape == (a.n,) and x.dtype == y.dtype:
            bad |= x != y
        elif isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            if not (isinstance(x, np.ndarray) and isinstance(y, np.ndarray)
                    and x.dtype == y.dtype and np.array_equal(x, y)):
                bad[:] = True
        elif x != y:
            bad[:] = True
    return int(bad.sum())


def planning_grids():
    from benchmarks.sweep_throughput import build_grid
    return [("smoke", build_grid("smoke")),
            ("serve", build_grid("serve")),
            ("large/liveness",
             dataclasses.replace(build_grid("large"), assembly="liveness"))]


def planning_phase() -> set:
    """Run every grid on both engines; returns the platforms the jitted
    composition's outputs were found on."""
    import jax
    from repro.core import batch_jax as BJ
    from repro.core import search as SR
    from repro.core import sweep as SW

    platforms: set = set()
    compose_fn = BJ._compose_fn

    def observed_compose_fn():
        fn = compose_fn()

        def compose(*args, **kwargs):
            out = fn(*args, **kwargs)
            for leaf in jax.tree.leaves(out):
                platforms.update(d.platform for d in leaf.devices())
            return out
        return compose

    BJ._compose_fn = observed_compose_fn
    try:
        for name, grid in planning_grids():
            t0 = time.perf_counter()
            ref = SW.SweepEngine().sweep(grid, engine="numpy")
            t_np = time.perf_counter() - t0
            eng = SW.SweepEngine()
            timed = []
            for leg in ("cold", "warm"):
                t0 = time.perf_counter()
                got = eng.sweep(grid, engine="jax")
                timed.append((leg, time.perf_counter() - t0, got))
            bad = [columns_mismatch(ref, got) for _, _, got in timed]
            log(f"planning: {name}: {len(ref)} cells, mismatching cells "
                f"cold {bad[0]} warm {bad[1]}; seconds numpy(host) "
                f"{t_np:.3f}, jax cold {timed[0][1]:.3f}, warm "
                f"{timed[1][1]:.3f}")
            if any(bad) or len(ref) == 0:
                raise SystemExit(f"chip_smoke: {name} grid differs from "
                                 f"the numpy engine")

        grid = SW.SweepGrid(
            arch="qwen3-32b", chips=(8, 16, 32, 64, 128, 256, 512, 1024),
            mesh_axes=("data", "model", "pipe"), max_axis={"pipe": 8},
            microbatches=(1, 4, 8), schedules=("1f1b", "gpipe"),
            global_batches=(16,), seq_lens=(4096,), kind="train",
            chip="v5e", backend="tpu")
        t0 = time.perf_counter()
        best = SR.min_chips_search(grid, engine=SW.SweepEngine(),
                                   compute_engine="jax", oracle=True)
        t_jax = time.perf_counter() - t0
        ref = SR.min_chips_search(grid, engine=SW.SweepEngine(),
                                  compute_engine="numpy", oracle=True)
        log(f"planning: min_chips_search[qwen3-32b] jax {best}; "
            f"identical to numpy: {best == ref}; jax seconds {t_jax:.3f}")
        if best is None or best != ref:
            raise SystemExit("chip_smoke: jax min_chips_search differs "
                             "from numpy")
    finally:
        BJ._compose_fn = compose_fn
    return platforms


def one_chip() -> None:
    import jax
    trainer_phase()
    platforms = planning_phase()
    log(f"planning: default backend {jax.default_backend()}, jitted "
        f"composition outputs on {sorted(platforms)}")
    if jax.default_backend() != "tpu" or platforms != {"tpu"}:
        raise SystemExit("chip_smoke: the jitted composition did not run "
                         "on the TPU")


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def four_chips(devs) -> None:
    from repro.core.xla_metrics import memory_stats

    steps = 3
    run = run_trainer(steps, data=2)
    # read before the one-device run raises device 0's lifetime peak
    peaks = [peak_and_limit(d)[0] for d in devs]
    hist, plan = run.history, run.report
    args = memory_stats(run.compiled).argument_bytes
    del run
    one = run_trainer(steps, devices=devs[:1])
    one_peak = peak_and_limit(devs[0])[0]
    one_args = memory_stats(one.compiled).argument_bytes
    if (one.report.remat, one.report.grad_accum) \
            != (plan.remat, plan.grad_accum):
        raise SystemExit("chip_smoke: the one-device plan differs from the "
                         "sharded plan; the runs are not comparable")

    pred = plan.peak_bytes
    for d, p in zip(devs, peaks):
        log(f"sharded: device {d.id} peak_bytes_in_use {p} "
            f"({p / GiB:.3f} GiB) = {p / one_peak:.3f} of the one-device "
            f"peak {one_peak}; APE vs predicted per-device peak {pred} "
            f"({pred / GiB:.3f} GiB) {ape(pred, p):.2f}%")
    log(f"sharded: step arguments {args} B per device = "
        f"{args / one_args:.3f} of one device's {one_args} B")
    if max(peaks) > MAX_SHARD_SHARE * one_peak \
            or args > MAX_SHARD_SHARE * one_args:
        raise SystemExit(f"chip_smoke: a device holds more than "
                         f"{MAX_SHARD_SHARE} of the one-device job; the "
                         f"state is not spread over the mesh")

    checks = [("loss step 1", hist[0]["loss"], one.history[0]["loss"],
               LOSS_RTOL),
              ("grad_norm step 1", hist[0]["grad_norm"],
               one.history[0]["grad_norm"], GRAD_NORM_RTOL),
              (f"loss step {steps}", hist[-1]["loss"],
               one.history[-1]["loss"], LOSS_RTOL)]
    for name, got, ref, rtol in checks:
        log(f"sharded: {name} {got!r} on {{data: 2, model: 2}} vs {ref!r} "
            f"on one device: relative diff {rel_diff(got, ref):.3e}, bound "
            f"{rtol:.0e}")
    if not all(math.isfinite(ref) and rel_diff(got, ref) <= rtol
               for _, got, ref, rtol in checks):
        raise SystemExit("chip_smoke: the sharded run differs from the "
                         "one-device run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.launch import compile_cache
    log(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, compile cache "
        f"{compile_cache.enable()}")
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
