import os

# APPEND the host-platform device-count flag (must happen before the jax
# import below); a user-supplied XLA_FLAGS is preserved, and an existing
# device-count setting wins over ours.
_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count=512"
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        filter(None, [os.environ.get("XLA_FLAGS", ""),
                      _DEVICE_COUNT_FLAG]))

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes with ShapeDtypeStruct stand-ins (no allocation), record
XLA memory/cost/collective analysis AND the paper-framework's memory
prediction side by side.

    python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
    python -m repro.launch.dryrun --all            # every cell, subprocesses
    python -m repro.launch.dryrun --all --multi-pod

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json
(the same directory repro.calibrate's MeasurementStore ingests by
default) and are consumed by benchmarks/ and EXPERIMENTS.md.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import SHAPES, cells, get_config, skipped_cells
from repro.core import factors as FA
from repro.core import predictor as PR
from repro.core import xla_metrics as XM
from repro.core.spec import FULL_TRAIN
from repro.launch import mesh as M
from repro.launch.train import train_program
from repro.mesh_ctx import mesh_axis_sizes, mesh_context
from repro.models import build_model

from repro.calibrate.paths import dryrun_dir

# pathlib repo-root resolution shared with the calibration MeasurementStore
# (write side and ingest side can never disagree on the artifact home)
OUT_DIR = str(dryrun_dir())


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = get_config(arch)
    model = build_model(cfg)
    return model.batch_spec(SHAPES[shape_name])


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               rules_override=None, remat=None):
    """Lower + compile one cell; returns (record, compiled).  A train
    cell compiles the launcher's step (:func:`train_program`)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    mesh = M.make_production_mesh(multi_pod=multi_pod)
    rules = {**M.arch_rules(cfg, shape.kind), **(rules_override or {})}
    remat = remat or cfg.remat

    with mesh_context(mesh, rules):
        psh = M.param_shardings(model, mesh)
        if shape.kind == "train":
            init, step, _ = train_program(model, shape, mesh, remat=remat,
                                          grad_accum=1)
            state = jax.eval_shape(init,
                                   jax.ShapeDtypeStruct((2,), jnp.uint32))
            lowered = step.lower(state, model.batch_spec(shape))
        elif shape.kind == "prefill":
            batch = model.batch_spec(shape)
            bsh = M.batch_shardings(mesh, batch)
            fn = lambda p, b: model.prefill(p, b)
            jitted = jax.jit(fn, in_shardings=(psh, bsh))
            lowered = jitted.lower(model.param_specs(), batch)
        else:  # decode
            B = shape.global_batch
            if cfg.family == "encdec":
                cache = jax.eval_shape(
                    lambda: model.init_cache(B, shape.seq_len,
                                             enc_len=shape.seq_len))
            else:
                cache = jax.eval_shape(
                    lambda: model.init_cache(B, shape.seq_len))
            csh = M.cache_shardings(mesh, cache, cfg)
            token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            from repro.mesh_ctx import resolve_pspec
            tsh = NamedSharding(mesh, resolve_pspec((B, 1), ("batch", None),
                                                    mesh))
            fn = lambda p, t, c: model.decode_step(p, t, c)
            jitted = jax.jit(fn, in_shardings=(psh, tsh, csh),
                             donate_argnums=(2,))
            lowered = jitted.lower(model.param_specs(), token, cache)

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    n_dev = mesh.devices.size
    mem = XM.memory_stats(compiled)
    cost = XM.cost_stats(compiled)
    hlo_txt = compiled.as_text()
    coll = XM.collective_stats(hlo_txt, n_dev)
    # loop-aware accounting: XLA cost_analysis counts while bodies ONCE;
    # these numbers multiply by trip counts (scan-stacked layers, flash
    # chunk loops, chunked losses) — the roofline reads THESE.
    la = XM.loop_aware_stats(hlo_txt, n_dev)

    # the paper framework's prediction for the same cell
    ctx = FA.PredictContext(
        mesh_shape=mesh_axis_sizes(mesh), rules=rules,
        optimizer=cfg.optimizer, fsdp=cfg.fsdp,
        master_fp32=cfg.optimizer != "adafactor",
        remat=remat,
        global_batch=shape.global_batch, seq_len=shape.seq_len,
        enc_seq=int(shape.seq_len * cfg.encdec.enc_seq_ratio)
        if cfg.encdec else 0,
        kind=shape.kind, max_len=shape.seq_len)
    pred = PR.predict(model, FULL_TRAIN, ctx)

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mesh_shape": mesh_axis_sizes(mesh),
        "n_devices": n_dev, "kind": shape.kind,
        "compile_seconds": round(compile_s, 2),
        "memory": {
            "argument_bytes": mem.argument_bytes,
            "output_bytes": mem.output_bytes,
            "temp_bytes": mem.temp_bytes,
            "alias_bytes": mem.alias_bytes,
            "total_bytes": mem.total_bytes,
        },
        "predicted": {
            "param_bytes": pred.param_bytes,
            "grad_bytes": pred.grad_bytes,
            "opt_bytes": pred.opt_bytes,
            "act_saved_bytes": pred.act_saved_bytes,
            "act_transient_bytes": pred.act_transient_bytes,
            "loss_bytes": pred.loss_bytes,
            "input_bytes": pred.input_bytes,
            "cache_bytes": pred.cache_bytes,
            "peak_bytes": pred.peak_bytes,
        },
        "cost": {"flops_per_device": cost.flops,
                 "bytes_accessed_per_device": cost.bytes_accessed},
        "collectives": {
            "counts": coll.counts,
            "operand_bytes_per_device": coll.operand_bytes,
            "wire_bytes_per_device": coll.wire_bytes,
            "total_wire_bytes_per_device": coll.total_wire_bytes,
        },
        "loop_aware": {
            "flops_per_device": la.flops,
            "bytes_accessed_per_device": la.bytes_accessed,
            "collective_counts": la.collectives.counts,
            "collective_wire_bytes": la.collectives.wire_bytes,
            "total_wire_bytes_per_device":
                la.collectives.total_wire_bytes,
        },
    }
    return record, compiled


def run_cell(arch, shape_name, multi_pod, out_dir) -> dict:
    record, compiled = lower_cell(arch, shape_name, multi_pod)
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    print({k: v for k, v in sorted(ca.items())
           if k in ("flops", "bytes accessed")})
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir,
                      f"{arch}__{shape_name}__{record['mesh']}.json")
    with open(fn, "w") as f:
        json.dump(record, f, indent=1)
    gib = 1024 ** 3
    print(f"[dryrun] {arch} x {shape_name} x {record['mesh']}: "
          f"OK compile={record['compile_seconds']}s "
          f"xla_total={record['memory']['total_bytes'] / gib:.2f} GiB "
          f"pred={record['predicted']['peak_bytes'] / gib:.2f} GiB "
          f"colls={record['collectives']['counts']}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(OUT_DIR))
    args = ap.parse_args()

    if args.all:
        pods = [False, True] if args.both_meshes else [args.multi_pod]
        failures = []
        for arch, shape_name in cells():
            for mp in pods:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out", args.out] + (["--multi-pod"] if mp else [])
                # each child compiles on XLA:CPU's forced host devices;
                # pinning the platform keeps it off any attached chip
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   env={**os.environ,
                                        "JAX_PLATFORMS": "cpu"})
                tail = (r.stdout + r.stderr).strip().splitlines()
                print(tail[-1] if tail else "(no output)")
                if r.returncode != 0:
                    failures.append((arch, shape_name, mp,
                                     "\n".join(tail[-15:])))
        for a, s, mp, err in failures:
            print(f"FAILED: {a} x {s} multi_pod={mp}\n{err}\n")
        for a, s, why in skipped_cells():
            print(f"SKIPPED: {a} x {s}: {why}")
        sys.exit(1 if failures else 0)

    run_cell(args.arch, args.shape, args.multi_pod, args.out)


if __name__ == "__main__":
    main()
