"""The paper's workflow as a CLI: parse -> factorize -> predict -> verdict.

    PYTHONPATH=src python examples/predict_memory.py --arch qwen3-32b \\
        --shape train_4k --data 16 --model 16 [--validate]

``--validate`` additionally compiles the same cell with XLA (CPU oracle)
and reports the prediction error — the paper's evaluation, one cell at a
time.
"""

import argparse

GiB = 1024 ** 3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--data", type=int, default=16)
    ap.add_argument("--model", type=int, default=16)
    ap.add_argument("--policy", default="full",
                    choices=["full", "llava_stage1", "llava_stage2"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    ap.add_argument("--hbm-gib", type=float, default=16.0)
    ap.add_argument("--validate", action="store_true")
    args = ap.parse_args()

    from repro.configs import SHAPES, get_config
    from repro.core import factors as FA
    from repro.core import predictor as PR
    from repro.core.parser import parse_model, modules_of, total_params
    from repro.core.spec import (FULL_TRAIN, LLAVA_STAGE1, LLAVA_STAGE2)
    from repro.launch import mesh as M
    from repro.models import build_model

    policy = {"full": FULL_TRAIN, "llava_stage1": LLAVA_STAGE1,
              "llava_stage2": LLAVA_STAGE2}[args.policy]
    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    model = build_model(cfg)

    # workflow step 1-4: parse into modules and fine-grained layers
    rows = parse_model(model.spec, policy)
    mods = modules_of(rows)
    print(f"parsed {args.arch}: {len(mods)} modules, {len(rows)} layer "
          f"kinds, {total_params(rows) / 1e9:.2f}B params")

    # step 5-6: factorize + per-factor equations; step 7: aggregate (Eq.1)
    mesh_shape = {"data": args.data, "model": args.model}
    ctx = FA.PredictContext(
        mesh_shape=mesh_shape, rules=M.arch_rules(cfg, shape.kind),
        optimizer=cfg.optimizer, fsdp=cfg.fsdp, remat=cfg.remat,
        master_fp32=cfg.optimizer != "adafactor",
        global_batch=shape.global_batch, seq_len=shape.seq_len,
        enc_seq=int(shape.seq_len * cfg.encdec.enc_seq_ratio)
        if cfg.encdec else 0,
        kind=shape.kind, max_len=shape.seq_len,
        grad_accum=args.grad_accum, backend=args.backend)
    pred = PR.predict(model, policy, ctx)

    print(f"\nper-device prediction ({args.backend} oracle, mesh "
          f"data={args.data} x model={args.model}):")
    print(pred.summary())
    budget = args.hbm_gib * GiB * 0.92
    print(f"\nverdict: {'FITS' if pred.peak_bytes <= budget else 'OOM'} "
          f"on a {args.hbm_gib:.0f} GiB chip "
          f"({pred.peak_bytes / GiB:.2f} vs budget {budget / GiB:.2f} GiB)")

    if args.validate:
        import os
        import subprocess
        import sys
        n_dev = args.data * args.model
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        env["JAX_PLATFORMS"] = "cpu"      # an XLA:CPU compile, never a chip
        code = f"""
import jax
from repro.launch.dryrun import lower_cell
record, compiled = lower_cell({args.arch!r}, {args.shape!r})
print("XLA_TOTAL", record["memory"]["total_bytes"])
"""
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env)
        for line in r.stdout.splitlines():
            if line.startswith("XLA_TOTAL"):
                actual = int(line.split()[1])
                cpu_ctx = FA.PredictContext(**{
                    **ctx.__dict__, "backend": "cpu"})
                cpu_pred = PR.predict(model, policy, cpu_ctx)
                err = abs(cpu_pred.peak_bytes - actual) / actual * 100
                print(f"\nvalidation vs compiled XLA (cpu oracle): "
                      f"predicted {cpu_pred.peak_bytes / GiB:.2f} GiB, "
                      f"actual {actual / GiB:.2f} GiB, APE {err:.1f}%")
                return
        print("validation failed:", r.stderr[-500:])


if __name__ == "__main__":
    main()
