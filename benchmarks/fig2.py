"""Paper reproduction: Fig. 2a / 2b — LLaVA-1.5 (7B) peak-memory prediction
accuracy across data-parallel degrees 1..8, two hyper-parameter settings:

  fig2a: SeqLen 1024, micro-batch 16/GPU   (paper: avg MAPE 13%)
  fig2b: SeqLen 2048, micro-batch  8/GPU   (paper: avg MAPE 8.7%)

Protocol mirrors the paper §4: LLaVA-1.5-7B (frozen CLIP ViT-L/14 tower +
projector + Vicuna-7B, stage-2 behaviour), ZeRO-2 (grads reduce-scattered,
Adam states sharded over DP; params replicated), DP swept 1..8.  Ground
truth is the compiled-XLA per-device peak (the quantity whose overflow is
the OoM the paper prevents); each DP degree compiles on XLA:CPU in a
subprocess with that many forced host devices, so these are compiler
numbers, never chip measurements.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import EXP_DIR, GiB, mape

_CELL_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={dp}"
import json
import jax, jax.numpy as jnp
from repro.configs import ShapeConfig, get_config
from repro.core import factors as FA, predictor as PR, xla_metrics as XM
from repro.core.spec import LLAVA_STAGE2
from repro.launch import mesh as M
from repro.mesh_ctx import mesh_context
from repro.models import build_model, param as PM
from repro.train import OptimizerConfig, TrainState, make_train_step
from repro.train.optimizer import opt_state_specs

dp, seq, mbs = {dp}, {seq}, {mbs}
cfg = get_config("llava15-7b")
model = build_model(cfg)
shape = ShapeConfig("paper", seq, mbs * dp, "train")
mesh = jax.make_mesh((dp, 1), ("data", "model"))
opt_cfg = OptimizerConfig(name="adamw")

with mesh_context(mesh, M.arch_rules(cfg)):
    params = model.param_specs()
    mask = PM.trainable_mask(model.spec, LLAVA_STAGE2)
    tr, _ = PM.partition_params(params, mask)
    opt = opt_state_specs(tr, opt_cfg)
    state = TrainState(params=params, opt=opt,
                       step=jax.ShapeDtypeStruct((), jnp.int32))
    axes_tree = model.param_axes()
    t_axes = jax.tree.map(lambda m, ax: ax if m else None, mask, axes_tree)
    t_specs, _ = PM.partition_params(params, mask)
    zsh = M.zero_grad_shardings(mesh, t_specs, t_axes)       # ZeRO-2
    osh = M.opt_shardings(model, mesh, t_specs, opt_cfg, t_axes)
    psh = M.param_shardings(model, mesh)
    batch = model.batch_spec(shape)
    bsh = M.batch_shardings(mesh, batch)
    step = make_train_step(model, LLAVA_STAGE2, opt_cfg, zero_shardings=zsh)
    state_sh = TrainState(params=psh, opt=osh,
                          step=jax.sharding.NamedSharding(
                              mesh, jax.sharding.PartitionSpec()))
    lowered = jax.jit(step, in_shardings=(state_sh, bsh),
                      donate_argnums=(0,)).lower(state, batch)
    compiled = lowered.compile()

mem = XM.memory_stats(compiled)
ctx = FA.PredictContext(mesh_shape={{"data": dp}}, rules=M.arch_rules(cfg),
                        optimizer="adamw", zero=True, backend="cpu",
                        global_batch=mbs * dp, seq_len=seq, kind="train",
                        remat=cfg.remat)
pred = PR.predict(model, LLAVA_STAGE2, ctx)
print("RESULT " + json.dumps({{
    "dp": dp, "seq": seq, "mbs": mbs,
    "actual_bytes": mem.total_bytes,
    "predicted_bytes": pred.peak_bytes,
    "pred_parts": {{"param": pred.param_bytes, "grad": pred.grad_bytes,
                   "opt": pred.opt_bytes, "act_saved": pred.act_saved_bytes,
                   "act_trans": pred.act_transient_bytes,
                   "loss": pred.loss_bytes, "inputs": pred.input_bytes}},
    "mem_parts": {{"args": mem.argument_bytes, "out": mem.output_bytes,
                  "temp": mem.temp_bytes, "alias": mem.alias_bytes}},
}}))
"""


def run_cell(dp: int, seq: int, mbs: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"          # an XLA:CPU compile, never a chip
    code = _CELL_CODE.format(dp=dp, seq=seq, mbs=mbs)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1800)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"fig2 cell dp={dp} seq={seq} failed:\n"
                       f"{r.stdout[-2000:]}\n{r.stderr[-3000:]}")


def run_setting(name: str, seq: int, mbs: int, dps=(1, 2, 4, 8),
                verbose: bool = True) -> dict:
    rows = [run_cell(dp, seq, mbs) for dp in dps]
    result = {
        "setting": name, "seq": seq, "mbs": mbs, "rows": rows,
        "mape": mape([(r["predicted_bytes"], r["actual_bytes"])
                      for r in rows]),
    }
    if verbose:
        print(f"\n=== {name}: LLaVA-1.5-7B, SeqLen {seq}, MBS {mbs}, "
              f"ZeRO-2 (paper protocol) ===")
        print(f"{'DP':>4s}{'pred GiB':>10s}{'actual GiB':>12s}{'APE%':>8s}")
        for r in rows:
            ape = 100 * abs(r["predicted_bytes"] - r["actual_bytes"]) \
                / r["actual_bytes"]
            print(f"{r['dp']:>4d}{r['predicted_bytes']/GiB:>10.2f}"
                  f"{r['actual_bytes']/GiB:>12.2f}{ape:>8.1f}")
        print(f"MAPE {name}: {result['mape']:.1f}%  "
              f"(paper: {'13%' if name == 'fig2a' else '8.7%'})")
    os.makedirs(EXP_DIR, exist_ok=True)
    with open(os.path.join(EXP_DIR, f"{name}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def run(verbose: bool = True) -> dict:
    a = run_setting("fig2a", seq=1024, mbs=16, verbose=verbose)
    b = run_setting("fig2b", seq=2048, mbs=8, verbose=verbose)
    return {"fig2a": a, "fig2b": b}


if __name__ == "__main__":
    run()
