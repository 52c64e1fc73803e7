"""Plain reference of the memory planner: one cell at a time.

A frozen copy of the program's per-cell path (``planner.check`` ->
``predictor.predict`` over the spec tree, with the Eq. 1 factors and the
liveness assembly), taken when the benchmark was defined and importing
nothing of the program.  It evaluates each cell alone, with no columns,
tables, caches shared between cells or device code, so it answers what
the jitted columnar engine (``engine="jax"``) has to answer, and a later
change to that engine cannot change it.  The architecture comes from the
configuration file's published keys (``arch.from_config``), not from the
program's registry.

``Planner.cell`` is one cell's verdict.  ``min_chips`` and ``frontier``
answer the planning queries by plain enumeration: every legal mesh of
every chip count, in order, until the answer is settled.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

from . import factors as F
from . import predictor as PR
from .arch import ArchConfig, ShapeConfig, from_config
from .parser import parse_model
from .spec import FULL_TRAIN
from .specs import (arch_rules, cp_degree, enumerate_meshes, ep_degree,
                    lm_spec, pp_degree)
from .stages import SCHEDULES

GiB = 1024 ** 3
#: per-device HBM of the chips the cells plan for
CHIPS = {"v5e": 16 * GiB, "v5p": 95 * GiB, "v6e": 32 * GiB,
         "h100": 80 * GiB}
#: the planner plans against this share of a chip's HBM
HEADROOM = 0.92


@dataclass
class Model:
    """What the predictor reads of a model: its spec tree and the shapes
    of its training batch (token ids and labels, int32)."""

    cfg: ArchConfig
    spec: object

    def batch_spec(self, shape: ShapeConfig) -> dict:
        import jax
        import jax.numpy as jnp
        tok = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                   jnp.int32)
        if shape.kind == "decode":
            return {"token": jax.ShapeDtypeStruct((shape.global_batch, 1),
                                                  jnp.int32)}
        out = {"tokens": tok, "labels": tok}
        if shape.kind == "prefill":
            out.pop("labels")
        return out


def legal(cfg: ArchConfig, mesh: dict, kind: str, seq_len: int) -> bool:
    """Whether the expert and context axes can run this architecture and
    step (the program's ``planner.check_parallel``)."""
    ep, cp = ep_degree(mesh), cp_degree(mesh)
    if ep > 1 and (cfg.moe is None or ep > cfg.moe.n_experts
                   or cfg.moe.n_experts % ep):
        return False
    if cp > 1 and (kind == "decode" or seq_len % cp):
        return False
    return True


@dataclass(frozen=True)
class Verdict:
    n_chips: int
    peak_bytes: int
    budget_bytes: int
    fits: bool
    overlap_slack_bytes: int


class Planner:
    """``int32=True`` is the control: every peak held in a 32-bit signed
    integer, the narrower integer below the int64 the engine computes
    in (byte counts above 2**31 wrap)."""

    def __init__(self, name: str, config: dict, int32: bool = False):
        self.int32 = int32
        self.cfg = from_config(name, config)
        self.model = Model(self.cfg, lm_spec(self.cfg))
        self.rows = parse_model(self.model.spec, FULL_TRAIN)

    def cell(self, mesh: dict, *, kind: str, global_batch: int,
             seq_len: int, chip: str = "v5e", backend: str = "tpu",
             grad_accum: int = 1, remat: Optional[str] = None,
             optimizer: Optional[str] = None, microbatches: int = 1,
             schedule: str = "1f1b", assembly: str = "legacy") -> Verdict:
        if schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {schedule!r}")
        if not legal(self.cfg, mesh, kind, seq_len):
            raise ValueError(f"illegal mesh {mesh} for {kind}")
        cfg = self.cfg
        opt = optimizer or cfg.optimizer
        ctx = F.PredictContext(
            mesh_shape=mesh, rules=arch_rules(cfg, kind), optimizer=opt,
            fsdp=cfg.fsdp, master_fp32=opt != "adafactor",
            remat=remat or cfg.remat, backend=backend,
            global_batch=global_batch, seq_len=seq_len, enc_seq=0,
            kind=kind, max_len=seq_len, grad_accum=grad_accum,
            pp=pp_degree(mesh), microbatches=microbatches,
            schedule=schedule, serve=None, offload_opt=False)
        pred = PR.predict(self.model, FULL_TRAIN, ctx, rows=self.rows,
                          assembly=assembly)
        budget = int(CHIPS[chip] * HEADROOM)
        peak = pred.peak_bytes
        if self.int32:
            peak = (peak + 2 ** 31) % 2 ** 32 - 2 ** 31
        return Verdict(n_chips=math.prod(mesh.values()),
                       peak_bytes=peak, budget_bytes=budget,
                       fits=peak <= budget,
                       overlap_slack_bytes=pred.overlap_slack_bytes)

    # -- planning queries, by enumeration ---------------------------------
    def meshes(self, n_chips: int, axes: tuple, max_axis: dict,
               kind: str, seq_len: int) -> list:
        return [m for m in enumerate_meshes(n_chips, axes, max_axis)
                if legal(self.cfg, m, kind, seq_len)]

    def _cells(self, n_chips: int, q: dict, global_batch: int):
        """Every (mesh, microbatches, schedule) of one chip count."""
        for mesh in self.meshes(n_chips, q["axes"], q["max_axis"],
                                q["kind"], q["seq_len"]):
            for mb in q["microbatches"]:
                for sched in q["schedules"]:
                    yield mesh, mb, sched, self.cell(
                        mesh, kind=q["kind"], global_batch=global_batch,
                        seq_len=q["seq_len"], chip=q["chip"],
                        microbatches=mb, schedule=sched,
                        assembly=q["assembly"])

    def min_chips(self, q: dict) -> Optional[tuple]:
        """(n_chips, peak_bytes) of the smallest chip count with a fitting
        plan, the smallest peak among its fitting plans; None if none."""
        for n in sorted(q["chips"]):
            fit = [v.peak_bytes for _, _, _, v in
                   self._cells(n, q, q["global_batch"]) if v.fits]
            if fit:
                return n, min(fit)
        return None

    def frontier(self, q: dict) -> list:
        """(n_chips, largest fitting global batch) for every chip count
        with a fitting plan, ascending."""
        out = []
        for n in sorted(q["chips"]):
            for gb in sorted(q["global_batches"], reverse=True):
                if any(v.fits for _, _, _, v in self._cells(n, q, gb)):
                    out.append((n, gb))
                    break
        return out
