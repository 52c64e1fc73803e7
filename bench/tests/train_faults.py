"""The tiny training cell, and the faults that the fault tests plant in
its timed path.  The tests use it in process on one CPU device; run as a
script it drives the cell on four devices as a {data 2, model 2} mesh,
sound and with each fault a sharded cell can have, and prints one JSON
line, fault -> whether the run came out correct:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/train_faults.py

On a mesh, a data replica's gradient left out of the exchange is the
half-batch fault: the step then follows the mean over the other rows.
"""

import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402

SEED = 2 ** 33 + 21


def tiny_cell(data=None, chips: int = 1) -> harness.Cell:
    """The tiny CPU cell under the one-chip train cell's limits;
    ``data`` devices on the data axis, the model axis takes the rest."""
    traffic = harness.load_json("bench/tests/data/tiny-train.json")
    traffic["data"] = data
    traffic["limits"] = harness.load_json(
        harness.traffic_file("train-s4096-b8"))["limits"]
    return harness.Cell(
        name="tiny.train", chips=chips, config_name="tiny-lm",
        config=harness.load_json("bench/tests/data/tiny-lm.json"),
        traffic=traffic, end_to_end=[], per_layer=[])


def run(cell):
    import jax
    drv = harness.load_module(harness.driver_file("train"))
    return drv.run(cell, SEED, 0.2, False, jax.devices()[:cell.chips])


def state_unchanged(mp, cell) -> None:
    """The step returns its state unchanged."""
    from repro.train import train_step
    mp.setattr(train_step, "apply_updates",
               lambda p, g, s, step, cfg: (p, s))


def half_batch(mp, cell) -> None:
    """Half of each batch left out, the mean taken over the rest."""
    import repro.models as models
    build = models.build_model

    def half(cfg):
        m = build(cfg)
        loss = m.loss

        def half_loss(p, b, **kw):
            n = b["tokens"].shape[0] // 2
            return loss(p, {k: v[:n] for k, v in b.items()}, **kw)
        return dataclasses.replace(m, loss=half_loss)

    mp.setattr(models, "build_model", half)


def control(mp, cell) -> None:
    """The reference at float8 in the program's place."""
    drv = harness.load_module(harness.driver_file("train"))
    ref = harness.load_module(cell.config["reference"])
    t = cell.traffic

    def readings(job, steps):
        return ref.run(cell.config, t["optimizer"], SEED,
                       [job.host_batch(i) for i in range(steps)],
                       rows_per_block=int(t["rows_per_block"]), lowp=True)

    mp.setattr(drv, "program_readings", readings)


def main() -> int:
    import pytest
    from repro.core import planner
    planner.DEVICE_KINDS["cpu"] = "v5e"
    cell = tiny_cell(data=2, chips=4)
    out = {"sound": run(cell).correct}
    for fault in (state_unchanged, half_batch):
        with pytest.MonkeyPatch.context() as mp:
            fault(mp, cell)
            out[fault.__name__] = run(cell).correct
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
