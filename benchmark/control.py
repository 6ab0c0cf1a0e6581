"""Readings for the limits, taken on the chip (never by a benchmark run).

    python3 benchmark/control.py --workload <name> --seeds 1,2,3

Per seed: the reference (float32) against itself computed in float8 (the
control), with half of the batch left out, with one chip's rows only (the
exchange left out) and with its state left unchanged, by the same numbers
a run compares. Each goes through the harness's ``judge`` with the cell's
own limits, and the verdict is printed: every one of them has to come out
not correct. One process for all seeds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest as mf  # noqa: E402


def train_readings(cell: mf.Cell, seed: int) -> dict:
    import jax

    from benchmark import traffic, train_cell, weights

    mix = traffic.train_mix(cell.mix, cell.chips)
    dims = weights.model_dims(cell.config)
    batches = [traffic.train_batch(seed, i, mix["batch"], mix["seq"],
                                   dims["vocab"], mix["labels"])
               for i in range(train_cell.WARM_STEPS)]
    devices = jax.devices()[:cell.chips]
    ref = train_cell.reference_steps(cell.config, seed, batches,
                                     devices=devices)
    out = {"seed": seed, "ref_losses": ref["losses"]}
    half = mix["batch"] // 2
    faults = {"control_float8": dict(quant=True),
              "half_batch": dict(rows_used=slice(0, half)),
              "state_unchanged": dict(frozen=True)}
    if cell.chips > 1:
        shard = mix["batch"] // cell.chips
        faults["no_exchange"] = dict(rows_used=slice(0, shard))
    for name, kw in faults.items():
        config = cell.config
        if kw.pop("frozen", False):
            # a step that returns its state unchanged: the same steps with
            # a learning rate of nought (the losses are what is read; the
            # change reads 1 by its measure)
            train = dict(config["train"])
            train["optimizer"] = {**train["optimizer"], "lr": 0.0}
            config = {**config, "train": train}
        seen = train_cell.reference_steps(config, seed, batches,
                                          devices=devices, **kw)
        where = {}
        out[name] = {**train_cell.compare(seen, ref, where), **where}
    return out


def verdicts(cell: mf.Cell, readings: dict) -> dict:
    """For the control and each fault in ``readings``: the compared
    numbers that fail the cell's limits (``run.judge``). An empty list is
    a fault the limits let through."""
    from benchmark import run as harness

    out = {}
    for name, numbers in readings.items():
        if not isinstance(numbers, dict):
            continue
        _, rows = harness.judge({**numbers, "window_losses_finite": 1.0},
                                cell.limits)
        out[name] = [k for k, row in rows.items() if not row["ok"]]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("control readings are taken on the chip")
    from benchmark import run as harness

    harness.configure_cache(mf.ROOT)
    cell = mf.Cell(mf.load_manifest(), args.workload)
    out_dir = os.path.join(mf.ROOT, "chiprun_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = train_readings(cell, seed)
        r["fails"] = verdicts(cell, r)
        print(json.dumps(r), flush=True)
        for name, failed in r["fails"].items():
            print(f"[control] seed {seed} {name}: " + (
                "not correct, by " + ", ".join(failed) if failed
                else "CORRECT: the limits let it through"), flush=True)
        with open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a") as f:
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
