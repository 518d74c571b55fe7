#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, in one process.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
      [--control-seeds 1,2,3] [--faults half_batch,no_exchange] \
      [--fault-seeds 1,2,3] [--out readings.jsonl]

For every seed it drives the timed step through its checked steps, as a
run's set-up does, and compares with the plain reference: the numbers of
sound runs, whose largest is a limit's lower reading.  For the control
seeds it puts the reference computed in the configuration's ``control``
precision in the program's place, and for each planted fault
(``chipbench.faults``) it runs the broken program: the smallest of those
is a limit's upper reading.  Each reading is one JSON line, with
``correct`` as the cell's limits judge it (a control or fault row must
read false); the last line sums them up per number.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import checks, faults, harness, spec  # noqa: E402
from chipbench.reference import numerics  # noqa: E402


def _seeds(text: str):
    return [int(x) for x in text.split(",") if x]


def readings(cell, used, seeds, control_seeds, fault_names, fault_seeds,
             emit):
    """Emit one line per reading; return them by kind."""
    import jax
    refs, out = {}, {}

    def record(kind, seed, nums):
        out.setdefault(kind, []).append(nums)
        correct, _ = checks.judge(nums, cell.limits)
        emit({"kind": kind, "seed": seed, "correct": correct,
              "numbers": nums})

    def against(ref, grad):
        """``ref`` with its gradient's difference taken from ``grad``."""
        diffs = jax.jit(numerics.diff_norms)
        return {**ref, "grad_diff_norms": jax.device_get(diffs(
            ref["grad"], jax.device_put(grad, used[0]))).tolist()}

    for seed in seeds:
        _, _, _, pool, shapes, prog = harness.setup(cell, used, seed)
        ref = harness.reference_readings(cell, shapes, seed, used[0], pool,
                                         against=prog["grad"])
        record("program", seed, checks.numbers(prog, ref))
        if seed in control_seeds:
            ctl = harness.reference_readings(
                cell, shapes, seed, used[0], pool,
                numerics.control(cell.config["control"]))
            record("control", seed,
                   checks.numbers(ctl, against(ref, ctl["grad"])))
        if seed in fault_seeds:
            refs[seed] = {**ref, "grad": jax.device_get(ref["grad"])}
    for name in fault_names:
        with faults.planted(name):
            for seed in fault_seeds:
                *_, prog = harness.setup(cell, used, seed)
                ref = {**refs[seed], "grad": jax.device_put(
                    refs[seed]["grad"], used[0])}
                record(name, seed, checks.numbers(
                    prog, against(ref, prog["grad"])))
    return out


def summary(out):
    """Per number: the largest sound reading, the smallest control and
    fault readings."""
    res = {}
    for kind, rows in out.items():
        pick = max if kind == "program" else min
        for name in rows[0]:
            vals = [r[name] for r in rows]
            res.setdefault(name, {})[kind] = pick(
                v if math.isfinite(v) else float("inf") for v in vals)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from chipbench.run import chip_devices, enable_compile_cache
    cell = spec.load_cell(args.workload)
    enable_compile_cache()
    devices = chip_devices(cell.chips)
    if devices is None:
        return 3
    seeds = _seeds(args.seeds)
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        row = {"cell": cell.name, **row}
        print(json.dumps(row), flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()
    fault_seeds = _seeds(args.fault_seeds) or seeds[:3]
    out = readings(cell, devices[:cell.chips], seeds,
                   set(_seeds(args.control_seeds)),
                   [f for f in args.faults.split(",") if f],
                   fault_seeds, emit)
    emit({"kind": "summary", "numbers": summary(out),
          "device": jax.devices()[0].device_kind})
    return 0


if __name__ == "__main__":
    sys.exit(main())
