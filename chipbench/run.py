#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Run from the checkout's root.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics and
the trace's breakdown.  Notes go to standard error, and the numbers that
decide ``correct`` are its last lines; the result is the last line of
standard output.  The run refuses, with a non-zero exit and no result,
unless JAX's first device is a TPU of a kind in ``peaks.json`` and there
are as many as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import checks, spec  # noqa: E402


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    it is set, else ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chip_devices(chips: int):
    """JAX's devices, or ``None`` (with the reason on stderr) where they
    are not TPUs of a known kind or are fewer than ``chips``."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        log(f"chipbench: no TPU (first device is {d.platform}); "
            f"nothing was run")
        return None
    if d.device_kind not in spec.known_device_kinds():
        log(f"chipbench: device kind {d.device_kind!r} is not in "
            f"peaks.json; nothing was run")
        return None
    if len(devices) < chips:
        log(f"chipbench: the cell needs {chips} chips, JAX has "
            f"{len(devices)}; nothing was run")
        return None
    return devices


def result_line(res, bench, trace: bool) -> dict:
    run = res["run"]
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench, run.cell.name, kind):
        value = spec.load_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    used = res["devices"]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": res["memory_peak"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = {name: {"value": c["value"], "limit": c["limit"]}
                     for name, c in res["checks"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.load_cell(args.workload)
    cache = enable_compile_cache()
    devices = chip_devices(cell.chips)
    if devices is None:
        return 3
    log(f"cell={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} chips={cell.chips} compile_cache={cache} "
        f"devices_ready_s={time.perf_counter() - T_START!r}")

    from chipbench import harness
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START, log=log)
    res["devices"] = devices[:cell.chips]
    line = result_line(res, bench, bool(args.trace))
    for text in checks.lines(res["checks"]):
        log(text)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
