"""Run one cell once: set up, check, measure, trace, compare, report.

Set-up builds the train step as ``repro.launch.train`` does, makes the
weights from the seed on the device, compiles the step for the cell's
shapes (and nothing else), and drives it through its first
``checks.STEPS`` steps, reading the loss of each, the first gradient from
the optimizer's state and the parameters' change.  The same compiled
step, with the state those steps left, then runs the window.

The window keeps one step in flight: it puts batch i+1 on the device,
dispatches step i+1, then waits on step i's loss.  A step's time is the
gap between successive completions; the first is timed from the window's
start.  The window closes at the first completion at or after
``seconds``; the step still in flight is drained and not counted.

With ``trace``, a slice of a few steps after the window runs under the
profiler.  Then the program's state is freed and the plain reference
trains from the same weights on the same rows.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import checks, hlo, reference, spec, trace, traffic
from chipbench.spec import Cell

#: seconds of steps the traced slice holds (at least ``TRACE_MIN_STEPS``)
TRACE_SECONDS = 2.0
TRACE_MIN_STEPS = 4


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    work_per_step: int           # tokens or images per step
    flops_per_unit: float        # model FLOPs per token or image
    peak: Dict[str, Any]         # one chip's published peaks
    setup_s: float
    step_s: List[float]          # every step of the window
    window_s: float              # window start to last counted completion
    trace: Optional[trace.Summary] = None


def _now() -> float:
    return time.perf_counter()


def drive(step, state, feed, pool, start: int, *, seconds=None, steps=None):
    """Run steps from ``pool[start]`` on, one in flight, until ``seconds``
    have passed at a completion or ``steps`` have completed; the step in
    flight then is drained, not counted.  Returns (state, step times,
    elapsed, non-finite losses, next pool index)."""
    import jax
    from jax.profiler import TraceAnnotation
    times: List[float] = []
    bad = 0
    pending = None
    i = start
    t0 = t_last = _now()
    while True:
        with TraceAnnotation("input"):
            batch = feed(pool[i % len(pool)])
        with TraceAnnotation("dispatch"):
            state, metrics = step(state, batch)
        i += 1
        if pending is not None:
            with TraceAnnotation("wait"):
                loss = float(pending)
            t = _now()
            times.append(t - t_last)
            t_last = t
            bad += not math.isfinite(loss)
            if (seconds is not None and t - t0 >= seconds) or \
                    (steps is not None and len(times) >= steps):
                break
        pending = metrics["loss"]
    with TraceAnnotation("wait"):
        jax.block_until_ready(metrics)
    return state, times, t_last - t0, bad, i


def first_grad(opt_state, opt: Dict[str, Any]):
    """The first step's gradient, as the optimizer got it, from the
    optimizer's state after that step."""
    import jax
    if opt["name"] == "adamw":
        return jax.tree.map(lambda m: m / (1 - opt["b1"]), opt_state["m"])
    return opt_state["mu"]


def peak_bytes(devices) -> Optional[int]:
    vals = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


def setup(cell: Cell, used, seed: int):
    """Build the step, make the weights and the pool, compile, and drive
    the first ``checks.STEPS`` steps.  Returns (compiled step, state after
    those steps, feed, pool, parameter shapes, program readings)."""
    import jax
    from chipbench import system
    from chipbench.reference.numerics import diff_norms, leaf_norms

    marks = [("start", _now())]
    sysm = system.build(cell, used)
    shapes = system.param_shapes(sysm.model)
    params = system.make_params(shapes, cell.config, seed, used[0])
    state = sysm.ts.init_state(jax.random.PRNGKey(0), dtype_params=params)
    del params
    marks.append(("weights", _now()))
    pool = traffic.make_pool(cell.traffic, cell.config, seed)
    marks.append(("pool", _now()))

    def feed(batch):
        return jax.device_put(batch, sysm.feed_shardings)
    compiled = sysm.ts.step_fn.lower(state, feed(pool[0])).compile()
    norms, diffs = jax.jit(leaf_norms), jax.jit(diff_norms)
    marks.append(("compile", _now()))

    params0 = state["params"]
    prog = {"losses": []}
    for k in range(checks.STEPS):
        state, metrics = compiled(state, feed(pool[k]))
        prog["losses"].append(float(metrics["loss"]))
        if k == 0:
            grad = first_grad(state["opt"], cell.traffic["optimizer"])
            prog["grad_norms"] = np.asarray(norms(grad)).tolist()
            prog["grad"] = jax.device_get(grad)
    prog["change_norms"] = np.asarray(
        diffs(state["params"], params0)).tolist()
    marks.append(("checked_steps", _now()))
    prog["setup_phases_s"] = {name: t - marks[i][1]
                              for i, (name, t) in enumerate(marks[1:])}
    return compiled, state, feed, pool, shapes, prog


def reference_readings(cell: Cell, shapes, seed: int, device, pool,
                       num=None, against=None) -> Dict[str, Any]:
    """The plain reference (or, given ``num``, a control) trained from the
    same weights on the same first rows; ``against`` is the first gradient
    of the run it is compared with."""
    from chipbench import system
    from chipbench.reference.numerics import REFERENCE
    params = system.make_params(shapes, cell.config, seed, device)
    return reference.train_readings(cell.config, cell.traffic, params,
                                    pool[:checks.STEPS], num or REFERENCE,
                                    against=against)


def run(cell: Cell, seed: int, seconds: float, do_trace: bool, devices,
        t_start: float, log=print) -> Dict[str, Any]:
    """Run ``cell`` on ``devices`` (its first ``cell.chips``) and return
    the parts of the result line, the compared numbers and the ``Run``."""
    import jax

    used = devices[:cell.chips]
    # the CPU of the tests has no peaks: its runs read no share of one
    peak = spec.peaks(used[0].device_kind) if used[0].platform == "tpu" \
        else {}
    compiled, state, feed, pool, shapes, prog = setup(cell, used, seed)
    gc.collect()

    # ---------------- the window ----------------
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(name)
        if "backend_compile" in name else None)
    setup_s = _now() - t_start
    n_before = len(compiles)
    state, step_s, window_s, bad, nxt = drive(
        compiled, state, feed, pool, checks.STEPS, seconds=seconds)
    in_window = len(compiles) - n_before
    mem = peak_bytes(used)

    arch = reference.module_for(cell.config)
    run_rec = Run(cell=cell, work_per_step=traffic.work_per_step(cell.traffic),
                  flops_per_unit=arch.train_flops_per_unit(cell.config,
                                                           cell.traffic),
                  peak=peak, setup_s=setup_s, step_s=step_s,
                  window_s=window_s)

    # ---------------- the traced slice ----------------
    traced = None
    if do_trace:
        from jax.profiler import TraceAnnotation
        tdir = spec.ROOT / ".chipbench" / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        n = max(TRACE_MIN_STEPS,
                math.ceil(TRACE_SECONDS / float(np.median(step_s))))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=options)
        # the first steps under the profiler start it up: not in the window
        state, _, _, bad_t, nxt = drive(compiled, state, feed, pool, nxt,
                                        steps=1)
        with TraceAnnotation("window"):
            state, _, _, bad_w, end = drive(compiled, state, feed, pool, nxt,
                                            steps=n)
        jax.profiler.stop_trace()
        bad += bad_t + bad_w
        text = compiled.as_text()
        (tdir / "step.hlo.txt").write_text(text)
        traced = (tdir, hlo.parse(text), end - nxt)

    # ---------------- the reference, once the program is gone ----------------
    del state, compiled
    gc.collect()
    t_ref = _now()
    ref = reference_readings(cell, shapes, seed, used[0], pool,
                             against=prog.pop("grad"))
    ref_s = _now() - t_ref
    ref.pop("grad")
    values = checks.numbers(prog, ref)
    correct, compared = checks.judge(values, cell.limits)

    if traced is not None:
        run_rec.trace = trace.reduce_dir(str(traced[0]), traced[1],
                                         traced[2])

    slowest = sorted(range(len(step_s)), key=lambda i: -step_s[i])[:3]
    log(f"steps_in_window={len(step_s)} window_s={window_s!r} "
        f"step_s_median={float(np.median(step_s))!r} "
        f"slowest_steps={[(i, step_s[i]) for i in slowest]!r} "
        f"nonfinite_losses={bad} compiles_in_window={in_window} "
        f"reference_s={ref_s!r}")
    log(f"setup_s={setup_s!r} phases={prog.pop('setup_phases_s')!r}")
    log(f"program_losses={prog['losses']!r} "
        f"reference_losses={ref['losses']!r}")
    return {"run": run_rec, "correct": correct,
            "attempted": len(step_s), "failed": bad, "memory_peak": mem,
            "checks": compared, "prog": prog, "ref": ref}
