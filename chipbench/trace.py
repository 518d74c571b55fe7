"""Capture a profiler trace of a few steps, and reduce it to numbers.

The reduction reads the ``.xplane.pb`` the JAX profiler writes, with
``jax.profiler.ProfileData``: one plane per device (``/device:TPU:<n>``)
whose "XLA Ops" line holds every operation the device ran, each named by
its HLO instruction's text ("Async XLA Ops" holds the spans of async
operations from start to done), and the host plane, where the harness's own
spans sit on the same clock: "window" around the traced steps, and
"input", "dispatch" and "wait" inside it.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices; idle share is one minus busy over the
window.  ``chipbench.hlo`` classes each operation as matmul, collective,
control (a loop or branch, which encloses other operations) or other.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

from chipbench.hlo import Instr

HOST_SPANS = ("window", "input", "dispatch", "wait")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_NAME = re.compile(r"^%?([\w.\-]+)\s*=")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    devices: int
    steps: int                    # steps whose work lies in the window
    window_s: float
    busy_s: float                 # mean over devices
    matmul_flops: float           # mean over devices
    matmul_s: float               # mean over devices
    collective_s: float           # mean over devices
    collective_exposed_s: float   # mean over devices
    device_ops: List[list]        # [op, seconds], mean over devices
    idle_gaps: List[list]         # [host span, seconds], longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` that no interval of the
    disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction's name from a trace event's name."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name


def read(path: str):
    """(device ops by device id: [(op, start, end)], async ops likewise,
    host spans: [(name, start, end)]), times in seconds."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, List[tuple]] = {}
    async_ops: Dict[int, List[tuple]] = {}
    spans: List[tuple] = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            for line in plane.lines:
                into = {OPS_LINE: ops, ASYNC_LINE: async_ops}.get(line.name)
                if into is not None:
                    into.setdefault(int(m.group(1)), []).extend(
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         ev.end_ns * 1e-9) for ev in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                             for ev in line.events if ev.name in HOST_SPANS)
    return ops, async_ops, spans


def summarize(ops: Dict[int, List[tuple]], async_ops: Dict[int, List[tuple]],
              spans: List[tuple], table: Dict[str, Instr],
              steps: int) -> Summary:
    """Reduce one traced window to a ``Summary``.  A collective's time is
    the union of its op intervals and, where it runs asynchronously, its
    start-to-done span."""
    if not ops:
        raise ValueError("the trace holds no device operations")
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = windows[0]
    host = sorted((s, e, n) for n, s, e in spans
                  if n != "window" and e > lo and s < hi)
    other = Instr()
    n = len(ops)
    busy = coll = exposed = mm_flops = mm_s = 0.0
    per_op: Dict[str, float] = {}
    gaps: List[list] = []
    for dev in sorted(ops):
        kinds = []
        for name, s, e in ops[dev]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                kinds.append((table.get(name, other), name, s, e))
        busy_iv = union([(s, e) for _, _, s, e in kinds])
        busy += measure(busy_iv)
        compute = union([(s, e) for ins, _, s, e in kinds
                         if ins.cls not in ("collective", "control")])
        colls = union([(s, e) for ins, _, s, e in kinds
                       if ins.cls == "collective"] +
                      [(max(s, lo), min(e, hi))
                       for name, s, e in async_ops.get(dev, [])
                       if table.get(name, other).cls == "collective"
                       and min(e, hi) > max(s, lo)])
        coll += measure(colls)
        exposed += measure(subtract(colls, compute))
        for ins, name, s, e in kinds:
            if ins.cls == "control":
                continue
            label = f"{name} ({ins.cls})"
            per_op[label] = per_op.get(label, 0.0) + (e - s)
            if ins.cls == "matmul":
                mm_flops += ins.flops
                mm_s += e - s
        if dev == min(ops):
            edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
            gaps = [[_host_during(host, g0, g1), g1 - g0]
                    for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        devices=n, steps=steps, window_s=hi - lo, busy_s=busy / n,
        matmul_flops=mm_flops / n, matmul_s=mm_s / n, collective_s=coll / n,
        collective_exposed_s=exposed / n,
        device_ops=[[name, t / n] for name, t in top], idle_gaps=gaps[:10])


def _host_during(host: List[tuple], g0: float, g1: float) -> str:
    """The host span that covers most of [g0, g1], or "other"."""
    best, name = 0.0, "other"
    for s, e, span in host:
        if s >= g1:
            break
        cover = min(e, g1) - max(s, g0)
        if cover > best:
            best, name = cover, span
    return name


def reduce_dir(trace_dir: str, table: Dict[str, Instr],
               steps: int) -> Summary:
    ops, async_ops, spans = read(find_xplane(trace_dir))
    return summarize(ops, async_ops, spans, table, steps)
