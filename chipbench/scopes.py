"""Device time per phase of the train step, from the program's name scopes.

The program opens fixed ``jax.named_scope``s (``src/repro``):

  forward     around the loss that ``jax.value_and_grad`` differentiates
              (``core/train_step.py``); autodiff names the backward
              ``transpose(jvp(forward))`` and remat's recomputed forward
              ``.../rematted_computation/...``
  sync        around the strategy's gradient sync and the FSDP mean
  optimizer   around the optimizer's update and its application
  attention   around the attention core (``models/attention.py``), in the
              forward, the recompute and the backward

JAX writes the name stack into each HLO instruction's
``metadata={op_name=...}``; the device trace names each op by its HLO
instruction, so instruction -> op_name -> phase is the join (an
instruction without metadata of its own takes that of the loop or branch
that runs it).  Each device
op of the traced window falls in exactly one phase, tried in this order:
``optimizer``, ``sync``, ``recompute``, ``backward``, ``forward``, else
``unscoped``.  Control ops (while, conditional, call) enclose other ops
and belong to none; the time in which only a control op runs counts as
``unscoped``, so the phases and ``unscoped`` add up to the device's busy
time.  A phase's time is the union of its ops' intervals per device,
averaged over the devices, per traced step; ``attention`` the same over
the attention's ops of every phase.

The names come from the compiled step's text that the harness writes
beside the trace.  A persistent compile cache can hand back an executable
whose metadata another checkout of the same program wrote; where that text
holds no scope, the step is compiled again with the cache off and its
names taken if it is the same program.  Where the program opens none of
the scopes, as before they were added, ``read`` returns ``None``: the
metrics that use it then read nothing.
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys
import traceback
from typing import Dict, List, Optional

from chipbench import hlo, spec, trace

PHASES = ("forward", "recompute", "backward", "sync", "optimizer")
UNSCOPED = "unscoped"

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def op_names(text: str) -> Dict[str, str]:
    """Each instruction of an HLO module's text with its ``op_name``; where
    the field joins several names with ``;``, the first.  An instruction
    with none, as the copies and async slices the compiler adds have, takes
    that of the nearest instruction (a loop, branch or call) that runs the
    computation holding it."""
    own: Dict[str, str] = {}
    home: Dict[str, str] = {}        # instruction -> its computation
    runner: Dict[str, str] = {}      # computation -> the instruction calling it
    comp = None
    for line in text.splitlines():
        if comp is None:
            m = _COMP.match(line)
            comp = m.group(1) if m else None
            continue
        if line.strip() == "}":
            comp = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        home[name] = comp
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1).split(";")[0]
        called = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            called += re.findall(r"%?([\w.\-]+)", group)
        for c in called:
            runner.setdefault(c, name)
    out = dict(own)
    for name in home:
        seen, cur = set(), name
        while cur not in own and cur not in seen:
            seen.add(cur)
            cur = runner.get(home.get(cur), cur)
        if cur in own:
            out[name] = own[cur]
    return out


def scope_names(op_name: str) -> List[str]:
    """The parts of a name stack with transform wrappers taken off:
    ``jit(f)/transpose(jvp(forward))/mul`` -> ``[f, forward, mul]``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out


def phase(op_name: str) -> str:
    """The phase of the step an op of this name belongs to."""
    parts = set(scope_names(op_name))
    if "optimizer" in parts:
        return "optimizer"
    if "sync" in parts:
        return "sync"
    if "rematted_computation" in parts:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "forward" in parts:
        return "forward"
    return UNSCOPED


def in_attention(op_name: str) -> bool:
    return "attention" in scope_names(op_name)


def is_scoped(names: Dict[str, str]) -> bool:
    """Whether the program opened the step's scopes at all."""
    return any("forward" in scope_names(n) for n in names.values())


@dataclasses.dataclass(frozen=True)
class Split:
    """Device seconds per traced step, mean over devices."""
    phase_s: Dict[str, float]    # PHASES and UNSCOPED
    attention_s: float
    busy_s: float                # union of every op, control ops included

    @property
    def unscoped_pct(self) -> float:
        return 100.0 * self.phase_s[UNSCOPED] / self.busy_s


def split(ops: Dict[int, List[tuple]], spans: List[tuple],
          table: Dict[str, hlo.Instr], names: Dict[str, str],
          steps: int) -> Split:
    """Split the device ops of the traced "window" span by phase; ``ops``
    and ``spans`` as ``trace.read`` gives them, ``table`` from
    ``hlo.parse`` and ``names`` from ``op_names`` of the same module."""
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not ops or not windows:
        raise ValueError("the trace holds no device ops or no 'window'")
    lo, hi = windows[0]
    other = hlo.Instr()
    keys = PHASES + (UNSCOPED,)
    tot = dict.fromkeys(keys, 0.0)
    att = busy = 0.0
    for dev in sorted(ops):
        by = {k: [] for k in keys}
        att_iv, all_iv, ctl_iv = [], [], []
        for name, s, e in ops[dev]:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if table.get(name, other).cls == "control":
                ctl_iv.append((s, e))
                continue
            op = names.get(name, "")
            by[phase(op)].append((s, e))
            all_iv.append((s, e))
            if in_attention(op):
                att_iv.append((s, e))
        ops_u = trace.union(all_iv)
        busy_u = trace.union(all_iv + ctl_iv)
        busy += trace.measure(busy_u)
        att += trace.measure(trace.union(att_iv))
        for k in keys:
            tot[k] += trace.measure(trace.union(by[k]))
        tot[UNSCOPED] += trace.measure(trace.subtract(busy_u, ops_u))
    per = len(ops) * steps
    return Split(phase_s={k: v / per for k, v in tot.items()},
                 attention_s=att / per, busy_s=busy / per)


def trace_dir(cell_name: str) -> str:
    """Where ``harness.run`` leaves a cell's traced run."""
    return str(spec.ROOT / ".chipbench" / "trace" / cell_name)


def code_only(text: str) -> str:
    """An HLO module's text without its metadata: each instruction's
    ``metadata={...}`` and the tables of source frames it points into."""
    blocks = [b for b in text.split("\n\n")
              if b.strip().split("\n")[0] not in _FRAME_TABLES]
    return _METADATA.sub("", "\n\n".join(blocks))


def fresh_step_text(cell) -> str:
    """The optimized HLO of ``cell``'s train step as ``harness.setup``
    compiles it, compiled again with the persistent compile cache off.

    The cache's key leaves metadata out, so an executable it hands back
    carries the name stacks of whichever program compiled it first: a
    checkout without the scopes, say, that ran the same cell before."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from chipbench import system, traffic
    sysm = system.build(cell, jax.devices())
    ts = sysm.ts
    state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        ts.state_sds(), ts.state_shardings)
    first = traffic.make_pool(cell.traffic, cell.config, 0)[0]
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                     sharding=sysm.feed_shardings[k])
             for k, v in first.items()}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return ts.step_fn.lower(state, batch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def step_op_names(text: str, cell) -> Optional[Dict[str, str]]:
    """The ``op_name`` of each instruction of the compiled step ``text``,
    from ``text`` where it holds the scopes, else from the step compiled
    afresh (``fresh_step_text``) where that is the same program; ``None``
    where neither holds them."""
    names = op_names(text)
    if is_scoped(names):
        return names
    try:
        fresh = fresh_step_text(cell)
    except Exception:   # a reader reports nothing rather than end the run
        traceback.print_exc(file=sys.stderr)
        return None
    if code_only(fresh) != code_only(text):
        return None
    names = op_names(fresh)
    return names if is_scoped(names) else None


# the split of the one trace last read: every metric of a run reads it
_LAST: Dict[tuple, Optional[Split]] = {}


def read(run) -> Optional[Split]:
    """The phase split of ``run``'s traced slice, or ``None`` where the run
    was not traced or the program opens no scope.  Each trace is parsed
    once, however many metrics read it."""
    if run.trace is None:
        return None
    tdir = trace_dir(run.cell.name)
    xplane = trace.find_xplane(tdir)
    key = (tdir, xplane, os.path.getmtime(xplane), run.trace.steps)
    if key not in _LAST:
        _LAST.clear()
        with open(os.path.join(tdir, "step.hlo.txt")) as f:
            text = f.read()
        names = step_op_names(text, run.cell)
        if names is None:
            _LAST[key] = None
        else:
            ops, _, spans = trace.read(xplane)
            _LAST[key] = split(ops, spans, hlo.parse(text), names,
                               run.trace.steps)
    return _LAST[key]
