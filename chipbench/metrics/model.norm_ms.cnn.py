"""Device time per step of the CNN's normalisation, CNN cells: ops under
the ``norm`` scope (``models/cnn.py``'s GroupNorm) in the forward and the
backward, as the union of their intervals per traced step, averaged over
the devices, control ops left out (``chipbench.scopes``).

Reads nothing where the run was not traced or no op of the compiled step
carries the scope, as in a program from before it was opened.  Where the
step's text holds the other scopes but not this one, as a persistent
compile cache can hand back the executable of such a program for this one
(its key leaves metadata out), the names are taken from the step compiled
afresh, if it is the same program."""
import os
import sys
import traceback

from chipbench import hlo, scopes, trace

UNIT = "ms"
LAYER = "model"
SCOPE = "norm"


def in_norm(op_name: str) -> bool:
    return SCOPE in scopes.scope_names(op_name)


def _names(text: str, cell):
    names = scopes.step_op_names(text, cell)
    if names is None or any(in_norm(n) for n in names.values()):
        return names
    try:
        fresh = scopes.fresh_step_text(cell)
    except Exception:   # a reader reports nothing rather than end the run
        traceback.print_exc(file=sys.stderr)
        return None
    if scopes.code_only(fresh) != scopes.code_only(text):
        return None
    return scopes.op_names(fresh)


def norm_seconds(ops, spans, table, names, steps: int) -> float:
    """Device seconds per traced step under the scope, mean over devices;
    ``ops`` and ``spans`` as ``trace.read`` gives them."""
    lo, hi = next((s, e) for n, s, e in spans if n == "window")
    other = hlo.Instr()
    total = 0.0
    for dev in sorted(ops):
        iv = [(max(s, lo), min(e, hi)) for name, s, e in ops[dev]
              if min(e, hi) > max(s, lo)
              and table.get(name, other).cls != "control"
              and in_norm(names.get(name, ""))]
        total += trace.measure(trace.union(iv))
    return total / (len(ops) * steps)


def read(run):
    if run.trace is None or run.cell.traffic["kind"] != "images":
        return None
    tdir = scopes.trace_dir(run.cell.name)
    with open(os.path.join(tdir, "step.hlo.txt")) as f:
        text = f.read()
    names = _names(text, run.cell)
    if names is None or not any(in_norm(n) for n in names.values()):
        return None
    ops, _, spans = trace.read(trace.find_xplane(tdir))
    return 1e3 * norm_seconds(ops, spans, hlo.parse(text), names,
                              run.trace.steps)
