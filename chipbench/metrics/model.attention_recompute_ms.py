"""Device time per step of the attention that remat recomputes inside the
backward, LM cells: the union of the ops that are both under the
``attention`` scope and in the recompute phase (``chipbench.scopes``),
over the traced "window" span, per device and per traced step.  Where
remat reruns the attention's forward it reads that forward's time; where
remat keeps the attention's output and log-sum-exp, only the layout work
it still rebuilds around them.

Reads nothing where the run was not traced, the cell trains no language
model, or no op of the step carries the attention scope."""
import os

from chipbench import hlo, scopes, trace

UNIT = "ms"
LAYER = "model"


def recompute_seconds(ops, spans, table, names, steps):
    """Seconds per step of the attention's ops in the recompute phase:
    ``scopes.split`` over the attention's ops alone, the others left
    without a name and so unscoped.  Arguments as ``scopes.split`` takes
    them."""
    attention = {k: v for k, v in names.items() if scopes.in_attention(v)}
    return scopes.split(ops, spans, table, attention,
                        steps).phase_s["recompute"]


def read(run):
    if run.trace is None or run.cell.traffic["kind"] != "lm":
        return None
    tdir = scopes.trace_dir(run.cell.name)
    with open(os.path.join(tdir, "step.hlo.txt")) as f:
        text = f.read()
    names = scopes.step_op_names(text, run.cell)
    if names is None or not any(map(scopes.in_attention, names.values())):
        return None
    ops, _, spans = trace.read(trace.find_xplane(tdir))
    return 1e3 * recompute_seconds(ops, spans, hlo.parse(text), names,
                                   run.trace.steps)
