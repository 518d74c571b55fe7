"""Share of the attention's device time per step that runs in Mosaic
kernels, LM cells: of the union of the ops under the ``attention`` scope
(``chipbench.scopes``; forward, recompute and backward), the part in
which one of them is a ``tpu_custom_call`` instruction, in percent.  It
reads 0 where the attention runs as XLA fusions and near 100 where Pallas
kernels run it, the layout changes around them aside.

Reads nothing where the run was not traced, the cell trains no language
model, or no op of the step carries the attention scope."""
import os
import re

from chipbench import hlo, scopes, trace

UNIT = "%"
LAYER = "model"

_MOSAIC = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bcustom-call\(.*"
                     r'\bcustom_call_target="tpu_custom_call"')


def mosaic_calls(text: str) -> set:
    """Names of the instructions of an HLO module's text that call a
    Mosaic kernel."""
    return {m.group(1) for m in map(_MOSAIC.match, text.splitlines()) if m}


def kernel_share(ops, spans, table, names, kernels):
    """Percent of the attention's device time in ``kernels``, over the
    traced "window" span, summed over devices; ``None`` where no attention
    op ran.  ``ops`` and ``spans`` as ``trace.read`` gives them, ``table``
    from ``hlo.parse``, ``names`` from ``scopes.op_names``."""
    lo, hi = next((s, e) for n, s, e in spans if n == "window")
    other = hlo.Instr()
    att = ker = 0.0
    for dev in sorted(ops):
        att_iv, ker_iv = [], []
        for name, s, e in ops[dev]:
            s, e = max(s, lo), min(e, hi)
            if e <= s or table.get(name, other).cls == "control" \
                    or not scopes.in_attention(names.get(name, "")):
                continue
            att_iv.append((s, e))
            if name in kernels:
                ker_iv.append((s, e))
        att += trace.measure(trace.union(att_iv))
        ker += trace.measure(trace.union(ker_iv))
    return 100.0 * ker / att if att > 0 else None


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
    return kernel_share(ops, spans, hlo.parse(text), names,
                        mosaic_calls(text))
