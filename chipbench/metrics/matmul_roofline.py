"""Roofline share of the dot and convolution operations in the traced
steps, LM cells: their FLOPs over the chip's bf16 peak, over their summed
device time.  FLOPs come from the compiled step's shapes
(``chipbench.hlo``), so the recomputed forward counts: those operations
really run.  The FLOP bound is the one taken: a fusion's operands are
whole arrays of which it may read a slice, so no byte bound can be read
from the shapes."""
UNIT = "%"
LAYER = "kernels"


def read(run):
    t = run.trace
    if t is None or run.cell.traffic["kind"] != "lm" or t.matmul_s <= 0:
        return None
    return 100.0 * t.matmul_flops / run.peak["bf16_flops"] / t.matmul_s
