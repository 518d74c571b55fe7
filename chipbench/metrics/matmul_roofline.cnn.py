"""Roofline share of the convolutions and the head's dot in the traced
steps, CNN cells: their FLOPs from the compiled step's shapes
(``chipbench.hlo``: taps on a convolution's padding not counted), over the
chip's bf16 peak, over their summed device time.  XLA runs the float32
convolutions as one bf16 pass on the TPU, so the bf16 peak is the one that
applies.  The FLOP bound is the one taken: a fusion's operands are whole
arrays of which it may read a slice, so no byte bound can be read from
the shapes."""
UNIT = "%"
LAYER = "kernels"


def read(run):
    t = run.trace
    if t is None or run.cell.traffic["kind"] != "images" or t.matmul_s <= 0:
        return None
    return 100.0 * t.matmul_flops / run.peak["bf16_flops"] / t.matmul_s
