"""Whole train step's share of the chips' bf16 peak, CNN cells: images per
second of the window x model FLOPs per image (three forward passes of the
convolutions and head, from the configuration's reference module), over
chips x peak.  The float32 convolutions run at XLA's default precision,
one bf16 pass on TPU, so the bf16 peak is the one that applies."""
UNIT = "%"
LAYER = "train step"


def read(run):
    if run.cell.traffic["kind"] != "images":
        return None
    rate = run.work_per_step * len(run.step_s) / run.window_s
    return 100.0 * rate * run.flops_per_unit / (
        run.cell.chips * run.peak["bf16_flops"])
