"""Whole train step's share of the chips' bf16 peak, LM cells: tokens per
second of the window x model FLOPs per token (three forward passes, from
``chipbench.flops``; recomputation not counted), over chips x peak."""
UNIT = "%"
LAYER = "train step"


def read(run):
    if run.cell.traffic["kind"] != "lm":
        return None
    rate = run.work_per_step * len(run.step_s) / run.window_s
    return 100.0 * rate * run.flops_per_unit / (
        run.cell.chips * run.peak["bf16_flops"])
