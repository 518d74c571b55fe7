"""Training tokens per second: every token of every step the window
completed, summed over the cell's chips, over the window's time."""
UNIT = "tokens/s"
LAYER = "end to end"


def read(run):
    if run.cell.traffic["kind"] != "lm":
        return None
    return run.work_per_step * len(run.step_s) / run.window_s
