"""Training images per second: every image of every step the window
completed, summed over the cell's chips, over the window's time."""
UNIT = "images/s"
LAYER = "end to end"


def read(run):
    if run.cell.traffic["kind"] != "images":
        return None
    return run.work_per_step * len(run.step_s) / run.window_s
