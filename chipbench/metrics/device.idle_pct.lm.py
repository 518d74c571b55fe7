"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices, LM cells."""
UNIT = "%"
LAYER = "device"


def read(run):
    if run.trace is None or run.cell.traffic["kind"] != "lm":
        return None
    return run.trace.idle_pct
