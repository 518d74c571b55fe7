"""Process start to the window's first step: imports, device start,
weights, data, compilation (or the compile cache) and the checked steps."""
UNIT = "s"
LAYER = "end to end"


def read(run):
    return run.setup_s
