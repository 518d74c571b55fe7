"""90th percentile of every step time of the window, in milliseconds: the
tail a synchronous data-parallel job pays for stalls and stragglers."""
import numpy as np

UNIT = "ms"
LAYER = "end to end"


def read(run):
    return 1e3 * float(np.percentile(run.step_s, 90))
