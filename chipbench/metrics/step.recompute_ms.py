"""Device time per step of the forward that remat recomputes inside the
backward: ops under ``rematted_computation`` (``chipbench.scopes``)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "train step"


def read(run):
    s = scopes.read(run)
    return None if s is None else 1e3 * s.phase_s["recompute"]
