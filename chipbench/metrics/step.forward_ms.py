"""Device time per step of the forward pass under the program's ``forward``
scope, the backward and the recompute left out (``chipbench.scopes``)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "train step"


def read(run):
    s = scopes.read(run)
    return None if s is None else 1e3 * s.phase_s["forward"]
