"""Device time per step of the backward pass: ops under
``transpose(jvp(forward))``, the recompute left out (``chipbench.scopes``)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "train step"


def read(run):
    s = scopes.read(run)
    return None if s is None else 1e3 * s.phase_s["backward"]
