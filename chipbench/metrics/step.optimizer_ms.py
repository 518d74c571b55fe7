"""Device time per step of the optimizer's update and its application: ops
under the ``optimizer`` scope (``chipbench.scopes``)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "optimizer"


def read(run):
    s = scopes.read(run)
    return None if s is None else 1e3 * s.phase_s["optimizer"]
