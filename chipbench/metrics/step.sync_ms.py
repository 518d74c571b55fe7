"""Device time per step of the gradient sync: ops under the ``sync`` scope
(``chipbench.scopes``); on one chip the sync is bypassed."""
from chipbench import scopes

UNIT = "ms"
LAYER = "sync strategy"


def read(run):
    s = scopes.read(run)
    return None if s is None else 1e3 * s.phase_s["sync"]
