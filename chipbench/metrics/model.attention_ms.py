"""Device time per step of the attention core (scores, online softmax,
values): ops under the ``attention`` scope in the forward, the recompute
and the backward (``chipbench.scopes``)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"


def read(run):
    s = scopes.read(run)
    return None if s is None else 1e3 * s.attention_s
