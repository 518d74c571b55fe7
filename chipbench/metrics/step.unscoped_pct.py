"""Share of the device's busy time per step that no phase of
``chipbench.scopes`` claims: a check that the program's scopes still
cover the step."""
from chipbench import scopes

UNIT = "%"
LAYER = "train step"


def read(run):
    s = scopes.read(run)
    return None if s is None else s.unscoped_pct
