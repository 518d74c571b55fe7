"""The tiny LM on a (4, 1) data mesh of four virtual CPU devices, in a
child process (the device count is fixed before JAX starts): against one
device and against the reference it agrees, and with the exchange between
devices left out the run is incorrect."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from chipbench import faults, harness, spec
    data = Path({data!r})
    seed = 2 ** 31 + 99
    four = spec.load_cell("tiny-lm-4", data)
    one = spec.load_cell("tiny-lm-1", data)
    assert len(jax.devices()) == 4
    sound = harness.run(four, seed, 0.2, False, jax.devices(),
                        time.perf_counter(), log=lambda *a: None)
    with faults.planted("no_exchange"):
        broken = harness.run(four, seed, 0.2, False, jax.devices(),
                             time.perf_counter(), log=lambda *a: None)
    *_, prog1 = harness.setup(one, jax.devices()[:1], seed)
    print(json.dumps({{"sound": sound["correct"], "checks": sound["checks"],
                      "broken": broken["correct"],
                      "losses4": sound["prog"]["losses"],
                      "losses1": prog1["losses"]}}))
""")


def test_four_virtual_devices_against_one_and_the_reference():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"),
                        data=str(ROOT / "chipbench" / "tests" / "data"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"], out["checks"]
    assert not out["broken"]
    for a, b in zip(out["losses4"], out["losses1"]):
        assert abs(a - b) <= 2e-3 * abs(b)
