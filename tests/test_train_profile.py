"""``repro.launch.train --profile DIR``: the steps after the first (the
compile) are traced into DIR, under the span names that
``chipbench.trace`` reads."""
import os
import subprocess
import sys

from repro.launch._subprocess import child_env

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from chipbench import trace  # noqa: E402


def test_profile_flag_writes_a_trace_of_the_steps_after_the_first(tmp_path):
    env = child_env(1)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = tmp_path / "profile"
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "smollm-135m",
         "--reduced", "--steps", "3", "--batch", "2", "--seq", "32",
         "--profile", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    xplane = trace.find_xplane(str(out))
    assert xplane.endswith(".xplane.pb")
    _, _, spans = trace.read(xplane)
    names = [n for n, _, _ in spans]
    assert names.count("input") == names.count("dispatch") == 2
