"""CPU rehearsal of ``chip_smoke.py``: its phases at ``cfg.reduced()``.

The script's ``main()`` refuses any first device but a TPU; the phases
themselves take a config, so here they run on the CPU at a tiny size —
the one-chip phase in this process, the four-chip phase on four virtual
devices in a child process.
"""
import importlib.util
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs.base import get_config
from repro.launch._subprocess import child_env

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_reduced(smoke):
    cfg = get_config(smoke.ARCH).reduced()
    runs = smoke.one_chip_phases(cfg, steps=2, batch=4, seq=32)
    assert [r["strategy"] for r in runs] == ["allreduce", "mlless"]
    for r in runs:
        assert len(r["losses"]) == len(r["step_s"]) == 2
        assert abs(r["losses"][0] - math.log(cfg.vocab_size)) < 0.5
        assert r["losses"][-1] < r["losses"][0]
        assert r["params"] > 0 and r["param_devices"] == [0]
        assert not r["tpu_custom_call"]       # no Mosaic kernel on the CPU
    line = json.loads(smoke.result_line(jax.devices()))
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


@pytest.mark.parametrize("losses,fault", [
    ([math.log(512), math.log(512) - 0.1], None),
    ([math.log(512), float("nan")], "non-finite"),
    ([math.log(512) + 0.6, math.log(512)], "not within 0.5"),
    ([math.log(512), math.log(512) + 0.1], "did not fall"),
])
def test_check_losses(smoke, losses, fault):
    r = {"strategy": "allreduce", "losses": losses}
    if fault is None:
        smoke.check_losses(r, 512)
    else:
        with pytest.raises(smoke.SmokeFailure, match=fault):
            smoke.check_losses(r, 512)


def test_four_chip_phase_on_virtual_devices():
    code = f"""
    import importlib.util, json
    spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs.base import get_config
    cfg = get_config(smoke.ARCH).reduced()
    runs = smoke.four_chip_phase(cfg, steps=2, batch=8, seq=32)
    print(json.dumps([[r["strategy"], r["mesh"], r["param_devices"],
                       r["losses"]] for r in runs]))
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=560,
                         env=child_env(4))
    assert out.returncode == 0, out.stderr[-3000:]
    runs = json.loads(out.stdout.strip().splitlines()[-1])
    assert [(s, m["data"], d) for s, m, d, _ in runs] == [
        ("allreduce", 4, [0, 1, 2, 3]), ("scatterreduce", 4, [0, 1, 2, 3]),
        ("allreduce", 1, [0])]
    first = [l[0] for *_, l in runs]
    assert max(first) - min(first) < 1e-4 * abs(first[0])


def test_main_refuses_the_cpu():
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=300, cwd=ROOT, env=child_env(1))
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the cache
    goes to the checkout's fixed ``.jax_cache``."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert calls == []
