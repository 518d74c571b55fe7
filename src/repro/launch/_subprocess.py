"""Shared bootstrap for multi-device subprocess drivers.

``--xla_force_host_platform_device_count`` must be set in the
environment *before* jax initializes, so every driver that simulates a
multi-worker fleet on host devices (``byzantine_train``,
``resilient_train``) runs as ``python -m repro.launch.<driver>`` in a
child process.  This module is the one place that knows how to build
that child's environment and read its answer back.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict, Sequence


def src_root() -> str:
    """The ``src/`` directory providing the ``repro`` package."""
    import repro
    # repro is a namespace package (__file__ is None): resolve src/ from
    # its search path
    return os.path.dirname(os.path.abspath(list(repro.__path__)[0]))


def child_env(devices: int) -> Dict[str, str]:
    """A copy of the environment for a virtual-device fleet simulation:
    the CPU backend with ``devices`` host devices, and this repo's
    ``src/`` first on the child's PYTHONPATH.

    The child is pinned to the CPU even where an accelerator is
    attached, so it never contends with its parent for the chip and
    always sees the fleet it asked for, not the chip's device count."""
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = (src_root() + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return env


def run_module(module: str, argv: Sequence[str], *, devices: int,
               timeout: float = 1800.0) -> str:
    """Run ``python -m <module> <argv>`` with ``devices`` forced host
    devices; return its stdout, raising ``RuntimeError`` (with the
    stderr tail) on a non-zero exit."""
    out = subprocess.run(
        [sys.executable, "-m", module, *argv],
        capture_output=True, text=True, timeout=timeout,
        env=child_env(devices))
    if out.returncode != 0:
        raise RuntimeError(
            f"{module} exited {out.returncode}: {out.stderr[-3000:]}")
    return out.stdout


def parse_result_line(stdout: str,
                      numeric_except: Sequence[str] = ()) -> Dict[str, Any]:
    """Parse the last ``RESULT,k=v,...`` line of a driver's stdout.

    Values are floated except the keys in ``numeric_except`` (kept as
    strings).  Raises ``RuntimeError`` when no RESULT line was printed
    — the driver died after jax init but before reporting."""
    lines = [l for l in stdout.splitlines() if l.startswith("RESULT,")]
    if not lines:
        raise RuntimeError(
            f"no RESULT line in driver output: {stdout[-2000:]!r}")
    fields = dict(kv.split("=", 1) for kv in lines[-1].split(",")[1:])
    return {k: (v if k in numeric_except else float(v))
            for k, v in fields.items()}


def read_json_out(path: str) -> Any:
    """Load a driver's ``--json-out`` payload."""
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)
