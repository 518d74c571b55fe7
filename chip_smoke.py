#!/usr/bin/env python3
"""Bring-up check: smollm-135m training at its published widths on TPU.

  python3 chip_smoke.py               # one chip: allreduce, then mlless
  python3 chip_smoke.py --four-chips  # four chips: (4, 1) data mesh under
                                      # allreduce and scatterreduce, against
                                      # the same batch on one chip

Each phase builds the model with ``build_model`` and the step with
``build_train_step`` (the path ``repro.launch.train`` runs) on an Auto
("data", "model") mesh, with AdamW, random weights from a seed and one
fixed seeded batch.  The loss must be finite, start within 0.5 of
ln(vocab) and fall over the steps; under ``mlless`` the compiled step
must hold the Pallas kernel (``tpu_custom_call``), and with
``--four-chips`` the first-step losses of the three runs must agree
within bf16 tolerance.  Numbers are printed on earlier lines, each named
for what it is; the last line is one JSON object naming the device.

The script exits non-zero, printing no result, unless JAX's first device
is a TPU: there is no CPU fallback.  It runs in one process and starts
none, so it is the only user of the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import optim  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core import build_train_step, get_strategy  # noqa: E402
from repro.core.sharding import make_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402

ARCH = "smollm-135m"
BATCH, SEQ = 8, 512            # global batch: 8 sequences of 512 tokens
STEPS = 5
LR = 1e-3
SEED = 0
#: two bf16 ulps: how far the first-step losses of the mesh layouts may
#: differ (the forward pass is the same math, summed in another order)
BF16_RTOL = 2.0 ** -7


class SmokeFailure(RuntimeError):
    """A phase produced a result outside what it must produce."""


def seeded_batch(cfg, batch: int, seq: int, seed: int = SEED):
    """Next-token batch of uniform random tokens (host numpy)."""
    toks = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def train_phase(cfg, strategy: str, mesh, batch, *, steps: int,
                seed: int = SEED, lr: float = LR) -> dict:
    """Build and compile the train step, then take ``steps`` steps on
    ``batch``.  Returns losses, compile and per-step wall seconds, the
    parameter count and placement, and whether the compiled step holds a
    Mosaic kernel."""
    model = build_model(cfg)
    ts = build_train_step(model, optim.adamw(lr), get_strategy(strategy),
                          mesh, data_axes=("data",))
    state = ts.init_state(jax.random.PRNGKey(seed))
    b = {k: jax.device_put(v, ts.batch_shardings[k])
         for k, v in batch.items()}
    t0 = time.perf_counter()  # repro: allow[no-wallclock] -- bring-up compile time, printed only
    step = ts.step_fn.lower(state, b).compile()
    compile_s = time.perf_counter() - t0  # repro: allow[no-wallclock] -- bring-up compile time, printed only
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()  # repro: allow[no-wallclock] -- bring-up step wall time, printed only
        state, metrics = step(state, b)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)  # repro: allow[no-wallclock] -- bring-up step wall time, printed only
        losses.append(float(metrics["loss"]))
    leaves = jax.tree.leaves(state["params"])
    held = [{s.device.id for s in leaf.addressable_shards}
            for leaf in leaves]
    return {"strategy": strategy, "mesh": dict(mesh.shape),
            "params": sum(int(np.prod(leaf.shape)) for leaf in leaves),
            "param_devices": sorted(set().union(*held)),
            "min_devices_per_param": min(len(h) for h in held),
            "compile_s": compile_s, "step_s": step_s, "losses": losses,
            "tpu_custom_call": "tpu_custom_call" in step.as_text()}


def check_losses(r: dict, vocab: int) -> None:
    """Finite, near ln(vocab) at step 0, and lower at the last step."""
    losses, name = r["losses"], r["strategy"]
    if not all(math.isfinite(l) for l in losses):
        raise SmokeFailure(f"{name}: non-finite loss in {losses}")
    if abs(losses[0] - math.log(vocab)) > 0.5:
        raise SmokeFailure(f"{name}: step-0 loss {losses[0]} is not within "
                           f"0.5 of ln({vocab}) = {math.log(vocab)}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{name}: loss did not fall: {losses}")


def one_chip_phases(cfg, *, steps: int = STEPS, batch: int = BATCH,
                    seq: int = SEQ) -> list:
    """allreduce, then mlless, on a (1, 1) mesh of the first device."""
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=jax.devices()[:1])
    data = seeded_batch(cfg, batch, seq)
    out = []
    for strategy in ("allreduce", "mlless"):
        r = train_phase(cfg, strategy, mesh, data, steps=steps)
        check_losses(r, cfg.vocab_size)
        out.append(r)
    return out


def four_chip_phase(cfg, *, steps: int = 2, batch: int = BATCH,
                    seq: int = SEQ) -> list:
    """allreduce and scatterreduce on a (4, 1) data mesh, and allreduce
    on one chip, all on the same global batch; their first-step losses
    must agree and every parameter must live on all four devices."""
    devices = jax.devices()
    if len(devices) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, have "
                           f"{len(devices)}")
    data = seeded_batch(cfg, batch, seq)
    mesh4 = make_mesh((4, 1), ("data", "model"), devices=devices[:4])
    runs = [train_phase(cfg, s, mesh4, data, steps=steps)
            for s in ("allreduce", "scatterreduce")]
    for r in runs:
        check_losses(r, cfg.vocab_size)
        if r["min_devices_per_param"] != 4:
            raise SmokeFailure(f"{r['strategy']}: a parameter is held by "
                               f"{r['min_devices_per_param']} of 4 devices")
    one = train_phase(cfg, "allreduce",
                      make_mesh((1, 1), ("data", "model"),
                                devices=devices[:1]), data, steps=steps)
    check_losses(one, cfg.vocab_size)
    ref = one["losses"][0]
    for r in runs:
        if abs(r["losses"][0] - ref) > BF16_RTOL * abs(ref):
            raise SmokeFailure(f"{r['strategy']} on 4 devices: first-step "
                               f"loss {r['losses'][0]} vs {ref} on one")
    return runs + [one]


def report(r: dict, peak_bytes=None) -> None:
    print(f"phase strategy={r['strategy']} mesh={r['mesh']} "
          f"params={r['params']} param_devices={r['param_devices']} "
          f"compile_s={r['compile_s']:.3f} "
          f"step_wall_s={[round(s, 6) for s in r['step_s']]} "
          f"losses={[round(l, 6) for l in r['losses']]} "
          f"tpu_custom_call={r['tpu_custom_call']}", flush=True)
    if peak_bytes is not None:
        print(f"  device0_peak_bytes_in_use={peak_bytes}", flush=True)


def result_line(devices) -> str:
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (first device is "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    counts = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event, **_):
        key = event.rsplit("/", 1)[-1]
        if key in counts:
            counts[key] += 1
    jax.monitoring.register_event_listener(on_event)

    cfg = get_config(ARCH)
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} batch={BATCH}x{SEQ} "
          f"compile_cache={cache_dir}", flush=True)
    if args.four_chips:
        runs = four_chip_phase(cfg)
        for r in runs:
            report(r)
    else:
        for r in one_chip_phases(cfg):
            report(r, devices[0].memory_stats()["peak_bytes_in_use"])
            if r["strategy"] == "mlless" and not r["tpu_custom_call"]:
                raise SmokeFailure("mlless step holds no tpu_custom_call: "
                                   "the block_significance kernel fell back")
    print(f"compile_cache_hits={counts['cache_hits']} "
          f"compile_cache_misses={counts['cache_misses']}", flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
