"""Real-training byzantine-robustness driver (subprocess entry point).

Trains the MobileNet CNN on the synthetic CIFAR set, 4-way
data-parallel, with a chosen byzantine worker set wrapped in
``ByzantineGradients`` under any registered attack model
(``repro.serverless.adversarial``: sign_flip / scale / gaussian_noise /
little_is_enough / zero) and any inner aggregation strategy —
including the robust family (``trimmed_mean``, ``coordinate_median``,
``krum``, ``geometric_median``).  This is the single harness behind
``benchmarks/fault_tolerance.py``, ``benchmarks/adversarial_curves.py``
(the real-JAX rows of the byzantine-fraction curves) and
``tests/test_robust_agg.py`` / ``tests/test_adversarial.py``.

It must run in its own process so ``--xla_force_host_platform_
device_count`` is set before jax initializes; use
:func:`run_in_subprocess` from the parent, or directly:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
    python -m repro.launch.byzantine_train --inner trimmed_mean \\
    --attack sign_flip --steps 150

Prints one machine-readable line:

  RESULT,inner=<name>,attack=<name>,steps=<n>,acc=<f>,final_loss=<f>,\\
max_loss=<f>,head_loss=<f>,tail_loss=<f>

The in-process :func:`run` additionally returns the full per-step loss
trace (``"losses"``), which is bit-identical across same-seed runs —
pinned by a regression test.
"""
from __future__ import annotations

import argparse
from typing import Any, Dict, Optional, Tuple

#: robust aggregators constructible by name with their tuning kwarg
ROBUST_INNER = ("trimmed_mean", "coordinate_median", "krum",
                "geometric_median")


def run(inner: str = "trimmed_mean", *, attack: str = "scale",
        steps: int = 150, batch: int = 64, data_size: int = 4096,
        trim: int = 1, krum_f: int = 0, microbatches: int = 4,
        byz_scale: Optional[float] = None,
        byz_workers: Tuple[int, ...] = (0,), lr: float = 0.1,
        eval_size: int = 512, seed: int = 0) -> Dict[str, Any]:
    """One training run under an active byzantine worker set.

    ``byz_scale=None`` keeps PR 1's calibrated -8x magnitude for the
    ``scale`` attack and falls through to the attack model's own
    default for everything else.  ``krum_f=0`` because the 4-way
    harness only satisfies Krum's ``W >= 2f + 3`` at ``f = 0`` (the
    neighbourhood scoring still excludes the attacker).  The returned
    dict includes the full loss trace — a pure function of the
    arguments, so equal seeds replay bit-identically.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import optim
    from repro.configs.base import get_config
    from repro.core import build_train_step, get_strategy, losses
    from repro.core.sharding import make_mesh
    from repro.data import cifar_like
    from repro.models import build_cnn

    cfg = get_config("mobilenet-cifar").reduced()
    imgs, labels = cifar_like(data_size, seed=0)
    timgs, tlabels = cifar_like(eval_size, seed=99)
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    bsh = NamedSharding(mesh, P("data"))
    model = build_cnn(cfg)

    def loss_fn(params, b):
        logits, _ = model.apply(params, b)
        return losses.classification_loss(logits, b["labels"])

    if inner in ROBUST_INNER:
        kw = {"microbatches": microbatches}
        if inner == "trimmed_mean":
            kw["trim"] = trim
        elif inner == "krum":
            kw["f"] = krum_f
        inner_strat = get_strategy(inner, **kw)
    else:
        inner_strat = get_strategy(inner)
    if byz_scale is None and attack == "scale":
        byz_scale = -8.0               # PR 1's calibrated attack
    strat = get_strategy("byzantine", inner=inner_strat,
                         workers=tuple(byz_workers), attack=attack,
                         scale=byz_scale, seed=seed, n_workers=n_dev)
    ts = build_train_step(model, optim.sgd(lr, momentum=0.9), strat, mesh,
                          loss_fn=loss_fn)
    state = ts.init_state(jax.random.PRNGKey(seed))
    rs = np.random.RandomState(seed)
    seen = []
    for _ in range(steps):
        idx = rs.randint(0, len(imgs), batch)
        b = {"images": jax.device_put(jnp.asarray(imgs[idx]), bsh),
             "labels": jax.device_put(jnp.asarray(labels[idx]), bsh)}
        state, m = ts.step_fn(state, b)
        seen.append(float(m["loss"]))
    logits, _ = jax.jit(model.apply)(state["params"],
                                     {"images": jnp.asarray(timgs)})
    acc = float(losses.accuracy(logits, jnp.asarray(tlabels)))
    k = min(10, len(seen))
    return {"acc": acc, "final_loss": seen[-1], "max_loss": max(seen),
            "head_loss": float(np.mean(seen[:k])),
            "tail_loss": float(np.mean(seen[-k:])),
            "losses": tuple(seen)}


def run_in_subprocess(inner: str, *, steps: int, attack: str = "scale",
                      data_size: int = 4096, devices: int = 4,
                      seed: int = 0,
                      timeout: float = 1800.0) -> Dict[str, Any]:
    """Spawn this module with its own XLA device count; parse RESULT."""
    from repro.launch import _subprocess
    stdout = _subprocess.run_module(
        "repro.launch.byzantine_train",
        ["--inner", inner, "--attack", attack, "--steps", str(steps),
         "--data-size", str(data_size), "--seed", str(seed)],
        devices=devices, timeout=timeout)
    return _subprocess.parse_result_line(
        stdout, numeric_except=("inner", "attack"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", default="trimmed_mean")
    ap.add_argument("--attack", default="scale")
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--data-size", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    r = run(args.inner, attack=args.attack, steps=args.steps,
            data_size=args.data_size, seed=args.seed)
    print(f"RESULT,inner={args.inner},attack={args.attack},"
          f"steps={args.steps},acc={r['acc']},"
          f"final_loss={r['final_loss']},max_loss={r['max_loss']},"
          f"head_loss={r['head_loss']},tail_loss={r['tail_loss']}")


if __name__ == "__main__":
    main()
