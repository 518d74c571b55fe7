"""End-to-end driver (paper Fig. 4 in miniature): train the MobileNet
CNN on the synthetic CIFAR-like set with two contrasting strategies —
SPIRT (gradient accumulation) and MLLess (significance filtering) — for
a few hundred steps and print accuracy trajectories.

  PYTHONPATH=src python examples/train_cnn_convergence.py [--steps 200]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy, losses
from repro.core.sharding import make_mesh
from repro.data import cifar_like
from repro.models import build_cnn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()

    cfg = get_config("mobilenet-cifar").reduced()
    imgs, labels = cifar_like(8192, seed=0)
    test_imgs, test_labels = cifar_like(1024, seed=99)
    mesh = make_mesh((1, 1), ("data", "model"))

    for sname, kw in (("spirt", {"microbatches": 4}),
                      ("mlless", {"threshold": 0.7})):
        model = build_cnn(cfg)

        def loss_fn(params, b):
            logits, _ = model.apply(params, b)
            return losses.classification_loss(logits, b["labels"])

        ts = build_train_step(model, optim.sgd(0.05, momentum=0.9),
                              get_strategy(sname, **kw), mesh,
                              loss_fn=loss_fn)
        state = ts.init_state(jax.random.PRNGKey(0))
        rs = np.random.RandomState(0)
        t0 = time.time()  # repro: allow[no-wallclock] -- progress print of real training time
        print(f"\n--- {sname} ---")
        for step in range(args.steps):
            idx = rs.randint(0, len(imgs), args.batch)
            b = {"images": jnp.asarray(imgs[idx]),
                 "labels": jnp.asarray(labels[idx])}
            state, metrics = ts.step_fn(state, b)
            if (step + 1) % 50 == 0:
                logits, _ = jax.jit(model.apply)(
                    state["params"], {"images": jnp.asarray(test_imgs)})
                acc = float(losses.accuracy(logits,
                                            jnp.asarray(test_labels)))
                extra = "".join(f" {k}={float(v):.2f}"
                                for k, v in metrics.items()
                                if k not in ("loss", "step"))
                print(f"step {step + 1:4d} loss {float(metrics['loss']):.3f}"
                      f" test_acc {acc:.3f}{extra}"
                      f" ({time.time() - t0:.0f}s)")  # repro: allow[no-wallclock] -- progress print of real training time


if __name__ == "__main__":
    main()
