"""Training entrypoint.

Examples:
  # tiny LM on CPU with the SPIRT strategy
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --strategy spirt --steps 50

  # the paper's CNN x strategy matrix
  PYTHONPATH=src python -m repro.launch.train --arch mobilenet-cifar \
      --reduced --strategy mlless --steps 100
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.checkpoint import restore, save
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy, losses
from repro.core.sharding import make_mesh
from repro.data import cifar_like, lm_batches, token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_cnn, build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--strategy", default="allreduce",
                    choices=["allreduce", "scatterreduce",
                             "parameter_server", "spirt", "mlless"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--mesh", default="1x1",
                    help="data x model, e.g. 2x2 (needs host devices)")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--fused-optimizer", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dims = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("data", "model") if len(dims) == 2 else \
        ("pod", "data", "model")
    mesh = make_mesh(dims, axes)

    is_cnn = cfg.family == "cnn"
    if is_cnn:
        model = build_cnn(cfg)
        imgs, labels = cifar_like(args.batch * 64, seed=0)

        def loss_fn(params, b):
            logits, _ = model.apply(params, b)
            return losses.classification_loss(logits, b["labels"])

        def batches():
            rs = np.random.RandomState(0)
            while True:
                idx = rs.randint(0, len(imgs), args.batch)
                yield {"images": jnp.asarray(imgs[idx]),
                       "labels": jnp.asarray(labels[idx])}
        loss = loss_fn
    else:
        model = build_model(cfg)
        stream = token_stream(args.batch * args.seq * 64, cfg.vocab_size)
        it = lm_batches(stream, args.batch, args.seq)

        def batches():
            for b in it:
                yield jax.tree.map(jnp.asarray, b)
        loss = None

    opt = optim.adamw(args.lr, use_fused=args.fused_optimizer) \
        if not is_cnn else optim.sgd(args.lr, momentum=0.9)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    ts = build_train_step(model, opt, get_strategy(args.strategy), mesh,
                          data_axes=data_axes, fsdp=args.fsdp,
                          loss_fn=loss)
    state = ts.init_state(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} strategy={args.strategy} params={n_params:,} "
          f"mesh={mesh.shape}")

    t0 = time.time()
    for step, batch in zip(range(args.steps), batches()):
        state, metrics = ts.step_fn(state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            extra = "".join(
                f" {k}={float(v):.3f}" for k, v in metrics.items()
                if k not in ("loss", "step"))
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}"
                  f"{extra}  ({time.time() - t0:.1f}s)")
    if args.checkpoint:
        save(args.checkpoint, state["params"])
        print(f"saved params to {args.checkpoint}")


if __name__ == "__main__":
    main()
