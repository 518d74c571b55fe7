"""Quickstart: train a tiny LM with each of the paper's five
gradient-synchronization strategies and compare the resulting losses and
logical communication volumes.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs.base import get_config
from repro.core import build_train_step, get_strategy
from repro.core.sharding import make_mesh
from repro.data import lm_batches, token_stream
from repro.models import build_model


def main():
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg, remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))

    stream = token_stream(200_000, cfg.vocab_size)
    batches = lm_batches(stream, batch=16, seq=64)
    fixed = [jax.tree.map(jnp.asarray, next(batches)) for _ in range(30)]

    print(f"{'strategy':18s} {'final loss':>10s} {'comm bytes/step':>16s}")
    for name in ("allreduce", "scatterreduce", "parameter_server", "spirt",
                 "mlless"):
        strategy = get_strategy(name)
        ts = build_train_step(model, optim.adamw(3e-3), strategy, mesh)
        state = ts.init_state(jax.random.PRNGKey(0))
        for b in fixed:
            state, metrics = ts.step_fn(state, b)
        grads_like = jax.tree.leaves(state["params"])
        comm = strategy.comm_bytes(grads_like, n_workers=4)
        print(f"{name:18s} {float(metrics['loss']):10.4f} {comm:16,d}")


if __name__ == "__main__":
    main()
