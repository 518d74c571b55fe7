"""Paper Table 3 / Fig. 4: convergence & accuracy per strategy.

REAL training (no simulation): the reduced MobileNet on the synthetic
CIFAR-like set, trained with each of the five sync strategies under the
same global batch, recording accuracy-vs-step curves and the simulated
wall-clock each strategy would take per the serverless timing model —
reproducing Fig. 4's time axis (log scale in the paper) and Table 3's
ordering:

  GPU fastest; SPIRT best serverless trade-off; MLLess slower-but-equal
  accuracy; Scatter/AllReduce slowest wall-clock (per-minibatch sync).
"""
from __future__ import annotations

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
from repro.serverless import ARCHS, get_arch, simulate_epoch

# each ArchSpec names its real-training strategy (gpu = ring allreduce,
# spirt = K-step accumulation, allreduce = the λML master as a
# parameter server, ...) — the sim arch and the trained arch are one
# registry object
STRATS = {name: (get_arch(name).jax_strategy,
                 dict(get_arch(name).jax_strategy_kwargs))
          for name in ARCHS}


def run(csv_rows, steps=50, batch=96):
    imgs, labels = cifar_like(4096, seed=0)
    test_imgs, test_labels = cifar_like(512, seed=99)
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("mobilenet-cifar").reduced()

    def loss_fn(params, b):
        logits, _ = model.apply(params, b)
        return losses.classification_loss(logits, b["labels"])

    results = {}
    for name, (sname, kw) in STRATS.items():
        model = build_cnn(cfg)
        ts = build_train_step(model, optim.sgd(0.05, momentum=0.9),
                              get_strategy(sname, **kw), mesh,
                              loss_fn=loss_fn)
        state = ts.init_state(jax.random.PRNGKey(0))
        rs = np.random.RandomState(0)
        acc_curve = []
        for step in range(steps):
            idx = rs.randint(0, len(imgs), batch)
            b = {"images": jnp.asarray(imgs[idx]),
                 "labels": jnp.asarray(labels[idx])}
            state, metrics = ts.step_fn(state, b)
            if (step + 1) % 25 == 0:
                logits, _ = jax.jit(model.apply)(
                    state["params"], {"images": jnp.asarray(test_imgs)})
                acc = float(losses.accuracy(logits,
                                            jnp.asarray(test_labels)))
                acc_curve.append(acc)
        # simulated wall-clock per epoch for this strategy; GPU compute
        # per batch is ~4x faster than a Lambda vCPU (paper: 92s/24
        # batches vs 14-15s per serverless batch)
        rep = simulate_epoch(name, n_params=int(4.2e6),
                             compute_s_per_batch=0.25 if name == "gpu"
                             else 1.0)
        results[name] = (acc_curve[-1], rep.per_worker_s)
        csv_rows.append((f"table3/{name}/final_acc", acc_curve[-1],
                         f"curve={['%.3f' % a for a in acc_curve]}"))
        csv_rows.append((f"table3/{name}/sim_epoch_s", rep.per_worker_s,
                         "serverless timing model"))

    # Table 3 orderings the paper reports (time axis):
    assert results["gpu"][1] <= min(r[1] for r in results.values()) + 1e-9
    assert results["spirt"][1] < results["allreduce"][1]
    # all strategies learn (well above 10-class chance)
    for name, (acc, _) in results.items():
        assert acc > 0.25, (name, acc)
    return csv_rows
