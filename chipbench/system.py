"""The system under test, built from a cell as ``repro.launch.train`` does.

``build`` returns the model, the ``TrainStep`` from
``repro.core.build_train_step`` (the cell's strategy and optimizer on its
mesh) and the shardings batches are put on the device with; the window
drives the step's ``step_fn``.  ``make_params`` is the benchmark's own
weight generator: one jitted call from the seed, on the device, in the
dtypes the program holds its parameters in.  The plain reference starts
from the same generator, so it takes nothing that the program has made.
The program's sizes and each weight's scale come from the configuration's
reference module (``chipbench.reference.module_for``).
"""
from __future__ import annotations

import dataclasses
import hashlib
from pathlib import Path
from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import reference
from chipbench.spec import HERE, Cell


def program_config(config: Dict[str, Any], base: Path = HERE):
    """The program's config object, its sizes taken from the file by the
    configuration's reference module."""
    return reference.module_for(config, base).program_config(config)


@dataclasses.dataclass
class System:
    model: Any
    ts: Any                  # repro.core.TrainStep
    feed_shardings: Dict[str, Any]


def build_optimizer(spec: Dict[str, Any]):
    from repro import optim
    if spec["name"] == "adamw":
        return optim.adamw(spec["lr"], b1=spec["b1"], b2=spec["b2"],
                           eps=spec["eps"],
                           weight_decay=spec["weight_decay"])
    if spec["name"] == "sgd":
        return optim.sgd(spec["lr"], momentum=spec["momentum"])
    raise ValueError(f"unknown optimizer {spec['name']!r}")


def build(cell: Cell, devices) -> System:
    from repro.core import build_train_step, get_strategy, losses
    from repro.core.sharding import make_mesh
    from repro.models import build_cnn, build_model

    cfg = program_config(cell.config)
    mesh = make_mesh(cell.mesh, ("data", "model"),
                     devices=devices[:cell.chips])
    loss_fn = None
    if cell.config["kind"] == "lm":
        model = build_model(cfg)
        keys = ("tokens", "labels")
    else:
        model = build_cnn(cfg)
        keys = ("images", "labels")

        def loss_fn(params, b):
            logits, _ = model.apply(params, b)
            return losses.classification_loss(logits, b["labels"])
    ts = build_train_step(model, build_optimizer(cell.traffic["optimizer"]),
                          get_strategy(cell.traffic["strategy"]), mesh,
                          data_axes=("data",), loss_fn=loss_fn)
    feed = {k: NamedSharding(mesh, P("data")) for k in keys}
    return System(model=model, ts=ts, feed_shardings=feed)


# ---------------------------------------------------------------------------
# weights: the benchmark's generator, keyed by each leaf's path
# ---------------------------------------------------------------------------
def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _leaf_key(seed_key, name: str):
    digest = hashlib.sha256(name.encode()).digest()
    return jax.random.fold_in(seed_key, int.from_bytes(digest[:4], "little")
                              & 0x7FFFFFFF)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def make_params(shapes, config: Dict[str, Any], seed: int, device,
                base: Path = HERE):
    """Every parameter from ``seed`` in one jitted call on ``device``, in
    the dtype of ``shapes`` (the program's parameter tree), each leaf made
    by the reference module's ``init_leaf``."""
    from chipbench.traffic import rng_for
    seed32 = int(rng_for(seed, "weights").integers(0, 2 ** 31 - 1))
    leaf = reference.module_for(config, base).init_leaf

    def gen(key):
        def one(path, s):
            name = _path_name(path)
            return leaf(_leaf_key(key, name), name, s.shape,
                        config).astype(s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(gen, out_shardings=sharding)(jax.random.PRNGKey(seed32))
