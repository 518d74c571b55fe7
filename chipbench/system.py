"""The system under test, built from a cell as ``repro.launch.train`` does.

``build`` returns the model, the ``TrainStep`` from
``repro.core.build_train_step`` (the cell's strategy and optimizer on its
mesh) and the shardings batches are put on the device with; the window
drives the step's ``step_fn``.  ``make_params`` is the benchmark's own
weight generator: one jitted call from the seed, on the device, in the
dtypes the program holds its parameters in.  The plain reference starts
from the same generator, so it takes nothing that the program has made.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.spec import Cell


def program_config(config: Dict[str, Any]):
    """The program's config object, its sizes taken from the file."""
    from repro.configs.base import get_config
    c = config["config"]
    base = get_config(config["program"])
    if config["kind"] == "lm":
        return dataclasses.replace(
            base, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            rope_theta=c["rope_theta"], dtype=config["precision"]["params"])
    return dataclasses.replace(
        base, num_classes=c["num_classes"], image_size=c["image_size"],
        channels=c["channels"], width_mult=c["width_mult"],
        dtype=config["precision"]["params"])


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


def _lm_leaf(key, name, shape, config):
    """Scales of the program's own initializer; norm gains get a spread so
    that a path that drops them is seen."""
    last = name.split("/")[-1]
    if last == "table":
        std = config["config"]["initializer_range"]
        return jax.random.normal(key, shape, jnp.float32) * std
    if last.startswith("norm") or last == "final_norm":
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / fan_in ** 0.5


def _cnn_leaf(key, name, shape, config):
    parts = name.split("/")
    last = parts[-1]
    if last == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if last == "bias" or (parts[0] == "head" and last == "b"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if parts[0] == "head":
        return jax.random.normal(key, shape, jnp.float32) / shape[0] ** 0.5
    fan_in = 1
    for d in shape[:-1]:
        fan_in *= d
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def make_params(shapes, config: Dict[str, Any], seed: int, device):
    """Every parameter from ``seed`` in one jitted call on ``device``, in
    the dtype of ``shapes`` (the program's parameter tree)."""
    from chipbench.traffic import rng_for
    seed32 = int(rng_for(seed, "weights").integers(0, 2 ** 31 - 1))
    leaf = _lm_leaf if config["kind"] == "lm" else _cnn_leaf

    def gen(key):
        def one(path, s):
            name = _path_name(path)
            return leaf(_leaf_key(key, name), name, s.shape,
                        config).astype(s.dtype)
        return jax.tree_util.tree_map_with_path(one, shapes)
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(gen, out_shardings=sharding)(jax.random.PRNGKey(seed32))
