"""Recurrence equivalence: the chunked/parallel training forms must match
exact step-by-step recurrences (the decode path) token for token."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import rglru, rwkv6


def _cfg(name):
    return get_config(name).reduced(d_model=128)


def test_rwkv_chunked_equals_stepwise():
    cfg = _cfg("rwkv6-7b")
    p = rwkv6.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T = 2, 37                       # deliberately not a chunk multiple
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model))

    y_par, state_par = rwkv6.rwkv_apply(p, x, cfg, chunk=16)

    state = rwkv6.rwkv_init_state(cfg, B, x.dtype)
    ys = []
    for t in range(T):
        y_t, state = rwkv6.rwkv_decode_step(p, x[:, t:t + 1], cfg, state)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)

    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(state_par["S"]),
                               np.asarray(state["S"]), atol=2e-4)


def test_rwkv_state_carry_across_segments():
    """apply(x) == apply(x[:, :k]) then apply(x[:, k:], state)."""
    cfg = _cfg("rwkv6-7b")
    p = rwkv6.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T, k = 1, 48, 19
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (B, T, cfg.d_model))
    y_full, _ = rwkv6.rwkv_apply(p, x, cfg, chunk=16)
    y1, st = rwkv6.rwkv_apply(p, x[:, :k], cfg, chunk=16)
    y2, _ = rwkv6.rwkv_apply(p, x[:, k:], cfg, state=st, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-4)


def test_rglru_scan_equals_stepwise():
    cfg = _cfg("recurrentgemma-2b")
    p = rglru.rglru_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T = 2, 29
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.d_model))

    y_par, state_par = rglru.rglru_apply(p, x, cfg)

    state = rglru.rglru_init_state(cfg, B, x.dtype)
    ys = []
    for t in range(T):
        y_t, state = rglru.rglru_decode_step(p, x[:, t:t + 1], cfg, state)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(state_par["h"]),
                               np.asarray(state["h"]), atol=2e-4)


def test_rwkv_pallas_kernel_path_matches_jnp():
    """use_kernel=True routes WKV through the Pallas kernel with a
    custom-VJP backward — forward and grads must match the jnp path."""
    cfg = _cfg("rwkv6-7b")
    p = rwkv6.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))

    def run(use_kernel):
        return lambda p_, x_: rwkv6.rwkv_apply(p_, x_, cfg,
                                               use_kernel=use_kernel)[0]
    y1 = jax.jit(run(False))(p, x)
    y2 = jax.jit(run(True))(p, x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=1e-4)

    def loss(use_kernel):
        return lambda p_, x_: jnp.mean(run(use_kernel)(p_, x_) ** 2)
    g1 = jax.grad(loss(False), argnums=(0, 1))(p, x)
    g2 = jax.grad(loss(True), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
