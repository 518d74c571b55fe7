"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology and compiled by the TPU
compiler that ships with jaxlib, at smollm-135m sizes.  That catches
what interpret mode cannot — block shapes off the (8, 128) tiling, VMEM
overruns — and the compiled text must hold the Mosaic kernel
(``tpu_custom_call``), so no kernel silently fell back to XLA.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and pytest-xdist imports
this file in every worker.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import block_significance as bs
from repro.kernels import flash_attention as kflash
from repro.kernels import fused_adamw as fa
from repro.kernels import robust_agg
from repro.models import build_model

CFG = get_config("smollm-135m")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a TPU executable written to the persistent cache cannot be read
        # back without a chip: keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sizes():
    """(total parameter count, largest leaf size) of smollm-135m."""
    shapes = jax.eval_shape(build_model(CFG).init, jax.random.PRNGKey(0))
    n = [int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)]
    return sum(n), max(n)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_sizes_are_smollm_135m(sizes):
    total, largest = sizes
    assert 160e6 < total < 165e6          # ~163M with the untied head
    assert largest == CFG.vocab_size * CFG.d_model     # the embedding


@pytest.mark.parametrize("kernel", ["block_norms", "masked_filter"])
def test_block_significance_kernels_compile(one_chip, sizes, kernel):
    # MLLess views the largest gradient leaf as (n, 256) blocks
    rows = sizes[1] // 256
    if kernel == "block_norms":
        txt = _compiled_text(lambda x: bs.block_norms(x, interpret=False),
                             ((rows, 256), jnp.float32), sharding=one_chip)
    else:
        txt = _compiled_text(
            lambda x, m: bs.masked_filter(x, m, interpret=False),
            ((rows, 256), jnp.float32), ((rows,), jnp.bool_),
            sharding=one_chip)
    assert "tpu_custom_call" in txt


def test_fused_adamw_compiles(one_chip, sizes):
    n = sizes[1]

    def step(g, m, v, p, c):
        return fa.fused_adamw_flat(g, m, v, p, c[0], c[1], lr=1e-3, b1=0.9,
                                   b2=0.95, eps=1e-8, wd=0.01,
                                   interpret=False)
    txt = _compiled_text(step, ((n,), jnp.bfloat16), ((n,), jnp.float32),
                         ((n,), jnp.float32), ((n,), jnp.bfloat16),
                         ((2,), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("entry,workers", [
    ("trimmed_mean", 4), ("coordinate_median", 5), ("krum_pairwise", 4),
    ("weiszfeld_step", 4)])
def test_robust_agg_kernels_compile(one_chip, sizes, entry, workers):
    D = sizes[0]
    stack = ((workers, D), jnp.float32)
    if entry == "trimmed_mean":
        txt = _compiled_text(
            lambda x: robust_agg.trimmed_mean(x, 1, interpret=False), stack,
            sharding=one_chip)
    elif entry == "weiszfeld_step":
        txt = _compiled_text(
            lambda x, z: robust_agg.weiszfeld_step(x, z, 1e-12,
                                                   interpret=False),
            stack, ((D,), jnp.float32), sharding=one_chip)
    else:
        fn = getattr(robust_agg, entry)
        txt = _compiled_text(lambda x: fn(x, interpret=False), stack,
                             sharding=one_chip)
    assert "tpu_custom_call" in txt


@pytest.fixture(scope="module")
def train_step_text(one_chip):
    """The compiled reduced train step, once per (strategy, fused)."""
    from repro import optim
    from repro.core import build_train_step, get_strategy
    from repro.core.sharding import make_mesh
    from repro.kernels import ops

    texts = {}

    def text(strategy, fused):
        if (strategy, fused) in texts:
            return texts[strategy, fused]
        with pytest.MonkeyPatch.context() as mp:
            # the code asks the backend (the CPU here); steer it to the
            # TPU path
            mp.setattr(ops, "default_interpret", lambda: False)
            cfg = CFG.reduced()
            mesh = make_mesh((1, 1), ("data", "model"),
                             devices=[next(iter(one_chip.device_set))])
            ts = build_train_step(build_model(cfg, remat=False),
                                  optim.adamw(1e-3, use_fused=fused),
                                  get_strategy(strategy), mesh)
            batch = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32,
                                             sharding=s)
                     for k, s in ts.batch_shardings.items()}
            texts[strategy, fused] = ts.step_fn.lower(
                ts.state_sds(), batch).compile().as_text()
        return texts[strategy, fused]
    return text


@pytest.mark.parametrize("strategy,fused", [("mlless", False),
                                             ("allreduce", True)])
def test_train_step_compiles_with_kernel(train_step_text, strategy, fused):
    """A Pallas kernel inside the train step's shard_map (data manual,
    ``model`` Auto) must reach the chip's compiler as a Mosaic call:
    MLLess's block significance, and the fused AdamW update."""
    assert "tpu_custom_call" in train_step_text(strategy, fused)


@pytest.mark.parametrize("strategy,fused,kernels,scope", [
    ("mlless", False, ("block_significance_norms",), "sync"),
    ("allreduce", True, ("fused_adamw",), "optimizer")])
def test_train_step_kernels_are_named_in_their_phase(train_step_text,
                                                     strategy, fused, kernels,
                                                     scope):
    """Each Mosaic call is an instruction named after its kernel, so a
    trace names it, and sits under its call site's name scope."""
    txt = train_step_text(strategy, fused)
    for kernel in kernels:
        line = next((ln for ln in txt.splitlines()
                     if re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", ln)),
                    None)
        assert line is not None, kernel
        assert "tpu_custom_call" in line
        assert f"/{scope}/" in line, line[:300]


@pytest.mark.parametrize("kernel", [
    "block_significance_norms", "block_significance_filter", "fused_adamw",
    "robust_agg_trimmed_mean", "robust_agg_median", "robust_agg_krum_sqdist",
    "robust_agg_weiszfeld_sqdist", "robust_agg_weiszfeld_wsum",
    "flash_attention_fwd", "flash_attention_bwd"])
def test_kernel_instruction_carries_its_name(one_chip, kernel):
    W, D, n = 4, 8192, 4096
    f32 = jnp.float32
    calls = {
        "block_significance_norms": (
            lambda x: bs.block_norms(x, interpret=False), ((64, 256), f32)),
        "block_significance_filter": (
            lambda x, m: bs.masked_filter(x, m, interpret=False),
            ((64, 256), f32), ((64,), jnp.bool_)),
        "fused_adamw": (
            lambda g, m, v, p, c: fa.fused_adamw_flat(
                g, m, v, p, c[0], c[1], lr=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                wd=0.01, interpret=False),
            ((n,), jnp.bfloat16), ((n,), f32), ((n,), f32),
            ((n,), jnp.bfloat16), ((2,), f32)),
        "robust_agg_trimmed_mean": (
            lambda x: robust_agg.trimmed_mean(x, 1, interpret=False),
            ((W, D), f32)),
        "robust_agg_median": (
            lambda x: robust_agg.coordinate_median(x, interpret=False),
            ((W + 1, D), f32)),
        "robust_agg_krum_sqdist": (
            lambda x: robust_agg.krum_pairwise(x, interpret=False),
            ((W, D), f32)),
        "robust_agg_weiszfeld_sqdist": (
            lambda x, z: robust_agg.weiszfeld_step(x, z, 1e-12,
                                                   interpret=False),
            ((W, D), f32), ((D,), f32)),
        "flash_attention_fwd": (
            lambda q, k, v: kflash.flash_attention(q, k, v, interpret=False),
            ((1, 512, CFG.n_heads, CFG.head_dim), jnp.bfloat16),
            ((1, 512, CFG.n_kv_heads, CFG.head_dim), jnp.bfloat16),
            ((1, 512, CFG.n_kv_heads, CFG.head_dim), jnp.bfloat16)),
    }
    calls["robust_agg_weiszfeld_wsum"] = calls["robust_agg_weiszfeld_sqdist"]
    # the backward called as such: under jax.grad the instruction's name
    # takes the transforms' prefix (``transpose_jvp_...``)
    group = (1, CFG.n_kv_heads, CFG.n_heads // CFG.n_kv_heads, 512)
    calls["flash_attention_bwd"] = (
        lambda q, k, v, do, lse, di: kflash._backward(
            q, k, v, do, lse, di, None, 512, 512, False),
        ((*group, CFG.head_dim), jnp.bfloat16),
        ((1, CFG.n_kv_heads, 512, CFG.head_dim), jnp.bfloat16),
        ((1, CFG.n_kv_heads, 512, CFG.head_dim), jnp.bfloat16),
        ((*group, CFG.head_dim), jnp.bfloat16), (group, f32), (group, f32))
    fn, *shapes = calls[kernel]
    txt = _compiled_text(fn, *shapes, sharding=one_chip)
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(", txt), kernel


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_attention_compiles(one_chip, window):
    """The forward and, through ``jax.vjp``, the fused backward compile
    for v5e at S 2048 with smollm's heads, windowed or not."""
    B, S = 1, 2048
    q = ((B, S, CFG.n_heads, CFG.head_dim), jnp.bfloat16)
    kv = ((B, S, CFG.n_kv_heads, CFG.head_dim), jnp.bfloat16)

    def fwd_bwd(q_, k_, v_):
        out, vjp = jax.vjp(
            lambda *a: kflash.flash_attention(*a, window=window,
                                              interpret=False), q_, k_, v_)
        return out, vjp(out)
    txt = _compiled_text(fwd_bwd, q, kv, kv, sharding=one_chip)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        # under jax.vjp the names take the transforms' affixes
        # (``jvp_flash_attention_fwd_``, ``transpose_jvp_..._bwd__``)
        assert re.search(rf"%\w*{kernel}\w*(\.\d+)? = .*tpu_custom_call",
                         txt), kernel


@pytest.fixture(scope="module")
def lm_step_text(one_chip):
    """The compiled train step of a two-layer smollm-135m (its widths and
    head layout, remat over the layer scan) at batch 2 x 1024, where the
    chunked attention's score block is (2, 512, 3, 3, 512)."""
    from repro import optim
    from repro.core import build_train_step, get_strategy
    from repro.core.sharding import make_mesh
    from repro.kernels import ops

    with pytest.MonkeyPatch.context() as mp:
        # the code asks the backend (the CPU here); steer it to the TPU path
        mp.setattr(ops, "default_interpret", lambda: False)
        cfg = dataclasses.replace(CFG, n_layers=2, vocab_size=512)
        mesh = make_mesh((1, 1), ("data", "model"),
                         devices=[next(iter(one_chip.device_set))])
        ts = build_train_step(build_model(cfg, remat=True),
                              optim.adamw(1e-3), get_strategy("allreduce"),
                              mesh)
        batch = {k: jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=s)
                 for k, s in ts.batch_shardings.items()}
        return ts.step_fn.lower(ts.state_sds(), batch).compile().as_text()


def _phase(op_name):
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(jvp(forward))" in op_name:
        return "backward"
    return "forward" if "jvp(forward)" in op_name else None


@pytest.mark.parametrize("kernel,phase", [
    ("flash_attention_fwd", "forward"), ("flash_attention_fwd", "recompute"),
    ("flash_attention_bwd", "backward")])
def test_train_step_attention_is_the_flash_kernel(lm_step_text, kernel,
                                                  phase):
    """On TPU the LM step's attention is a Mosaic call named after its
    kernel, under the ``attention`` scope, in each pass that runs it.
    Remat keeps the forward kernel's output and log-sum-exp, so the
    recompute runs no ``flash_attention_fwd``."""
    found = set()
    for line in lm_step_text.splitlines():
        if not re.match(rf"\s*(ROOT )?%{kernel}(\.\d+)? = ", line):
            continue
        assert "tpu_custom_call" in line
        op = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "/attention/" in op, op
        found.add(_phase(op))
    assert (phase in found) is (phase != "recompute"), found


def test_train_step_holds_no_score_block(lm_step_text):
    """No fusion of the step computes over a whole (B, 512, G, KV, 512)
    f32 score block, as the chunked attention's dots do."""
    blocks = [ln[:120] for ln in lm_step_text.splitlines()
              if " fusion(" in ln and "f32[2,512,3,3,512]" in ln]
    assert not blocks, blocks
