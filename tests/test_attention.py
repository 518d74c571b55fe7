"""Chunked flash attention (``models/attention.py``): its masking against a
dense float32 reference, and a lowering check that the differentiated
model never stacks mask or fill arrays of the full score-block shape.
The Pallas flash kernels (``kernels/flash_attention.py``, interpret mode
here) against both, and the rule that picks them.  Remat over the layer
scan keeps the kernel's output and log-sum-exp, so the gradient runs no
kernel forward again."""
import dataclasses
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import losses
from repro.kernels import flash_attention as kflash
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import attention, build_model
from repro.models.attention import chunked_attention


def dense_attention(q, k, v, *, causal, window):
    """Plain float32 masked softmax attention over the whole sequence."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qr, k,
                   precision="highest") / (hd ** 0.5)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision="highest")
    return o.reshape(B, Sq, H, hd)


@pytest.mark.parametrize("Sq,Skv,causal,window", [
    (256, 256, True, None),      # causal, sequence a multiple of the chunk
    (256, 256, True, 48),        # causal with a sliding window
    (256, 192, False, None),     # bidirectional / cross attention
    (200, 200, True, None),      # padding: 200 is no multiple of 64
    (200, 136, False, None),     # padding on both sides, no causal mask
])
def test_chunked_attention_matches_dense(Sq, Skv, causal, window):
    rs = np.random.RandomState(Sq + Skv)
    B, H, KV, hd = 2, 6, 2, 32
    q = jnp.asarray(rs.randn(B, Sq, H, hd), jnp.float32)
    k = jnp.asarray(rs.randn(B, Skv, KV, hd), jnp.float32)
    v = jnp.asarray(rs.randn(B, Skv, KV, hd), jnp.float32)
    w = jnp.asarray(rs.randn(B, Sq, H, hd), jnp.float32)

    def chunked(q, k, v):
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_chunk=64, kv_chunk=64)

    def dense(q, k, v):
        return dense_attention(q, k, v, causal=causal, window=window)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * w),
                        argnums=(0, 1, 2))(q, k, v)

    np.testing.assert_allclose(np.asarray(chunked(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    for name, got, want in zip("qkv", grads(chunked), grads(dense)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("remat", [True, False])
def test_grad_stacks_no_full_mask_blocks(remat):
    """Under ``jax.grad``, a mask or fill broadcast to the score block's
    shape depends on no parameter, so JAX hoists it out of the layer scan
    as a stack of blocks: (nq, nk, B, q_chunk, G, KV, kv_chunk) at the
    scan, (nq, B, ...) at the map over q blocks.  None may be lowered."""
    cfg = dataclasses.replace(get_config("smollm-135m"), n_layers=2,
                              d_model=384, n_heads=6, n_kv_heads=2,
                              head_dim=64, d_ff=768, vocab_size=512)
    model = build_model(cfg, remat=remat)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S = 2, 2048
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def loss(p, b):
        logits, aux = model.apply(p, b)
        return losses.softmax_cross_entropy(logits, b["labels"]) + aux

    text = jax.jit(jax.grad(loss)).lower(params, batch).as_text()
    # the score block itself, (B, q_chunk, G, KV, kv_chunk), must be there
    assert "tensor<2x512x3x2x512xf32>" in text
    stacked = re.findall(
        r"tensor<(?:4x4|4)x2x512x3x2x512x(?:f32|i1)>", text)
    assert not stacked, sorted(set(stacked))


def _smollm_heads(S, dtype, seed=0):
    """q, k, v and an output weighting at smollm-135m's head layout: 9
    query heads over 3 kv heads of 64, batch 2."""
    rs = np.random.RandomState(seed + S)
    B, H, KV, hd = 2, 9, 3, 64
    q = jnp.asarray(rs.randn(B, S, H, hd), dtype)
    k = jnp.asarray(rs.randn(B, S, KV, hd), dtype)
    v = jnp.asarray(rs.randn(B, S, KV, hd), dtype)
    w = jnp.asarray(rs.randn(B, S, H, hd), jnp.float32)
    return q, k, v, w


def _value_and_grads(f, q, k, v, w):
    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + vjp(w.astype(out.dtype))


def _rel(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("S,window", [(256, None), (512, None), (256, 48),
                                      (512, 100)])
def test_flash_kernel_matches_chunked_and_dense(S, window, dtype, tol):
    """Output, dq, dk and dv of the kernels against ``_flash`` and the dense
    float32 masked softmax.  In bf16 the kernels sit within 1.5 times
    ``_flash``'s own distance from the dense reference: they round ``p``
    and ``ds`` to bf16 before their dots, as the MXU takes them, where
    ``_flash``'s backward multiplies in f32 on the CPU."""
    q, k, v, w = _smollm_heads(S, dtype)
    kernel = _value_and_grads(
        lambda *a: kops.flash_attention(*a, window=window), q, k, v, w)
    chunk = _value_and_grads(
        lambda *a: attention._flash(*a, True, window, 128, 128), q, k, v, w)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    dense = _value_and_grads(
        lambda *a: dense_attention(*a, causal=True, window=window), *f32, w)
    np.testing.assert_allclose(np.asarray(kref.flash_attention(
        *f32, window=window)), np.asarray(dense[0]), rtol=1e-5, atol=1e-5)
    for name, got, c, want in zip(("out", "dq", "dk", "dv"), kernel, chunk,
                                  dense):
        assert got.dtype == dtype, name
        assert _rel(got, want) < tol, (name, _rel(got, want))
        assert _rel(got, want) < 1.5 * _rel(c, want) + 1e-6, name


@pytest.mark.parametrize("S,G,dtype,want", [
    (2048, 3, jnp.bfloat16, True), (8192, 3, jnp.bfloat16, True),
    (16384, 3, jnp.bfloat16, False), (8192, 3, jnp.float32, False)])
def test_flash_kernel_fits_vmem(S, G, dtype, want):
    """The backward holds whole q, dO and dQ strips: smollm's group of 3
    heads fits up to 8192 tokens in bf16 (a v5e compile of 16384 runs out
    of VMEM)."""
    assert kflash.flash_attention_fits(S, G, 64, dtype) is want


def test_flash_kernel_window_hides_what_causal_shows():
    q, k, v, _ = _smollm_heads(256, jnp.float32)
    full = kflash.flash_attention(q, k, v, interpret=True)
    local = kflash.flash_attention(q, k, v, window=48, interpret=True)
    np.testing.assert_allclose(np.asarray(full[:, :48]),
                               np.asarray(local[:, :48]), rtol=1e-6)
    assert float(jnp.max(jnp.abs(full[:, 48:] - local[:, 48:]))) > 0.1


def _shapes(Sq, Skv=None):
    f = lambda S, n: jax.ShapeDtypeStruct((2, S, n, 64), jnp.bfloat16)
    return f(Sq, 9), f(Skv or Sq, 3), f(Skv or Sq, 3)


def test_dispatch_keeps_chunked_off_tpu():
    q, k, _ = _shapes(256)
    assert not attention.uses_flash_kernel(q, k, True)


@pytest.mark.parametrize("case,want", [
    ("causal", True), ("cross", False), ("bidirectional", False),
    ("off the block", False), ("past VMEM", False), ("model axis 2", False),
    ("model axis 1", True)])
def test_dispatch_on_tpu(case, want, monkeypatch):
    """On TPU (steered here through the backend helper the kernels use) the
    kernels take causal self-attention whose length is a multiple of 128
    and whose working set fits VMEM, where no Auto mesh axis would split
    them; everything else stays on ``_flash``."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    q, k, _ = {"cross": _shapes(256, 128), "off the block": _shapes(200),
               "past VMEM": _shapes(16384)}.get(case, _shapes(256))
    causal = case != "bidirectional"
    sizes = {"model axis 2": (1, 2), "model axis 1": (1, 1)}.get(case)
    if sizes is None:
        assert attention.uses_flash_kernel(q, k, causal) is want
        return
    auto = jax.sharding.AxisType.Auto
    mesh = jax.sharding.AbstractMesh(sizes, ("data", "model"),
                                     axis_types=(auto, auto))
    with jax.sharding.use_abstract_mesh(mesh):
        assert attention.uses_flash_kernel(q, k, causal) is want


@pytest.mark.parametrize("causal", [True, False])
def test_chunked_attention_runs_the_kernels_where_dispatch_says(
        causal, monkeypatch):
    """Under ``jax.grad`` on TPU, causal self-attention is the kernel pair
    (forward and fused backward); bidirectional attention stays XLA."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    q, k, v = _shapes(256)

    def loss(q, k, v):
        o = chunked_attention(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        assert (kernel in text) is causal, kernel


def _eqns(jaxpr, in_remat=False):
    """Each equation of ``jaxpr`` and of the jaxprs inside it, with
    whether it lies in a ``remat2`` region (remat's recompute, and the
    backward that the transpose puts beside it)."""
    for e in jaxpr.eqns:
        yield e, in_remat
        inner = in_remat or e.primitive.name == "remat2"
        for v in e.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, jax.extend.core.ClosedJaxpr):
                    x = x.jaxpr
                if isinstance(x, jax.extend.core.Jaxpr):
                    yield from _eqns(x, inner)


def test_remat_reruns_no_flash_kernel_forward(monkeypatch):
    """On TPU (steered) the loss gradient of a two-layer smollm-135m (its
    widths and head layout, remat over the layer scan, batch 2 x 1024)
    runs ``flash_attention_fwd`` once, in the forward, and never in the
    remat region."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("smollm-135m"), n_layers=2,
                              vocab_size=512)
    model = build_model(cfg, remat=True)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1024), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 1024), jnp.int32)}

    def loss(p, b):
        logits, aux = model.apply(p, b)
        return losses.softmax_cross_entropy(logits, b["labels"]) + aux

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr
    where = [r for e, r in _eqns(jaxpr)
             if e.primitive.name == "pallas_call"
             and e.params["name"] == "flash_attention_fwd"]
    assert where == [False]
