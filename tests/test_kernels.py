"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, robust_agg

RS = np.random.RandomState(0)


# ---------------------------------------------------------------------------
# causal (optionally windowed) flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # (B, S, H, KV, hd, window, dtype)
    (2, 256, 4, 2, 64, None, jnp.float32),
    (1, 512, 8, 8, 128, 128, jnp.float32),
    (2, 256, 4, 1, 32, 64, jnp.bfloat16),
    (1, 128, 2, 2, 64, None, jnp.bfloat16),
    (1, 256, 6, 3, 32, 32, jnp.float32),
    (3, 128, 4, 4, 128, 96, jnp.float32),
]


@pytest.mark.parametrize("B,S,H,KV,hd,window,dtype", FLASH_CASES)
def test_flash_kernel_vs_ref(B, S, H, KV, hd, window, dtype):
    q = jnp.asarray(RS.randn(B, S, H, hd), dtype)
    k = jnp.asarray(RS.randn(B, S, KV, hd), dtype)
    v = jnp.asarray(RS.randn(B, S, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, window=window)
    expect = ref.flash_attention(q, k, v, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


def test_flash_kernel_grad():
    B, S, H, KV, hd = 1, 256, 4, 2, 64
    q = jnp.asarray(RS.randn(B, S, H, hd), jnp.float32)
    k = jnp.asarray(RS.randn(B, S, KV, hd), jnp.float32)
    v = jnp.asarray(RS.randn(B, S, KV, hd), jnp.float32)
    f1 = lambda *a: jnp.sum(jnp.tanh(ops.flash_attention(*a, window=64)))
    f2 = lambda *a: jnp.sum(jnp.tanh(ref.flash_attention(*a, window=64)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# block significance
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,b", [(100, 128), (1000, 256), (7, 512),
                                 (513, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_norms_vs_ref(n, b, dtype):
    x = jnp.asarray(RS.randn(n, b), dtype)
    from repro.kernels.block_significance import block_norms
    got = block_norms(x, interpret=True)
    want = ref.block_norms(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("n,b", [(100, 128), (513, 64)])
@pytest.mark.parametrize("threshold", [0.5, 1.0, 2.0])
def test_block_significance_vs_ref(n, b, threshold):
    x = jnp.asarray(RS.randn(n, b), jnp.float32)
    got = ops.block_significance(x, threshold)
    want = ref.block_significance(x, threshold)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,b", [(64, 128), (257, 256)])
def test_significance_filter_vs_ref(n, b):
    x = jnp.asarray(RS.randn(n, b), jnp.float32)
    kept, resid, mask = ops.significance_filter(x, threshold=1.0)
    k2, r2, m2 = ref.significance_filter(x, threshold=1.0)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(m2))
    np.testing.assert_allclose(np.asarray(kept), np.asarray(k2),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(resid), np.asarray(r2),
                               atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_filter_preserves_dtype(dtype):
    """bf16 gradients must come back bf16 from kernel AND oracle — the
    kernel used to pin out_shape to fp32, silently doubling the
    filtered-sync wire bytes."""
    from repro.kernels import block_significance as bs
    x = jnp.asarray(RS.randn(257, 256), dtype)
    mask = ref.block_significance(x, 1.0)
    kept, resid = bs.masked_filter(x, mask, interpret=True)
    k2, r2 = ref.masked_filter(x, mask)
    assert kept.dtype == dtype and resid.dtype == dtype
    assert k2.dtype == dtype and r2.dtype == dtype
    # both paths filter in fp32 and round once to the input dtype, so
    # they agree bit-for-bit even in bf16
    np.testing.assert_array_equal(np.asarray(kept, np.float32),
                                  np.asarray(k2, np.float32))
    np.testing.assert_array_equal(np.asarray(resid, np.float32),
                                  np.asarray(r2, np.float32))


@pytest.mark.parametrize("n,b", [(64, 128), (1000, 256)])
def test_significance_filter_conservation(n, b):
    x = jnp.asarray(RS.randn(n, b), jnp.float32)
    kept, resid, mask = ops.significance_filter(x, threshold=1.0)
    k2, r2 = ref.masked_filter(x, mask)
    np.testing.assert_allclose(np.asarray(kept), np.asarray(k2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(resid), np.asarray(r2), atol=1e-6)
    # error feedback conservation: kept + residual == input
    np.testing.assert_allclose(np.asarray(kept + resid), np.asarray(x),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# fused adamw
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [100, 4096, 65537])
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
def test_fused_adamw_vs_ref(n, pdtype):
    g = jnp.asarray(RS.randn(n), pdtype)
    m = jnp.asarray(RS.randn(n) * 0.01, jnp.float32)
    v = jnp.abs(jnp.asarray(RS.randn(n) * 0.01, jnp.float32))
    p = jnp.asarray(RS.randn(n), pdtype)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.01)
    u1, m1, v1 = ops.fused_adamw(g, m, v, p, c1=jnp.asarray(0.1),
                                 c2=jnp.asarray(0.05), **kw)
    u2, m2, v2 = ref.fused_adamw_flat(g, m, v, p, jnp.asarray(0.1),
                                      jnp.asarray(0.05), **kw)
    np.testing.assert_allclose(np.asarray(u1, np.float32),
                               np.asarray(u2.astype(pdtype), np.float32),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-5)


@pytest.mark.parametrize("n,tile", [
    (100, (8, 16)),     # n < one tile
    (256, (8, 16)),     # exact tile multiple, empty remainder
    (257, (8, 16)),     # one past a tile boundary
    (7, (16, 128)),     # n smaller than a single row
])
def test_fused_adamw_tile_edges(n, tile):
    g = jnp.asarray(RS.randn(n), jnp.float32)
    m = jnp.asarray(RS.randn(n) * 0.01, jnp.float32)
    v = jnp.abs(jnp.asarray(RS.randn(n) * 0.01, jnp.float32))
    p = jnp.asarray(RS.randn(n), jnp.float32)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.01)
    c1, c2 = jnp.asarray(0.1), jnp.asarray(0.05)
    from repro.kernels.fused_adamw import fused_adamw_flat
    got = fused_adamw_flat(g, m, v, p, c1, c2, tile=tile,
                           interpret=True, **kw)
    want = ref.fused_adamw_flat(g, m, v, p, c1, c2, **kw)
    for a, b in zip(got, want):
        assert a.shape == (n,)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_fused_adamw_optimizer_path():
    """optim.adamw(use_fused=True) must match the unfused optimizer."""
    from repro import optim
    params = {"a": jnp.asarray(RS.randn(33, 7), jnp.float32),
              "b": jnp.asarray(RS.randn(5), jnp.float32)}
    grads = jax.tree.map(lambda p: jnp.asarray(RS.randn(*p.shape),
                                               jnp.float32), params)
    o1 = optim.adamw(1e-3, weight_decay=0.01)
    o2 = optim.adamw(1e-3, weight_decay=0.01, use_fused=True)
    s1, s2 = o1.init(params), o2.init(params)
    u1, s1 = o1.update(grads, s1, params)
    u2, s2 = o2.update(grads, s2, params)
    for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# RWKV6 chunked wkv
# ---------------------------------------------------------------------------
WKV_CASES = [
    # (B, T, H, N, chunk, dtype)
    (2, 64, 2, 32, 16, jnp.float32),
    (1, 128, 4, 64, 64, jnp.float32),
    (2, 96, 3, 16, 32, jnp.float32),   # chunk auto-halves to divide T
    (1, 64, 2, 32, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("B,T,H,N,chunk,dtype", WKV_CASES)
def test_wkv6_kernel_vs_exact_recurrence(B, T, H, N, chunk, dtype):
    r = jnp.asarray(RS.randn(B, T, H, N) * 0.5, dtype)
    k = jnp.asarray(RS.randn(B, T, H, N) * 0.5, dtype)
    v = jnp.asarray(RS.randn(B, T, H, N) * 0.5, dtype)
    logw = -jnp.exp(jnp.asarray(RS.randn(B, T, H, N) * 0.5 - 2.0,
                                jnp.float32)).astype(dtype)
    u = jnp.asarray(RS.randn(H, N) * 0.5, dtype)
    got = ops.wkv6(r, k, v, logw, u, chunk=chunk)
    want = ref.wkv6(r, k, v, logw, u)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


# ---------------------------------------------------------------------------
# robust aggregation kernels (trimmed mean / median / krum / weiszfeld)
# ---------------------------------------------------------------------------
# (W, trailing shape, dtype) — exercising every tiling regime:
#  D < one lane (pad-to-128), D == one tile (empty remainder),
#  D % tile != 0 (one-past-boundary and ragged), odd D, bf16 inputs,
#  and a trailing shape that must round-trip.
RA_CASES = [
    (5, (1000,), jnp.float32),     # ragged remainder inside one tile
    (8, (513,), jnp.float32),      # one past a 512-tile boundary
    (16, (127,), jnp.float32),     # D < one lane: pad to 128
    (3, (512,), jnp.float32),      # exact tile, empty remainder
    (12, (131,), jnp.bfloat16),    # odd (prime) D + bf16 stack
    (4, (7, 9), jnp.float32),      # trailing shape round-trip
]
# small tile so multi-tile grids actually run in the interpreter
RA_TILE = 512


def _ra_stack(W, shape, dtype):
    x = RS.randn(W, *shape) * RS.choice([1.0, 30.0], size=(W,) + (1,) *
                                        len(shape))
    return jnp.asarray(x, dtype)


def _ra_tols(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("W,shape,dtype", RA_CASES)
@pytest.mark.parametrize("trim", [1, 2])
def test_robust_trimmed_mean_kernel_vs_ref(W, shape, dtype, trim):
    if W <= 2 * trim:
        pytest.skip("W too small for this trim")
    x = _ra_stack(W, shape, dtype)
    want = np.asarray(ref.trimmed_mean(x, trim))
    fused = robust_agg.trimmed_mean(x, trim, tile_d=RA_TILE)
    interp = robust_agg.trimmed_mean(x, trim, tile_d=RA_TILE,
                                     interpret=True)
    assert fused.shape == x.shape[1:]
    np.testing.assert_allclose(np.asarray(fused), want, **_ra_tols(dtype))
    np.testing.assert_allclose(np.asarray(interp), want,
                               **_ra_tols(dtype))


@pytest.mark.parametrize("W,shape,dtype", RA_CASES)
def test_robust_coordinate_median_kernel_vs_ref(W, shape, dtype):
    x = _ra_stack(W, shape, dtype)
    want = np.asarray(ref.coordinate_median(x))
    fused = robust_agg.coordinate_median(x, tile_d=RA_TILE)
    interp = robust_agg.coordinate_median(x, tile_d=RA_TILE,
                                          interpret=True)
    assert fused.shape == x.shape[1:]
    np.testing.assert_allclose(np.asarray(fused), want, **_ra_tols(dtype))
    np.testing.assert_allclose(np.asarray(interp), want,
                               **_ra_tols(dtype))


@pytest.mark.parametrize("W,shape,dtype", RA_CASES)
def test_robust_krum_pairwise_kernel_vs_ref(W, shape, dtype):
    x = _ra_stack(W, shape, dtype)
    want = np.asarray(ref.krum_pairwise(x))
    scale = want.max() + 1e-6
    fused = np.asarray(robust_agg.krum_pairwise(x, tile_d=RA_TILE))
    interp = np.asarray(robust_agg.krum_pairwise(x, tile_d=RA_TILE,
                                                 interpret=True))
    # Gram-form cancellation: compare relative to the matrix scale
    rel = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    assert np.max(np.abs(fused - want)) / scale < rel
    assert np.max(np.abs(interp - want)) / scale < rel
    assert (fused >= 0).all() and (interp >= 0).all()


@pytest.mark.parametrize("W,shape,dtype", RA_CASES)
def test_robust_weiszfeld_step_kernel_vs_ref(W, shape, dtype):
    x = _ra_stack(W, shape, dtype)
    flat = np.asarray(x, np.float32).reshape(W, -1)
    z = jnp.asarray(np.median(flat, axis=0))
    floor = 1e-12 * max(np.linalg.norm(flat, axis=1).max(), 1e-12)
    want = np.asarray(ref.weiszfeld_step(x, z, floor))
    fused = robust_agg.weiszfeld_step(x, z, floor, tile_d=RA_TILE)
    cached = robust_agg.weiszfeld_step(
        x, z, floor, row_sqnorms=jnp.sum(jnp.asarray(flat) ** 2, axis=1),
        tile_d=RA_TILE)
    interp = robust_agg.weiszfeld_step(x, z, floor, tile_d=RA_TILE,
                                       interpret=True)
    for got in (fused, cached, interp):
        np.testing.assert_allclose(np.asarray(got), want,
                                   **_ra_tols(dtype))


def test_robust_agg_kernels_validate_inputs():
    x = jnp.ones((4, 16))
    with pytest.raises(ValueError):
        robust_agg.trimmed_mean(x, trim=0)
    with pytest.raises(ValueError):
        robust_agg.trimmed_mean(x, trim=2)       # W <= 2*trim
    with pytest.raises(ValueError):
        robust_agg.weiszfeld_step(x, jnp.ones(15), 1e-12)  # z length


# ---------------------------------------------------------------------------
# kernel bench (BENCH_kernels.json): deterministic spec + floors
# ---------------------------------------------------------------------------
def test_entry_io_bytes_pins_compiled_io():
    from repro.costmodel.hlo_analysis import entry_io_bytes
    fn = jax.jit(lambda x: jnp.sum(x, axis=0))
    hlo = fn.lower(jnp.zeros((8, 4096), jnp.float32)).compile().as_text()
    assert entry_io_bytes(hlo) == (8 * 4096 * 4, 4096 * 4)
    assert entry_io_bytes("no entry header here") == (0, 0)


def test_kernel_bench_spec_is_deterministic():
    """The hashed sections of BENCH_kernels.json are a pure function of
    (configs, SEED): same case table on re-derivation, timings and the
    machine probe excluded from the content hash."""
    from benchmarks import kernel_bench as kb
    a = kb.kernel_cases(quick=True)
    assert a == kb.kernel_cases(quick=True)
    # every public kernel appears in both modes; krum's oracle-memory
    # cap stays tighter than the general cap
    full = kb.kernel_cases(quick=False)
    assert {c["kernel"] for c in full} == {c["kernel"] for c in a}
    krum_d = max(c["D"] for c in full if c["kernel"] == "krum_pairwise")
    other_d = max(c["D"] for c in full if c["kernel"] == "trimmed_mean")
    assert krum_d < other_d
    payload = {"benchmark": "kernel_bench", "quick": True,
               "seed": kb.SEED, "spec": a,
               "probe": {"stream_bytes_per_s": 123.0},
               "results": [{"kernel_s": 1.0}]}
    h = kb._content_hash(payload)
    payload["probe"]["stream_bytes_per_s"] = 456.0
    payload["results"] = []
    assert kb._content_hash(payload) == h


@pytest.mark.slow
def test_kernel_bench_quick_floors(tmp_path):
    """Every --quick row clears its per-backend roofline and speedup
    floors, and the stored content hash re-derives from the payload's
    deterministic sections."""
    import json
    from benchmarks import kernel_bench as kb
    rows = []
    path = tmp_path / "BENCH_kernels.json"
    kb.run(rows, quick=True, json_path=str(path))
    payload = json.loads(path.read_text())
    assert payload["results"]
    misses = [r for r in payload["results"] if not r["passed"]]
    assert not misses, misses
    clone = dict(payload)
    clone.pop("content_hash")
    assert payload["content_hash"] == kb._content_hash(clone)
    for r in payload["results"]:
        assert r["entry_param_bytes"] > 0 and r["entry_result_bytes"] > 0
