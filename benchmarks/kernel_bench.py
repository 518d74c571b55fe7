"""Kernel micro-bench: every public Pallas kernel vs its ref.py
oracle, roofline-gated.

Two sections, recorded in ``BENCH_kernels.json``:

  1. *probe* — measured stream bandwidth of this container (a jitted
     fp32 triad over ~64 MB), the denominator of every roofline floor.
  2. *kernels* — one row per public kernel entry point, timed on its
     **production path for the bench backend** against its pure-jnp
     oracle (``repro.kernels.ref``) across realistic shapes derived
     from the registered model configs (CNN fleets for the [W, D]
     robust-aggregation stacks, transformer geometry for attention /
     wkv; oversize dimensions are capped with the truncation logged in
     the row).  Each row must clear two floors:

       roofline_frac >= floor   measured time vs the bytes-touched /
                                stream-bandwidth lower bound (the
                                ``costmodel.hlo_analysis.entry_io_bytes``
                                compiler-confirmed IO is recorded
                                alongside the analytic count), and
       speedup >= floor         vs the jitted oracle.

     Off-TPU the production path is the kernel's fused-jnp twin where
     one exists (the robust-aggregation set, ``interpret=None``
     auto-dispatch) or the best jnp formulation the repo ships (windowed
     causal attention -> ``models.attention.chunked_attention``, against
     ``ref.flash_attention``).  Kernels whose CPU
     production path IS the oracle (fused_adamw, wkv6, block_norms,
     masked_filter — their win is Mosaic-only) time jit(ref) against
     itself and carry a noise-tolerant parity-class floor of 0.5x;
     the roofline floor still gates them.
     Floors are therefore per-kernel *and* per-backend, recorded in the
     deterministic payload.

Everything except timings is a pure function of (configs, shapes,
SEED): the payload records a content hash over the deterministic
``spec`` section, and a slow-marked test in ``tests/test_kernels.py``
re-runs ``--quick`` and asserts every row passes its floors.

Rows: kernels/<name>/<metric>,value,notes
Usage:
    PYTHONPATH=src python -m benchmarks.kernel_bench [--quick]
        [--only probe|kernels] [--json BENCH_kernels.json]
    PYTHONPATH=src python -m benchmarks.run --only kernels
"""
from __future__ import annotations

import argparse
import hashlib
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

SECTIONS = ("probe", "kernels")
SEED = 0
_REPS = 3


# ---------------------------------------------------------------------------
# timing + probe
# ---------------------------------------------------------------------------
def _timed(fn, *args) -> float:
    """Median-of-_REPS wall-clock of a jitted callable (one warmup)."""
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def stream_bandwidth_bytes_per_s(n: int = 16 * 2**20) -> float:
    """Measured triad bandwidth: y = a*x + y over fp32 length n
    (3 array touches per element = 12n bytes per call)."""
    x = jnp.arange(n, dtype=jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    triad = jax.jit(lambda x, y: 2.5 * x + y)
    t = _timed(triad, x, y)
    return 12.0 * n / t


def bench_probe(csv_rows) -> dict:
    bw = stream_bandwidth_bytes_per_s()
    backend = jax.default_backend()
    csv_rows.append(("kernels/probe/backend", 0, backend))
    csv_rows.append(("kernels/probe/stream_gb_per_s", bw / 1e9,
                     "fp32 triad, 64 MB working set"))
    return dict(backend=backend, stream_bytes_per_s=bw)


# ---------------------------------------------------------------------------
# shapes from the registered configs
# ---------------------------------------------------------------------------
def _cnn_params(name: str) -> int:
    from repro.configs.base import get_config
    from repro.models.cnn import build_cnn
    model = build_cnn(get_config(name))
    params = model.init(jax.random.PRNGKey(0))
    return int(sum(x.size for x in jax.tree.leaves(params)))


def _capped(d: int, cap: int):
    """(capped D, truncation note)."""
    if d <= cap:
        return d, ""
    return cap, f"D truncated {d:,} -> {cap:,}"


def kernel_cases(quick: bool):
    """One spec dict per (kernel, shape): deterministic, hash-covered.

    [W, D] stacks: D from the serverless CNN configs (what SPIRT/MLLess
    actually aggregate) plus the smallest registered transformer; W
    from the paper's fleet sizes.  Oversize D (and krum's W^2-memory
    oracle) are capped with the truncation logged.
    """
    from repro.configs.base import get_config
    from repro.costmodel.flops import param_count

    # krum's oracle materializes [W, W, D] fp32, so its cap is tighter
    # (W=16 at 2**18 is already a 268 MB broadcast)
    cap = 2**18 if quick else 2**22
    krum_cap = 2**16 if quick else 2**18
    d_mobile = _cnn_params("mobilenet-cifar")
    d_resnet = _cnn_params("resnet18-cifar")
    d_smollm = param_count(get_config("smollm-135m"))

    cases = []

    def robust(kernel, floors, shapes, note=""):
        for cfg_name, w, d_full in shapes:
            this_cap = krum_cap if kernel == "krum_pairwise" else cap
            d, trunc = _capped(d_full, this_cap)
            cases.append(dict(
                kernel=kernel, config=cfg_name, W=w, D=d,
                trunc=trunc or note, floors=floors,
                cpu_path="fused-jnp-twin"))

    fleets = [("mobilenet-cifar", 8, d_mobile),
              ("resnet18-cifar", 16, d_resnet),
              ("smollm-135m", 12, d_smollm)]
    robust("trimmed_mean", dict(speedup=2.0, roofline_frac=0.05), fleets)
    robust("coordinate_median", dict(speedup=1.2, roofline_frac=0.02),
           fleets)
    robust("krum_pairwise", dict(speedup=2.0, roofline_frac=0.05),
           fleets)
    robust("weiszfeld_step", dict(speedup=1.1, roofline_frac=0.05),
           fleets)

    n, trunc = _capped(d_mobile, cap)
    cases.append(dict(kernel="fused_adamw_flat", config="mobilenet-cifar",
                      n=n, trunc=trunc,
                      floors=dict(speedup=0.5, roofline_frac=0.05),
                      cpu_path="oracle-jit"))

    # chunked attention only beats the naive S x S ref once S is large
    # enough that the full score matrix dominates; below ~1k it loses,
    # so even --quick stays at S=1024
    smollm = get_config("smollm-135m")
    S = 1024 if quick else 2048
    win = min(smollm.window, S // 4)
    cases.append(dict(
        kernel="chunked_attention", config="smollm-135m", B=1, S=S,
        H=smollm.n_heads, KV=smollm.n_kv_heads, hd=smollm.head_dim,
        window=win,
        trunc=f"window capped {smollm.window} -> {win} (S={S})",
        floors=dict(speedup=1.0, roofline_frac=0.002),
        cpu_path="chunked-jnp"))

    rwkv = get_config("rwkv6-7b")
    T = 256 if quick else 1024
    H = 4 if quick else 8
    cases.append(dict(
        kernel="wkv6_chunked", config="rwkv6-7b", B=1, T=T, H=H,
        N=rwkv.head_dim,
        trunc=f"heads capped {rwkv.n_heads} -> {H}",
        floors=dict(speedup=0.5, roofline_frac=0.001),
        cpu_path="oracle-jit"))

    nb = 1024 if quick else 4096
    blk = 1024
    for kernel in ("block_norms", "masked_filter"):
        cases.append(dict(kernel=kernel, config="mobilenet-cifar",
                          n_blocks=nb, block=blk, trunc="",
                          floors=dict(speedup=0.5, roofline_frac=0.05),
                          cpu_path="oracle-jit"))
    return cases


# ---------------------------------------------------------------------------
# per-kernel bench/ref callables + analytic bytes
# ---------------------------------------------------------------------------
def _stack(rng, w, d):
    x = rng.standard_normal((w, d), dtype=np.float32)
    x[0] *= 50.0                        # one outlier row, like an attack
    return jnp.asarray(x)


def _build(case, rng):
    """Returns (bench_fn, ref_fn, args, bytes_touched) — both callables
    un-jitted here; the caller jits uniformly."""
    from repro.kernels import ref, robust_agg
    k = case["kernel"]
    f4 = 4  # fp32
    if k in ("trimmed_mean", "coordinate_median", "krum_pairwise",
             "weiszfeld_step"):
        w, d = case["W"], case["D"]
        x = _stack(rng, w, d)
        if k == "trimmed_mean":
            return (lambda s: robust_agg.trimmed_mean(s, 1),
                    lambda s: ref.trimmed_mean(s, 1), (x,),
                    (w + 1) * d * f4)
        if k == "coordinate_median":
            return (robust_agg.coordinate_median,
                    ref.coordinate_median, (x,), (w + 1) * d * f4)
        if k == "krum_pairwise":
            return (robust_agg.krum_pairwise, ref.krum_pairwise, (x,),
                    w * d * f4)
        z = jnp.asarray(np.median(np.asarray(x), axis=0))
        sq = jnp.sum(x * x, axis=1)
        floor = 1e-12 * float(np.linalg.norm(np.asarray(x), axis=1).max())
        return (lambda s, z_, sq_: robust_agg.weiszfeld_step(
                    s, z_, floor, row_sqnorms=sq_),
                lambda s, z_, sq_: ref.weiszfeld_step(s, z_, floor),
                (x, z, sq), (2 * w + 1) * d * f4)
    if k == "fused_adamw_flat":
        n = case["n"]
        g = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        m = jnp.asarray(rng.standard_normal(n, dtype=np.float32) * 0.01)
        v = jnp.abs(jnp.asarray(
            rng.standard_normal(n, dtype=np.float32) * 0.01))
        p = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.01)
        fn = lambda *a: ref.fused_adamw_flat(*a, **kw)
        return (fn, fn, (g, m, v, p, jnp.float32(0.1), jnp.float32(0.05)),
                7 * n * f4)
    if k == "chunked_attention":
        from repro.models.attention import chunked_attention
        from repro.kernels import ref as _r
        B, S, H, KV, hd, win = (case[x] for x in
                                ("B", "S", "H", "KV", "hd", "window"))
        q = jnp.asarray(rng.standard_normal((B, S, H, hd),
                                            dtype=np.float32))
        kk = jnp.asarray(rng.standard_normal((B, S, KV, hd),
                                             dtype=np.float32))
        vv = jnp.asarray(rng.standard_normal((B, S, KV, hd),
                                             dtype=np.float32))
        return (lambda q_, k_, v_: chunked_attention(
                    q_, k_, v_, window=win, causal=True),
                lambda q_, k_, v_: _r.flash_attention(q_, k_, v_,
                                                      window=win),
                (q, kk, vv), (2 * B * S * H * hd
                              + 2 * B * S * KV * hd) * f4)
    if k == "wkv6_chunked":
        B, T, H, N = (case[x] for x in ("B", "T", "H", "N"))
        r_ = jnp.asarray(rng.standard_normal((B, T, H, N),
                                             dtype=np.float32) * 0.5)
        kk = jnp.asarray(rng.standard_normal((B, T, H, N),
                                             dtype=np.float32) * 0.5)
        vv = jnp.asarray(rng.standard_normal((B, T, H, N),
                                             dtype=np.float32) * 0.5)
        lw = -jnp.exp(jnp.asarray(rng.standard_normal(
            (B, T, H, N), dtype=np.float32) * 0.5 - 2.0))
        u = jnp.asarray(rng.standard_normal((H, N),
                                            dtype=np.float32) * 0.5)
        return (ref.wkv6, ref.wkv6, (r_, kk, vv, lw, u),
                5 * B * T * H * N * f4)
    if k == "block_norms":
        nb, blk = case["n_blocks"], case["block"]
        x = jnp.asarray(rng.standard_normal((nb, blk),
                                            dtype=np.float32))
        return ref.block_norms, ref.block_norms, (x,), nb * blk * f4
    if k == "masked_filter":
        nb, blk = case["n_blocks"], case["block"]
        x = jnp.asarray(rng.standard_normal((nb, blk),
                                            dtype=np.float32))
        mask = jnp.asarray(rng.standard_normal(nb) > 0.0)
        return (ref.masked_filter, ref.masked_filter, (x, mask),
                3 * nb * blk * f4)
    raise ValueError(f"unknown kernel case {k!r}")


def bench_kernels(csv_rows, quick: bool, stream_bw: float):
    """Returns (spec_rows, result_rows) — spec is deterministic."""
    from repro.costmodel.hlo_analysis import entry_io_bytes
    spec, results = [], []
    for case in kernel_cases(quick):
        rng = np.random.default_rng(SEED)
        bench_fn, ref_fn, args, touched = _build(case, rng)
        jb, jr = jax.jit(bench_fn), jax.jit(ref_fn)
        pb, rb = entry_io_bytes(jb.lower(*args).compile().as_text())
        t_k = _timed(jb, *args)
        t_r = _timed(jr, *args)
        floor_s = touched / stream_bw
        frac = floor_s / t_k if t_k > 0 else 0.0
        speedup = t_r / t_k if t_k > 0 else 0.0
        floors = case["floors"]
        ok = (frac >= floors["roofline_frac"]
              and speedup >= floors["speedup"])
        label = "/".join(
            str(case[x]) for x in ("kernel", "config") if x in case)
        spec.append({**{k: v for k, v in case.items()},
                     "bytes_touched": touched})
        results.append(dict(
            kernel=case["kernel"], config=case["config"],
            kernel_s=t_k, ref_s=t_r, speedup=speedup,
            roofline_floor_s=floor_s, roofline_frac=frac,
            entry_param_bytes=pb, entry_result_bytes=rb,
            passed=bool(ok)))
        csv_rows.append((f"kernels/{label}/speedup", speedup,
                         f"floor {floors['speedup']}x; "
                         f"path {case['cpu_path']}"))
        csv_rows.append((f"kernels/{label}/roofline_frac", frac,
                         f"floor {floors['roofline_frac']}; "
                         f"kernel {t_k * 1e3:.1f}ms vs "
                         f"stream floor {floor_s * 1e3:.1f}ms"))
        if not ok:
            csv_rows.append((f"kernels/{label}/_FLOOR_MISS", 1,
                             f"speedup {speedup:.2f} "
                             f"frac {frac:.4f}"))
    n_pass = sum(r["passed"] for r in results)
    csv_rows.append(("kernels/rows_passed", n_pass,
                     f"of {len(results)}"))
    return spec, results


# ---------------------------------------------------------------------------
# payload
# ---------------------------------------------------------------------------
def _jsonable(x):
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (bool, np.bool_)):
        return bool(x)
    if isinstance(x, (np.floating, float)):
        f = float(x)
        return f if math.isfinite(f) else None
    if isinstance(x, (np.integer, int)):
        return int(x)
    return x


def _content_hash(payload: dict) -> str:
    """Hash of the deterministic sections (probe + timings excluded) —
    the bit-reproducibility receipt the tests re-derive."""
    det = {k: v for k, v in payload.items()
           if k not in ("probe", "results")}
    blob = json.dumps(_jsonable(det), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def run(csv_rows, *, quick: bool = False,
        json_path: str = "BENCH_kernels.json", only=None):
    sections = SECTIONS if only is None else (only,)
    payload = {"benchmark": "kernel_bench", "quick": quick,
               "seed": SEED}
    stream_bw = None
    if "probe" in sections or "kernels" in sections:
        payload["probe"] = bench_probe(csv_rows)
        stream_bw = payload["probe"]["stream_bytes_per_s"]
    if "kernels" in sections:
        spec, results = bench_kernels(csv_rows, quick, stream_bw)
        payload["spec"] = spec
        payload["results"] = results
    payload["content_hash"] = _content_hash(payload)
    csv_rows.append(("kernels/_content_hash", payload["content_hash"],
                     "sha256[:16] of the deterministic spec"))
    # only a run of ALL sections may replace the TRACKED
    # BENCH_kernels.json (a --only iteration must not overwrite the
    # record with a partial payload); an explicit non-default --json
    # path is always honoured
    if json_path and (only is None or json_path != "BENCH_kernels.json"):
        with open(json_path, "w") as f:
            json.dump(_jsonable(payload), f, indent=2)
        csv_rows.append(("kernels/_json", 1, json_path))
    return csv_rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes (CI)")
    ap.add_argument("--only", default=None, choices=SECTIONS)
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="payload path; with --only, the tracked "
                         "default is left untouched")
    args = ap.parse_args()
    rows = []
    run(rows, quick=args.quick, json_path=args.json, only=args.only)
    print("name,value,derived")
    for name, value, notes in rows:
        print(f"{name},{value},{str(notes).replace(',', ';')}")


if __name__ == "__main__":
    main()
