"""The one generator of training traffic, driven by a traffic file.

A traffic file gives the job: global batch, lengths, how many distinct
batches the pool holds, the sync strategy and the optimizer.  The data is
made on the host from ``--seed`` during set-up; the same seed gives the
same pool.  Batch ``i`` of the run is ``pool[i % len(pool)]``, so the
first steps see rows that all differ.

The two generators are copies of ``repro.data.synthetic``'s
``token_stream``/``lm_batches`` and ``cifar_like`` (the benchmark keeps
its own yardstick), seeded through a ``SeedSequence`` so that any whole
number is a seed.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream for one use of the seed."""
    words = [ord(c) for c in stream]
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2 ** 64, *words]))


def _markov_stream(rng, n_tokens: int, vocab: int, successors: int,
                   follow: float) -> np.ndarray:
    """Order-1 Markov chain: each token prefers ``successors`` next
    tokens, taken with probability ``follow``; otherwise uniform."""
    succ = (np.arange(vocab)[:, None] * 7 + rng.integers(
        0, vocab, size=(vocab, successors))) % vocab
    out = np.empty(n_tokens, np.int32)
    t = int(rng.integers(vocab))
    noise = rng.random(n_tokens)
    choices = rng.integers(0, successors, size=n_tokens)
    uniform = rng.integers(0, vocab, size=n_tokens)
    for i in range(n_tokens):
        out[i] = t
        t = succ[t, choices[i]] if noise[i] < follow else uniform[i]
    return out


def lm_pool(traffic: Dict[str, Any], vocab: int, seed: int) -> List[dict]:
    """``pool_batches`` next-token batches of ``global_batch`` sequences
    of ``seq_len`` tokens, cut from one Markov stream in a seeded order."""
    B, S, n = (traffic["global_batch"], traffic["seq_len"],
               traffic["pool_batches"])
    m = traffic["markov"]
    rng = rng_for(seed, "tokens")
    stream = _markov_stream(rng, n * B * S + 1, vocab, m["successors"],
                            m["follow"])
    order = rng.permutation(n * B)
    pool = []
    for i in range(n):
        rows = order[i * B:(i + 1) * B]
        toks = np.stack([stream[r * S:(r + 1) * S] for r in rows])
        labs = np.stack([stream[r * S + 1:(r + 1) * S + 1] for r in rows])
        pool.append({"tokens": toks, "labels": labs})
    return pool


def image_pool(traffic: Dict[str, Any], config: Dict[str, Any],
               seed: int) -> List[dict]:
    """``pool_batches`` batches of CIFAR-shaped images: class-conditional
    oriented gratings with a colour cast and Gaussian noise, in [-1, 1]."""
    c = config["config"]
    B, n = traffic["global_batch"], traffic["pool_batches"]
    size, ch, k = c["image_size"], c["channels"], c["num_classes"]
    rng = rng_for(seed, "images")
    total = n * B
    labels = rng.integers(0, k, size=total).astype(np.int32)
    yy, xx = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    imgs = np.empty((total, size, size, ch), np.float32)
    thetas = np.linspace(0, np.pi, k, endpoint=False)
    freqs = 2 + np.arange(k) % 5
    for cls in range(k):
        proj = np.cos(thetas[cls]) * xx + np.sin(thetas[cls]) * yy
        tmpl = np.sin(2 * np.pi * freqs[cls] * proj / size)
        base = np.repeat(tmpl[None, :, :, None], ch, axis=3)
        cast = np.sin(np.arange(ch) + cls)[None, None, None, :]
        imgs[labels == cls] = 0.6 * base + 0.25 * cast
    imgs += rng.standard_normal(imgs.shape, np.float32) * \
        traffic["images"]["noise"]
    imgs = np.clip(imgs, -1, 1)
    return [{"images": imgs[i * B:(i + 1) * B],
             "labels": labels[i * B:(i + 1) * B]} for i in range(n)]


def make_pool(traffic: Dict[str, Any], config: Dict[str, Any],
              seed: int) -> List[dict]:
    if traffic["kind"] == "lm":
        return lm_pool(traffic, config["config"]["vocab_size"], seed)
    if traffic["kind"] == "images":
        return image_pool(traffic, config, seed)
    raise ValueError(f"unknown traffic kind {traffic['kind']!r}")


def work_per_step(traffic: Dict[str, Any]) -> int:
    """Tokens (LM) or images (CNN) that one step trains on."""
    if traffic["kind"] == "lm":
        return traffic["global_batch"] * traffic["seq_len"]
    return traffic["global_batch"]
