"""``scale_free``: sources uniform, destinations Zipf-like (popularity ∝
rank^-exponent by inverse-CDF sampling on a permuted rank order),
integer weights, a uniform draw of pre-trusted peers.

Copied from the program (``protocol_tpu/models/graphs.py::scale_free``)
so that no later change to the program can move the yardstick."""

from __future__ import annotations

import numpy as np

from benchmark.traffic.generate import Graph


def draw(
    n: int,
    nnz: int,
    *,
    seed: int,
    exponent: float = 1.1,
    n_pre_trusted: int = 64,
    weight_low: int = 1,
    weight_high: int = 101,
    chunk: int = 1 << 22,
) -> Graph:
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n).astype(np.int32)
    srcs, dsts, ws = [], [], []
    remaining = nnz
    while remaining > 0:
        m = min(chunk, remaining)
        remaining -= m
        src = rng.integers(0, n, m, dtype=np.int32)
        u = rng.random(m)
        if abs(exponent - 1.0) < 1e-9:
            ranks = np.exp(u * np.log(n))
        else:
            a = 1.0 - exponent
            ranks = (1.0 + u * (n**a - 1.0)) ** (1.0 / a)
        dst = perm[np.clip(ranks.astype(np.int64) - 1, 0, n - 1)]
        w = rng.integers(weight_low, weight_high, m).astype(np.float32)
        srcs.append(src)
        dsts.append(dst)
        ws.append(w)
    pre = np.zeros(n, bool)
    pre[rng.choice(n, min(n_pre_trusted, n), replace=False)] = True
    return Graph(n, np.concatenate(srcs), np.concatenate(dsts), np.concatenate(ws), pre)
