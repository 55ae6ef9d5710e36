"""``graph500``: the Graph500 Kronecker generator (kernel 0 of the Graph500
specification; LDBC Graphalytics' ``graph500-*`` data sets), read as a
trust graph.

Each of the ``nnz`` generated tuples picks, at every one of the
``log2(n)`` levels, one quadrant of the adjacency matrix with the
initiator's probabilities A/B/C/D: (0, 0), (0, 1), (1, 0), (1, 1) for
the (start, end) bits of that level.  One 4-way draw per level gives the
same distribution as the reference Octave code's two dependent draws,
with half the random numbers.  Vertex labels are then permuted by one
uniform permutation.  Self-loops are dropped, as Graph500's kernel 1
drops them, and duplicate (start, end) pairs are merged into one
attestation: Graphalytics' data set is a simple graph, and a sender
attests a peer once.  Each tuple is one directed attestation
start -> end with an integer weight in ``[weight_low, weight_high)``;
``n_pre_trusted`` ids are drawn uniformly.  Edges come out sorted by
(start, end), as the data set's edge file is.

The draws run on threads, one generator per piece of ``chunk`` tuples,
each spawned from ``seed``: the graph depends on the seed alone.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.traffic.generate import Graph

THREADS = min(8, os.cpu_count() or 1)


def _tuples(rng: np.random.Generator, m: int, scale: int, cuts: np.ndarray):
    """``(start, end)`` of ``m`` tuples, one quadrant draw per level: the
    quadrant is the number of ``cuts`` (A, A+B, A+B+C) at or below the
    draw, its high bit the start's bit and its low bit the end's."""
    start = np.zeros(m, np.int32)
    end = np.zeros(m, np.int32)
    for low in range(0, scale, 8):  # eight levels a byte, widened once
        s8 = np.zeros(m, np.uint8)
        e8 = np.zeros(m, np.uint8)
        for k in range(min(8, scale - low)):
            u = rng.random(m, dtype=np.float32)
            high = u >= cuts[1]
            s8 |= high.view(np.uint8) << k
            e8 |= ((u >= cuts[0]) ^ high ^ (u >= cuts[2])).view(np.uint8) << k
        start |= s8.astype(np.int32) << low
        end |= e8.astype(np.int32) << low
    return start, end


def draw(
    n: int,
    nnz: int,
    *,
    seed: int,
    initiator: tuple[float, float, float, float] = (0.57, 0.19, 0.19, 0.05),
    n_pre_trusted: int = 64,
    weight_low: int = 1,
    weight_high: int = 101,
    chunk: int = 1 << 22,
) -> Graph:
    scale = n.bit_length() - 1
    if n != 1 << scale:
        raise ValueError(f"a Kronecker graph has 2**scale ids, not {n}")
    a = np.asarray(initiator, np.float64)
    if a.shape != (4,) or abs(a.sum() - 1.0) > 1e-9:
        raise ValueError("the initiator is four probabilities that sum to 1")
    cuts = np.cumsum(a)[:3].astype(np.float32)
    sizes = [min(chunk, nnz - lo) for lo in range(0, nnz, chunk)]
    labels_rng, weight_rng, *piece_rngs = [
        np.random.default_rng(s) for s in np.random.SeedSequence(seed).spawn(2 + len(sizes))
    ]
    labels = labels_rng.permutation(n).astype(np.int32)

    def keys(rng: np.random.Generator, m: int) -> np.ndarray:
        """Relabelled ``start << scale | end`` of one piece, less its self-loops."""
        start, end = _tuples(rng, m, scale, cuts)
        keep = start != end
        return labels[start[keep]].astype(np.int64) << scale | labels[end[keep]]

    with ThreadPoolExecutor(THREADS) as pool:
        key = np.concatenate(list(pool.map(keys, piece_rngs, sizes)))
    key.sort()
    key = key[np.concatenate([[True], key[1:] != key[:-1]])]
    src = (key >> scale).astype(np.int32)
    dst = (key & (n - 1)).astype(np.int32)
    del key
    w = weight_rng.integers(weight_low, weight_high, src.shape[0]).astype(np.float32)
    pre = np.zeros(n, bool)
    pre[labels_rng.choice(n, min(n_pre_trusted, n), replace=False)] = True
    return Graph(n, src, dst, w, pre)
