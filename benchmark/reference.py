"""The plain reference: EigenTrust power iteration with pre-trust damping
(Kamvar, Schlosser, Garcia-Molina, WWW 2003, §4.5), in float64 on the
host, independent of ``protocol_tpu``.

It computes the same answer the node's converge stage promises, by the
same rule, from the same graph:

- self-scores are dropped, and each sender's weights are divided by
  their sum (the local trust ``c_ij``); a sender with no out-weight is
  dangling, and its trust goes to the pre-trust vector ``p``;
- ``p`` is uniform over the pre-trusted peers;
- one step is ``t' = (1 - a)(C^T t + (dangling · t) p) + a p``, then
  ``t' / sum(t')``;
- it starts from the previous epoch's answer (clipped at 0 and
  L1-normalised) or, cold, from ``p``, and steps while fewer than
  ``max_iter`` steps ran and the last step moved ``t`` by more than
  ``tol`` in L1 (at least one step).

Each reference epoch starts from the reference's own previous answer,
never from one the program made.  ``round_to`` runs the same chain with
the weights and the score vector rounded to a lower precision after
every operation that writes them: the control.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np
import scipy.sparse as sp

#: Row blocks of ``C^T``, multiplied on threads (scipy releases the GIL
#: there): the reference replays every epoch of the run after the
#: window, and its time counts against the run's 360 s.
THREADS = min(8, os.cpu_count() or 1)


def _identity(x: np.ndarray) -> np.ndarray:
    return x


def bfloat16(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back to float64."""
    import ml_dtypes

    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


ROUNDING = {"float64": _identity, "bfloat16": bfloat16}


@dataclass
class Answer:
    scores: np.ndarray  # float64 (n,)
    iterations: int
    residual: float


class Chain:
    """One epoch after another, each seeded with the previous answer."""

    def __init__(self, trust: dict, round_to: str = "float64"):
        self.alpha = float(trust["alpha"])
        self.tol = float(trust["tol"])
        self.max_iter = int(trust["max_iter"])
        self.round = ROUNDING[round_to]
        self.last: np.ndarray | None = None

    def epoch(self, graph) -> Answer:
        n, r = graph.n, self.round
        keep = graph.src != graph.dst
        src, dst = graph.src[keep], graph.dst[keep]
        w = graph.weight[keep].astype(np.float64)
        sums = np.bincount(src, weights=w, minlength=n)
        dangling = sums <= 0
        c = r(w / np.where(dangling, 1.0, sums)[src])
        ct = sp.csr_matrix((c, (dst, src)), shape=(n, n))
        del c, w, src, dst
        cuts = np.linspace(0, n, THREADS + 1).astype(np.int64)
        blocks = [ct[a:b] for a, b in zip(cuts, cuts[1:])]
        del ct
        pre = graph.pre_trusted.astype(np.float64)
        p = pre / pre.sum() if pre.any() else np.full(n, 1.0 / n)
        p = r(p)
        t = p if self.last is None else np.maximum(self.last, 0.0)
        t = r(t / t.sum())
        resid, it = np.inf, 0
        with ThreadPoolExecutor(THREADS) as pool:
            while it < self.max_iter and (it == 0 or resid > self.tol):
                spread = r(np.concatenate(list(pool.map(lambda b: b @ t, blocks))))
                new = r(
                    (1.0 - self.alpha) * (spread + r(t[dangling].sum()) * p)
                    + self.alpha * p
                )
                new = r(new / new.sum())
                resid = float(np.abs(new - t).sum())
                t, it = new, it + 1
        self.last = t
        return Answer(t, it, resid)


def gaps(scores: np.ndarray, reference: np.ndarray) -> dict[str, float]:
    """The numbers compared: ``l1_gap``, the L1 distance of the two
    score vectors (both sum to 1), and ``peer_gap``, the largest gap of
    one peer's score relative to its reference score plus the mean
    score ``1/n`` (so a peer the reference gives almost nothing is
    judged on the scale of an average peer)."""
    scores = np.asarray(scores, np.float64)
    diff = np.abs(scores - reference)
    floor = 1.0 / reference.shape[0]
    return {
        "l1_gap": float(diff.sum()),
        "peer_gap": float((diff / (np.abs(reference) + floor)).max()),
    }
