"""The local-trust graph assembled from attestations.

The reference's "graph" is an N×N dense ops matrix gathered from the
attestation cache (server/src/manager/mod.rs:182-188).  At TPU scale the
graph is edge-list COO: ``src`` scored ``dst`` with weight ``w``.  This
module owns host-side assembly and normalization; device kernels consume
the arrays it produces.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property

import numpy as np

from ..obs.metrics import EDGE_WEIGHTS


@dataclass
class TrustGraph:
    """A weighted directed trust graph.

    ``src/dst/weight`` are parallel COO arrays; ``pre_trusted`` flags the
    seed set used for the pre-trust vector p (the scaled analog of the
    reference's fixed bootstrap set, server/src/manager/mod.rs:40-61).
    """

    n: int
    src: np.ndarray  # int32 (nnz,)
    dst: np.ndarray  # int32 (nnz,)
    weight: np.ndarray  # float32 (nnz,)
    pre_trusted: np.ndarray | None = None  # bool (n,)

    def __post_init__(self):
        self.src = np.asarray(self.src, dtype=np.int32)
        self.dst = np.asarray(self.dst, dtype=np.int32)
        self.weight = np.asarray(self.weight, dtype=np.float32)
        assert self.src.shape == self.dst.shape == self.weight.shape

    @property
    def nnz(self) -> int:
        return int(self.src.shape[0])

    @classmethod
    def from_dense(cls, ops: np.ndarray, pre_trusted=None) -> "TrustGraph":
        ops = np.asarray(ops, dtype=np.float64)
        src, dst = np.nonzero(ops)
        return cls(
            n=ops.shape[0],
            src=src.astype(np.int32),
            dst=dst.astype(np.int32),
            weight=ops[src, dst].astype(np.float32),
            pre_trusted=pre_trusted,
        )

    def to_dense(self) -> np.ndarray:
        ops = np.zeros((self.n, self.n), dtype=np.float64)
        np.add.at(ops, (self.src, self.dst), self.weight.astype(np.float64))
        return ops

    def drop_self_edges(self) -> "TrustGraph":
        """EigenTrust nullifies self-scores (native.rs:183-191).  A graph
        with no self-edge is returned as it is, its arrays uncopied."""
        keep = self.src != self.dst
        if keep.all():
            return self
        return TrustGraph(
            self.n, self.src[keep], self.dst[keep], self.weight[keep], self.pre_trusted
        )

    def row_sums(self) -> tuple[np.ndarray, np.ndarray]:
        """Return ``(safe, dangling mask)``: each row's (sender's) weight
        sum in float64, added in edge order, with 1.0 in place of the
        sum of a row with no positive mass.  Such rows are *dangling*;
        device kernels redistribute their rank to the pre-trust
        vector."""
        sums = np.zeros(self.n, dtype=np.float64)
        np.add.at(sums, self.src, self.weight.astype(np.float64))
        dangling = sums <= 0
        return np.where(dangling, 1.0, sums), dangling

    def row_normalized(self) -> tuple[np.ndarray, np.ndarray]:
        """Return ``(normalized weights, dangling mask)``.

        Weights are divided by their row (sender) sum — the local-trust
        normalization ``c_ij = s_ij / Σ_j s_ij`` of the EigenTrust paper
        and of the set kernel's credit distribution (native.rs:89-102).
        """
        safe, dangling = self.row_sums()
        return _divide(self.weight, self.src, safe), dangling

    def pre_trust_vector(self) -> np.ndarray:
        """p: uniform over the pre-trusted set, or uniform over all peers
        when no seed set is designated."""
        if self.pre_trusted is None or not self.pre_trusted.any():
            return np.full(self.n, 1.0 / self.n, dtype=np.float32)
        p = self.pre_trusted.astype(np.float64)
        return (p / p.sum()).astype(np.float32)

    def sorted_by_dst(self) -> "TrustGraph":
        """Sort edges by destination — enables ``segment_sum`` with
        ``indices_are_sorted=True`` on TPU (no random-scatter path)."""
        order = np.argsort(self.dst, kind="stable")
        return TrustGraph(
            self.n, self.src[order], self.dst[order], self.weight[order], self.pre_trusted
        )

    def row_ptr_by_dst(self) -> np.ndarray:
        """CSC-style pointers over dst-sorted edges: ``row_ptr[j] ..
        row_ptr[j+1]`` is the edge range whose destination is j.  Feeds
        the cumsum SpMV formulation (gather-only, no scatter)."""
        return np.searchsorted(self.dst, np.arange(self.n + 1), side="left").astype(
            np.int32
        )


def _divide(weight: np.ndarray, src: np.ndarray, safe: np.ndarray) -> np.ndarray:
    """Each edge's weight over its row's sum, in float64, rounded to
    float32: elementwise, so any selection of edges divides to the bits
    the whole array would hold there."""
    return (weight.astype(np.float64) / safe[src]).astype(np.float32)


@dataclass(frozen=True, eq=False)
class NormalizedGraph:
    """What every edge-list backend iterates, as
    ``trust.backend.normalize_graph`` builds it: self-edges dropped,
    the row sums, the dangling mask and the pre-trust vector.  The
    row-normalized weights are divided on demand: ``w`` builds the whole
    array once, at its first read; ``weights_of`` divides only the
    edges a caller selects (a plan delta reads its re-attested rows,
    the fingerprint its strided samples), each counted on
    ``eigentrust_edge_weights_total{path}``."""

    n: int
    src: np.ndarray  # int32 (nnz,), no self-edges
    dst: np.ndarray  # int32 (nnz,)
    weight: np.ndarray  # float32 (nnz,), as attested
    safe: np.ndarray  # float64 (n,), row sums, 1.0 where dangling
    dangling: np.ndarray  # float32 (n,), 1 where a row has no positive mass
    p: np.ndarray  # float32 (n,), the pre-trust vector

    @property
    def nnz(self) -> int:
        return int(self.src.shape[0])

    @cached_property
    def w(self) -> np.ndarray:
        """The row-normalized weights, float32 (nnz,), built once."""
        EDGE_WEIGHTS.inc(path="full")
        return _divide(self.weight, self.src, self.safe)

    def weights_of(self, sel) -> np.ndarray:
        """``w[sel]`` for a boolean mask, an index array or a slice,
        dividing only the selected edges; the whole selection
        (``slice(None)``) reads ``w``."""
        if isinstance(sel, slice) and sel == slice(None):
            return self.w
        EDGE_WEIGHTS.inc(path="rows")
        return _divide(self.weight[sel], self.src[sel], self.safe)

    @cached_property
    def fingerprint(self) -> str:
        """The plan-reuse identity, computed once from strided samples."""
        from ..ops.gather_window import graph_fingerprint

        return graph_fingerprint(self.n, self.src, self.dst, self.weights_of)

    def by_dst(self) -> TrustGraph:
        """The sparse and CSR layout: the edges sorted by destination."""
        return TrustGraph(self.n, self.src, self.dst, self.w).sorted_by_dst()
