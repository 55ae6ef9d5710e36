"""Declarative per-backend kernel budgets — the ``KERNEL_INVARIANTS`` table.

Every trust backend's fast path rests on invariants of its *lowered*
computation that neither the type system nor the test assertions see:
how many random gathers one power step performs, that the boundary read
streams (``indices_are_sorted``), that nothing upcasts to f64 or calls
back to the host inside the jit'd loop.  "Analysis of Power Iteration
Algorithm with Partially Observed Matrix-vector Products" (PAPERS.md)
makes the underlying point precise: the convergence claims hold for a
specific per-iteration access pattern, so the access pattern is part of
the kernel's contract.

The budgets are *declared next to the kernels they pin* — each kernel
module calls :func:`declare` at import time — and *checked* by
``protocol_tpu.analysis.invariants``, which traces each backend's step
function to a closed jaxpr and walks it.  Adding a backend to the
``trust/backend.py`` registry without declaring its budget is itself a
lint error (``undeclared-backend``), so every future backend inherits
the gate for free.

This module is a dependency leaf: the kernel modules import it, so it
must not import jax, numpy, or anything else from ``protocol_tpu``.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class GatherBudget:
    """Budget for the gathers of one named size class.

    ``dim`` names a dimension the trace recipe reports (e.g.
    ``n_segments``); every gather whose leading output dimension equals
    that size is counted against this budget.
    """

    dim: str
    max_total: int
    max_random: int


@dataclass(frozen=True)
class KernelBudget:
    """The per-backend invariant contract checked by pass 1.

    Counting conventions: gathers/scatters inside a ``pallas_call``
    body are excluded (interpret-mode bodies re-express the Mosaic
    kernel as XLA ops; on the real chip they are not XLA gathers), and
    a "random" gather is one not marked ``indices_are_sorted``.
    """

    backend: str
    #: Max gathers without ``indices_are_sorted`` per step (all sizes).
    max_random_gathers: int
    #: Max scatter-family ops per step (``scatter``/``scatter-add``/...).
    max_scatters: int = 0
    #: f64 avals permitted anywhere in the step jaxpr.
    allow_f64: bool = False
    #: Exact number of ``psum``/``psum_invariant`` collectives per step; any
    #: psum present must sit under a ``shard_map``.
    psum_count: int = 0
    #: Primitives that must appear somewhere in the step (e.g.
    #: ``dot_general`` for the MXU path, ``pallas_call`` for windowed).
    require_primitives: tuple[str, ...] = ()
    #: Size-classed gather budgets (see :class:`GatherBudget`).
    gather_budgets: tuple[GatherBudget, ...] = ()
    #: Converge-function arguments declared donated; the analyzer
    #: verifies the aliasing materialized in the lowered computation.
    donated_args: tuple[str, ...] = ()
    #: Free-form rationale recorded in ANALYSIS.json.
    notes: str = ""

    @property
    def invariant_count(self) -> int:
        """How many distinct invariants checking this budget evaluates
        (the acceptance floor is >= 3 per registered backend)."""
        n = 4  # random-gather, scatter, f64, callback checks always run
        n += 1  # psum count/placement is always asserted (incl. == 0)
        n += len(self.require_primitives)
        for gb in self.gather_budgets:
            n += 2
        if self.donated_args:
            n += 1
        return n


#: Backends that never enter jax — exempt from jaxpr analysis.
NON_JAX_BACKENDS = frozenset({"native-cpu"})

#: The table: backend name -> declared budget.  Populated by kernel
#: modules at import; read by ``protocol_tpu.analysis.invariants`` and
#: cross-checked against the ``trust/backend.py`` registry.
KERNEL_INVARIANTS: dict[str, KernelBudget] = {}


def declare(budget: KernelBudget) -> KernelBudget:
    """Register a kernel budget (idempotent per backend name; kernel
    modules call this at import time, next to the kernel they pin)."""
    KERNEL_INVARIANTS[budget.backend] = budget
    return budget


# ---------------------------------------------------------------------------
# Communication budgets — the ``COMM_INVARIANTS`` table (graftlint pass 8)
# ---------------------------------------------------------------------------

#: Collective kinds the SPMD partitioner can emit, as spelled in HLO.
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)


@dataclass(frozen=True)
class CollectiveBudget:
    """Allowance for one collective kind in the lowered module.

    ``max_count`` caps the number of ops of this kind anywhere in the
    compiled module (the power-iteration body runs once per step, so a
    static op in the loop body IS the per-iteration count); a kind with
    no :class:`CollectiveBudget` entry is forbidden outright — a
    partitioner-introduced all-gather must be declared, never silent.
    """

    kind: str  # one of COLLECTIVE_KINDS
    max_count: int


@dataclass(frozen=True)
class CommBudget:
    """Per-backend communication contract checked by pass 8 against the
    *compiled* (SPMD-partitioned) module, not the jaxpr.

    The byte budget is deliberately declarative-linear: the allowance
    for per-iteration collective traffic is ``bytes_n * N +
    bytes_segments * n_segments + bytes_shards * n_shards +
    bytes_const``.  An O(E) term is structurally inexpressible, and the
    analyzer still *evaluates* the budget against measured bytes at two
    problem scales where E grows 4x while N grows 2x — so an O(E)
    lowering cannot hide inside a padded constant either (the sparse
    power-method scaling argument of arXiv:2105.03874: communication
    must follow boundary + N, never edges).
    """

    backend: str
    #: Allowed collective kinds and per-module op-count caps; kinds
    #: absent from this tuple are forbidden in the lowering.
    collectives: tuple[CollectiveBudget, ...] = ()
    #: Linear coefficients of the per-iteration collective byte budget.
    bytes_n: float = 0.0
    bytes_segments: float = 0.0
    bytes_shards: float = 0.0
    bytes_const: float = 0.0
    #: Host round-trips (infeed/outfeed/send/recv/host-callback
    #: custom-calls) permitted in the compiled module.
    max_host_round_trips: int = 0
    #: Require every lowered collective's replica groups to form ONE
    #: group spanning all ``n_shards`` devices (pod doctrine: the
    #: boundary-completing psum must cover the whole mesh — a
    #: partitioner that splits it into per-host subgroups leaves rows
    #: whose runs straddle hosts incomplete, a silent wrong-result,
    #: and a hierarchical reduce that *re-covers* the mesh shows up as
    #: extra collectives under the count caps above).  Groups the HLO
    #: leaves empty mean "all devices" and pass.
    require_full_replica_group: bool = False
    #: Arguments whose donation must survive all the way into the
    #: compiled module's ``input_output_alias`` table (a dropped alias
    #: doubles peak HBM at the 1M-peer shape and ships silently).
    donated_args: tuple[str, ...] = ()
    #: Free-form rationale recorded in ANALYSIS.json.
    notes: str = ""

    def max_bytes(self, n: int, n_segments: int, n_shards: int) -> float:
        """Evaluate the linear byte budget at one problem scale."""
        return (
            self.bytes_n * n
            + self.bytes_segments * n_segments
            + self.bytes_shards * n_shards
            + self.bytes_const
        )

    def allowed_count(self, kind: str) -> int:
        for cb in self.collectives:
            if cb.kind == kind:
                return cb.max_count
        return 0


#: backend name -> declared comm budget.  Populated by kernel modules
#: at import (next to their KERNEL_INVARIANTS declarations); read by
#: ``protocol_tpu.analysis.comm`` and cross-checked against the
#: ``trust/backend.py`` registry — a registered jax backend without an
#: entry is an error, the same policy as kernel budgets.
COMM_INVARIANTS: dict[str, CommBudget] = {}


def declare_comm(budget: CommBudget) -> CommBudget:
    """Register a comm budget (idempotent per backend name; kernel
    modules call this at import time, next to ``declare``)."""
    COMM_INVARIANTS[budget.backend] = budget
    return budget


# ---------------------------------------------------------------------------
# Memory budgets — the ``MEM_INVARIANTS`` table (graftlint pass 12)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MemBudget:
    """Per-backend peak-HBM contract checked by pass 12 against the
    compiled module's buffer assignment (``compiled.memory_analysis()``,
    with a conservative live-range walk over the optimized HLO as
    fallback).  All numbers are the **per-device** view — under the
    8-way analysis mesh that IS the per-shard footprint, so "per-shard
    peak scales as E/n_shards" is the formula itself, not a separate
    rule.

    The allowance decomposes into two declarative halves:

    - **resident** — the argument arrays the kernel holds for the whole
      call (edge tables, window-plan rows, segment tables, score
      vectors).  The edge term is divided by ``n_shards``: an
      accidentally replicated edge operand busts the budget by
      construction (``shard-replicated-edges``).
    - **transient** — XLA's temp arena plus unaliased outputs: the
      iteration's live working set.  It is linear in N, n_segments,
      and plan vreg-rows only — there is **no edge coefficient**, so a
      second O(E)-sized live buffer beyond the declared resident
      arrays is structurally inexpressible (``o-e-live-temporary``).
      ``transient_rows`` exists for the windowed kernels: the Pallas
      interpret-mode compile re-expresses the Mosaic kernel as XLA
      ops, and its scratch is a small multiple of the 8 KB row tables
      (on the real chip this is VMEM scratch, not HBM) — rows are a
      plan-layout dimension (1024 edge slots each), never a raw edge
      count.

    Coefficients are pinned tight: the analyzer compiles the sharded
    composites at two scales where E grows 4x vs N's 2x, and the
    acceptance test asserts the committed slack is below a 4 B/edge
    live temporary at *either* scale — the COMM_INVARIANTS pinning
    trick (PERF.md §15), applied to liveness instead of wire bytes.
    """

    backend: str
    #: Resident (argument) allowance coefficients.
    resident_edge_bytes: float = 0.0  # x E / n_shards
    resident_n: float = 0.0  # x N
    resident_segments: float = 0.0  # x n_segments (per-shard table)
    resident_rows: float = 0.0  # x plan vreg-rows (per shard)
    resident_const: float = 0.0
    #: Transient (temp arena + unaliased output) allowance — NO edge
    #: coefficient can be declared here, by construction.
    transient_n: float = 0.0
    transient_segments: float = 0.0
    transient_rows: float = 0.0
    transient_const: float = 0.0
    #: Arguments whose donation must materialize as buffer aliasing:
    #: a dropped alias shows up as a doubled f32[N] carry
    #: (``donation-peak-doubled``).  Each entry is an f32[N] seed.
    donated_args: tuple[str, ...] = ()
    #: Per-op host-transfer byte cap (``staging_n * N + staging_const``):
    #: a transfer custom-call carrying more than this — an O(E) staging
    #: copy outside plan build — is a ``host-staging-over-cap`` finding.
    staging_n: float = 0.0
    staging_const: float = 0.0
    #: Free-form rationale recorded in ANALYSIS.json.
    notes: str = ""

    def max_resident(
        self, n: int, edges: int, n_segments: int, rows: int, n_shards: int
    ) -> float:
        return (
            self.resident_edge_bytes * edges / max(n_shards, 1)
            + self.resident_n * n
            + self.resident_segments * n_segments
            + self.resident_rows * rows
            + self.resident_const
        )

    def max_transient(self, n: int, n_segments: int, rows: int) -> float:
        return (
            self.transient_n * n
            + self.transient_segments * n_segments
            + self.transient_rows * rows
            + self.transient_const
        )

    def staging_cap(self, n: int) -> float:
        return self.staging_n * n + self.staging_const


#: backend name -> declared memory budget.  Populated by kernel modules
#: at import (next to their KERNEL_INVARIANTS / COMM_INVARIANTS
#: declarations); read by ``protocol_tpu.analysis.memory`` and
#: cross-checked against the ``trust/backend.py`` registry — a
#: registered jax backend without an entry is an error, the same policy
#: as kernel and comm budgets.
MEM_INVARIANTS: dict[str, MemBudget] = {}


def declare_mem(budget: MemBudget) -> MemBudget:
    """Register a memory budget (idempotent per backend name; kernel
    modules call this at import time, next to ``declare``)."""
    MEM_INVARIANTS[budget.backend] = budget
    return budget


__all__ = [
    "COLLECTIVE_KINDS",
    "COMM_INVARIANTS",
    "CollectiveBudget",
    "CommBudget",
    "GatherBudget",
    "KernelBudget",
    "KERNEL_INVARIANTS",
    "MEM_INVARIANTS",
    "MemBudget",
    "NON_JAX_BACKENDS",
    "declare",
    "declare_comm",
    "declare_mem",
]
