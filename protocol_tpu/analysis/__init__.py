"""protocol_tpu.analysis — graftlint, the two-pass static analyzer.

The invariants that make the trust backends fast — one random gather
per windowed step, no f64 upcasts, no host
callbacks inside the jit'd loop, one psum under shard_map — are
contracts of the *lowered* computation, invisible to ruff and mypy.
This subsystem checks them by machine:

- **Pass 1** (``invariants``): trace every registered backend's step
  function to a closed jaxpr on a synthetic graph and check the
  declarative :data:`~protocol_tpu.analysis.budget.KERNEL_INVARIANTS`
  budgets declared next to each kernel.
- **Pass 2** (``ast_rules``): an ``ast.NodeVisitor`` ruleset over
  ``protocol_tpu/`` catching implicit host syncs and import-time
  device work.
- **Pass 7** (``concurrency``): the whole-program threading-contract
  analyzer with its enumerated, stale-tested waiver table.
- **Pass 8** (``comm``): the SPMD-lowering communication analyzer —
  compiles every backend under the 8-device CPU mesh and checks the
  declarative :data:`~protocol_tpu.analysis.budget.COMM_INVARIANTS`
  budgets (collective kinds/counts, O(boundary + N) byte allowances
  evaluated at two scales, host round-trips, donation aliasing) against
  what the partitioner actually emitted.
- **Pass 12** (``memory``): the static peak-HBM analyzer — reads the
  buffer assignment of the same executables pass 8 compiles and checks
  the declarative :data:`~protocol_tpu.analysis.budget.MEM_INVARIANTS`
  budgets (per-shard resident bytes scaling as E/n_shards, an
  N/n_segments-linear transient allowance in which an O(E) live
  temporary is structurally inexpressible, donation-reduces-peak,
  host-staging byte caps), plus the edge-materialization and
  cache-growth AST rules over the long-lived node trees.
- **Pass 13** (``determinism``): the divergence analyzer — an AST
  taint walk over the trees feeding bit-identity sinks (set-order
  materialization, unsorted directory scans, ``hash()``/``id()``
  keys, unseeded RNGs, wall-clock-in-digest) plus an HLO leg over the
  same executables passes 8/12 compile asserting replay-stability
  (no nondeterministic scatter, no reduce-precision, double-compile
  canonical-diff), with its own stale-tested waiver table.  The
  runtime half is ``tools/divergence_probe.py``.

Run as ``python -m protocol_tpu.analysis``: emits ``ANALYSIS.json``
plus ``file:line`` findings; any error-severity finding exits non-zero
(``scripts/lint.sh`` and CI treat it as a hard gate).  PERF.md §9
documents the pinned invariants and how to declare one for a new
backend.

This ``__init__`` stays dependency-light (the kernel modules import
``.budget`` at their own import time); the tracing passes load jax
only when invoked.
"""

from .budget import (
    COMM_INVARIANTS,
    KERNEL_INVARIANTS,
    MEM_INVARIANTS,
    NON_JAX_BACKENDS,
    CollectiveBudget,
    CommBudget,
    GatherBudget,
    KernelBudget,
    MemBudget,
    declare,
    declare_comm,
    declare_mem,
)
from .report import Finding, Report

__all__ = [
    "COMM_INVARIANTS",
    "CollectiveBudget",
    "CommBudget",
    "Finding",
    "GatherBudget",
    "KERNEL_INVARIANTS",
    "KernelBudget",
    "MEM_INVARIANTS",
    "MemBudget",
    "NON_JAX_BACKENDS",
    "Report",
    "declare",
    "declare_comm",
    "declare_mem",
]
