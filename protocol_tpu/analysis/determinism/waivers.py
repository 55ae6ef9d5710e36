"""Explicit pass-13 waivers — same doctrine as the pass-7/8/12 tables:
every suppression is enumerated with its rationale, emitted into
ANALYSIS.json's ``determinism.waived`` list, and **stale-tested** in
every run that evaluates the table — a waiver that no longer matches a
live finding is itself an error (``stale-waiver``), so a fixed
divergence source takes its waiver with it.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..concurrency.waivers import Waiver


@dataclass(frozen=True)
class HloWaiver(Waiver):
    """A waiver of an HLO-leg finding: ``backend`` names the compiled
    module it lives in, so a backend-subset run judges its staleness
    only when it compiled that backend."""

    backend: str = ""

#: (rule, file substring, message substring) -> rationale — see
#: :class:`~protocol_tpu.analysis.concurrency.waivers.Waiver`.
DET_WAIVERS: tuple[Waiver, ...] = (
    Waiver(
        rule="unseeded-rng",
        file="protocol_tpu/node/ethereum.py",
        symbol="random.Random",
        reason=(
            "ChainEventSource's retry-backoff jitter RNG is unseeded on "
            "purpose: jitter exists to DE-correlate hosts (every host "
            "retrying an RPC on the same schedule is the thundering "
            "herd the backoff is there to break), so seeding it from "
            "the shared protocol seed would be the bug.  The draw "
            "feeds only sleep durations inside the retry loop — it "
            "never reaches a WAL record, checkpoint column, manifest, "
            "job seed, or partition key, which is the bit-identity "
            "plane this pass protects.  The divergence probe "
            "(tools/divergence_probe.py) replays the full pod twice "
            "with this RNG live and proves every sink digest "
            "bit-identical regardless."
        ),
    ),
    HloWaiver(
        rule="hlo-nondeterministic-scatter",
        file="protocol_tpu/ops/sparse.py",
        symbol="jit(converge_sparse)/",
        backend="tpu-sparse",
        reason=(
            "tpu-sparse IS the COO segment_sum formulation: its one "
            "sorted scatter-add (indices_are_sorted=true) is the kernel "
            "budget's declared scatter (KERNEL_INVARIANTS['tpu-sparse'] "
            "max_scatters=1).  It is a dominated reference rung (ROADMAP "
            "C3), never the node default, and no pod or bit-identity "
            "plane runs it — the pod and the divergence probe converge "
            "on tpu-windowed.  The previous JAX's compiled CPU module "
            "showed the scan no scatter here (PERF.md §21 of PR 17); "
            "0.9.0's does."
        ),
    ),
)

__all__ = ["DET_WAIVERS", "HloWaiver"]
