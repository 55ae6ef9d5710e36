"""Explicit pass-7 waivers — every suppression is enumerated and tested.

A waiver is a *documented* decision that a finding describes a design
the code makes safe by other means (GIL-atomic single-opcode ops,
boot-time-only writes, advisory counters).  The checker records every
match in the ANALYSIS.json ``concurrency.waived`` list, and
``tests/test_analysis.py`` asserts two invariants:

- zero **unwaived** findings on the real tree, and
- zero **stale** waivers (every entry below still matches a live
  finding — a fixed bug must take its waiver with it).

Matching is (rule, file substring, message substring) — the symbol
string names the class attribute or call site precisely enough that a
new, different bug in the same file cannot hide behind an old waiver.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Waiver:
    rule: str
    file: str  # substring of the repo-relative path
    symbol: str  # substring of the finding message (Class.attr / call)
    reason: str

    def matches(self, rule: str, file: str, message: str) -> bool:
        return (
            rule == self.rule and self.file in file and self.symbol in message
        )


WAIVERS: tuple[Waiver, ...] = (
    Waiver(
        rule="unguarded-shared-attr",
        file="protocol_tpu/obs/journal.py",
        symbol="FlightRecorder._file",
        reason=(
            "record()/flush() read _file bare by design: the hot path "
            "must never take a lock (doctrine at the top of journal.py). "
            "_file only transitions between None and an open handle "
            "under _io_lock; a torn read sees one of the two valid "
            "states, and flush() re-checks under the lock before "
            "writing.  The witness stress test exercises this exact "
            "interleaving."
        ),
    ),
    Waiver(
        rule="unguarded-rmw",
        file="protocol_tpu/zk/cs.py",
        symbol="ConstraintSystem.n_rows",
        reason=(
            "zk/ stopped being tree-confined at the prover pool "
            "(ISSUE 10), but a ConstraintSystem is still *instance*-"
            "confined: each one is synthesized and consumed by exactly "
            "one prove path — one plane dispatcher thread, one worker "
            "process, or one /aggregate executor call — and never "
            "escapes it.  The genuinely shared zk state (the "
            "zk/native.py loader globals) grew a real lock instead.  "
            "The pooled-vs-inline bit-equality test would catch any "
            "cross-thread sharing regression as a torn row count."
        ),
    ),
    Waiver(
        rule="check-then-act",
        file="protocol_tpu/zk/plonk.py",
        symbol="_CosetEvaluator._shift_pows",
        reason=(
            "Per-prove lazy memo: a _CosetEvaluator lives inside one "
            "prove() call (one dispatcher thread or worker process); "
            "the flag flip can never race because the instance never "
            "crosses a thread.  Same instance-confinement argument as "
            "ConstraintSystem.n_rows — recorded, not locked, to keep "
            "the MSM-adjacent hot path allocation-free."
        ),
    ),
    Waiver(
        rule="unguarded-shared-attr",
        file="protocol_tpu/obs/lineage.py",
        symbol="LineageTracker._every",
        reason=(
            "maybe_begin() reads _every bare by design: it is the "
            "per-submission intake hot path (ingest plane submit), and "
            "the no-lock contract there mirrors the journal's record() "
            "doctrine.  _every is a single int flipped by configure() "
            "at node boot (and by tests); a torn read samples one "
            "period early or late — the sampled fraction is advisory, "
            "the entry table itself is fully lock-guarded."
        ),
    ),
    Waiver(
        rule="unguarded-rmw",
        file="protocol_tpu/obs/journal.py",
        symbol="FlightRecorder._seq",
        reason=(
            "_seq is an advisory ordering hint (commented 'benign "
            "race'): a lost increment reorders two events' seq numbers "
            "but loses no event — the ring append is the source of "
            "truth.  Locking the hot record() path to fix a cosmetic "
            "counter would invert the recorder's no-block contract."
        ),
    ),
)


__all__ = ["WAIVERS", "Waiver"]
