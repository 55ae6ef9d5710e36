"""Buffer-liveness view of a compiled module — the pass-12 counterpart
of ``comm/hlo_walk``.

The primary source of truth for peak HBM is the compiler's own buffer
assignment, surfaced through jax's AOT path as
``compiled.memory_analysis()`` (argument / output / alias / temp byte
totals per device) and captured into :class:`~..comm.lowering.CommCase`
at compile time.  This module supplies the two things that view cannot:

- **a conservative live-range fallback** (:func:`live_range_peak`) for
  runtimes whose executables expose no memory analysis: a per-
  computation liveness sweep over the optimized-HLO text (def site to
  last use, parameters excluded — they are the caller's bytes), summed
  across computations because nested computations (fusions, while
  bodies) execute inside their callers' arenas.  A deliberate
  over-estimate: the fallback may fail a budget the real buffer
  assignment would pass, never the reverse.
- **attribution** (:func:`largest_temp_site`): the op defining the
  largest non-parameter buffer in the module, with its jax source
  breadcrumb — so a transient-over-budget finding points at the line
  that materialized the offending temporary, the same ``file:line``
  contract as every other graftlint rule.

Text parsing is deliberate, for the same reason as ``hlo_walk``: the
dump format is the compiler's round-trippable syntax, stable where the
proto bindings churn.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from ..comm.hlo_walk import shape_bytes, source_site, source_table

#: One op line: ``%name = <type> <op>(<operands>)<attrs>`` — the
#: general form this time, not just collectives.
_ANY_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\([^=]*?\)|\S+)\s+(?P<op>[\w\-]+)"
    r"\((?P<operands>.*?)\)(?P<attrs>.*)$"
)

#: Operand references inside the parenthesized operand list.
_REF = re.compile(r"%(?P<ref>[\w.\-]+)")

_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]+)"')

#: Computation headers: ``%name (params...) -> type {`` or ``ENTRY ...``.
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?[\w.\-]+\s*(?:\([^)]*\))?.*\{\s*$")


@dataclass(frozen=True)
class TempSite:
    """The op that defined one temp buffer, with its size and source."""

    bytes: int
    op: str
    op_name: str
    file: str | None
    line: int | None

    def to_dict(self) -> dict:
        return {
            "bytes": self.bytes,
            "op": self.op,
            "op_name": self.op_name,
            "file": self.file,
            "line": self.line,
        }


def _computation_blocks(text: str) -> list[list[str]]:
    """Split a module dump into computation bodies (lists of lines)."""
    blocks: list[list[str]] = []
    current: list[str] | None = None
    for line in text.splitlines():
        stripped = line.rstrip()
        if current is None:
            if _COMPUTATION.match(stripped):
                current = []
            continue
        if stripped.endswith("}") and not stripped.lstrip().startswith("%"):
            blocks.append(current)
            current = None
            continue
        current.append(stripped)
    if current:
        blocks.append(current)
    return blocks


def live_range_peak(text: str) -> int:
    """Conservative peak live bytes of the module's non-parameter
    buffers: per-computation liveness sweep (def to last use), summed
    over computations — nested computations run inside their callers,
    so their arenas can coexist.  An upper bound on what the buffer
    assignment would allocate as its temp arena."""
    total = 0
    for block in _computation_blocks(text):
        defs: list[tuple[str, int]] = []  # (buffer, bytes) in def order
        last_use: dict[str, int] = {}
        sizes: dict[str, int] = {}
        for i, line in enumerate(block):
            m = _ANY_OP.match(line)
            if m is None:
                continue
            name = m.group("name")
            if m.group("op") != "parameter":
                sizes[name] = shape_bytes(m.group("type"))
                defs.append((name, i))
            for ref in _REF.finditer(m.group("operands")):
                if ref.group("ref") in sizes:
                    last_use[ref.group("ref")] = i
        peak = 0
        live = 0
        expiring: dict[int, list[str]] = {}
        for name, i in defs:
            live += sizes[name]
            expiring.setdefault(last_use.get(name, i), []).append(name)
            peak = max(peak, live)
            for dead in expiring.pop(i, ()):
                live -= sizes[dead]
        total += peak
    return total


def largest_temp_site(text: str) -> TempSite | None:
    """The op defining the largest non-parameter buffer in the module
    (metadata-bearing ops preferred at equal size) — the attribution
    anchor for a transient-over-budget finding."""
    best: TempSite | None = None
    sources = source_table(text)
    for block in _computation_blocks(text):
        for line in block:
            m = _ANY_OP.match(line)
            if m is None or m.group("op") in ("parameter", "constant"):
                continue
            nbytes = shape_bytes(m.group("type"))
            attrs = m.group("attrs")
            op_name = _OP_NAME.search(attrs)
            file, lineno = source_site(attrs, sources)
            site = TempSite(
                bytes=nbytes,
                op=m.group("op"),
                op_name=op_name.group("op_name") if op_name else "",
                file=file,
                line=lineno,
            )
            if (
                best is None
                or nbytes > best.bytes
                or (nbytes == best.bytes and best.file is None and site.file)
            ):
                best = site
    return best


def measured_view(case) -> tuple[dict[str, int], str]:
    """``(per-device byte view, source)`` for one compiled case: the
    buffer assignment when the executable exposed one (``source =
    "buffer-assignment"``), else the conservative live-range walk over
    the module text (``source = "live-range-walk"``; arguments are then
    estimated from the entry parameters, aliasing is assumed absent)."""
    if case.mem is not None:
        mem = case.mem
        resident = mem["argument_bytes"]
        transient = mem["temp_bytes"] + mem["output_bytes"] - mem["alias_bytes"]
        return (
            {
                "resident_bytes": resident,
                "transient_bytes": transient,
                "peak_bytes": resident + transient,
                **mem,
            },
            "buffer-assignment",
        )
    resident = _entry_parameter_bytes(case.module_text)
    transient = live_range_peak(case.module_text)
    return (
        {
            "resident_bytes": resident,
            "transient_bytes": transient,
            "peak_bytes": resident + transient,
        },
        "live-range-walk",
    )


def _entry_parameter_bytes(text: str) -> int:
    """Total bytes of the module's entry parameters (the resident
    estimate of the fallback path)."""
    total = 0
    for block in _computation_blocks(text):
        block_total = 0
        for line in block:
            m = _ANY_OP.match(line)
            if m is not None and m.group("op") == "parameter":
                block_total += shape_bytes(m.group("type"))
        # Entry parameters dominate; nested computations repeat them as
        # their own parameters, so take the max block, not the sum.
        total = max(total, block_total)
    return total


__all__ = [
    "TempSite",
    "largest_temp_site",
    "live_range_peak",
    "measured_view",
]
