"""Compiled-HLO module walking — the pass-8 counterpart of
``jaxpr_walk``.

Pass 1 pins the *jaxpr*; this module reads what the SPMD partitioner
actually emitted.  ``jax``'s AOT path exposes the post-partitioning,
post-optimization HLO as text (``lowered.compile().as_text()``), and
that text is a stable, line-oriented format: one op per line with the
result type, typed operands, attributes, and — crucially — jax's
``metadata={... stack_frame_id=N}`` breadcrumb, resolved through the
module's ``FileNames``/``FileLocations``/``StackFrames`` tables back to
the user code that traced the op.  The walker extracts:

- every **collective** (all-reduce / all-gather / reduce-scatter /
  collective-permute / all-to-all) with its replica groups and byte
  volume computed from the operand/result shapes;
- every **host round-trip**: infeed/outfeed/send/recv ops and
  custom-calls whose target is a host callback (``xla_python_*`` /
  ``*callback*`` / ``*host*`` targets — device custom-calls like
  sort comparators are not round-trips and are ignored);
- the module-header **input_output_alias** table, where donation either
  materialized or silently died between the jaxpr and the executable.

Text parsing is deliberate: the HLO proto bindings churn across
jaxlib versions, while the dump format is the compiler's own
round-trippable syntax.  Every regex here is pinned by the seeded
fixtures (``analysis/fixtures.py``) that lower real modules through
the real jit path.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

#: HLO op names counted as collectives (with -start/-done variants the
#: async pipeliner splits them into).
_COLLECTIVE_RE = (
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
)

#: Bytes per element by HLO dtype prefix.
_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
}

_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%\S+\s*=\s*(?P<result>\([^=]*?\)|\S+)\s+"
    rf"(?P<op>(?:{_COLLECTIVE_RE})(?:-start|-done)?|infeed|outfeed|"
    r"send|send-done|recv|recv-done|custom-call)"
    r"\((?P<operands>.*?)\)(?P<attrs>.*)$"
)

#: ``%name = <type> op(`` — one instruction definition.
_DEF = re.compile(r"(?P<name>%[\w.\-]+)\s*=\s*(?P<type>\([^=]*?\)|\S+)\s+[\w\-]+\(")
#: A typed operand (``f32[8]{0} %x``, older dumps) or a bare ``%x``.
_REF = re.compile(r"(?:[a-z]+\d*\[[\d,]*\](?:\{[\d,]*\})?\s+)?%[\w.\-]+")
_SHAPE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")

_STACK_FRAME_ID = re.compile(r"metadata=\{[^}]*?stack_frame_id=(?P<id>\d+)")
_TABLE_ROW = re.compile(r"^(?P<id>\d+) (?P<body>.+)$")
_FIELD = re.compile(r"(?P<key>\w+)=(?P<value>\d+)")
_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]+)"')
_REPLICA_GROUPS = re.compile(r"replica_groups=(?P<groups>\{[^=]*?\}\})")
_CUSTOM_TARGET = re.compile(r'custom_call_target="(?P<target>[^"]+)"')

#: custom_call_target substrings that mean "leave the device, talk to
#: the Python host" — the one-round-trip-per-iteration wall class.
_HOST_TARGET_MARKS = ("callback", "python", "host_")

#: ``input_output_alias={ {0}: (3, {}, may-alias), ... }`` — pairs of
#: (output tuple index, parameter number).  The table ends at the last
#: ``) }`` so the inner ``{}`` shape-index braces cannot truncate it.
_ALIAS_TABLE = re.compile(r"input_output_alias=\{(?P<table>.*?)\)\s*\}")
_ALIAS_PAIR = re.compile(r"\{(?P<out>[\d,\s]*)\}:\s*\((?P<param>\d+)")


def source_table(text: str) -> dict[int, tuple[str, int]]:
    """``stack_frame_id`` -> ``(file, line)`` from the module's trailing
    ``FileNames``/``FileLocations``/``StackFrames`` tables."""
    tables: dict[str, dict[int, str]] = {}
    current: dict[int, str] | None = None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            current = tables.setdefault(line, {})
            continue
        row = _TABLE_ROW.match(line) if current is not None else None
        if row is None:
            current = None
            continue
        current[int(row.group("id"))] = row.group("body")
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    locations = {}
    for k, body in tables.get("FileLocations", {}).items():
        fields = {m.group("key"): int(m.group("value")) for m in _FIELD.finditer(body)}
        locations[k] = (files.get(fields.get("file_name_id", -1)), fields.get("line"))
    out = {}
    for k, body in tables.get("StackFrames", {}).items():
        fields = {m.group("key"): int(m.group("value")) for m in _FIELD.finditer(body)}
        file, lineno = locations.get(fields.get("file_location_id", -1), (None, None))
        if file is not None and lineno is not None:
            out[k] = (file, lineno)
    return out


def source_site(
    attrs: str, table: dict[int, tuple[str, int]]
) -> tuple[str | None, int | None]:
    """``(file, line)`` of one op's attribute string, via ``table``."""
    m = _STACK_FRAME_ID.search(attrs)
    if m is None:
        return None, None
    return table.get(int(m.group("id")), (None, None))


def shape_bytes(typed: str) -> int:
    """Total bytes of every shape literal in ``typed`` (an HLO type or
    typed-operand string): ``f32[512]{0}`` -> 2048, tuples summed."""
    total = 0
    for m in _SHAPE.finditer(typed):
        unit = _DTYPE_BYTES.get(m.group("dtype"))
        if unit is None:
            continue
        numel = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                numel *= int(d)
        total += unit * numel
    return total


@dataclass(frozen=True)
class CollectiveOp:
    """One lowered collective with its wire-volume accounting."""

    kind: str  # normalized: "all-reduce", "all-gather", ...
    result_bytes: int
    operand_bytes: int
    replica_groups: str
    op_name: str  # jax metadata path, e.g. ".../while/body/.../psum"
    file: str | None
    line: int | None

    @property
    def bytes(self) -> int:
        """Wire volume attributed to the op: the larger of what goes in
        and what comes out (all-gather outputs dominate, all-reduce is
        symmetric) — computed from the typed operand/result shapes."""
        return max(self.result_bytes, self.operand_bytes)

    @property
    def per_iteration(self) -> bool:
        """True when the op sits inside the power-iteration while body
        (jax's op_name metadata carries the trace path)."""
        return "/while/" in self.op_name

    def to_dict(self) -> dict:
        return {
            "kind": self.kind,
            "bytes": self.bytes,
            "replica_groups": self.replica_groups,
            "per_iteration": self.per_iteration,
            "op_name": self.op_name,
            "file": self.file,
            "line": self.line,
        }


@dataclass(frozen=True)
class HostCall:
    """One host round-trip site in the compiled module."""

    op: str  # "custom-call" | "infeed" | "outfeed" | "send" | "recv"
    target: str  # custom_call_target, or "" for infeed/outfeed/send/recv
    file: str | None
    line: int | None
    #: Bytes the transfer carries — max of what goes out (operands) and
    #: what comes back (result), from the typed shapes.  Pass 12 caps
    #: this per op (``host-staging-over-cap``): an O(E) staging copy
    #: outside plan build is a finding even where a round-trip per se
    #: is waived.
    bytes: int = 0

    def to_dict(self) -> dict:
        return {"op": self.op, "target": self.target, "file": self.file,
                "line": self.line, "bytes": self.bytes}


@dataclass
class ModuleComm:
    """Everything pass 8 reads out of one compiled module."""

    collectives: list[CollectiveOp] = field(default_factory=list)
    host_calls: list[HostCall] = field(default_factory=list)
    #: output tuple index -> donated parameter number.
    aliases: dict[int, int] = field(default_factory=dict)

    def kind_counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    def total_bytes(self, per_iteration_only: bool = False) -> int:
        return sum(
            op.bytes
            for op in self.collectives
            if op.per_iteration or not per_iteration_only
        )

    def aliased_params(self) -> set[int]:
        return set(self.aliases.values())


def replica_group_sizes(groups: str) -> list[int]:
    """Sizes of each replica group in an HLO ``replica_groups`` literal:
    ``{{0,1,2,3},{4,5,6,7}}`` -> ``[4, 4]``; the empty literal (``{}``
    or missing — HLO shorthand for "all devices in one group") ->
    ``[]``.  Pass 8's multi-host coverage rule reads this to assert the
    boundary-completing psum spans the whole pod mesh rather than a
    per-host subgroup."""
    sizes = []
    for inner in re.findall(r"\{([\d,\s]*)\}", groups):
        ids = [tok for tok in inner.replace(",", " ").split() if tok]
        if ids:
            sizes.append(len(ids))
    return sizes


def _normalize_kind(op: str) -> str:
    """Fold the async ``-start``/``-done`` split back to one op (count
    the start, drop the done — one wire transfer either way)."""
    return op[: -len("-start")] if op.endswith("-start") else op


def parse_module(text: str) -> ModuleComm:
    """Walk one compiled module dump (``compiled.as_text()``)."""
    mod = ModuleComm()
    header = text.split("\n", 1)[0]
    alias = _ALIAS_TABLE.search(header)
    if alias:
        for pair in _ALIAS_PAIR.finditer(alias.group("table")):
            out_idx = int((pair.group("out").strip() or "0").split(",")[0])
            mod.aliases[out_idx] = int(pair.group("param"))

    sources = source_table(text)
    # Compiled modules print operands by name only: resolve each to the
    # type its defining instruction declares.
    types = {d.group("name"): d.group("type") for d in _DEF.finditer(text)}
    for line in text.splitlines():
        m = _OP_LINE.match(line)
        if m is None:
            continue
        op = m.group("op")
        attrs = m.group("attrs")
        file, lineno = source_site(attrs, sources)
        if op.endswith("-done"):
            continue  # the matching -start carries the transfer
        operands = " ".join(
            types.get(ref.group(0), ref.group(0)) for ref in _REF.finditer(m.group("operands"))
        ) or m.group("operands")
        volume = max(shape_bytes(m.group("result")), shape_bytes(operands))
        if op == "custom-call":
            target = _CUSTOM_TARGET.search(attrs)
            name = target.group("target") if target else ""
            if any(mark in name.lower() for mark in _HOST_TARGET_MARKS):
                mod.host_calls.append(
                    HostCall("custom-call", name, file, lineno, bytes=volume)
                )
            continue
        if op in ("infeed", "outfeed", "send", "recv"):
            mod.host_calls.append(HostCall(op, "", file, lineno, bytes=volume))
            continue
        groups = _REPLICA_GROUPS.search(attrs)
        op_name = _OP_NAME.search(attrs)
        mod.collectives.append(
            CollectiveOp(
                kind=_normalize_kind(op),
                result_bytes=shape_bytes(m.group("result")),
                operand_bytes=shape_bytes(operands),
                replica_groups=groups.group("groups") if groups else "",
                op_name=op_name.group("op_name") if op_name else "",
                file=file,
                line=lineno,
            )
        )
    return mod


__all__ = [
    "CollectiveOp",
    "HostCall",
    "ModuleComm",
    "parse_module",
    "replica_group_sizes",
    "shape_bytes",
    "source_site",
    "source_table",
]
