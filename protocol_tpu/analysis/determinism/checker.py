"""Pass 13 — the determinism wall.

The pod substrate (PR 16) and the durability plane (PR 14) stake
correctness on bit-identical state: per-epoch residuals and score
digests must match across hosts before host 0 seals a pod manifest,
WAL replay must reconverge to a control-identical fixed point, and
pooled proofs must be byte-identical to in-process ones.  Nothing
before this pass stopped the next PR from introducing a set-iteration,
an unsorted ``os.listdir``, a ``hash()``-keyed ordering, or a
nondeterministic HLO reduction that silently diverges hosts until a
manifest seal fails in production.

Two static legs:

- **AST** (``ast_walk.py``): divergence-feasible Python sources over
  the trees that feed bit-identity sinks — see the module docstring
  there for the five rules.
- **HLO**: rides the pass-8/12 memoized lowerings
  (``comm.lowering.build_cases`` — the executables are compiled once
  and shared with passes 8 and 12) and asserts every compiled converge
  entry is replay-stable:

  - ``hlo-nondeterministic-scatter`` — a scatter instruction without
    ``unique_indices=true``: duplicate-index scatter combines in
    whatever order the backend schedules, so two hosts (or two runs)
    can legally produce different f32 sums from the same operands;
  - ``hlo-reduce-precision`` — a ``reduce-precision`` op inside a
    converge module: the f32 fixed-point path must carry full
    precision end to end, or residual thresholds stop being
    host-identical;
  - ``hlo-nondeterministic-compile`` — each backend is compiled
    **twice** (the memoized pass-8 executable plus one fresh compile
    at the first scale) and the two modules are diffed after
    canonicalization (SSA value names are renumbered in order of first
    appearance, so per-process naming counters cancel out).  Any
    surviving drift means compilation itself is an entropy source —
    the one failure mode no amount of Python-side seeding can fix.

Waiver doctrine and section shape mirror pass 12; the runtime half
(``tools/divergence_probe.py``) closes the loop by replaying the full
2-process pod under perturbed schedules and asserting every sink
digest identical.
"""

from __future__ import annotations

import difflib
import re
from typing import Any

from ..report import Finding
from ..comm.hlo_walk import source_site, source_table
from ..comm.lowering import COMM_BUILDERS, COMM_SCALES, build_cases
from .ast_walk import DET_AST_RULES, run_det_ast_pass
from .waivers import DET_WAIVERS


def _finding(rule: str, message: str, backend: str | None = None,
             file: str | None = None, line: int | None = None,
             severity: str = "error") -> Finding:
    return Finding(
        pass_name="determinism", rule=rule, severity=severity,
        message=message, backend=backend, file=file, line=line,
    )


# -- HLO canonicalization ---------------------------------------------------

#: SSA value names in HLO text: ``%fusion.123``, ``%param.0``,
#: ``%add.7`` — the numeric suffixes come from a per-process naming
#: counter, so two compiles of the same program legally differ in them.
_HLO_ID = re.compile(r"%[A-Za-z_][A-Za-z0-9_.\-]*")
#: Unnamed computation ids (``ENTRY %main.42``) share the same pattern;
#: buffer-donation comments carry absolute addresses we also drop.
_HLO_COMMENT = re.compile(r"\s*(//|/\*).*$")


def canonicalize_hlo(text: str) -> str:
    """Rename every SSA value name to ``%vN`` in order of first
    appearance and strip trailing comments, so two compiles of the same
    program map to the same text and any surviving difference is a real
    structural drift."""
    mapping: dict[str, str] = {}

    def rename(match: re.Match[str]) -> str:
        name = match.group(0)
        if name not in mapping:
            mapping[name] = f"%v{len(mapping)}"
        return mapping[name]

    lines = []
    for line in text.splitlines():
        line = _HLO_COMMENT.sub("", line)
        lines.append(_HLO_ID.sub(rename, line))
    return "\n".join(lines)


def diff_canonical(text_a: str, text_b: str, *, context: int = 1) -> str | None:
    """Canonicalize both module texts and return ``None`` when they
    match, else a short unified-diff excerpt naming the first drift."""
    a, b = canonicalize_hlo(text_a), canonicalize_hlo(text_b)
    if a == b:
        return None
    diff = difflib.unified_diff(
        a.splitlines(), b.splitlines(),
        fromfile="compile-1", tofile="compile-2",
        lineterm="", n=context,
    )
    excerpt = [line for line in diff][:12]
    return "\n".join(excerpt)


# -- HLO instruction rules --------------------------------------------------

_SCATTER_OP = re.compile(r"=\s*\S+\s+scatter\(")
_REDUCE_PRECISION_OP = re.compile(r"=\s*\S+\s+reduce-precision\(")
_OP_NAME = re.compile(r'op_name="([^"]+)"')


def scan_module_text(backend: str, module_text: str) -> tuple[list[Finding], dict]:
    """Instruction-level determinism scan of one compiled module.
    Returns ``(findings, stats record)``."""
    findings: list[Finding] = []
    scatter_ops = 0
    reduce_precision_ops = 0
    sources = source_table(module_text)
    for i, line in enumerate(module_text.splitlines(), start=1):
        if _SCATTER_OP.search(line):
            scatter_ops += 1
            if "unique_indices=true" not in line:
                op_name = _OP_NAME.search(line)
                file, lineno = source_site(line, sources)
                findings.append(_finding(
                    "hlo-nondeterministic-scatter",
                    f"scatter at module line {i} "
                    f"({op_name.group(1) if op_name else '?'}) lacks "
                    "unique_indices=true — duplicate-index updates "
                    "combine in backend schedule order, so two hosts can "
                    "legally produce different f32 sums from identical "
                    "operands; segment the indices (or assert uniqueness "
                    "at plan build) before this reaches the epoch loop",
                    backend, file=file, line=lineno or i,
                ))
        if _REDUCE_PRECISION_OP.search(line):
            reduce_precision_ops += 1
            findings.append(_finding(
                "hlo-reduce-precision",
                f"reduce-precision at module line {i} inside a converge "
                "module — the f32 fixed-point path must carry full "
                "precision end to end or residual thresholds stop being "
                "host-identical",
                backend, line=i,
            ))
    return findings, {
        "scatter_ops": scatter_ops,
        "reduce_precision_ops": reduce_precision_ops,
    }


def check_recompile(backend: str, text_a: str, text_b: str) -> list[Finding]:
    """The double-compile cross-check: canonical-diff two compiles of
    the same backend entry; drift is ``hlo-nondeterministic-compile``."""
    excerpt = diff_canonical(text_a, text_b)
    if excerpt is None:
        return []
    return [_finding(
        "hlo-nondeterministic-compile",
        f"two compiles of the {backend!r} converge entry disagree after "
        "canonicalization — compilation itself is an entropy source, "
        "the one failure mode no Python-side seeding can fix; first "
        f"drift:\n{excerpt}",
        backend,
    )]


# -- waivers ----------------------------------------------------------------


def _apply_waivers(findings: list[Finding]) -> tuple[list[Finding], list[dict], list[dict]]:
    """Split findings into (live, waived records, stale records) using
    the enumerated DET_WAIVERS table — pass-7 doctrine."""
    live: list[Finding] = []
    waived: list[dict] = []
    matched: set[int] = set()
    for f in findings:
        hit = next(
            (
                (i, w)
                for i, w in enumerate(DET_WAIVERS)
                if w.matches(f.rule, f.file or "", f.message)
            ),
            None,
        )
        if hit is None:
            live.append(f)
        else:
            matched.add(hit[0])
            waived.append({
                "rule": f.rule, "file": f.file, "line": f.line,
                "symbol": hit[1].symbol, "reason": hit[1].reason,
            })
    stale = [
        {
            "symbol": w.symbol, "rule": w.rule, "reason": w.reason,
            "backend": getattr(w, "backend", None),
        }
        for i, w in enumerate(DET_WAIVERS)
        if i not in matched
    ]
    return live, waived, stale


# -- the pass ---------------------------------------------------------------


def run_determinism_pass(
    backends: list[str] | None = None,
    *,
    include_zk: bool = False,
) -> tuple[list[Finding], dict[str, Any]]:
    """Run both static legs and return ``(findings, determinism
    section)`` for ANALYSIS.json.  ``backends`` narrows the HLO leg (and
    skips the AST leg) — the pass-12 subset-run convention.
    ``include_zk`` keeps the zk.graft proving kernels in the default
    HLO leg; without it they are filtered out of COMM_BUILDERS (pass 1
    registers their recipes in-process, but their EC compiles do not
    fit the default self-budget)."""
    findings: list[Finding] = []
    section: dict[str, Any] = {"backends": {}}

    from ..zk_lowering import register as _register_zk, zk_kernel_names

    zk_names = set(zk_kernel_names())
    if include_zk or (backends and set(backends) & zk_names):
        _register_zk()
    if backends is None:
        targets = [
            name for name in COMM_BUILDERS
            if include_zk or name not in zk_names
        ]
    else:
        targets = backends
    for name in targets:
        if name not in COMM_BUILDERS:
            section["backends"][name] = {"status": "no-recipe"}
            continue
        try:
            cases = build_cases(name)
        except Exception as exc:  # noqa: BLE001 - report, don't crash the gate
            section["backends"][name] = {
                "status": "lowering-failed", "error": repr(exc),
            }
            findings.append(_finding(
                "det-lowering-failure",
                f"compiling the step failed: {exc!r}", name,
            ))
            continue
        record: dict[str, Any] = {"status": "checked", "scales": []}
        for case in cases:
            case_findings, stats = scan_module_text(name, case.module_text)
            findings.extend(case_findings)
            record["scales"].append({
                "dims": case.dims,
                **stats,
                "violations": len(case_findings),
            })
        # Double-compile cross-check at the first scale only: the
        # memoized pass-8 executable vs one fresh compile — bypassing
        # the memo on purpose.  First scale bounds the added analyzer
        # cost (the windowed Pallas-interpret compiles dominate the
        # 120 s self-budget) while still exercising the full real
        # lowering path a second time.
        recipe, _two_scale = COMM_BUILDERS[name]
        try:
            fresh = recipe(*COMM_SCALES[0])
        except Exception as exc:  # noqa: BLE001
            section["backends"][name] = {
                "status": "recompile-failed", "error": repr(exc),
            }
            findings.append(_finding(
                "det-lowering-failure",
                f"fresh recompile for the drift check failed: {exc!r}",
                name,
            ))
            continue
        drift = check_recompile(name, cases[0].module_text, fresh.module_text)
        findings.extend(drift)
        record["recompile_drift"] = bool(drift)
        section["backends"][name] = record

    if backends is None:
        ast_findings, n_files = run_det_ast_pass()
        findings.extend(ast_findings)
        section["files_scanned"] = n_files

    live, waived, stale = _apply_waivers(findings)
    if backends is not None:
        # A backend-subset run never evaluates the AST leg, so the
        # staleness of an AST-rule waiver cannot be judged there —
        # only waivers whose domain this run covered may go stale.
        stale = [
            s for s in stale
            if s["rule"] not in DET_AST_RULES
            and s["backend"] in (None, *targets)
        ]
    for entry in stale:
        # A dead waiver is itself a gate failure — pass-7 doctrine,
        # enforced in every run that evaluates its table.
        live.append(_finding(
            "stale-waiver",
            f"determinism waiver {entry['symbol']!r} ({entry['rule']}) "
            "matches no live finding; a fixed divergence source must "
            "take its waiver with it",
            None,
        ))
    section["waived"] = waived
    section["stale_waivers"] = stale
    return live, section


__all__ = [
    "canonicalize_hlo",
    "check_recompile",
    "diff_canonical",
    "run_determinism_pass",
    "scan_module_text",
]
