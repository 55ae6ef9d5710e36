"""CLI: ``python -m protocol_tpu.analysis`` — run graftlint.

Exit code 0 iff no error-severity finding; writes ``ANALYSIS.json``
(CI uploads it as a build artifact).  ``--fixture`` runs one seeded
violation instead of the real tree — it must exit non-zero, which
doubles as the gate's self-check.
"""

from __future__ import annotations

import argparse
import os
import sys


def _ensure_cpu_mesh() -> None:
    """Force the 8-device virtual CPU mesh before jax is imported
    (same doctrine as tests/conftest.py): the sharded composites trace
    under a real Mesh without TPU hardware."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m protocol_tpu.analysis",
        description="graftlint: jaxpr/AST invariant analyzer for the trust backends",
    )
    parser.add_argument(
        "--output",
        default="ANALYSIS.json",
        help="machine-readable report path (default: %(default)s)",
    )
    parser.add_argument(
        "--pass",
        dest="passes",
        choices=("all", "jaxpr", "ast", "concurrency", "comm", "memory",
                 "determinism"),
        default="all",
        help="which pass(es) to run (default: %(default)s)",
    )
    parser.add_argument(
        "--zk",
        action="store_true",
        help=(
            "extend the compile passes (comm/memory/determinism) to the "
            "zk.graft proving kernels; their EC compiles take minutes, so "
            "only the zk-graft CI job runs this by default (the jaxpr "
            "pass always covers them — tracing is cheap)"
        ),
    )
    parser.add_argument(
        "--fixture",
        default=None,
        help="run one seeded violation fixture instead of the real tree",
    )
    parser.add_argument(
        "--list-fixtures", action="store_true", help="list fixture names and exit"
    )
    args = parser.parse_args(argv)

    _ensure_cpu_mesh()
    if args.zk:
        # The zk leg compiles EC kernels that take tens of seconds per
        # (shape, kernel) pair on XLA:CPU; persist executables so
        # repeat --zk runs pay compilation once per machine.
        from ..device import enable_compile_cache

        enable_compile_cache()
    from .report import Report

    report = Report()

    if args.list_fixtures:
        from .fixtures import FIXTURES

        for name, fixture in sorted(FIXTURES.items()):
            print(f"{name}: expects {fixture.rule}")
        return 0

    if args.fixture is not None:
        from .fixtures import FIXTURES, run_fixture

        if args.fixture not in FIXTURES:
            print(
                f"unknown fixture {args.fixture!r}; "
                f"available: {', '.join(sorted(FIXTURES))}",
                file=sys.stderr,
            )
            return 2
        report.extend(run_fixture(args.fixture))
        report.backends[f"fixture:{args.fixture}"] = {"status": "fixture"}
    else:
        if args.passes in ("all", "jaxpr"):
            from .invariants import run_jaxpr_pass

            findings, meta = run_jaxpr_pass()
            report.extend(findings)
            report.backends.update(meta)
        if args.passes in ("all", "ast"):
            from .ast_rules import run_ast_pass

            findings, n_files = run_ast_pass()
            report.extend(findings)
            report.files_scanned = n_files
        if args.passes in ("all", "concurrency"):
            from .concurrency import run_concurrency_pass

            findings, section = run_concurrency_pass()
            report.extend(findings)
            report.concurrency = section
        if args.passes in ("all", "comm"):
            from .comm import run_comm_pass

            findings, section = run_comm_pass(include_zk=args.zk)
            report.extend(findings)
            report.comm = section
        if args.passes in ("all", "memory"):
            from .memory import run_memory_pass

            findings, section = run_memory_pass(include_zk=args.zk)
            report.extend(findings)
            report.memory = section
        if args.passes in ("all", "determinism"):
            from .determinism import run_determinism_pass

            findings, section = run_determinism_pass(include_zk=args.zk)
            report.extend(findings)
            report.determinism = section

    report.write_json(args.output)
    print(report.render())
    return report.exit_code


if __name__ == "__main__":
    sys.exit(main())
