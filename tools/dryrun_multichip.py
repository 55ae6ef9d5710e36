"""Multi-chip dry run + pass-8 comm scrape -> ``MULTICHIP_r<N>.json``.

The driver's ``dryrun_multichip`` entry (``__graft_entry__.py``) proves
the sharded path *computes* correctly on an n-device mesh; this tool
runs the same dual-kernel dry run and additionally records what the
run *communicates*: for each sharded composite it scrapes the compiled
module with the graftlint pass-8 walker and persists the per-epoch
collective table — kind, replica groups, per-iteration byte volume —
next to the correctness verdict.  The ``entries`` list is shaped for
``tools/perf_sentinel.py``, which tracks ``comm_bytes_per_iter`` as a
per-metric series: a PR that silently inflates wire traffic (a
partitioner surprise at a new jax pin, a resharding regression) now
moves a recorded number, not just a lint bit.

The mesh is the devices this process sees; too few is an error.  A
CPU-mesh run sets its environment from outside:

Run: ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
python tools/dryrun_multichip.py [--devices 8] [--out PATH]``
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _next_round_path() -> str:
    """``MULTICHIP_r<N>.json`` with N following the highest recorded
    multichip round (same convention as bench.py's ladder rounds)."""
    rounds = [0]
    for p in REPO.glob("MULTICHIP_r*.json"):
        m = re.fullmatch(r"MULTICHIP_r(\d+)\.json", p.name)
        if m:
            rounds.append(int(m.group(1)))
    return str(REPO / f"MULTICHIP_r{max(rounds) + 1:02d}.json")


def _scrape(backend: str, lowered) -> dict:
    """Pass-8 walk + pass-12 buffer-assignment view of one compiled
    runner module."""
    from protocol_tpu.analysis.comm.hlo_walk import parse_module

    compiled = lowered.compile()
    mod = parse_module(compiled.as_text())
    out = {
        "collectives": [op.to_dict() for op in mod.collectives],
        "bytes_per_iter": mod.total_bytes(per_iteration_only=True),
        "input_output_alias": {
            str(k): v for k, v in sorted(mod.aliases.items())
        },
        "host_round_trips": len(mod.host_calls),
    }
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - absent on some runtimes
        ma = None
    if ma is not None:
        # memory_analysis is the PER-DEVICE view: under the mesh this
        # is the per-shard footprint the MEM_INVARIANTS budgets pin.
        out["peak_hbm_bytes_per_shard"] = int(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes
        )
    return out


def _body(n_devices: int, n_peers: int, n_edges: int, iters: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from protocol_tpu.models.graphs import scale_free
    from protocol_tpu.parallel.mesh import default_mesh
    from protocol_tpu.parallel.sharded import (
        ShardedTrustProblem,
        ShardedWindowPlan,
        _get_runner,
        converge_sharded,
        windowed_operands,
        windowed_runner,
    )
    from protocol_tpu.trust.backend import normalize_graph

    mesh = default_mesh(n_devices)
    graph = scale_free(n_peers, n_edges, seed=1)
    alpha = jnp.asarray(0.1, jnp.float32)

    ng = normalize_graph(graph)
    prob = ShardedTrustProblem.build(ng, mesh)
    t, iters_run, resid = converge_sharded(
        prob, alpha=0.1, tol=1e-6, max_iter=iters
    )
    scores = np.asarray(t)
    assert scores.shape == (graph.n,)
    assert abs(float(scores.sum()) - 1.0) < 1e-3

    swp = ShardedWindowPlan.build(ng, mesh)
    tw, _, _ = converge_sharded(swp, alpha=0.1, tol=1e-6, max_iter=iters)
    drift = float(np.abs(np.asarray(tw) - scores).sum())
    assert drift < 1e-4, f"windowed sharded kernel drifted from csr: {drift}"

    csr_run = _get_runner(mesh, prob.n)
    comm = {
        "tpu-sharded:tpu-csr": _scrape(
            "tpu-sharded:tpu-csr",
            csr_run.lower(
                prob.src, prob.w, prob.row_ptr, prob.t0(), prob.p,
                prob.dangling, alpha, max_iter=iters, tol=1e-6,
            ),
        )
    }
    win_run = windowed_runner(swp)
    comm["tpu-sharded:tpu-windowed"] = _scrape(
        "tpu-sharded:tpu-windowed",
        win_run.lower(
            *windowed_operands(swp), swp.t0(), swp.p, swp.dangling,
            alpha, max_iter=iters, tol=1e-6,
        ),
    )

    entries = [
        {
            "metric": (
                f"per-iteration collective bytes ({backend}, "
                f"{n_devices}-dev mesh, {graph.n} peers/{n_edges} edges)"
            ),
            "comm_bytes_per_iter": scraped["bytes_per_iter"],
            "unit": "bytes",
        }
        for backend, scraped in comm.items()
    ]
    # Pass-12 series: per-process (= per-shard) converge peaks, so a
    # PR that inflates the per-host footprint moves a recorded number.
    entries += [
        {
            "metric": (
                f"per-shard converge peak HBM bytes ({backend}, "
                f"{n_devices}-dev mesh, {graph.n} peers/{n_edges} edges)"
            ),
            "peak_hbm_bytes_per_shard": scraped["peak_hbm_bytes_per_shard"],
            "unit": "bytes",
        }
        for backend, scraped in comm.items()
        if "peak_hbm_bytes_per_shard" in scraped
    ]
    return {
        "n_devices": n_devices,
        "rc": 0,
        "ok": True,
        "skipped": False,
        "n_peers": graph.n,
        "n_edges": int(n_edges),
        "iterations": int(iters_run),
        "residual": float(resid),
        "windowed_vs_csr_l1": drift,
        "comm": comm,
        "entries": entries,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--peers", type=int, default=512)
    ap.add_argument("--edges", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument(
        "--out",
        default=None,
        help="report path (default: MULTICHIP_r<N>.json, next round)",
    )
    args = ap.parse_args(argv)
    out = args.out or _next_round_path()

    import jax

    if len(jax.devices()) < args.devices:
        raise SystemExit(
            f"dryrun_multichip: --devices {args.devices} but JAX sees "
            f"{len(jax.devices())} ({jax.default_backend()}); for a CPU "
            "mesh set JAX_PLATFORMS=cpu and "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )

    report = _body(args.devices, args.peers, args.edges, args.iters)
    Path(out).write_text(json.dumps(report, indent=2) + "\n")
    bytes_by_backend = {
        b: c["bytes_per_iter"] for b, c in report["comm"].items()
    }
    print(
        f"dryrun_multichip: {args.devices} devices, n={report['n_peers']}, "
        f"{report['iterations']} iters, residual {report['residual']:.2e}, "
        f"windowed drift {report['windowed_vs_csr_l1']:.2e}, "
        f"collective bytes/iter {bytes_by_backend} — OK ({out})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
