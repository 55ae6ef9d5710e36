"""Runtime memory probe -> ``MEM_r<N>.json`` (the CI mem-probe job).

Graftlint pass 12 pins peak HBM *statically* from the buffer
assignment of every backend's compiled converge; this probe closes the
loop at runtime, the way ``tools/comm_probe.py`` does for the comm
wall:

- **8-dev dryrun**: every registered jax backend runs a REAL converge
  on the analyzer's 8-device CPU mesh at the analyzer's pinned scale,
  with the PR 6 memory-watermark watcher armed.  Measured peak per
  backend is the allocator's ``memory_stats()`` high-water mark where
  the platform reports one (TPU); platforms without allocator stats
  (CPU) degrade to the executed module's buffer-assignment peak — the
  allocation the runtime actually makes — recorded through the
  watcher's new ``record_converge_peak`` so the
  ``eigentrust_converge_peak_bytes{backend}`` gauge is populated
  either way.
- **2-process ``jax.distributed`` round**: two workers (gloo CPU, 2x4
  mesh) run a real cross-process ``converge_sharded``; each scrapes
  its OWN executable's memory analysis and asserts the per-process
  peak fits the per-shard MEM_INVARIANTS allowance — per-shard peak
  must scale as E/n_shards, the ROADMAP item 1 prerequisite.

Every backend's measured peak is asserted ``<= static budget`` at the
probe scale; any overrun, worker crash, or diverged score exits
non-zero.  The report carries sentinel-shaped ``entries``
(``peak_hbm_bytes`` / ``peak_hbm_bytes_per_shard``, lower-is-better)
so ``tools/perf_sentinel.py`` gates the recorded trajectory.

Run: ``python tools/mem_probe.py [--smoke] [--out MEM_rNN.json]``
"""

from __future__ import annotations

import argparse
import json
import os
import re
import resource
import socket
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

#: Devices per worker process; 2 workers -> the analyzer's 8-way mesh.
LOCAL_DEVICES = 4
N_PROCESSES = 2

#: Probe scale = the analyzer's first compile scale, so the committed
#: budgets apply without re-derivation.
PROBE_PEERS, PROBE_EDGES = 1024, 4096


def _ensure_cpu_mesh() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])


def _next_round_path() -> str:
    rounds = [0]
    for p in REPO.glob("MEM_r*.json"):
        m = re.fullmatch(r"MEM_r(\d+)\.json", p.name)
        if m:
            rounds.append(int(m.group(1)))
    return str(REPO / f"MEM_r{max(rounds) + 1:02d}.json")


def _allocator_peak() -> int | None:
    """Summed ``peak_bytes_in_use`` over local devices, or None where
    the platform has no allocator stats (CPU)."""
    import jax

    try:
        stats = [d.memory_stats() for d in jax.local_devices()]
    except Exception:  # noqa: BLE001
        return None
    if not stats or any(s is None for s in stats):
        return None
    return sum(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _backend_round(name: str, budget) -> dict:
    """One backend: real converge on the 8-dev mesh + peak cross-check."""
    import numpy as np

    from protocol_tpu.analysis.comm.lowering import _graph, build_cases
    from protocol_tpu.analysis.memory.liveness import measured_view
    from protocol_tpu.obs.watchers import MEMORY_WATERMARKS
    from protocol_tpu.trust.backend import get_backend

    # The analyzer's executable for this backend at the probe scale:
    # its buffer assignment is the fallback measured signal, and its
    # dims evaluate the budget.
    case = build_cases(name)[0]
    view, source = measured_view(case)
    dims = case.dims
    static_budget = budget.max_resident(
        dims.get("n", 0), dims.get("edges", 0), dims.get("n_segments", 0),
        dims.get("n_rows", 0), dims.get("n_shards", 1),
    ) + budget.max_transient(
        dims.get("n", 0), dims.get("n_segments", 0), dims.get("n_rows", 0)
    )

    # Run the REAL converge through the trust-backend interface (the
    # node's code path, converge spans included) on the same synthetic
    # graph family the analyzer compiles.
    graph = _graph(PROBE_PEERS, PROBE_EDGES)
    backend = get_backend(name)
    result = backend.converge(graph, alpha=0.1, tol=1e-6, max_iter=8)
    scores = np.asarray(result.scores)
    l1 = float(scores.sum())

    alloc_peak = _allocator_peak()
    measured = alloc_peak if alloc_peak is not None else view["peak_bytes"]
    measured_source = "memory_stats" if alloc_peak is not None else source
    # Populate the per-backend gauge either way (the watcher's span
    # hook already did on allocator-stats platforms; this is the
    # explicit path for the rest).
    MEMORY_WATERMARKS.record_converge_peak(name, measured)

    ok = measured <= static_budget and abs(l1 - 1.0) < 1e-3
    return {
        "backend": name,
        "dims": dims,
        "iterations": int(result.iterations),
        "l1": l1,
        "measured_peak_bytes": int(measured),
        "measured_source": measured_source,
        "static_budget_bytes": static_budget,
        "buffer_assignment": view,
        "ok": bool(ok),
    }


def _worker(process_id: int, coordinator: str, out_path: str) -> int:
    """Distributed worker: one cross-process sharded converge + a
    per-shard peak scrape of its own executable."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={LOCAL_DEVICES}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    result: dict = {"process_id": process_id, "ok": False}
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=N_PROCESSES,
            process_id=process_id,
        )
    except Exception as exc:  # old jaxlib: no multi-process CPU
        result.update(skipped=True, reason=repr(exc))
        Path(out_path).write_text(json.dumps(result))
        return 0

    import jax.numpy as jnp
    import numpy as np

    from protocol_tpu.analysis.budget import MEM_INVARIANTS
    from protocol_tpu.models.graphs import scale_free
    from protocol_tpu.parallel.mesh import SHARD_AXIS, default_mesh
    from protocol_tpu.parallel.sharded import (
        ShardedTrustProblem,
        _get_runner,
        converge_sharded,
    )
    from protocol_tpu.trust.backend import normalize_graph

    backend = "tpu-sharded:tpu-csr"
    budget = MEM_INVARIANTS[backend]
    mesh = default_mesh()
    n_shards = mesh.shape[SHARD_AXIS]

    graph = scale_free(PROBE_PEERS, PROBE_EDGES, seed=1)
    prob = ShardedTrustProblem.build(normalize_graph(graph), mesh)
    t, iters, resid = converge_sharded(prob, alpha=0.1, tol=1e-6, max_iter=8)
    scores = np.asarray(t)

    run = _get_runner(mesh, prob.n)
    comp = run.lower(
        prob.src, prob.w, prob.row_ptr, prob.t0(), prob.p, prob.dangling,
        jnp.asarray(0.1, jnp.float32), max_iter=8, tol=1e-6,
    ).compile()
    ma = comp.memory_analysis()
    violations: list[str] = []
    if ma is None:
        violations.append("executable exposes no memory analysis")
        per_shard_peak = -1
    else:
        resident = int(ma.argument_size_in_bytes)
        transient = (
            int(ma.temp_size_in_bytes)
            + int(ma.output_size_in_bytes)
            - int(ma.alias_size_in_bytes)
        )
        per_shard_peak = resident + transient
        e_pad = int(prob.src.shape[0])
        allow = budget.max_resident(prob.n, e_pad, 0, 0, n_shards)
        allow += budget.max_transient(prob.n, 0, 0)
        if resident > budget.max_resident(prob.n, e_pad, 0, 0, n_shards):
            violations.append(
                f"per-shard resident {resident} > E/n_shards allowance "
                f"{budget.max_resident(prob.n, e_pad, 0, 0, n_shards):.0f}"
            )
        if per_shard_peak > allow:
            violations.append(
                f"per-shard peak {per_shard_peak} > budget {allow:.0f}"
            )
        result.update(budget_bytes=allow)
    result.update(
        backend=backend,
        n=int(prob.n),
        n_shards=n_shards,
        iterations=int(iters),
        residual=float(resid),
        l1=float(scores.sum()),
        peak_hbm_bytes_per_shard=per_shard_peak,
        violations=violations,
        ok=bool(not violations and abs(float(scores.sum()) - 1.0) < 1e-3),
    )
    Path(out_path).write_text(json.dumps(result))
    return 0 if result["ok"] else 1


def _distributed_round(timeout: float) -> dict:
    coordinator = f"127.0.0.1:{_free_port()}"
    with tempfile.TemporaryDirectory() as tmp:
        outs = [str(Path(tmp) / f"worker{i}.json") for i in range(N_PROCESSES)]
        procs = [
            subprocess.Popen(
                [
                    sys.executable, __file__,
                    "--worker", str(i),
                    "--coordinator", coordinator,
                    "--worker-out", outs[i],
                ],
                cwd=REPO,
            )
            for i in range(N_PROCESSES)
        ]
        rcs = []
        for p in procs:
            try:
                rcs.append(p.wait(timeout=timeout))
            except subprocess.TimeoutExpired:
                p.kill()
                rcs.append(-9)
        workers = []
        for path in outs:
            try:
                workers.append(json.loads(Path(path).read_text()))
            except (OSError, json.JSONDecodeError):
                workers.append({"ok": False, "error": "no worker report"})
    skipped = all(w.get("skipped") for w in workers)
    ok = skipped or (
        all(rc == 0 for rc in rcs) and all(w.get("ok") for w in workers)
    )
    if ok and not skipped:
        resids = [w["residual"] for w in workers]
        if abs(resids[0] - resids[1]) > 1e-9:
            ok = False
            workers.append({"error": f"residual divergence: {resids}"})
    return {
        "mesh": f"{N_PROCESSES}x{LOCAL_DEVICES}",
        "ok": ok,
        "skipped": skipped,
        "return_codes": rcs,
        "workers": workers,
    }


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None, help="report path (default: next MEM_r<N>.json)")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI smoke: same scales, distinct metric strings so the "
        "sentinel never cross-compares smoke vs recorded rounds",
    )
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--worker-out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker is not None:
        return _worker(args.worker, args.coordinator, args.worker_out)

    _ensure_cpu_mesh()
    from protocol_tpu.analysis.budget import MEM_INVARIANTS, NON_JAX_BACKENDS
    from protocol_tpu.obs.watchers import MEMORY_WATERMARKS
    from protocol_tpu.parallel import sharded  # noqa: F401  (declares budgets)
    from protocol_tpu.trust.backend import registered_backends

    tag = "smoke" if args.smoke else "8-dev CPU mesh"
    rounds = []
    for name in registered_backends():
        if name in NON_JAX_BACKENDS:
            continue
        rounds.append(_backend_round(name, MEM_INVARIANTS[name]))

    dist = _distributed_round(args.timeout)

    entries = [
        {
            "metric": (
                f"converge peak HBM bytes ({r['backend']}, {tag}, "
                f"{PROBE_PEERS} peers/{PROBE_EDGES} edges)"
            ),
            "peak_hbm_bytes": r["measured_peak_bytes"],
            "unit": "bytes",
        }
        for r in rounds
    ]
    for w in dist["workers"]:
        if w.get("peak_hbm_bytes_per_shard", -1) > 0 and w["process_id"] == 0:
            entries.append({
                "metric": (
                    f"per-shard converge peak HBM bytes "
                    f"({w['backend']}, 2-process jax.distributed, {tag}, "
                    f"{PROBE_PEERS} peers/{PROBE_EDGES} edges)"
                ),
                "peak_hbm_bytes_per_shard": w["peak_hbm_bytes_per_shard"],
                "unit": "bytes",
            })

    ok = all(r["ok"] for r in rounds) and dist["ok"]
    report = {
        "tool": "mem_probe",
        "ok": ok,
        "scale": {"peers": PROBE_PEERS, "edges": PROBE_EDGES},
        "backends": rounds,
        "distributed": dist,
        "converge_peak_gauge": MEMORY_WATERMARKS.converge_peaks(),
        "ru_maxrss_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
        "entries": entries,
    }
    out = args.out or _next_round_path()
    Path(out).write_text(json.dumps(report, indent=2) + "\n")
    status = "OK" if ok else "FAILED"
    worst = max(
        (r["measured_peak_bytes"] / r["static_budget_bytes"] for r in rounds),
        default=0.0,
    )
    print(
        f"mem_probe: {status} — {len(rounds)} backends measured <= static "
        f"budget (worst fill {worst:.1%}), distributed "
        f"{'SKIPPED' if dist['skipped'] else 'OK' if dist['ok'] else 'FAILED'} "
        f"({out})"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
