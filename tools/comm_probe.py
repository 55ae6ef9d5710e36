"""2-process ``jax.distributed`` CPU comm smoke (the CI comm-probe job)
— the first executable artifact of the multi-host path (ROADMAP item 3).

Graftlint pass 8 proves the partitioner's collective structure
*statically* under a single-process 8-device mesh; this probe proves
the same structure holds when the mesh actually spans processes.  The
parent spawns two workers; each one:

- initializes ``jax.distributed`` (gloo CPU collectives, 4 local
  devices per process -> a 2x4 = 8-device global mesh, the same shard
  count the static analyzer uses),
- builds the sharded trust problem and runs one real
  ``converge_sharded`` across both processes,
- scrapes its own compiled module (``run.lower(...).compile()``) with
  the pass-8 walker, and
- asserts the **measured collective structure is a subset of the
  static COMM_INVARIANTS budget**: only declared kinds, within
  declared counts, per-iteration bytes within the O(boundary + N)
  allowance, donation alias present.

The parent aggregates both workers into ``COMM_PROBE.json`` (CI
artifact) and exits non-zero on any violation, a diverged score
vector, or a worker crash.  On a jax build without multi-process CPU
collectives the probe reports ``skipped`` and exits 0 — the static
pass still gates.

Run: ``python tools/comm_probe.py [--out COMM_PROBE.json]``
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

#: Defaults: 2 workers x 4 devices -> the analyzer's 8-way mesh.  Both
#: are CLI-tunable (``--processes``/``--local-devices``) so the pod
#: dryrun and CI can run probes of different shapes concurrently — the
#: coordinator port is always picked from a free socket, never fixed.
LOCAL_DEVICES = 4
N_PROCESSES = 2


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker(process_id: int, coordinator: str, out_path: str,
            n_peers: int, n_edges: int,
            n_processes: int = N_PROCESSES,
            local_devices: int = LOCAL_DEVICES) -> int:
    """Worker body: distributed init, one sharded converge, self-scrape."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    result: dict = {"process_id": process_id, "ok": False}
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=n_processes,
            process_id=process_id,
        )
    except Exception as exc:  # old jaxlib: no multi-process CPU
        result.update(skipped=True, reason=repr(exc))
        Path(out_path).write_text(json.dumps(result))
        return 0

    import jax.numpy as jnp
    import numpy as np

    from protocol_tpu.analysis.budget import COMM_INVARIANTS
    from protocol_tpu.analysis.comm.hlo_walk import parse_module
    from protocol_tpu.models.graphs import scale_free
    from protocol_tpu.parallel.mesh import SHARD_AXIS, default_mesh
    from protocol_tpu.parallel.sharded import (
        ShardedTrustProblem,
        _get_runner,
        converge_sharded,
    )
    from protocol_tpu.trust.backend import normalize_graph

    backend = "tpu-sharded:tpu-csr"
    budget = COMM_INVARIANTS[backend]
    mesh = default_mesh()
    n_shards = mesh.shape[SHARD_AXIS]
    result.update(
        backend=backend,
        n_processes=n_processes,
        local_devices=len(jax.local_devices()),
        global_devices=len(jax.devices()),
        n_shards=n_shards,
    )

    graph = scale_free(n_peers, n_edges, seed=1)
    prob = ShardedTrustProblem.build(normalize_graph(graph), mesh)
    t, iters, resid = converge_sharded(prob, alpha=0.1, tol=1e-6, max_iter=8)
    scores = np.asarray(t)
    result.update(
        n=int(prob.n),
        iterations=int(iters),
        residual=float(resid),
        l1=float(scores.sum()),
    )

    # Scrape THIS process's lowering — the module the local runtime
    # actually executes — and judge it against the static budget.
    run = _get_runner(mesh, prob.n)
    comp = run.lower(
        prob.src, prob.w, prob.row_ptr, prob.t0(), prob.p, prob.dangling,
        jnp.asarray(0.1, jnp.float32), max_iter=8, tol=1e-6,
    ).compile()
    mod = parse_module(comp.as_text())
    violations: list[str] = []
    counts = mod.kind_counts()
    for kind, count in sorted(counts.items()):
        allowed = budget.allowed_count(kind)
        if allowed == 0:
            violations.append(f"undeclared collective kind {kind} (x{count})")
        elif count > allowed:
            violations.append(f"{kind} x{count} > budget {allowed}")
    measured = mod.total_bytes(per_iteration_only=True)
    allowance = budget.max_bytes(prob.n, 0, n_shards)
    if measured > allowance:
        violations.append(
            f"per-iteration bytes {measured} > budget {allowance:.0f}"
        )
    if 3 not in mod.aliased_params():  # t0 is parameter 3 of the runner
        violations.append(
            f"t0 donation missing from input_output_alias "
            f"{sorted(mod.aliases.items())}"
        )
    if mod.host_calls:
        violations.append(
            f"host round-trips in module: "
            f"{[h.target or h.op for h in mod.host_calls]}"
        )
    result.update(
        collectives=[op.to_dict() for op in mod.collectives],
        bytes_per_iter=measured,
        budget_bytes=allowance,
        input_output_alias={str(k): v for k, v in sorted(mod.aliases.items())},
        violations=violations,
        ok=bool(not violations and abs(float(scores.sum()) - 1.0) < 1e-3),
    )
    Path(out_path).write_text(json.dumps(result))
    return 0 if result["ok"] else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="COMM_PROBE.json", help="report path")
    ap.add_argument("--peers", type=int, default=512)
    ap.add_argument("--edges", type=int, default=4096)
    ap.add_argument(
        "--processes", type=int, default=N_PROCESSES,
        help="worker process count (default 2)",
    )
    ap.add_argument(
        "--local-devices", type=int, default=LOCAL_DEVICES,
        help="forced CPU devices per process (default 4)",
    )
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--worker-out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker is not None:
        return _worker(
            args.worker, args.coordinator, args.worker_out,
            args.peers, args.edges, args.processes, args.local_devices,
        )

    coordinator = f"127.0.0.1:{_free_port()}"
    with tempfile.TemporaryDirectory() as tmp:
        outs = [
            str(Path(tmp) / f"worker{i}.json") for i in range(args.processes)
        ]
        procs = [
            subprocess.Popen(
                [
                    sys.executable, __file__,
                    "--worker", str(i),
                    "--coordinator", coordinator,
                    "--worker-out", outs[i],
                    "--peers", str(args.peers),
                    "--edges", str(args.edges),
                    "--processes", str(args.processes),
                    "--local-devices", str(args.local_devices),
                ],
                cwd=REPO,
            )
            for i in range(args.processes)
        ]
        rcs = []
        for p in procs:
            try:
                rcs.append(p.wait(timeout=args.timeout))
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
    # Cross-process agreement: every worker holds the full replicated
    # result; their residuals must match bit-for-bit-ish.
    if ok and not skipped:
        resids = [w["residual"] for w in workers]
        if max(resids) - min(resids) > 1e-9:
            ok = False
            workers.append({"error": f"residual divergence: {resids}"})
    report = {
        "tool": "comm_probe",
        "mesh": f"{args.processes}x{args.local_devices}",
        "ok": ok,
        "skipped": skipped,
        "return_codes": rcs,
        "workers": workers,
    }
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    status = "SKIPPED (no multi-process CPU collectives)" if skipped else (
        "OK" if ok else "FAILED"
    )
    print(f"comm_probe: {status} — report in {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
