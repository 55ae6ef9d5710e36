"""Pod scale-out dryrun: a multi-process ``jax.distributed`` churned
epoch replay over host-sharded edge partitions (ROADMAP item 1,
PERF.md §20 — the ISSUE 16 acceptance tool).

The parent spawns ``--processes`` workers.  Each worker is one pod
host: it initializes ``jax.distributed`` (gloo CPU collectives,
``--local-devices`` forced host devices), derives the identical
deterministic attestation stream (``models.churn`` sender-centric
epochs), and per epoch

- journals **only the churn rows whose source peer it owns**
  (``parallel.partition`` rendezvous hash) into its own per-host WAL
  shard, fsyncs, and acknowledges them (``acks-h*.jsonl``);
- builds its **local** window plan only (``parallel.pod.PodWindowPlan``
  — reuse/delta/rebuild against the local fingerprint, so churn owned
  by other hosts never forces a rebuild here) and assembles the global
  sharded arrays without moving an edge byte across hosts;
- runs the identical ``converge_sharded`` windowed runner across the
  whole pod (one boundary-completing psum per step) with a warm start
  from the previous fixed point;
- checkpoints its local graph shard + plan (``CheckpointStore``),
  publishes its shard stamp, and host 0 seals the epoch into the pod
  manifest (``node.pod.PodDurability``).

The first executed epoch is also **scraped**: the worker compiles its
own runner AOT and judges the module with the real graftlint passes —
``check_comm_case`` (collective kinds/counts/bytes, donation aliases,
replica-group coverage over the *multi-process* mesh) and
``check_mem_case`` (per-shard resident/transient vs MEM_INVARIANTS,
plus the ``pod_budget_view`` allowance the measured peak must clear).

The parent asserts **per-epoch residuals and score digests are
bit-identical across all workers** (every host holds the replicated
result; the pod either agrees exactly or is broken), aggregates a
reference subprocess (serial full-graph plan build vs per-partition
builds → ``plan_build_seconds`` sentinel series; a single-host run at
1/H scale for the flat-epoch-seconds comparison; a full-scale cold
converge for the L1 correctness pin), and emits sentinel-shaped
``entries`` keyed on ``n_hosts``.

``--chaos-host-loss`` adds the crash-matrix host-loss row: a second
run crashes one worker mid-epoch (``os._exit`` after WAL ack, before
converge — the kill -9 analog), the parent reaps the stuck survivors,
relaunches the whole pod with ``--resume``, and requires (a) zero
acknowledged attestations lost — every acked epoch past the sealed
manifest replays from the host's WAL shard with the exact payload
digest — and (b) a final fixed point **bit-identical** to the
uncrashed control run.

Run::

    python tools/dryrun_pod.py --smoke --out POD_smoke.json
    python tools/dryrun_pod.py --smoke --chaos-host-loss --out POD_smoke.json
    python tools/dryrun_pod.py --peers 20000 --edges 160000 --epochs 4 \
        --chaos-host-loss --round 1 --out POD_r01.json

Exit 0 = every invariant held (or the jax build has no multi-process
CPU collectives: ``skipped``); 1 = divergence, budget violation, or
lost acknowledged data.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import struct
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

BACKEND = "tpu-sharded:tpu-windowed"

#: L1 bar for pod-warm vs single-host-cold scores (the bench.py
#: ``warm_vs_cold_l1`` doctrine / crash_matrix SCORE_TOL).
SCORE_TOL = 1e-4

#: WAL record header: epoch u32, row u32, out-degree u32, then
#: ``deg`` destinations (u32) and ``deg`` weights (f32).  Genuinely
#: reconstructive — recovery re-derives the row's out-edges from the
#: record alone, no side channel.
_HDR = struct.Struct("<III")


def encode_row(epoch: int, row: int, dst, w) -> bytes:
    import numpy as np

    dst = np.asarray(dst, "<u4")
    return _HDR.pack(epoch, row, dst.size) + dst.tobytes() + (
        np.asarray(w, "<f4").tobytes()
    )


def decode_row(payload: bytes):
    import numpy as np

    epoch, row, deg = _HDR.unpack_from(payload)
    off = _HDR.size
    dst = np.frombuffer(payload, "<u4", deg, off)
    w = np.frombuffer(payload, "<f4", deg, off + 4 * deg)
    return epoch, row, dst, w


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _jsonable(o):
    import numpy as np

    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON-serializable: {type(o)}")


# ---------------------------------------------------------------------------
# deterministic stream — every host derives the identical epochs
# ---------------------------------------------------------------------------


def _epoch_rng(seed: int, epoch: int):
    import numpy as np

    # Independent per-epoch seeding (not one carried generator): any
    # host — including one recovering from a crash — regenerates epoch
    # e without replaying epochs < e.
    return np.random.default_rng((seed + 1) * 1_000_003 + epoch)


def bootstrap_graph(args):
    from protocol_tpu.models.graphs import scale_free

    return scale_free(args.peers, args.edges, seed=args.seed).drop_self_edges()


def churn_epoch(cur, epoch: int, args):
    from protocol_tpu.models.churn import churn_cohort_dims, sender_centric_churn

    cohort_size, deg = churn_cohort_dims(cur, args.churn)
    return sender_centric_churn(
        _epoch_rng(args.seed, epoch), cur, cohort_size=cohort_size, deg=deg
    )


# ---------------------------------------------------------------------------
# worker (one pod host)
# ---------------------------------------------------------------------------


def _scrape(podplan, n_edges: int):
    """Compile this process's runner AOT and judge the module with the
    real graftlint comm + memory passes over the multi-process mesh."""
    from dataclasses import asdict
    from functools import partial

    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from protocol_tpu.analysis.budget import COMM_INVARIANTS, MEM_INVARIANTS
    from protocol_tpu.analysis.comm.checker import check_comm_case
    from protocol_tpu.analysis.comm.lowering import (
        CommCase,
        _jaxpr_psums,
        _mem_stats,
    )
    from protocol_tpu.analysis.memory.checker import (
        check_mem_case,
        pod_budget_view,
    )
    from protocol_tpu.parallel.mesh import SHARD_AXIS
    from protocol_tpu.parallel.sharded import windowed_operands, windowed_runner

    run = windowed_runner(podplan)
    alpha = jax.device_put(np.float32(0.1), NamedSharding(podplan.mesh, P()))
    plan_args = (
        *windowed_operands(podplan), podplan.t0(), podplan.p, podplan.dangling, alpha,
    )
    kw = dict(max_iter=4, tol=1e-6)
    comp = run.lower(*plan_args, **kw).compile()
    jaxpr = jax.make_jaxpr(partial(run, **kw))(*plan_args)
    n_shards = podplan.mesh.shape[SHARD_AXIS]
    case = CommCase(
        backend=BACKEND,
        dims={
            "n": podplan.n,
            "edges": n_edges,
            "n_segments": podplan.s_max,
            "n_rows": podplan.rows_per_shard,
            "n_shards": n_shards,
        },
        module_text=comp.as_text(),
        arg_names=(
            "wid", "local", "weight", "run_start", "run_end", "dst_ptr",
            "t0", "p", "dangling", "alpha",
        ),
        jaxpr_psums=_jaxpr_psums(jaxpr),
        mem=_mem_stats(comp),
    )
    comm_findings, comm_record = check_comm_case(COMM_INVARIANTS[BACKEND], case)
    mem_findings, mem_record = check_mem_case(MEM_INVARIANTS[BACKEND], case)
    pod_view = pod_budget_view(
        MEM_INVARIANTS[BACKEND],
        n=podplan.n, edges=n_edges, n_segments=podplan.s_max,
        rows=podplan.rows_per_shard, n_shards=n_shards,
        n_hosts=podplan.n_hosts,
    )
    findings = comm_findings + mem_findings
    peak = mem_record.get("measured", {}).get("peak_bytes")
    return {
        "comm": comm_record,
        "mem": mem_record,
        "pod_budget": pod_view,
        "peak_within_pod_budget": (
            peak is not None and peak <= pod_view["peak_bytes"]
        ),
        "findings": [asdict(f) for f in findings],
        "ok": not findings
        and peak is not None
        and peak <= pod_view["peak_bytes"],
    }


def worker_main(args) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.local_devices}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    out = Path(args.worker_out)
    result: dict = {"process_id": args.worker, "ok": False}
    if args.processes > 1:
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=args.coordinator,
                num_processes=args.processes,
                process_id=args.worker,
            )
        except Exception as exc:  # old jaxlib: no multi-process CPU
            result.update(skipped=True, reason=repr(exc))
            out.write_text(json.dumps(result))
            return 0

    import numpy as np

    from protocol_tpu.node.epoch import Epoch
    from protocol_tpu.node.pod import PodDurability
    from protocol_tpu.obs import metrics as obs_metrics
    from protocol_tpu.obs import podtrace as obs_podtrace
    from protocol_tpu.obs.fleet import load_directory, publish_snapshot
    from protocol_tpu.obs.journal import JOURNAL, install_worker_dump_handler
    from protocol_tpu.obs.slo import SLO_ENGINE, install_pod_defaults
    from protocol_tpu.obs.trace import TRACER
    from protocol_tpu.obs.watchers import STRAGGLERS
    from protocol_tpu.parallel.mesh import SHARD_AXIS
    from protocol_tpu.parallel.pod import PodContext, PodWindowPlan
    from protocol_tpu.parallel.sharded import converge_sharded
    from protocol_tpu.trust.backend import normalize_graph
    from protocol_tpu.trust.graph import TrustGraph

    pod = PodContext.current(seed=args.seed)
    host = pod.host_id
    obs_metrics.POD_HOSTS.set(pod.n_hosts)
    obs_metrics.POD_HOST_ID.set(host)
    pd = PodDurability(args.state_dir, host, pod.n_hosts)
    ack_path = Path(args.state_dir) / f"acks-h{host:03d}.jsonl"

    # Pod observability plumbing (ISSUE 19): per-epoch trace + metric
    # snapshots ride the shared exchange; flight-recorder rings dump
    # per epoch (and on SIGTERM) so a crashed host's last events
    # survive for the parent to collect.
    fleet_dir = Path(args.state_dir) / "fleet"
    flight_dir = Path(args.state_dir) / "flight"
    flight_dir.mkdir(parents=True, exist_ok=True)
    install_worker_dump_handler(flight_dir, "pod")
    straggler_host = min(1, pod.n_hosts - 1) if args.seed_straggler else -1
    if host == 0:
        STRAGGLERS.configure(
            ratio=args.straggler_ratio, k=args.straggler_k, min_seconds=0.1
        )

    # -- recovery (resume runs): newest sealed manifest + own shards --
    start_epoch, scores, plan = 0, None, None
    last_seq = -1
    replayed: dict[int, dict] = {}
    recovery = None
    manifest = None
    if args.resume:
        t_rec = time.perf_counter()
        manifest = pd.load_manifest()
        lost: list[int] = []
        if manifest is not None:
            stamp = pd.my_stamp(manifest)
            snap = pd.checkpoints.load(Epoch(int(manifest["epoch"])))
            scores, plan = snap.scores, snap.plan
            start_epoch = int(manifest["epoch"]) + 1
            last_seq = int(stamp["wal_seq"])
            # The WAL tail past the sealed stamp, digested per epoch.
            tail: dict[int, "hashlib._Hash"] = {}
            tail_counts: dict[int, int] = {}
            for seq, payload in pd.wal.replay(after_seq=last_seq):
                e = _HDR.unpack_from(payload)[0]
                tail.setdefault(e, hashlib.sha256()).update(payload)
                tail_counts[e] = tail_counts.get(e, 0) + 1
                last_seq = seq
            # Zero acknowledged loss: every acked epoch past the
            # manifest must replay from this host's shard bit-exactly.
            acks = []
            if ack_path.exists():
                acks = [
                    json.loads(line)
                    for line in ack_path.read_text().splitlines()
                    if line.strip()
                ]
            for rec in acks:
                if rec["epoch"] <= int(manifest["epoch"]):
                    continue  # inside the checkpoint shard
                got = tail.get(rec["epoch"])
                if got is None or got.hexdigest() != rec["digest"]:
                    lost.append(rec["epoch"])
                else:
                    replayed[rec["epoch"]] = rec
            recovery = {
                "seconds": round(time.perf_counter() - t_rec, 4),
                "manifest_epoch": int(manifest["epoch"]),
                "resume_epoch": start_epoch,
                "wal_tail_records": int(sum(tail_counts.values())),
                "acked_epochs_replayed": sorted(replayed),
                "lost_acked_epochs": lost,
            }
        else:
            recovery = {
                "seconds": round(time.perf_counter() - t_rec, 4),
                "resume_epoch": 0,
                "cold": True,
                "lost_acked_epochs": lost,
            }

    # -- regenerate the deterministic stream up to the resume point --
    cur = bootstrap_graph(args)
    for e in range(1, start_epoch):
        _, cur, _ = churn_epoch(cur, e, args)
    owner = pod.partition.assign_ids(cur.n)
    if manifest is not None:
        # The checkpoint shard must equal the stream-derived local
        # partition column-for-column (recovery is reconstruction, not
        # trust).
        m = owner[cur.src] == host
        g = snap.graph
        recovery["checkpoint_matches_stream"] = bool(
            np.array_equal(g.src, cur.src[m])
            and np.array_equal(g.dst, cur.dst[m])
            and np.array_equal(g.weight, cur.weight[m])
        )

    epochs_detail = []
    stitch_reports: list[dict] = []
    scrape = None
    prev_dims = None
    ok = True
    for e in range(start_epoch, args.epochs):
        rows = None
        owned_count = 0
        if e > 0:
            rows, cur, (ns, nd, nw) = churn_epoch(cur, e, args)
        t_epoch = time.perf_counter()
        with TRACER.epoch(e):
            if e > 0:
                with TRACER.span("wal_flush"):
                    deg = ns.shape[0] // rows.shape[0]
                    owned_idx = np.flatnonzero(owner[rows] == host)
                    owned_count = int(owned_idx.size)
                    payloads = [
                        encode_row(
                            e, int(rows[i]),
                            nd[i * deg:(i + 1) * deg],
                            nw[i * deg:(i + 1) * deg],
                        )
                        for i in owned_idx
                    ]
                    digest = hashlib.sha256(b"".join(payloads)).hexdigest()
                    if e in replayed:
                        # Already durable + acknowledged before the
                        # crash; the recovery audit verified the WAL
                        # shard replays it, so re-journaling would only
                        # duplicate records.  The regenerated stream
                        # must still agree with what was acked — the
                        # reconstruction cross-check.
                        if replayed[e]["digest"] != digest:
                            recovery.setdefault(
                                "replay_stream_mismatch", []
                            ).append(e)
                            ok = False
                    else:
                        for pbytes in payloads:
                            last_seq = pd.wal.append(pbytes, flush=False)
                        pd.wal.flush()
                        with ack_path.open("a") as f:
                            f.write(json.dumps({
                                "epoch": e,
                                "count": len(payloads),
                                "digest": digest,
                                "wal_to": last_seq,
                            }) + "\n")
                            f.flush()
                            os.fsync(f.fileno())
                if args.crash_host == host and args.crash_epoch == e:
                    # kill -9 analog: acked data is on disk, nothing else
                    # is — recovery must replay exactly this epoch's rows.
                    # The flight ring dump is the crash handler's last
                    # act (same contract as the SIGTERM dump handler).
                    JOURNAL.dump(
                        flight_dir / f"flight-pod-h{host:03d}.jsonl",
                        reason=f"crash-epoch-{e}",
                    )
                    os._exit(137)

            with TRACER.span("plan"):
                t_plan = time.perf_counter()
                # time.monotonic (not perf_counter) so the barrier and
                # sync stamps share a clock base with the
                # clock_sync_samples() pairs the stitcher aligns on.
                podplan = PodWindowPlan.build(
                    normalize_graph(cur), pod, plan=plan, delta_rows=rows,
                    clock=time.monotonic, wall=time.time,
                )
                plan_update_seconds = time.perf_counter() - t_plan
                plan = podplan.plan

            dims = (podplan.rows_per_shard, podplan.table_entries,
                    podplan.s_max)
            with TRACER.span("converge"):
                if dims != prev_dims:
                    # Eat the jit compile outside the timed region
                    # (bench.py's warm-up policy); recompiles are
                    # counted per epoch.
                    converge_sharded(
                        podplan, alpha=0.1, tol=args.tol,
                        max_iter=args.max_iter, t0=scores,
                    )
                t_conv = time.perf_counter()
                t, iters, resid = converge_sharded(
                    podplan, alpha=0.1, tol=args.tol,
                    max_iter=args.max_iter, t0=scores,
                )
                converge_seconds = time.perf_counter() - t_conv
            scores = np.asarray(t)

            if scrape is None and not args.skip_scrape:
                with TRACER.span("scrape"):
                    scrape = _scrape(podplan, int(cur.nnz))
                ok = ok and scrape["ok"]

            # Durability: local shard checkpoint -> stamp -> host 0 seal.
            with TRACER.span("checkpoint"):
                if host == straggler_host and e > 0:
                    # Seeded straggler rides a LOCAL phase: converge is
                    # collective-synchronized, so a pre-converge sleep
                    # would elongate every host's converge span equally
                    # and produce zero skew.
                    time.sleep(args.straggler_sleep)
                m = owner[cur.src] == host
                lg = TrustGraph(
                    cur.n, cur.src[m], cur.dst[m], cur.weight[m],
                    cur.pre_trusted,
                )
                pd.checkpoints.save(
                    Epoch(e), lg, scores=scores, plan=plan, wal_seq=last_seq
                )
                entry = pd.checkpoints.manifest_entry(Epoch(e))
                sdig = hashlib.sha256(scores.tobytes()).hexdigest()
                pd.publish_shard(
                    e, wal_seq=last_seq, columns=entry["columns"],
                    extra={"scores_sha256": sdig, "residual": float(resid)},
                )
            sealed = None
            if host == 0:
                # Sealing waits on every host's stamp, so it gets its
                # OWN span — folded into `checkpoint` it would read as
                # host-0 checkpoint skew whenever a peer runs late.
                with TRACER.span("seal"):
                    deadline = time.monotonic() + args.seal_timeout
                    while sealed is None and time.monotonic() < deadline:
                        sealed = pd.seal_epoch(e)
                        if sealed is None:
                            time.sleep(0.02)
                ok = ok and sealed is not None

        epoch_seconds = time.perf_counter() - t_epoch
        obs_metrics.POD_OWNED_PEERS.set(int((owner == host).sum()))
        obs_metrics.POD_LOCAL_EDGES.set(podplan.local_edges)
        obs_metrics.POD_PLAN_BUILD_SECONDS.set(podplan.build_seconds)
        obs_metrics.POD_PLAN_REUSED.inc(outcome=podplan.plan_outcome)
        obs_metrics.POD_EPOCH_SECONDS.set(epoch_seconds)
        if sealed is not None:
            obs_metrics.POD_MANIFESTS_SEALED.inc()

        # Pod obs exchange: ship this epoch's span tree + clock-sync
        # burst + barrier probe, refresh the heartbeat snapshot, dump
        # the flight ring (the parent collects the tails), and — host 0
        # — stitch the pod trace once every host has published.
        t_obs = time.perf_counter()
        sync = obs_podtrace.clock_sync_samples()
        if podplan.sync_unix > 0.0:
            sync.append({
                "monotonic": podplan.sync_monotonic,
                "unix": podplan.sync_unix,
            })
        obs_podtrace.publish_epoch_trace(
            fleet_dir, host, e,
            sync=sync,
            barrier={
                "enter_monotonic": podplan.barrier_enter_monotonic,
                "wait_seconds": podplan.barrier_wait_seconds,
            },
        )
        publish_snapshot(fleet_dir, f"h{host:03d}")
        obs_publish_seconds = time.perf_counter() - t_obs

        stitch_summary = None
        if host == 0 and pod.n_hosts > 1:
            obs_deadline = time.monotonic() + args.seal_timeout
            while (
                len(obs_podtrace.directory_hosts(fleet_dir, e)) < pod.n_hosts
                and time.monotonic() < obs_deadline
            ):
                time.sleep(0.02)
            stitched = obs_podtrace.stitch_epoch(
                fleet_dir, e, expected_hosts=pod.n_hosts, graft_into=TRACER
            )
            load_directory(fleet_dir, skip_pid=os.getpid(), max_age_s=30.0)
            if stitched is not None:
                stitch_summary = {
                    "epoch": e,
                    "complete": stitched["complete"],
                    "missing_hosts": stitched["missing_hosts"],
                    "stitch_seconds": stitched["stitch_seconds"],
                    "phase_skew_s": stitched["phase_skew_s"],
                    "barrier_spread_s": stitched["barrier"]["spread_s"],
                    "phase_attribution": stitched["phase_attribution"],
                    "stragglers": stitched.get("stragglers", []),
                }
                stitch_reports.append(stitch_summary)

        epochs_detail.append({
            "epoch": e,
            "seconds": round(epoch_seconds, 4),
            "plan_update_seconds": round(plan_update_seconds, 4),
            "converge_seconds": round(converge_seconds, 4),
            "iterations": int(iters),
            "residual": float(resid),
            "scores_sha256": sdig,
            "plan_outcome": podplan.plan_outcome,
            "local_plan_build_seconds": round(podplan.build_seconds, 4),
            "local_edges": int(podplan.local_edges),
            "owned_rows": owned_count,
            "recompiled": dims != prev_dims,
            "sealed": (sealed is not None) if host == 0 else None,
            "phase_seconds": {
                p: round(d, 4)
                for p, d in obs_podtrace.phase_durations(
                    TRACER.get_trace(e) or {}
                ).items()
            },
            "obs_publish_seconds": round(obs_publish_seconds, 4),
            "stitch": stitch_summary,
        })
        prev_dims = dims

    # End-of-run flight ring dump — the per-host tail the parent ships
    # into the pod artifact (crash paths dump via the crash-exit hook
    # and the SIGTERM handler instead).
    JOURNAL.dump(
        flight_dir / f"flight-pod-h{host:03d}.jsonl", reason="run-end"
    )

    if args.dump_scores and host == 0:
        np.save(args.dump_scores, scores)

    if recovery is not None:
        ok = ok and not recovery["lost_acked_epochs"]
        ok = ok and recovery.get("checkpoint_matches_stream", True)
    ok = ok and abs(float(scores.sum()) - 1.0) < 1e-3

    # -- pod obs verdict (host 0, multi-host pods) --------------------
    pod_obs = None
    if host == 0 and pod.n_hosts > 1 and stitch_reports:
        install_pod_defaults(
            phase_skew_p99_s=args.skew_slo_target, heartbeat_max_age_s=30.0
        )
        slo_doc = SLO_ENGINE.evaluate()
        flagged = sorted(STRAGGLERS.flagged())
        complete_all = all(r["complete"] for r in stitch_reports)
        steady = [r for r in stitch_reports if r["epoch"] > 0]
        attrs = [
            v for r in steady for v in r["phase_attribution"].values()
        ]
        min_attr = min(attrs) if attrs else None
        # Serve through the real node route — the acceptance probe is
        # GET /trace/pod/latest with every host present in the stitch.
        from protocol_tpu.node.server import handle_request

        status_code, body = handle_request("GET", "/trace/pod/latest", None)
        trace_latest = json.loads(body) if status_code == 200 else None
        served_ok = (
            status_code == 200
            and trace_latest is not None
            and sorted(trace_latest.get("hosts", []))
            == list(range(pod.n_hosts))
        )
        # Obs overhead: publish + stitch cost against the steady epoch
        # wall-clock (the <1% acceptance bar; seeded runs are skewed by
        # design, so the bar applies to clean runs only).
        steady_detail = [d for d in epochs_detail if d["epoch"] > 0]
        obs_cost = sum(
            d["obs_publish_seconds"]
            + ((d.get("stitch") or {}).get("stitch_seconds") or 0.0)
            for d in steady_detail
        )
        steady_seconds = sum(d["seconds"] for d in steady_detail)
        overhead_pct = (
            round(100.0 * obs_cost / steady_seconds, 4)
            if steady_detail and steady_seconds
            else None
        )
        pod_obs = {
            "stitch_reports": stitch_reports,
            "stitch_complete": complete_all,
            "min_phase_attribution": min_attr,
            "trace_pod_served": served_ok,
            "trace_latest": trace_latest,
            "obs_overhead_pct": overhead_pct,
            "straggler_flagged": flagged,
            "seeded_straggler": bool(args.seed_straggler),
            "skew_slo": slo_doc["objectives"].get("pod-phase-skew-p99"),
            "slo_ok": slo_doc["ok"],
            "slo": {
                name: {"ok": obj["ok"], "value": obj["value"]}
                for name, obj in slo_doc["objectives"].items()
            },
        }
        if args.seed_straggler:
            pod_obs["seeded_straggler_fired"] = bool(flagged) and not (
                slo_doc["objectives"]
                .get("pod-phase-skew-p99", {})
                .get("ok", True)
            )
        # Gate: a complete served stitch with green SLOs and no
        # straggler is the healthy verdict; a seeded straggler MUST
        # flip it (the CI must-fail leg checks exit-nonzero).
        ok = ok and complete_all and served_ok
        ok = ok and slo_doc["ok"] and not flagged
        if not args.seed_straggler:
            ok = ok and (min_attr is None or min_attr >= 0.9)
            ok = ok and (overhead_pct is None or overhead_pct < 1.0)
        result.update(pod_obs=pod_obs)

    result.update(
        backend=BACKEND,
        n_hosts=pod.n_hosts,
        host_id=host,
        local_devices=len(jax.local_devices()),
        global_devices=len(jax.devices()),
        n_shards=pod.mesh.shape[SHARD_AXIS],
        n=int(cur.n),
        edges=int(cur.nnz),
        owned_peers=int((owner == host).sum()),
        epochs=epochs_detail,
        recovery=recovery,
        scrape=scrape,
        final_scores_sha256=hashlib.sha256(scores.tobytes()).hexdigest(),
        l1=float(scores.sum()),
        ok=bool(ok),
    )
    out.write_text(json.dumps(result, default=_jsonable))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# reference (single subprocess): serial-vs-partitioned plan build,
# 1/H-scale single host, full-scale cold correctness pin
# ---------------------------------------------------------------------------


def reference_main(args) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.local_devices}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from protocol_tpu.ops.gather_window import build_window_plan
    from protocol_tpu.parallel.partition import HostPartition
    from protocol_tpu.parallel.pod import PodContext, PodWindowPlan
    from protocol_tpu.parallel.sharded import converge_sharded
    from protocol_tpu.trust.backend import normalize_graph

    result: dict = {}
    cur = bootstrap_graph(args)
    ng = normalize_graph(cur)

    # Serial full build (the PERF.md §11 bottleneck) vs the per-host
    # partitioned builds, run back-to-back in ONE process so neither
    # side pays multi-process core contention: the pod's plan-build
    # critical path is the slowest partition.
    t0 = time.perf_counter()
    build_window_plan(ng.src, ng.dst, ng.w, n=ng.n)
    serial = time.perf_counter() - t0
    owner = HostPartition(args.processes, seed=args.seed).assign_ids(cur.n)
    per_part = []
    for h in range(args.processes):
        m = owner[ng.src] == h
        t0 = time.perf_counter()
        build_window_plan(ng.src[m], ng.dst[m], ng.w[m], n=ng.n)
        per_part.append(round(time.perf_counter() - t0, 4))
    critical = max(per_part)
    result.update(
        serial_plan_build_seconds=round(serial, 4),
        partitioned_plan_build_seconds=per_part,
        plan_build_seconds=critical,
        plan_build_speedup=round(serial / max(critical, 1e-9), 3),
    )

    # Full-scale cold converge of the FINAL churned graph — the
    # correctness pin the pod's warm fixed point must match in L1.
    for e in range(1, args.epochs):
        _, cur, _ = churn_epoch(cur, e, args)
    pod = PodContext.current(seed=args.seed)  # single process
    podplan = PodWindowPlan.build(normalize_graph(cur), pod, clock=time.perf_counter)
    t, iters, resid = converge_sharded(
        podplan, alpha=0.1, tol=args.tol, max_iter=args.max_iter
    )
    np.save(args.dump_scores, np.asarray(t))
    result.update(ref_iterations=int(iters), ref_residual=float(resid))
    Path(args.worker_out).write_text(json.dumps(result, default=_jsonable))
    return 0


# ---------------------------------------------------------------------------
# parent orchestration
# ---------------------------------------------------------------------------


def _passthrough(args) -> list[str]:
    return [
        "--peers", str(args.peers), "--edges", str(args.edges),
        "--epochs", str(args.epochs), "--churn", str(args.churn),
        "--processes", str(args.processes),
        "--local-devices", str(args.local_devices),
        "--seed", str(args.seed), "--tol", str(args.tol),
        "--max-iter", str(args.max_iter),
        "--seal-timeout", str(args.seal_timeout),
        "--straggler-sleep", str(args.straggler_sleep),
        "--skew-slo-target", str(args.skew_slo_target),
        "--straggler-ratio", str(args.straggler_ratio),
        "--straggler-k", str(args.straggler_k),
    ] + (["--skip-scrape"] if args.skip_scrape else []) + (
        ["--seed-straggler"] if args.seed_straggler else []
    )


def collect_pod_flight_tails(flight_dir: Path, tail_events: int = 20) -> dict:
    """Per-host flight-recorder tails from the workers' per-epoch ring
    dumps — ``collect_worker_dumps`` semantics (bounded tail, journaled
    into the parent's ring, files consumed), grouped per host by
    staging each dump into its own directory first."""
    from protocol_tpu.obs.journal import collect_worker_dumps

    tails: dict[str, list] = {}
    if not flight_dir.is_dir():
        return tails
    for path in sorted(flight_dir.glob("flight-pod-*.jsonl")):
        hostkey = path.stem.removeprefix("flight-pod-")
        staging = flight_dir / f"collect-{hostkey}"
        staging.mkdir(exist_ok=True)
        path.rename(staging / path.name)
        tails[hostkey] = collect_worker_dumps(
            staging, "pod", tail_events=tail_events
        )
        staging.rmdir()
    return tails


def launch_pod(args, state_dir: Path, out_dir: Path, *, resume=False,
               crash_host=-1, crash_epoch=-1, dump_scores=None):
    """Spawn the pod; returns ``(return codes, worker reports)``.  On a
    crash run the parent reaps the killed worker, gives the survivors a
    grace period (they hang in the gloo collective their peer left),
    then kills them — the host-loss failure mode itself."""
    coordinator = f"127.0.0.1:{_free_port()}"
    outs = [out_dir / f"worker{i}.json" for i in range(args.processes)]
    procs = []
    for i in range(args.processes):
        cmd = [
            sys.executable, __file__, "--worker", str(i),
            "--coordinator", coordinator,
            "--state-dir", str(state_dir),
            "--worker-out", str(outs[i]),
        ] + _passthrough(args)
        if resume:
            cmd.append("--resume")
        if crash_host >= 0:
            cmd += ["--crash-host", str(crash_host),
                    "--crash-epoch", str(crash_epoch)]
        if dump_scores is not None and i == 0:
            cmd += ["--dump-scores", str(dump_scores)]
        procs.append(subprocess.Popen(cmd, cwd=REPO))

    rcs: list[int | None] = [None] * len(procs)
    deadline = time.monotonic() + args.timeout
    expect_crash = crash_host >= 0
    grace_until = None
    while any(rc is None for rc in rcs):
        for i, p in enumerate(procs):
            if rcs[i] is None:
                rcs[i] = p.poll()
        now = time.monotonic()
        if expect_crash and grace_until is None and any(
            rc not in (None, 0) for rc in rcs
        ):
            grace_until = now + 15.0  # survivors are stuck in gloo
        if now > deadline or (grace_until is not None and now > grace_until):
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    p.kill()
                    rcs[i] = -9
            break
        time.sleep(0.2)
    for p in procs:
        p.wait()

    workers = []
    for path in outs:
        try:
            workers.append(json.loads(path.read_text()))
        except (OSError, json.JSONDecodeError):
            workers.append({"ok": False, "error": "no worker report"})
    return rcs, workers


def _bit_identity(workers: list[dict]) -> dict:
    """Per-epoch residual + score-digest agreement across all workers —
    exact equality, not a tolerance: every host holds the replicated
    vector, so the pod either agrees bit-for-bit or is broken."""
    by_epoch: dict[int, list[tuple[float, str]]] = {}
    for wkr in workers:
        for ep in wkr.get("epochs", []):
            by_epoch.setdefault(ep["epoch"], []).append(
                (ep["residual"], ep["scores_sha256"])
            )
    mismatches = {
        e: vals for e, vals in sorted(by_epoch.items())
        if len(set(vals)) != 1
    }
    return {
        "epochs_checked": len(by_epoch),
        "ok": not mismatches and bool(by_epoch),
        "mismatches": {str(e): v for e, v in mismatches.items()},
    }


def chaos_host_loss(args, workdir: Path, control_workers: list[dict]) -> dict:
    """Crash-matrix host-loss row: kill one worker of N mid-epoch
    (after WAL ack, before converge), reap the stuck pod, relaunch with
    ``--resume``, and require zero acked loss + a control-identical
    fixed point."""
    crash_epoch = max(1, args.epochs // 2)
    crash_host = min(1, args.processes - 1)
    state = workdir / "chaos-state"
    state.mkdir(parents=True, exist_ok=True)
    out_crash = workdir / "chaos-crash"
    out_crash.mkdir(exist_ok=True)
    crash_rcs, _ = launch_pod(
        args, state, out_crash, crash_host=crash_host, crash_epoch=crash_epoch
    )
    t0 = time.perf_counter()
    out_resume = workdir / "chaos-resume"
    out_resume.mkdir(exist_ok=True)
    resume_rcs, resume_workers = launch_pod(
        args, state, out_resume, resume=True
    )
    recovery_seconds = time.perf_counter() - t0

    control_digest = {
        w.get("host_id"): w.get("final_scores_sha256") for w in control_workers
    }
    resume_digest = {
        w.get("host_id"): w.get("final_scores_sha256") for w in resume_workers
    }
    identity = _bit_identity(resume_workers)
    lost = [
        w.get("recovery", {}).get("lost_acked_epochs")
        for w in resume_workers
        if isinstance(w.get("recovery"), dict)
    ]
    crashed_recovery = next(
        (
            w.get("recovery")
            for w in resume_workers
            if w.get("host_id") == crash_host
        ),
        None,
    )
    ok = (
        all(rc == 0 for rc in resume_rcs)
        and all(w.get("ok") for w in resume_workers)
        and identity["ok"]
        and all(not x for x in lost)
        and set(control_digest.values()) == set(resume_digest.values())
        and len(set(control_digest.values())) == 1
    )
    return {
        "point": "pod.host-loss",
        "crash_host": crash_host,
        "crash_epoch": crash_epoch,
        "crash_return_codes": crash_rcs,
        "resume_return_codes": resume_rcs,
        "recovery_seconds": round(recovery_seconds, 4),
        "crashed_host_recovery": crashed_recovery,
        "lost_acked": lost,
        "fixed_point_matches_control": set(control_digest.values())
        == set(resume_digest.values()),
        "residual_bit_identity": identity,
        "resume_workers": resume_workers,
        "ok": bool(ok),
    }


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="POD_smoke.json")
    ap.add_argument("--peers", type=int, default=8192)
    ap.add_argument("--edges", type=int, default=65536)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--churn", type=float, default=0.01)
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=2)
    ap.add_argument("--seed", type=int, default=16)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--max-iter", type=int, default=60)
    ap.add_argument("--seal-timeout", type=float, default=120.0)
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--smoke", action="store_true", help="CI scale")
    ap.add_argument("--chaos-host-loss", action="store_true")
    ap.add_argument("--skip-reference", action="store_true")
    ap.add_argument("--skip-scrape", action="store_true")
    ap.add_argument("--round", type=int, default=0)
    ap.add_argument("--workdir", default=None)
    ap.add_argument(
        "--seed-straggler", action="store_true",
        help="inject a sleep in one host's checkpoint phase; the "
             "straggler watcher and skew SLO MUST fire (exit 1)",
    )
    ap.add_argument("--straggler-sleep", type=float, default=0.5)
    ap.add_argument("--skew-slo-target", type=float, default=0.2)
    ap.add_argument("--straggler-ratio", type=float, default=1.5)
    ap.add_argument("--straggler-k", type=int, default=2)
    ap.add_argument(
        "--obs-out", default=None,
        help="also write the OBS_r*.json pod series for perf_sentinel",
    )
    # hidden subprocess plumbing
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--reference", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--state-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--worker-out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--resume", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--crash-host", type=int, default=-1, help=argparse.SUPPRESS)
    ap.add_argument("--crash-epoch", type=int, default=-1, help=argparse.SUPPRESS)
    ap.add_argument("--dump-scores", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.smoke:
        args.peers = min(args.peers, 2048)
        args.edges = min(args.edges, 16384)
        args.epochs = min(args.epochs, 3)

    if args.worker is not None:
        return worker_main(args)
    if args.reference:
        return reference_main(args)

    import numpy as np

    tmp_ctx = (
        tempfile.TemporaryDirectory()
        if args.workdir is None
        else None
    )
    workdir = Path(args.workdir or tmp_ctx.name)
    workdir.mkdir(parents=True, exist_ok=True)
    try:
        # -- reference subprocess (serial build + correctness pin) ----
        reference = None
        ref_scores = workdir / "ref_scores.npy"
        if not args.skip_reference:
            ref_out = workdir / "reference.json"
            rc = subprocess.run(
                [sys.executable, __file__, "--reference",
                 "--worker-out", str(ref_out),
                 "--dump-scores", str(ref_scores)] + _passthrough(args),
                cwd=REPO, timeout=args.timeout,
            ).returncode
            try:
                reference = json.loads(ref_out.read_text())
            except (OSError, json.JSONDecodeError):
                reference = {"error": f"reference failed rc={rc}"}

        # -- single-host run at 1/H scale (flat-epoch-seconds pin) ----
        single_host = None
        if not args.skip_reference and args.processes > 1:
            sh_args = argparse.Namespace(**vars(args))
            sh_args.processes = 1
            sh_args.seed_straggler = False
            sh_args.peers = max(args.peers // args.processes, 64)
            sh_args.edges = max(args.edges // args.processes, 256)
            sh_state = workdir / "single-host-state"
            sh_state.mkdir(exist_ok=True)
            sh_out = workdir / "single-host"
            sh_out.mkdir(exist_ok=True)
            sh_rcs, sh_workers = launch_pod(sh_args, sh_state, sh_out)
            single_host = {
                "peers": sh_args.peers,
                "edges": sh_args.edges,
                "return_codes": sh_rcs,
                "worker": sh_workers[0] if sh_workers else None,
            }

        # -- the pod itself (control run) -----------------------------
        state = workdir / "pod-state"
        state.mkdir(exist_ok=True)
        out_dir = workdir / "pod"
        out_dir.mkdir(exist_ok=True)
        pod_scores = workdir / "pod_scores.npy"
        rcs, workers = launch_pod(
            args, state, out_dir, dump_scores=pod_scores
        )
        skipped = all(w.get("skipped") for w in workers)
        identity = _bit_identity(workers)
        if skipped and args.seed_straggler:
            # The CI must-fail leg inverts our exit code; a skip-exit-0
            # there would read as "watcher fired" on a jax build that
            # never ran the pod at all.
            print(
                "dryrun_pod: SKIPPED but --seed-straggler expects a "
                "failure — exiting 1 so the must-fail leg stays honest"
            )
            return 1
        flight_tails = collect_pod_flight_tails(state / "flight")

        warm_vs_cold_l1 = None
        if not skipped and ref_scores.exists() and pod_scores.exists():
            warm_vs_cold_l1 = float(
                np.abs(np.load(pod_scores) - np.load(ref_scores)).sum()
            )

        chaos = None
        if args.chaos_host_loss and not skipped and args.processes > 1:
            chaos = chaos_host_loss(args, workdir, workers)
            chaos["flight_tails"] = {
                k: len(v)
                for k, v in collect_pod_flight_tails(
                    workdir / "chaos-state" / "flight"
                ).items()
            }
    finally:
        if tmp_ctx is not None:
            tmp_ctx.cleanup()

    ok = skipped or (
        all(rc == 0 for rc in rcs)
        and all(w.get("ok") for w in workers)
        and identity["ok"]
        and (warm_vs_cold_l1 is None or warm_vs_cold_l1 < SCORE_TOL)
        and (chaos is None or chaos["ok"])
    )

    # -- sentinel-shaped entries (perf_sentinel keys on n_hosts) ------
    scale = f"{args.peers} peers/{args.edges} edges"
    meshs = f"{args.processes}x{args.local_devices}"
    entries: list[dict] = []
    if not skipped and workers and workers[0].get("epochs"):
        w0 = workers[0]
        steady = _median(
            [e["seconds"] for e in w0["epochs"] if e["epoch"] > 0]
        )
        cold = next(
            (e["seconds"] for e in w0["epochs"] if e["epoch"] == 0), None
        )
        sh_epochs = (
            (single_host or {}).get("worker") or {}
        ).get("epochs") or []
        sh_steady = _median(
            [e["seconds"] for e in sh_epochs if e["epoch"] > 0]
        )
        entries.append({
            "metric": (
                f"pod steady-state epoch wall-clock ({scale}, "
                f"{meshs} mesh, {BACKEND})"
            ),
            "value": steady,
            "unit": "seconds",
            "n_hosts": args.processes,
            "cold_epoch_seconds": cold,
            "single_host_steady_epoch_seconds": sh_steady,
            "single_host_scale": (
                f"{single_host['peers']} peers/{single_host['edges']} edges"
                if single_host else None
            ),
            "warm_vs_cold_l1": warm_vs_cold_l1,
            "per_epoch": w0["epochs"],
        })
        if reference and "plan_build_seconds" in reference:
            entries.append({
                "metric": (
                    f"pod plan-build critical path ({scale}, "
                    f"{args.processes} hosts)"
                ),
                "value": reference["plan_build_seconds"],
                "unit": "seconds",
                "n_hosts": args.processes,
                "plan_build_seconds": reference["plan_build_seconds"],
                "serial_plan_build_seconds":
                    reference["serial_plan_build_seconds"],
                "plan_build_speedup": reference["plan_build_speedup"],
                "partitioned_plan_build_seconds":
                    reference["partitioned_plan_build_seconds"],
                "pod_measured_local_build_seconds": [
                    w["epochs"][0]["local_plan_build_seconds"]
                    for w in workers if w.get("epochs")
                ],
            })
        scrape = w0.get("scrape") or {}
        if scrape.get("comm"):
            entries.append({
                "metric": (
                    f"pod per-iteration collective bytes ({scale}, "
                    f"{meshs} mesh)"
                ),
                "value": scrape["comm"]["bytes_per_iter"],
                "comm_bytes_per_iter": scrape["comm"]["bytes_per_iter"],
                "unit": "bytes",
                "n_hosts": args.processes,
                "budget_bytes": scrape["comm"]["budget_bytes"],
            })
        if scrape.get("mem"):
            entries.append({
                "metric": (
                    f"pod per-shard peak HBM ({scale}, {meshs} mesh)"
                ),
                "value": scrape["mem"]["measured"]["peak_bytes"],
                "peak_hbm_bytes_per_shard":
                    scrape["mem"]["measured"]["peak_bytes"],
                "unit": "bytes",
                "n_hosts": args.processes,
                "pod_budget_peak_bytes":
                    scrape["pod_budget"]["peak_bytes"],
            })
        pod_obs = w0.get("pod_obs") or {}
        stitch_reports = pod_obs.get("stitch_reports") or []
        if stitch_reports:
            skews = [
                max(r["phase_skew_s"].values()) if r["phase_skew_s"] else 0.0
                for r in stitch_reports
            ]
            spreads = [
                r["barrier_spread_s"] for r in stitch_reports
                if r.get("barrier_spread_s") is not None
            ]
            stitch_ms = round(
                (_median([r["stitch_seconds"] for r in stitch_reports])
                 or 0.0) * 1e3, 3,
            )
            entries.append({
                "metric": (
                    f"pod trace stitch + phase skew ({scale}, "
                    f"{meshs} mesh)"
                ),
                "value": stitch_ms,
                "unit": "ms",
                "n_hosts": args.processes,
                "stitch_ms": stitch_ms,
                "phase_skew_p99_ms": round(max(skews) * 1e3, 3),
                "barrier_spread_ms": (
                    round((_median(spreads) or 0.0) * 1e3, 3)
                    if spreads else None
                ),
                "obs_overhead_pct": pod_obs.get("obs_overhead_pct"),
                "stitch_complete": pod_obs.get("stitch_complete"),
                "min_phase_attribution":
                    pod_obs.get("min_phase_attribution"),
            })

    report = {
        "tool": "dryrun_pod",
        "round": args.round,
        "backend": BACKEND,
        "mesh": meshs,
        "n_hosts": args.processes,
        "n_cpus": os.cpu_count(),
        "params": {
            "peers": args.peers, "edges": args.edges,
            "epochs": args.epochs, "churn": args.churn,
            "tol": args.tol, "max_iter": args.max_iter,
            "seed": args.seed,
        },
        "ok": bool(ok),
        "skipped": skipped,
        "return_codes": rcs,
        "residual_bit_identity": identity,
        "warm_vs_cold_l1": warm_vs_cold_l1,
        "reference": reference,
        "single_host": single_host,
        "chaos": chaos,
        "entries": entries,
        "flight_tails": flight_tails if not skipped else {},
        "workers": workers,
    }
    Path(args.out).write_text(
        json.dumps(report, indent=2, default=_jsonable) + "\n"
    )
    # The stitched pod trace as its own artifact (CI uploads it), plus
    # the sentinel-walkable OBS series when asked.
    pod_trace_doc = next(
        (
            w["pod_obs"]["trace_latest"]
            for w in workers
            if isinstance(w.get("pod_obs"), dict)
            and w["pod_obs"].get("trace_latest")
        ),
        None,
    )
    if pod_trace_doc is not None:
        Path(args.out).with_name("POD_TRACE_latest.json").write_text(
            json.dumps(pod_trace_doc, indent=2, default=_jsonable) + "\n"
        )
    if args.obs_out and not skipped:
        Path(args.obs_out).write_text(json.dumps({
            "tool": "dryrun_pod",
            "round": args.round,
            "n_hosts": args.processes,
            "entries": [e for e in entries if "stitch_ms" in e],
        }, indent=2, default=_jsonable) + "\n")
    status = (
        "SKIPPED (no multi-process CPU collectives)" if skipped
        else ("OK" if ok else "FAILED")
    )
    print(f"dryrun_pod: {status} — report in {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
