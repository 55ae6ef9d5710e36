"""Benchmarks: the headline 1M-peer convergence plus the full
BASELINE.md five-config ladder.

Default mode (what the driver runs) prints ONE JSON line for config 4 —
the 1M-peer / 50M-edge scale-free convergence on the fused windowed
pipeline (``tpu-windowed``, PERF.md §7), 40 fixed power iterations,
wall-clock excluding compile, host->HBM transfer, and the one-time
bucketing plan (reported separately as ``plan_seconds``).  The previous
headline kernel stays reachable via ``--backend tpu-csr`` to reproduce
the 17.9 s PERF.md §1 number.  The reference publishes no numbers
(BASELINE.md); the driver target is "< 2 s on a v5e-8" and this runs on
however many chips are visible.

``--ladder`` runs all five BASELINE.md configs, prints one JSON report
with five entries (plus the same headline line last, so driver parsing
keeps working), and persists the report as ``LADDER_r<N>.json``
(``--ladder-out`` overrides).  Config 3 runs a *synthetic* scale-free
stand-in at the OP-mainnet snapshot's sparsity class — no real snapshot
ships in this image — and its metric says so.  ``--scale-div N``
divides every ladder config's size by N (CI smoke runs on CPU).
``--backend tpu-sharded:tpu-windowed`` runs the headline on the fused
pipeline sharded across the visible mesh (PERF.md §8).

Per-iteration cost model and kernel-selection evidence: PERF.md.
"""

from __future__ import annotations

import argparse
import json
import time


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _peak_memory_bytes(compiled) -> int | None:
    """Per-device peak HBM of one AOT executable from its buffer
    assignment (graftlint pass-12 view): resident arguments + temp
    arena + unaliased outputs.  None where the runtime exposes no
    memory analysis."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - absent on some runtimes
        return None
    if ma is None:
        return None
    return int(
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
        + ma.temp_size_in_bytes
    )


def headline_entry(
    iters: int = 40,
    backend: str = "tpu-windowed",
    n_peers: int = 1_000_000,
    n_edges: int = 50_000_000,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from protocol_tpu.device import pallas_interpret
    from protocol_tpu.models.graphs import scale_free
    from protocol_tpu.obs import TRACER
    from protocol_tpu.ops.gather_window import build_window_plan, converge_windowed
    from protocol_tpu.ops.sparse import converge_csr
    from protocol_tpu.trust.backend import normalize_graph

    target_seconds = 2.0

    ng = normalize_graph(scale_free(n_peers, n_edges, seed=7))
    g = ng.by_dst()
    p, dangling = ng.p, ng.dangling
    extra: dict = {}
    # Span-derived phase timings (ISSUE 4): the bench emits the SAME
    # obs spans the node's epoch tick does (plan, converge), so a
    # BENCH_*.json line and a production /trace/<epoch> use identical
    # phase names.
    phases: dict = {}

    if backend == "tpu-csr":
        device_args = (
            jax.device_put(jnp.asarray(g.src)),
            jax.device_put(jnp.asarray(g.row_ptr_by_dst())),
            jax.device_put(jnp.asarray(g.weight)),
            jax.device_put(jnp.asarray(p)),
            jax.device_put(jnp.asarray(dangling)),
        )
        alpha = jax.device_put(np.float32(0.1))
        jax.block_until_ready(device_args)
        # Pass-12 memory scrape (PERF.md §19): per-device peak HBM of
        # the exact module this bench executes, from the AOT buffer
        # assignment — compiled once, outside the timed region, like
        # the comm scrape.
        extra["peak_memory_bytes"] = _peak_memory_bytes(
            converge_csr.lower(
                device_args[0], device_args[1], device_args[2],
                jax.device_put(jnp.asarray(p)), device_args[3],
                device_args[4], alpha=alpha, tol=0.0, max_iter=iters,
            ).compile()
        )

        def run():
            # t0 is donated by converge_csr: stage a fresh buffer per
            # call (4 MB host->HBM, noise next to the compute).
            t0 = jax.device_put(jnp.asarray(p))
            t, it, resid = converge_csr(
                device_args[0],
                device_args[1],
                device_args[2],
                t0,
                device_args[3],
                device_args[4],
                alpha=alpha,
                tol=0.0,
                max_iter=iters,
            )
            # Materialise on the host: the timing includes the 4 MB
            # score-vector copy, noise next to the compute.
            return np.asarray(t)

    elif backend == "tpu-windowed":
        # One-time static plan: excluded from the per-iteration metric
        # (it amortizes across epochs and reboots via the checkpoint
        # store) but reported so regressions in host bucketing show up.
        with TRACER.span("plan", backend=backend) as plan_span:
            plan, plan_dt = _timed(
                lambda: build_window_plan(g.src, g.dst, g.weight, n=g.n)
            )
        phases["plan"] = round(plan_span.duration_s or 0.0, 4)
        interpret = pallas_interpret()
        plan_args = tuple(jax.device_put(a) for a in plan.device_args())
        vec_args = (
            jax.device_put(jnp.asarray(p)),
            jax.device_put(jnp.asarray(dangling)),
        )
        alpha = jax.device_put(np.float32(0.1))
        jax.block_until_ready((plan_args, vec_args))
        extra = {
            "plan_seconds": round(plan_dt, 4),
            "bridge_segments": plan.n_segments,
            "bridge_compression": round(plan.compression, 2),
        }
        # Pass-12 memory scrape: AOT buffer-assignment peak of the
        # module this bench executes, outside the timed region.
        extra["peak_memory_bytes"] = _peak_memory_bytes(
            converge_windowed.lower(
                *plan_args,
                jax.device_put(jnp.asarray(p)),
                *vec_args,
                **plan.static_kwargs(),
                alpha=alpha,
                tol=0.0,
                max_iter=iters,
                interpret=interpret,
            ).compile()
        )

        def run():
            # t0 is donated by converge_windowed: fresh buffer per call.
            t0 = jax.device_put(jnp.asarray(p))
            t, it, resid = converge_windowed(
                *plan_args,
                t0,
                *vec_args,
                **plan.static_kwargs(),
                alpha=alpha,
                tol=0.0,
                max_iter=iters,
                interpret=interpret,
            )
            return np.asarray(t)

    elif backend == "tpu-sharded:tpu-windowed":
        # The fused pipeline taken multi-chip (PERF.md §8): window rows
        # partitioned across the default mesh, per-shard windowed step
        # under shard_map, boundary dst rows completed by psum.  On one
        # chip this measures the Mesh(1) overhead floor; on a v5e-8 it
        # is the headline multi-chip number.
        from protocol_tpu.parallel.mesh import SHARD_AXIS, default_mesh
        from protocol_tpu.parallel.sharded import ShardedWindowPlan, converge_sharded

        mesh = default_mesh()
        with TRACER.span("plan", backend=backend) as plan_span:
            swp, plan_dt = _timed(lambda: ShardedWindowPlan.build(ng, mesh))
        phases["plan"] = round(plan_span.duration_s or 0.0, 4)
        extra = {
            "plan_seconds": round(plan_dt, 4),
            "bridge_segments": swp.plan.n_segments,
            "bridge_compression": round(swp.plan.compression, 2),
            "mesh_shards": int(mesh.shape[SHARD_AXIS]),
            "rows_per_shard": swp.rows_per_shard,
        }
        # Pass-8 comm scrape (PERF.md §15): per-iteration collective
        # byte volume of the exact module this bench executes, recorded
        # into the LADDER round so tools/perf_sentinel.py tracks it as
        # a comm_bytes_per_iter series.  AOT-compiled once, outside the
        # timed region.
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from protocol_tpu.analysis.comm.hlo_walk import parse_module
        from protocol_tpu.parallel.sharded import windowed_operands, windowed_runner

        runner = windowed_runner(swp)
        alpha_repl = jax.device_put(np.float32(0.1), NamedSharding(mesh, P()))
        compiled = runner.lower(
            *windowed_operands(swp), swp.t0(), swp.p, swp.dangling,
            alpha_repl, max_iter=iters, tol=0.0,
        ).compile()
        mod = parse_module(compiled.as_text())
        extra["comm_bytes_per_iter"] = mod.total_bytes(per_iteration_only=True)
        extra["comm_collectives"] = mod.kind_counts()
        # Pass-12 memory scrape: per-SHARD peak HBM (memory_analysis is
        # the per-device view) of the same executable.
        extra["peak_memory_bytes"] = _peak_memory_bytes(compiled)

        def run():
            t, it, resid = converge_sharded(swp, alpha=0.1, tol=0.0, max_iter=iters)
            return np.asarray(t)

    else:
        raise ValueError(
            "headline backend must be tpu-windowed, tpu-csr, or "
            f"tpu-sharded:tpu-windowed, got {backend!r}"
        )

    run()  # compile + warm up
    t0 = time.perf_counter()
    with TRACER.span("converge", backend=backend):
        scores = run()
    elapsed = time.perf_counter() - t0
    phases["converge"] = round(elapsed, 4)
    assert abs(scores.sum() - 1.0) < 1e-3

    return {
        "metric": f"1M-peer/50M-edge global-trust convergence wall-clock (40 power iters, {backend})",
        "value": round(elapsed, 4),
        "unit": "seconds",
        "n_hosts": 1,
        "vs_baseline": round(target_seconds / elapsed, 3),
        "phases": phases,
        **extra,
    }


def epochs_entry(
    epochs: int = 5,
    churn: float = 0.01,
    backend: str = "tpu-windowed",
    n_peers: int = 1_000_000,
    n_edges: int = 50_000_000,
    tol: float = 1e-6,
    max_iter: int = 60,
    seed: int = 7,
) -> dict:
    """Multi-epoch steady-state benchmark (PERF.md §11, ISSUE 5).

    Epoch 0 runs the cold path: full ``WindowPlan`` build plus a
    cold-start convergence from the pre-trust vector.  Every later
    epoch replays ``churn``·E edges of *sender-centric* churn — a
    recency-biased cohort of peers re-attests, each rewriting its whole
    out-row (row normalization makes the row the atomic delta unit) —
    and runs the steady-state path: plan delta
    (``WindowPlan.apply_delta`` via the backend's ``delta_rows`` hint)
    + warm-start convergence from the previous fixed point.  The
    recency bias mirrors production id assignment: manager peer ids are
    first-seen order, so the churning cohort (recently joined / most
    active users) is id-local, keeping the delta's touched windows far
    below the window count (the delta/rebuild crossover, PERF.md §11).

    The cold number excludes compile (one discarded warm-up converge,
    same policy as the headline); the per-epoch numbers are pure
    plan-update + converge wall-clock.  Correctness is pinned by
    cold-converging the FINAL churned graph on a fresh backend and
    requiring the warm scores to match within the convergence
    tolerance.
    """
    import numpy as np

    from protocol_tpu.models.churn import churn_cohort_dims, sender_centric_churn
    from protocol_tpu.models.graphs import scale_free
    from protocol_tpu.obs.metrics import PLAN_OUTCOMES
    from protocol_tpu.trust.backend import get_backend, normalize_graph

    rng = np.random.default_rng(seed)
    graph = scale_free(n_peers, n_edges, seed=seed).drop_self_edges()
    b = get_backend(backend)

    # Pre-build the plan so epoch 0 separates plan cost from converge
    # cost, and a throwaway converge eats the jit compile.
    plan_seconds = 0.0
    if hasattr(b, "plan"):
        from protocol_tpu.ops.gather_window import build_window_plan

        ng = normalize_graph(graph)
        plan, plan_seconds = _timed(
            lambda: build_window_plan(ng.src, ng.dst, ng.w, n=ng.n)
        )
        b.plan = plan
    b.converge(graph, alpha=0.1, tol=tol, max_iter=max_iter)  # compile
    res0, cold_converge = _timed(
        lambda: b.converge(graph, alpha=0.1, tol=tol, max_iter=max_iter)
    )
    cold_epoch_seconds = plan_seconds + cold_converge

    per_epoch = []
    scores = res0.scores
    cur = graph
    delta0 = PLAN_OUTCOMES.value(outcome="delta")
    rebuild0 = PLAN_OUTCOMES.value(outcome="rebuild")
    cohort_size, deg = churn_cohort_dims(cur, churn)
    for k in range(1, epochs):
        # Recency-biased re-attesting cohort (models.churn — the
        # shared sender-centric stream the pod dryrun replays too).
        rows, cur, _ = sender_centric_churn(
            rng, cur, cohort_size=cohort_size, deg=deg
        )
        if hasattr(b, "delta_rows"):
            b.delta_rows = rows
        res, dt = _timed(
            lambda: b.converge(cur, alpha=0.1, tol=tol, max_iter=max_iter, t0=scores)
        )
        scores = res.scores
        per_epoch.append(
            {"epoch": k, "seconds": round(dt, 4), "iterations": res.iterations}
        )

    # Correctness pin: a fresh backend cold-converges the final graph.
    ref = get_backend(backend).converge(cur, alpha=0.1, tol=tol, max_iter=max_iter)
    warm_vs_cold_l1 = float(np.abs(scores - ref.scores).sum())

    steady = sorted(e["seconds"] for e in per_epoch)
    steady_state_epoch_seconds = steady[len(steady) // 2] if steady else 0.0
    warm_iters = [e["iterations"] for e in per_epoch]
    return {
        "metric": (
            f"steady-state epoch wall-clock (plan update + converge) at "
            f"{churn:.2%} churn/epoch, {n_peers} peers / {n_edges} edges, {backend}"
        ),
        "value": round(steady_state_epoch_seconds, 4),
        "unit": "seconds",
        "n_hosts": 1,
        "epochs": epochs,
        "churn": churn,
        "cold_epoch_seconds": round(cold_epoch_seconds, 4),
        "steady_state_epoch_seconds": round(steady_state_epoch_seconds, 4),
        "cold_vs_steady_speedup": round(
            cold_epoch_seconds / max(steady_state_epoch_seconds, 1e-9), 2
        ),
        "plan_seconds": round(plan_seconds, 4),
        "cold_iterations": int(ref.iterations),
        "warm_iterations_mean": round(sum(warm_iters) / max(len(warm_iters), 1), 2),
        "iterations_saved_by_warm_start": round(
            ref.iterations - sum(warm_iters) / max(len(warm_iters), 1), 2
        ),
        "plan_outcomes": {
            "delta": PLAN_OUTCOMES.value(outcome="delta") - delta0,
            "rebuild": PLAN_OUTCOMES.value(outcome="rebuild") - rebuild0,
        },
        "warm_vs_cold_l1": warm_vs_cold_l1,
        "per_epoch": per_epoch,
    }


def ladder(scale_div: int = 1, iters: int = 40, backend: str = "tpu-windowed") -> list[dict]:
    """The five BASELINE.md configs.

    Configs 1-3 and 5 time one ``backend.converge`` call after a warm-up
    call has compiled the kernel — the timed region therefore includes
    host-side normalization/sorting and the host->device transfer (the
    backend API bundles them), unlike the headline config 4 which
    pre-stages device arrays and times only the iteration loop.
    ``iters`` scales the per-config iteration count (tests shrink it)."""
    from pathlib import Path

    import numpy as np

    from protocol_tpu.models.graphs import erdos_renyi, scale_free, sybil_mass, sybil_stress
    from protocol_tpu.node.bootstrap import read_bootstrap_csv
    from protocol_tpu.trust.backend import get_backend
    from protocol_tpu.trust.graph import TrustGraph

    entries: list[dict] = []

    def converge_timed(backend, graph, *, warm=True, **kw):
        b = get_backend(backend)
        if warm:
            b.converge(graph, **kw)  # compile
        res, dt = _timed(lambda: b.converge(graph, **kw))
        return res, dt

    # -- config 1: bootstrap set, 5 peers, native CPU parity ------------
    nodes = read_bootstrap_csv(Path(__file__).resolve().parent / "data" / "bootstrap-nodes.csv")
    n1 = len(nodes)
    ops = np.full((n1, n1), 200.0, np.float32)
    np.fill_diagonal(ops, 0.0)
    g1 = TrustGraph.from_dense(ops)
    res1, dt1 = converge_timed("native-cpu", g1, warm=False, alpha=0.0, tol=0.0, max_iter=10)
    # Reference parity: uniform opinions converge to uniform scores
    # (manager/mod.rs:246-262 initial-attestation test semantics).
    assert np.allclose(res1.scores, 1.0 / n1, atol=1e-12)
    entries.append(
        {
            "config": "1-bootstrap-5peer-native-cpu",
            "metric": "5-peer exact dense power iteration (10 iters)",
            "value": round(dt1, 5),
            "unit": "seconds",
            "power_iters_per_sec": round(10 / dt1, 1),
        }
    )

    # -- config 2: 10k dense jnp.matmul ---------------------------------
    n2 = 10_000 // scale_div
    g2 = erdos_renyi(n2, avg_degree=100.0, seed=11)
    res2, dt2 = converge_timed("tpu-dense", g2, alpha=0.1, tol=0.0, max_iter=iters)
    entries.append(
        {
            "config": "2-erdos-renyi-10k-dense",
            "metric": f"{n2}-peer dense matmul convergence ({iters} iters)",
            "value": round(dt2, 4),
            "unit": "seconds",
            "power_iters_per_sec": round(iters / dt2, 2),
        }
    )

    # -- config 3: synthetic stand-in at snapshot sparsity, BCOO SpMV ---
    # No OP-mainnet snapshot ships in this image; a SYNTHETIC scale-free
    # graph at the snapshot's sparsity class (avg degree ~20) stands in,
    # and the output says so (VERDICT item #5) — the number is the
    # kernel's wall-clock at that shape, not a real-snapshot replay.
    n3, e3 = 100_000 // scale_div, 2_000_000 // scale_div
    g3 = scale_free(n3, e3, seed=13)
    res3, dt3 = converge_timed("tpu-sparse", g3, alpha=0.1, tol=0.0, max_iter=iters)
    entries.append(
        {
            "config": "3-synthetic-standin-sparsity-bcoo",
            "metric": (
                f"{n3}-peer/{e3}-edge sparse SpMV convergence ({iters} iters) "
                "on a synthetic scale-free stand-in (no OP-mainnet snapshot "
                "in image)"
            ),
            "value": round(dt3, 4),
            "unit": "seconds",
            "power_iters_per_sec": round(iters / dt3, 2),
        }
    )

    # -- config 4: the headline (1M/50M, fused windowed by default) -----
    if scale_div == 1:
        entries.append({"config": f"4-scale-free-1M-{backend}", **headline_entry(backend=backend)})
    else:
        n4, e4 = 1_000_000 // scale_div, 50_000_000 // scale_div
        g4 = scale_free(n4, e4, seed=7)
        res4, dt4 = converge_timed(backend, g4, alpha=0.1, tol=0.0, max_iter=iters)
        entries.append(
            {
                "config": f"4-scale-free-1M-{backend}",
                "metric": f"{n4}-peer/{e4}-edge {backend} convergence ({iters} iters)",
                "value": round(dt4, 4),
                "unit": "seconds",
                "power_iters_per_sec": round(iters / dt4, 2),
            }
        )

    # -- config 5: 10M-peer sybil stress, damping sweep -----------------
    n5, e5 = 10_000_000 // scale_div, 50_000_000 // scale_div
    frac = 0.3
    g5 = sybil_stress(n5, e5, sybil_fraction=frac, seed=17)
    sweep = []
    b5 = get_backend("tpu-csr")
    b5.converge(g5, alpha=0.1, tol=0.0, max_iter=iters)  # compile once
    t0 = time.perf_counter()
    for alpha in (0.0, 0.05, 0.1, 0.2, 0.3):
        res = b5.converge(g5, alpha=alpha, tol=0.0, max_iter=iters)
        sweep.append(
            {
                "alpha": alpha,
                "sybil_mass": round(sybil_mass(res.scores, n5, frac), 5),
            }
        )
    dt5 = time.perf_counter() - t0
    # Damping must monotonically squeeze the collective's captured mass.
    masses = [s["sybil_mass"] for s in sweep]
    assert all(a >= b - 1e-6 for a, b in zip(masses, masses[1:])), masses
    entries.append(
        {
            "config": "5-sybil-stress-10M-damping-sweep",
            "metric": f"{n5}-peer/{e5}-edge 30%-sybil damping sweep (5 alphas x {iters} iters)",
            "value": round(dt5, 4),
            "unit": "seconds",
            "power_iters_per_sec": round(5 * iters / dt5, 2),
            "sybil_mass_curve": sweep,
        }
    )
    return entries


def _next_round_path() -> str:
    """``LADDER_r<N>.json`` with N following the highest recorded
    BENCH/LADDER round, so ladder reports land next to the driver's
    bench history without clobbering earlier rounds."""
    import re
    from pathlib import Path

    here = Path(__file__).resolve().parent
    rounds = [0]
    for p in here.glob("*_r*.json"):
        m = re.fullmatch(r"(?:BENCH|LADDER)_r(\d+)\.json", p.name)
        if m:
            rounds.append(int(m.group(1)))
    return str(here / f"LADDER_r{max(rounds) + 1:02d}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ladder", action="store_true", help="run all 5 BASELINE configs")
    ap.add_argument("--scale-div", type=int, default=1, help="divide ladder sizes (CI smoke)")
    ap.add_argument(
        "--ladder-out",
        default=None,
        help="path for the --ladder JSON report (default: LADDER_r<N>.json "
        "with N = next round after the recorded BENCH/LADDER files)",
    )
    ap.add_argument(
        "--backend",
        default="tpu-windowed",
        choices=["tpu-windowed", "tpu-csr", "tpu-sharded:tpu-windowed"],
        help="headline (config 4) kernel: the fused windowed pipeline "
        "(default, PERF.md §7), the previous CSR/cumsum formulation, or "
        "the mesh-sharded windowed pipeline (PERF.md §8)",
    )
    ap.add_argument(
        "--epochs",
        type=int,
        default=None,
        help="multi-epoch steady-state benchmark: epoch 0 cold (full "
        "plan build + cold converge), then N-1 churned epochs on the "
        "steady-state path (plan delta + warm start); prints one JSON "
        "line with steady_state_epoch_seconds and "
        "iterations_saved_by_warm_start next to the cold number",
    )
    ap.add_argument(
        "--churn",
        type=float,
        default=0.01,
        help="edge fraction rewired per steady-state epoch (with --epochs)",
    )
    ap.add_argument(
        "--peers", type=int, default=1_000_000, help="graph size for --epochs"
    )
    ap.add_argument(
        "--edges", type=int, default=50_000_000, help="edge count for --epochs"
    )
    args = ap.parse_args()

    from protocol_tpu.device import enable_compile_cache

    enable_compile_cache()

    if args.epochs is not None:
        print(
            json.dumps(
                epochs_entry(
                    epochs=args.epochs,
                    churn=args.churn,
                    backend=args.backend,
                    n_peers=args.peers,
                    n_edges=args.edges,
                )
            )
        )
        return

    if args.ladder:
        entries = ladder(scale_div=args.scale_div, backend=args.backend)
        report = {"ladder": entries, "scale_div": args.scale_div}
        print(json.dumps(report, indent=2))
        # Persist the full ladder as LADDER_r<N>.json (VERDICT item #5)
        # so every recorded round keeps its five wall-clocks.
        out_path = args.ladder_out or _next_round_path()
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"ladder report written to {out_path}", flush=True)
        # Driver-parsable single line, last.
        headline = next(e for e in entries if e["config"].startswith("4-"))
        line = {k: headline[k] for k in ("metric", "value", "unit") if k in headline}
        if "vs_baseline" in headline:
            line["vs_baseline"] = headline["vs_baseline"]
        print(json.dumps(line))
        return

    print(json.dumps(headline_entry(backend=args.backend)))


if __name__ == "__main__":
    main()
