"""Chip smoke: the node's main path, once, on a real TPU.

Not a benchmark.  The seconds it prints are one cold run's, compile
included, and say only that the path ran.  It proves, on the chip:

- **device** — JAX reports platform ``tpu``; anything else is a failure,
  never a fallback to the CPU;
- **native** — ``native/`` rebuilt from the committed sources;
- **epoch** — BASELINE config 4 (``scale_free(1_000_000, 50_000_000,
  seed=7)``) converged through ``Manager`` on ``tpu-windowed``, within
  L1 1e-4 of ``tpu-csr`` (the plain reference) on the same graph, with
  the Pallas kernel compiled into the converge (``tpu_custom_call``);
- **churn** — a second, warm-started epoch after 1 % sender churn on the
  plan-delta path with zero recompiles;
- **node** — an in-process node on ``tpu-windowed``: self-attestations
  POSTed, one epoch ticked with no failure anomaly, ``GET /score`` 200
  with a proof from an epoch whose timeline records ``tpu-windowed``.

``--chips 4`` instead runs ``tpu-sharded:tpu-windowed`` through
``Manager`` on a 4-device mesh at the same width against single-device
``tpu-csr``, and checks the plan shards sit on 4 devices and the
compiled step has one all-reduce.

One process; no child touches JAX.  The last stdout line is
``{"ok": true, "device": {...}}`` only when every phase passed; any
failure exits non-zero without it.

Run through the chip tool: ``chiprun -- python chip_smoke.py`` or
``chiprun --chips 4 -- python chip_smoke.py --chips 4``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent

#: BASELINE.md config 4, the headline deployment.
N_PEERS, N_EDGES, SEED = 1_000_000, 50_000_000, 7
ALPHA, TOL, MAX_ITER = 0.1, 1e-6, 50
#: The windowed-vs-CSR agreement bound of ``__graft_entry__``'s dry run.
L1_BOUND = 1e-4
CHURN = 0.01
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
EPOCH_FAILURES = ("epoch-tick-failed", "epoch-device-stage-failed")


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def say(phase: str, **fields) -> None:
    print(f"smoke {phase}: {json.dumps(fields)}", flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles since the last ``take``."""

    def __init__(self) -> None:
        import jax.monitoring

        self._seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self._seconds += duration

    def take(self) -> float:
        seconds, self._seconds = self._seconds, 0.0
        return seconds


def tpu_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    check(
        platform == "tpu",
        f"JAX found no TPU (platform {platform!r}); this smoke never runs on the CPU",
    )
    check(len(devices) >= chips, f"--chips {chips} but JAX sees {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def build_native() -> None:
    start = time.perf_counter()
    subprocess.run(
        ["make", "-C", str(REPO / "native"), "-B", f"-j{os.cpu_count() or 1}"],
        check=True,
        capture_output=True,
    )
    say("native", build_seconds=time.perf_counter() - start)


def config4_graph(n_peers: int, n_edges: int):
    from protocol_tpu.models.graphs import scale_free

    start = time.perf_counter()
    graph = scale_free(n_peers, n_edges, seed=SEED)
    say("graph", peers=graph.n, edges=graph.nnz, seconds=time.perf_counter() - start)
    return graph


def reference_scores(graph, clock: CompileClock):
    """``tpu-csr`` on one device: the plain reference."""
    import numpy as np

    from protocol_tpu.trust.backend import get_backend

    start = time.perf_counter()
    result = get_backend("tpu-csr").converge(
        graph, alpha=ALPHA, tol=TOL, max_iter=MAX_ITER, record_residuals=False
    )
    say(
        "reference",
        backend=result.backend,
        iterations=result.iterations,
        compile_seconds=clock.take(),
        seconds=time.perf_counter() - start,
    )
    check(bool(np.isfinite(result.scores).all()), "tpu-csr scores are not finite")
    return result.scores


def manager_epoch(manager, prepared, clock: CompileClock, phase: str):
    from protocol_tpu.obs import TRACER

    with TRACER.epoch(prepared.epoch.number) as root:
        result = manager.converge_prepared(
            prepared, alpha=ALPHA, tol=TOL, max_iter=MAX_ITER
        )
    plan, converge = root.find("plan"), root.find("converge")
    say(
        phase,
        backend=result.backend,
        iterations=result.iterations,
        residual=result.residual,
        compile_seconds=clock.take(),
        plan_seconds=plan.duration_s if plan else None,
        converge_seconds=converge.duration_s if converge else None,
    )
    return result


def check_scores(result, reference, n: int, phase: str) -> None:
    """Finite scores of the expected shape and L1 mass, within
    ``L1_BOUND`` of ``reference`` when one is given."""
    import numpy as np

    scores = result.scores
    check(scores.shape == (n,), f"{phase}: scores shape {scores.shape}, want ({n},)")
    check(bool(np.isfinite(scores).all()), f"{phase}: scores are not finite")
    check(abs(float(scores.sum()) - 1.0) < 1e-3, f"{phase}: scores sum {scores.sum()}")
    if reference is None:
        return
    l1 = float(np.abs(scores - reference).sum())
    say(phase, l1_to_tpu_csr=l1, bound=L1_BOUND)
    check(l1 < L1_BOUND, f"{phase}: L1 {l1} to tpu-csr exceeds {L1_BOUND}")


def converge_text(plan) -> str:
    """Compiled text of the windowed converge the backend runs on
    ``plan`` (the jit cache holds the same executable)."""
    import jax
    import jax.numpy as jnp

    from protocol_tpu.device import pallas_interpret
    from protocol_tpu.ops.gather_window import converge_windowed

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    vec = jax.ShapeDtypeStruct((plan.n,), jnp.float32)
    return converge_windowed.lower(
        *(spec(a) for a in plan.device_args()), vec, vec, vec,
        **plan.static_kwargs(),
        alpha=jax.ShapeDtypeStruct((), jnp.float32),
        tol=TOL,
        max_iter=MAX_ITER,
        interpret=pallas_interpret(),
        record_residuals=True,
    ).compile().as_text()


def prepared_epoch(number: int, graph, id_order, t0=None, delta_rows=None):
    from protocol_tpu.node.epoch import Epoch
    from protocol_tpu.node.manager import PreparedEpoch

    return PreparedEpoch(
        epoch=Epoch(number),
        graph=graph,
        id_order=id_order,
        t0=t0,
        delta_rows=delta_rows,
        dirty_snapshot=set(),
    )


def windowed_epochs(graph, reference, clock: CompileClock, *, expect_kernel: bool) -> None:
    """Phases epoch and churn: config 4 through ``Manager`` on
    ``tpu-windowed``, then one churned, warm-started delta epoch."""
    import numpy as np

    from protocol_tpu.models.churn import churn_cohort_dims, sender_centric_churn
    from protocol_tpu.node.manager import Manager, ManagerConfig
    from protocol_tpu.obs.metrics import PLAN_OUTCOMES
    from protocol_tpu.obs.watchers import RECOMPILES

    manager = Manager(ManagerConfig(backend="tpu-windowed", prover="commitment"))
    id_order = list(range(graph.n))
    result = manager_epoch(manager, prepared_epoch(1, graph, id_order), clock, "epoch")
    check(result.backend == "tpu-windowed", f"epoch ran on {result.backend}")
    check_scores(result, reference, graph.n, "epoch")

    rng = np.random.default_rng(SEED)
    cohort, deg = churn_cohort_dims(graph, CHURN)
    rows, churned, _ = sender_centric_churn(rng, graph, cohort_size=cohort, deg=deg)
    prepared = prepared_epoch(
        2, churned, id_order, t0=manager._warm_t0(id_order), delta_rows=rows.astype(np.int64)
    )
    check(prepared.t0 is not None, "churn: no warm-start seed")
    deltas = PLAN_OUTCOMES.value(outcome="delta")
    before = RECOMPILES.snapshot()
    result = manager_epoch(manager, prepared, clock, "churn")
    after = RECOMPILES.snapshot()
    recompiles = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    delta_path = PLAN_OUTCOMES.value(outcome="delta") > deltas
    say("churn", rows_churned=int(rows.size), delta_path=delta_path, recompiles=recompiles)
    check_scores(result, None, graph.n, "churn")
    check(delta_path, "churn: the plan was not updated by delta")
    check(not recompiles, f"churn: steady-state epoch recompiled {recompiles}")

    kernel = "tpu_custom_call" in converge_text(manager.window_plan)
    say("kernel", tpu_custom_call=kernel, compile_seconds=clock.take())
    check(kernel == expect_kernel, "the windowed converge does not hold the compiled kernel")


async def _http(port: int, method: str, path: str, body: bytes = b"") -> tuple[int, str]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    head = f"{method} {path} HTTP/1.1\r\nhost: smoke\r\ncontent-length: {len(body)}\r\n\r\n"
    writer.write(head.encode() + body)
    await writer.drain()
    response = (await reader.read()).decode()
    writer.close()
    status_line, _, rest = response.partition("\r\n")
    return int(status_line.split()[1]), rest.partition("\r\n\r\n")[2]


async def node_epoch(interval: int, timeout_s: float) -> None:
    """Phase node: boot a node on ``tpu-windowed``, POST its
    self-attestations, and read a proven score after one epoch."""
    from protocol_tpu.node.attestation import AttestationData
    from protocol_tpu.node.config import ProtocolConfig
    from protocol_tpu.node.server import Node
    from protocol_tpu.obs import JOURNAL
    from protocol_tpu.obs.timeline import TIMELINE

    known = set(TIMELINE.epochs())
    # This node's anomalies alone: the journal is the process's, and an
    # earlier node in the same process may have recorded its own.
    since = max((e["seq"] for e in JOURNAL.tail()), default=0)
    node = Node.from_config(
        ProtocolConfig(
            epoch_interval=interval,
            endpoint=((127, 0, 0, 1), 0),
            prover="commitment",
            trust_backend="tpu-windowed",
        )
    )
    await node.start()
    try:
        port = node._server.sockets[0].getsockname()[1]
        for att in list(node.manager.attestations.values()):
            payload = AttestationData.from_attestation(att).to_bytes()
            status, body = await _http(port, "POST", "/attestation", payload)
            check(status == 200 and json.loads(body)["accepted"], f"POST: {status} {body}")
        start = time.perf_counter()
        epoch, status, body = None, 0, ""
        while epoch is None or status != 200:
            check(time.perf_counter() - start < timeout_s, "node: no proven epoch in time")
            failures = [
                e for e in JOURNAL.tail()
                if e["seq"] > since
                and e.get("kind") == "anomaly" and e.get("what") in EPOCH_FAILURES
            ]
            check(not failures, f"node: {failures}")
            await asyncio.sleep(0.25)
            ticked = [
                e for e in TIMELINE.epochs()
                if e not in known and "converge" in (TIMELINE.get(e) or {})
            ]
            if ticked:
                epoch = ticked[0]
                status, body = await _http(port, "GET", "/score")
        converge = TIMELINE.get(epoch)["converge"]
        proof = json.loads(body)
        say(
            "node",
            epoch=epoch,
            backend=converge["backend"],
            iterations=converge["iterations"],
            score_status=status,
            proof_bytes=len(proof.get("proof", [])),
            seconds=time.perf_counter() - start,
        )
        check(converge["backend"] == "tpu-windowed", f"node epoch ran on {converge['backend']}")
        check(len(proof.get("proof", [])) > 0, "GET /score carried no proof")
    finally:
        await node.stop()


def sharded_epoch(
    graph, reference, clock: CompileClock, chips: int, *, expect_kernel: bool
) -> None:
    """The ``--chips`` path: ``tpu-sharded:tpu-windowed`` through
    ``Manager`` on the default mesh, against the single-device reference."""
    from collections import Counter

    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from protocol_tpu.analysis.comm.hlo_walk import parse_module
    from protocol_tpu.node.manager import Manager, ManagerConfig
    from protocol_tpu.parallel.mesh import default_mesh
    from protocol_tpu.parallel.sharded import (
        ShardedWindowPlan,
        windowed_operands,
        windowed_runner,
    )
    from protocol_tpu.trust.backend import normalize_graph

    backend = "tpu-sharded:tpu-windowed"
    manager = Manager(ManagerConfig(backend=backend, prover="commitment"))
    result = manager_epoch(
        manager, prepared_epoch(1, graph, list(range(graph.n))), clock, "sharded"
    )
    check(result.backend == backend, f"sharded epoch ran on {result.backend}")
    check_scores(result, reference, graph.n, "sharded")

    mesh = default_mesh()
    check(mesh.size == chips, f"default mesh has {mesh.size} devices, want {chips}")
    swp = ShardedWindowPlan.build(
        normalize_graph(graph), mesh, plan=manager.window_plan
    )
    arrays = windowed_operands(swp)
    devices = [sorted(s.device.id for s in a.addressable_shards) for a in arrays]
    run = windowed_runner(swp)
    alpha = jax.device_put(np.float32(ALPHA), NamedSharding(mesh, P()))
    text = run.lower(
        *arrays, swp.t0(), swp.p, swp.dangling, alpha,
        max_iter=MAX_ITER, tol=TOL, record_residuals=True,
    ).compile().as_text()
    collectives = Counter(op.kind for op in parse_module(text).collectives)
    say(
        "mesh",
        plan_outcome=swp.plan_outcome,
        shard_devices=devices[0],
        collectives=dict(collectives),
        tpu_custom_call="tpu_custom_call" in text,
        compile_seconds=clock.take(),
    )
    check(all(len(set(d)) == chips for d in devices), f"plan shards on {devices}")
    check(dict(collectives) == {"all-reduce": 1}, f"step collectives {dict(collectives)}")
    check(
        ("tpu_custom_call" in text) == expect_kernel,
        "the sharded step does not hold the compiled kernel",
    )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, default=1,
        help="1: the single-chip phases; 4: only the sharded windowed "
        "path on a 4-chip mesh and its single-device reference",
    )
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere else
    try:
        device = tpu_device(args.chips)
        build_native()
        from protocol_tpu.device import enable_compile_cache

        enable_compile_cache()
        clock = CompileClock()
        graph = config4_graph(N_PEERS, N_EDGES)
        reference = reference_scores(graph, clock)
        if args.chips > 1:
            sharded_epoch(graph, reference, clock, args.chips, expect_kernel=True)
        else:
            windowed_epochs(graph, reference, clock, expect_kernel=True)
            del graph, reference
            asyncio.run(node_epoch(interval=5, timeout_s=300.0))
    except Exception as e:  # noqa: BLE001 - every failure is the smoke's verdict
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
