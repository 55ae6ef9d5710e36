"""Seeded violation fixtures — kernels that deliberately break one
invariant each, so the analyzer itself is testable.

Every fixture pairs a tiny step function with a budget it violates;
``run_fixture`` traces and checks it exactly like a real backend, and
``tests/test_analysis.py`` asserts the right rule fires with the right
``file:line`` (the violating lines carry ``# VIOLATION: <name>``
markers the test resolves against this file).  The CLI exposes them as
``python -m protocol_tpu.analysis --fixture <name>`` (exits non-zero),
which doubles as a self-check that the gate can actually fail.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from .budget import GatherBudget, KernelBudget
from .invariants import TraceCase, check_case
from .report import Finding


@dataclass(frozen=True)
class Fixture:
    name: str
    rule: str  # the finding rule this fixture must trigger
    #: jaxpr fixtures return ``(budget, case)`` for ``check_case``; ast
    #: fixtures return ``(source, rel_path)`` for ``scan_source`` —
    #: violating code lives in strings, never as real module code, so
    #: the fixture file itself stays clean under the repo-wide pass.
    build: Callable[[], tuple]
    #: Marker suffix of the ``# VIOLATION:`` comment anchoring the
    #: expected finding line; None when the finding has no source site.
    marker: str | None
    #: Which analyzer pass evaluates this fixture.
    kind: str = "jaxpr"


def _extra_gather() -> tuple[KernelBudget, TraceCase]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    t = jnp.asarray(np.arange(8, dtype=np.float32))
    idx = jnp.asarray(np.array([3, 1, 2], np.int32))

    def step(t, idx):
        a = t[idx]
        b = t[idx + 1]  # VIOLATION: extra-gather
        return a + b

    jaxpr = jax.make_jaxpr(step)(t, idx)
    budget = KernelBudget(backend="fixture:extra-gather", max_random_gathers=1)
    return budget, TraceCase("fixture:extra-gather", jaxpr)


def _f64_leak() -> tuple[KernelBudget, TraceCase]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    def step(t):
        wide = t.astype(jnp.float64)  # VIOLATION: f64-leak
        return wide * 2.0

    with jax.enable_x64():
        jaxpr = jax.make_jaxpr(step)(np.ones(4, np.float32))
    budget = KernelBudget(backend="fixture:f64-leak", max_random_gathers=0)
    return budget, TraceCase("fixture:f64-leak", jaxpr)


def _callback_in_jit() -> tuple[KernelBudget, TraceCase]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    def host_sum(x):
        return np.float32(np.asarray(x).sum())

    def step(t):
        out = jax.ShapeDtypeStruct((), jnp.float32)
        s = jax.pure_callback(host_sum, out, t)  # VIOLATION: callback-in-jit
        return t * s

    jaxpr = jax.make_jaxpr(step)(jnp.ones(4, jnp.float32))
    budget = KernelBudget(backend="fixture:callback-in-jit", max_random_gathers=0)
    return budget, TraceCase("fixture:callback-in-jit", jaxpr)


def _scatter_in_step() -> tuple[KernelBudget, TraceCase]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    t = jnp.asarray(np.ones(4, np.float32))
    idx = jnp.asarray(np.array([2, 0, 3, 1], np.int32))

    def step(t, idx):
        return jnp.zeros(8, jnp.float32).at[idx].add(t)  # VIOLATION: scatter-in-step

    jaxpr = jax.make_jaxpr(step)(t, idx)
    budget = KernelBudget(
        backend="fixture:scatter-in-step", max_random_gathers=4, max_scatters=0
    )
    return budget, TraceCase("fixture:scatter-in-step", jaxpr)


def _missing_donation() -> tuple[KernelBudget, TraceCase]:
    import jax
    import jax.numpy as jnp

    @jax.jit  # declares no donate_argnames — the aliasing never lowers
    def undonated(t0):
        return t0 * 2.0

    arg = jnp.ones(4, jnp.float32)
    jaxpr = jax.make_jaxpr(undonated)(arg)
    budget = KernelBudget(
        backend="fixture:missing-donation",
        max_random_gathers=0,
        donated_args=("t0",),
    )
    return budget, TraceCase(
        "fixture:missing-donation",
        jaxpr,
        lowered_text=undonated.lower(arg).as_text(),
    )


#: Pass-3 seeded violations (observability-boundary rules).  The source
#: lives in strings so the AST pass over the real tree never sees it;
#: the fake paths place them in a hot/kernel tree so tree-scoped rules
#: apply exactly as they would to real code.
_TIME_IN_JIT_SRC = '''\
import time

import jax


@jax.jit
def step(t):
    t0 = time.perf_counter()  # VIOLATION: time-in-jit
    return t * 2.0, t0
'''


def _time_in_jit() -> tuple[str, str]:
    return _TIME_IN_JIT_SRC, "protocol_tpu/trust/_fixture_time_in_jit.py"


_LOGGING_IN_JIT_SRC = '''\
import logging

import jax

log = logging.getLogger(__name__)


@jax.jit
def step(t):
    log.info("converged to %s", t)  # VIOLATION: logging-in-jit
    return t * 2.0
'''


def _logging_in_jit() -> tuple[str, str]:
    return _LOGGING_IN_JIT_SRC, "protocol_tpu/trust/_fixture_logging_in_jit.py"


_CLOCK_IN_KERNEL_SRC = '''\
import time  # VIOLATION: clock-in-kernel-tree


def rowsum_probe(x):
    return time.monotonic(), x
'''


def _clock_in_kernel_tree() -> tuple[str, str]:
    return _CLOCK_IN_KERNEL_SRC, "protocol_tpu/ops/_fixture_clock_in_kernel.py"


_PLAN_MUTATION_SRC = '''\
import jax


def make_step(plan, fingerprint):
    @jax.jit
    def step(t, inserts, deletes):
        # Delta application belongs in the host stage, pre-dispatch;
        # under a trace it runs once at trace time and the kernel then
        # serves a stale layout forever after.
        new_plan = plan.apply_delta(inserts, deletes, fingerprint=fingerprint)  # VIOLATION: plan-mutation-in-converge
        return t * 2.0, new_plan

    return step
'''


def _plan_mutation_in_converge() -> tuple[str, str]:
    return _PLAN_MUTATION_SRC, "protocol_tpu/trust/_fixture_plan_mutation.py"


_JOURNAL_IN_JIT_SRC = '''\
import jax

from protocol_tpu.obs.journal import JOURNAL


@jax.jit
def step(t):
    # Under a trace this records ONE event at trace time and never
    # again — the flight recorder would replay a stale line forever.
    JOURNAL.record("iteration", residual=t)  # VIOLATION: journal-write-in-jit
    return t * 2.0
'''


def _journal_write_in_jit() -> tuple[str, str]:
    return _JOURNAL_IN_JIT_SRC, "protocol_tpu/trust/_fixture_journal_in_jit.py"


_BLOCKING_INGEST_SRC = '''\
import queue

from protocol_tpu.obs import metrics as obs_metrics

PENDING = queue.Queue(maxsize=4)


def observe_depth():
    # Keeps this fixture single-purpose: pass 10's unobserved-queue
    # rule is satisfied so only the pass-6 rules below fire.
    obs_metrics.PIPELINE_QUEUE_DEPTH.set(PENDING.qsize())


def device_stage(manager, atts, prepared):
    # The epoch loop verifying signatures re-couples convergence
    # cadence to ingest load — admission belongs in the ingest plane.
    results = manager.add_attestations_bulk(atts)  # VIOLATION: blocking-ingest-in-epoch-loop
    # An unbounded blocking put can park the epoch loop forever when
    # the consumer stalls; put_nowait (coalescing) or timeout= are the
    # sanctioned shapes.
    PENDING.put(prepared)
    return results
'''


def _blocking_ingest_in_epoch_loop() -> tuple[str, str]:
    # The fake path lands on an epoch-loop file so the file-scoped
    # pass-6 rule applies exactly as it would to the real module.
    return _BLOCKING_INGEST_SRC, "protocol_tpu/node/pipeline.py"


_BLOCKING_PROVE_SRC = '''\
def device_stage(manager, prepared):
    # A synchronous SNARK on the epoch path re-serializes seconds of
    # whole-core proving into the epoch cadence — the coupling the
    # async proving plane (protocol_tpu/prover/) exists to remove.
    manager.calculate_proofs(prepared.epoch)  # VIOLATION: blocking-prove-in-epoch-loop
    return prepared
'''


def _blocking_prove_in_epoch_loop() -> tuple[str, str]:
    # Same file-scoped shape as pass 6: the fake path lands on an
    # epoch-loop file so the pass-9 rule applies exactly as it would
    # to the real module.
    return _BLOCKING_PROVE_SRC, "protocol_tpu/node/pipeline.py"


_UNOBSERVED_QUEUE_SRC = '''\
import queue


class Stage:
    def __init__(self):
        # A bounded queue is a backpressure point; without a depth
        # gauge in this file, "the stage is saturated" is a guess
        # instead of a scrape.
        self._queue = queue.Queue(maxsize=8)  # VIOLATION: unobserved-queue

    def push(self, item):
        self._queue.put_nowait(item)
'''


def _unobserved_queue() -> tuple[str, str]:
    return _UNOBSERVED_QUEUE_SRC, "protocol_tpu/ingest/_fixture_unobserved_queue.py"


_NON_ATOMIC_STATE_WRITE_SRC = '''\
import json


def persist_cursor(path, cursor):
    # Durable node state through a bare open(): a crash mid-write tears
    # the file and the next boot reads garbage — the checkpoint store's
    # _atomic_write (tmp + fsync + rename) is the sanctioned shape, or
    # an append path that fsyncs what it wrote (node/wal.py).
    with open(path, "w") as f:  # VIOLATION: non-atomic-state-write
        json.dump({"cursor": cursor}, f)
'''


def _non_atomic_state_write() -> tuple[str, str]:
    # The fake path lands in node/ so the tree-scoped pass-11 rule
    # applies exactly as it would to real node state code.
    return _NON_ATOMIC_STATE_WRITE_SRC, "protocol_tpu/node/_fixture_state_write.py"


_FAULT_POINT_IN_JIT_SRC = '''\
import jax

from protocol_tpu import chaos


@jax.jit
def step(t):
    # Under a trace this hook fires ONCE at trace time and never again:
    # the chaos schedule silently stops covering the point, and a
    # callback-shaped rewrite would smuggle a host sync into the hot
    # loop — fault points live at host boundaries, like spans and
    # journal writes.
    chaos.fire("epoch.post_converge")  # VIOLATION: fault-point-in-jit
    return t * 2.0
'''


def _fault_point_in_jit() -> tuple[str, str]:
    return _FAULT_POINT_IN_JIT_SRC, "protocol_tpu/trust/_fixture_chaos_in_jit.py"


#: Pass-7 seeded violations (whole-program concurrency rules).  Each
#: source is a self-contained "program": it declares its own thread
#: roots, so the analyzer's reachability machinery runs exactly as it
#: does on the real tree.  Paths land outside the thread-confined
#: trees so the shared-state rules apply.
_UNGUARDED_SHARED_ATTR_SRC = '''\
import threading


class Tally:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0

    def bump(self):
        with self._lock:
            self.count += 1

    def read(self):
        return self.count  # VIOLATION: unguarded-shared-attr


def run():
    t = Tally()
    threading.Thread(target=t.bump).start()
    threading.Thread(target=t.read).start()
'''


def _unguarded_shared_attr() -> tuple[str, str]:
    return _UNGUARDED_SHARED_ATTR_SRC, "protocol_tpu/node/_fixture_shared_attr.py"


_UNGUARDED_RMW_SRC = '''\
import threading


class Hits:
    def __init__(self):
        self.n = 0

    def work(self):
        self.n += 1  # VIOLATION: unguarded-rmw


def run():
    h = Hits()
    threading.Thread(target=h.work, name="w1").start()
    threading.Thread(target=h.work, name="w2").start()
'''


def _unguarded_rmw() -> tuple[str, str]:
    return _UNGUARDED_RMW_SRC, "protocol_tpu/obs/_fixture_rmw.py"


_CHECK_THEN_ACT_SRC = '''\
import threading


class Once:
    def __init__(self):
        self.started = False

    def boot(self):
        if not self.started:
            self.started = True  # VIOLATION: check-then-act


def run():
    o = Once()
    threading.Thread(target=o.boot, name="a").start()
    threading.Thread(target=o.boot, name="b").start()
'''


def _check_then_act() -> tuple[str, str]:
    return _CHECK_THEN_ACT_SRC, "protocol_tpu/ingest/_fixture_check_act.py"


_LOCK_ORDER_CYCLE_SRC = '''\
import threading


class Transfer:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def ab(self):
        with self._a:
            with self._b:  # VIOLATION: lock-order-cycle
                pass

    def ba(self):
        with self._b:
            with self._a:
                pass
'''


def _lock_order_cycle() -> tuple[str, str]:
    return _LOCK_ORDER_CYCLE_SRC, "protocol_tpu/node/_fixture_lock_order.py"


_BLOCKING_UNDER_LOCK_SRC = '''\
import queue
import threading


class Stage:
    def __init__(self):
        self._lock = threading.Lock()
        self._queue = queue.Queue(maxsize=4)

    def push(self, item):
        with self._lock:
            self._queue.put(item)  # VIOLATION: blocking-call-under-lock
'''


def _blocking_call_under_lock() -> tuple[str, str]:
    return _BLOCKING_UNDER_LOCK_SRC, "protocol_tpu/ingest/_fixture_block_lock.py"


_NATIVE_UNDER_LOCK_SRC = '''\
import threading

from protocol_tpu.crypto import native as cnative


class Verifier:
    def __init__(self):
        self._lock = threading.Lock()

    def check(self, sigs):
        with self._lock:
            return cnative.eddsa_verify_batch(sigs)  # VIOLATION: native-call-under-lock
'''


def _native_call_under_lock() -> tuple[str, str]:
    return _NATIVE_UNDER_LOCK_SRC, "protocol_tpu/node/_fixture_native_lock.py"


#: Pass-8 seeded violations (SPMD-lowering comm rules).  Each fixture
#: compiles a REAL module through the real jit/partitioner path under
#: the 8-device CPU mesh and pairs it with a CommBudget it violates;
#: the finding anchors through jax's HLO source metadata back to the
#: ``# VIOLATION:`` line below — the same file:line contract as the
#: jaxpr fixtures.


def _comm_mesh():
    from ..parallel.mesh import SHARD_AXIS, default_mesh

    return default_mesh(), SHARD_AXIS


def _comm_case(backend, fn, args, dims, arg_names=(), donate=()):
    """Compile ``fn`` and wrap it as a CommCase (jaxpr psums counted
    from the same trace the module was lowered from; the buffer
    assignment rides along for the pass-12 fixtures)."""
    import jax

    from .comm.lowering import CommCase, _mem_stats
    from .jaxpr_walk import PSUM_PRIMITIVES, collect_primitives

    compiled = jax.jit(fn, donate_argnames=tuple(donate)).lower(*args).compile()
    jaxpr = jax.make_jaxpr(fn)(*args)
    return CommCase(
        backend=backend,
        dims=dims,
        module_text=compiled.as_text(),
        arg_names=tuple(arg_names),
        jaxpr_psums=len(collect_primitives(jaxpr, PSUM_PRIMITIVES)),
        mem=_mem_stats(compiled),
    )


def _surprise_all_gather():
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .budget import CollectiveBudget, CommBudget

    mesh, axis = _comm_mesh()
    n_shards = mesh.shape[axis]
    v = jax.device_put(
        np.ones(64 * n_shards, np.float32), NamedSharding(mesh, P(axis))
    )

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def step(local):
        # The partitioner-surprise anti-pattern: re-materializing the
        # full edge slice on every shard before reducing.
        full = lax.all_gather(local, "shard", tiled=True)  # VIOLATION: surprise-all-gather
        return lax.psum(jnp.sum(full), "shard")

    budget = CommBudget(
        backend="fixture:surprise-all-gather",
        collectives=(CollectiveBudget(kind="all-reduce", max_count=1),),
        bytes_const=1 << 20,
    )
    case = _comm_case(
        "fixture:surprise-all-gather", step, (v,),
        dims={"n": 64, "n_shards": n_shards},
    )
    return budget, [case]


def _comm_bytes_over_budget():
    from functools import partial

    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .budget import CollectiveBudget, CommBudget

    mesh, axis = _comm_mesh()
    n_shards = mesh.shape[axis]
    n, e = 64, 4096
    v = jax.device_put(np.ones(e, np.float32), NamedSharding(mesh, P()))

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P())
    def step(acc):
        # An O(E) psum inside the iteration loop: every step ships the
        # whole edge-sized vector over the wire instead of the N-sized
        # boundary completion.
        return lax.psum(acc, "shard") / n_shards  # VIOLATION: comm-bytes-over-budget

    def run(v):
        return lax.fori_loop(0, 4, lambda i, acc: step(acc), v)

    budget = CommBudget(
        backend="fixture:comm-bytes-over-budget",
        collectives=(CollectiveBudget(kind="all-reduce", max_count=1),),
        bytes_n=8.0,  # O(N) allowance only — E-sized traffic must trip
    )
    case = _comm_case(
        "fixture:comm-bytes-over-budget", run, (v,),
        dims={"n": n, "edges": e, "n_shards": n_shards},
    )
    return budget, [case]


def _host_round_trip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .budget import CommBudget

    def host_sum(x):
        return np.float32(np.asarray(x).sum())

    def step(t):
        out = jax.ShapeDtypeStruct((), jnp.float32)
        s = jax.pure_callback(host_sum, out, t)  # VIOLATION: host-round-trip
        return t * s

    budget = CommBudget(
        backend="fixture:host-round-trip", max_host_round_trips=0
    )
    case = _comm_case(
        "fixture:host-round-trip", step, (jnp.ones(8, jnp.float32),),
        dims={"n": 8, "n_shards": 1},
    )
    return budget, [case]


def _alias_dropped():
    import jax.numpy as jnp

    from .budget import CommBudget

    def undonated(t0):  # no donate_argnames — the alias never lowers
        return t0 * 2.0

    budget = CommBudget(
        backend="fixture:alias-dropped", donated_args=("t0",)
    )
    case = _comm_case(
        "fixture:alias-dropped", undonated, (jnp.ones(4, jnp.float32),),
        dims={"n": 4, "n_shards": 1}, arg_names=("t0",),
    )
    return budget, [case]


def _psum_lowering_mismatch():
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .budget import CollectiveBudget, CommBudget

    mesh, axis = _comm_mesh()
    v = jax.device_put(
        np.arange(16, dtype=np.float32), NamedSharding(mesh, P(axis))
    )

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def step(local):
        # The dead psum survives the jaxpr but DCE strips it from the
        # compiled module: the jaxpr now LIES about the wire — exactly
        # the jaxpr-vs-lowering drift the cross-check exists to catch.
        dead = lax.psum(jnp.sum(local * 2.0), "shard")  # noqa: F841
        return lax.psum(jnp.sum(local), "shard")  # VIOLATION: psum-lowering-mismatch

    budget = CommBudget(
        backend="fixture:psum-lowering-mismatch",
        collectives=(CollectiveBudget(kind="all-reduce", max_count=2),),
        bytes_const=1 << 20,
    )
    case = _comm_case(
        "fixture:psum-lowering-mismatch", step, (v,),
        dims={"n": 16, "n_shards": mesh.shape[axis]},
    )
    return budget, [case]


#: Pass-12 seeded violations (peak-HBM rules).  The lowering fixtures
#: compile REAL modules through the real jit path and judge their
#: buffer assignment against a MemBudget they violate; anchored
#: fixtures resolve through the largest-temp / host-transfer HLO
#: metadata back to the ``# VIOLATION:`` line, the same file:line
#: contract as the comm fixtures.  The AST fixtures ride source
#: strings scanned with the memory rules armed (kind="mem-ast").


def _o_e_live_temporary():
    import jax.numpy as jnp
    import numpy as np

    from .budget import MemBudget

    n, e = 512, 4096
    src = jnp.asarray(np.arange(e, dtype=np.int32) % n)
    w = jnp.asarray(np.ones(e, np.float32))
    t = jnp.asarray(np.ones(n, np.float32))

    def step(src, w, t):
        # The anti-pattern the transient budget exists to forbid: a
        # full edge-sized contribution vector held live across two
        # reductions instead of streamed through the fused pipeline.
        contrib = w * t[src]  # VIOLATION: o-e-live-temporary
        return jnp.sum(contrib) + jnp.sum(contrib * contrib)

    budget = MemBudget(
        backend="fixture:o-e-live-temporary",
        resident_edge_bytes=8.0,  # src + w are legal resident inputs
        resident_n=8.0,
        resident_const=4096.0,
        transient_n=8.0,  # N-linear only: the E-sized temp must trip
        transient_const=1024.0,
    )
    case = _comm_case(
        "fixture:o-e-live-temporary", step, (src, w, t),
        dims={"n": n, "edges": e, "n_shards": 1},
    )
    return budget, [case]


def _donation_peak_doubled():
    import jax.numpy as jnp

    from .budget import MemBudget

    def undonated(t0, p):  # no donate_argnames — the alias never lowers
        return t0 * 0.9 + p * 0.1

    budget = MemBudget(
        backend="fixture:donation-peak-doubled",
        resident_n=16.0,
        resident_const=4096.0,
        transient_n=64.0,  # generous: only the donation rule may fire
        transient_const=65536.0,
        donated_args=("t0",),
    )
    n = 1024
    case = _comm_case(
        "fixture:donation-peak-doubled", undonated,
        (jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32)),
        dims={"n": n, "n_shards": 1}, arg_names=("t0", "p"),
    )
    return budget, [case]


def _shard_replicated_edges():
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .budget import MemBudget

    mesh, axis = _comm_mesh()
    n_shards = mesh.shape[axis]
    n, e = 64, 8192
    # The regression ROADMAP item 1 cannot afford: the edge-sized
    # operand REPLICATED onto every shard instead of partitioned —
    # per-device resident holds all E entries, not E/n_shards.
    w = jax.device_put(np.ones(e, np.float32), NamedSharding(mesh, P()))

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P())
    def step(w_full):  # VIOLATION: shard-replicated-edges
        return lax.psum(jnp.sum(w_full), "shard") / n_shards

    budget = MemBudget(
        backend="fixture:shard-replicated-edges",
        resident_edge_bytes=4.0,  # f32 edge weights, PER SHARD
        resident_n=16.0,
        resident_const=4096.0,
        transient_n=64.0,
        transient_const=65536.0,
    )
    case = _comm_case(
        "fixture:shard-replicated-edges", step, (w,),
        dims={"n": n, "edges": e, "n_shards": n_shards},
    )
    return budget, [case]


def _host_staging_over_cap():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .budget import MemBudget

    n, e = 64, 8192

    def host_norm(x):
        return np.float32(np.abs(np.asarray(x)).sum())

    def step(edges):
        out = jax.ShapeDtypeStruct((), jnp.float32)
        # An O(E) operand shipped through a host callback: the staging
        # cap (O(N) bytes) exists to keep edge-scale data on-device.
        s = jax.pure_callback(host_norm, out, edges)  # VIOLATION: host-staging-over-cap
        return edges * s

    budget = MemBudget(
        backend="fixture:host-staging-over-cap",
        resident_edge_bytes=4.0,
        resident_const=4096.0,
        transient_n=64.0,
        transient_const=65536.0,
        staging_n=4.0,  # an f32[N] scalar reduction would be fine
    )
    case = _comm_case(
        "fixture:host-staging-over-cap", step,
        (jnp.ones(e, jnp.float32),),
        dims={"n": n, "edges": e, "n_shards": 1},
    )
    return budget, [case]


_HOST_MATERIALIZATION_SRC = '''\
import numpy as np


def device_stage(manager, prepared, plan):
    # Materializing an edge-scale plan column on the host per tick:
    # O(E) bytes copied device->host inside the epoch cadence — edge
    # host work belongs in plan build (Manager.prepare_epoch).
    seg_dst = np.asarray(plan.seg_dst)  # VIOLATION: host-materialization-of-edges
    return seg_dst.shape[0]
'''


def _host_materialization_of_edges() -> tuple[str, str]:
    # The fake path lands on an epoch-loop file so the file-scoped
    # pass-12 rule applies exactly as it would to the real module.
    return _HOST_MATERIALIZATION_SRC, "protocol_tpu/node/pipeline.py"


_UNBOUNDED_CACHE_SRC = '''\
class ScoreServer:
    """A long-lived node class with an epoch-keyed cache that only
    ever grows — the leak the ring-eviction doctrine exists to stop
    (4 MB of f32[N] scores per epoch at 1M peers)."""

    def __init__(self):
        self._score_cache = {}  # VIOLATION: unbounded-cache-growth

    def publish(self, epoch, scores):
        self._score_cache[epoch] = scores

    def serve(self, epoch):
        return self._score_cache.get(epoch)
'''


def _unbounded_cache_growth() -> tuple[str, str]:
    return _UNBOUNDED_CACHE_SRC, "protocol_tpu/node/_fixture_cache_growth.py"


# -- pass-13 determinism fixtures -------------------------------------------

_SET_ORDER_TO_STATE_SRC = '''\
import numpy as np


def stamp_columns(peers, scores):
    # A peer *set* flattened straight into a checkpoint column: the
    # array inherits per-process hash order, so two hosts digest
    # different bytes from identical peer sets.
    live = {p for p in peers if p >= 0}
    column = np.asarray(list(live))  # VIOLATION: set-order-to-state
    return column, scores[column]
'''


def _set_order_to_state() -> tuple[str, str]:
    return _SET_ORDER_TO_STATE_SRC, "protocol_tpu/node/_fixture_set_order.py"


_UNSORTED_DIRSCAN_SRC = '''\
import os


def replay_segments(wal_dir):
    # WAL segments replayed in directory-scan order: inode history
    # decides the replay sequence, so two hosts reconverge through
    # different intermediate states.
    names = os.listdir(wal_dir)  # VIOLATION: unsorted-dirscan
    return [os.path.join(wal_dir, n) for n in names]
'''


def _unsorted_dirscan() -> tuple[str, str]:
    return _UNSORTED_DIRSCAN_SRC, "protocol_tpu/node/_fixture_dirscan.py"


_HASH_ORDERING_SRC = '''\
def partition_key(sender_pk, n_partitions):
    # Builtin hash() as a partition key: hash(str) is salted per
    # process (PYTHONHASHSEED), so the same sender lands on different
    # partitions on different hosts.
    return hash(sender_pk) % n_partitions  # VIOLATION: hash-ordering
'''


def _hash_ordering() -> tuple[str, str]:
    return _HASH_ORDERING_SRC, "protocol_tpu/ingest/_fixture_hash_key.py"


_UNSEEDED_RNG_SRC = '''\
import numpy as np


def churn_draw(n_peers):
    # A churn-stream draw from the process-global RNG: every host
    # samples a different peer set, so the epoch graphs diverge
    # before the first matvec.
    return np.random.permutation(n_peers)  # VIOLATION: unseeded-rng
'''


def _unseeded_rng() -> tuple[str, str]:
    return _UNSEEDED_RNG_SRC, "protocol_tpu/models/_fixture_churn_rng.py"


_CLOCK_IN_DIGEST_SRC = '''\
import hashlib
import time


def seal_manifest(columns):
    # Wall clock folded into the manifest digest: the seal differs on
    # every host and every replay, so bit-identity verification can
    # never pass.
    stamp = time.time()
    h = hashlib.sha256(str(columns).encode())
    h.update(str(stamp).encode())  # VIOLATION: clock-in-digest
    return h.hexdigest()
'''


def _clock_in_digest() -> tuple[str, str]:
    return _CLOCK_IN_DIGEST_SRC, "protocol_tpu/node/_fixture_clock_seal.py"


def _hlo_nondeterministic_compile() -> tuple[str, str, str]:
    # Two "compiles" of the same entry that differ structurally after
    # canonicalization: identical SSA naming-counter noise (different
    # value numbers, same shape) cancels out, but compile 2 fuses an
    # extra multiply — the drift the double-compile cross-check exists
    # to catch.
    module_a = """\
HloModule converge_fixture

ENTRY %main.12 {
  %param.0 = f32[64]{0} parameter(0)
  %param.1 = f32[64]{0} parameter(1)
  %add.3 = f32[64]{0} add(%param.0, %param.1)
  ROOT %mul.4 = f32[64]{0} multiply(%add.3, %param.1)
}
"""
    module_b = """\
HloModule converge_fixture

ENTRY %main.47 {
  %param.8 = f32[64]{0} parameter(0)
  %param.9 = f32[64]{0} parameter(1)
  %mul.13 = f32[64]{0} multiply(%param.8, %param.9)
  %add.11 = f32[64]{0} add(%mul.13, %param.9)
  ROOT %mul.14 = f32[64]{0} multiply(%add.11, %param.9)
}
"""
    return "fixture:hlo-drift", module_a, module_b


FIXTURES: dict[str, Fixture] = {
    f.name: f
    for f in (
        Fixture("extra-gather", "gather-budget", _extra_gather, "extra-gather"),
        Fixture("f64-leak", "f64-dtype", _f64_leak, "f64-leak"),
        Fixture(
            "callback-in-jit", "callback-in-jit", _callback_in_jit, "callback-in-jit"
        ),
        Fixture(
            "scatter-in-step", "scatter-budget", _scatter_in_step, "scatter-in-step"
        ),
        Fixture(
            "missing-donation", "donation-not-materialized", _missing_donation, None
        ),
        Fixture(
            "time-in-jit", "host-clock-in-jit", _time_in_jit, "time-in-jit",
            kind="ast",
        ),
        Fixture(
            "logging-in-jit", "logging-in-jit", _logging_in_jit,
            "logging-in-jit", kind="ast",
        ),
        Fixture(
            "clock-in-kernel-tree", "clock-in-kernel-tree",
            _clock_in_kernel_tree, "clock-in-kernel-tree", kind="ast",
        ),
        Fixture(
            "plan-mutation-in-converge", "plan-mutation-in-converge",
            _plan_mutation_in_converge, "plan-mutation-in-converge",
            kind="ast",
        ),
        Fixture(
            "journal-write-in-jit", "journal-write-in-jit",
            _journal_write_in_jit, "journal-write-in-jit",
            kind="ast",
        ),
        Fixture(
            "blocking-ingest-in-epoch-loop", "blocking-ingest-in-epoch-loop",
            _blocking_ingest_in_epoch_loop, "blocking-ingest-in-epoch-loop",
            kind="ast",
        ),
        Fixture(
            "blocking-prove-in-epoch-loop", "blocking-prove-in-epoch-loop",
            _blocking_prove_in_epoch_loop, "blocking-prove-in-epoch-loop",
            kind="ast",
        ),
        Fixture(
            "unobserved-queue", "unobserved-queue",
            _unobserved_queue, "unobserved-queue", kind="ast",
        ),
        Fixture(
            "non-atomic-state-write", "non-atomic-state-write",
            _non_atomic_state_write, "non-atomic-state-write", kind="ast",
        ),
        Fixture(
            "fault-point-in-jit", "fault-point-in-jit",
            _fault_point_in_jit, "fault-point-in-jit", kind="ast",
        ),
        Fixture(
            "unguarded-shared-attr", "unguarded-shared-attr",
            _unguarded_shared_attr, "unguarded-shared-attr",
            kind="concurrency",
        ),
        Fixture(
            "unguarded-rmw", "unguarded-rmw", _unguarded_rmw,
            "unguarded-rmw", kind="concurrency",
        ),
        Fixture(
            "check-then-act", "check-then-act", _check_then_act,
            "check-then-act", kind="concurrency",
        ),
        Fixture(
            "lock-order-cycle", "lock-order-cycle", _lock_order_cycle,
            "lock-order-cycle", kind="concurrency",
        ),
        Fixture(
            "blocking-call-under-lock", "blocking-call-under-lock",
            _blocking_call_under_lock, "blocking-call-under-lock",
            kind="concurrency",
        ),
        Fixture(
            "native-call-under-lock", "native-call-under-lock",
            _native_call_under_lock, "native-call-under-lock",
            kind="concurrency",
        ),
        Fixture(
            "surprise-all-gather", "collective-kind",
            _surprise_all_gather, "surprise-all-gather", kind="comm",
        ),
        Fixture(
            "comm-bytes-over-budget", "comm-bytes-budget",
            _comm_bytes_over_budget, "comm-bytes-over-budget", kind="comm",
        ),
        Fixture(
            "host-round-trip", "host-round-trip",
            _host_round_trip, "host-round-trip", kind="comm",
        ),
        Fixture(
            "alias-dropped", "alias-dropped", _alias_dropped, None,
            kind="comm",
        ),
        Fixture(
            "psum-lowering-mismatch", "psum-lowering-mismatch",
            _psum_lowering_mismatch, "psum-lowering-mismatch", kind="comm",
        ),
        Fixture(
            "o-e-live-temporary", "o-e-live-temporary",
            _o_e_live_temporary, "o-e-live-temporary", kind="mem",
        ),
        Fixture(
            "donation-peak-doubled", "donation-peak-doubled",
            _donation_peak_doubled, None, kind="mem",
        ),
        Fixture(
            "shard-replicated-edges", "shard-replicated-edges",
            _shard_replicated_edges, None, kind="mem",
        ),
        Fixture(
            "host-staging-over-cap", "host-staging-over-cap",
            _host_staging_over_cap, "host-staging-over-cap", kind="mem",
        ),
        Fixture(
            "host-materialization-of-edges", "host-materialization-of-edges",
            _host_materialization_of_edges, "host-materialization-of-edges",
            kind="mem-ast",
        ),
        Fixture(
            "unbounded-cache-growth", "unbounded-cache-growth",
            _unbounded_cache_growth, "unbounded-cache-growth",
            kind="mem-ast",
        ),
        Fixture(
            "set-order-to-state", "set-order-to-state",
            _set_order_to_state, "set-order-to-state", kind="det-ast",
        ),
        Fixture(
            "unsorted-dirscan", "unsorted-dirscan",
            _unsorted_dirscan, "unsorted-dirscan", kind="det-ast",
        ),
        Fixture(
            "hash-ordering", "hash-ordering",
            _hash_ordering, "hash-ordering", kind="det-ast",
        ),
        Fixture(
            "unseeded-rng", "unseeded-rng",
            _unseeded_rng, "unseeded-rng", kind="det-ast",
        ),
        Fixture(
            "clock-in-digest", "clock-in-digest",
            _clock_in_digest, "clock-in-digest", kind="det-ast",
        ),
        Fixture(
            "hlo-nondeterministic-compile", "hlo-nondeterministic-compile",
            _hlo_nondeterministic_compile, None, kind="det-hlo",
        ),
    )
}


def run_fixture(name: str) -> list[Finding]:
    """Trace and check one seeded violation; raises KeyError on an
    unknown name (the CLI lists valid ones)."""
    fixture = FIXTURES[name]
    if fixture.kind == "ast":
        from .ast_rules import scan_source

        source, rel_path = fixture.build()
        return scan_source(source, rel_path)
    if fixture.kind == "concurrency":
        from .concurrency import analyze_sources

        source, rel_path = fixture.build()
        return analyze_sources({rel_path: source})
    if fixture.kind == "comm":
        from .comm.checker import check_comm_case

        budget, cases = fixture.build()
        return [f for c in cases for f in check_comm_case(budget, c)[0]]
    if fixture.kind == "mem":
        from .memory.checker import check_mem_case

        budget, cases = fixture.build()
        return [f for c in cases for f in check_mem_case(budget, c)[0]]
    if fixture.kind == "mem-ast":
        from .ast_rules import scan_source

        source, rel_path = fixture.build()
        return scan_source(source, rel_path, mem_rules=True)
    if fixture.kind == "det-ast":
        from .determinism.ast_walk import scan_det_source

        source, rel_path = fixture.build()
        return scan_det_source(source, rel_path)
    if fixture.kind == "det-hlo":
        from .determinism.checker import check_recompile

        backend, module_a, module_b = fixture.build()
        return check_recompile(backend, module_a, module_b)
    budget, case = fixture.build()
    return check_case(budget, case)


__all__ = ["FIXTURES", "Fixture", "run_fixture"]
