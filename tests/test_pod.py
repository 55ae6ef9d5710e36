"""Pod scale-out plumbing (ISSUE 16): the single-process halves of the
multi-host path — partition plan build, the sharded durability plane,
the multi-host analyzer extensions, and the sentinel keying.

The genuinely multi-process claims (bit-identical residuals across
hosts, host-loss recovery to a control-identical fixed point, flat
steady-state epoch seconds) are driven by ``tools/dryrun_pod.py`` and
the crash-matrix ``pod.host-loss`` row; this file pins everything that
can be checked in one process:

- a 1-host pod's ``PodWindowPlan`` is **byte-identical** to the
  single-host ``ShardedWindowPlan`` — the pod builder is a
  generalization, not a fork (same runner cache key, same arrays);
- the pod delta path resolves churn against the *local* plan and
  produces the same partition arrays as a cold rebuild;
- ``PodDurability`` seals only complete stamp sets, recovery reads the
  newest *sealed* manifest (torn pod states unrepresentable);
- ``pod_budget_view`` divides the resident edge term by the global
  shard count; the replica-group-coverage rule rejects per-host
  subgroup collectives; ``_warm_t0``'s vectorized remap matches the
  per-peer definition; pod sentinel series never collide with the
  single-host history.
"""

import json
import sys
import threading
import time
import types
from pathlib import Path

import numpy as np
import pytest

from protocol_tpu.analysis import COMM_INVARIANTS, MEM_INVARIANTS
from protocol_tpu.analysis.comm.checker import check_comm_case
from protocol_tpu.analysis.comm.hlo_walk import replica_group_sizes
from protocol_tpu.analysis.comm.lowering import CommCase
from protocol_tpu.analysis.memory.checker import pod_budget_view
from protocol_tpu.models import scale_free
from protocol_tpu.models.churn import churn_cohort_dims, sender_centric_churn
from protocol_tpu.node.manager import Manager
from protocol_tpu.node.pod import PodDurability
from protocol_tpu.obs import metrics as obs_metrics
from protocol_tpu.parallel.mesh import default_mesh
from protocol_tpu.parallel.partition import HostPartition
from protocol_tpu.parallel.pod import PodContext, PodWindowPlan
from protocol_tpu.parallel.sharded import ShardedWindowPlan
from protocol_tpu.trust.backend import normalize_graph

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import perf_sentinel

pytestmark = pytest.mark.allow_transfer

SHARDED_ARRAYS = (
    "wid", "local", "weight", "run_start", "run_end", "dst_ptr", "p",
    "dangling",
)


def _graph(n=1024, e=8192, seed=16):
    return normalize_graph(scale_free(n, e, seed=seed))


def _pod1():
    """A 1-host pod over the test mesh — ``PodContext.current`` under
    a single process, which is exactly what it resolves to."""
    return PodContext.current(seed=16)


class TestPodPlanBuild:
    def test_single_host_pod_matches_sharded_plan(self):
        g = _graph()
        pod = _pod1()
        pp = PodWindowPlan.build(g, pod, clock=time.perf_counter)
        sp = ShardedWindowPlan.build(g, default_mesh())
        assert (pp.n, pp.rows_per_shard, pp.s_max, pp.table_entries) == (
            sp.n, sp.rows_per_shard, sp.s_max, sp.table_entries
        )
        for name in SHARDED_ARRAYS:
            a, b = np.asarray(getattr(pp, name)), np.asarray(getattr(sp, name))
            assert np.array_equal(a, b), name
        assert pp.plan.fingerprint == sp.plan.fingerprint
        assert pp.host_id == 0 and pp.n_hosts == 1
        assert np.array_equal(pp.owner, np.zeros(g.n, np.int32))
        assert pp.local_edges == g.nnz
        assert pp.plan_outcome == "rebuild" and pp.build_seconds > 0

    def test_plan_reuse_and_delta_outcomes(self):
        raw = scale_free(1024, 8192, seed=16)
        g = normalize_graph(raw)
        pod = _pod1()
        cold = PodWindowPlan.build(g, pod)
        # Same graph + cached plan: fingerprint revalidates, no build.
        warm = PodWindowPlan.build(g, pod, plan=cold.plan)
        assert warm.plan_outcome == "reuse"
        assert warm.build_seconds == 0.0
        # Churn one epoch and resolve with the hint: delta path, and
        # the partition arrays match a cold rebuild of the new graph.
        cohort_size, deg = churn_cohort_dims(raw, 0.01)
        rows, g2, _ = sender_centric_churn(
            np.random.default_rng(16), raw, cohort_size=cohort_size, deg=deg
        )
        g2 = normalize_graph(g2)
        delta = PodWindowPlan.build(g2, pod, plan=cold.plan, delta_rows=rows)
        rebuilt = PodWindowPlan.build(g2, pod)
        assert delta.plan_outcome == "delta"
        assert delta.plan.fingerprint == rebuilt.plan.fingerprint
        for name in SHARDED_ARRAYS:
            a = np.asarray(getattr(delta, name))
            b = np.asarray(getattr(rebuilt, name))
            assert np.array_equal(a, b), name

    @pytest.mark.slow
    def test_single_host_pod_converge_bit_identical(self):
        """The pod plan through the real runner: same cache key, same
        arrays — the scores must be bit-identical to the single-host
        sharded windowed backend (the multi-process version of this
        claim is the dryrun's cross-host residual identity check)."""
        from protocol_tpu.parallel.sharded import converge_sharded

        g = _graph(512, 4096)
        sp = ShardedWindowPlan.build(g, default_mesh())
        s_ref, it_ref, _ = converge_sharded(sp, max_iter=30)
        pp = PodWindowPlan.build(g, _pod1())
        s_pod, it_pod, _ = converge_sharded(pp, max_iter=30)
        assert it_pod == it_ref
        assert np.array_equal(np.asarray(s_pod), np.asarray(s_ref))

    def test_t0_is_a_fresh_copy(self):
        pp = PodWindowPlan.build(_graph(256, 2048), _pod1())
        t0 = pp.t0()
        assert t0 is not pp.p
        assert np.array_equal(np.asarray(t0), np.asarray(pp.p))


class TestPodDurability:
    def _pod(self, root, host, n=2):
        return PodDurability(root, host, n, fsync=False)

    def test_host_id_validation(self, tmp_path):
        with pytest.raises(ValueError):
            PodDurability(tmp_path, 2, 2)

    def test_seal_requires_complete_stamp_set(self, tmp_path):
        h0, h1 = self._pod(tmp_path, 0), self._pod(tmp_path, 1)
        h0.publish_shard(3, wal_seq=7, columns={"graph": "aa"})
        assert h0.seal_epoch(3) is None  # host 1 not published yet
        assert h0.load_manifest() is None
        h1.publish_shard(3, wal_seq=9, columns={"graph": "bb"})
        manifest = h0.seal_epoch(3)
        assert manifest is not None and manifest["epoch"] == 3
        loaded = h1.load_manifest()
        assert loaded == manifest
        assert h1.my_stamp(loaded)["wal_seq"] == 9
        assert h0.my_stamp(loaded)["columns"] == {"graph": "aa"}

    def test_recovery_reads_newest_sealed_not_newest_published(self, tmp_path):
        h0, h1 = self._pod(tmp_path, 0), self._pod(tmp_path, 1)
        for e in (1, 2):
            h0.publish_shard(e, wal_seq=e, columns={})
            h1.publish_shard(e, wal_seq=e, columns={})
            h0.seal_epoch(e)
        # Crash between publish and seal at epoch 3: host 0 stamped,
        # host 1 (and the seal) never happened.
        h0.publish_shard(3, wal_seq=3, columns={})
        manifest = h1.load_manifest()
        assert manifest is not None and manifest["epoch"] == 2
        # Partial epoch-3 state is invisible — every host rolls back
        # to the same epoch.
        assert h0.load_manifest()["epoch"] == 2

    def test_stamps_are_atomic_no_tmp_litter(self, tmp_path):
        h0 = self._pod(tmp_path, 0, n=1)
        h0.publish_shard(1, wal_seq=0, columns={"a": "b"})
        h0.seal_epoch(1)
        assert not list(tmp_path.glob("manifests/*.tmp"))
        stamp = json.loads(
            (tmp_path / "manifests" / "shard-e00000001-h000.json").read_text()
        )
        assert stamp["n_hosts"] == 1

    def test_wal_and_checkpoints_shard_per_host(self, tmp_path):
        h0, h1 = self._pod(tmp_path, 0), self._pod(tmp_path, 1)
        s0 = h0.wal.append(b"host0-att", flush=True)
        s1 = h1.wal.append(b"host1-att", flush=True)
        # Each host replays only its own shard, and the sequence
        # counters are per-shard (independent WALs, not one log).
        assert [p for _, p in h0.wal.replay()] == [b"host0-att"]
        assert [p for _, p in h1.wal.replay()] == [b"host1-att"]
        assert s0 == s1
        assert (tmp_path / "host-000" / "wal").is_dir()
        assert (tmp_path / "host-001" / "checkpoints").is_dir()


class TestPodAnalyzers:
    def test_pod_budget_view_divides_edges_by_global_shards(self):
        # CSR composite: raw edge arrays, so the resident edge term
        # divides by the GLOBAL shard count directly.
        budget = MEM_INVARIANTS["tpu-sharded:tpu-csr"]
        dims = dict(n=4096, edges=1 << 20, n_segments=0, rows=0)
        one = pod_budget_view(budget, n_shards=8, n_hosts=1, **dims)
        pod = pod_budget_view(budget, n_shards=32, n_hosts=4, **dims)
        assert pod["n_hosts"] == 4 and pod["n_shards"] == 32
        # 4x the shards: the edge-resident term shrinks, the O(N)
        # replicated terms don't — per-shard peak strictly drops.
        assert pod["resident_bytes"] < one["resident_bytes"]
        assert pod["transient_bytes"] == one["transient_bytes"]
        assert pod["peak_bytes"] == pod["resident_bytes"] + pod["transient_bytes"]

    def test_pod_budget_view_windowed_scales_with_per_host_plan(self):
        # Windowed composite: edge residency lives in the plan's
        # vreg-rows, so the pod division shows up through the per-host
        # plan dims (a host's plan over E/H edges has ~rows/H rows).
        budget = MEM_INVARIANTS["tpu-sharded:tpu-windowed"]
        assert budget.resident_edge_bytes == 0.0
        one = pod_budget_view(
            budget, n=4096, edges=1 << 20, n_segments=2048, rows=512,
            n_shards=8, n_hosts=1,
        )
        pod = pod_budget_view(
            budget, n=4096, edges=1 << 20, n_segments=512, rows=128,
            n_shards=32, n_hosts=4,
        )
        assert pod["peak_bytes"] < one["peak_bytes"]

    def test_replica_group_sizes_parsing(self):
        assert replica_group_sizes("{{0,1,2,3},{4,5,6,7}}") == [4, 4]
        assert replica_group_sizes("{{0,1,2,3,4,5,6,7}}") == [8]
        assert replica_group_sizes("{}") == []
        assert replica_group_sizes("") == []

    def _case(self, groups: str) -> CommCase:
        text = (
            "HloModule jit_run, is_scheduled=true\n"
            "%all-reduce.4 = f32[512]{0} all-reduce(f32[512]{0} %c.2), "
            f"channel_id=1, replica_groups={groups}, "
            "use_global_device_ids=true, to_apply=%region_1.205, "
            'metadata={op_name="jit(run)/jit(main)/while/body/'
            'jit(shmap_body)/psum2" source_file="/repo/parallel/sharded.py" '
            "source_line=171}\n"
        )
        return CommCase(
            backend="tpu-sharded:tpu-windowed",
            dims={"n": 512, "edges": 4096, "n_shards": 4, "n_segments": 1024},
            module_text=text,
            arg_names=("t0",),
            jaxpr_psums=1,
        )

    def test_per_host_subgroup_psum_is_rejected(self):
        budget = COMM_INVARIANTS["tpu-sharded:tpu-windowed"]
        assert budget.require_full_replica_group
        findings, _ = check_comm_case(budget, self._case("{{0,1},{2,3}}"))
        rules = [f.rule for f in findings]
        assert "replica-group-coverage" in rules

    def test_full_mesh_group_passes(self):
        budget = COMM_INVARIANTS["tpu-sharded:tpu-windowed"]
        for groups in ("{{0,1,2,3}}", "{}"):
            findings, _ = check_comm_case(budget, self._case(groups))
            assert "replica-group-coverage" not in [f.rule for f in findings], groups


class TestWarmT0Remap:
    """The vectorized searchsorted remap (PERF.md §20) against the
    per-peer definition it replaced."""

    def _warm(self, scores, hashes, id_order):
        m = types.SimpleNamespace(
            _state_lock=threading.Lock(),
            last_scores=scores,
            last_peer_hashes=hashes,
        )
        return Manager._warm_t0(m, id_order)

    def test_matches_per_peer_remap(self):
        rng = np.random.default_rng(16)
        prev_hashes = [int(h) for h in rng.integers(1, 1 << 62, 300)]
        scores = rng.random(300).astype(np.float64)
        survivors = prev_hashes[:200]
        joined = [int(h) for h in rng.integers(1 << 62, 1 << 63, 100)]
        id_order = survivors + joined
        rng.shuffle(id_order)
        got = self._warm(scores, prev_hashes, id_order)
        ref = np.array(
            [
                max(scores[prev_hashes.index(h)], 0.0) if h in set(survivors) else 0.0
                for h in id_order
            ]
        )
        ref /= ref.sum()
        assert got is not None
        np.testing.assert_allclose(got, ref, rtol=1e-12)
        assert abs(got.sum() - 1.0) < 1e-9

    def test_cold_start_cases(self):
        assert self._warm(None, None, [1, 2]) is None
        assert self._warm(np.array([]), [], [1, 2]) is None
        # Zero overlap: every score drops out -> cold start.
        assert self._warm(np.array([0.5, 0.5]), [10, 11], [20, 21]) is None
        # Negative garbage clamps to zero rather than poisoning the seed.
        got = self._warm(np.array([-1.0, 0.5]), [10, 11], [10, 11])
        np.testing.assert_allclose(got, [0.0, 1.0])


    @pytest.mark.parametrize("joined", [0, 37])
    def test_prefix_seed_is_bit_identical(self, joined):
        """An unchanged or extended column takes the prefix path; its
        seed equals, bit for bit, the search path's (the previous column
        permuted) and the 64-bit searchsorted remap it replaced."""
        rng = np.random.default_rng(28)
        prev = [int.from_bytes(rng.bytes(32), "big") >> 2 for _ in range(500)]
        scores = rng.random(500).astype(np.float32)
        scores[[5, 9]] = -0.25  # clamped to zero on every path
        id_order = prev + [int.from_bytes(rng.bytes(32), "big") >> 2 for _ in range(joined)]
        before = obs_metrics.PEER_ALIGN.value(site="warm_seed", path="prefix")
        fast = self._warm(scores, prev, id_order)
        assert obs_metrics.PEER_ALIGN.value(site="warm_seed", path="prefix") == before + 1
        perm = rng.permutation(500)
        searched = self._warm(scores[perm], [prev[i] for i in perm], id_order)
        assert np.array_equal(fast, searched)
        assert np.array_equal(fast, _searchsorted_remap(scores, prev, id_order))

    def test_low_64_bit_collision_is_absent(self):
        """A joined hash sharing its low 64 bits with a departed one
        starts at zero: the search checks the full hash."""
        a = (0xDEAD << 192) | 0x1234
        b = (0xBEEF << 192) | 0x1234
        c = 99
        got = self._warm(np.array([0.75, 0.25]), [a, c], [b, c])
        np.testing.assert_array_equal(got, [0.0, 1.0])
        assert _searchsorted_remap(np.array([0.75, 0.25]), [a, c], [b, c])[0] > 0


def _searchsorted_remap(scores, hashes, id_order):
    """The warm seed as the 64-bit key searchsorted remap computed it
    before the alignment helper (collisions read as matches)."""
    from protocol_tpu.parallel.partition import keys_from_hashes

    prev_keys, new_keys = keys_from_hashes(hashes), keys_from_hashes(id_order)
    order = np.argsort(prev_keys, kind="stable")
    sorted_prev = prev_keys[order]
    pos = np.minimum(np.searchsorted(sorted_prev, new_keys), len(sorted_prev) - 1)
    hit = sorted_prev[pos] == new_keys
    j = order[pos]
    prev_scores = np.maximum(np.asarray(scores, np.float64), 0.0)
    t0 = np.where(hit, prev_scores[j], 0.0)
    return t0 / t0.sum()


class TestPodSentinelKeys:
    def test_multi_host_entries_get_their_own_series(self):
        single = {"metric": "pod steady-state epoch wall-clock", "n_hosts": 1}
        pod = {"metric": "pod steady-state epoch wall-clock", "n_hosts": 2}
        legacy = {"metric": "pod steady-state epoch wall-clock"}
        k1 = perf_sentinel._series_key(single, "value")
        k2 = perf_sentinel._series_key(pod, "value")
        k3 = perf_sentinel._series_key(legacy, "value")
        # n_hosts=1 and legacy entries share the historical key; pods
        # fork their own series instead of gating against it.
        assert k1 == k3 == "pod steady-state epoch wall-clock :: value"
        assert k2 == "pod steady-state epoch wall-clock :: value [n_hosts=2]"

    def test_plan_build_fields_gate(self):
        assert perf_sentinel._FIELDS["plan_build_seconds"] is True
        assert perf_sentinel._FIELDS["plan_build_speedup"] is False
