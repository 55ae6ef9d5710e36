"""Determinism wall (ISSUE 18): graftlint pass 13 + divergence probe.

Covers: the five pass-13 AST rules fire on minimal positive snippets
and stay quiet on the blessed idioms (``sorted(...)``, seeded RNGs,
timing deltas into metrics), the HLO leg's canonicalizer cancels SSA
renumbering while structural drift still trips
``hlo-nondeterministic-compile``, every seeded determinism fixture is
registered and fails the CLI, the real tree is clean modulo the one
enumerated waiver, a dead determinism waiver is itself a gate error,
the two real divergence sources this pass found (checkpoint glob
order, dedup ``hash()`` shard key) stay fixed, the probe's comparator
discriminates leg by leg, and the committed DET_r01.json round feeds
the perf sentinel as a lower-is-better multi-host series.
"""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import divergence_probe
import perf_sentinel

from protocol_tpu.analysis.__main__ import main as analysis_main
from protocol_tpu.analysis.determinism import (
    DET_AST_RULES,
    DET_TREES,
    DET_WAIVERS,
    canonicalize_hlo,
    check_recompile,
    diff_canonical,
    scan_det_source,
    scan_module_text,
)
from protocol_tpu.analysis.determinism import checker as det_checker
from protocol_tpu.analysis.determinism.ast_walk import run_det_ast_pass
from protocol_tpu.analysis.fixtures import FIXTURES

REPO = Path(__file__).resolve().parent.parent

DET_FIXTURES = {
    "set-order-to-state": "det-ast",
    "unsorted-dirscan": "det-ast",
    "hash-ordering": "det-ast",
    "unseeded-rng": "det-ast",
    "clock-in-digest": "det-ast",
    "hlo-nondeterministic-compile": "det-hlo",
}


def _scan(code: str, rel: str = "protocol_tpu/node/_snippet.py"):
    return scan_det_source(code, rel)


def _rules(code: str, rel: str = "protocol_tpu/node/_snippet.py"):
    return [f.rule for f in _scan(code, rel)]


# ---------------------------------------------------------------------------
# rule: set-order-to-state
# ---------------------------------------------------------------------------


class TestSetOrderToState:
    def test_list_of_set_fires(self):
        code = (
            "def seal(live):\n"
            "    live = set(live)\n"
            "    return list(live)\n"
        )
        assert _rules(code) == ["set-order-to-state"]

    def test_comprehension_over_set_fires(self):
        code = (
            "def columns(peers):\n"
            "    alive = {p for p in peers}\n"
            "    return [p * 2 for p in alive]\n"
        )
        assert _rules(code) == ["set-order-to-state"]

    def test_sum_over_set_fires(self):
        code = (
            "def residual(scores):\n"
            "    pending = set(scores)\n"
            "    return sum(pending)\n"
        )
        assert _rules(code) == ["set-order-to-state"]

    def test_np_asarray_of_set_fires(self):
        code = (
            "import numpy as np\n"
            "def column(live):\n"
            "    live = frozenset(live)\n"
            "    return np.asarray(list(live))\n"
        )
        assert "set-order-to-state" in _rules(code)

    def test_accumulating_loop_over_set_fires(self):
        code = (
            "def order(ids):\n"
            "    live = ids | {0}\n"
            "    out = []\n"
            "    for i in live:\n"
            "        out.append(i)\n"
            "    return out\n"
        )
        # `ids | {0}` is set-ish through the BinOp only when a side is
        # known set-ish; make it explicit:
        code = code.replace("ids | {0}", "set(ids) | {0}")
        assert _rules(code) == ["set-order-to-state"]

    def test_sorted_set_is_quiet(self):
        code = (
            "def seal(live):\n"
            "    live = set(live)\n"
            "    return sorted(live)\n"
        )
        assert _rules(code) == []

    def test_sorted_genexp_over_set_is_quiet(self):
        # The manager.py idiom: sorted(<genexp over set>).
        code = (
            "def seal(stale):\n"
            "    stale = set(stale)\n"
            "    return sorted(s.key for s in stale)\n"
        )
        assert _rules(code) == []

    def test_order_insensitive_consumers_are_quiet(self):
        code = (
            "def stats(live):\n"
            "    live = set(live)\n"
            "    return len(live), min(live), max(live), any(live), all(live)\n"
        )
        assert _rules(code) == []

    def test_membership_loop_is_quiet(self):
        code = (
            "def check(live, want):\n"
            "    live = set(live)\n"
            "    for i in live:\n"
            "        print(i)\n"
            "    return want in live\n"
        )
        assert _rules(code) == []

    def test_dict_keys_iteration_is_quiet(self):
        # dicts preserve insertion order — not a hash-order source.
        code = (
            "def cols(table):\n"
            "    out = []\n"
            "    for k in table.keys():\n"
            "        out.append(k)\n"
            "    return out\n"
        )
        assert _rules(code) == []


# ---------------------------------------------------------------------------
# rule: unsorted-dirscan
# ---------------------------------------------------------------------------


class TestUnsortedDirscan:
    def test_listdir_fires(self):
        code = (
            "import os\n"
            "def segments(wal_dir):\n"
            "    return os.listdir(wal_dir)\n"
        )
        assert _rules(code) == ["unsorted-dirscan"]

    @pytest.mark.parametrize("call", ["glob('*.npz')", "rglob('*.json')", "iterdir()"])
    def test_path_scan_methods_fire(self, call):
        code = (
            "def epochs(root):\n"
            f"    return list(root.{call})\n"
        )
        assert _rules(code) == ["unsorted-dirscan"]

    def test_sorted_listdir_is_quiet(self):
        code = (
            "import os\n"
            "def segments(wal_dir):\n"
            "    return sorted(os.listdir(wal_dir))\n"
        )
        assert _rules(code) == []

    def test_sorted_glob_is_quiet(self):
        code = (
            "def epochs(root):\n"
            "    return sorted(root.glob('epoch_*.npz'))\n"
        )
        assert _rules(code) == []


# ---------------------------------------------------------------------------
# rule: hash-ordering
# ---------------------------------------------------------------------------


class TestHashOrdering:
    def test_builtin_hash_fires(self):
        code = (
            "def shard(sender, n):\n"
            "    return hash(sender) % n\n"
        )
        assert _rules(code) == ["hash-ordering"]

    def test_id_fires(self):
        code = (
            "def key(obj):\n"
            "    return id(obj)\n"
        )
        assert _rules(code) == ["hash-ordering"]

    def test_method_hash_is_quiet(self):
        # pk.hash() is the curve point's own digest, not builtin hash().
        code = (
            "def key(pk, n):\n"
            "    return pk.hash() % n\n"
        )
        assert _rules(code) == []


# ---------------------------------------------------------------------------
# rule: unseeded-rng
# ---------------------------------------------------------------------------


class TestUnseededRng:
    @pytest.mark.parametrize(
        "expr",
        [
            "random.random()",
            "random.shuffle(xs)",
            "np.random.permutation(8)",
            "np.random.randint(0, 8)",
            "random.Random()",
            "np.random.default_rng()",
        ],
    )
    def test_unseeded_draws_fire(self, expr):
        code = (
            "import random\n"
            "import numpy as np\n"
            "def churn(xs):\n"
            f"    return {expr}\n"
        )
        assert _rules(code) == ["unseeded-rng"]

    @pytest.mark.parametrize(
        "expr",
        [
            "np.random.default_rng(7)",
            "random.Random(7)",
            "rng.permutation(8)",
            "rng.integers(0, 8)",
        ],
    )
    def test_seeded_streams_are_quiet(self, expr):
        code = (
            "import random\n"
            "import numpy as np\n"
            "def churn(rng):\n"
            f"    return {expr}\n"
        )
        assert _rules(code) == []


# ---------------------------------------------------------------------------
# rule: clock-in-digest
# ---------------------------------------------------------------------------


class TestClockInDigest:
    def test_clock_through_binding_into_update_fires(self):
        code = (
            "import hashlib, time\n"
            "def seal(h):\n"
            "    stamp = time.time()\n"
            "    h.update(str(stamp).encode())\n"
        )
        assert _rules(code) == ["clock-in-digest"]

    def test_pid_directly_into_sha256_fires(self):
        code = (
            "import hashlib, os\n"
            "def token():\n"
            "    return hashlib.sha256(str(os.getpid()).encode())\n"
        )
        assert _rules(code) == ["clock-in-digest"]

    def test_clock_bound_to_seedish_name_fires(self):
        code = (
            "import time\n"
            "def job():\n"
            "    job_seed = time.time_ns()\n"
            "    return job_seed\n"
        )
        assert _rules(code) == ["clock-in-digest"]

    def test_timing_deltas_into_metrics_are_quiet(self):
        code = (
            "import time\n"
            "def bench(fn, metrics):\n"
            "    t0 = time.perf_counter()\n"
            "    fn()\n"
            "    wall = time.perf_counter() - t0\n"
            "    metrics['wall_seconds'] = wall\n"
            "    return wall\n"
        )
        assert _rules(code) == []

    def test_returned_timestamp_is_quiet(self):
        # epoch.py idiom: a wall-clock *observation* returned to the
        # caller is not a digest/seed sink.
        code = (
            "import time\n"
            "def now_unix():\n"
            "    return int(time.time())\n"
        )
        assert _rules(code) == []

    def test_taint_does_not_leak_across_functions(self):
        code = (
            "import hashlib, time\n"
            "def a():\n"
            "    stamp = time.time()\n"
            "    return stamp\n"
            "def b(stamp_text):\n"
            "    other = 'static'\n"
            "    return hashlib.sha256(other.encode())\n"
        )
        assert _rules(code) == []


# ---------------------------------------------------------------------------
# the HLO leg
# ---------------------------------------------------------------------------

_MODULE_A = """\
HloModule converge.0
ENTRY %main.1 {
  %param.3 = f32[64]{0} parameter(0)  // arg shard
  %add.17 = f32[64]{0} add(%param.3, %param.3)
  ROOT %mul.29 = f32[64]{0} multiply(%add.17, %param.3)
}
"""

#: Same structure, different per-process SSA numbering + comments.
_MODULE_A_RENUMBERED = """\
HloModule converge.0
ENTRY %main.7 {
  %param.9 = f32[64]{0} parameter(0)  /* other naming counter */
  %add.101 = f32[64]{0} add(%param.9, %param.9)
  ROOT %mul.4 = f32[64]{0} multiply(%add.101, %param.9)
}
"""

#: Structurally different: an extra fused add the renamer cannot hide.
_MODULE_B = """\
HloModule converge.0
ENTRY %main.1 {
  %param.3 = f32[64]{0} parameter(0)
  %add.17 = f32[64]{0} add(%param.3, %param.3)
  %add.18 = f32[64]{0} add(%add.17, %param.3)
  ROOT %mul.29 = f32[64]{0} multiply(%add.18, %param.3)
}
"""


class TestHloLeg:
    def test_renumbering_cancels_under_canonicalization(self):
        assert canonicalize_hlo(_MODULE_A) == canonicalize_hlo(_MODULE_A_RENUMBERED)
        assert diff_canonical(_MODULE_A, _MODULE_A_RENUMBERED) is None
        assert check_recompile("tpu-dense", _MODULE_A, _MODULE_A_RENUMBERED) == []

    def test_structural_drift_fires(self):
        findings = check_recompile("tpu-dense", _MODULE_A, _MODULE_B)
        assert [f.rule for f in findings] == ["hlo-nondeterministic-compile"]
        assert findings[0].severity == "error"
        assert findings[0].backend == "tpu-dense"
        assert "drift" in findings[0].message

    def test_scatter_without_unique_indices_fires(self):
        text = (
            "%scatter.5 = f32[64]{0} scatter(%operand, %idx, %upd), "
            "to_apply=%add_f32\n"
        )
        findings, stats = scan_module_text("tpu-sparse", text)
        assert [f.rule for f in findings] == ["hlo-nondeterministic-scatter"]
        assert stats == {"scatter_ops": 1, "reduce_precision_ops": 0}

    def test_scatter_with_unique_indices_is_quiet(self):
        text = (
            "%scatter.5 = f32[64]{0} scatter(%operand, %idx, %upd), "
            "unique_indices=true, to_apply=%add_f32\n"
        )
        findings, stats = scan_module_text("tpu-sparse", text)
        assert findings == []
        assert stats["scatter_ops"] == 1

    def test_reduce_precision_fires(self):
        text = "%rp.2 = f32[64]{0} reduce-precision(%add.1), exponent_bits=8\n"
        findings, stats = scan_module_text("tpu-dense", text)
        assert [f.rule for f in findings] == ["hlo-reduce-precision"]
        assert stats["reduce_precision_ops"] == 1


# ---------------------------------------------------------------------------
# seeded fixtures + CLI gate
# ---------------------------------------------------------------------------


class TestSeededFixtures:
    def test_all_six_registered(self):
        for name, kind in DET_FIXTURES.items():
            assert name in FIXTURES, name
            assert FIXTURES[name].kind == kind
            assert FIXTURES[name].rule == name

    def test_cli_exits_nonzero_on_det_ast_fixture(self, tmp_path):
        out = tmp_path / "fixture.json"
        rc = analysis_main(["--fixture", "unseeded-rng", "--output", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["findings"][0]["rule"] == "unseeded-rng"
        assert report["findings"][0]["pass"] == "determinism"

    def test_cli_exits_nonzero_on_det_hlo_fixture(self, tmp_path):
        out = tmp_path / "fixture.json"
        rc = analysis_main(
            ["--fixture", "hlo-nondeterministic-compile", "--output", str(out)]
        )
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["findings"][0]["rule"] == "hlo-nondeterministic-compile"
        assert report["findings"][0]["pass"] == "determinism"


# ---------------------------------------------------------------------------
# the real tree + waiver doctrine
# ---------------------------------------------------------------------------


class TestRealTree:
    def test_det_trees_are_clean_modulo_the_enumerated_waiver(self):
        findings, n_files = run_det_ast_pass()
        assert n_files > 20  # the five trees are really being walked
        live, waived, stale = det_checker._apply_waivers(findings)
        assert live == [], [
            f"{f.file}:{f.line} {f.rule}: {f.message}" for f in live
        ]
        assert [w["symbol"] for w in waived] == ["random.Random"]
        assert waived[0]["file"] == "protocol_tpu/node/ethereum.py"
        # The AST leg alone judges only the AST-rule waivers.
        assert [s for s in stale if s["rule"] in DET_AST_RULES] == []

    def test_waiver_table_is_enumerated_not_patterned(self):
        assert [w.rule for w in DET_WAIVERS] == [
            "unseeded-rng", "hlo-nondeterministic-scatter",
        ]
        assert DET_WAIVERS[0].rule in DET_AST_RULES
        assert DET_WAIVERS[1].backend == "tpu-sparse"
        for w in DET_WAIVERS:
            assert w.reason  # every waiver carries its rationale

    def test_dead_det_waiver_is_error(self, monkeypatch):
        from protocol_tpu.analysis.concurrency.waivers import Waiver

        dead = Waiver(
            rule="hlo-nondeterministic-scatter", file="gone.py",
            symbol="ghost", reason="the scatter this waived was segmented",
        )
        monkeypatch.setattr(det_checker, "DET_WAIVERS", (dead,))
        live, waived, stale = det_checker._apply_waivers([])
        assert live == [] and waived == []
        assert [s["symbol"] for s in stale] == ["ghost"]
        findings, section = det_checker.run_determinism_pass(backends=[])
        assert [f.rule for f in findings] == ["stale-waiver"]
        assert findings[0].severity == "error"
        assert section["stale_waivers"][0]["symbol"] == "ghost"

    def test_subset_run_does_not_stale_ast_waivers(self):
        # backends=[] never evaluates the AST leg, so the real (AST-rule)
        # waiver must not be judged stale there.
        findings, section = det_checker.run_determinism_pass(backends=[])
        assert findings == []
        assert section["stale_waivers"] == []


# ---------------------------------------------------------------------------
# regressions: the divergence sources pass 13 found and fixed
# ---------------------------------------------------------------------------


class TestFoundAndFixed:
    def test_checkpoint_epochs_sorted_despite_creation_order(self, tmp_path):
        """checkpoint.epochs() fed prune order and boot-time latest()
        from raw glob order — inode-history-dependent.  Now sorted."""
        from protocol_tpu.node.checkpoint import CheckpointStore

        store = CheckpointStore(tmp_path / "ckpt")
        # Reverse creation order so a naive glob would plausibly return
        # [10, 2]; the contract is numeric order regardless of history.
        (tmp_path / "ckpt" / "epoch_10.npz").touch()
        (tmp_path / "ckpt" / "epoch_2.npz").touch()
        assert store.epochs() == [2, 10]

    def test_dedup_shard_key_is_the_stable_mix_not_builtin_hash(self):
        """The dedup shard key was ``hash((x, y)) % n`` — stable in
        today's CPython but an implementation detail.  Now a splitmix
        mix, pinned here against an independent reimplementation."""
        from protocol_tpu.ingest.dedup import ShardedDedupCache, _shard_index

        mask = (1 << 64) - 1

        def reference(sender, n):
            x, y = sender
            acc = (int(x) * 0x9E3779B97F4A7C15 + int(y)) & mask
            acc ^= acc >> 31
            acc = (acc * 0xBF58476D1CE4E5B9) & mask
            acc ^= acc >> 27
            return acc % n

        senders = [(0, 0), (1, 2), (2, 1), (2**255 - 19, 7), (17, 2**200)]
        for sender in senders:
            got = _shard_index(sender, 16)
            assert 0 <= got < 16
            assert got == reference(sender, 16), sender

        # The cache still routes consistently: a digest admitted once is
        # a duplicate on the second admit through the same shard.
        cache = ShardedDedupCache(n_shards=4)
        assert cache.admit((1, 2), b"d" * 32) is None
        assert cache.admit((1, 2), b"d" * 32) == "duplicate"

    def test_shard_key_spreads(self):
        from protocol_tpu.ingest.dedup import _shard_index

        hits = {_shard_index((i, i + 1), 16) for i in range(256)}
        assert len(hits) == 16  # all shards reachable


# ---------------------------------------------------------------------------
# the runtime probe (unit level — the full replay runs in CI)
# ---------------------------------------------------------------------------


def _run_record(**over) -> dict:
    base = {
        "return_codes": [0, 0],
        "workers_ok": [True, True],
        "wal_ack_digests": {"h000/acks-h000.jsonl": "a1", "h001/acks-h001.jsonl": "a2"},
        "manifest_digests": {"h000/manifest.json": "m1"},
        "epoch_digests": [{"epoch": 0, "residual": 0.5, "scores_sha256": "s0"}],
        "cross_host_bit_identity": True,
        "final_scores_sha256": ["fs", "fs"],
        "scores_npy_sha256": "npy",
        "proof": {
            "prover": "poseidon-commitment",
            "proof_bytes": 32,
            "proof_sha256": "pf",
            "verified": True,
        },
        "fleet": {"scrapes": 9, "sources": ["decoy-0"], "aggregate_sha256": "fl"},
    }
    base.update(over)
    return base


class TestCompareRuns:
    def test_identical_runs_pass_every_leg(self):
        verdict = divergence_probe.compare_runs(_run_record(), _run_record())
        assert verdict["ok"] is True
        assert set(verdict["legs"]) == {
            "return_codes", "workers_ok", "wal_ack_digests",
            "manifest_digests", "epoch_digests", "cross_host_bit_identity",
            "final_scores_sha256", "scores_npy_bytes", "proof_bytes",
            "fleet_merge_order_insensitive",
        }
        assert all(verdict["legs"].values())

    def test_wal_digest_drift_trips_exactly_that_leg(self):
        b = _run_record(
            wal_ack_digests={"h000/acks-h000.jsonl": "XX", "h001/acks-h001.jsonl": "a2"}
        )
        verdict = divergence_probe.compare_runs(_run_record(), b)
        assert verdict["ok"] is False
        bad = [k for k, v in verdict["legs"].items() if not v]
        assert bad == ["wal_ack_digests"]

    def test_proof_drift_trips_the_proof_leg(self):
        b = _run_record(proof={
            "prover": "poseidon-commitment", "proof_bytes": 32,
            "proof_sha256": "OTHER", "verified": True,
        })
        verdict = divergence_probe.compare_runs(_run_record(), b)
        assert verdict["legs"]["proof_bytes"] is False

    def test_cross_host_disagreement_trips_its_leg(self):
        b = _run_record(cross_host_bit_identity=False)
        verdict = divergence_probe.compare_runs(_run_record(), b)
        assert verdict["legs"]["cross_host_bit_identity"] is False

    def test_empty_digests_never_vacuously_pass(self):
        # A probe that collected nothing must not report bit-identity.
        a = _run_record(wal_ack_digests={})
        verdict = divergence_probe.compare_runs(a, _run_record(wal_ack_digests={}))
        assert verdict["legs"]["wal_ack_digests"] is False

    def test_unreadable_manifest_fails_the_manifest_leg(self):
        a = _run_record(manifest_digests={"h000/manifest.json": "unreadable"})
        verdict = divergence_probe.compare_runs(a, a)
        assert verdict["legs"]["manifest_digests"] is False


class TestProbePlumbing:
    def test_canonical_json_digest_is_key_order_insensitive(self):
        a = divergence_probe._canonical_json_digest({"a": 1, "b": [2, 3]})
        b = divergence_probe._canonical_json_digest({"b": [2, 3], "a": 1})
        assert a == b
        c = divergence_probe._canonical_json_digest({"a": 1, "b": [3, 2]})
        assert a != c  # list order is real data

    def test_decoy_write_order_does_not_change_the_merge(self, tmp_path):
        """The scrape-interleaving leg's foundation: two fleet dirs with
        the same snapshots written in different orders aggregate to the
        same canonical digest."""
        from protocol_tpu.obs.fleet import FleetAggregator, load_directory

        dir_a, dir_b = tmp_path / "a", tmp_path / "b"
        divergence_probe._write_decoys(dir_a, (0, 1, 2))
        divergence_probe._write_decoys(dir_b, (2, 0, 1))
        digests = []
        for d in (dir_a, dir_b):
            agg = FleetAggregator()
            load_directory(d, agg)
            digests.append(
                divergence_probe._canonical_json_digest(agg.snapshots())
            )
        assert digests[0] == digests[1]

    def test_schedules_really_perturb(self):
        a, b = divergence_probe.SCHEDULES
        assert a["hashseed"] != b["hashseed"]
        assert a["omp_threads"] != b["omp_threads"]
        assert a["reverse_launch"] != b["reverse_launch"]
        assert a["decoy_order"] != b["decoy_order"]
        assert a["scrape_interval"] != b["scrape_interval"]


# ---------------------------------------------------------------------------
# the committed round + perf sentinel plumbing
# ---------------------------------------------------------------------------


class TestCommittedRound:
    def test_det_r01_shows_bit_identity_under_perturbation(self):
        report = json.loads((REPO / "DET_r01.json").read_text())
        assert report["ok"] is True
        assert report["skipped"] is False
        assert report["seed_divergence_mode"] is False
        assert report["n_hosts"] == 2
        legs = report["comparison"]["legs"]
        assert len(legs) == 10 and all(legs.values()), legs
        entry = report["entries"][0]
        assert entry["unit"] == "seconds"
        assert entry["n_hosts"] == 2
        assert len(entry["per_schedule_seconds"]) == len(
            divergence_probe.SCHEDULES
        )

    def test_det_report_is_not_skipped_as_artifact(self):
        report = json.loads((REPO / "DET_r01.json").read_text())
        assert not perf_sentinel._is_non_bench_artifact(report)

    def test_det_entry_is_lower_is_better_multi_host(self):
        report = json.loads((REPO / "DET_r01.json").read_text())
        entry = report["entries"][0]
        assert perf_sentinel._lower_is_better("value", entry) is True
        assert perf_sentinel._series_key(entry, "value").endswith("[n_hosts=2]")

    def test_committed_det_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert "DET_r01.json" in report["history_files"]
        det_series = [k for k in report["series"] if "divergence probe" in k]
        assert det_series, sorted(report["series"])
        assert any(k.endswith("[n_hosts=2]") for k in det_series)


# ---------------------------------------------------------------------------
# pass wiring
# ---------------------------------------------------------------------------


class TestPassWiring:
    def test_det_trees_cover_the_state_planes(self):
        assert DET_TREES == ("node", "parallel", "ingest", "prover", "models")
        for tree in DET_TREES:
            assert (REPO / "protocol_tpu" / tree).is_dir()

    def test_empty_subset_run_still_writes_the_section_shape(self):
        # Narrow smoke of the section contract; the full HLO leg runs
        # in test_analysis.py's module-scoped real_report.
        findings, section = det_checker.run_determinism_pass(backends=[])
        assert findings == []
        assert section["backends"] == {}
        assert "waived" in section and "stale_waivers" in section
