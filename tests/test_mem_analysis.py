"""graftlint pass 12 (protocol_tpu.analysis.memory) — the ISSUE 15
acceptance suite.

Covers: the memory pass runs clean on the real tree with every
registered backend covered; the sharded composites are judged at TWO
problem scales whose committed budgets provably cannot absorb a
4 B/edge live temporary at either scale (the COMM_INVARIANTS pinning
trick applied to liveness); donation materializes as buffer aliasing
for every donating backend; per-shard resident budgets cannot absorb
a replicated edge operand; the conservative live-range walker and the
buffer-assignment view agree in shape on hostile snippets; dead
memory waivers fail the gate (``stale-waiver``); the pass-12 AST
rules fire/stay-quiet on minimal snippets and the fixed Manager
caches are ring-bounded (the first analyzer run's real findings).

The seeded pass-12 fixtures themselves are exercised by the
parametrized ``tests/test_analysis.py::TestViolationFixtures`` (rule +
file:line against the ``# VIOLATION:`` markers) — this file pins their
registration and the CLI plumbing.
"""

import json

import pytest

from protocol_tpu.analysis import MEM_INVARIANTS, NON_JAX_BACKENDS
from protocol_tpu.analysis.__main__ import main as analysis_main
from protocol_tpu.analysis.ast_rules import run_mem_ast_pass, scan_source
from protocol_tpu.analysis.fixtures import FIXTURES
from protocol_tpu.analysis.memory import run_memory_pass
from protocol_tpu.analysis.memory.liveness import (
    largest_temp_site,
    live_range_peak,
    measured_view,
)
from protocol_tpu.trust.backend import registered_backends

#: Backends whose converge donates its f32[N] seed (the pass-12
#: donation-reduces-peak contract; dense re-feeds its own carry).
DONATING_BACKENDS = (
    "tpu-sparse",
    "tpu-csr",
    "tpu-windowed",
    "tpu-sharded:tpu-csr",
    "tpu-sharded:tpu-windowed",
)


@pytest.fixture(scope="module")
def mem_report():
    """One full pass-12 run (module-scoped; the compiled cases are
    shared with pass 8 through the lowering memo)."""
    findings, section = run_memory_pass()
    return findings, section


class TestRealTree:
    def test_memory_pass_clean(self, mem_report):
        findings, _ = mem_report
        assert [f.render() for f in findings] == []

    def test_every_registered_backend_covered(self, mem_report):
        _, section = mem_report
        for name in registered_backends():
            assert name in section["backends"], name
            status = section["backends"][name]["status"]
            expected = "skipped" if name in NON_JAX_BACKENDS else "checked"
            assert status == expected, (name, status)

    def test_sharded_composites_checked_at_two_scales(self, mem_report):
        _, section = mem_report
        for name in ("tpu-sharded:tpu-csr", "tpu-sharded:tpu-windowed"):
            scales = section["backends"][name]["scales"]
            assert len(scales) == 2, name
            ns = [s["dims"]["n"] for s in scales]
            es = [s["dims"]["edges"] for s in scales]
            assert ns[1] == 2 * ns[0], ns  # N doubles...
            assert es[1] > 3.5 * es[0], es  # ...while E quadruples

    def test_budgets_cannot_absorb_4_bytes_per_edge(self, mem_report):
        """The ISSUE 15 acceptance: at EVERY compiled scale of EVERY
        backend, measured resident and transient fit their allowances
        AND the slack in each component is below a 4 B/edge live
        buffer — so an extra edge-sized temporary (or a replicated
        edge operand) trips the gate no matter which component it
        lands in, and no padded constant can hide it."""
        _, section = mem_report
        for name, rec in section["backends"].items():
            if rec.get("status") != "checked":
                continue
            for s in rec["scales"]:
                o_e = 4 * s["dims"]["edges"]
                m = s["measured"]
                for comp, budget_key in (
                    ("resident_bytes", "budget_resident_bytes"),
                    ("transient_bytes", "budget_transient_bytes"),
                ):
                    assert m[comp] <= s[budget_key], (name, s["scale"], comp)
                    slack = s[budget_key] - m[comp]
                    assert slack < o_e, (
                        f"{name} at {s['scale']}: {comp} slack "
                        f"{slack:.0f} could absorb a 4 B/edge buffer "
                        f"({o_e}) — tighten the budget"
                    )

    def test_per_shard_resident_cannot_absorb_replication(self, mem_report):
        """The shard-replicated-edges contract: the per-shard resident
        allowance is small enough that holding the FULL edge slice on
        one device (instead of E/n_shards) busts it."""
        _, section = mem_report
        for name in ("tpu-sharded:tpu-csr",):
            for s in section["backends"][name]["scales"]:
                e_bytes = 8 * s["dims"]["edges"]  # src + w, full graph
                replicated = (
                    s["measured"]["resident_bytes"]
                    + e_bytes * (s["dims"]["n_shards"] - 1) / s["dims"]["n_shards"]
                )
                assert replicated > s["budget_resident_bytes"], (name, s)

    def test_per_shard_transient_tracks_n_not_e(self, mem_report):
        """Across the 4x edge growth the sharded transient must grow
        by no more than the budget's N/n_segments-linear coefficients
        (the replicated score vectors and per-shard segment tables) —
        the measured fact the no-edge-coefficient model rests on: at
        this step E quadrupled while the growth fits tn*dN + ts*dS."""
        _, section = mem_report
        for name in ("tpu-sharded:tpu-csr", "tpu-sharded:tpu-windowed"):
            rec = section["backends"][name]
            scales = rec["scales"]
            t1 = scales[0]["measured"]["transient_bytes"]
            t2 = scales[1]["measured"]["transient_bytes"]
            dn = scales[1]["dims"]["n"] - scales[0]["dims"]["n"]
            ds = scales[1]["dims"].get("n_segments", 0) - scales[0][
                "dims"
            ].get("n_segments", 0)
            linear_growth = (
                rec["budget"]["transient_n"] * dn
                + rec["budget"]["transient_segments"] * ds
            )
            assert t2 - t1 <= linear_growth, (name, t1, t2, linear_growth)
            # ...whereas a per-shard 4 B/edge transient would have had
            # to grow with the edge slice on top of that.
            de = scales[1]["dims"]["edges"] - scales[0]["dims"]["edges"]
            per_shard_o_e = 4 * de / scales[0]["dims"]["n_shards"]
            assert t2 - t1 < linear_growth + per_shard_o_e

    def test_donation_reduces_peak(self, mem_report):
        """Every donating backend's buffer assignment aliases at least
        the 4*N seed bytes — the executable-level half of the PR 3/9
        donation pins."""
        _, section = mem_report
        for name in DONATING_BACKENDS:
            for s in section["backends"][name]["scales"]:
                m = s["measured"]
                assert m.get("alias_bytes", 0) >= 4 * s["dims"]["n"], (name, s)

    def test_no_host_transfers_in_any_converge(self, mem_report):
        _, section = mem_report
        for name, rec in section["backends"].items():
            if rec.get("status") != "checked":
                continue
            for s in rec["scales"]:
                assert s["host_transfers"] == [], (name, s["scale"])

    def test_budget_table_matches_registry(self):
        from protocol_tpu.analysis.zk_lowering import ensure_budgets

        zk_names = set(ensure_budgets())
        declared = set(MEM_INVARIANTS)
        registered = {
            n for n in registered_backends() if n not in NON_JAX_BACKENDS
        }
        assert declared == registered | zk_names

    def test_waiver_table_live_not_stale(self, mem_report):
        """The hash-memo waiver is live (the rule really fires on
        Manager._hash_cache, which is bounded by the peer set) and no
        waiver is stale."""
        _, section = mem_report
        assert section["stale_waivers"] == []
        assert [w["symbol"] for w in section["waived"]] == [
            "Manager._hash_cache"
        ]

    def test_buffer_assignment_source_used(self, mem_report):
        """On this runtime the primary view is the compiler's buffer
        assignment, not the conservative fallback."""
        _, section = mem_report
        for name, rec in section["backends"].items():
            if rec.get("status") != "checked":
                continue
            for s in rec["scales"]:
                assert s["source"] == "buffer-assignment", name


class TestRegistryGate:
    def test_undeclared_mem_budget_is_error(self):
        findings, section = run_memory_pass(backends=["tpu-quantum"])
        assert section["backends"]["tpu-quantum"]["status"] == "undeclared"
        assert [(f.rule, f.severity) for f in findings] == [
            ("undeclared-mem-budget", "error")
        ]


class TestFixturePlumbing:
    def test_mem_fixtures_registered(self):
        mem = {n for n, f in FIXTURES.items() if f.kind in ("mem", "mem-ast")}
        assert mem == {
            "o-e-live-temporary",
            "donation-peak-doubled",
            "shard-replicated-edges",
            "host-staging-over-cap",
            "host-materialization-of-edges",
            "unbounded-cache-growth",
        }

    def test_cli_exits_nonzero_on_mem_fixture(self, tmp_path):
        out = tmp_path / "fixture.json"
        rc = analysis_main(
            ["--fixture", "donation-peak-doubled", "--output", str(out)]
        )
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["findings"][0]["rule"] == "donation-peak-doubled"
        assert report["findings"][0]["pass"] == "memory"


class TestLivenessWalk:
    """Walker units on hostile snippets (no compile)."""

    MODULE = (
        "HloModule jit_f, is_scheduled=true\n"
        "\n"
        "%fused_computation (p: f32[512]) -> f32[512] {\n"
        "  %p = f32[512]{0} parameter(0)\n"
        "  ROOT %m = f32[512]{0} multiply(f32[512]{0} %p, f32[512]{0} %p)\n"
        "}\n"
        "\n"
        "ENTRY %main (a: f32[1024], b: f32[1024]) -> f32[] {\n"
        "  %a = f32[1024]{0} parameter(0)\n"
        "  %b = f32[1024]{0} parameter(1)\n"
        '  %big = f32[4096]{0} broadcast(f32[1024]{0} %a), metadata={op_name="jit(f)/bc" stack_frame_id=1}\n'
        "  %s = f32[] reduce(f32[4096]{0} %big, f32[] %b)\n"
        "  ROOT %r = f32[] add(f32[] %s, f32[] %s)\n"
        "}\n"
        "\nFileNames\n"
        '1 "/repo/x.py"\n'
        "\nFileLocations\n"
        "1 {file_name_id=1 function_name_id=1 line=7 end_line=7}\n"
        "\nStackFrames\n"
        "1 {file_location_id=1 parent_frame_id=1}\n"
    )

    def test_live_range_peak_counts_temps_not_params(self):
        peak = live_range_peak(self.MODULE)
        # entry: big (16384) + s (4) live together; fused adds m (2048).
        assert peak >= 16384 + 4
        assert peak < 16384 + 4096 + 4096  # parameters excluded

    def test_largest_temp_site_with_metadata(self):
        site = largest_temp_site(self.MODULE)
        assert site is not None
        assert site.bytes == 16384
        assert site.op == "broadcast"
        assert site.file == "/repo/x.py"
        assert site.line == 7

    def test_measured_view_prefers_buffer_assignment(self):
        from protocol_tpu.analysis.comm.lowering import CommCase

        case = CommCase(
            backend="x", dims={}, module_text=self.MODULE, arg_names=(),
            mem={
                "argument_bytes": 100,
                "output_bytes": 20,
                "alias_bytes": 10,
                "temp_bytes": 50,
            },
        )
        view, source = measured_view(case)
        assert source == "buffer-assignment"
        assert view["resident_bytes"] == 100
        assert view["transient_bytes"] == 60
        assert view["peak_bytes"] == 160

    def test_measured_view_falls_back_to_live_range_walk(self):
        from protocol_tpu.analysis.comm.lowering import CommCase

        case = CommCase(
            backend="x", dims={}, module_text=self.MODULE, arg_names=(),
            mem=None,
        )
        view, source = measured_view(case)
        assert source == "live-range-walk"
        # resident estimate = the largest computation's parameters.
        assert view["resident_bytes"] == 8192
        assert view["transient_bytes"] == live_range_peak(self.MODULE)


class TestStaleWaivers:
    """A dead memory waiver fails the gate in every run that evaluates
    the table — the cross-table staleness parity of ISSUE 15."""

    def test_dead_mem_waiver_is_error(self, monkeypatch):
        from protocol_tpu.analysis.concurrency.waivers import Waiver
        from protocol_tpu.analysis.memory import checker as mem_checker

        dead = Waiver(
            rule="o-e-live-temporary", file="gone.py", symbol="ghost",
            reason="the leak this waived was fixed",
        )
        monkeypatch.setattr(mem_checker, "MEM_WAIVERS", (dead,))
        live, waived, stale = mem_checker._apply_waivers([])
        assert live == [] and waived == []
        assert [s["symbol"] for s in stale] == ["ghost"]
        findings, section = mem_checker.run_memory_pass(backends=[])
        assert [f.rule for f in findings] == ["stale-waiver"]
        assert findings[0].severity == "error"

    def test_all_four_tables_enforce_staleness(self):
        """Concurrency, comm, memory, and determinism waiver tables all
        turn a dead entry into an error — no table rots silently."""
        from protocol_tpu.analysis.comm import checker as comm_checker
        from protocol_tpu.analysis.concurrency.checker import (
            analyze_models,
            build_program_model,
        )
        from protocol_tpu.analysis.concurrency.waivers import Waiver
        from protocol_tpu.analysis.determinism import checker as det_checker
        from protocol_tpu.analysis.memory import checker as mem_checker

        dead = Waiver(rule="x", file="gone.py", symbol="ghost", reason="r")
        conc, _, _ = analyze_models(
            build_program_model({"protocol_tpu/node/_x.py": "x = 1\n"}),
            (dead,),
        )
        assert [f.rule for f in conc] == ["stale-waiver"]
        for checker in (comm_checker, mem_checker, det_checker):
            live, _, stale = checker._apply_waivers([])
            # the committed tables have no dead entries...
            assert [s for s in stale if s["symbol"] == "ghost"] == []


def _scan(rel: str, code: str):
    return scan_source(code, rel, mem_rules=True)


class TestHostMaterializationRule:
    """Pass 12: no edge-scale host materialization on the epoch loop's
    critical path (file-scoped like passes 6/9)."""

    def test_np_asarray_on_edge_array_fires(self):
        findings = _scan(
            "protocol_tpu/node/pipeline.py",
            "import numpy as np\n"
            "def device_stage(plan):\n"
            "    return np.asarray(plan.seg_dst)\n",
        )
        assert [f.rule for f in findings] == ["host-materialization-of-edges"]
        assert findings[0].line == 3

    def test_device_get_and_tolist_fire(self):
        findings = _scan(
            "protocol_tpu/node/epoch.py",
            "import jax\n"
            "def tick(graph):\n"
            "    a = jax.device_get(graph.src)\n"
            "    b = graph.edge_weights.tolist()\n"
            "    return a, b\n",
        )
        assert [f.rule for f in findings] == [
            "host-materialization-of-edges"
        ] * 2
        assert [f.line for f in findings] == [3, 4]

    def test_n_sized_materialization_is_fine(self):
        """Scores and peer columns are O(N) — the rule only fences
        edge-scale names."""
        findings = _scan(
            "protocol_tpu/node/pipeline.py",
            "import numpy as np\n"
            "def device_stage(result):\n"
            "    return np.asarray(result.scores)\n",
        )
        assert findings == []

    def test_same_code_outside_epoch_loop_files_is_fine(self):
        """Plan build (manager.py) materializes edge arrays on the
        host legitimately — the rule is epoch-loop-file-scoped."""
        findings = _scan(
            "protocol_tpu/node/manager.py",
            "import numpy as np\n"
            "def build_graph(src, dst, w):\n"
            "    return np.asarray(src), np.asarray(dst), np.asarray(w)\n",
        )
        assert [
            f for f in findings if f.rule == "host-materialization-of-edges"
        ] == []

    def test_rule_off_without_mem_pass(self):
        findings = scan_source(
            "import numpy as np\n"
            "def device_stage(plan):\n"
            "    return np.asarray(plan.seg_dst)\n",
            "protocol_tpu/node/pipeline.py",
        )
        assert findings == []


class TestUnboundedCacheGrowthRule:
    """Pass 12: cache-named attributes of long-lived node classes must
    evict, bound, or rotate."""

    GROWING = (
        "class Server:\n"
        "    def __init__(self):\n"
        "        self._proof_cache = {}\n"
        "    def put(self, epoch, proof):\n"
        "        self._proof_cache[epoch] = proof\n"
    )

    def test_growing_cache_fires(self):
        findings = _scan("protocol_tpu/node/server2.py", self.GROWING)
        assert [f.rule for f in findings] == ["unbounded-cache-growth"]
        assert findings[0].line == 3
        assert "Server._proof_cache" in findings[0].message

    def test_pop_eviction_quiets(self):
        findings = _scan(
            "protocol_tpu/node/server2.py",
            self.GROWING
            + "    def evict(self):\n"
            + "        while len(self._proof_cache) > 4:\n"
            + "            self._proof_cache.pop(min(self._proof_cache))\n",
        )
        assert findings == []

    def test_del_eviction_quiets(self):
        findings = _scan(
            "protocol_tpu/node/server2.py",
            self.GROWING
            + "    def evict(self, k):\n"
            + "        del self._proof_cache[k]\n",
        )
        assert findings == []

    def test_generation_rotation_quiets(self):
        """The dedup-cache shape: reassignment outside __init__ is a
        rotation, not growth."""
        findings = _scan(
            "protocol_tpu/ingest/dedup2.py",
            self.GROWING
            + "    def advance_epoch(self):\n"
            + "        self._proof_cache = {}\n",
        )
        assert findings == []

    def test_non_cache_names_are_exempt(self):
        findings = _scan(
            "protocol_tpu/node/state.py",
            "class Store:\n"
            "    def __init__(self):\n"
            "        self.attestations = {}\n"
            "    def put(self, k, v):\n"
            "        self.attestations[k] = v\n",
        )
        assert findings == []

    def test_read_only_cache_is_exempt(self):
        """A cache filled once in __init__ and only read never grows."""
        findings = _scan(
            "protocol_tpu/node/memo.py",
            "class Memo:\n"
            "    def __init__(self, pairs):\n"
            "        self._hash_cache = {}\n"
            "    def get(self, k):\n"
            "        return self._hash_cache.get(k)\n",
        )
        assert findings == []

    def test_outside_long_lived_trees_is_exempt(self):
        findings = _scan("protocol_tpu/obs/cacheish.py", self.GROWING)
        # scan_source arms the rules, but run_mem_ast_pass only walks
        # node/ + ingest/; at the pass level obs/ is out of scope.
        # The visitor itself is tree-agnostic, so this still fires —
        # scope is enforced by the pass walker:
        from protocol_tpu.analysis.ast_rules import MEM_AST_TREES

        assert MEM_AST_TREES == ("node", "ingest")
        assert [f.rule for f in findings] == ["unbounded-cache-growth"]

    def test_real_tree_only_waived_finding(self):
        """After the cached_proofs/cached_results fixes, the only
        pass-12 AST finding on the real tree is the (waived) pk-hash
        memo — the clean-real-tree half of the acceptance."""
        findings, n_files = run_mem_ast_pass()
        assert n_files > 15
        assert [(f.rule, "Manager._hash_cache" in f.message) for f in findings] == [
            ("unbounded-cache-growth", True)
        ]


class TestManagerCacheBounds:
    """Regression tests for the first analyzer run's real findings:
    Manager.cached_results held a full f32[N] fixed point per epoch
    forever (4 MB/epoch at 1M peers), Manager.cached_proofs a SNARK
    per epoch forever.  Both now ring-evict."""

    def _manager(self):
        from protocol_tpu.node.manager import Manager, ManagerConfig

        m = Manager(ManagerConfig(prover="commitment", backend="tpu-sparse"))
        m.generate_initial_attestations()
        return m

    def test_cached_results_ring_bounded(self, monkeypatch):
        import protocol_tpu.node.manager as manager_mod
        from protocol_tpu.node.epoch import Epoch

        monkeypatch.setattr(manager_mod, "RESULT_CACHE_EPOCHS", 2)
        m = self._manager()
        for e in range(4):
            m.converge_epoch(Epoch(e), alpha=0.1)
        assert len(m.cached_results) == 2
        assert sorted(e.number for e in m.cached_results) == [2, 3]

    def test_cached_proofs_ring_bounded(self, monkeypatch):
        import protocol_tpu.node.manager as manager_mod
        from protocol_tpu.node.epoch import Epoch
        from protocol_tpu.zk.proof import Proof

        monkeypatch.setattr(manager_mod, "PROOF_CACHE_EPOCHS", 3)
        m = self._manager()
        for e in range(6):
            m.cache_proof(Epoch(e), Proof(pub_ins=[e], proof=b"p%d" % e))
        assert sorted(e.number for e in m.cached_proofs) == [3, 4, 5]
        # latest_proof still serves the newest surviving epoch.
        assert m.cached_proofs[max(m.cached_proofs, key=lambda e: e.number)]

    def test_install_proof_routes_through_ring(self, monkeypatch):
        import protocol_tpu.node.manager as manager_mod

        monkeypatch.setattr(manager_mod, "PROOF_CACHE_EPOCHS", 1)
        m = self._manager()
        m.install_proof(1, [1], b"a")
        m.install_proof(2, [2], b"b")
        assert [e.number for e in m.cached_proofs] == [2]
