"""graftlint pass 8 (protocol_tpu.analysis.comm) — the ISSUE 9
acceptance suite.

Covers: the comm pass runs clean on the real tree with every
registered backend covered; the sharded composites are judged at TWO
problem scales whose byte budgets provably cannot absorb an O(E)
collective; donation survives all the way into the compiled module's
``input_output_alias`` table for every donating backend (the PR 3
regression pin, now at the executable level); the jaxpr-psum vs
lowered-all-reduce cross-check holds; the HLO walker parses the text
format correctly on hostile snippets; and dead comm/concurrency
waivers fail the gate (``stale-waiver``).

The seeded comm fixtures themselves are exercised by the parametrized
``tests/test_analysis.py::TestViolationFixtures`` (rule + file:line
against the ``# VIOLATION:`` markers) — this file pins their
registration and the CLI plumbing.
"""

import json

import pytest

from protocol_tpu.analysis import COMM_INVARIANTS, NON_JAX_BACKENDS
from protocol_tpu.analysis.__main__ import main as analysis_main
from protocol_tpu.analysis.comm import run_comm_pass
from protocol_tpu.analysis.comm.hlo_walk import parse_module, shape_bytes
from protocol_tpu.analysis.fixtures import FIXTURES
from protocol_tpu.trust.backend import registered_backends

#: Parameter index of the donated ``t0`` in each backend's converge
#: entry point — the regression pin for the PR 3 donation work, now
#: asserted against the compiled module, not the jaxpr.
DONATED_T0_PARAM = {
    "tpu-sparse": 3,
    "tpu-csr": 3,
    "tpu-windowed": 6,
    "tpu-sharded:tpu-csr": 3,
    "tpu-sharded:tpu-windowed": 6,
}


@pytest.fixture(scope="module")
def comm_report():
    """One full pass-8 run (module-scoped: compiles all six backends,
    the sharded pair at two scales)."""
    findings, section = run_comm_pass()
    return findings, section


class TestRealTree:
    def test_comm_pass_clean(self, comm_report):
        findings, _ = comm_report
        assert [f.render() for f in findings] == []

    def test_every_registered_backend_covered(self, comm_report):
        _, section = comm_report
        for name in registered_backends():
            assert name in section["backends"], name
            status = section["backends"][name]["status"]
            expected = "skipped" if name in NON_JAX_BACKENDS else "checked"
            assert status == expected, (name, status)

    def test_sharded_composites_checked_at_two_scales(self, comm_report):
        _, section = comm_report
        for name in ("tpu-sharded:tpu-csr", "tpu-sharded:tpu-windowed"):
            scales = section["backends"][name]["scales"]
            assert len(scales) == 2, name
            ns = [s["dims"]["n"] for s in scales]
            es = [s["dims"]["edges"] for s in scales]
            assert ns[1] == 2 * ns[0], ns  # N doubles...
            assert es[1] > 3.5 * es[0], es  # ...while E quadruples

    def test_exactly_one_psum_lowered_per_sharded_step(self, comm_report):
        """The pass-1 promise (psum_count=1) holds at the executable:
        one all-reduce, full replica group, inside the while body."""
        _, section = comm_report
        for name in ("tpu-sharded:tpu-csr", "tpu-sharded:tpu-windowed"):
            for scale in section["backends"][name]["scales"]:
                assert scale["jaxpr_psums"] == 1, (name, scale["scale"])
                assert scale["lowered_all_reduces"] == 1
                (op,) = [
                    c for c in scale["collectives"] if c["per_iteration"]
                ]
                assert op["kind"] == "all-reduce"
                assert op["replica_groups"] == "{{0,1,2,3,4,5,6,7}}"

    def test_single_device_backends_have_no_wire(self, comm_report):
        _, section = comm_report
        for name in ("tpu-dense", "tpu-sparse", "tpu-csr", "tpu-windowed"):
            for scale in section["backends"][name]["scales"]:
                assert scale["collectives"] == [], name
                assert scale["host_round_trips"] == [], name
                assert scale["bytes_per_iter"] == 0

    def test_byte_budget_is_o_boundary_plus_n_never_o_e(self, comm_report):
        """The ISSUE 9 acceptance: at BOTH scales, measured collective
        bytes fit the linear budget AND an O(E) collective (4 bytes/f32
        per edge) would NOT fit — the budget cannot absorb edge-scaled
        traffic at either scale, so no constant-padding can hide an
        O(E) lowering.  Measured volume itself must track N linearly
        across the scales."""
        _, section = comm_report
        for name in ("tpu-sharded:tpu-csr", "tpu-sharded:tpu-windowed"):
            scales = section["backends"][name]["scales"]
            for s in scales:
                assert s["bytes_per_iter"] <= s["budget_bytes"], (name, s)
                o_e_volume = 4 * s["dims"]["edges"]
                assert o_e_volume > s["budget_bytes"], (
                    f"{name} at {s['scale']}: the byte budget "
                    f"({s['budget_bytes']:.0f}) could absorb an O(E) "
                    f"all-reduce ({o_e_volume}) — tighten bytes_n/const"
                )
            ratio = scales[1]["bytes_per_iter"] / scales[0]["bytes_per_iter"]
            n_ratio = scales[1]["dims"]["n"] / scales[0]["dims"]["n"]
            assert ratio == pytest.approx(n_ratio), (name, ratio)

    def test_donation_survives_lowering(self, comm_report):
        """t0's donation materializes in the compiled module's
        input_output_alias for converge_sparse/csr/windowed and both
        sharded composites (the sharded runners donate since ISSUE 9)."""
        _, section = comm_report
        for name, param in DONATED_T0_PARAM.items():
            for scale in section["backends"][name]["scales"]:
                aliased = set(scale["input_output_alias"].values())
                assert param in aliased, (
                    f"{name}: t0 (param {param}) not in alias table "
                    f"{scale['input_output_alias']} at {scale['scale']}"
                )

    def test_budget_table_matches_registry(self):
        from protocol_tpu.analysis.zk_lowering import ensure_budgets

        zk_names = set(ensure_budgets())
        declared = set(COMM_INVARIANTS)
        registered = {
            n for n in registered_backends() if n not in NON_JAX_BACKENDS
        }
        assert declared == registered | zk_names

    def test_no_stale_comm_waivers(self, comm_report):
        _, section = comm_report
        assert section["stale_waivers"] == []


class TestRegistryGate:
    def test_undeclared_comm_budget_is_error(self):
        findings, section = run_comm_pass(backends=["tpu-quantum"])
        assert section["backends"]["tpu-quantum"]["status"] == "undeclared"
        assert [(f.rule, f.severity) for f in findings] == [
            ("undeclared-comm-budget", "error")
        ]


class TestFixturePlumbing:
    def test_comm_fixtures_registered(self):
        comm = {n for n, f in FIXTURES.items() if f.kind == "comm"}
        assert comm == {
            "surprise-all-gather",
            "comm-bytes-over-budget",
            "host-round-trip",
            "alias-dropped",
            "psum-lowering-mismatch",
        }

    def test_cli_exits_nonzero_on_comm_fixture(self, tmp_path):
        out = tmp_path / "fixture.json"
        rc = analysis_main(["--fixture", "alias-dropped", "--output", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["findings"][0]["rule"] == "alias-dropped"
        assert report["findings"][0]["pass"] == "comm"


class TestHloWalk:
    """Parser units on hostile snippets (no compile)."""

    def test_shape_bytes(self):
        assert shape_bytes("f32[512]{0}") == 2048
        assert shape_bytes("f32[512,128]{1,0}") == 512 * 128 * 4
        assert shape_bytes("pred[1024]{0}") == 1024
        assert shape_bytes("f32[]") == 4
        assert shape_bytes("(f32[8]{0}, s32[])") == 36
        assert shape_bytes("token[]") == 0

    def test_collective_parse_with_metadata(self):
        text = (
            "HloModule jit_run, is_scheduled=true\n"
            "%all-reduce.4 = f32[512]{0} all-reduce(f32[512]{0} %call.2), "
            "channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, "
            "use_global_device_ids=true, to_apply=%region_1.205, "
            'metadata={op_name="jit(run)/jit(main)/while/body/'
            'shard_map/psum_invariant" stack_frame_id=2}\n'
            "\nFileNames\n"
            '1 "/repo/parallel/sharded.py"\n'
            "\nFileLocations\n"
            "1 {file_name_id=1 function_name_id=1 line=120 end_line=120}\n"
            "2 {file_name_id=1 function_name_id=1 line=171 end_line=171}\n"
            "\nStackFrames\n"
            "1 {file_location_id=1 parent_frame_id=1}\n"
            "2 {file_location_id=2 parent_frame_id=1}\n"
        )
        mod = parse_module(text)
        (op,) = mod.collectives
        assert op.kind == "all-reduce"
        assert op.bytes == 2048
        assert op.per_iteration
        assert op.replica_groups == "{{0,1,2,3,4,5,6,7}}"
        assert op.file == "/repo/parallel/sharded.py"
        assert op.line == 171

    def test_all_gather_bytes_use_output_shape(self):
        text = (
            "HloModule m\n"
            "%all-gather.1 = f32[16]{0} all-gather(f32[2]{0} %param), "
            "channel_id=1, replica_groups={{0,1}}, dimensions={0}\n"
        )
        (op,) = parse_module(text).collectives
        assert op.bytes == 64  # result f32[16], not operand f32[2]
        assert not op.per_iteration  # no while in (absent) op_name

    def test_async_start_done_counted_once(self):
        text = (
            "HloModule m\n"
            "%ar-start = f32[8]{0} all-reduce-start(f32[8]{0} %x), channel_id=1\n"
            "%ar-done = f32[8]{0} all-reduce-done(f32[8]{0} %ar-start)\n"
        )
        mod = parse_module(text)
        assert mod.kind_counts() == {"all-reduce": 1}

    def test_host_callback_flagged_device_custom_call_ignored(self):
        text = (
            "HloModule m\n"
            "%cc.1 = (f32[]) custom-call(s64[] %c, f32[8]{0} %x), "
            'custom_call_target="xla_python_cpu_callback", '
            "api_version=API_VERSION_STATUS_RETURNING\n"
            "%cc.2 = f32[8]{0} custom-call(f32[8]{0} %x), "
            'custom_call_target="TopK"\n'
        )
        mod = parse_module(text)
        assert [h.target for h in mod.host_calls] == ["xla_python_cpu_callback"]

    def test_alias_table_multi_pair(self):
        text = (
            "HloModule jit_f, is_scheduled=true, input_output_alias="
            "{ {0}: (3, {}, may-alias), {1}: (4, {}) }, "
            "entry_computation_layout={(f32[4]{0})->f32[4]{0}}\n"
        )
        mod = parse_module(text)
        assert mod.aliases == {0: 3, 1: 4}
        assert mod.aliased_params() == {3, 4}

    def test_no_alias_table(self):
        assert parse_module("HloModule jit_f, is_scheduled=true\n").aliases == {}


class TestStaleWaivers:
    """A dead waiver fails the gate in every run that evaluates its
    table — concurrency (pass 7) and comm (pass 8) alike."""

    def test_dead_concurrency_waiver_is_error(self):
        from protocol_tpu.analysis.concurrency.checker import (
            analyze_models,
            build_program_model,
        )
        from protocol_tpu.analysis.concurrency.waivers import Waiver

        dead = Waiver(
            rule="unguarded-rmw", file="gone.py", symbol="Ghost.attr",
            reason="the bug this waived was fixed",
        )
        findings, section, _ = analyze_models(
            build_program_model({"protocol_tpu/node/_x.py": "x = 1\n"}),
            (dead,),
        )
        assert [f.rule for f in findings] == ["stale-waiver"]
        assert all(f.severity == "error" for f in findings)
        assert section["stale_waivers"] == [
            {"symbol": "Ghost.attr", "rule": "unguarded-rmw",
             "reason": "the bug this waived was fixed"}
        ]

    def test_dead_comm_waiver_is_error(self, monkeypatch):
        from protocol_tpu.analysis.comm import checker as comm_checker
        from protocol_tpu.analysis.concurrency.waivers import Waiver

        dead = Waiver(
            rule="comm-bytes-budget", file="gone.py", symbol="ghost",
            reason="fixed",
        )
        monkeypatch.setattr(comm_checker, "COMM_WAIVERS", (dead,))
        live, waived, stale = comm_checker._apply_waivers([])
        assert live == [] and waived == []
        assert [s["symbol"] for s in stale] == ["ghost"]
        # and the pass turns it into an error finding:
        findings, section = comm_checker.run_comm_pass(backends=[])
        assert [f.rule for f in findings] == ["stale-waiver"]
        assert findings[0].severity == "error"


class TestManagerCommWarning:
    """Config-time pin check: a configured sharded backend without a
    COMM_INVARIANTS entry warns at Manager construction (mirror of the
    per-converge unpinned-kernel-budget warning)."""

    def _manager(self, backend):
        from protocol_tpu.node.manager import Manager, ManagerConfig

        return Manager(ManagerConfig(backend=backend, prover="commitment"))

    def test_pinned_sharded_backend_is_quiet(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING, logger="protocol_tpu.node.manager"):
            self._manager("tpu-sharded:tpu-windowed")
        assert "COMM_INVARIANTS" not in caplog.text

    def test_unpinned_sharded_backend_warns(self, caplog, monkeypatch):
        import logging

        from protocol_tpu.analysis.budget import COMM_INVARIANTS as table
        from protocol_tpu.parallel import sharded  # noqa: F401  (declares)

        monkeypatch.delitem(table, "tpu-sharded:tpu-csr")
        with caplog.at_level(logging.WARNING, logger="protocol_tpu.node.manager"):
            self._manager("tpu-sharded")
        assert "COMM_INVARIANTS" in caplog.text

    def test_single_device_backend_never_comm_warns(self, caplog):
        import logging

        with caplog.at_level(logging.WARNING, logger="protocol_tpu.node.manager"):
            self._manager("tpu-csr")
        assert "COMM_INVARIANTS" not in caplog.text
