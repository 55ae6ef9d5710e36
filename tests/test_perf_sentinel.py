"""Perf-regression sentinel (ISSUE 6): recorded BENCH_r*.json
trajectories are *read* — the sentinel gates on per-metric trend deltas
against the best recorded value."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import perf_sentinel

REPO = Path(__file__).resolve().parent.parent


def _record(n: int, value: float, metric: str = "headline seconds") -> dict:
    return {
        "n": n,
        "parsed": {"metric": metric, "value": value, "unit": "seconds"},
    }


def _write_rounds(path: Path, values: list[float], **kw) -> None:
    for i, v in enumerate(values, start=1):
        (path / f"BENCH_r{i:02d}.json").write_text(json.dumps(_record(i, v, **kw)))


#: The driver's bench-round shape: the command, its exit code, the end
#: of its output, and the headline line parsed out of it.
HEADLINE = "1M-peer/50M-edge global-trust convergence wall-clock (40 power iters)"


def _bench_round(n: int, value: float) -> dict:
    line = {"metric": HEADLINE, "value": value, "unit": "seconds"}
    return {
        "n": n,
        "cmd": "python bench.py",
        "rc": 0,
        "tail": json.dumps(line) + "\n",
        "parsed": line,
    }


def _write_bench_series(path: Path, values: list[float]) -> None:
    for i, v in enumerate(values, start=1):
        (path / f"BENCH_r{i:02d}.json").write_text(json.dumps(_bench_round(i, v)))


class TestBenchSeries:
    def test_improving_bench_series_passes(self, tmp_path):
        """Acceptance: exit 0 on a five-round headline series that
        only improves."""
        _write_bench_series(tmp_path, [42.4, 18.2, 17.9, 17.8, 17.76])
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert report["ok"] is True
        assert any("BENCH_r01.json" in f for f in report["history_files"])
        # The headline series was parsed across all five rounds.
        headline = [k for k in report["series"] if "convergence wall-clock" in k]
        assert headline and report["series"][headline[0]]["rounds"] == 5

    def test_sentinel_passes_on_the_tree(self, tmp_path):
        """The default-glob run over the committed records exits 0."""
        out = tmp_path / "SENTINEL.json"
        assert perf_sentinel.main(["--history", str(REPO), "--out", str(out)]) == 0
        assert json.loads(out.read_text())["ok"] is True


class TestIngestSeries:
    def test_ingest_storm_rounds_feed_the_gate(self, tmp_path):
        """ISSUE 7: INGEST_r*.json is in the default globs, its
        ``entries`` list is walked, and sigs_per_s gates downward /
        p99_admission_ms upward."""
        for i, (sigs, p99) in enumerate([(1000.0, 50.0), (400.0, 200.0)], start=1):
            (tmp_path / f"INGEST_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "bench": "ingest_storm",
                        "entries": [
                            {
                                "metric": "ingest-storm accepted sigs/s (honest)",
                                "sigs_per_s": sigs,
                                "p99_admission_ms": p99,
                            }
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1  # r02 regressed both directions vs r01
        report = json.loads(out.read_text())
        assert set(report["regressions"]) == {
            "ingest-storm accepted sigs/s (honest) :: sigs_per_s",
            "ingest-storm accepted sigs/s (honest) :: p99_admission_ms",
        }

    def test_committed_ingest_round_passes(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("INGEST_r01.json" in f for f in report["history_files"])
        assert any("sigs_per_s" in k for k in report["series"])


class TestProverSeries:
    def test_prover_storm_rounds_feed_the_gate(self, tmp_path):
        """ISSUE 10: PROVER_r*.json is in the default globs, its
        ``entries`` list is walked, and steady_state_epoch_seconds /
        p99_proof_lag_ms gate upward, sustained_proofs_per_s
        downward."""
        for i, (steady, lag, pps) in enumerate(
            [(6.0, 12000.0, 0.1), (12.0, 40000.0, 0.03)], start=1
        ):
            (tmp_path / f"PROVER_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "entries": [
                            {
                                "metric": "steady-state epoch with async plane",
                                "value": steady,
                                "unit": "seconds",
                                "steady_state_epoch_seconds": steady,
                            },
                            {
                                "metric": "proving-plane proof latency",
                                "p99_proof_lag_ms": lag,
                                "sustained_proofs_per_s": pps,
                            },
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1  # r02 regressed all three directions vs r01
        report = json.loads(out.read_text())
        assert {
            "proving-plane proof latency :: p99_proof_lag_ms",
            "proving-plane proof latency :: sustained_proofs_per_s",
            "steady-state epoch with async plane :: steady_state_epoch_seconds",
        } <= set(report["regressions"])

    def test_committed_prover_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("PROVER_r01.json" in f for f in report["history_files"])
        assert any("p99_proof_lag_ms" in k for k in report["series"])


class TestSyntheticRegression:
    def test_regressed_latest_round_fails(self, tmp_path):
        """Acceptance: exit non-zero on a synthetically regressed
        fixture — the newest round is >threshold worse than the best."""
        _write_bench_series(tmp_path, [42.4, 18.2, 17.9, 17.8, 17.76])
        rec = _bench_round(6, 17.76 * 3)
        (tmp_path / "BENCH_r06.json").write_text(json.dumps(rec))
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["ok"] is False
        assert len(report["regressions"]) == 1
        row = report["series"][report["regressions"][0]]
        assert row["status"] == "REGRESSED"
        assert row["candidate_source"] == "BENCH_r06.json"

    def test_within_threshold_wobble_passes(self, tmp_path):
        _write_rounds(tmp_path, [10.0, 8.0, 8.5])  # 6.25% above best
        rc = perf_sentinel.main(
            ["--history", str(tmp_path), "--out", str(tmp_path / "s.json")]
        )
        assert rc == 0

    def test_threshold_is_configurable(self, tmp_path):
        _write_rounds(tmp_path, [10.0, 8.0, 8.5])
        rc = perf_sentinel.main(
            [
                "--history", str(tmp_path),
                "--threshold", "0.05",
                "--out", str(tmp_path / "s.json"),
            ]
        )
        assert rc == 1

    def test_higher_is_better_metrics_gate_downward(self, tmp_path):
        for i, sigs in enumerate([3000.0, 3554.0, 1000.0], start=1):
            rec = {
                "n": i,
                "parsed": {
                    "metric": "sustained ingest",
                    "value": 1.0,
                    "unit": "seconds",
                    "sigs_per_s": sigs,
                },
            }
            (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(rec))
        out = tmp_path / "s.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert any("sigs_per_s" in k for k in report["regressions"])


class TestFreshRun:
    def test_fresh_entry_gates_against_recorded_best(self, tmp_path):
        _write_rounds(tmp_path, [10.0, 8.0, 8.1])
        fresh = {"metric": "headline seconds", "value": 12.0, "unit": "seconds"}
        (tmp_path / "FRESH.json").write_text(json.dumps(fresh))
        rc = perf_sentinel.main(
            [
                "--history", str(tmp_path),
                "--fresh", str(tmp_path / "FRESH.json"),
                "--out", str(tmp_path / "s.json"),
            ]
        )
        assert rc == 1
        report = json.loads((tmp_path / "s.json").read_text())
        row = report["series"][report["regressions"][0]]
        assert row["candidate_source"] == "fresh" and row["candidate"] == 12.0

    def test_fresh_smoke_scale_never_compared(self, tmp_path):
        """A differently-shaped fresh run (CI smoke) has a different
        metric string — it must report as no-baseline, never gate."""
        _write_rounds(tmp_path, [10.0, 8.0])
        fresh = {
            "metric": "smoke-scale convergence (tpu-csr)",
            "value": 999.0,
            "unit": "seconds",
        }
        (tmp_path / "FRESH.json").write_text(json.dumps(fresh))
        rc = perf_sentinel.main(
            [
                "--history", str(tmp_path),
                "--fresh", str(tmp_path / "FRESH.json"),
                "--out", str(tmp_path / "s.json"),
            ]
        )
        assert rc == 0
        report = json.loads((tmp_path / "s.json").read_text())
        smoke = [k for k in report["series"] if "smoke-scale" in k]
        assert report["series"][smoke[0]]["status"] == "no-baseline"

    def test_richer_epoch_metrics_are_tracked(self, tmp_path):
        """cold/steady-state epoch seconds and plan seconds from an
        epochs-mode bench entry become their own gated series."""
        entry = {
            "metric": "steady-state epoch wall-clock",
            "value": 5.82,
            "unit": "seconds",
            "cold_epoch_seconds": 7.37,
            "steady_state_epoch_seconds": 5.82,
            "plan_seconds": 2.5,
        }
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps({"n": 1, "parsed": entry})
        )
        regressed = dict(entry, steady_state_epoch_seconds=9.0, value=9.0)
        (tmp_path / "BENCH_r02.json").write_text(
            json.dumps({"n": 2, "parsed": regressed})
        )
        out = tmp_path / "s.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert any(
            "steady_state_epoch_seconds" in k for k in report["regressions"]
        )
        assert any("plan_seconds" in k for k in report["series"])


class TestNoHistory:
    def test_missing_history_is_usage_error(self, tmp_path):
        rc = perf_sentinel.main(
            ["--history", str(tmp_path), "--out", str(tmp_path / "s.json")]
        )
        assert rc == 2


class TestSiblingArtifactsIgnored:
    """ISSUE 8: SANITIZER/ANALYSIS artifacts living beside the bench
    rounds (or caught by an over-broad --glob) are skipped gracefully —
    never mined for numbers, never a parse failure."""

    def _sanitizer_doc(self) -> dict:
        return {
            "mode": "asan",
            "sanflags": "-fsanitize=address,undefined",
            "build_rc": 0,
            "runs": [{"name": "native-test-subset", "rc": 0}],
            "reports": [],
            "ok": True,
        }

    def _analysis_doc(self) -> dict:
        return {
            "version": 1,
            "tool": "protocol_tpu.analysis (graftlint)",
            "summary": {"error": 0},
            "findings": [
                {"pass": "concurrency", "rule": "unguarded-rmw", "line": 42}
            ],
            "concurrency": {"roots": [], "findings": 0},
            # ISSUE 9: the report grew a pass-8 comm section whose
            # per-scale records carry bytes_per_iter numbers — still an
            # analysis artifact, still never mined.
            "comm": {
                "backends": {
                    "tpu-sharded:tpu-csr": {
                        "status": "checked",
                        "scales": [{"bytes_per_iter": 4096}],
                    }
                }
            },
        }

    def test_artifacts_beside_rounds_do_not_pollute_series(self, tmp_path):
        _write_rounds(tmp_path, [10.0, 9.5])
        (tmp_path / "SANITIZER_asan_r01.json").write_text(
            json.dumps(self._sanitizer_doc())
        )
        (tmp_path / "ANALYSIS_r01.json").write_text(
            json.dumps(self._analysis_doc())
        )
        out = tmp_path / "s.json"
        rc = perf_sentinel.main(
            ["--history", str(tmp_path), "--glob", "*_r*.json", "--out", str(out)]
        )
        assert rc == 0
        report = json.loads(out.read_text())
        assert report["ok"] is True
        # Exactly the bench series — nothing mined from the artifacts.
        assert list(report["series"]) == ["headline seconds :: value"]
        assert report["series"]["headline seconds :: value"]["rounds"] == 2

    def test_artifact_only_history_is_empty_not_crash(self, tmp_path):
        (tmp_path / "SANITIZER_tsan_r01.json").write_text(
            json.dumps(self._sanitizer_doc() | {"mode": "tsan"})
        )
        out = tmp_path / "s.json"
        rc = perf_sentinel.main(
            ["--history", str(tmp_path), "--glob", "*_r*.json", "--out", str(out)]
        )
        assert rc == 0
        assert json.loads(out.read_text())["series"] == {}

    def test_committed_sanitizer_rounds_are_ignored_by_defaults(self):
        """The repo now commits SANITIZER_*_r01.json next to BENCH_r*;
        the default-glob run must not pick them up."""
        series = perf_sentinel.collect_series(
            [REPO / "SANITIZER_asan_r01.json", REPO / "SANITIZER_tsan_r01.json"]
        )
        assert series == {}


class TestCommBytesSeries:
    """ISSUE 9: MULTICHIP_r*.json is in the default globs and its
    pass-8 comm scrape feeds a ``comm_bytes_per_iter`` series that
    gates UPWARD — a partitioner surprise that inflates per-iteration
    wire traffic is a regression like any wall-clock."""

    METRIC = "per-iteration collective bytes (tpu-sharded:tpu-csr, 8-dev mesh)"

    def _multichip(self, n: int, bytes_per_iter: float) -> dict:
        return {
            "n": n,
            "n_devices": 8,
            "ok": True,
            "comm": {
                "tpu-sharded:tpu-csr": {"bytes_per_iter": bytes_per_iter}
            },
            "entries": [
                {
                    "metric": self.METRIC,
                    "comm_bytes_per_iter": bytes_per_iter,
                    "unit": "bytes",
                }
            ],
        }

    def test_stable_comm_bytes_pass(self, tmp_path):
        for i in (1, 2):
            (tmp_path / f"MULTICHIP_r{i:02d}.json").write_text(
                json.dumps(self._multichip(i, 2048.0))
            )
        out = tmp_path / "s.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        key = f"{self.METRIC} :: comm_bytes_per_iter"
        assert report["series"][key]["rounds"] == 2
        assert report["series"][key]["lower_is_better"] is True

    def test_inflated_comm_bytes_fail(self, tmp_path):
        for i, b in ((1, 2048.0), (2, 4096.0)):  # 2x wire out of nowhere
            (tmp_path / f"MULTICHIP_r{i:02d}.json").write_text(
                json.dumps(self._multichip(i, b))
            )
        out = tmp_path / "s.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["regressions"] == [
            f"{self.METRIC} :: comm_bytes_per_iter"
        ]

    def test_legacy_multichip_rounds_yield_no_series(self, tmp_path):
        """Pre-ISSUE-9 MULTICHIP rounds (rc/ok/tail only) match the
        default globs but carry no metric entries — they must
        contribute nothing rather than break the parse."""
        legacy = tmp_path / "MULTICHIP_r01.json"
        legacy.write_text(json.dumps({
            "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": "dryrun_multichip: 8 devices, n=512, 8 iters — OK\n",
        }))
        series = perf_sentinel.collect_series([legacy])
        assert series == {}

    def test_committed_multichip_round_feeds_the_gate(self, tmp_path):
        """The ISSUE 9 recorded round (MULTICHIP_r06+) is picked up by
        the default-glob run as a comm_bytes_per_iter series."""
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("MULTICHIP_r06.json" in f for f in report["history_files"])
        assert any("comm_bytes_per_iter" in k for k in report["series"])


class TestObsFreshnessSeries:
    def test_obs_rounds_feed_the_gate(self, tmp_path):
        """ISSUE 11: OBS_r*.json is in the default globs, its
        ``entries`` list is walked, and freshness_p99_ms /
        obs_overhead_pct gate upward."""
        for i, (fresh, overhead) in enumerate(
            [(9000.0, 0.2), (30000.0, 2.5)], start=1
        ):
            (tmp_path / f"OBS_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "entries": [
                            {
                                "metric": "end-to-end freshness (200k/2M churned)",
                                "value": fresh,
                                "unit": "ms p99 accepted-to-proven",
                                "freshness_p99_ms": fresh,
                            },
                            {
                                "metric": "lineage+SLO overhead",
                                "obs_overhead_pct": overhead,
                            },
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1  # r02 regressed both series vs r01
        report = json.loads(out.read_text())
        assert {
            "end-to-end freshness (200k/2M churned) :: freshness_p99_ms",
            "lineage+SLO overhead :: obs_overhead_pct",
        } <= set(report["regressions"])

    def test_committed_obs_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("OBS_r01.json" in f for f in report["history_files"])
        assert any("freshness_p99_ms" in k for k in report["series"])


class TestPodObsSeries:
    def test_pod_obs_rounds_gate_with_n_hosts_key(self, tmp_path):
        """ISSUE 19: pod OBS rounds carry ``n_hosts`` so the stitch /
        skew series never collide with the single-host freshness
        series; stitch_ms and phase_skew_p99_ms gate upward."""
        for i, (stitch, skew) in enumerate([(0.5, 8.0), (4.0, 60.0)], start=1):
            (tmp_path / f"OBS_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "tool": "dryrun_pod",
                        "n_hosts": 2,
                        "entries": [
                            {
                                "metric": "pod trace stitch + phase skew",
                                "value": stitch,
                                "unit": "ms",
                                "n_hosts": 2,
                                "stitch_ms": stitch,
                                "phase_skew_p99_ms": skew,
                            }
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1  # r02 regressed both pod series vs r01
        report = json.loads(out.read_text())
        assert {
            "pod trace stitch + phase skew :: stitch_ms [n_hosts=2]",
            "pod trace stitch + phase skew :: phase_skew_p99_ms [n_hosts=2]",
        } <= set(report["regressions"])

    def test_committed_pod_obs_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("OBS_r02.json" in f for f in report["history_files"])
        assert any(
            "stitch_ms [n_hosts=2]" in k for k in report["series"]
        )


class TestChaosRecoverySeries:
    def test_chaos_rounds_feed_the_gate(self, tmp_path):
        """ISSUE 14: CHAOS_r*.json is in the default globs, its
        ``entries`` list is walked, and recovery_seconds /
        wal_overhead_pct gate upward (a slower kill -9 recovery or a
        heavier WAL both regress the durability plane)."""
        for i, (recovery, overhead) in enumerate(
            [(0.8, 1.5), (4.0, 9.0)], start=1
        ):
            (tmp_path / f"CHAOS_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "tool": "crash_matrix",
                        "entries": [
                            {
                                "metric": "crash-matrix recovery (2000 peers)",
                                "recovery_seconds": recovery,
                                "wal_overhead_pct": overhead,
                            }
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1  # r02 regressed both series vs r01
        report = json.loads(out.read_text())
        assert {
            "crash-matrix recovery (2000 peers) :: recovery_seconds",
            "crash-matrix recovery (2000 peers) :: wal_overhead_pct",
        } <= set(report["regressions"])

    def test_committed_chaos_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("CHAOS_r01.json" in f for f in report["history_files"])
        assert any("recovery_seconds" in k for k in report["series"])


class TestMemPeakSeries:
    def test_mem_rounds_feed_the_gate(self, tmp_path):
        """ISSUE 15: MEM_r*.json is in the default globs, its
        ``entries`` list is walked, and peak_hbm_bytes /
        peak_hbm_bytes_per_shard gate upward (a silently materialized
        O(E) temporary or a replicated edge operand moves a recorded
        number before it trips the static wall)."""
        for i, (peak, per_shard) in enumerate(
            [(500_000, 60_000), (900_000, 140_000)], start=1
        ):
            (tmp_path / f"MEM_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "tool": "mem_probe",
                        "entries": [
                            {
                                "metric": "converge peak HBM bytes (tpu-csr)",
                                "peak_hbm_bytes": peak,
                                "unit": "bytes",
                            },
                            {
                                "metric": (
                                    "per-shard converge peak HBM bytes "
                                    "(tpu-sharded:tpu-csr)"
                                ),
                                "peak_hbm_bytes_per_shard": per_shard,
                                "unit": "bytes",
                            },
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1  # r02 regressed both series vs r01
        report = json.loads(out.read_text())
        assert {
            "converge peak HBM bytes (tpu-csr) :: peak_hbm_bytes",
            "per-shard converge peak HBM bytes (tpu-sharded:tpu-csr) "
            ":: peak_hbm_bytes_per_shard",
        } <= set(report["regressions"])

    def test_stable_mem_rounds_pass(self, tmp_path):
        for i in (1, 2):
            (tmp_path / f"MEM_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "tool": "mem_probe",
                        "entries": [
                            {
                                "metric": "converge peak HBM bytes (x)",
                                "peak_hbm_bytes": 500_000,
                                "unit": "bytes",
                            }
                        ],
                    }
                )
            )
        rc = perf_sentinel.main(
            ["--history", str(tmp_path), "--out", str(tmp_path / "S.json")]
        )
        assert rc == 0

    def test_committed_mem_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("MEM_r01.json" in f for f in report["history_files"])
        assert any("peak_hbm_bytes" in k for k in report["series"])
        assert any("peak_hbm_bytes_per_shard" in k for k in report["series"])

    def test_mem_probe_report_is_not_skipped_as_artifact(self):
        """MEM rounds carry "tool" but no "findings", so the
        non-bench-artifact filter must NOT skip them — the COMM_PROBE
        parity the ISSUE names."""
        report = {"tool": "mem_probe", "ok": True, "entries": []}
        assert not perf_sentinel._is_non_bench_artifact(report)


class TestZkKernelSeries:
    def test_msm_rounds_feed_the_gate(self, tmp_path):
        """ISSUE 20: MSM_r*.json is in the default globs, its
        ``entries`` list is walked, and msm_points_per_s /
        ntt_butterflies_per_s gate downward while prove_seconds gates
        upward — per backend/size, since the metric string carries
        both."""
        rounds = [
            (50_000.0, 2_000_000.0, 8.0),
            (20_000.0, 800_000.0, 14.0),  # all three regressed
        ]
        for i, (msm, ntt, prove) in enumerate(rounds, start=1):
            (tmp_path / f"MSM_r{i:02d}.json").write_text(
                json.dumps(
                    {
                        "n": i,
                        "entries": [
                            {
                                "metric": "zk msm throughput (native, n=2^14, bn254 G1)",
                                "msm_points_per_s": msm,
                                "unit": "points/s",
                            },
                            {
                                "metric": "zk ntt throughput (native, n=2^14, fr)",
                                "ntt_butterflies_per_s": ntt,
                                "unit": "butterflies/s",
                            },
                            {
                                "metric": "plonk epoch prove wall (native, 5 peers)",
                                "prove_seconds": prove,
                                "unit": "seconds",
                            },
                        ],
                    }
                )
            )
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(tmp_path), "--out", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert {
            "zk msm throughput (native, n=2^14, bn254 G1) :: msm_points_per_s",
            "zk ntt throughput (native, n=2^14, fr) :: ntt_butterflies_per_s",
            "plonk epoch prove wall (native, 5 peers) :: prove_seconds",
        } <= set(report["regressions"])

    def test_backend_series_never_cross_compare(self, tmp_path):
        """A slow graft round beside a fast native round is two
        different series (the backend is in the metric string), so
        neither regresses the other."""
        (tmp_path / "MSM_r01.json").write_text(
            json.dumps(
                {
                    "n": 1,
                    "entries": [
                        {
                            "metric": "zk msm throughput (native, n=2^10, bn254 G1)",
                            "msm_points_per_s": 100_000.0,
                            "unit": "points/s",
                        }
                    ],
                }
            )
        )
        (tmp_path / "MSM_r02.json").write_text(
            json.dumps(
                {
                    "n": 2,
                    "entries": [
                        {
                            "metric": "zk msm throughput (graft, n=2^10, bn254 G1)",
                            "msm_points_per_s": 150.0,
                            "unit": "points/s",
                        }
                    ],
                }
            )
        )
        rc = perf_sentinel.main(
            ["--history", str(tmp_path), "--out", str(tmp_path / "S.json")]
        )
        assert rc == 0

    def test_committed_msm_round_feeds_the_gate(self, tmp_path):
        out = tmp_path / "SENTINEL.json"
        rc = perf_sentinel.main(["--history", str(REPO), "--out", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        assert any("MSM_r01.json" in f for f in report["history_files"])
        assert any("msm_points_per_s" in k for k in report["series"])
        assert any("ntt_butterflies_per_s" in k for k in report["series"])
