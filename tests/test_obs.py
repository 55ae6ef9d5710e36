"""Observability subsystem (ISSUE 4): trace spans, metrics registry,
exporters, the /metrics and /trace/<epoch> endpoints, and the
zero-hot-path-sync contracts (residual carry adds no gathers/callbacks;
instrumented backends are bit-identical to uninstrumented ones under a
transfer guard)."""

import json
import logging
import re
import threading
import zlib

import numpy as np
import pytest

import jax

from protocol_tpu.models.graphs import erdos_renyi
from protocol_tpu.node.epoch import Epoch
from protocol_tpu.node.manager import Manager, ManagerConfig
from protocol_tpu.node.server import handle_request
from protocol_tpu.obs import METRICS, TRACER, metrics_json, prometheus_text
from protocol_tpu.obs import metrics as obs_metrics
from protocol_tpu.obs.metrics import MetricsRegistry
from protocol_tpu.obs.trace import SpanContextFilter, Tracer, configure_logging
from protocol_tpu.trust.backend import get_backend


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_and_serialization(self):
        tracer = Tracer()
        with tracer.epoch(3):
            with tracer.span("prove"):
                with tracer.span("snark"):
                    pass
            with tracer.span("converge", backend="tpu-csr"):
                pass
        tree = tracer.get_trace(3)
        assert tree["name"] == "epoch_tick"
        assert tree["attrs"]["epoch"] == 3
        assert [c["name"] for c in tree["children"]] == ["prove", "converge"]
        (snark,) = tree["children"][0]["children"]
        assert snark["name"] == "snark"
        assert snark["duration_s"] >= 0
        assert tree["children"][1]["attrs"]["backend"] == "tpu-csr"

    def test_trace_survives_tick_exception(self):
        tracer = Tracer()
        with pytest.raises(RuntimeError):
            with tracer.epoch(9):
                with tracer.span("prove"):
                    raise RuntimeError("boom")
        tree = tracer.get_trace(9)
        assert tree is not None and tree["attrs"]["error"] is True

    def test_epoch_ring_evicts_oldest(self):
        tracer = Tracer(keep_epochs=2)
        for e in (1, 2, 3):
            with tracer.epoch(e):
                pass
        assert tracer.epochs() == [2, 3]
        assert tracer.get_trace(1) is None
        assert tracer.latest_epoch() == 3

    def test_threads_have_independent_span_stacks(self):
        tracer = Tracer()
        seen = {}

        def tick(epoch):
            with tracer.epoch(epoch):
                with tracer.span(f"work_{epoch}"):
                    pass
            seen[epoch] = tracer.get_trace(epoch)

        threads = [threading.Thread(target=tick, args=(e,)) for e in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in range(4):
            assert [c["name"] for c in seen[e]["children"]] == [f"work_{e}"]

    def test_span_close_hook_feeds_phase_histogram(self):
        before = obs_metrics.PHASE_SECONDS.count(phase="unit_phase")
        with TRACER.span("unit_phase"):
            pass
        assert obs_metrics.PHASE_SECONDS.count(phase="unit_phase") == before + 1


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "help", labelnames=("reason",))
        c.inc(reason="a")
        c.inc(2, reason="a")
        c.inc(reason="b")
        assert c.value(reason="a") == 3 and c.value(reason="b") == 1
        g = reg.gauge("g")
        g.set(7.5)
        assert g.value() == 7.5
        h = reg.histogram("h", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()[()]
        assert snap["count"] == 3 and snap["sum"] == 55.5
        # cumulative buckets: le=1 -> 1, le=10 -> 2, le=+Inf -> 3
        assert snap["buckets"] == [1, 2, 3]

    def test_counters_are_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_label_mismatch_rejected(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", labelnames=("reason",))
        with pytest.raises(ValueError):
            c.inc()
        with pytest.raises(ValueError):
            c.inc(reason="x", extra="y")

    def test_registration_idempotent_but_kind_pinned(self):
        reg = MetricsRegistry()
        c1 = reg.counter("x_total")
        assert reg.counter("x_total") is c1
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_thread_safety_under_concurrent_scrape(self):
        """The ISSUE 4 concurrency contract: writer threads (epoch tick
        / ingest) and scrape threads (HTTP GET /metrics) race on one
        registry; totals stay exact and rendering never throws."""
        reg = MetricsRegistry()
        c = reg.counter("writes_total", labelnames=("worker",))
        h = reg.histogram("vals", buckets=(0.25, 0.5, 0.75))
        n_writers, per_writer = 8, 2000
        errors = []
        stop = threading.Event()

        def writer(k):
            try:
                for i in range(per_writer):
                    c.inc(worker=str(k))
                    h.observe((i % 100) / 100.0)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        def scraper():
            try:
                while not stop.is_set():
                    from protocol_tpu.obs.export import prometheus_text

                    text = prometheus_text(reg)
                    assert "writes_total" in text
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        scrapers = [threading.Thread(target=scraper) for _ in range(2)]
        writers = [threading.Thread(target=writer, args=(k,)) for k in range(n_writers)]
        for t in scrapers + writers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in scrapers:
            t.join()
        assert errors == []
        for k in range(n_writers):
            assert c.value(worker=str(k)) == per_writer
        assert h.count() == n_writers * per_writer


# ---------------------------------------------------------------------------
# Prometheus exposition format
# ---------------------------------------------------------------------------

_LABEL_RE = r"[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""  # value may escape \" \\ \n
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"  # metric name
    rf"(\{{{_LABEL_RE}(,{_LABEL_RE})*\}})?"
    r" (-?[0-9.]+(e[+-]?[0-9]+)?|\+Inf|-Inf|NaN)$"
)


def _parse_prometheus(text: str) -> dict:
    """Minimal exposition-format parser: every non-comment line must be
    a well-formed sample; returns {sample_name_with_labels: value}."""
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ", line), line
            continue
        assert _SAMPLE_RE.match(line), f"unparseable sample line: {line!r}"
        name_labels, value = line.rsplit(" ", 1)
        samples[name_labels] = float(value)
    return samples


class TestPrometheusExport:
    def test_text_format_parses(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "with help").inc(3)
        reg.counter("b_total", "labelled", labelnames=("reason",)).inc(
            reason='we"ird\nvalue'
        )
        reg.gauge("g", "a gauge").set(1.25)
        reg.histogram("h", "a histogram", buckets=(0.1, 1.0)).observe(0.3)
        from protocol_tpu.obs.export import prometheus_text

        samples = _parse_prometheus(prometheus_text(reg))
        assert samples["a_total"] == 3
        assert samples["g"] == 1.25
        assert samples['h_bucket{le="0.1"}'] == 0
        assert samples['h_bucket{le="1"}'] == 1
        assert samples['h_bucket{le="+Inf"}'] == 1
        assert samples["h_count"] == 1

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        from protocol_tpu.obs.export import prometheus_text

        samples = _parse_prometheus(prometheus_text(reg))
        counts = [
            samples[f'h_bucket{{le="{b}"}}'] for b in ("1", "2", "4", "+Inf")
        ]
        assert counts == sorted(counts)  # monotone non-decreasing
        assert counts[-1] == samples["h_count"] == 4

    def test_global_registry_renders(self):
        _parse_prometheus(prometheus_text())
        assert "eigentrust_epochs_total" in metrics_json()


# ---------------------------------------------------------------------------
# Node endpoints
# ---------------------------------------------------------------------------


def _ticked_manager(backend="tpu-sparse"):
    """A manager with one full epoch of work driven under the epoch
    trace root, exactly as Node._epoch_tick does."""
    m = Manager(ManagerConfig(prover="commitment", backend=backend))
    m.generate_initial_attestations()
    with TRACER.epoch(4):
        with TRACER.span("prove"):
            m.calculate_proofs(Epoch(4))
        m.converge_epoch(Epoch(4), alpha=0.1)
    return m


class TestEndpoints:
    def test_metrics_endpoint_prometheus_parses(self):
        METRICS.reset()
        m = _ticked_manager()
        status, body = handle_request("GET", "/metrics", m)
        assert status == 200
        samples = _parse_prometheus(body)
        assert samples["eigentrust_graph_peers"] == 5
        assert samples["eigentrust_convergence_iterations"] >= 1

    def test_residual_histogram_length_equals_iterations(self):
        METRICS.reset()
        m = _ticked_manager()
        result = m.cached_results[Epoch(4)]
        status, body = handle_request("GET", "/metrics", m)
        samples = _parse_prometheus(body)
        assert samples["eigentrust_convergence_residual_count"] == result.iterations
        assert len(result.residuals) == result.iterations

    def test_trace_endpoint_span_tree_nesting(self):
        m = _ticked_manager()
        status, body = handle_request("GET", "/trace/4", m)
        assert status == 200
        tree = json.loads(body)
        assert tree["name"] == "epoch_tick"
        names = [c["name"] for c in tree["children"]]
        assert names[0] == "prove"
        assert "build_graph" in names and "converge" in names
        prove_children = [c["name"] for c in tree["children"][0]["children"]]
        assert prove_children == ["power_iterate", "circuit_check", "snark"]

    def test_trace_latest_and_errors(self):
        m = _ticked_manager()
        status, body = handle_request("GET", "/trace/latest", m)
        assert status == 200 and json.loads(body)["name"] == "epoch_tick"
        status, _ = handle_request("GET", "/trace/notanint", m)
        assert status == 400
        status, body = handle_request("GET", "/trace/123456789", m)
        assert status == 404 and "no trace" in json.loads(body)["error"]

    def test_status_lists_traced_epochs(self):
        m = _ticked_manager()
        status, body = handle_request("GET", "/status", m)
        assert 4 in json.loads(body)["traced_epochs"]

    def test_metrics_content_type_over_socket(self):
        """Socket-level: /metrics must be served text/plain (Prometheus
        scrapers reject JSON content types)."""
        import asyncio

        from protocol_tpu.node.config import ProtocolConfig
        from protocol_tpu.node.server import Node

        async def scenario():
            cfg = ProtocolConfig(
                epoch_interval=3600, endpoint=((127, 0, 0, 1), 0),
                prover="commitment",
            )
            node = Node.from_config(cfg)
            await node.start()
            port = node._server.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(b"GET /metrics HTTP/1.1\r\nhost: x\r\n\r\n")
            await writer.drain()
            response = (await reader.read()).decode()
            writer.close()
            await node.stop()
            return response

        response = asyncio.run(scenario())
        head, _, body = response.partition("\r\n\r\n")
        assert "200 OK" in head
        assert "content-type: text/plain; version=0.0.4" in head
        _parse_prometheus(body)

    def test_bulk_ingest_rejection_reasons_counted(self):
        from protocol_tpu.crypto.eddsa import SecretKey, sign
        from tests.test_node import make_attestation

        METRICS.reset()
        good = make_attestation(0)
        bad_sig = make_attestation(1)
        bad_sig.sig = sign(SecretKey.random(), SecretKey.random().public(), 1)
        bad_sum = make_attestation(2, scores=[1, 0, 0, 0, 0])
        m = Manager()
        results = m.add_attestations_bulk([good, bad_sig, bad_sum])
        assert [r.accepted for r in results] == [True, False, False]
        assert obs_metrics.ATTESTATIONS_ACCEPTED.value() == 1
        assert obs_metrics.ATTESTATIONS_REJECTED.value(reason="bad-signature") == 1
        assert (
            obs_metrics.ATTESTATIONS_REJECTED.value(reason="non-conserving-scores")
            == 1
        )
        _, body = handle_request("GET", "/metrics", m)
        assert (
            'eigentrust_attestations_rejected_total{reason="bad-signature"} 1'
            in body
        )

    def test_checkpoint_counters(self, tmp_path):
        from protocol_tpu.node.checkpoint import CheckpointStore

        METRICS.reset()
        store = CheckpointStore(tmp_path)
        g = erdos_renyi(30, seed=2)
        store.save(Epoch(1), g)
        store.load_latest()
        assert obs_metrics.CHECKPOINT_SAVES.value() == 1
        assert obs_metrics.CHECKPOINT_RESTORES.value() == 1


# ---------------------------------------------------------------------------
# Hot-path contracts
# ---------------------------------------------------------------------------


class TestHotPathContracts:
    """The residual carry must not change the kernel access pattern,
    and instrumented convergence must be bit-identical."""

    BACKENDS = ("tpu-sparse", "tpu-csr", "tpu-windowed", "tpu-sharded")

    @pytest.mark.parametrize("name", BACKENDS)
    def test_instrumented_bit_identical_under_transfer_guard(self, name):
        g = erdos_renyi(150, avg_degree=5.0, seed=3)
        backend_on = get_backend(name)
        backend_off = get_backend(name)
        with jax.transfer_guard("disallow"):
            on = backend_on.converge(g, alpha=0.1, tol=1e-9, max_iter=30)
            off = backend_off.converge(
                g, alpha=0.1, tol=1e-9, max_iter=30, record_residuals=False
            )
        assert np.array_equal(on.scores, off.scores)  # bit-identical
        assert off.residuals is None
        assert len(on.residuals) == on.iterations == off.iterations
        # The trajectory's last entry is the reported final residual.
        np.testing.assert_allclose(on.residuals[-1], on.residual, rtol=1e-6)

    def test_residual_carry_adds_no_gathers_or_callbacks(self):
        """ISSUE 4 satellite: KERNEL_INVARIANTS budgets are unchanged
        with the residual-carry step — the full converge jaxpr has the
        same gather count, zero scatters, and zero callbacks with the
        history carry enabled."""
        import jax.numpy as jnp

        from protocol_tpu.analysis.jaxpr_walk import (
            CALLBACK_PRIMITIVES,
            SCATTER_PRIMITIVES,
            collect_primitives,
        )
        from protocol_tpu.ops.sparse import converge_csr
        from protocol_tpu.trust.graph import TrustGraph

        g = erdos_renyi(100, avg_degree=4.0, seed=5).drop_self_edges()
        w, dangling = g.row_normalized()
        gs = TrustGraph(g.n, g.src, g.dst, w, g.pre_trusted).sorted_by_dst()
        p = g.pre_trust_vector()
        args = (
            jnp.asarray(gs.src),
            jnp.asarray(gs.row_ptr_by_dst()),
            jnp.asarray(gs.weight),
            jnp.asarray(p),
            jnp.asarray(p),
            jnp.asarray(dangling.astype(np.float32)),
        )

        def counts(record):
            jaxpr = jax.make_jaxpr(
                lambda *a: converge_csr(
                    a[0], a[1], a[2], a[3], a[4], a[5],
                    alpha=0.1, tol=1e-6, max_iter=8,
                    record_residuals=record,
                )
            )(*args)
            return (
                len(collect_primitives(jaxpr, {"gather"})),
                len(collect_primitives(jaxpr, SCATTER_PRIMITIVES)),
                len(collect_primitives(jaxpr, CALLBACK_PRIMITIVES)),
            )

        gathers_off, scatters_off, callbacks_off = counts(False)
        gathers_on, scatters_on, callbacks_on = counts(True)
        assert gathers_on == gathers_off
        assert scatters_on == scatters_off == 0
        assert callbacks_on == callbacks_off == 0

    def test_trace_store_read_does_not_touch_device(self):
        """Serving /trace is a host-side dict copy: no jax arrays are
        reachable from the serialized tree."""
        m = _ticked_manager()
        tree = TRACER.get_trace(4)

        def walk(node):
            assert isinstance(node["name"], str)
            for k, v in node.get("attrs", {}).items():
                assert isinstance(v, (str, int, float, bool, type(None))), (k, v)
            for child in node["children"]:
                walk(child)

        walk(tree)


# ---------------------------------------------------------------------------
# Logging integration
# ---------------------------------------------------------------------------


class TestConfigureLogging:
    def _fresh_root(self):
        root = logging.getLogger()
        saved = (root.handlers[:], root.level)
        root.handlers[:] = []
        return root, saved

    def _restore(self, root, saved):
        root.handlers[:] = saved[0]
        root.setLevel(saved[1])

    def test_installs_handler_on_pristine_root(self):
        root, saved = self._fresh_root()
        try:
            configure_logging()
            assert len(root.handlers) == 1
            handler = root.handlers[0]
            assert any(isinstance(f, SpanContextFilter) for f in handler.filters)
            # The format resolves: a record through the handler must not
            # raise on the %(epoch)s / %(span)s columns.
            record = logging.LogRecord(
                "x", logging.INFO, __file__, 1, "hello", (), None
            )
            for f in handler.filters:
                f.filter(record)
            assert "epoch=-" in handler.format(record)
        finally:
            self._restore(root, saved)

    def test_respects_existing_root_handler(self):
        root, saved = self._fresh_root()
        try:
            mine = logging.StreamHandler()
            fmt = logging.Formatter("%(message)s")
            mine.setFormatter(fmt)
            root.addHandler(mine)
            configure_logging()
            configure_logging()  # idempotent
            assert root.handlers == [mine]  # no second handler
            assert mine.formatter is fmt  # formatter untouched
            # ...but the span filter was attached exactly once.
            filters = [f for f in mine.filters if isinstance(f, SpanContextFilter)]
            assert len(filters) == 1
        finally:
            self._restore(root, saved)

    def test_records_carry_span_context(self):
        record_holder = {}

        class Capture(logging.Handler):
            def emit(self, record):
                record_holder["r"] = record

        logger = logging.getLogger("protocol_tpu.test_obs")
        handler = Capture()
        handler.addFilter(SpanContextFilter())
        logger.addHandler(handler)
        try:
            with TRACER.epoch(11):
                with TRACER.span("prove"):
                    logger.warning("inside")
            r = record_holder["r"]
            assert r.epoch == 11 and r.span == "prove" and r.span_id > 0
        finally:
            logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# Bench parity
# ---------------------------------------------------------------------------


class TestBenchPhases:
    def test_headline_embeds_span_phase_timings(self):
        """BENCH_*.json parity (ISSUE 4 CI satellite): the headline
        entry embeds span-derived phase timings under the same names
        the node's /trace reports."""
        import bench

        entry = bench.headline_entry(
            iters=2, backend="tpu-csr", n_peers=1024, n_edges=4096
        )
        assert entry["phases"].keys() == {"converge"}
        assert entry["phases"]["converge"] >= 0
        windowed = bench.headline_entry(
            iters=2, backend="tpu-windowed", n_peers=2048, n_edges=8192
        )
        assert set(windowed["phases"]) == {"plan", "converge"}


class TestProfileSession:
    def test_noop_without_dir(self):
        from protocol_tpu.obs import profile_session

        with profile_session(None):
            pass

    def test_writes_profile_artifacts(self, tmp_path):
        from protocol_tpu.obs import profile_session

        import jax.numpy as jnp

        with profile_session(str(tmp_path / "prof")):
            jnp.asarray(np.ones(8, np.float32)).sum().block_until_ready()
        assert any((tmp_path / "prof").rglob("*")), "no profiler output written"

    def test_mirrors_program_spans_inside_the_session_only(self, monkeypatch, tmp_path):
        """An operator's capture names host activity as the benchmark's
        does: spans inside the session become ``program:<name>``
        annotations, chained to (and then restoring) the hooks already
        installed; spans outside it are not mirrored."""
        import contextlib

        from protocol_tpu.obs import profile_session

        events: list[tuple[str, str]] = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append(("enter", self.name))

            def __exit__(self, *exc):
                events.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        monkeypatch.setattr(jax.profiler, "trace", lambda d: contextlib.nullcontext())
        hooks = (TRACER.on_span_open, TRACER.on_span_close)
        closed: list[str] = []
        monkeypatch.setattr(TRACER, "on_span_close", lambda sp: closed.append(sp.name))
        with TRACER.span("before"):
            pass
        with profile_session(str(tmp_path / "prof")):
            with TRACER.span("plan"):
                with TRACER.span("plan_select"):
                    pass
        with TRACER.span("after"):
            pass
        assert events == [
            ("enter", "program:plan"), ("enter", "program:plan_select"),
            ("exit", "program:plan_select"), ("exit", "program:plan"),
        ]
        # the hook chain: the installed close hook saw every span
        assert closed == ["before", "plan_select", "plan", "after"]
        assert TRACER.on_span_open is hooks[0]


# ---------------------------------------------------------------------------
# Exposition hardening (ISSUE 6 satellite)
# ---------------------------------------------------------------------------


def _unescape_label(value: str) -> str:
    """Inverse of the exposition-format label escaping."""
    out, i = [], 0
    while i < len(value):
        c = value[i]
        if c == "\\" and i + 1 < len(value):
            nxt = value[i + 1]
            out.append({"n": "\n", '"': '"', "\\": "\\"}.get(nxt, "\\" + nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


class TestExpositionHardening:
    def test_hostile_reason_label_round_trips(self):
        """A reason label containing every escapable character must
        render as a parseable sample whose unescaped value recovers
        the original string byte-for-byte."""
        hostile = 'quote:" backslash:\\ newline:\nend'
        reg = MetricsRegistry()
        reg.counter("rej_total", "rejections", labelnames=("reason",)).inc(
            reason=hostile
        )
        from protocol_tpu.obs.export import prometheus_text

        text = prometheus_text(reg)
        samples = _parse_prometheus(text)  # every line must stay well-formed
        (label_line,) = [k for k in samples if k.startswith("rej_total{")]
        m = re.match(r'rej_total\{reason="(.*)"\}', label_line)
        assert m is not None
        assert _unescape_label(m.group(1)) == hostile
        assert samples[label_line] == 1

    def test_help_text_escaped(self):
        reg = MetricsRegistry()
        reg.gauge("g", "first line\nsecond \\ line").set(1)
        from protocol_tpu.obs.export import prometheus_text

        text = prometheus_text(reg)
        (help_line,) = [
            line for line in text.splitlines() if line.startswith("# HELP g ")
        ]
        assert help_line == "# HELP g first line\\nsecond \\\\ line"
        _parse_prometheus(text)

    def test_content_type_version(self):
        from protocol_tpu.obs.export import PROMETHEUS_CONTENT_TYPE

        assert PROMETHEUS_CONTENT_TYPE.startswith("text/plain; version=0.0.4")


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_tail_ordered(self):
        from protocol_tpu.obs.journal import FlightRecorder

        rec = FlightRecorder(max_events=64)
        for i in range(500):
            rec.record("tick", i=i)
        assert len(rec) == 64
        tail = rec.tail(10)
        assert [e["i"] for e in tail] == list(range(490, 500))
        assert all(e["kind"] == "tick" for e in tail)
        seqs = [e["seq"] for e in rec.tail()]
        assert seqs == sorted(seqs)

    def test_batched_writer_lands_events_on_disk(self, tmp_path):
        from protocol_tpu.obs.journal import FlightRecorder

        path = tmp_path / "j.jsonl"
        rec = FlightRecorder(max_events=128).configure(path)
        for i in range(20):
            rec.record("span", name=f"s{i}", duration_s=0.1)
        rec.close()
        lines = [json.loads(x) for x in path.read_text().splitlines()]
        assert [e["name"] for e in lines] == [f"s{i}" for i in range(20)]

    def test_rotation_keeps_recent_window(self, tmp_path):
        from protocol_tpu.obs.journal import FlightRecorder

        path = tmp_path / "j.jsonl"
        rec = FlightRecorder(max_events=50, max_bytes=2000).configure(path)
        for i in range(400):
            rec.record("tick", i=i)
        rec.close()
        assert path.stat().st_size < 10_000  # bounded, not 400 lines' worth
        lines = [json.loads(x) for x in path.read_text().splitlines()]
        assert lines  # the recent window survived
        assert lines[-1]["i"] == 399

    def test_dump_writes_ring_plus_marker(self, tmp_path):
        from protocol_tpu.obs.journal import FlightRecorder

        rec = FlightRecorder(max_events=32)
        for i in range(5):
            rec.record("tick", i=i)
        out = rec.dump(tmp_path / "post" / "mortem.jsonl", reason="test")
        lines = [json.loads(x) for x in out.read_text().splitlines()]
        assert len(lines) == 6
        assert lines[-1]["kind"] == "journal-dump"
        assert lines[-1]["reason"] == "test" and lines[-1]["events"] == 5

    def test_record_never_raises_on_unserializable(self, tmp_path):
        from protocol_tpu.obs.journal import FlightRecorder

        rec = FlightRecorder().configure(tmp_path / "j.jsonl")
        rec.record("weird", obj=object())  # json falls back to str()
        rec.close()
        line = json.loads((tmp_path / "j.jsonl").read_text().splitlines()[0])
        assert "object object" in line["obj"]

    def test_span_close_feeds_global_journal(self):
        from protocol_tpu.obs import JOURNAL

        before = len(JOURNAL.tail())
        with TRACER.span("journal_unit_phase"):
            pass
        events = JOURNAL.tail()
        assert len(events) > before
        assert any(
            e["kind"] == "span" and e.get("name") == "journal_unit_phase"
            for e in events
        )


# ---------------------------------------------------------------------------
# Watchers: recompiles, memory watermarks, drift
# ---------------------------------------------------------------------------


class TestRecompileTrackerUnit:
    def test_snapshot_observe_counts_misses(self):
        import jax.numpy as jnp

        from protocol_tpu.obs.watchers import RecompileTracker

        tracker = RecompileTracker()

        @jax.jit
        def f(x):
            return x * 2

        tracker.register("f", f)
        f(jnp.ones(3))
        snap = tracker.snapshot()
        assert tracker.observe(snap) == {}  # no new shapes
        f(jnp.ones(7))  # fresh shape -> one compile
        assert tracker.observe(snap) == {"f": 1}

    def test_non_jit_objects_are_ignored(self):
        from protocol_tpu.obs.watchers import RecompileTracker

        tracker = RecompileTracker()
        tracker.register("not_jit", lambda x: x)
        assert tracker.registered() == []


class TestMemoryWatermarks:
    def test_disables_itself_without_allocator_stats(self):
        from protocol_tpu.obs.watchers import MemoryWatermarkWatcher

        w = MemoryWatermarkWatcher()
        with TRACER.span("mem_probe") as sp:
            w.on_open(sp)
        # CPU devices report no memory_stats: the watcher must neither
        # leave snapshot attrs behind nor claim a delta.
        if jax.local_devices()[0].memory_stats() is None:
            assert w._enabled is False
            assert "dev_mem_delta_bytes" not in sp.attrs
            assert "_mem_open_bytes" not in sp.attrs

    def test_records_delta_with_fake_stats(self):
        from protocol_tpu.obs.watchers import MemoryWatermarkWatcher

        class Fake(MemoryWatermarkWatcher):
            def __init__(self):
                super().__init__()
                self.now = 1000

            def _bytes_in_use(self):
                return (self.now, self.now + 7)

        w = Fake()
        with TRACER.span("mem_fake") as sp:
            w.on_open(sp)
            w.now = 1500
            w.on_close(sp)
        assert sp.attrs["dev_mem_delta_bytes"] == 500
        assert sp.attrs["dev_mem_peak_bytes"] == 1507
        assert "_mem_open_bytes" not in sp.attrs
        assert obs_metrics.DEVICE_MEMORY_DELTA.value(phase="mem_fake") == 500


class TestScoreDriftMonitor:
    def test_first_epoch_has_no_drift_then_l1_linf(self):
        from protocol_tpu.obs.watchers import ScoreDriftMonitor

        mon = ScoreDriftMonitor(top_k=2)
        first = mon.observe(1, [10, 11, 12], [0.5, 0.3, 0.2])
        assert first["l1"] is None and first["top_movers"] == []
        second = mon.observe(2, [10, 11, 13], [0.4, 0.35, 0.25])
        assert abs(second["l1"] - 0.15) < 1e-12
        assert abs(second["linf"] - 0.1) < 1e-12
        assert second["joined"] == 1 and second["departed"] == 1
        movers = second["top_movers"]
        assert movers[0]["peer_hash"] == hex(10)
        assert abs(movers[0]["delta"] + 0.1) < 1e-12
        assert mon.last()["epoch"] == 2

    def test_residual_stall_detection(self):
        from protocol_tpu.obs.watchers import ScoreDriftMonitor

        mon = ScoreDriftMonitor()
        before = obs_metrics.RESIDUAL_STALLS.value()
        ok = mon.observe(1, [1], [1.0], residuals=[0.5, 0.4, 0.41, 0.2])
        assert ok["residual_increases"] == 1 and not ok["stalled"]
        bad = mon.observe(2, [1], [1.0], residuals=[0.5, 0.6, 0.4, 0.55])
        assert bad["residual_increases"] == 2 and bad["stalled"]
        assert obs_metrics.RESIDUAL_STALLS.value() == before + 1


    @pytest.mark.parametrize(
        "case",
        [
            "same_object",
            "equal_copy",
            "append_only",
            "reordered",
            "ties_at_top_k",
            "few_movers",
            "poseidon_collision",
            "wide_column",
        ],
    )
    def test_matches_per_peer_walk(self, case):
        """The vectorized drift equals the per-peer walk it replaced,
        field by field (``l1`` to summation order), over chains of
        epochs that take the prefix and the search alignment."""
        from protocol_tpu.obs.watchers import ScoreDriftMonitor

        mon = ScoreDriftMonitor()
        prev = None
        for epoch, (hashes, scores) in enumerate(_drift_chain(case)):
            got = mon.observe(epoch, hashes, scores)
            want = _per_peer_drift(prev, hashes, scores, mon.top_k)
            prev = ([int(h) for h in hashes], [float(s) for s in scores])
            assert got["epoch"] == epoch and got["peers"] == len(hashes)
            for key in ("linf", "joined", "departed", "top_movers"):
                assert got[key] == want[key], (case, epoch, key)
            if want["l1"] is None:
                assert got["l1"] is None
            else:
                assert got["l1"] == pytest.approx(want["l1"], rel=1e-12, abs=0.0)
        assert prev is not None and epoch >= 2


def _per_peer_drift(prev, hashes, scores, top_k):
    """The drift monitor's per-peer walk as it stood before the
    alignment was vectorized: the oracle for the summary fields."""
    hashes = [int(h) for h in hashes]
    vals = [float(s) for s in scores]
    out = {"l1": None, "linf": None, "joined": 0, "departed": 0, "top_movers": []}
    if prev is None:
        return out
    prev_by_hash = dict(zip(prev[0], prev[1]))
    cur_set = set(hashes)
    deltas = []
    l1 = linf = 0.0
    for h, v in zip(hashes, vals):
        old = prev_by_hash.get(h)
        if old is None:
            out["joined"] += 1
            continue
        d = v - old
        l1 += abs(d)
        linf = max(linf, abs(d))
        deltas.append((abs(d), h, d))
    out["departed"] = sum(1 for h in prev[0] if h not in cur_set)
    out["l1"], out["linf"] = l1, linf
    deltas.sort(reverse=True)
    out["top_movers"] = [
        {"peer_hash": hex(h), "delta": d} for absd, h, d in deltas[:top_k] if absd > 0.0
    ]
    return out


def _drift_chain(case):
    """Four or more ``(peer hashes, scores)`` epochs for one case."""
    rng = np.random.default_rng(zlib.crc32(case.encode()))

    def scores(n):
        return rng.random(n).astype(np.float32)

    if case == "same_object":
        ids = list(range(500))
        return [(ids, scores(500)) for _ in range(4)]
    if case == "equal_copy":
        ids = [int(h) for h in rng.integers(1, 1 << 62, 400)]
        return [(list(ids), scores(400)) for _ in range(4)]
    if case == "append_only":
        ids = list(range(1000, 1300))
        chain = []
        for n in (300, 300, 340, 420):
            ids.extend(range(1000 + len(ids), 1000 + n))
            chain.append((ids[:n], scores(n)))
        return chain
    if case == "reordered":
        chain, ids = [], list(range(600))
        for _ in range(5):
            keep = [h for h in ids if rng.random() > 0.1]
            ids = keep + [int(h) for h in rng.integers(10**6, 10**7, 40)]
            rng.shuffle(ids)
            chain.append((list(ids), scores(len(ids))))
        return chain
    if case == "ties_at_top_k":
        # Exact binary steps: 4 peers move by 0.5 and 13 by 0.25, so the
        # 10th largest |d| is a tie that six places cannot all take.
        ids = [int(h) for h in rng.permutation(np.arange(100, 140))]
        base = np.full(40, 1.0)
        step = np.zeros(40)
        step[:4] = 0.5
        step[4:17] = 0.25
        sign = np.where(rng.random(40) < 0.5, -1.0, 1.0)
        moved = base + sign * step
        return [(ids, base), (ids, moved), (ids[::-1], moved[::-1]), (ids, base)]
    if case == "few_movers":
        ids = list(range(50))
        still = np.linspace(0.1, 0.9, 50)
        moved = still.copy()
        moved[[3, 17, 41]] += [1e-3, -2e-3, 5e-4]
        return [(ids, still), (ids, moved), (list(ids), moved), (ids, still)]
    if case == "poseidon_collision":
        big = [int.from_bytes(rng.bytes(32), "big") >> 2 for _ in range(200)]
        a = big[0]
        b = a ^ (1 << 200)  # the same low 64 bits as ``a``
        assert (a ^ b) & ((1 << 64) - 1) == 0 and a != b
        first = big
        second = [b] + big[1:150] + big[180:] + [int.from_bytes(rng.bytes(32), "big") >> 2]
        third = [a] + second  # both colliding hashes present
        fourth = list(third)
        rng.shuffle(fourth)
        return [(c, scores(len(c))) for c in (first, second, third, fourth)]
    if case == "wide_column":
        # 40 blocks of the monitor's 1024: eight large movers in the last
        # block and 30 exact ties at 2**-7 spread over the others, under
        # small noise everywhere, so the top ten take two of the ties.
        n = 40_000
        ids = list(range(n))
        base = 0.5 + 0.25 * rng.random(n)
        moved = base + 1e-6 * rng.standard_normal(n)
        moved[-8:] = base[-8:] + 0.01 + 0.09 * rng.random(8)
        tied = rng.choice(n - 1024, 30, replace=False)
        moved[tied] = base[tied] + 2.0**-7
        return [(ids, base), (ids, moved), (ids, base)]
    raise AssertionError(case)


# ---------------------------------------------------------------------------
# New node endpoints: /scores/drift and /debug/flight
# ---------------------------------------------------------------------------


class TestDeepAttributionEndpoints:
    def test_drift_endpoint_after_tick(self):
        from protocol_tpu.obs.watchers import DRIFT

        DRIFT.reset()
        m = _ticked_manager()
        status, body = handle_request("GET", "/scores/drift", m)
        assert status == 200
        drift = json.loads(body)
        assert drift["epoch"] == 4
        assert drift["peers"] == 5
        assert "stalled" in drift and "top_movers" in drift

    def test_flight_endpoint_serves_jsonl_tail(self):
        m = _ticked_manager()
        status, body = handle_request("GET", "/debug/flight", m)
        assert status == 200
        events = [json.loads(line) for line in body.splitlines() if line]
        assert events, "flight recorder empty after a full tick"
        kinds = {e["kind"] for e in events}
        assert "span" in kinds
        status, limited = handle_request("GET", "/debug/flight?n=3", m)
        assert status == 200
        assert len(limited.splitlines()) == 3
        status, _ = handle_request("GET", "/debug/flight?n=bogus", m)
        assert status == 400

    def test_flight_tail_replays_ingest_rejection(self):
        from protocol_tpu.crypto.eddsa import SecretKey, sign
        from protocol_tpu.obs import JOURNAL
        from tests.test_node import make_attestation

        bad_sig = make_attestation(1)
        bad_sig.sig = sign(SecretKey.random(), SecretKey.random().public(), 1)
        m = Manager()
        m.add_attestations_bulk([bad_sig])
        rejects = [
            e for e in JOURNAL.tail() if e["kind"] == "ingest-reject"
        ]
        assert rejects and rejects[-1]["reason"] == "bad-signature"


# ---------------------------------------------------------------------------
# Prover-internal spans (deep attribution)
# ---------------------------------------------------------------------------


class TestProverSubSpans:
    def test_attach_closed_hangs_child_under_current_span(self):
        tracer = Tracer()
        with tracer.epoch(21):
            with tracer.span("snark"):
                sp = tracer.attach_closed("msm", 0.125, calls=7)
                assert sp is not None
        tree = tracer.get_trace(21)
        (snark,) = tree["children"]
        (msm,) = snark["children"]
        assert msm["name"] == "msm"
        assert msm["duration_s"] == 0.125
        assert msm["attrs"]["calls"] == 7
        assert msm["start_offset_s"] >= 0

    def test_attach_closed_without_open_span_is_noop(self):
        tracer = Tracer()
        assert tracer.attach_closed("msm", 1.0) is None

    def test_plonk_prove_attributes_engine_and_stage_time(self):
        """The acceptance shape: snark -> named prover sub-spans with
        call counts, summing to (nearly) the whole snark span."""
        from protocol_tpu.zk import native as zk_native, plonk
        from tests.test_plonk import _mul_add_circuit

        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        with TRACER.epoch(31):
            with TRACER.span("prove"):
                with TRACER.span("snark"):
                    proof = plonk.prove(pk, cs, [17], seed=b"t")
        assert plonk.verify(pk.vk, [17], proof)
        tree = TRACER.get_trace(31)
        snark = tree["children"][0]["children"][0]
        by_name = {c["name"]: c for c in snark["children"]}
        expected = {"witness_gen", "commit", "quotient", "open", "transcript"}
        if zk_native.available():
            expected |= {"msm", "ntt"}
        assert expected <= set(by_name), sorted(by_name)
        assert len(by_name) >= 4
        for child in by_name.values():
            assert child["duration_s"] >= 0
            assert child["attrs"]["calls"] >= 1
        covered = sum(c["duration_s"] for c in snark["children"])
        assert covered <= snark["duration_s"] * 1.05  # disjoint, no double count
        assert covered >= snark["duration_s"] * 0.5  # attribution is substantial

    def test_native_phase_stats_accumulate_and_reset(self):
        from protocol_tpu.zk import native as zk_native

        if not zk_native.available():
            pytest.skip("native zk runtime unavailable")
        zk_native.reset_phase_stats()
        before = zk_native.phase_stats()
        assert before["msm"] == {"calls": 0, "seconds": 0.0}
        root = pow(5, (zk_native.R - 1) >> 2, zk_native.R)
        zk_native.ntt([1, 2, 3, 4], root)
        after = zk_native.phase_stats()
        assert after["ntt"]["calls"] == 1
        assert after["ntt"]["seconds"] >= 0
        delta = zk_native.phase_delta(before, after)
        assert delta["ntt"]["calls"] == 1 and delta["msm"]["calls"] == 0
