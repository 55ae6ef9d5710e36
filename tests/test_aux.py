"""Auxiliary subsystems: checkpoint/resume, /status (SURVEY.md §5)."""

import json

import numpy as np

from protocol_tpu.models.graphs import erdos_renyi
from protocol_tpu.node.checkpoint import CheckpointStore
from protocol_tpu.node.epoch import Epoch
from protocol_tpu.node.manager import Manager, ManagerConfig
from protocol_tpu.node.server import handle_request


class TestCheckpointStore:
    def test_save_load_roundtrip(self, tmp_path):
        store = CheckpointStore(tmp_path)
        g = erdos_renyi(100, avg_degree=4.0, seed=1)
        scores = np.linspace(0, 1, 100)
        store.save(Epoch(5), g, scores)

        snap = store.load_latest()
        assert snap.epoch == Epoch(5)
        assert snap.graph.n == g.n
        np.testing.assert_array_equal(snap.graph.src, g.src)
        np.testing.assert_array_equal(snap.graph.weight, g.weight)
        np.testing.assert_array_equal(snap.graph.pre_trusted, g.pre_trusted)
        np.testing.assert_allclose(snap.scores, scores)

    def test_latest_tracks_manifest(self, tmp_path):
        store = CheckpointStore(tmp_path)
        g = erdos_renyi(20, seed=2)
        store.save(Epoch(1), g)
        store.save(Epoch(3), g)
        store.save(Epoch(2), g)  # out-of-order write
        assert store.load_latest().epoch == Epoch(2)  # manifest wins

    def test_prune_keeps_recent(self, tmp_path):
        store = CheckpointStore(tmp_path, keep=2)
        g = erdos_renyi(10, seed=3)
        for e in range(5):
            store.save(Epoch(e), g)
        assert sorted(store.epochs()) == [3, 4]

    def test_empty_dir(self, tmp_path):
        assert CheckpointStore(tmp_path).load_latest() is None

    def test_scores_optional(self, tmp_path):
        store = CheckpointStore(tmp_path)
        store.save(Epoch(0), erdos_renyi(10, seed=4))
        assert store.load_latest().scores is None

    def test_node_restores_proof_from_checkpoint(self, tmp_path):
        """Restart path: a new node serves the checkpointed proof before
        any epoch has run (SURVEY.md §5 checkpoint/resume doctrine)."""
        import asyncio

        from protocol_tpu.node.config import ProtocolConfig
        from protocol_tpu.node.server import Node
        from protocol_tpu.zk.proof import ProofRaw

        m = Manager(ManagerConfig(prover="commitment"))
        m.generate_initial_attestations()
        m.calculate_proofs(Epoch(41))
        store = CheckpointStore(tmp_path)
        store.save(
            Epoch(41),
            m.build_graph(),
            None,
            m.get_proof(Epoch(41)).to_raw().to_json(),
        )

        async def scenario():
            cfg = ProtocolConfig(
                epoch_interval=3600,
                endpoint=((127, 0, 0, 1), 0),
                checkpoint_dir=str(tmp_path),
                prover="commitment",
            )
            node = Node.from_config(cfg)
            await node.start()
            status, body = handle_request("GET", "/score", node.manager)
            await node.stop()
            return status, body

        status, body = asyncio.run(scenario())
        assert status == 200
        assert ProofRaw.from_json(body).to_proof().pub_ins == m.get_proof(Epoch(41)).pub_ins


class TestTelemetry:
    def test_status_endpoint(self):
        m = Manager(ManagerConfig(prover="commitment"))
        m.generate_initial_attestations()
        m.calculate_proofs(Epoch(9))
        status, body = handle_request("GET", "/status", m)
        assert status == 200
        obj = json.loads(body)
        assert obj["attestations"] == 5
        assert obj["cached_proofs"] == 1
        assert obj["latest_epoch"] == 9
        assert obj["backend"] == "native-cpu"
        # Phase timings are served by /metrics (eigentrust_phase_seconds)
        # and /trace, not by a second timer registry here.
        assert "telemetry" not in obj
