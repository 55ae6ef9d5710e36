"""The device boundary (``protocol_tpu/device.py``): the Pallas mode is
decided once and never falls back silently; worker processes stay off
the chip; the compile cache honours ``JAX_COMPILATION_CACHE_DIR``; and
the node refuses a prover pool that would fight it for the device."""

from __future__ import annotations

import os

import jax
import pytest

from protocol_tpu import device
from protocol_tpu.node.config import ProtocolConfig
from protocol_tpu.node.manager import Manager, ManagerConfig
from protocol_tpu.node.server import Node


class TestPallasInterpret:
    def test_cpu_interprets(self):
        assert jax.default_backend() == "cpu"
        assert device.pallas_interpret() is True

    def test_tpu_compiles(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert device.pallas_interpret() is False

    @pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "METAL"])
    def test_other_platforms_raise(self, monkeypatch, platform):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        with pytest.raises(RuntimeError, match=platform):
            device.pallas_interpret()


class TestCompileCache:
    def test_env_dir_is_left_alone(self, monkeypatch, tmp_path):
        updates = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
        device.enable_compile_cache()
        assert updates == []

    def test_default_dir_is_fixed_under_the_repo(self, monkeypatch):
        updates = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
        device.enable_compile_cache()
        assert updates == [("jax_compilation_cache_dir", str(device.COMPILE_CACHE_DIR))]
        assert device.COMPILE_CACHE_DIR.parts[-2:] == (".cache", "jax")


def test_pin_to_host_sets_cpu(monkeypatch):
    updates = []
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    device.pin_to_host()
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert updates == [("jax_platforms", "cpu")]


class TestGraftProverPool:
    def _node(self, **cfg):
        manager = Manager(ManagerConfig(prover="commitment", zk_backend="graft"))
        return Node(config=ProtocolConfig(prover="commitment", **cfg), manager=manager)

    def test_refused_with_spawned_prover_workers(self):
        with pytest.raises(ValueError, match="prover_workers=0"):
            self._node(async_prover=True, prover_workers=1)

    @pytest.mark.parametrize(
        "cfg",
        [
            {"async_prover": True, "prover_workers": 0},
            {"async_prover": False, "prover_workers": 1},
        ],
    )
    def test_in_process_proving_is_accepted(self, cfg):
        assert self._node(**cfg).manager.config.zk_backend == "graft"
