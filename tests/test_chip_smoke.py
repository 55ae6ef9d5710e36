"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases hold at a tiny size on the CPU (the Pallas kernel
interpreted), so the script cannot rot between chip runs."""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.fixture(scope="module")
def small():
    clock = chip_smoke.CompileClock()
    graph = chip_smoke.config4_graph(4096, 32768)
    return graph, chip_smoke.reference_scores(graph, clock), clock


def test_windowed_and_churned_epochs(small, capsys):
    graph, reference, clock = small
    chip_smoke.windowed_epochs(graph, reference, clock, expect_kernel=False)
    out = capsys.readouterr().out
    assert '"delta_path": true' in out and '"recompiles": {}' in out


def test_sharded_epoch_on_a_4_device_mesh(small, capsys, monkeypatch):
    from protocol_tpu.parallel import mesh

    graph, reference, clock = small
    four = mesh.default_mesh(4)
    monkeypatch.setattr(mesh, "default_mesh", lambda *a, **k: four)
    chip_smoke.sharded_epoch(graph, reference, clock, 4, expect_kernel=False)
    assert '"all-reduce": 1' in capsys.readouterr().out


def test_node_epoch(capsys):
    # The wait only bounds a hang: alone on a CPU the proven epoch
    # takes about 36 s, and parallel test workers share the cores.
    asyncio.run(chip_smoke.node_epoch(interval=2, timeout_s=240.0))
    assert '"backend": "tpu-windowed"' in capsys.readouterr().out
