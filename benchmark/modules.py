"""Modules the harness finds by name: a per-layer metric's reader in
``benchmark/metrics``, a graph kind's generator in ``benchmark/graphs``."""

from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType


def load_module(path: Path, prefix: str) -> ModuleType:
    """The module in the file ``path``, named ``prefix`` plus its stem
    with ``.`` and ``-`` made ``_``."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
