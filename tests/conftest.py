"""Test configuration: force an 8-device virtual CPU mesh.

Sharded-backend tests exercise real `jax.sharding.Mesh` layouts without
TPU hardware, per SURVEY.md §4 rebuild test doctrine (tier 5).  The
environment is set before JAX is imported, so every backend initializes
on it.  chip_smoke.py is what runs on the real chip.
"""

import os
import pathlib

# Keep the PLONK keygen cache repo-local: tests must not write pickles
# into (or silently reuse stale keys from) the developer's home cache.
os.environ.setdefault(
    "PROTOCOL_TPU_CACHE",
    str(pathlib.Path(__file__).resolve().parent.parent / ".cache" / "protocol_tpu"),
)

_platform = os.environ.get("PROTOCOL_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from protocol_tpu.device import enable_compile_cache  # noqa: E402

# The zk.graft proving kernels are large traced programs (an EC add
# inlines 16 Montgomery multiplies); XLA:CPU takes tens of seconds per
# (shape, kernel) pair.  Persist compiled executables so repeat test
# runs pay compilation once per machine.
enable_compile_cache()

#: Modules run under ``jax.transfer_guard("disallow")``: the
#: backend-equivalence suites, where an implicit host transfer means a
#: per-call (or worse, per-iteration) sync hiding in a hot path — the
#: runtime counterpart of the analyzer's bare-sync/host-op AST rules.
#: Explicit staging (``jnp.asarray``/``device_put``/``device_get``)
#: stays legal; tests that legitimately rely on implicit transfers opt
#: out with ``@pytest.mark.allow_transfer``.
_TRANSFER_GUARDED = {"test_trust_backends", "test_windowed_pipeline"}


@pytest.fixture(autouse=True)
def _no_implicit_transfers(request):
    module = getattr(request.node, "module", None)
    name = getattr(module, "__name__", "").rpartition(".")[2]
    if name not in _TRANSFER_GUARDED or request.node.get_closest_marker(
        "allow_transfer"
    ):
        yield
        return
    with jax.transfer_guard("disallow"):
        yield
