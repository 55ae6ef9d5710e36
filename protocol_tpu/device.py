"""Where the program meets the device: the Pallas execution mode and the
persistent compilation cache.

Both used to be decided ad hoc at each call site.  A site that ran the
Pallas kernel interpreted whenever the platform was not literally
``"tpu"`` silently interpreted it on any other accelerator platform
name, so a chip run measured the interpreter.  Here the decision is
made once, and an unknown platform is an error.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

#: The fixed cache path used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: the path is part of the cache key, so it must not move between runs.
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".cache" / "jax"


def pallas_interpret() -> bool:
    """Whether Pallas kernels run interpreted on the default backend:
    compiled on ``tpu``, interpreted on ``cpu`` (tests, rehearsals),
    and an error anywhere else — never a silent interpreter fallback."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas path for JAX platform {platform!r}: the windowed "
        "kernel compiles for 'tpu' and is interpreted only on 'cpu'"
    )


def pin_to_host() -> None:
    """Keep this process off the accelerator.  For worker processes that
    do no device work: a chip belongs to one process, and the parent
    that spawned the worker already holds it."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:  # imported, but no backend initialized yet
        jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.  A cache directory
    given by ``JAX_COMPILATION_CACHE_DIR`` is left as JAX read it;
    otherwise the cache lives at :data:`COMPILE_CACHE_DIR`."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    COMPILE_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


__all__ = [
    "COMPILE_CACHE_DIR",
    "enable_compile_cache",
    "pallas_interpret",
    "pin_to_host",
]
