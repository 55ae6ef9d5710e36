"""Measure the windowed Pallas gather (ops/gather_window.py) against
the XLA gather at bench scale on the real chip — run it through the
chip tool (PERF.md §6 queue).

Expected from the primitive measurements (PERF.md §1): ~30 vreg ops per
1024 edges ⇒ low single-digit ms per 50M-edge pass plus ~600 MB HBM
streaming, vs 386 ms for the XLA gather.  Output lands in PERF.md.
"""

import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from protocol_tpu.ops.gather_window import bucket_by_window, gather_windowed

E, N = 50_000_000, 1_048_576
rng = np.random.default_rng(0)
src = rng.integers(0, N, E).astype(np.int32)
w = rng.random(E, dtype=np.float32)
t = rng.random(N, dtype=np.float32)

print("bucketing (host, one-time)...", flush=True)
t0 = time.perf_counter()
b = bucket_by_window(src, w, table_size=N)
print(f"bucketed in {time.perf_counter()-t0:.1f}s, rows={b['n_rows']} "
      f"(pad {(b['n_rows']*1024 - E)/E*100:.2f}%)", flush=True)

wid = jax.device_put(jnp.asarray(b["wid"]))
tbl = jax.device_put(jnp.asarray(t))
loc = jax.device_put(jnp.asarray(b["local"]))
wgt = jax.device_put(jnp.asarray(b["weight"]))

REPS = 8
eps = jnp.float32(1e-38)


@jax.jit
def chain_windowed(wid, tbl, loc, wgt):
    def step(_, acc):
        out = gather_windowed(
            wid, tbl + acc * eps, loc, wgt, n_rows=b["n_rows"], table_chunk=b["table_chunk"]
        )
        return out[0, 0]
    return lax.fori_loop(0, REPS, step, jnp.float32(0))


@jax.jit
def chain_xla(tbl, src, w):
    def step(_, acc):
        return ((tbl + acc * eps)[src] * w).max()
    return lax.fori_loop(0, REPS, step, jnp.float32(0))


src_d = jax.device_put(jnp.asarray(src))
w_d = jax.device_put(jnp.asarray(w))

for name, fn, args in [
    ("windowed pallas", chain_windowed, (wid, tbl, loc, wgt)),
    ("xla gather", chain_xla, (tbl, src_d, w_d)),
]:
    r = np.asarray(fn(*args))
    t0 = time.perf_counter()
    for _ in range(2):
        r = np.asarray(fn(*args))
    dt = (time.perf_counter() - t0) / 2 / REPS
    print(f"{name}: {dt*1e3:.1f} ms per 50M-edge gather pass", flush=True)
