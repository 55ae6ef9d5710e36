"""LICM-defeated component breakdown of the headline bench step.

Every loop body depends on the carry so WhileLoopInvariantCodeMotion
cannot hoist the op being measured — without this, XLA hoists any
loop-invariant gather and the "benchmark" times dispatch overhead
(PERF.md §1 documents both the trap and the numbers).

Recorded output (TPU v5 lite, 2026-07-29, before PR 1):

    gather 50M (dep): 386.08 ms/iter  (3089 ms total)
    w*gather 50M (dep): 385.75 ms/iter  (3086 ms total)
    rowsum_sorted 50M (dep): 65.68 ms/iter  (525 ms total)
    50M elementwise mul (dep): 8.81 ms/iter  (71 ms total)

Conclusion: the bench is gather-op-bound (86 % of the 447 ms step).
"""
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from protocol_tpu.ops.sparse import rowsum_sorted

rng = np.random.default_rng(0)
E, N = 50_000_000, 1_000_000
REPS = 8


def timeit(name, fn, *args, reps=2, per=REPS):
    f = jax.jit(fn)
    r = np.asarray(jax.tree.leaves(f(*args))[0])
    t0 = time.perf_counter()
    for _ in range(reps):
        r = np.asarray(jax.tree.leaves(f(*args))[0])
    dt = (time.perf_counter() - t0) / reps
    print(f"{name}: {dt/per*1e3:.2f} ms/iter  ({dt*1e3:.0f} ms total)", flush=True)


t_full = jax.device_put(jnp.asarray(rng.random(N, dtype=np.float32)))
src = jax.device_put(jnp.asarray(rng.integers(0, N, E).astype(np.int32)))
w = jax.device_put(jnp.asarray(rng.random(E, dtype=np.float32)))
contrib = jax.device_put(jnp.asarray(rng.random(E, dtype=np.float32)))
row_ptr = jax.device_put(jnp.asarray(
    np.searchsorted(np.sort(rng.integers(0, N, E)), np.arange(N + 1)).astype(np.int32)))

EPS = jnp.float32(1e-38)

def dep_chain(body):
    """body(x_perturbed, *args) -> array; carry a scalar that perturbs
    the input each iteration so nothing is loop-invariant."""
    def run(*args):
        def step(_, acc):
            return body(acc * EPS, *args)
        return lax.fori_loop(0, REPS, step, jnp.float32(0))
    return run

timeit("gather 50M (dep)", dep_chain(lambda d, t, s: (t + d)[s].max()), t_full, src)
timeit("w*gather 50M (dep)", dep_chain(lambda d, t, s, w: (w * (t + d)[s]).max()), t_full, src, w)
timeit("rowsum_sorted 50M (dep)", dep_chain(
    lambda d, c, rp: rowsum_sorted(c + d, rp).max()), contrib, row_ptr)
timeit("50M elementwise mul (dep)", dep_chain(lambda d, c, w: ((c + d) * w).max()), contrib, w)
