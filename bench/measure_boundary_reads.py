"""Measure the fused pipeline's boundary-read patterns on the real
chip (PERF.md §8) — run it through the chip tool.

PERF.md §7 left one open variable: whether gathers over *graph-static*
indices (host-precomputed, loop-invariant) run at §1's op-bound ~7.2
cycles/element like data-dependent random gathers, or stream.  This
probe measures, at the bench graph's boundary shape (S = 14.7M runs
over an L = 50.5M-slot prefix array):

1. the v1 bridge pattern — 4 separate 1-wide gathers at dst-sorted
   (random-order) run boundaries (hi/lo lanes at start−1 and end);
2. the v2 bridge pattern — one 2-wide slice gather at bucket-order
   (strictly increasing) run ends with ``indices_are_sorted=True``,
   adjacent differencing (a shift, no gather), then the single
   n_segments dst permutation — the only random pass;
3. isolation probes: the sorted 2-wide gather alone, the random
   permutation alone, and a data-dependent-index control (same index
   values, but derived from the loop carry so XLA cannot treat them as
   loop-invariant).

Timing-loop doctrine (PERF.md §1): every measured op carries a data
dependence on the loop state through its *operand* (``+ acc * eps``) so
WhileLoopInvariantCodeMotion can't hoist it; the indices stay
loop-invariant — that is exactly the graph-static pattern under test —
except in the control, which threads the carry through the index array
via a select.  The operand dep-chain add is a full-array elementwise
pass (~0.5 ms at the v5e's HBM bandwidth), so every number is a slight
over-estimate — an upper bound, like the rest of PERF.md.
"""

import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

S = 14_700_000  # bench-graph n_segments (PERF.md §7)
L = 49_344 * 1024  # bench-graph slot count (n_rows * ROW)
REPS = 8
eps = jnp.float32(1e-38)

rng = np.random.default_rng(0)
hi = rng.random(L, np.float32)
lo = rng.random(L, np.float32) * 1e-7
# Bucket-order run ends: strictly increasing slots (v2 layout).
ends_sorted = np.sort(rng.choice(L, S, replace=False)).astype(np.int32)
first = np.zeros(S, bool)
first[0] = True
first[1:] = (ends_sorted[1:] >> 10) != (ends_sorted[:-1] >> 10)
# dst permutation of the partials (v2) / dst-sorted boundaries (v1).
perm = rng.permutation(S).astype(np.int32)
starts_v1 = np.maximum(ends_sorted - 3, 0)[perm]
ends_v1 = ends_sorted[perm]

hi_d = jax.device_put(jnp.asarray(hi))
lo_d = jax.device_put(jnp.asarray(lo))
cum2_d = jax.device_put(jnp.stack([jnp.asarray(hi), jnp.asarray(lo)], axis=-1))
ends_d = jax.device_put(jnp.asarray(ends_sorted))
first_d = jax.device_put(jnp.asarray(first))
perm_d = jax.device_put(jnp.asarray(perm))
starts_v1_d = jax.device_put(jnp.asarray(starts_v1))
ends_v1_d = jax.device_put(jnp.asarray(ends_v1))


@jax.jit
def chain_v1(hi, lo, starts, ends):
    """4 × 1-wide static-index random gathers (the pre-§8 bridge)."""

    def step(_, acc):
        h, l = hi + acc * eps, lo + acc * eps
        partial = (h[ends] - h[starts]) + (l[ends] - l[starts])
        return partial[0]

    return lax.fori_loop(0, REPS, step, jnp.float32(0))


@jax.jit
def chain_v2(cum2, ends, first, perm):
    """1 × 2-wide sorted gather + shift + 1 × random permutation."""

    def step(_, acc):
        e = (cum2 + acc * eps).at[ends].get(
            indices_are_sorted=True, unique_indices=True
        )
        eh, el = e[:, 0], e[:, 1]
        zero = jnp.zeros(1, eh.dtype)
        ph = jnp.where(first, 0.0, jnp.concatenate([zero, eh[:-1]]))
        pl = jnp.where(first, 0.0, jnp.concatenate([zero, el[:-1]]))
        partial = (eh - ph) + (el - pl)
        return partial[perm][0]

    return lax.fori_loop(0, REPS, step, jnp.float32(0))


@jax.jit
def chain_sorted_only(cum2, ends):
    def step(_, acc):
        e = (cum2 + acc * eps).at[ends].get(
            indices_are_sorted=True, unique_indices=True
        )
        return e[0, 0]

    return lax.fori_loop(0, REPS, step, jnp.float32(0))


@jax.jit
def chain_random_only(hi, ends):
    def step(_, acc):
        return (hi + acc * eps)[ends][0]

    return lax.fori_loop(0, REPS, step, jnp.float32(0))


@jax.jit
def chain_data_dependent(hi, ends):
    """Control: identical index values, but the index array is derived
    from the loop carry (a select XLA cannot fold), so the compiler
    must treat them as data-dependent every iteration."""

    def step(_, acc):
        idx = jnp.where(acc > -1.0, ends, ends[::-1])
        return (hi + acc * eps)[idx][0]

    return lax.fori_loop(0, REPS, step, jnp.float32(0))


for name, fn, args in [
    ("v1 bridge: 4x 1-wide random static-idx", chain_v1,
     (hi_d, lo_d, starts_v1_d, ends_v1_d)),
    ("v2 bridge: 2-wide sorted + 1 permutation", chain_v2,
     (cum2_d, ends_d, first_d, perm_d)),
    ("sorted 2-wide gather alone", chain_sorted_only, (cum2_d, ends_d)),
    ("random 1-wide gather alone (static idx)", chain_random_only,
     (hi_d, ends_v1_d)),
    ("random 1-wide gather alone (data-dep idx)", chain_data_dependent,
     (hi_d, ends_v1_d)),
]:
    r = np.asarray(fn(*args))  # compile + warm up
    t0 = time.perf_counter()
    for _ in range(2):
        r = np.asarray(fn(*args))
    dt = (time.perf_counter() - t0) / 2 / REPS
    print(f"{name}: {dt * 1e3:.1f} ms per {S / 1e6:.1f}M-boundary pass", flush=True)
