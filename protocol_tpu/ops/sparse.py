"""Sparse trust kernels: COO transpose-SpMV power iteration with
pre-trust damping.

The scaled generalization of the reference's 5×5 loop (SURVEY.md §5
"long-context" note): the local-trust matrix C is an edge list, and one
power step is

    t' = (1−α)·(Cᵀt + (Σ_{i dangling} t_i)·p) + α·p

— the EigenTrust paper's damped iteration, where p is the pre-trust
vector and dangling rows (peers with no valid outgoing scores) donate
their mass to p, the at-scale analog of filter_peers' redistribution
(circuit/src/native.rs:200-228).

TPU-first design notes: edges are pre-sorted by destination so the
gather-multiply-reduce lowers to ``segment_sum`` with
``indices_are_sorted=True`` (sequential HBM traffic, no random scatter);
the iteration runs under ``lax.while_loop`` with an L1 residual bound so
convergence detection happens on-device (no host sync per step).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis.budget import (
    CommBudget,
    GatherBudget,
    KernelBudget,
    MemBudget,
    declare,
    declare_comm,
    declare_mem,
)


def _compensated_cumsum(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Inclusive prefix sum in double-single (hi, lo) arithmetic via
    ``lax.associative_scan`` with a TwoSum combiner.

    A plain f32 cumsum over tens of millions of edges accumulates
    O(eps·√E) absolute error, which then cancels catastrophically when
    differencing at row pointers for small rows.  Tracking the rounding
    error in a second f32 lane recovers ~f64 accuracy while staying in
    the TPU's fast vector path (device f64 is emulated and slow)."""

    def two_sum(a, b):
        a_hi, a_lo = a
        b_hi, b_lo = b
        s = a_hi + b_hi
        bb = s - a_hi
        err = (a_hi - (s - bb)) + (b_hi - bb)
        return s, a_lo + b_lo + err

    hi, lo = lax.associative_scan(two_sum, (x, jnp.zeros_like(x)))
    return hi, lo


#: Edges per cumsum block in the hierarchical row-sum.  Within-block
#: prefix sums run as one vectorized cumsum along the minor axis; only
#: the ~E/2048 block totals need the compensated scan.
_ROWSUM_BLOCK = 2048


def _ds_add(ah, al, bh, bl):
    """Double-single addition (TwoSum + renormalize)."""
    s = ah + bh
    v = s - ah
    e = (ah - (s - v)) + (bh - v)
    e = e + al + bl
    hi = s + e
    lo = e - (hi - s)
    return hi, lo


def _ds_cumsum_axis1(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Inclusive double-single prefix sum along axis 1 via Hillis-Steele
    (log2(B) shifted vector adds — no sequential scan anywhere)."""
    hi, lo = x, jnp.zeros_like(x)
    b = x.shape[1]
    shift = 1
    while shift < b:
        sh = jnp.pad(hi, ((0, 0), (shift, 0)))[:, :-shift]
        sl = jnp.pad(lo, ((0, 0), (shift, 0)))[:, :-shift]
        hi, lo = _ds_add(hi, lo, sh, sl)
        shift <<= 1
    return hi, lo


def rowsum_sorted(contrib: jax.Array, row_ptr: jax.Array) -> jax.Array:
    """Per-row sums of dst-sorted edge contributions via a hierarchical
    double-single prefix sum: ``out[j] = sum(contrib[row_ptr[j] :
    row_ptr[j+1]])``.

    TPU scatter (what ``segment_sum`` lowers to) serializes on
    destination indices even when they are sorted.  Measured on the
    v5e at full bench scale (1M peers / 50M edges, 40 iters,
    PERF.md §1): the end-to-end COO
    segment_sum convergence runs 42.4 s vs 17.9 s for this cumsum
    formulation (2.4×); the op-level gap is larger at smaller scales
    (7.5× end-to-end at 200K peers / 10M edges).  Within each
    2048-edge block the prefix runs as a
    Hillis-Steele scan in (hi, lo) compensated arithmetic (vectorized
    over all blocks at once); block totals get the TwoSum
    ``associative_scan``; row sums difference the hi/lo lanes
    separately so the hi cancellation stays exact (Sterbenz) and the
    residual lives in lo."""
    e = contrib.shape[0]
    b = _ROWSUM_BLOCK
    n_blocks = -(-e // b)
    # jnp.pad, not zeros().at[:e].set(): the update-slice form lowers
    # to a real XLA scatter, which would break the scatter-free budget
    # the analyzer pins on the CSR/windowed steps (analysis/budget.py).
    padded = jnp.pad(contrib, (0, n_blocks * b - e))
    wh, wl = _ds_cumsum_axis1(padded.reshape(n_blocks, b))
    hi_in, lo_in = _compensated_cumsum(wh[:, -1] + wl[:, -1])
    # Exclusive block prefixes.
    zero = jnp.zeros(1, contrib.dtype)
    bhi = jnp.concatenate([zero, hi_in[:-1]])
    blo = jnp.concatenate([zero, lo_in[:-1]])
    # Inclusive prefix at index i-1 for every row pointer (i=0 -> 0).
    i = row_ptr - 1
    blk = jnp.clip(i // b, 0, n_blocks - 1)
    off = jnp.clip(i % b, 0, b - 1)
    ph, pl = _ds_add(bhi[blk], blo[blk], wh[blk, off], wl[blk, off])
    ph = jnp.where(i < 0, 0.0, ph)
    pl = jnp.where(i < 0, 0.0, pl)
    return (ph[1:] - ph[:-1]) + (pl[1:] - pl[:-1])


def power_step_csr(
    src: jax.Array,
    row_ptr: jax.Array,
    w: jax.Array,
    t: jax.Array,
    p: jax.Array,
    dangling: jax.Array,
    alpha: jax.Array | float,
) -> jax.Array:
    """One damped step in the gather-only CSR formulation:
    ``cᵀt[j] = rowsum_sorted(w · t[src], row_ptr)`` — the fast path for
    dst-sorted edge lists (no scatter anywhere)."""
    ct = rowsum_sorted(w * t[src], row_ptr)
    dangling_mass = jnp.sum(t * dangling)
    t_new = (1.0 - alpha) * (ct + dangling_mass * p) + alpha * p
    return t_new / jnp.sum(t_new)


def run_power_iteration(
    step_fn, t0: jax.Array, *, tol: float, max_iter: int,
    record_residuals: bool = False,
):
    """Shared on-device convergence driver: iterate ``step_fn`` under
    while_loop until the L1 residual drops below ``tol`` (or fori_loop
    for exactly ``max_iter`` fixed steps when ``tol <= 0``, the
    benchmark mode).  Used by every sparse/sharded convergence kernel so
    early-exit semantics can't drift between formulations.

    With ``record_residuals`` the loop additionally carries a
    ``(max_iter,)`` residual-history vector and writes each iteration's
    L1 residual into it *device-side* (``lax.dynamic_update_slice`` on
    the carry — not a scatter, not a callback, no host sync; the
    telemetry contract ``tests/test_obs.py`` pins against the jaxpr),
    returning ``(t, iterations, residual, history)``; callers fetch the
    history ONCE after convergence and slice ``history[:iterations]``.
    The score arithmetic is the identical op sequence either way, so
    instrumented and uninstrumented runs are bit-identical."""

    def cond(state):
        it = state[2]
        if record_residuals:
            # The body already reduced this iteration's residual for
            # the history write; reuse the carried scalar instead of
            # re-reducing — identical value, one O(n) pass per
            # iteration either way.
            resid = state[4]
        else:
            t, prev = state[0], state[1]
            resid = jnp.sum(jnp.abs(t - prev))
        return (it < max_iter) & ((it == 0) | (resid > tol))

    def body(state):
        t, _, it = state[:3]
        t_new = step_fn(t)
        if not record_residuals:
            return (t_new, t, it + 1)
        resid = jnp.sum(jnp.abs(t_new - t))
        hist = lax.dynamic_update_index_in_dim(state[3], resid, it, 0)
        return (t_new, t, it + 1, hist, resid)

    init = (t0, jnp.full_like(t0, jnp.inf), jnp.array(0, jnp.int32))
    if record_residuals:
        init = init + (
            jnp.zeros(max_iter, t0.dtype),
            jnp.array(jnp.inf, t0.dtype),
        )
    if tol <= 0:
        out = lax.fori_loop(0, max_iter, lambda _, s: body(s), init)
    else:
        out = lax.while_loop(cond, body, init)
    t, prev, it = out[:3]
    if record_residuals:
        return t, it, out[4], out[3]
    return t, it, jnp.sum(jnp.abs(t - prev))


@partial(
    jax.jit,
    static_argnames=("tol", "max_iter", "record_residuals"),
    donate_argnames=("t0",),
)
def converge_csr(
    src: jax.Array,
    row_ptr: jax.Array,
    w: jax.Array,
    t0: jax.Array,
    p: jax.Array,
    dangling: jax.Array,
    *,
    alpha: jax.Array | float = 0.1,
    tol: float = 1e-6,
    max_iter: int = 50,
    record_residuals: bool = False,
) -> tuple[jax.Array, ...]:
    """CSR/cumsum analog of ``converge_sparse``.  ``t0`` is donated:
    the iteration consumes the initial vector in place (4 MB saved at
    the 1M-peer shape), so callers must pass a fresh buffer.
    ``record_residuals`` appends the device-side residual history to
    the returned tuple (see ``run_power_iteration``)."""
    return run_power_iteration(
        lambda t: power_step_csr(src, row_ptr, w, t, p, dangling, alpha),
        t0,
        tol=tol,
        max_iter=max_iter,
        record_residuals=record_residuals,
    )


def power_step_coo(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    t: jax.Array,
    p: jax.Array,
    dangling: jax.Array,
    alpha: jax.Array | float,
    *,
    n: int,
    sorted_by_dst: bool = True,
) -> jax.Array:
    """One damped transpose-SpMV step (edge arrays may be zero-padded:
    pad edges with w=0)."""
    contrib = w * t[src]
    ct = jax.ops.segment_sum(
        contrib, dst, num_segments=n, indices_are_sorted=sorted_by_dst
    )
    dangling_mass = jnp.sum(t * dangling)
    t_new = (1.0 - alpha) * (ct + dangling_mass * p) + alpha * p
    # L1 renorm guards against floating-point drift over many iterations.
    return t_new / jnp.sum(t_new)


@partial(
    jax.jit,
    static_argnames=("n", "tol", "max_iter", "sorted_by_dst", "record_residuals"),
    donate_argnames=("t0",),
)
def converge_sparse(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    t0: jax.Array,
    p: jax.Array,
    dangling: jax.Array,
    *,
    n: int,
    alpha: jax.Array | float = 0.1,
    tol: float = 1e-6,
    max_iter: int = 50,
    sorted_by_dst: bool = True,
    record_residuals: bool = False,
) -> tuple[jax.Array, ...]:
    """Iterate to an L1 fixed point; returns ``(t, iterations,
    residual)``.  ``tol <= 0`` runs exactly ``max_iter`` steps (the
    benchmarking mode — fixed work, no early exit).  ``alpha`` is a
    traced operand so damping sweeps reuse one compiled kernel.
    ``t0`` is donated — pass a fresh buffer.  ``record_residuals``
    appends the device-side residual history to the returned tuple."""
    return run_power_iteration(
        lambda t: power_step_coo(
            src, dst, w, t, p, dangling, alpha, n=n, sorted_by_dst=sorted_by_dst
        ),
        t0,
        tol=tol,
        max_iter=max_iter,
        record_residuals=record_residuals,
    )


# ---------------------------------------------------------------------------
# Pinned kernel invariants (PERF.md §9) — checked per step by
# `python -m protocol_tpu.analysis` against the traced jaxpr.
# ---------------------------------------------------------------------------

#: COO segment-sum step: one random ``t[src]`` gather; the scatter-add
#: is the formulation (segment_sum) and is capped at exactly one.
declare(
    KernelBudget(
        backend="tpu-sparse",
        max_random_gathers=1,
        max_scatters=1,
        gather_budgets=(GatherBudget(dim="edges", max_total=1, max_random=1),),
        donated_args=("t0",),
        notes="segment_sum SpMV: 1 random E-gather + 1 sorted scatter-add",
    )
)

#: Gather-only CSR/cumsum step: one random ``t[src]`` gather plus the
#: four (n+1)-sized block-prefix lookups of ``rowsum_sorted`` — and no
#: scatter anywhere (the formulation's reason to exist; PERF.md §1
#: measured segment_sum 2.4× slower end-to-end at the bench shape).
declare(
    KernelBudget(
        backend="tpu-csr",
        max_random_gathers=5,
        max_scatters=0,
        gather_budgets=(GatherBudget(dim="edges", max_total=1, max_random=1),),
        donated_args=("t0",),
        notes="scatter-free CSR: 1 random E-gather + 4 rowsum pointer reads",
    )
)

# -- communication budgets (PERF.md §15, graftlint pass 8) ------------------
# Single-device steps: zero collectives, zero host round-trips, and the
# t0 donation of the jit entry must survive into the compiled module's
# input_output_alias table (a dropped alias doubles peak HBM at 1M
# peers and ships silently — the jaxpr cannot see it).

declare_comm(
    CommBudget(
        backend="tpu-sparse",
        donated_args=("t0",),
        notes="single-device segment-sum loop: no wire, no host traffic",
    )
)

declare_comm(
    CommBudget(
        backend="tpu-csr",
        donated_args=("t0",),
        notes="single-device CSR/cumsum loop: no wire, no host traffic",
    )
)

# -- memory budgets (PERF.md §19, graftlint pass 12) ------------------------
# Measured from the buffer assignment at the analyzer's compile scale
# (N=1024/E=4073); the committed slack is below a 4 B/edge live
# temporary, pinned by test.  The single-device kernels' E-sized
# working vectors (the ``w * t[src]`` contribution stream and its
# reduction passes) are declared in the transient_n coefficient: at
# the pinned compile scale E ≈ 4N, and the slack test keeps the
# coefficient honest — a SECOND edge-sized live buffer busts it.

declare_mem(
    MemBudget(
        backend="tpu-sparse",
        resident_edge_bytes=12.0,  # src + dst + w
        resident_n=12.0,  # t0 + p + dangling
        resident_const=4096.0,
        transient_n=36.0,  # contribution stream + segment_sum passes
        transient_const=8192.0,
        donated_args=("t0",),
        notes="segment-sum SpMV: COO triplet resident, E-working set in "
        "the scatter-add loop",
    )
)

declare_mem(
    MemBudget(
        backend="tpu-csr",
        resident_edge_bytes=8.0,  # src + w (row_ptr rides resident_n)
        resident_n=16.0,  # t0 + p + dangling + (n+1) row pointers
        resident_const=4096.0,
        transient_n=430.0,  # contribution stream + cumsum scan levels
        transient_const=8192.0,
        donated_args=("t0",),
        notes="scatter-free CSR: the compensated-cumsum rowsum streams "
        "log-depth scan levels over the contribution vector (E ~ 4N at "
        "the pinned scale)",
    )
)
