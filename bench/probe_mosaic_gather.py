"""Probe the Mosaic dynamic_gather support envelope: axis1 (lane)
range scaling, axis0 (sublane) shapes, transposes, and XLA gather
speed vs table size.

Recorded output (TPU v5 lite, jax 0.9.0, 2026-07-29, before PR 1;
compile/crash envelope only — the per-op timings of that run were
dispatch-dominated and are superseded):

    axis1 (8192,128) range=128: compiles, correct
    axis1 range 1024/8192/16384/131072/1048576: Mosaic compiler crash
    axis0 (8,128) range=8: compiles, correct
    axis0 range 64/256/1024/8192: Mosaic compiler crash
    transpose (128,8192) and (8192,128): compile, correct
    XLA gather 8M indices: table-size independent (op-bound)

Timing-loop doctrine (PERF.md §1): the original per-dispatch loops here
measured that setup's ~70 ms per-dispatch overhead, not the op.  Every timing below
now runs REPS dependent iterations inside one jit — the measured op's
operand is perturbed by the loop carry (the ``dep_chain`` pattern from
``bench/profile_components.py``) so ``WhileLoopInvariantCodeMotion``
cannot hoist the body, and the op output feeds the carry so nothing is
dead.  pallas_call is opaque to the algebraic simplifier, so a
one-element read of its output keeps the whole kernel live; the XLA
gather is consumed through a full-array ``max`` for the same reason.

Conclusion (PERF.md §1): cross-vreg dynamic gathers are unusable on
this toolchain, which rules out a VMEM-resident-table Pallas gather
for the 1M-entry score table.
"""
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from jax.experimental import pallas as pl

rng = np.random.default_rng(0)
REPS = 8
EPS = jnp.float32(1e-38)


def chain(body):
    """REPS dependent iterations of ``body(perturbation, *args)`` inside
    one jit: the scalar carry perturbs the measured op's operand (LICM
    can't hoist) and is fed from its output (DCE can't drop it)."""

    @jax.jit
    def run(*args):
        def step(_, acc):
            return body(acc * EPS, *args)

        return lax.fori_loop(0, REPS, step, jnp.float32(0))

    return run


def timed_chain(name, body, *args):
    f = chain(body)
    jax.block_until_ready(f(*args))  # compile + warm up
    t0 = time.perf_counter()
    for _ in range(2):
        r = jax.block_until_ready(f(*args))
    dt = (time.perf_counter() - t0) / 2 / REPS
    return r, dt


def bench_gather(axis, R, L):
    rng_hi = R if axis == 0 else L
    name = f"axis{axis} ({R},{L}) range={rng_hi}"
    try:
        t = jax.device_put(jnp.asarray(rng.random((R, L), dtype=np.float32)))
        idx = jax.device_put(jnp.asarray(rng.integers(0, rng_hi, (R, L)).astype(np.int32)))
        kernel = pl.pallas_call(
            lambda t_ref, i_ref, o_ref: o_ref.__setitem__(
                slice(None), jnp.take_along_axis(t_ref[:], i_ref[:], axis=axis)),
            out_shape=jax.ShapeDtypeStruct((R, L), jnp.float32),
        )
        # Correctness once, outside the timing chain.
        r = jax.block_until_ready(jax.jit(kernel)(t, idx))
        tn, ixn = np.asarray(t), np.asarray(idx)
        if axis == 0:
            exp = tn[ixn, np.arange(L)[None, :]]
        else:
            exp = tn[np.arange(R)[:, None], ixn]
        ok = np.array_equal(np.asarray(r), exp)
        _, dt = timed_chain(name, lambda d, t, i: kernel(t + d, i)[0, 0], t, idx)
        print(f"{name}: {dt*1e6:.1f} us  ({R*L/dt/1e9:.1f} Gelem/s)  correct={ok}", flush=True)
    except Exception as e:
        s = str(e).splitlines()
        print(f"{name}: FAILED — {type(e).__name__}: {s[0][:120] if s else ''}", flush=True)


print("== axis1 (lane gather) range scaling ==", flush=True)
for R, L in [(8192, 128), (1024, 1024), (128, 8192), (64, 16384), (8, 131072), (8, 1048576)]:
    bench_gather(1, R, L)

print("== axis0 (sublane gather) shapes ==", flush=True)
for R, L in [(8, 128), (64, 128), (256, 128), (1024, 128), (8192, 128)]:
    bench_gather(0, R, L)

print("== in-kernel transpose ==", flush=True)
for R, L in [(128, 8192), (8192, 128)]:
    try:
        t = jax.device_put(jnp.asarray(rng.random((R, L), dtype=np.float32)))
        kernel = pl.pallas_call(
            lambda t_ref, o_ref: o_ref.__setitem__(slice(None), t_ref[:].T),
            out_shape=jax.ShapeDtypeStruct((L, R), jnp.float32),
        )
        r = jax.block_until_ready(jax.jit(kernel)(t))
        ok = np.array_equal(np.asarray(r), np.asarray(t).T)
        _, dt = timed_chain(f"transpose ({R},{L})", lambda d, t: kernel(t + d)[0, 0], t)
        print(f"transpose ({R},{L}): {dt*1e6:.1f} us  correct={ok}", flush=True)
    except Exception as e:
        s = str(e).splitlines()
        print(f"transpose ({R},{L}): FAILED — {type(e).__name__}: {s[0][:120] if s else ''}", flush=True)

print("== XLA gather vs table size (8M indices) ==", flush=True)
E = 8_000_000
for tbl in [16384, 131072, 1048576]:
    t = jax.device_put(jnp.asarray(rng.random(tbl, dtype=np.float32)))
    idx = jax.device_put(jnp.asarray(rng.integers(0, tbl, E).astype(np.int32)))
    _, dt = timed_chain(f"XLA gather 8M from {tbl}", lambda d, t, i: (t + d)[i].max(), t, idx)
    print(f"XLA gather 8M from {tbl}: {dt*1e3:.2f} ms/pass", flush=True)
