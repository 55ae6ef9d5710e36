"""MSM/NTT micro-bench across the zk kernel backends.

Measures the proving plane's two inner loops — Pippenger MSM over the
G1 ladder and the radix-2 NTT — per ``zk_backend`` at power-of-two
sizes, reporting ``msm_points_per_s`` and ``ntt_butterflies_per_s``
(butterflies = (n/2)·log2(n) per transform).  Optionally times one
full epoch prove (``--prove``) for the ``prove_seconds`` series.

Timing loops are LICM-proof: every rep draws its scalar vector from a
rotating pool (so no iteration is loop-invariant), results are synced
(``block_until_ready`` on the jit path, the ctypes call is
synchronous) and folded into a checksum that lands in the report — a
compiler or a lazy runtime cannot elide the timed work without
changing the output.

Backends:

- ``native``: the ctypes runtime (sizes up to 2^16 by default);
- ``graft``: the jit multi-limb Pippenger/NTT (sizes capped at 2^12
  by default — one XLA:CPU MSM rep at 2^12 is tens of seconds, and
  the point of the row is the parity-checked lowering the TPU
  projection in PERF.md §22 scales from, not CPU supremacy).

Writes a perf-sentinel-shaped report (``entries`` list with exact
metric strings) — record rounds as ``MSM_r<N>.json`` in the repo
root; ``tools/perf_sentinel.py`` tracks the series.

Run (recorded round)::

    JAX_PLATFORMS=cpu python bench/msm_bench.py --out MSM_r01.json

``--smoke`` is the CI shape (2^10 only, one rep, both backends).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def _setup_jax_cache() -> None:
    """Persist compiled kernels: repeat bench runs must measure the
    kernels, not XLA's compile times."""
    from protocol_tpu.device import enable_compile_cache

    enable_compile_cache()


def _scalar_pool(rng: np.random.Generator, n: int, pool: int, R: int):
    """A rotating pool of scalar vectors (python ints < R)."""
    return [
        [int.from_bytes(rng.bytes(32), "little") % R for _ in range(n)]
        for _ in range(pool)
    ]


def _bench_msm(backend: str, srs, sizes, reps: int, rng, R: int):
    """Per-size MSM timing against the SRS ladder prefix."""
    from protocol_tpu.utils.limbs import to_limbs_fast
    from protocol_tpu.zk import graft as zk_graft
    from protocol_tpu.zk import native as zk_native

    if backend == "graft":
        cache = zk_graft.point_cache(srs.g1_powers)
    else:
        point_limbs = zk_native._points_to_limbs(srs.g1_powers)

    rows = []
    for n in sizes:
        pool = _scalar_pool(rng, n, min(reps, 3), R)
        arrs = [np.asarray(to_limbs_fast(s)) for s in pool]
        checksum = 0

        def one(i: int):
            arr = arrs[i % len(arrs)]
            if backend == "graft":
                with zk_graft.use_zk_backend("graft"):
                    return zk_graft.msm_limbs(arr, cache)
            return zk_native.msm_limbs(arr, point_limbs[:n])

        one(0)  # warm: jit compile / first-touch outside the timed loop
        t0 = time.perf_counter()
        for i in range(reps):
            pt = one(i)
            checksum ^= pt.x  # consume: the loop body is observable
        dt = time.perf_counter() - t0
        rows.append(
            {
                "n": n,
                "reps": reps,
                "seconds_per_msm": dt / reps,
                "points_per_s": n * reps / dt,
                "checksum": checksum % (1 << 32),
            }
        )
        print(
            f"msm[{backend}] n=2^{n.bit_length() - 1}: "
            f"{rows[-1]['points_per_s']:.1f} points/s "
            f"({rows[-1]['seconds_per_msm']:.3f} s/msm)",
            flush=True,
        )
    return rows


def _bench_ntt(backend: str, sizes, reps: int, rng, R: int):
    from protocol_tpu.utils.limbs import to_limbs_fast
    from protocol_tpu.zk import graft as zk_graft
    from protocol_tpu.zk import plonk

    rows = []
    for n in sizes:
        k = n.bit_length() - 1
        d = plonk.Domain(k)
        pool = [
            np.asarray(
                to_limbs_fast(
                    [int.from_bytes(rng.bytes(32), "little") % R
                     for _ in range(n)]
                )
            )
            for _ in range(min(reps, 3))
        ]
        checksum = 0

        def one(i: int):
            arr = pool[i % len(pool)].copy()  # the native NTT is in-place
            if backend == "graft":
                with zk_graft.use_zk_backend("graft"):
                    return d.ntt_limbs(arr, d.omega, False)
            return d.ntt_limbs(arr, d.omega, False)

        one(0)
        t0 = time.perf_counter()
        for i in range(reps):
            out = one(i)
            checksum ^= int(out[0, 0])
        dt = time.perf_counter() - t0
        butterflies = (n // 2) * k
        rows.append(
            {
                "n": n,
                "reps": reps,
                "seconds_per_ntt": dt / reps,
                "butterflies_per_s": butterflies * reps / dt,
                "checksum": checksum % (1 << 32),
            }
        )
        print(
            f"ntt[{backend}] n=2^{k}: "
            f"{rows[-1]['butterflies_per_s']:.1f} butterflies/s",
            flush=True,
        )
    return rows


def _bench_prove(zk_backend: str, peers: int) -> float:
    """One full epoch prove wall under the given backend."""
    from protocol_tpu.node.bootstrap import FIXED_SET
    from protocol_tpu.node.epoch import Epoch
    from protocol_tpu.node.manager import Manager, ManagerConfig
    from protocol_tpu.prover import prove_job

    cfg = (
        ManagerConfig(prover="plonk", zk_backend=zk_backend)
        if peers == 5
        else ManagerConfig(
            prover="plonk",
            num_neighbours=peers,
            num_iter=1,
            fixed_set=list(FIXED_SET[:peers]),
            zk_backend=zk_backend,
        )
    )
    mgr = Manager(cfg)
    mgr.generate_initial_attestations()
    job = mgr.build_proof_job(Epoch(1))
    return prove_job(job).prove_seconds


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--k-min", type=int, default=10, help="smallest size, log2")
    ap.add_argument(
        "--k-max", type=int, default=16, help="largest native size, log2"
    )
    ap.add_argument(
        "--k-max-graft",
        type=int,
        default=12,
        help="largest graft size, log2 (XLA:CPU MSM reps are slow)",
    )
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument(
        "--backends",
        default="native,graft",
        help="comma list of zk backends to measure",
    )
    ap.add_argument(
        "--prove",
        action="store_true",
        help="also time one full epoch prove per backend (native only "
        "unless --prove-graft; feeds the prove_seconds series)",
    )
    ap.add_argument(
        "--prove-graft",
        action="store_true",
        help="include the graft backend in the --prove leg (hours on CPU)",
    )
    ap.add_argument(
        "--prove-peers", type=int, default=5, help="statement size for --prove"
    )
    ap.add_argument("--smoke", action="store_true", help="CI shape: 2^10, 1 rep")
    ap.add_argument("--n", type=int, default=0, help="bench round number")
    ap.add_argument("--out", default="MSM_smoke.json")
    args = ap.parse_args(argv)

    if args.smoke:
        args.k_min = args.k_max = args.k_max_graft = 10
        args.reps = 1

    _setup_jax_cache()
    from protocol_tpu.crypto.field import MODULUS as R
    from protocol_tpu.zk import kzg

    backends = [b.strip() for b in args.backends.split(",") if b.strip()]
    rng = np.random.default_rng(20_26)
    t0 = time.perf_counter()
    print(f"msm_bench: generating 2^{args.k_max} SRS ladder...", flush=True)
    srs = kzg.Setup.generate(args.k_max, seed=b"msm-bench-srs")
    print(f"msm_bench: SRS in {time.perf_counter() - t0:.1f}s", flush=True)

    entries = []
    for backend in backends:
        k_hi = args.k_max_graft if backend == "graft" else args.k_max
        sizes = [1 << k for k in range(args.k_min, k_hi + 1)]
        msm_rows = _bench_msm(backend, srs, sizes, args.reps, rng, R)
        for row in msm_rows:
            k = row["n"].bit_length() - 1
            entries.append(
                {
                    "metric": f"zk msm throughput ({backend}, n=2^{k}, bn254 G1)",
                    "value": round(row["points_per_s"], 2),
                    "unit": "points/s",
                    "msm_points_per_s": round(row["points_per_s"], 2),
                    "seconds_per_msm": round(row["seconds_per_msm"], 5),
                    "reps": row["reps"],
                    "checksum": row["checksum"],
                }
            )
        ntt_rows = _bench_ntt(backend, sizes, args.reps, rng, R)
        for row in ntt_rows:
            k = row["n"].bit_length() - 1
            entries.append(
                {
                    "metric": f"zk ntt throughput ({backend}, n=2^{k}, fr)",
                    "value": round(row["butterflies_per_s"], 2),
                    "unit": "butterflies/s",
                    "ntt_butterflies_per_s": round(row["butterflies_per_s"], 2),
                    "seconds_per_ntt": round(row["seconds_per_ntt"], 6),
                    "reps": row["reps"],
                    "checksum": row["checksum"],
                }
            )
        if args.prove and (backend != "graft" or args.prove_graft):
            secs = _bench_prove(backend, args.prove_peers)
            entries.append(
                {
                    "metric": (
                        f"plonk epoch prove wall ({backend}, "
                        f"{args.prove_peers} peers)"
                    ),
                    "value": round(secs, 3),
                    "unit": "seconds",
                    "prove_seconds": round(secs, 3),
                }
            )
            print(f"prove[{backend}]: {secs:.2f}s", flush=True)

    report = {
        "config": {
            "k_min": args.k_min,
            "k_max": args.k_max,
            "k_max_graft": args.k_max_graft,
            "reps": args.reps,
            "backends": backends,
            "smoke": bool(args.smoke),
        },
        "n": args.n,
        "entries": entries,
    }
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(f"msm_bench: wrote {args.out} ({len(entries)} entries)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
