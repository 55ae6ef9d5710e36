"""jit-vs-native equivalence for every trust kernel (SURVEY.md §4 tier 3)
and cross-backend consistency, including the sharded mesh path (tier 5)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from protocol_tpu.crypto.eddsa import SecretKey
from protocol_tpu.models.graphs import erdos_renyi, scale_free, sybil_mass, sybil_stress
from protocol_tpu.ops.dense import converge_dense, filter_and_normalize, set_converge_dense
from protocol_tpu.parallel.mesh import default_mesh
from protocol_tpu.parallel.sharded import ShardedTrustProblem, converge_sharded
from protocol_tpu.trust.backend import get_backend, normalize_graph
from protocol_tpu.trust.graph import TrustGraph
from protocol_tpu.trust.native import EigenTrustSet, Opinion, power_iterate_rational
from protocol_tpu.crypto.eddsa import Signature


def unsigned_opinion(pks, scores):
    """Set tests that don't exercise signatures use a zero signature."""
    return Opinion(sig=Signature.new(0, 0, 0), message_hash=0, scores=list(zip(pks, scores)))


class TestDenseKernel:
    def test_matches_exact_rational(self):
        """converge_dense on the row-stochastic matrix equals native()'s
        unscaled rational result (circuit.rs:425-470 equivalence)."""
        rng = np.random.default_rng(3)
        n, iters, scale = 7, 10, 1000
        # Random rows summing to SCALE.
        ops = np.zeros((n, n), dtype=np.int64)
        for i in range(n):
            cuts = np.sort(rng.integers(0, scale + 1, n - 1))
            parts = np.diff(np.concatenate([[0], cuts, [scale]]))
            ops[i] = parts
            ops[i, i] = 0
            ops[i] = ops[i] * scale // max(ops[i].sum(), 1)
            ops[i, (i + 1) % n] += scale - ops[i].sum()
        init = [1000] * n

        exact = power_iterate_rational(init, ops.tolist(), iters, scale)
        c_t = jnp.asarray((ops.T / scale).astype(np.float32))
        out = converge_dense(c_t, jnp.asarray(np.array(init, np.float32)), iters)
        np.testing.assert_allclose(
            np.asarray(out), [float(x) for x in exact], rtol=2e-4
        )

    def test_jit_static_iters(self):
        # Explicit staging: eager jnp constructors (eye/ones) build from
        # host scalars, which the module's transfer guard rejects.
        c = jnp.asarray(np.eye(4, dtype=np.float32))
        s = jnp.asarray(np.ones(4, np.float32))
        assert converge_dense(c, s, 3).shape == (4,)


class TestSetKernelVectorized:
    def _scenario(self, seed=0):
        s = EigenTrustSet(num_neighbours=6, num_iterations=20, initial_score=1000)
        pks = [SecretKey.random().public() for _ in range(4)]
        for pk in pks[:3]:
            s.add_member(pk)
        from protocol_tpu.crypto.eddsa import PublicKey

        null = PublicKey.null()
        padded = pks[:3] + [null, null, null]
        # Mixed scenario: valid rows, a mismatched pk (pks[3] in slot 5),
        # a self-score, and one zero-sum opinion.
        s.update_op(pks[0], unsigned_opinion([pks[0], pks[1], pks[2], null, null, pks[3]], [10, 10, 0, 0, 10, 5]))
        s.update_op(pks[1], unsigned_opinion(padded, [0, 0, 30, 0, 0, 0]))
        s.update_op(pks[2], unsigned_opinion(padded, [0, 0, 0, 0, 0, 0]))
        return s

    def test_filter_matches_native(self):
        s = self._scenario()
        ops, match, valid, credits = s.to_arrays()
        stochastic = np.asarray(
            filter_and_normalize(jnp.asarray(ops), jnp.asarray(match), jnp.asarray(valid))
        )

        filtered_set, filtered_ops = s.filter_peers()
        for i, (pk, _) in enumerate(filtered_set):
            if pk.is_null():
                assert np.all(stochastic[i] == 0)
                continue
            native_scores = np.array(
                [float(score) for _, score in filtered_ops[pk].scores]
            )
            expected = native_scores / native_scores.sum()
            np.testing.assert_allclose(stochastic[i], expected, rtol=1e-6)

    def test_converge_matches_native(self):
        s = self._scenario()
        ops, match, valid, credits = s.to_arrays()
        stochastic = filter_and_normalize(
            jnp.asarray(ops), jnp.asarray(match), jnp.asarray(valid)
        )
        out = np.asarray(
            set_converge_dense(stochastic, jnp.asarray(credits.astype(np.float32)), 20)
        )
        exact = s.converge_rational()
        # Native raw scores grow by INITIAL_SCORE^20; compare normalized.
        expected = np.array([float(x / 1000**20) for x in exact])
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-7)


class TestSparseBackend:
    def test_matches_dense_backend(self):
        g = erdos_renyi(200, avg_degree=6.0, seed=1)
        dense = get_backend("tpu-dense").converge(g, alpha=0.1, tol=1e-9, max_iter=80)
        sparse = get_backend("tpu-sparse").converge(g, alpha=0.1, tol=1e-9, max_iter=80)
        np.testing.assert_allclose(sparse.scores, dense.scores, rtol=1e-3, atol=1e-8)

    def test_matches_exact_native_backend(self):
        g = erdos_renyi(40, avg_degree=4.0, seed=2)
        exact = get_backend("native-cpu").converge(g, alpha=0.15, tol=0, max_iter=25)
        sparse = get_backend("tpu-sparse").converge(g, alpha=0.15, tol=0, max_iter=25)
        np.testing.assert_allclose(sparse.scores, exact.scores, rtol=1e-3, atol=1e-7)

    def test_l1_normalized(self):
        g = scale_free(500, 4000, seed=3)
        res = get_backend("tpu-sparse").converge(g, alpha=0.1)
        assert res.scores.sum() == pytest.approx(1.0, rel=1e-5)
        assert (res.scores >= 0).all()

    def test_fixed_iter_mode(self):
        g = erdos_renyi(100, seed=4)
        res = get_backend("tpu-sparse").converge(g, alpha=0.1, tol=0, max_iter=7)
        assert res.iterations == 7


class TestCsrBackend:
    def test_matches_sparse_backend(self):
        g = scale_free(800, 6400, seed=9)
        sparse = get_backend("tpu-sparse").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        csr = get_backend("tpu-csr").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        np.testing.assert_allclose(csr.scores, sparse.scores, rtol=1e-3, atol=1e-8)

    def test_matches_exact_native(self):
        g = erdos_renyi(40, avg_degree=4.0, seed=2)
        exact = get_backend("native-cpu").converge(g, alpha=0.15, tol=0, max_iter=25)
        csr = get_backend("tpu-csr").converge(g, alpha=0.15, tol=0, max_iter=25)
        np.testing.assert_allclose(csr.scores, exact.scores, rtol=1e-3, atol=1e-7)

    def test_row_ptr_construction(self):
        g = erdos_renyi(50, avg_degree=3.0, seed=12).drop_self_edges().sorted_by_dst()
        rp = g.row_ptr_by_dst()
        assert rp.shape == (51,)
        assert rp[0] == 0 and rp[-1] == g.nnz
        for j in range(50):
            assert (g.dst[rp[j] : rp[j + 1]] == j).all()


class TestShardedBackend:
    def test_mesh_has_8_devices(self):
        assert len(jax.devices()) == 8  # conftest virtual CPU mesh

    def test_matches_sparse_backend(self):
        g = scale_free(1000, 8000, seed=5)
        sparse = get_backend("tpu-sparse").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        sharded = get_backend("tpu-sharded").converge(g, alpha=0.1, tol=1e-9, max_iter=60)
        np.testing.assert_allclose(sharded.scores, sparse.scores, rtol=1e-3, atol=1e-8)

    def test_explicit_small_mesh(self):
        mesh = default_mesh(4)
        g = erdos_renyi(300, seed=6)
        res = get_backend("tpu-sharded", mesh=mesh).converge(g, alpha=0.1)
        assert res.scores.shape == (300,)
        assert res.scores.sum() == pytest.approx(1.0, rel=1e-5)

    def test_sharded_problem_padding(self):
        # nnz not divisible by the mesh size must zero-pad cleanly.
        g = erdos_renyi(50, avg_degree=3.1, seed=7)
        problem = ShardedTrustProblem.build(normalize_graph(g), default_mesh(8))
        assert problem.src.shape[0] % 8 == 0
        t, it, resid = converge_sharded(problem, alpha=0.2, max_iter=30)
        assert np.asarray(t).sum() == pytest.approx(1.0, rel=1e-5)


class TestSybilDamping:
    def test_damping_bounds_collective(self):
        """BASELINE config 5 semantics: pre-trust damping caps the trust
        mass a closed sybil collective can capture."""
        g = sybil_stress(2000, 16000, sybil_fraction=0.3, seed=8)
        masses = []
        for alpha in (0.01, 0.2, 0.5):
            res = get_backend("tpu-sparse").converge(g, alpha=alpha, max_iter=80)
            masses.append(sybil_mass(res.scores, g.n, 0.3))
        assert masses[0] > masses[1] > masses[2]
        assert masses[2] < 0.2


class TestBackendRegistry:
    def test_unknown_backend(self):
        with pytest.raises(ValueError, match="unknown trust backend"):
            get_backend("gpu-magic")

    def test_all_named_backends_construct(self):
        for name in ("native-cpu", "tpu-dense", "tpu-sparse", "tpu-sharded"):
            assert get_backend(name).name == name


class TestBenchLadder:
    def test_ladder_smoke(self):
        """All five BASELINE.md configs execute and report: shape check
        at 1/1000 scale (bench.py --ladder is the real run)."""
        import bench

        entries = bench.ladder(scale_div=1000, iters=6)
        assert [e["config"][:2] for e in entries] == ["1-", "2-", "3-", "4-", "5-"]
        curve = entries[-1]["sybil_mass_curve"]
        masses = [p["sybil_mass"] for p in curve]
        assert masses == sorted(masses, reverse=True)  # damping squeezes the clique


class TestTransferGuard:
    """This module runs under ``jax.transfer_guard("disallow")``
    (conftest): implicit transfers in any backend path fail loudly
    here, so a hidden per-iteration host sync can't land silently."""

    def test_implicit_transfers_rejected_here(self):
        step = jax.jit(lambda a: a * 2)
        with pytest.raises(Exception, match="Disallowed host-to-device"):
            step(np.arange(3.0, dtype=np.float32))

    @pytest.mark.allow_transfer
    def test_marker_opts_out(self):
        step = jax.jit(lambda a: a * 2)
        out = np.asarray(step(np.arange(3.0, dtype=np.float32)))
        np.testing.assert_allclose(out, [0.0, 2.0, 4.0])


class TestWindowedGather:
    def test_bucketed_gather_matches_direct(self):
        """The windowed Pallas gather (interpret mode on CPU; PERF.md
        §1 documents the TPU compilation envelope it is built for)
        reproduces w * t[src] under the bucket permutation."""
        import numpy as np

        from protocol_tpu.ops.gather_window import bucket_by_window, gather_windowed

        rng = np.random.default_rng(5)
        n, e = 1 << 13, 1 << 15
        src = rng.integers(0, n, e).astype(np.int32)
        w = rng.random(e, dtype=np.float32)
        t = rng.random(n, dtype=np.float32)

        b = bucket_by_window(src, w, table_size=n)
        out = np.asarray(
            gather_windowed(
                jnp.asarray(b["wid"]),
                jnp.asarray(t),
                jnp.asarray(b["local"]),
                jnp.asarray(b["weight"]),
                n_rows=b["n_rows"],
                table_chunk=b["table_chunk"],
                interpret=True,
            )
        ).reshape(-1)
        expect = w[b["order"]] * t[src[b["order"]]]
        np.testing.assert_allclose(out[b["out_pos"]], expect, rtol=1e-6)
        # Padding slots carry zero weight, so the bucketed sum matches.
        np.testing.assert_allclose(out.sum(), (w * t[src]).sum(), rtol=1e-4)
