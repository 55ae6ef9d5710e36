"""Peer→host partition properties (ISSUE 16, pod scale-out).

The rendezvous-hash partition is the pod's only membership agreement
mechanism — every host computes it independently, so the properties
below are load-bearing protocol invariants, not implementation trivia:

- **determinism**: two processes (here: two instances) with the same
  ``(n_hosts, seed)`` assign every key identically, including keys
  folded from arbitrary-width Poseidon hashes;
- **balance**: per-host buckets stay within ±20% of ``n/n_hosts`` at
  realistic sizes (an unbalanced partition silently serializes the
  pod's plan-build critical path back toward the single-host wall);
- **minimal remap**: a host join moves ≈ ``1/(n_hosts+1)`` of the keys
  (all of them TO the joiner); a host leave moves exactly the leaver's
  keys and nothing else — surviving hosts' window plans stay valid;
- **churn locality**: the bench.py sender-centric churn stream is
  partition-local — every churned row is dirty on exactly one host,
  and the per-host edge partitions tile the edge set exactly.
"""

import numpy as np
import pytest

from protocol_tpu.models import scale_free
from protocol_tpu.models.churn import churn_cohort_dims, sender_centric_churn
from protocol_tpu.ops.gather_window import partition_delta
from protocol_tpu.parallel.partition import (
    MASK64,
    HostPartition,
    align_by_hash,
    keys_from_hashes,
    mix64,
    remap_fraction,
)


class TestDeterminism:
    def test_identical_across_instances(self):
        keys = np.random.default_rng(0).integers(
            0, 1 << 63, 50_000, dtype=np.uint64
        )
        a = HostPartition(5, seed=3).assign(keys)
        b = HostPartition(5, seed=3).assign(keys)
        assert np.array_equal(a, b)
        assert a.dtype == np.int32
        assert a.min() >= 0 and a.max() < 5

    def test_assign_ids_matches_assign_on_arange(self):
        part = HostPartition(4, seed=1)
        n = 10_000
        assert np.array_equal(
            part.assign_ids(n), part.assign(np.arange(n, dtype=np.uint64))
        )

    def test_seed_namespaces_the_partition(self):
        keys = np.arange(20_000, dtype=np.uint64)
        a = HostPartition(4, seed=0).assign(keys)
        b = HostPartition(4, seed=1).assign(keys)
        # Different salt chains: assignments must differ somewhere (a
        # seed that did nothing would collide test and production pods).
        assert not np.array_equal(a, b)

    def test_wide_hash_folding(self):
        # Poseidon field elements are ~254-bit Python ints; folding
        # masks to 64 bits, so two hashes equal mod 2^64 get one owner.
        wide = [(7 << 200) | 12345, (3 << 150) | 12345, 12345]
        keys = keys_from_hashes(wide)
        assert keys.dtype == np.uint64
        assert np.array_equal(keys, np.full(3, 12345, np.uint64))
        owners = HostPartition(8, seed=2).assign(keys)
        assert len(set(owners.tolist())) == 1

    def test_single_host_owns_everything(self):
        owners = HostPartition(1).assign_ids(1000)
        assert np.array_equal(owners, np.zeros(1000, np.int32))

    def test_invalid_pod_size_rejected(self):
        with pytest.raises(ValueError):
            HostPartition(0)

    def test_mix64_reference_vector(self):
        # splitmix64(0) first output — the published reference value;
        # pins the mixer against accidental constant/shift edits.
        out = mix64(np.asarray([0], np.uint64))[0]
        assert int(out) == 0xE220A8397B1DCDAF


class TestBalance:
    @pytest.mark.parametrize("n_hosts", [2, 4, 8])
    def test_buckets_within_20_percent(self, n_hosts):
        n = 100_000
        counts = np.bincount(
            HostPartition(n_hosts, seed=16).assign_ids(n), minlength=n_hosts
        )
        expect = n / n_hosts
        assert counts.min() >= 0.8 * expect, counts
        assert counts.max() <= 1.2 * expect, counts


class TestMinimalRemap:
    def test_join_moves_one_over_h_plus_1(self):
        keys = np.arange(200_000, dtype=np.uint64)
        before = HostPartition(4, seed=16).assign(keys)
        after = HostPartition(5, seed=16).assign(keys)
        moved = before != after
        # Every mover lands on the new host — rendezvous only ever
        # reassigns keys whose new candidate wins.
        assert np.all(after[moved] == 4)
        frac = remap_fraction(before, after)
        assert abs(frac - 1 / 5) < 0.02, frac

    def test_leave_moves_only_the_leavers_keys(self):
        keys = np.arange(100_000, dtype=np.uint64)
        before = HostPartition(5, seed=16).assign(keys)
        after = HostPartition(4, seed=16).assign(keys)
        survivors = before != 4
        # Removing a candidate never changes the argmax among the
        # rest: surviving hosts keep every key — their window plans
        # stay byte-identical across the membership change.
        assert np.array_equal(before[survivors], after[survivors])
        assert np.all(after[~survivors] < 4)

    def test_modulo_baseline_is_worse(self):
        # The property HRW buys: a modulo partition moves ~H/(H+1) of
        # the keys on the same join (here 4/5), ~4x the HRW remap.
        keys = np.arange(50_000, dtype=np.uint64)
        frac = remap_fraction(keys % 4, keys % 5)
        assert frac > 0.7

    def test_remap_fraction_edge_cases(self):
        assert remap_fraction(np.array([]), np.array([])) == 0.0
        with pytest.raises(ValueError):
            remap_fraction(np.zeros(3), np.zeros(4))


class TestChurnLocality:
    """The bench.py sender-centric churn stream against the partition:
    the exact claim the pod dryrun's steady-state relies on."""

    def _graph(self):
        return scale_free(2048, 16384, seed=16).drop_self_edges()

    def test_churned_rows_partition_local(self):
        g = self._graph()
        cohort_size, deg = churn_cohort_dims(g, 0.01)
        rng = np.random.default_rng(16)
        rows, g2, _ = sender_centric_churn(
            rng, g, cohort_size=cohort_size, deg=deg
        )
        part = HostPartition(4, seed=16)
        owner = part.assign_ids(g2.n)
        g2 = g2.drop_self_edges()
        w, _ = g2.row_normalized()
        seen_rows, seen_edges = [], 0
        for h in range(4):
            owned, lsrc, ldst, lw = partition_delta(
                rows, g2.src, g2.dst, w, owner, h
            )
            # Every local edge's source belongs to this host.
            assert np.all(owner[lsrc] == h)
            assert lsrc.shape == ldst.shape == lw.shape
            seen_rows.append(owned)
            seen_edges += lsrc.shape[0]
        # The per-host dirty rows tile the churn cohort exactly: each
        # row dirty on exactly one host.
        tiled = np.sort(np.concatenate(seen_rows))
        assert np.array_equal(tiled, np.sort(np.unique(rows)))
        # And the edge partitions tile the edge set exactly.
        assert seen_edges == g2.nnz

    def test_no_hint_forces_fingerprint_revalidation(self):
        g = self._graph()
        w, _ = g.row_normalized()
        owner = HostPartition(2, seed=16).assign_ids(g.n)
        owned, lsrc, _, _ = partition_delta(None, g.src, g.dst, w, owner, 0)
        assert owned is None
        assert np.all(owner[lsrc] == 0)

    def test_churn_stream_is_deterministic(self):
        g = self._graph()
        cohort_size, deg = churn_cohort_dims(g, 0.01)
        r1, g1, (ns1, nd1, nw1) = sender_centric_churn(
            np.random.default_rng(7), g, cohort_size=cohort_size, deg=deg
        )
        r2, g2, (ns2, nd2, nw2) = sender_centric_churn(
            np.random.default_rng(7), g, cohort_size=cohort_size, deg=deg
        )
        assert np.array_equal(r1, r2)
        assert np.array_equal(ns1, ns2)
        assert np.array_equal(nd1, nd2)
        assert np.array_equal(nw1, nw2)
        assert np.array_equal(g1.src, g2.src)
        # Row i's fresh out-row is the [i*deg, (i+1)*deg) slice — the
        # WAL journaling contract the pod dryrun encodes per host.
        assert np.array_equal(ns1, np.repeat(r1.astype(np.int32), deg))
        assert not np.any(nd1 == ns1)  # no self-edges survive resampling


class TestAlignByHash:
    """The previous column aligned to the new one: each new peer's
    position in it or -1, by the prefix path (an unchanged or extended
    column) or the verified search (any other)."""

    def test_prefix_for_the_same_an_equal_and_an_extended_column(self):
        prev = [7, 3, 11, 5]
        for old, new, want in (
            (prev, prev, [0, 1, 2, 3]),
            (prev, list(prev), [0, 1, 2, 3]),
            (prev, prev + [2, 13], [0, 1, 2, 3, -1, -1]),
            ([], [4, 6], [-1, -1]),
        ):
            idx, path = align_by_hash(old, new)
            assert path == "prefix"
            assert idx.dtype == np.int64
            assert idx.tolist() == want

    def test_search_for_a_reordered_column_with_joins_and_departures(self):
        idx, path = align_by_hash([7, 3, 11, 5], [5, 8, 7, 11, 1])
        assert path == "search"
        assert idx.dtype == np.int64
        assert idx.tolist() == [3, -1, 0, 2, -1]
        idx, path = align_by_hash([7, 3], [3])  # a shorter column
        assert (path, idx.tolist()) == ("search", [1])

    def test_low_64_bit_collisions_resolve_by_the_full_hash(self):
        a = (1 << 250) | 0xABC
        b = (1 << 201) | 0xABC
        c = (1 << 160) | 0xABC
        assert len({h & MASK64 for h in (a, b, c)}) == 1
        # ``a`` departed, ``b`` joined: a key hit, not a match.
        assert align_by_hash([a, 4], [b, 4])[0].tolist() == [-1, 1]
        # Three entries under one key: each finds its own position.
        idx, path = align_by_hash([c, 9, b, a], [a, b, 9, c])
        assert path == "search"
        assert idx.tolist() == [3, 2, 1, 0]
