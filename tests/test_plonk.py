"""PLONK prover/verifier tests — the tier-3 analog of the reference's
real-proving ladder (circuit.rs:556-620 prove_and_verify): every proof
is checked end-to-end through the KZG pairing, with tampered-proof and
wrong-instance negatives.

The full 5-peer epoch statement (k=14; ~8.4 s proving + ~13 s cold
keygen, amortized by the on-disk key cache) runs when
PROTOCOL_TPU_SLOW_TESTS=1; the default suite exercises the same
machinery (chunked permutation, rotation gates, fixed columns,
blinding) on smaller circuits, and drives one real 2-peer epoch →
PLONK → EVM-verify flow through the Manager.
"""

import os

import pytest

from protocol_tpu.crypto import field
from protocol_tpu.crypto.poseidon import permute
from protocol_tpu.zk import plonk
from protocol_tpu.zk.cs import ConstraintSystem
from protocol_tpu.zk.gadgets import Bits2NumChip, PoseidonChip, StdGate

P = field.MODULUS


def _mul_add_circuit():
    """out = 3*4 + 5, bound to the public instance."""
    cs = ConstraintSystem()
    std = StdGate(cs)
    x, y, c5 = std.witness(3), std.witness(4), std.witness(5)
    out = std.add(std.mul(x, y), c5)
    inst = cs.column("instance", "instance")
    cs.copy(cs.assign(inst, 0, 17), out)
    cs.assert_satisfied()
    return cs


class TestSymTracing:
    def test_trace_matches_direct_eval(self):
        """A traced gate evaluated symbolically at scalar values must
        match the constraint system's own row evaluation."""
        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        # All gates must trace to pure-arithmetic Syms.
        for spec in pk.vk.gates:
            assert spec.constraints
            for con in spec.constraints:
                assert con.deg >= 1

    def test_linearize_roundtrip(self):
        s = (plonk.Sym.col(0) * plonk.Sym.col(1) - plonk.Sym.const(7)) * plonk.Sym.col(
            0, 1
        )
        vals = {(0, 0): 3, (1, 0): 5, (0, 1): 11}
        direct = plonk.sym_eval(s, lambda sl, r: vals[(sl, r)])
        assert direct == (3 * 5 - 7) * 11 % P
        code, pool = [], {}
        depth = plonk.linearize(s, {0: 0, 1: 1}, pool, code)
        assert depth <= 4 and code


class TestPlonkSmall:
    def test_roundtrip(self):
        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        proof = plonk.prove(pk, cs, [17], seed=b"t")
        assert plonk.verify(pk.vk, [17], proof)

    def test_wrong_instance_rejected(self):
        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        proof = plonk.prove(pk, cs, [17], seed=b"t")
        assert not plonk.verify(pk.vk, [18], proof)

    def test_tampered_proof_rejected(self):
        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        proof = plonk.prove(pk, cs, [17], seed=b"t")
        for off in (0, len(proof) // 2, len(proof) - 1):
            bad = bytearray(proof)
            bad[off] ^= 1
            assert not plonk.verify(pk.vk, [17], bytes(bad))

    def test_truncated_and_extended_proofs_rejected(self):
        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        proof = plonk.prove(pk, cs, [17], seed=b"t")
        assert not plonk.verify(pk.vk, [17], proof[:-32])
        assert not plonk.verify(pk.vk, [17], proof + b"\x00" * 32)

    def test_blinding_changes_proof_not_validity(self):
        """Two proofs of the same statement with different blinding
        randomness differ byte-wise but both verify (the zk property's
        observable half)."""
        cs = _mul_add_circuit()
        pk = plonk.compile_circuit(cs)
        p1 = plonk.prove(pk, cs, [17], seed=b"a")
        p2 = plonk.prove(pk, cs, [17], seed=b"b")
        assert p1 != p2
        assert plonk.verify(pk.vk, [17], p1) and plonk.verify(pk.vk, [17], p2)

    def test_forged_witness_unsatisfying_trace(self):
        """A trace that satisfies the mock checker is provable; one that
        doesn't produces a proof the verifier rejects (the quotient
        division leaves a non-vanishing remainder)."""
        cs = ConstraintSystem()
        std = StdGate(cs)
        x, y = std.witness(3), std.witness(4)
        out = std.mul(x, y)
        inst = cs.column("instance", "instance")
        cs.copy(cs.assign(inst, 0, 12), out)
        pk = plonk.compile_circuit(cs)
        # Corrupt the witness after keygen: claim 3*4 = 13.
        cs2 = ConstraintSystem()
        std2 = StdGate(cs2)
        x2, y2 = std2.witness(3), std2.witness(4)
        r = std2.row(
            {std2.a: x2, std2.b: y2, std2.c: 13}, {"s_ab": 1, "sc": P - 1}
        )
        from protocol_tpu.zk.cs import Cell

        cs2.copy(cs2.assign(cs2.column("instance", "instance"), 0, 13), Cell(std2.c, r))
        assert cs2.verify()  # mock checker catches it
        proof = plonk.prove(pk, cs2, [13], seed=b"t")
        assert not plonk.verify(pk.vk, [13], proof)


class TestPlonkPoseidon:
    """Rotation gates, fixed round-constant columns, multi-chunk
    permutation."""

    def test_poseidon_circuit_roundtrip(self):
        cs = ConstraintSystem()
        std = StdGate(cs)
        pos = PoseidonChip(cs)
        ins = [std.witness(i + 1) for i in range(5)]
        outs = pos.permute(ins)
        expected = permute([1, 2, 3, 4, 5])
        inst = cs.column("instance", "instance")
        cs.copy(cs.assign(inst, 0, expected[0]), outs[0])
        cs.assert_satisfied()
        pk = plonk.compile_circuit(cs)
        assert len(pk.vk.chunks) >= 2  # chunked permutation exercised
        proof = plonk.prove(pk, cs, [expected[0]], seed=b"x")
        assert plonk.verify(pk.vk, [expected[0]], proof)
        assert not plonk.verify(pk.vk, [(expected[0] + 1) % P], proof)

    def test_bits2num_rotation_gate(self):
        cs = ConstraintSystem()
        std = StdGate(cs)
        b2n = Bits2NumChip(cs)
        val = std.witness(0b101101)
        bits = b2n.decompose(val, 8)
        assert [cs.value(b.column, b.row) for b in bits[:6]] == [1, 0, 1, 1, 0, 1]
        inst = cs.column("instance", "instance")
        cs.copy(cs.assign(inst, 0, 0b101101), val)
        cs.assert_satisfied()
        pk = plonk.compile_circuit(cs)
        proof = plonk.prove(pk, cs, [0b101101], seed=b"y")
        assert plonk.verify(pk.vk, [0b101101], proof)
        assert not plonk.verify(pk.vk, [0b101100], proof)


class TestPlonkLookup:
    """The lookup argument (Halo2-style A'/S' + grand product) proving
    RangeCheckChip circuits under the real SNARK."""

    def _range_circuit(self):
        from protocol_tpu.zk.chips import RangeCheckChip

        cs = ConstraintSystem()
        std = StdGate(cs)
        rng = RangeCheckChip(cs, word_bits=4)
        x = std.witness(13)
        rng.assert_word(x)
        y = std.witness(200)
        rng.assert_range(y, 2)
        inst = cs.column("instance", "instance")
        cs.copy(cs.assign(inst, 0, 13), x)
        cs.assert_satisfied()
        return cs

    def test_range_lookup_roundtrip(self):
        cs = self._range_circuit()
        pk = plonk.compile_circuit(cs)
        assert len(pk.vk.lookups) == 1
        proof = plonk.prove(pk, cs, [13], seed=b"lk")
        assert plonk.verify(pk.vk, [13], proof)
        assert not plonk.verify(pk.vk, [14], proof)
        bad = bytearray(proof)
        bad[100] ^= 1
        assert not plonk.verify(pk.vk, [13], bytes(bad))

    def test_out_of_table_witness_unprovable(self):
        from protocol_tpu.zk.chips import RangeCheckChip

        cs = self._range_circuit()
        pk = plonk.compile_circuit(cs)
        cs2 = ConstraintSystem()
        std2 = StdGate(cs2)
        rng2 = RangeCheckChip(cs2, word_bits=4)
        x2 = std2.witness(21)  # 21 >= 16: not in the 4-bit table
        r = cs2.alloc_rows(1)
        cs2.copy(cs2.assign(rng2.word, r, 21), x2)
        cs2.enable(rng2._sel_word, r)
        y2 = std2.witness(200)
        rng2.assert_range(y2, 2)
        cs2.copy(cs2.assign(cs2.column("instance", "instance"), 0, 21), x2)
        with pytest.raises(AssertionError, match="not in table"):
            plonk.prove(pk, cs2, [21], seed=b"bad")

    def test_table_forces_domain_growth(self):
        """A 2^8 table in a tiny circuit still compiles (k grows to fit
        the table rows)."""
        from protocol_tpu.zk.chips import RangeCheckChip

        cs = ConstraintSystem()
        std = StdGate(cs)
        rng = RangeCheckChip(cs, word_bits=8)
        x = std.witness(250)
        rng.assert_word(x)
        inst = cs.column("instance", "instance")
        cs.copy(cs.assign(inst, 0, 250), x)
        pk = plonk.compile_circuit(cs)
        assert pk.vk.n >= 257
        proof = plonk.prove(pk, cs, [250], seed=b"t8")
        assert plonk.verify(pk.vk, [250], proof)


class TestDomain:
    def test_fft_roundtrip(self):
        d = plonk.Domain(5)
        coeffs = [i * 31 + 7 for i in range(20)]
        evals = d.fft(coeffs)
        back = d.ifft(evals)
        assert back[:20] == [c % P for c in coeffs]
        assert all(c == 0 for c in back[20:])

    def test_lagrange_eval_matches_poly(self):
        k = 4
        d = plonk.Domain(k)
        vals = {0: 5, 3: 11, 7: 2}
        dense = [0] * d.n
        for i, v in vals.items():
            dense[i] = v
        coeffs = d.ifft(dense)
        x = 0x1234567
        from protocol_tpu.zk.kzg import _eval_poly

        assert plonk._lagrange_eval(vals, x, k) == _eval_poly(coeffs, x)


class TestEpochProofSmall:
    """Default-suite flagship-path coverage: a real epoch → PLONK →
    EVM-verify roundtrip through the Manager at the smallest viable
    statement (2 peers, 1 iteration).  Keygen hits the on-disk key
    cache after the first run."""

    def test_manager_epoch_plonk_evm_roundtrip(self):
        from protocol_tpu.node.bootstrap import FIXED_SET
        from protocol_tpu.node.epoch import Epoch
        from protocol_tpu.node.manager import Manager, ManagerConfig
        from protocol_tpu.obs.metrics import PHASE_SECONDS
        from protocol_tpu.zk.evm_verifier import evm_verify

        mgr = Manager(
            ManagerConfig(
                prover="plonk",
                num_neighbours=2,
                num_iter=1,
                fixed_set=list(FIXED_SET[:2]),
            )
        )
        mgr.generate_initial_attestations()
        epoch = Epoch(1)
        mgr.calculate_proofs(epoch)
        proof = mgr.cached_proofs[epoch]
        assert mgr.prover.name == "plonk-kzg"
        assert mgr.prover.verify(proof.pub_ins, proof.proof)
        # Proving time must land in the phase histogram (the
        # reference's "Proving time" print, circuit/src/utils.rs:305-321).
        assert PHASE_SECONDS.count(phase="snark") >= 1
        # On-chain leg: generate the EVM verifier for this circuit and
        # verify the epoch proof on it (the epoch proof pins the
        # quotient-chunk count, so no extra sample prove is needed).
        from protocol_tpu.zk.evm_verifier import generate_evm_verifier, infer_n_t

        vk = mgr.prover.vk
        gen = generate_evm_verifier(vk, infer_n_t(vk, proof.proof), 2)
        ok, gas = evm_verify(gen, proof.pub_ins, proof.proof)
        assert ok and gas > 0
        bad = [(proof.pub_ins[0] + 1) % P] + proof.pub_ins[1:]
        assert not evm_verify(gen, bad, proof.proof)[0]


@pytest.mark.skipif(
    not os.environ.get("PROTOCOL_TPU_SLOW_TESTS"),
    reason="full 5-peer epoch proof: ~8.4 s prove + ~13 s cold keygen; "
    "set PROTOCOL_TPU_SLOW_TESTS=1",
)
class TestEpochProof:
    def test_epoch_statement_real_proof(self):
        from protocol_tpu.crypto import calculate_message_hash
        from protocol_tpu.crypto.eddsa import sign
        from protocol_tpu.node.attestation import Attestation
        from protocol_tpu.node.bootstrap import FIXED_SET, keyset_from_raw
        from protocol_tpu.trust.native import power_iterate
        from protocol_tpu.zk.circuit import prove_epoch_statement

        sks, pks = keyset_from_raw(FIXED_SET)
        rows = [[200] * 5 for _ in range(5)]
        _, messages = calculate_message_hash(pks, rows)
        atts = [
            Attestation(sig=sign(sk, pk, m), pk=pk, neighbours=list(pks), scores=r)
            for sk, pk, m, r in zip(sks, pks, messages, rows)
        ]
        pub = power_iterate([1000] * 5, rows, 10, 1000)
        cs = prove_epoch_statement(atts, pub)
        pk = plonk.compile_circuit(cs)
        assert pk.vk.k == 14  # same circuit size class as the reference
        proof = plonk.prove(pk, cs, pub, seed=b"epoch")
        assert plonk.verify(pk.vk, pub, proof)
        bad = list(pub)
        bad[0] = (bad[0] + 1) % P
        assert not plonk.verify(pk.vk, bad, proof)

    def test_manager_with_plonk_prover(self):
        """Node integration: a Manager configured with the PLONK
        backend serves a real SNARK from calculate_proofs (the
        reference's boot keygen + epoch proving flow,
        server/src/main.rs:70-83, manager/mod.rs:170-214)."""
        from protocol_tpu.node.epoch import Epoch
        from protocol_tpu.node.manager import Manager, ManagerConfig

        mgr = Manager(ManagerConfig(prover="plonk"))
        mgr.generate_initial_attestations()
        epoch = Epoch(1)
        mgr.calculate_proofs(epoch)
        proof = mgr.cached_proofs[epoch]
        assert mgr.prover.name == "plonk-kzg"
        assert mgr.prover.verify(proof.pub_ins, proof.proof)
        assert not mgr.prover.verify(
            [(proof.pub_ins[0] + 1) % P] + proof.pub_ins[1:], proof.proof
        )


class TestAggregationSurface:
    """Node-reachable proof aggregation (manager.aggregate_proofs +
    GET /aggregate): the reference left its aggregator unwired; here
    batch verification is a served feature."""

    def test_commitment_prover_rejects_aggregation(self):
        from protocol_tpu.node.epoch import Epoch
        from protocol_tpu.node.errors import EigenError
        from protocol_tpu.node.manager import Manager, ManagerConfig
        from protocol_tpu.node.server import handle_request

        mgr = Manager(ManagerConfig(prover="commitment"))
        mgr.generate_initial_attestations()
        mgr.calculate_proofs(Epoch(1))
        with pytest.raises(EigenError):
            mgr.aggregate_proofs([Epoch(1)])
        status, _ = handle_request("GET", "/aggregate?epochs=1", mgr)
        assert status == 400

    def test_aggregate_bad_queries(self):
        from protocol_tpu.node.manager import Manager, ManagerConfig
        from protocol_tpu.node.server import handle_request

        mgr = Manager(ManagerConfig(prover="commitment"))
        assert handle_request("GET", "/aggregate", mgr)[0] == 400
        assert handle_request("GET", "/aggregate?epochs=", mgr)[0] == 400
        assert handle_request("GET", "/aggregate?epochs=x", mgr)[0] == 400
        assert handle_request("GET", "/aggregate?epochs=9", mgr)[0] == 400  # no proof


@pytest.mark.skipif(
    not os.environ.get("PROTOCOL_TPU_SLOW_TESTS"),
    reason="aggregating two real epoch SNARKs proves twice (~10 s); "
    "set PROTOCOL_TPU_SLOW_TESTS=1",
)
class TestAggregationSurfaceSlow:
    def test_manager_aggregates_two_epochs(self):
        import json

        from protocol_tpu.node.bootstrap import FIXED_SET
        from protocol_tpu.node.epoch import Epoch
        from protocol_tpu.node.manager import Manager, ManagerConfig
        from protocol_tpu.node.server import handle_request

        mgr = Manager(
            ManagerConfig(
                prover="plonk",
                num_neighbours=2,
                num_iter=1,
                fixed_set=list(FIXED_SET[:2]),
            )
        )
        mgr.generate_initial_attestations()
        mgr.calculate_proofs(Epoch(3))
        mgr.calculate_proofs(Epoch(7))
        ok, acc = mgr.aggregate_proofs([Epoch(3), Epoch(7)])
        assert ok and acc is not None

        status, body = handle_request("GET", "/aggregate?epochs=3,7", mgr)
        obj = json.loads(body)
        assert status == 200 and obj["ok"] and obj["epochs"] == [3, 7]
        assert len(bytes.fromhex(obj["accumulator"])) == 128

        # A tampered cached proof must fail the batch.
        proof = mgr.cached_proofs[Epoch(7)]
        bad = bytearray(proof.proof)
        bad[11] ^= 1
        mgr.cached_proofs[Epoch(7)] = type(proof)(
            pub_ins=proof.pub_ins, proof=bytes(bad)
        )
        ok2, _ = mgr.aggregate_proofs([Epoch(3), Epoch(7)])
        assert not ok2
