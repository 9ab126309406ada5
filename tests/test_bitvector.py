import numpy as np

from repro.core.bitvector import BitVector


class TestBitVector:
    def test_set_test(self):
        bv = BitVector(1000)
        keys = np.array([0, 63, 64, 65, 999])
        bv.set(keys, True)
        assert bv.test(keys).all()
        assert not bv.test(np.array([1, 62, 66, 998])).any()
        assert bv.count() == 5

    def test_unset(self):
        bv = BitVector.from_keys(np.arange(100))
        bv.set(np.arange(0, 100, 2), False)
        assert bv.count() == 50
        assert bv.test(np.array([1, 3, 99])).all()
        assert not bv.test(np.array([0, 2, 98])).any()

    def test_grow_on_set(self):
        bv = BitVector(10)
        bv.set(np.array([1_000_000]), True)
        assert bv.capacity == 1_000_001
        assert bv.test(np.array([1_000_000]))[0]
        assert not bv.test(np.array([999_999]))[0]

    def test_out_of_domain_false(self):
        bv = BitVector.from_keys(np.array([5]))
        out = bv.test(np.array([-3, 100, 5]))
        assert out.tolist() == [False, False, True]

    def test_serialize_roundtrip(self):
        keys = np.random.default_rng(0).permutation(10_000)[:777]
        bv = BitVector.from_keys(keys, capacity=10_000)
        bv2 = BitVector.from_bytes(bv.to_bytes())
        assert bv2.capacity == bv.capacity
        np.testing.assert_array_equal(bv2.words, bv.words)

    def test_compressed_at_rest_smaller_for_sparse(self):
        bv = BitVector(1 << 20)
        bv.set(np.array([17]), True)
        assert bv.size_bytes() < bv.runtime_bytes() / 10

    def test_empty(self):
        bv = BitVector(0)
        assert bv.count() == 0
        assert bv.test(np.array([0, 1])).tolist() == [False, False]


class TestCountAndVersion:
    def test_count_matches_unpackbits(self):
        rng = np.random.default_rng(5)
        keys = rng.choice(100_000, size=33_333, replace=False)
        bv = BitVector.from_keys(keys, capacity=100_000)
        assert bv.count() == 33_333
        want = int(np.unpackbits(bv.words.view(np.uint8)).sum())
        assert bv.count() == want

    def test_count_empty_and_full_word_edges(self):
        assert BitVector(0).count() == 0
        bv = BitVector.from_keys(np.arange(64))  # exactly one full word
        assert bv.count() == 64
        bv.set(np.array([63]), False)
        assert bv.count() == 63

    def test_version_bumps_on_mutation(self):
        bv = BitVector.from_keys(np.array([1, 5]))
        v0 = bv.version
        bv.set(np.array([2]), True)
        assert bv.version > v0
        v1 = bv.version
        bv.set(np.array([2]), False)
        assert bv.version > v1
        bv.set(np.array([], dtype=np.int64), True)  # no-op: unchanged
        assert bv.version > v1 and bv.version == v1 + 1

    def test_size_bytes_compressed_once_per_version(self, monkeypatch):
        """The at-rest size is the compressed length, recomputed only
        after a mutation."""
        bv = BitVector.from_keys(np.arange(0, 10_000, 3))
        calls = []
        to_bytes = BitVector.to_bytes
        monkeypatch.setattr(BitVector, "to_bytes",
                            lambda self: calls.append(1) or to_bytes(self))
        first = bv.size_bytes()
        assert bv.size_bytes() == first == len(to_bytes(bv)) and len(calls) == 1
        bv.set(np.random.default_rng(0).integers(0, 10_000, 2_000), True)
        assert bv.size_bytes() == len(to_bytes(bv)) != first and len(calls) == 2
        restored = BitVector.from_bytes(to_bytes(bv))
        assert restored.size_bytes() == bv.size_bytes()
