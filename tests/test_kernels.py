"""Per-kernel validation: shape sweeps against the pure-jnp oracles in
``repro.kernels.ref`` (interpret=True on CPU), plus hypothesis property
tests on the packing/padding invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests only — the oracle conformance suite runs without it
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAS_HYPOTHESIS = False

from repro.core.bitvector import BitVector
from repro.core.encoding import KeyEncoder
from repro.core.inference import InferenceEngine
from repro.core.model import MLPSpec, init_params
from repro.kernels import bitvector_test, fused_mlp_codes, fused_mlp_logits
from repro.kernels.ops import check_vmem_budget
from repro.kernels.ref import (
    ref_bitvector_test,
    ref_fused_lookup,
    ref_fused_mlp_codes,
    ref_fused_mlp_logits,
)


def make_model(shared, private, cards, base=10, width=5, seed=0):
    spec = MLPSpec(
        base=base,
        width=width,
        shared=shared,
        private={f"t{i}": private for i in range(len(cards))},
        out_cards={f"t{i}": c for i, c in enumerate(cards)},
    )
    return spec, init_params(spec, seed=seed)


SHAPE_SWEEP = [
    # (shared, private, cards, base, n)
    ((64, 32), (16,), (7,), 10, 300),
    ((48,), (), (5, 3), 10, 64),
    ((), (24,), (9,), 10, 257),       # no shared trunk: head-first embed
    ((), (), (4,), 10, 128),          # degenerate: input -> logits
    ((32, 32), (16, 8), (300,), 10, 100),  # card > 256
    ((16,), (8,), (3, 5, 7), 2, 500),  # binary digit base
    ((128,), (64,), (11,), 16, 1000),  # hex base, larger batch
]


class TestFusedMLP:
    @pytest.mark.parametrize("shared,private,cards,base,n", SHAPE_SWEEP)
    def test_logits_match_oracle(self, shared, private, cards, base, n):
        spec, params = make_model(shared, private, cards, base=base)
        rng = np.random.default_rng(42)
        digits = jnp.asarray(
            rng.integers(0, base, size=(n, spec.width)).astype(np.int32)
        )
        got = fused_mlp_logits(params, spec, digits)
        want = ref_fused_mlp_logits(params, digits, spec)
        for t in spec.tasks:
            assert got[t].shape == want[t].shape
            np.testing.assert_allclose(got[t], want[t], rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("shared,private,cards,base,n", SHAPE_SWEEP)
    def test_codes_match_oracle(self, shared, private, cards, base, n):
        spec, params = make_model(shared, private, cards, base=base)
        rng = np.random.default_rng(7)
        digits = jnp.asarray(
            rng.integers(0, base, size=(n, spec.width)).astype(np.int32)
        )
        got = fused_mlp_codes(params, spec, digits)
        want = ref_fused_mlp_codes(params, digits, spec)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("tile_n", [8, 64, 256])
    def test_tile_size_invariance(self, tile_n):
        spec, params = make_model((32,), (16,), (6,))
        digits = jnp.asarray(
            np.random.default_rng(0).integers(0, 10, (100, 5)).astype(np.int32)
        )
        a = fused_mlp_codes(params, spec, digits, tile_n=tile_n)
        b = ref_fused_mlp_codes(params, digits, spec)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_batch_not_multiple_of_tile(self):
        spec, params = make_model((16,), (), (3,))
        digits = jnp.asarray(
            np.random.default_rng(1).integers(0, 10, (77, 5)).astype(np.int32)
        )
        got = fused_mlp_codes(params, spec, digits, tile_n=64)
        assert got.shape == (77, 1)

    def test_vmem_budget_rejects_oversized(self):
        spec, _ = make_model((2048, 2048), (2048,), (1000,))
        with pytest.raises(ValueError, match="VMEM"):
            check_vmem_budget(spec, tile_n=256)

    def test_store_with_pallas_path_lossless(self):
        """End-to-end: hybrid store built AND queried via the kernel."""
        from conftest import make_periodic_table
        from repro.core import DeepMappingConfig, DeepMappingStore
        from repro.core.trainer import TrainConfig

        table = make_periodic_table(n=500)
        cfg = DeepMappingConfig(
            shared=(48,),
            private=(16,),
            train=TrainConfig(epochs=10, batch_size=256),
            use_pallas=True,
        )
        store = DeepMappingStore.build(table, cfg)
        vals, exists = store.lookup(table.keys)
        assert exists.all()
        for c, col in table.columns.items():
            np.testing.assert_array_equal(vals[c], col)


def make_lookup_setup(max_key=9999, residues=(), tasks=2, seed=3):
    """Encoder + model + bitvector triple for key-level conformance."""
    enc = KeyEncoder(max_key, base=10, residues=residues)
    cards = tuple(3 + 2 * i for i in range(tasks))
    spec = MLPSpec(
        base=10,
        width=enc.width,
        shared=(32,),
        private={f"t{i}": (16,) for i in range(tasks)},
        out_cards={f"t{i}": c for i, c in enumerate(cards)},
    )
    params = init_params(spec, seed=seed)
    rng = np.random.default_rng(seed)
    present = rng.choice(max_key + 1, size=(max_key + 1) // 3, replace=False)
    bv = BitVector.from_keys(present)
    return enc, spec, params, bv


class TestFusedLookupConformance:
    """The ISSUE-3 acceptance bar: the fused key-encode+exist kernel
    (and every engine fallback path) must be byte-identical to the
    reference staged path — host digits + jnp forward + host
    BitVector.test — on every conformance case.  Runs in interpret
    mode on CPU CI (the ops wrapper auto-selects it off-TPU)."""

    TILE = 64

    def _engine(self, enc, spec, params, bv, use_pallas):
        return InferenceEngine(
            enc, spec, params, bv, use_pallas=use_pallas, tile_n=self.TILE
        )

    def _assert_matches(self, eng, enc, spec, params, bv, keys, tasks=None):
        t = eng.dispatch(keys, tasks=tasks, want_exists=True)
        codes, exists = eng.collect(t)
        if exists is None:  # non-fused paths: host existence fallback
            exists = bv.test(keys)
        else:
            assert t.path == "fused"  # only the kernel returns exist bits
        ref_codes, ref_exists = ref_fused_lookup(params, keys, enc, bv, spec)
        if tasks is not None:
            cols = [spec.tasks.index(x) for x in tasks]
            ref_codes = ref_codes[:, cols]
        np.testing.assert_array_equal(codes, ref_codes)
        np.testing.assert_array_equal(exists, ref_exists)
        return t.path

    @pytest.mark.parametrize("use_pallas", [True, False])
    @pytest.mark.parametrize(
        "n", [1, 63, 64, 65, 127, 128, 129, 500]
    )  # tile_n-1 / tile_n / bucket+1 boundaries for TILE=64
    def test_bucket_boundaries(self, use_pallas, n):
        enc, spec, params, bv = make_lookup_setup()
        eng = self._engine(enc, spec, params, bv, use_pallas)
        keys = np.random.default_rng(n).integers(0, 10000, size=n).astype(np.int64)
        path = self._assert_matches(eng, enc, spec, params, bv, keys)
        assert path == ("fused" if use_pallas else "jit_keys")

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_keys_beyond_encoder_capacity(self, use_pallas):
        enc, spec, params, bv = make_lookup_setup()
        eng = self._engine(enc, spec, params, bv, use_pallas)
        keys = np.array(
            [0, 1, 9999, 10000, 10001, 123456, 2**31 - 1, 2**31, 2**40, -1, -7],
            dtype=np.int64,
        )
        self._assert_matches(eng, enc, spec, params, bv, keys)

    @pytest.mark.parametrize("use_pallas", [True, False])
    @pytest.mark.parametrize("residues", [(7,), (5, 12)])
    def test_residue_encoders(self, use_pallas, residues):
        enc, spec, params, bv = make_lookup_setup(residues=residues)
        eng = self._engine(enc, spec, params, bv, use_pallas)
        keys = np.random.default_rng(1).integers(0, 10000, size=300).astype(np.int64)
        self._assert_matches(eng, enc, spec, params, bv, keys)

    @pytest.mark.parametrize("use_pallas", [True, False])
    @pytest.mark.parametrize("subset", [("t0",), ("t1",), ("t1", "t0")])
    def test_projection_pushdown_subsets(self, use_pallas, subset):
        enc, spec, params, bv = make_lookup_setup(tasks=2)
        eng = self._engine(enc, spec, params, bv, use_pallas)
        keys = np.random.default_rng(2).integers(0, 10000, size=200).astype(np.int64)
        self._assert_matches(eng, enc, spec, params, bv, keys, tasks=subset)

    def test_exists_tracks_bitvector_mutations(self):
        """Fused existence bits must follow set/clear (device word
        re-upload keyed by the bitvector's version counter)."""
        enc, spec, params, bv = make_lookup_setup()
        eng = self._engine(enc, spec, params, bv, use_pallas=True)
        keys = np.arange(0, 128, dtype=np.int64)
        self._assert_matches(eng, enc, spec, params, bv, keys)
        bv.set(np.array([2, 4, 6]), True)
        bv.set(np.array([1, 3]), False)
        self._assert_matches(eng, enc, spec, params, bv, keys)
        # growth beyond the old word array reshapes the kernel input
        bv.set(np.array([50_000]), True)
        self._assert_matches(eng, enc, spec, params, bv,
                             np.array([49_999, 50_000, 50_001], dtype=np.int64))

    def test_bucketed_compile_count(self):
        """50 distinct batch sizes must compile O(log N) programs."""
        enc, spec, params, bv = make_lookup_setup()
        eng = InferenceEngine(enc, spec, params, bv, use_pallas=False, tile_n=256)
        rng = np.random.default_rng(0)
        sizes = rng.choice(np.arange(1, 16384), size=50, replace=False)
        for n in sizes:
            eng.infer(rng.integers(0, 10000, size=int(n)).astype(np.int64))
        assert eng.stats.compiles <= 8, eng.stats.compiles

    def test_module_names_the_roofline_readers_key_on(self):
        """The benchmark sums device time per XLA module by name: the
        jitted functions keep theirs whatever the kernels are named."""
        from repro.core.inference import _codes_from_keys_jit
        from repro.kernels import bitvector as bv_kernel
        from repro.kernels import fused_mlp as fm
        from repro.kernels import ops as kops

        enc, spec, params, bv = make_lookup_setup()
        keys = jnp.zeros(self.TILE, jnp.int32)
        flat, _ = kops.pad_flat_weights(params, spec)
        words = jnp.asarray(bv_kernel.pack_words32(bv.words))
        fused = fm.fused_lookup_call.lower(
            keys, words, tuple(flat), spec, self.TILE, kops.LANE,
            tuple(enc.position_ops()), enc.capacity, True,
        ).as_text()
        assert "module @jit_fused_lookup_call" in fused
        jit_tier = _codes_from_keys_jit.lower(
            params, keys, spec, tuple(enc.position_ops()), enc.capacity,
        ).as_text()
        assert "module @jit__codes_from_keys_jit" in jit_tier

    def test_weight_cache_reused_across_calls(self):
        enc, spec, params, bv = make_lookup_setup()
        eng = self._engine(enc, spec, params, bv, use_pallas=True)
        keys = np.arange(200, dtype=np.int64)
        for _ in range(4):
            eng.collect(eng.dispatch(keys, want_exists=True))
        assert eng.stats.weight_cache_misses == 1
        assert eng.stats.dispatches == 4


class TestBitvectorKernel:
    @pytest.mark.parametrize("capacity", [64, 100, 1000, 65536])
    def test_matches_host_bitvector(self, capacity):
        rng = np.random.default_rng(capacity)
        keys = rng.choice(capacity, size=capacity // 3, replace=False)
        bv = BitVector.from_keys(keys, capacity=capacity)
        q = rng.integers(0, capacity, size=500).astype(np.int64)
        got = bitvector_test(bv.words, jnp.asarray(q))
        np.testing.assert_array_equal(np.asarray(got), bv.test(q))

    def test_oracle_agrees_with_kernel(self):
        rng = np.random.default_rng(3)
        keys = rng.choice(4096, size=1000, replace=False)
        bv = BitVector.from_keys(keys, capacity=4096)
        words32 = jnp.asarray(bv.words.view(np.uint32))
        q = jnp.asarray(rng.integers(0, 4096, size=256).astype(np.int32))
        ref = ref_bitvector_test(words32, q)
        got = bitvector_test(bv.words, q).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


if HAS_HYPOTHESIS:

    class TestKernelProperties:
        @settings(max_examples=25, deadline=None)
        @given(
            keys=st.lists(st.integers(0, 99999), min_size=1, max_size=64, unique=True),
            probe=st.lists(st.integers(0, 99999), min_size=1, max_size=64),
        )
        def test_bitvector_membership_property(self, keys, probe):
            bv = BitVector.from_keys(np.array(keys), capacity=100000)
            got = np.asarray(bitvector_test(bv.words, jnp.asarray(np.array(probe))))
            want = np.isin(np.array(probe), np.array(keys))
            np.testing.assert_array_equal(got, want)

        @settings(max_examples=10, deadline=None)
        @given(
            n=st.integers(1, 80),
            base=st.sampled_from([2, 10, 16]),
            card=st.integers(2, 40),
            seed=st.integers(0, 2**16),
        )
        def test_fused_codes_in_range(self, n, base, card, seed):
            spec, params = make_model((16,), (), (card,), base=base, seed=seed)
            rng = np.random.default_rng(seed)
            digits = jnp.asarray(rng.integers(0, base, (n, 5)).astype(np.int32))
            codes = np.asarray(fused_mlp_codes(params, spec, digits))
            assert codes.shape == (n, 1)
            assert (codes >= 0).all() and (codes < card).all()

        @settings(max_examples=10, deadline=None)
        @given(seed=st.integers(0, 2**16))
        def test_padding_is_exact(self, seed):
            """Zero-padding to MXU alignment must not change any logit."""
            spec, params = make_model((40,), (24,), (13,), seed=seed)
            rng = np.random.default_rng(seed)
            digits = jnp.asarray(rng.integers(0, 10, (33, 5)).astype(np.int32))
            got = fused_mlp_logits(params, spec, digits)
            want = ref_fused_mlp_logits(params, digits, spec)
            np.testing.assert_allclose(got["t0"], want["t0"], rtol=1e-5, atol=1e-5)
