import numpy as np
import pytest

from repro.core.aux_table import AuxTable
from repro.storage import MemoryPool


def make_aux(n=500, m=3, codec="zstd", partition_bytes=1024, pool=None, seed=0):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.permutation(10 * n)[:n]).astype(np.int64)
    codes = rng.integers(0, 100, size=(n, m)).astype(np.int32)
    return keys, codes, AuxTable.build(
        keys, codes, codec=codec, partition_bytes=partition_bytes, pool=pool
    )


class TestAuxTable:
    @pytest.mark.parametrize("codec", ["zstd", "lzma", "gzip", "none"])
    def test_exact_lookup(self, codec):
        keys, codes, aux = make_aux(codec=codec)
        found, got = aux.get(keys)
        assert found.all()
        np.testing.assert_array_equal(got, codes)

    def test_misses(self):
        keys, codes, aux = make_aux()
        missing = np.setdiff1d(np.arange(5000, dtype=np.int64), keys)[:200]
        found, _ = aux.get(missing)
        assert not found.any()

    def test_mixed_shuffled_queries(self):
        keys, codes, aux = make_aux()
        rng = np.random.default_rng(1)
        q = np.concatenate([keys[::3], keys[::3] + 1])
        perm = rng.permutation(q.shape[0])
        found, got = aux.get(q[perm])
        expect_found = np.concatenate(
            [np.ones(keys[::3].shape[0], bool), np.isin(keys[::3] + 1, keys)]
        )[perm]
        np.testing.assert_array_equal(found, expect_found)
        lut = {int(k): c for k, c in zip(keys, codes)}
        for i in np.flatnonzero(found):
            np.testing.assert_array_equal(got[i], lut[int(q[perm][i])])

    def test_partitioning_respects_target(self):
        keys, codes, aux = make_aux(n=1000, partition_bytes=512)
        assert len(aux._partitions) > 1
        row_bytes = 8 + 4 * 3
        assert max(aux._part_rows) <= max(1, 512 // row_bytes)

    def test_delta_overlay(self):
        keys, codes, aux = make_aux()
        nk = np.array([10**6, 10**6 + 1], dtype=np.int64)
        nc = np.array([[1, 2, 3], [4, 5, 6]], dtype=np.int32)
        aux.add(nk, nc)
        found, got = aux.get(nk)
        assert found.all()
        np.testing.assert_array_equal(got, nc)
        # update existing compacted key
        aux.update(keys[:1], np.array([[9, 9, 9]], dtype=np.int32))
        found, got = aux.get(keys[:1])
        assert found[0] and got[0].tolist() == [9, 9, 9]

    def test_tombstones(self):
        keys, codes, aux = make_aux()
        aux.remove(keys[:5])
        found, _ = aux.get(keys[:6])
        assert found.tolist() == [False] * 5 + [True]

    def test_compact_preserves_content(self):
        keys, codes, aux = make_aux()
        aux.remove(keys[:10])
        nk = np.array([10**6], dtype=np.int64)
        aux.add(nk, np.array([[7, 7, 7]], dtype=np.int32))
        pre_found, pre_got = aux.get(np.concatenate([keys, nk]))
        aux.compact()
        post_found, post_got = aux.get(np.concatenate([keys, nk]))
        np.testing.assert_array_equal(pre_found, post_found)
        np.testing.assert_array_equal(pre_got[pre_found], post_got[post_found])
        assert not aux._delta and not aux._tombstones

    def test_size_accounting_moves(self):
        keys, codes, aux = make_aux()
        base = aux.size_bytes()
        aux.add(
            np.arange(10**6, 10**6 + 100, dtype=np.int64),
            np.zeros((100, 3), dtype=np.int32),
        )
        assert aux.size_bytes() > base

    def test_shared_pool_eviction(self):
        pool = MemoryPool(budget_bytes=4096)
        keys, codes, aux = make_aux(n=2000, partition_bytes=1024, pool=pool)
        found, _ = aux.get(keys)
        assert found.all()
        assert pool.evictions > 0
        assert pool.used_bytes <= 4096

    def test_state_roundtrip(self):
        keys, codes, aux = make_aux()
        state = aux.to_state()
        aux2 = AuxTable.from_state(state)
        found, got = aux2.get(keys)
        assert found.all()
        np.testing.assert_array_equal(got, codes)

    def test_empty_table(self):
        aux = AuxTable.build(
            np.zeros(0, dtype=np.int64), np.zeros((0, 2), dtype=np.int32)
        )
        found, _ = aux.get(np.array([1, 2, 3]))
        assert not found.any()
        assert aux.size_bytes() >= 0


class TestAuxCounters:
    """``AuxTable.get`` counts once per call: keys probed by outcome and
    partitions visited, into the registry and the caller's stats."""

    @pytest.fixture()
    def reg(self):
        from repro import obs

        fresh = obs.MetricsRegistry()
        prev = obs.set_registry(fresh)
        yield fresh
        obs.set_registry(prev)

    def test_counts_exact_on_a_hand_built_table(self, reg):
        from repro.api.plan import ExplainStats

        # 20-byte rows in 100-byte partitions: 5 rows each, keys 0,10,..,190
        keys = np.arange(0, 200, 10, dtype=np.int64)
        codes = np.arange(40, dtype=np.int32).reshape(20, 2)
        aux = AuxTable.build(keys, codes, codec="none", partition_bytes=100)
        assert len(aux._partitions) == 4
        # partition 0 three times, partition 2 once, an absent key inside
        # partition 3, and a key below the first boundary (no partition)
        probe = np.array([0, 40, 100, 155, -5, 10], dtype=np.int64)
        stats = ExplainStats()
        found, _ = aux.get(probe, stats)
        assert found.tolist() == [True, True, True, False, False, True]
        visits = len({int(p) for p in np.searchsorted(keys[::5], probe, "right") - 1
                      if p >= 0})
        assert visits == 3
        assert (stats.aux_keys, stats.aux_visits) == (6, 3)
        probed = reg.counter("deepmap_aux_keys_total")
        assert probed.value(outcome="found") == 4
        assert probed.value(outcome="absent") == 2
        assert probed.value(outcome="found") + probed.value(outcome="absent") == probe.size
        assert reg.counter("deepmap_aux_partition_visits_total").value() == visits

    def test_decompress_span_only_on_pool_misses(self):
        from repro import obs

        trc = obs.Tracer()
        prev = obs.set_tracer(trc)
        try:
            keys, _, aux = make_aux(partition_bytes=256)
            aux.get(keys[:3])
            aux.get(keys[:3])
        finally:
            obs.set_tracer(prev)
        assert len(trc.spans("aux.get")) == 2
        (miss,) = trc.spans("aux.decompress")
        assert miss.parent == "aux.get"
        assert (aux.pool.hits, aux.pool.misses) == (1, 1)
