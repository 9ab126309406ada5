import numpy as np
import pytest

from repro.api.plan import ExplainStats
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


#: pool budgets: the whole table fits (resident view) / it does not
POOLS = {"resident": 1 << 30, "partitioned": 4096}


def _reference_probe(keys, codes, aux, delta, dead, q):
    """A per-key loop over a dict: the expected found mask, the codes of
    the found rows, and the partitions visited by keys the delta misses."""
    lut = {int(k): c for k, c in zip(keys, codes)}
    lut.update(delta)
    for k in dead:
        lut.pop(k, None)
    found = np.array([int(k) in lut for k in q])
    got = np.array([lut[int(k)] for k in q if int(k) in lut]).reshape(-1, codes.shape[1])
    bounds = keys[:: aux._part_rows[0]]
    visits = {int(np.searchsorted(bounds, k, "right")) - 1 for k in q if int(k) not in delta}
    visits.discard(-1)
    return found, got, len(visits)


class TestProbePaths:
    """The resident sorted view and the partitioned path give the same
    answers and counts; the view is built only where it fits the pool."""

    @pytest.mark.parametrize("path", sorted(POOLS))
    def test_paths_answer_and_count_alike(self, path):
        keys, codes, aux = make_aux(n=600, pool=MemoryPool(POOLS[path]))
        assert aux._compacted_rows * (8 + 4 * 3) > POOLS["partitioned"]
        rng = np.random.default_rng(7)
        delta = {10**6: np.array([1, 2, 3], np.int32), int(keys[9]): np.array([9, 9, 9], np.int32)}
        aux.add(np.array(list(delta), np.int64), np.stack(list(delta.values())))
        dead = [int(k) for k in keys[40:46]]
        aux.remove(np.array(dead, np.int64))
        inside_absent = np.setdiff1d(np.arange(keys[0], keys[-1]), keys)[::37]
        q = rng.permutation(np.concatenate([
            keys, keys[::5], keys[::11],                  # duplicates
            inside_absent,                                # between stored keys
            [keys[0] - 1, -7, keys[-1] + 1, 10**7],       # below / above every boundary
            list(delta),                                  # delta overlay
        ]).astype(np.int64))
        want_found, want_codes, want_visits = _reference_probe(keys, codes, aux, delta, dead, q)
        stats = ExplainStats()
        found, got = aux.get(q, stats)
        np.testing.assert_array_equal(found, want_found)
        np.testing.assert_array_equal(got[found], want_codes)
        assert (stats.aux_keys, stats.aux_visits) == (q.size, want_visits)
        assert stats.aux_resident_keys == (q.size if path == "resident" else 0)
        view_key = ("aux-flat", id(aux), aux._generation)
        assert (aux.pool.peek(view_key) is not None) == (path == "resident")

    @pytest.mark.parametrize("path", sorted(POOLS))
    def test_hand_built_table_counts_on_both_paths(self, path):
        from repro import obs

        keys = np.arange(0, 200, 10, dtype=np.int64)
        codes = np.arange(40, dtype=np.int32).reshape(20, 2)
        # the whole table is 20 rows of 16 bytes: 320 bytes decompressed
        pool = MemoryPool(POOLS[path] if path == "resident" else 200)
        aux = AuxTable.build(keys, codes, codec="none", partition_bytes=100, pool=pool)
        probe = np.array([0, 40, 100, 155, -5, 10], dtype=np.int64)
        stats = ExplainStats()
        trc, reg = obs.Tracer(), obs.MetricsRegistry()
        prev_t, prev_r = obs.set_tracer(trc), obs.set_registry(reg)
        try:
            found, got = aux.get(probe, stats)
        finally:
            obs.set_tracer(prev_t)
            obs.set_registry(prev_r)
        assert found.tolist() == [True, True, True, False, False, True]
        np.testing.assert_array_equal(got[found], codes[[0, 4, 10, 1]])
        resident = probe.size if path == "resident" else 0
        assert (stats.aux_keys, stats.aux_visits, stats.aux_resident_keys) == (6, 3, resident)
        (span,) = trc.spans("aux.get")
        # over the pool, each 80-byte partition is a wave of its own (half
        # the 200-byte budget holds one): three misses, each inline
        assert span.args == {"keys": 6, "visits": 3, "resident": resident, "device": 0,
                             "decompressed": 0 if resident else 3, "parallel": 0}
        by_path = reg.counter("deepmap_aux_path_keys_total")
        assert by_path.value(path="resident") == resident
        assert by_path.value(path="partitioned") == probe.size - resident

    def test_view_not_built_where_it_would_evict_another_table(self):
        pool = MemoryPool(15_000)
        keys_a, codes_a, a = make_aux(n=500, pool=pool, seed=1)
        keys_b, codes_b, b = make_aux(n=500, pool=pool, seed=2)
        a.get(keys_a)
        view_a = ("aux-flat", id(a), a._generation)
        assert pool.used_bytes == 500 * 20 and pool.peek(view_a) is not None
        found, got = b.get(keys_b[:60])                   # two of b's partitions
        assert found.all()
        np.testing.assert_array_equal(got, codes_b[:60])
        assert pool.peek(("aux-flat", id(b), b._generation)) is None
        assert pool.peek(view_a) is not None and pool.evictions == 0
        stats = ExplainStats()
        b.get(keys_b[:60], stats)
        assert stats.aux_resident_keys == 0 and stats.aux_visits == 2

    def test_view_releases_only_its_own_partitions(self):
        pool = MemoryPool(100)
        pool.get(("aux", 1, 1, 0), lambda: ("own", 30))
        pool.get(("aux", 2, 1, 0), lambda: ("other", 30))
        built = []

        def build():
            built.append(True)
            return "view"

        mine = lambda k: k[0] == "aux" and k[1] == 1  # noqa: E731
        assert pool.admit(("aux-flat", 1, 1), 71, build, mine) is None
        assert not built and pool.used_bytes == 60
        assert pool.admit(("aux-flat", 1, 1), 70, build, mine) == "view"
        assert pool.used_bytes == 100 and pool.evictions == 0
        assert pool.peek(("aux", 1, 1, 0)) is None and pool.peek(("aux", 2, 1, 0)) == "other"
        assert (pool.misses, pool.hits) == (3, 1)

    def test_compact_drops_the_old_view(self):
        keys, codes, aux = make_aux()
        aux.get(keys[:10])
        old = ("aux-flat", id(aux), aux._generation)
        assert aux.pool.peek(old) is not None
        aux.remove(keys[:1])
        aux.compact()
        assert aux.pool.peek(old) is None and aux.pool.used_bytes == 0
        stats = ExplainStats()
        found, got = aux.get(keys, stats)
        assert found.tolist() == [False] + [True] * (keys.size - 1)
        np.testing.assert_array_equal(got[1:], codes[1:])
        assert stats.aux_resident_keys == keys.size
        assert aux.pool.used_bytes == (keys.size - 1) * (8 + 4 * 3)


#: 2,000 rows of 20 bytes in 1,020-byte partitions: 40 partitions and
#: 40,000 bytes decompressed.  The pool holds a quarter of that, and half
#: of it (5,000 bytes) makes a wave of four partitions.
OVER = dict(n=2000, partition_bytes=1024)
OVER_POOL = 10_000
PART_BYTES = 51 * 20


class _RecordingPool(MemoryPool):
    """A pool that records its used bytes after every insertion."""

    def __init__(self, budget):
        super().__init__(budget)
        self.used_after_put = []

    def put(self, key, obj, nbytes):
        obj = super().put(key, obj, nbytes)
        self.used_after_put.append(self.used_bytes)
        return obj


def _accounted(pool):
    return sum(n for _, n in pool._entries.values())


def _mutate(case, keys, tables):
    """Apply ``case``'s overlay to every table; returns the queries, the
    delta rows and the tombstoned keys."""
    rng = np.random.default_rng(11)
    delta, dead = {}, []
    if case == "delta_overlay":
        delta = {int(keys[7]): np.array([5, 5, 5], np.int32),
                 int(keys[-1]) + 3: np.array([1, 2, 3], np.int32)}
    if case == "tombstones":
        dead = [int(k) for k in keys[300:360]]
    for aux in tables:
        if delta:
            aux.add(np.array(list(delta), np.int64), np.stack(list(delta.values())))
        if dead:
            aux.remove(np.array(dead, np.int64))
    inside_absent = np.setdiff1d(np.arange(keys[0], keys[-1]), keys)
    q = {
        "present_sorted": keys,
        "unsorted_duplicates": rng.permutation(np.concatenate([keys, keys[::3]])),
        "absent": np.concatenate([inside_absent[::17], [keys[-1] + 1, 10**9]]),
        "below_first_partition": np.concatenate([[keys[0] - 1, -5], keys[:30]]),
        "delta_overlay": rng.permutation(np.concatenate([keys[::2], list(delta)])),
        "tombstones": rng.permutation(keys),
    }[case]
    return np.asarray(q, np.int64), delta, dead


class TestParallelDecompress:
    """The partitioned path decompresses a wave's pool misses together on
    the shared worker threads, and answers as the resident path and a
    per-key reference do, under the pool's budget."""

    @pytest.mark.parametrize("case", ["present_sorted", "unsorted_duplicates", "absent",
                                      "below_first_partition", "delta_overlay",
                                      "tombstones"])
    def test_answers_as_the_resident_path_and_the_reference(self, case):
        keys, codes, over = make_aux(pool=MemoryPool(OVER_POOL), **OVER)
        _, _, resident = make_aux(pool=MemoryPool(1 << 30), **OVER)
        assert len(over._partitions) == 40 and over._compacted_rows * 20 > 2 * OVER_POOL
        q, delta, dead = _mutate(case, keys, (over, resident))
        want_found, want_codes, want_visits = _reference_probe(keys, codes, over, delta,
                                                               dead, q)
        stats, res_stats = ExplainStats(), ExplainStats()
        found, got = over.get(q, stats)
        res_found, res_got = resident.get(q, res_stats)
        np.testing.assert_array_equal(found, want_found)
        np.testing.assert_array_equal(got[found], want_codes)
        np.testing.assert_array_equal(res_found, found)
        np.testing.assert_array_equal(res_got[res_found], got[found])
        assert stats.aux_resident_keys == 0 and res_stats.aux_resident_keys == q.size
        assert stats.aux_visits == res_stats.aux_visits == want_visits
        # a cold pool: every visit misses; the first wave holds two or
        # more of them wherever two partitions are visited
        assert stats.aux_decompressed == want_visits
        assert (stats.aux_parallel > 0) == (want_visits > 1)
        assert (res_stats.aux_decompressed, res_stats.aux_parallel) == (0, 0)

    def test_pool_budget_and_wave_bytes_hold(self):
        from repro import obs

        pool = _RecordingPool(OVER_POOL)
        keys, codes, aux = make_aux(pool=pool, **OVER)
        rng = np.random.default_rng(3)
        trc = obs.Tracer()
        prev = obs.set_tracer(trc)
        try:
            for q in (keys, keys, rng.choice(keys, 700), keys[::-1]):
                found, got = aux.get(q)
                assert found.all()
        finally:
            obs.set_tracer(prev)
        assert pool.used_after_put and max(pool.used_after_put) <= OVER_POOL
        assert pool.used_bytes == _accounted(pool) <= OVER_POOL
        waves = trc.spans("aux.decompress")
        assert waves and all(w.parent == "aux.get" for w in waves)
        assert max(w.args["parts"] for w in waves) * PART_BYTES <= OVER_POOL // 2
        decompressed = sum(s.args["decompressed"] for s in trc.spans("aux.get"))
        assert sum(w.args["parts"] for w in waves) == decompressed == pool.misses

    def test_span_args_and_stats_count_misses_and_workers(self):
        from repro import obs
        from repro.core.aux_table import _decompress_workers

        keys, codes, aux = make_aux(pool=MemoryPool(OVER_POOL), **OVER)
        first_four = keys[: 4 * 51]                       # partitions 0-3: one wave
        calls = [first_four,                              # four misses, on the workers
                 first_four,                              # four hits
                 np.concatenate([first_four, keys[510:513]])]  # hits, then one miss
        trc = obs.Tracer()
        prev = obs.set_tracer(trc)
        got_stats = []
        try:
            for q in calls:
                stats = ExplainStats()
                found, got = aux.get(q, stats)
                assert found.all()
                np.testing.assert_array_equal(got, codes[np.searchsorted(keys, q)])
                got_stats.append(stats)
        finally:
            obs.set_tracer(prev)
        counts = [(s.aux_visits, s.aux_decompressed, s.aux_parallel) for s in got_stats]
        assert counts == [(4, 4, 4), (4, 0, 0), (5, 1, 0)]
        spans = trc.spans("aux.get")
        assert [(s.args["decompressed"], s.args["parallel"]) for s in spans] == [
            (4, 4), (0, 0), (1, 0)]
        waves = trc.spans("aux.decompress")
        cores = _decompress_workers()[1]
        assert [(w.args["parts"], w.args["workers"]) for w in waves] == [
            (4, min(4, cores)), (1, 0)]
        merged = ExplainStats()
        for s in got_stats:
            merged.merge_timings(s)
        assert (merged.aux_decompressed, merged.aux_parallel) == (5, 4)

    def test_one_miss_decompresses_inline(self, monkeypatch):
        from repro.core import aux_table

        keys, codes, aux = make_aux(pool=MemoryPool(OVER_POOL), **OVER)

        def no_workers():
            raise AssertionError("one miss must not reach the worker threads")

        monkeypatch.setattr(aux_table, "_decompress_workers", no_workers)
        counts = []
        for _ in range(2):                                # a miss, then a hit
            stats = ExplainStats()
            found, got = aux.get(keys[:5], stats)
            assert found.all()
            np.testing.assert_array_equal(got, codes[:5])
            counts.append((stats.aux_decompressed, stats.aux_parallel))
        assert counts == [(1, 0), (0, 0)]
        assert (aux.pool.misses, aux.pool.hits) == (1, 1)

    def test_concurrent_callers_answer_correctly(self):
        import os
        import sys
        import threading

        keys, codes, aux = make_aux(pool=MemoryPool(OVER_POOL), **OVER)
        lut = dict(zip(keys.tolist(), map(tuple, codes.tolist())))
        absent = np.setdiff1d(np.arange(keys[0], keys[-1]), keys)
        errors, done = [], []

        def caller(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(4):
                    q = rng.permutation(np.concatenate(
                        [rng.choice(keys, 600), rng.choice(absent, 60)]))
                    found, got = aux.get(q)
                    assert found.tolist() == [int(k) in lut for k in q]
                    assert [tuple(r) for r in got[found].tolist()] == [
                        lut[int(k)] for k in q[found]]
                done.append(seed)
            except Exception as e:  # reported below, with its caller
                errors.append((seed, repr(e)))

        n = 2 * (os.cpu_count() or 4)
        threads = [threading.Thread(target=caller, args=(i,)) for i in range(n)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not errors and sorted(done) == list(range(n))
        assert aux.pool.used_bytes == _accounted(aux.pool) <= OVER_POOL

    def test_put_of_a_cached_key_keeps_the_first(self):
        pool = MemoryPool(100)
        assert pool.put("k", "first", 30) == "first"
        assert pool.put("k", "second", 30) == "first"
        assert pool.used_bytes == 30 and pool.lookup("k") == "first"
        assert pool.lookup("absent") is None and (pool.hits, pool.misses) == (1, 1)
        assert pool.put("huge", "streamed", 101) == "streamed" and pool.used_bytes == 30


def test_zstd_context_is_one_a_thread():
    import threading

    pytest.importorskip("zstandard")
    from repro.storage import codecs

    codec = codecs.get_codec("zstd")
    blob = codec.compress(b"deepmapping" * 1000)
    mine = codecs._zstd_decompressor()
    assert codecs._zstd_decompressor() is mine
    seen = []

    def other():
        seen.append((codecs._zstd_decompressor(), codec.decompress(blob)))

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    ((theirs, data),) = seen
    assert theirs is not mine and data == b"deepmapping" * 1000
    assert codec.decompress(blob) == data
