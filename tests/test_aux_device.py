"""The ``T_aux`` override on the device against the host probe.

Where a table's resident view fits its pool, ``AuxTable.device_view``
keeps a copy of it in device memory and the engine corrects the model's
codes there (``DeviceView.correct``); ``AuxTable.merge_device`` applies the
delta overlay and counts on the host.  Every case here serves the same
store twice, once so and once with ``device_view`` giving None (the
host path, ``AuxTable.get``), and holds the codes, existence bits,
match bits, decoded values and counts of the two byte for byte, on each
tier that uploads int32 keys, for point lookups, scans, group-bys and
filtered range plans, after writes with and without ``compact()``.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

from conftest import make_periodic_table
from repro.api.plan import ExplainStats, Predicate
from repro.core import DeepMappingConfig, DeepMappingStore
from repro.core.aux_table import AuxTable
from repro.core.encoding import KeyEncoder
from repro.core.model import MLPSpec
from repro.core.trainer import TrainConfig
from repro.kernels import ops as kops
from repro.storage import MemoryPool

TIERS = ("fused", "fused_streamed", "jit_keys")
#: Chunks of 128 keys: several chunks a plan, each padded to its bucket.
CONFIG = dict(shared=(32, 32), private=(16,), inference_batch=128,
              train=TrainConfig(epochs=12, batch_size=256))


def _table():
    # keys 0, 2, 4, ...: the odd keys are gaps
    return make_periodic_table(n=900, period=8, cards=(5, 3, 7))


@contextlib.contextmanager
def _budget(tier, table):
    """The VMEM budget that sends a plan over every head to ``tier``:
    the trunk and one head for ``fused_streamed`` (a plan over one head
    stays resident), the default otherwise."""
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("REPRO_VMEM_BUDGET", raising=False)
        if tier == "fused_streamed":
            spec = MLPSpec(
                base=10, width=KeyEncoder(table.max_key).width,
                shared=CONFIG["shared"],
                private={c: CONFIG["private"] for c in table.columns},
                out_cards={c: int(v.max()) + 1 for c, v in table.columns.items()},
            )
            trunk, heads = kops.resident_parts(spec)
            mp.setenv("REPRO_VMEM_BUDGET", str(
                trunk + max(heads.values()) + kops.activation_bytes(spec, kops.DEFAULT_TILE_N)))
        yield


def _build(tier, pool=None):
    table = _table()
    cfg = DeepMappingConfig(use_pallas=tier != "jit_keys", **CONFIG)
    with _budget(tier, table):
        store = DeepMappingStore.build(table, cfg, pool=pool)
    return table, store


@pytest.fixture(scope="module", params=TIERS)
def served(request):
    return request.param, *_build(request.param)


@contextlib.contextmanager
def _host_path():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(AuxTable, "device_view", lambda self: None)
        yield


@contextlib.contextmanager
def _tiers(store):
    """Record the tier of every device dispatch through ``store``."""
    eng = store.engine
    dispatch = eng.dispatch
    taken = []

    def spy(*args, **kw):
        ticket = dispatch(*args, **kw)
        taken.append((ticket.path, len(ticket.task_order)))
        return ticket

    eng.dispatch = spy
    try:
        yield taken
    finally:
        del eng.dispatch


def _assert_same(a, b, where="result"):
    if isinstance(a, dict):
        assert a.keys() == b.keys(), where
        for k in a:
            _assert_same(a[k], b[k], f"{where}[{k!r}]")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), where
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_same(x, y, f"{where}[{i}]")
    elif isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, (where, a.dtype, b.dtype)
        np.testing.assert_array_equal(a, b, err_msg=where)
    else:
        assert a == b, (where, a, b)


COUNTS = ("aux_keys", "aux_visits", "aux_resident_keys")


def _both(store, run, device=True):
    """``run(store) -> (outputs, ExplainStats)`` on the device path and
    on the host path: outputs byte-identical, counts equal, and every
    key probed in ``T_aux`` corrected on the device (or none, where
    ``device`` is False)."""
    with _tiers(store) as taken:
        got, stats = run(store)
    with _host_path():
        want, host = run(store)
    _assert_same(got, want)
    assert [getattr(stats, c) for c in COUNTS] == [getattr(host, c) for c in COUNTS]
    assert stats.aux_keys > 0 and host.aux_device_keys == 0
    assert stats.aux_device_keys == (stats.aux_keys if device else 0)
    return taken, stats


def _probe_keys(table, n=300, seed=0):
    """Present keys, gap keys, and keys outside the int32 domain, in no
    order; 300 keys make chunks of 128, 128 and 44 (padded to 256)."""
    rng = np.random.default_rng(seed)
    edge = np.array([-7, -1, 2**31 - 1, 2**31, 2**40], dtype=np.int64)
    keys = np.concatenate([rng.choice(table.keys, n - 40 - edge.size, replace=False),
                           rng.choice(table.keys, 20) + 1, table.keys[-1] + 2 * np.arange(1, 21),
                           edge])
    return rng.permutation(keys)


PRED = Predicate("col2", "in", (1, 4, 6))


# ------------------------------------------------------------ operators
def op_point(store, table):
    keys = _probe_keys(table)
    values, exists, match, stats = store._collect_lookup(store._dispatch_lookup(keys))
    return (values, exists, match), stats


def op_point_codes(store, table):
    """The corrected codes, existence and match bits of every chunk."""
    stats = ExplainStats()
    pending = store._dispatch_lookup(_probe_keys(table), None, predicates=(PRED,))
    chunks = list(store._iter_corrected_chunks(pending, stats))
    return chunks, stats


def op_point_filtered(store, table):
    values, exists, match, stats = store._collect_lookup(store._dispatch_lookup(
        _probe_keys(table), ("col0", "col2"), predicates=(PRED,)))
    return (values, exists, match), stats


def op_scan(store, table):
    res = store.query().scan().execute()
    return (res.keys, res.values, res.exists), res.explain


def op_group_by(store, table):
    res = store.query().group_by("col1").agg("count").scan().execute()
    return (res.groups, res.aggregates), res.explain


def op_range_filtered(store, table):
    lo, hi = int(table.keys[100]), int(table.keys[700])
    res = (store.query().select("col0", "col1").where_range(lo, hi)
           .where("col2", "in", (1, 4, 6)).where("col0", "<", 4).execute())
    return (res.keys, res.values, res.exists), res.explain


OPS = {"point": op_point, "point_codes": op_point_codes, "point_filtered": op_point_filtered,
       "scan": op_scan, "group_by": op_group_by, "range_filtered": op_range_filtered}


@pytest.mark.parametrize("op", sorted(OPS))
def test_device_correction_answers_as_the_host_probe(served, op):
    tier, table, store = served
    assert store.aux.num_rows > 0
    taken, _ = _both(store, lambda s: OPS[op](s, table))
    assert taken
    # plans over every head take the fixture's tier; a plan over fewer
    # heads may stay resident under the streamed budget
    heads = len(store.spec.tasks)
    assert {path for path, k in taken if k == heads} <= {tier}
    if op in ("point", "point_codes", "scan"):
        assert (tier, heads) in taken


# --------------------------------------------------------------- writes
def _write(store, table, kind):
    rng = np.random.default_rng(5)
    if kind == "insert":
        new = rng.choice(table.keys, 24, replace=False) + 1        # gap keys
        store.insert(new, {c: rng.integers(0, int(v.max()) + 1, new.size).astype(v.dtype)
                           for c, v in table.columns.items()})
        return new
    if kind == "delete":
        gone = rng.choice(table.keys, 24, replace=False)
        store.delete(gone)
        return gone
    if kind == "update":
        # values the model gets wrong: every key lands in the delta
        keys = rng.choice(table.keys, 24, replace=False)
        codes = store._infer_codes(keys)
        store.update(keys, {t: store.codecs[t].decode((codes[:, i] + 1) % store.codecs[t].cardinality)
                            for i, t in enumerate(store.spec.tasks)})
        return keys
    # an update to what the model predicts: Algorithm 5 drops the key's
    # T_aux row, so it is tombstoned
    in_aux = np.asarray(store.aux._resident_view()[0][:5])
    codes = store._infer_codes(in_aux)
    store.update(in_aux, {t: store.codecs[t].decode(codes[:, i])
                          for i, t in enumerate(store.spec.tasks)})
    assert set(in_aux.tolist()) <= store.aux._tombstones
    return in_aux


WRITES = ("insert", "delete", "update", "update_to_model")


@pytest.mark.parametrize("compact", [False, True], ids=["overlay", "compacted"])
@pytest.mark.parametrize("kind", WRITES)
@pytest.mark.parametrize("tier", TIERS)
def test_writes_answer_as_the_host_probe(tier, kind, compact):
    table, store = _build(tier)
    touched = _write(store, table, kind)
    if compact:
        store.aux.compact()
    tombstones = bool(store.aux._tombstones)
    assert tombstones == (kind in ("delete", "update_to_model") and not compact)

    def run(s):
        keys = np.concatenate([touched, _probe_keys(table)])
        values, exists, match, stats = s._collect_lookup(s._dispatch_lookup(keys))
        res = s.query().group_by("col0").agg("count").scan().execute()
        stats.merge_timings(res.explain)
        return (values, exists, match, res.groups, res.aggregates), stats

    # the copy holds no tombstone: while the table has one, the host probes
    _both(store, run, device=not tombstones)


# ----------------------------------------------------------------- pool
def test_over_pool_table_takes_the_host_path():
    table, store = _build("fused", pool=MemoryPool(256))
    assert store.aux.device_view() is None
    _both(store, lambda s: op_point(s, table), device=False)


# ------------------------------------------------- the table's two halves
def _hand_table(pool=None, wide=False):
    keys = np.arange(0, 400, 10, dtype=np.int64)     # 40 rows, 5 a partition
    codes = np.arange(120, dtype=np.int32).reshape(40, 3)
    if wide:  # 7-, 20- and 30-bit columns
        codes *= np.array([1, 9000, 1 << 23], dtype=np.int32)
    return keys, codes, AuxTable.build(keys, codes, codec="none", partition_bytes=100,
                                       pool=pool)


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("delta", [False, True], ids=["compacted", "delta"])
@pytest.mark.parametrize("order", ["sorted", "shuffled"])
def test_merge_device_answers_and_counts_as_get(order, delta, wide):
    import jax.numpy as jnp

    keys, codes, aux = _hand_table(wide=wide)
    if delta:
        aux.add(np.array([20, 55, 2**33], np.int64),
                np.array([[7, 7, 7], [8, 8, 8], [9, 9, 9]], np.int32))
    q = np.array([0, 20, 30, 31, 55, 100, 120, 395, 399, 500, -3, 2**33], np.int64)
    if order == "shuffled":
        q = np.random.default_rng(1).permutation(q)
    exists = np.ones(q.size, dtype=bool)
    exists[np.flatnonzero(q == 31)] = False            # probed only where it exists
    probe = np.flatnonzero(exists)
    task_idx = [2, 0]
    model = np.full((q.size, 2), -9, dtype=np.int32)

    view = aux.device_view()
    assert view is not None and len(view.codes) == 3
    bucket = 16
    kp = np.full(bucket, -1, np.int32)
    kp[: q.size] = np.where((q >= 0) & (q < 2**31), q, -1)
    mp = np.zeros((bucket, 2), np.int32)
    mp[: q.size] = model
    fixed, hit = view.correct(jnp.asarray(mp), jnp.asarray(kp), tuple(task_idx))
    assert not np.asarray(hit)[q.size:].any()          # padding never hits
    got_codes = np.asarray(fixed)[: q.size].copy()
    dev = ExplainStats()
    rows = aux.merge_device(q, probe, got_codes, np.asarray(hit)[: q.size], task_idx, dev)

    host = ExplainStats()
    found, aux_codes = aux.get(q[probe], host)
    want_rows = probe[found]
    want_codes = model.copy()
    want_codes[want_rows] = aux_codes[:, task_idx][found]
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(got_codes[probe], want_codes[probe])
    assert (dev.aux_keys, dev.aux_visits, dev.aux_resident_keys, dev.aux_device_keys) == (
        host.aux_keys, host.aux_visits, host.aux_resident_keys, probe.size)


@pytest.mark.parametrize("case", ["over_pool", "tombstone", "empty", "beyond_int32", "sparse"])
def test_no_device_view_where_the_copy_cannot_answer(case):
    if case == "empty":
        aux = AuxTable.build(np.zeros(0, np.int64), np.zeros((0, 3), np.int32))
    elif case == "beyond_int32":
        aux = AuxTable.build(np.array([5, 2**31], np.int64), np.zeros((2, 3), np.int32))
    elif case == "sparse":  # a bitmap of 31,251 words for two keys
        aux = AuxTable.build(np.array([5, 10**6], np.int64), np.zeros((2, 3), np.int32))
    else:
        keys, _, aux = _hand_table(MemoryPool(64) if case == "over_pool" else None)
        if case == "tombstone":
            assert aux.device_view() is not None
            aux.remove(keys[:1])
    assert aux.device_view() is None


def test_compact_replaces_the_device_view():
    keys, codes, aux = _hand_table()
    old = aux.device_view()
    assert aux.device_view() is old and aux.device_current(old)
    aux.add(np.array([5], np.int64), np.array([[1, 2, 3]], np.int32))
    assert aux.device_current(old)                      # the delta stays on the host
    aux.compact()
    assert not aux.device_current(old) and aux._device is None
    new = aux.device_view()
    assert new is not old and int(new.ranks[-1]) == 41


@pytest.mark.parametrize("write", ["delete", "update_and_compact"])
def test_write_between_dispatch_and_collect(write, tmp_path):
    """A chunk dispatched before a write and collected after it answers
    as the host probe does in the same order: the correction it carries
    is from a view the write made stale, so the host probes it."""
    table, built = _build("jit_keys")
    built.save(str(tmp_path / "store"))
    keys = _probe_keys(table)
    answers = []
    for path in ("device", "host"):
        store = DeepMappingStore.load(str(tmp_path / "store"))
        with contextlib.ExitStack() as stack:
            if path == "host":
                stack.enter_context(_host_path())
            pending = store._dispatch_lookup(keys)
            assert pending.next_start == keys.size     # every chunk in flight
            assert all((tk.aux_dev is not None) == (path == "device")
                       for _, tk in pending.tickets)
            in_aux = np.intersect1d(keys, store.aux._resident_view()[0])[:4]
            if write == "delete":
                store.delete(in_aux)
            else:
                codes = store._infer_codes(in_aux)
                store.update(in_aux, {
                    t: store.codecs[t].decode((codes[:, i] + 1) % store.codecs[t].cardinality)
                    for i, t in enumerate(store.spec.tasks)})
                store.aux.compact()
            values, exists, _, stats = store._collect_lookup(pending)
        answers.append((values, exists))
        assert stats.aux_device_keys == 0 and stats.aux_keys > 0
    _assert_same(*answers)
