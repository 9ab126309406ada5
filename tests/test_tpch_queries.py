"""TPC-H query suite for the code-space aggregate/join layer (ISSUE 10).

Small generated TPC-H tables (``repro.data.tpch``) through the real
query verbs:

* Q1-style aggregation — ``GROUP BY l_returnflag, l_linestatus`` with
  count + sum/min/max(l_quantity), with and without the quantity
  predicate — value-identical to the pure-numpy oracle in
  ``tests/tpch_reference.py``;
* lineitem ⋈ orders key-equi join through the composite-key decode
  (``l_orderkey = key // 8``), surviving rows and joined ``o_clerk``
  values checked against a python-dict oracle;
* the tentpole evidence contract on real TPC-H shapes: count-only
  aggregates over the model-backed store report ``rows_decoded == 0``.

Marked ``tpch`` so the dedicated CI job can run it standalone
(``pytest -m tpch``); it stays cheap enough for tier-1 too.
"""

import numpy as np
import pytest
from tpch_reference import (
    assert_aggregate_equal,
    ref_group_aggregate,
    ref_join_mask,
    ref_select,
)

from repro.cluster import ClusterConfig, ShardedDeepMappingStore
from repro.core import DeepMappingConfig, DeepMappingStore, Table
from repro.core.trainer import TrainConfig
from repro.data.tpch import lineitem_like, orders_like

pytestmark = pytest.mark.tpch

TINY = DeepMappingConfig(
    shared=(16,), private=(4,), train=TrainConfig(epochs=2, batch_size=512)
)

N_LINEITEM = 8_400
N_ORDERS = 2_000

#: lineitem keys are pack_composite_key([orderkey, lineno(1..7)]) —
#: mixed-radix with radix 8 on the low digit.
def l_orderkey(keys):
    return keys // 8


@pytest.fixture(scope="module")
def lineitem():
    table = lineitem_like(n=N_LINEITEM, seed=3)
    return table, DeepMappingStore.build(table, TINY)


@pytest.fixture(scope="module")
def orders():
    table = orders_like(n=N_ORDERS, seed=4)
    store = ShardedDeepMappingStore.build(
        table, TINY, ClusterConfig(num_shards=3, policy="range")
    )
    return table, store


class TestQ1Aggregation:
    GROUP = ("l_returnflag", "l_linestatus")
    SPECS = (
        "count", ("sum", "l_quantity"), ("min", "l_quantity"),
        ("max", "l_quantity"),
    )
    REF = (
        ("count", None), ("sum", "l_quantity"), ("min", "l_quantity"),
        ("max", "l_quantity"),
    )

    def test_q1_groupby_matches_oracle(self, lineitem):
        table, store = lineitem
        groups, aggs = ref_group_aggregate(table.columns, self.GROUP, self.REF)
        res = (
            store.query().group_by(*self.GROUP).agg(*self.SPECS)
            .scan().execute()
        )
        assert_aggregate_equal(res, groups, aggs)
        assert res.num_groups == 6  # 3 returnflags x 2 linestatuses

    def test_q1_with_quantity_predicate(self, lineitem):
        table, store = lineitem
        sel = table.columns["l_quantity"] <= 25
        groups, aggs = ref_group_aggregate(
            table.columns, self.GROUP, self.REF, sel=sel
        )
        for pushdown in (True, False):
            res = (
                store.query().where("l_quantity", "<=", 25)
                .group_by(*self.GROUP).agg(*self.SPECS)
                .pushdown(pushdown).scan().execute()
            )
            assert_aggregate_equal(res, groups, aggs)

    def test_count_only_decodes_zero_rows(self, lineitem):
        table, store = lineitem
        res = (
            store.query().group_by(*self.GROUP).agg("count")
            .scan().execute()
        )
        groups, aggs = ref_group_aggregate(
            table.columns, self.GROUP, (("count", None),)
        )
        assert_aggregate_equal(res, groups, aggs)
        assert res.explain.rows_decoded == 0
        assert res.explain.groups_emitted == 6

    def test_shipmode_distribution(self, lineitem):
        table, store = lineitem
        groups, aggs = ref_group_aggregate(
            table.columns, ("l_shipmode",), (("count", None),)
        )
        res = store.query().group_by("l_shipmode").agg("count").scan().execute()
        assert_aggregate_equal(res, groups, aggs)
        assert res.explain.rows_decoded == 0


class TestLineitemOrdersJoin:
    def test_join_matches_oracle(self, lineitem, orders):
        ltable, lstore = lineitem
        otable, ostore = orders
        res = (
            lstore.query().join(ostore, key=l_orderkey, columns=("o_clerk",))
            .scan().execute()
        )
        mask = ref_join_mask(ltable.keys, l_orderkey, otable.keys)
        assert mask.any() and not mask.all()
        np.testing.assert_array_equal(res.keys, ltable.keys[mask])
        clerk = {int(k): int(v) for k, v in zip(
            otable.keys, otable.columns["o_clerk"]
        )}
        np.testing.assert_array_equal(
            np.asarray(res.values["o_clerk"]),
            [clerk[int(k) // 8] for k in res.keys],
        )
        assert res.explain.join_probes == len(ltable.keys)

    def test_join_with_lineitem_predicate(self, lineitem, orders):
        ltable, lstore = lineitem
        otable, ostore = orders
        res = (
            lstore.query().where("l_quantity", ">", 40)
            .join(ostore, key=l_orderkey, columns=("o_clerk",))
            .scan().execute()
        )
        mask = ref_join_mask(ltable.keys, l_orderkey, otable.keys)
        mask &= ltable.columns["l_quantity"] > 40
        np.testing.assert_array_equal(res.keys, ltable.keys[mask])
        np.testing.assert_array_equal(
            np.asarray(res.values["l_quantity"]),
            ltable.columns["l_quantity"][mask],
        )


# --------------------------------------------------- Q12 range selection
Q12_COLUMNS = ("l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate")
#: Q12's lineitem selection (TPC-H v3.0.1 section 2.4.12) at fixed
#: substitution parameters.
Q12_PREDICATES = (
    ("l_shipmode", "in", ("MAIL", "SHIP")),
    ("l_receiptdate", ">=", "1994-01-01"),
    ("l_receiptdate", "<", "1995-01-01"),
)
N_Q12 = 3_000


def _q12_lineitem():
    """A small seeded lineitem over the benchmark generator's domains
    (dbgen's dates and ship modes), Q12's columns only."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        "tpch_lineitem_sf1.py")
    spec = importlib.util.spec_from_file_location("tpch_lineitem_generator", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    keys, columns = gen.generate(
        {"rows": N_Q12, "scale_factor": N_Q12 / 6_001_215}, seed=12
    )
    return Table(keys=keys, columns={c: dom[idx] for c, (dom, idx) in columns.items()
                                     if c in Q12_COLUMNS})


def _q12_store(table, tier):
    cfg = DeepMappingConfig(
        shared=(16,), private=(4,), train=TrainConfig(epochs=2, batch_size=512),
        use_pallas=tier == "fused",
    )
    return DeepMappingStore.build(table, cfg)


def _q12_mutate(table, store, lo, hi):
    """Inserts into the empty slots (line number 0) of orders inside
    ``[lo, hi)``, deletes and updates of rows inside it, applied to the
    store and to a copy of the table alike."""
    keys = table.keys.copy()
    cols = {c: v.copy() for c, v in table.columns.items()}
    inside = np.flatnonzero((keys >= lo) & (keys < hi))
    rng = np.random.default_rng(7)
    new = np.unique(keys[inside] // 8 * 8)[:40]
    donor = rng.choice(inside, new.size)
    new_cols = {c: v[donor] for c, v in cols.items()}
    new_cols["l_shipmode"] = np.where(np.arange(new.size) % 2, "MAIL", "RAIL")
    new_cols["l_receiptdate"] = np.where(np.arange(new.size) % 3, "1994-06-30",
                                         "1993-12-31")
    store.insert(new, new_cols)
    gone = rng.choice(inside, 30, replace=False)
    store.delete(keys[gone])
    upd = rng.choice(np.setdiff1d(inside, gone), 30, replace=False)
    upd_cols = {c: v[upd] for c, v in cols.items()}
    upd_cols["l_shipmode"] = np.full(upd.size, "SHIP")
    upd_cols["l_receiptdate"] = np.full(upd.size, "1994-01-01")
    store.update(keys[upd], upd_cols)
    for c in cols:
        cols[c][upd] = upd_cols[c]
    keep = np.setdiff1d(np.arange(keys.size), gone)
    keys = np.concatenate([keys[keep], new])
    cols = {c: np.concatenate([v[keep], new_cols[c].astype(v.dtype)])
            for c, v in cols.items()}
    return keys, cols


@pytest.fixture(scope="module")
def q12_table():
    return _q12_lineitem()


@pytest.fixture(scope="module")
def q12_stores(q12_table):
    return {tier: _q12_store(q12_table, tier) for tier in ("fused", "jit")}


@pytest.mark.parametrize("case", ["empty", "clipped", "mutated"])
@pytest.mark.parametrize("pushdown", [True, False], ids=["pushdown", "posthoc"])
@pytest.mark.parametrize("tier", ["fused", "jit"])
def test_q12_range_selection_matches_ref_select(q12_table, q12_stores, tier,
                                                pushdown, case):
    """Q12's lineitem selection over a key range, through the normal
    path, equals ``ref_select`` exactly: keys and every projected
    value.  ``fused`` filters in-kernel (interpret mode) and re-runs the
    aux-corrected rows on the host; ``jit`` filters on the host."""
    keys = np.sort(q12_table.keys)
    if case == "empty":
        lo = int(keys[100] // 8 * 8)  # line number 0: never a key
        ranges = [(lo, lo + 1)]
    elif case == "clipped":
        ranges = [(-1_000, int(keys[800])), (int(keys[-900]), int(keys[-1]) + 10_000)]
    else:
        ranges = [(int(keys[1000]), int(keys[1600]))]
    store = q12_stores[tier]
    ref_keys, ref_cols = q12_table.keys, q12_table.columns
    if case == "mutated":
        store = _q12_store(q12_table, tier)
        ref_keys, ref_cols = _q12_mutate(q12_table, store, *ranges[0])
    for lo, hi in ranges:
        want_keys, want = ref_select(ref_keys, ref_cols, lo, hi, Q12_PREDICATES,
                                     Q12_COLUMNS)
        q = store.query().select(*Q12_COLUMNS).where_range(lo, hi).pushdown(pushdown)
        for column, op, value in Q12_PREDICATES:
            q = q.where(column, op, value)
        res = q.execute()
        np.testing.assert_array_equal(res.keys, want_keys)
        for c in Q12_COLUMNS:
            np.testing.assert_array_equal(res.values[c], want[c], err_msg=c)
        assert (case == "empty") == (res.explain.num_keys == 0)
        if tier == "fused" and pushdown and res.explain.num_keys:
            assert res.explain.kernel_filtered
            assert any(s.startswith("filter[kernel:") for s in res.explain.plan)
        assert res.explain.filter_host_rows <= res.explain.num_keys
    if case != "empty":
        assert want_keys.size  # the selection keeps some rows
