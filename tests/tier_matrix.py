"""Tier x operator differential matrix over the benchmark's relations.

Each ``tests/test_tier_matrix_<setting>.py`` builds the two relations
the chip cells serve (``bench/configs/tpcds_customer_demographics`` and
``tpch_lineitem_sf1``, cut to the rows, model width and batch of
``tests/bench/benchtiny.py``) through the benchmark's own
``build_store`` (``repro.build``, save, ``repro.open``), under one engine
setting, and serves every operator of :data:`CASES` from it.  Every
answer is held byte for byte to a numpy oracle over the generated
relation (``tests/tpch_reference.py``), and every device dispatch to
the tier the setting prescribes:

* ``fused``: ``use_pallas`` at the default VMEM budget — ``fused`` where
  the plan wants existence bits, ``pallas_digits`` where it does not;
* ``streamed``: ``use_pallas`` with ``REPRO_VMEM_BUDGET`` below the
  model's resident bytes but above any two heads, so plans over every
  head take ``fused_streamed`` and plans over one or two heads stay
  resident;
* ``jit``: ``use_pallas=False`` — ``jit_keys``.

The store is built under the setting it is served with, so ``T_aux``
corrects the tier that answers.  Training keeps each configuration's own
epochs: at benchtiny's two, the model answers 0.1% of the cut
customer_demographics and ``T_aux`` nearly all of it, so no tier's codes
would reach an answer; at the configuration's 50 it answers about a
sixth.  lineitem's columns are random per row, and its model answers
none at any count of epochs, as on the chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
from typing import Callable, Dict, List, Tuple

import numpy as np
import pytest
from tpch_reference import (
    assert_aggregate_equal,
    ref_group_aggregate,
    ref_join,
    ref_lookup,
    ref_select,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import harness  # noqa: E402
from repro.api.plan import PREDICATE_OPS  # noqa: E402
from repro.core.encoding import KeyEncoder  # noqa: E402
from repro.core.model import MLPSpec  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import LookupServer  # noqa: E402

benchtiny = harness.load_module(os.path.join(REPO, "tests", "bench", "benchtiny.py"))

SETTINGS = ("fused", "streamed", "jit")
RELATIONS = ("tpcds_customer_demographics", "tpch_lineitem_sf1")
SEED = 0
#: Tier every dispatch of a setting may take, by whether the plan's heads
#: fit the engine's VMEM budget and whether it wants existence bits.
RESIDENT = {True: "fused", False: "pallas_digits"}


@dataclasses.dataclass(frozen=True)
class Shape:
    """Per-relation parameters of the operators: a numeric column with
    a pivot and an ``in`` set, a Q12-shaped selection, the group-by
    columns, the summed column and the join's key map."""

    column: str
    pivot: object
    members: tuple
    q12: Tuple[Tuple[str, str, object], ...]
    q12_projection: Tuple[str, ...]
    group: Tuple[str, ...]
    summed: str
    join_key: Callable[[np.ndarray], np.ndarray]
    join_column: str


SHAPES = {
    "tpcds_customer_demographics": Shape(
        column="cd_purchase_estimate", pivot=5000, members=(500, 5000, 9500),
        q12=(("cd_marital_status", "in", ("M", "S")),
             ("cd_purchase_estimate", ">=", 3000),
             ("cd_purchase_estimate", "<", 8000)),
        q12_projection=("cd_marital_status", "cd_purchase_estimate",
                        "cd_gender", "cd_credit_rating"),
        group=("cd_gender", "cd_marital_status"), summed="cd_purchase_estimate",
        join_key=lambda k: k * 2, join_column="cd_education_status",
    ),
    # TPC-H Q12's lineitem selection and projection, Q1's group-by, and
    # each line joined to the order's next line
    "tpch_lineitem_sf1": Shape(
        column="l_quantity", pivot=25, members=(1, 25, 50),
        q12=(("l_shipmode", "in", ("MAIL", "SHIP")),
             ("l_receiptdate", ">=", "1994-01-01"),
             ("l_receiptdate", "<", "1995-01-01")),
        q12_projection=("l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"),
        group=("l_returnflag", "l_linestatus"), summed="l_quantity",
        join_key=lambda k: k + 1, join_column="l_shipmode",
    ),
}


@dataclasses.dataclass
class Relation:
    """The oracle's copy of a relation: sorted keys and each column's
    values, decoded."""

    keys: np.ndarray
    columns: Dict[str, np.ndarray]

    def gap_keys(self, n: int) -> np.ndarray:
        """``n`` absent keys from the key gaps where the relation has
        them (lineitem's sparse orderkeys), else past its end (the dense
        ``cd_demo_sk``)."""
        lo, hi = int(self.keys[0]), int(self.keys[-1])
        gaps = np.setdiff1d(np.arange(lo, hi), self.keys)
        pool = gaps if gaps.size >= n else np.arange(hi + 1, hi + 1 + n)
        return np.random.default_rng(SEED).choice(pool, n, replace=False).astype(np.int64)

    def absent_keys(self, n: int) -> np.ndarray:
        """``n`` gap keys plus keys outside the int32 domain."""
        edge = np.array([-7, -1, 0, 2**31 - 1, 2**31, 2**40], dtype=np.int64)
        return np.concatenate([self.gap_keys(n), edge[~np.isin(edge, self.keys)]])

    def sample(self, n: int) -> np.ndarray:
        rng = np.random.default_rng(SEED + 1)
        return rng.choice(self.keys, n, replace=False)

    def rows(self, keys: np.ndarray) -> Dict[str, np.ndarray]:
        pos = np.searchsorted(self.keys, keys)
        return {c: v[pos] for c, v in self.columns.items()}


@dataclasses.dataclass
class Served:
    """One relation built and served under one setting."""

    name: str
    setting: str
    cfg: dict
    path: str
    store: object
    rel: Relation
    shape: Shape

    @contextlib.contextmanager
    def budget(self):
        """The setting's ``REPRO_VMEM_BUDGET``, for as long as an engine
        is made (each engine reads it once, at construction)."""
        with pytest.MonkeyPatch.context() as mp:
            if self.setting == "streamed":
                mp.setenv("REPRO_VMEM_BUDGET", str(streamed_budget(self.cfg, self.rel)))
            else:
                mp.delenv("REPRO_VMEM_BUDGET", raising=False)
            yield

    def fresh(self):
        """Another copy of the store, read back from its saved files,
        for the operators that write."""
        import repro
        from repro.storage import MemoryPool

        with self.budget():
            store = repro.open(
                self.path, pool=MemoryPool(int(self.cfg["store"]["aux_pool_bytes"])))
            store.engine  # noqa: B018 — made here, under the setting's budget
        return store


def model_spec(cfg: dict, rel: Relation) -> MLPSpec:
    """The spec ``repro.build`` gives the relation under ``cfg``."""
    st = cfg["store"]
    return MLPSpec(
        base=st["base"],
        width=KeyEncoder(int(rel.keys.max()), base=st["base"]).width,
        shared=tuple(st["shared"]),
        private={c: tuple(st["private"]) for c in rel.columns},
        out_cards={c: int(np.unique(v).size) for c, v in rel.columns.items()},
        dtype=st["dtype"],
    )


def streamed_budget(cfg: dict, rel: Relation) -> int:
    """A VMEM budget that holds the trunk and the two widest heads, and
    so not the whole model: one or two heads stay resident, more stream."""
    spec = model_spec(cfg, rel)
    trunk, heads = kops.resident_parts(spec)
    widest = sorted(heads.values())[-2:]
    return trunk + sum(widest) + kops.activation_bytes(spec, kops.DEFAULT_TILE_N)


def serve(name: str, setting: str, tmp: str) -> Served:
    """Build ``name`` under ``setting`` as the benchmark does and return
    it with its oracle."""
    root = benchtiny.make_root(os.path.join(tmp, "root"))
    with open(os.path.join(root, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        cfg["train"]["epochs"] = json.load(f)["train"]["epochs"]
    cfg["store"]["use_pallas"] = setting != "jit"
    gen = harness.load_module(os.path.join(root, "bench", "configs", cfg["generator"]))
    keys, columns = gen.generate(cfg, SEED)
    order = np.argsort(keys)
    rel = Relation(keys[order], {c: dom[idx][order] for c, (dom, idx) in columns.items()})
    served = Served(name, setting, cfg, os.path.join(tmp, "store"), None, rel, SHAPES[name])
    with served.budget():
        served.store, _ = harness.build_store(cfg, keys, columns, served.path)
        eng = served.store.engine
    if eng.use_pallas != (setting != "jit"):
        raise AssertionError(f"{setting}: engine use_pallas={eng.use_pallas}")
    if served.store.spec != model_spec(cfg, rel):
        raise AssertionError(f"{name}: the store's spec is not the one the budget assumed")
    return served


def expected_tier(setting: str, eng, tasks: Tuple[str, ...], want_exists: bool) -> str:
    """The tier ``setting`` prescribes for a dispatch over ``tasks``."""
    if setting == "jit":
        return "jit_keys"
    if eng.kernel_filter_capable(tasks):
        return RESIDENT[want_exists]
    return "fused_streamed"


@contextlib.contextmanager
def served_tiers(setting: str, store):
    """Record ``(tier taken, tier prescribed)`` for every device dispatch
    made through ``store``'s engine."""
    eng = store.engine
    dispatch = eng.dispatch
    tiers: List[Tuple[str, str]] = []

    def spy(keys, tasks=None, want_exists=False, pred_tables=None, aux=None):
        ticket = dispatch(keys, tasks, want_exists, pred_tables, aux)
        if ticket.path != "empty":
            tiers.append((ticket.path,
                          expected_tier(setting, eng, ticket.task_order, want_exists)))
        return ticket

    eng.dispatch = spy
    try:
        yield tiers
    finally:
        del eng.dispatch


# ----------------------------------------------------------------- checks
def assert_rows(keys, values, exists, rel: Relation, projection=None):
    """A lookup answer against the oracle: every existence bit, and every
    projected value of every present key, of the same kind and equal."""
    want_values, want_exists = ref_lookup(rel.keys, rel.columns, keys, projection)
    np.testing.assert_array_equal(np.asarray(exists, dtype=bool), want_exists)
    assert set(values) == set(want_values)
    for c, want in want_values.items():
        got = np.asarray(values[c])[want_exists]
        assert got.dtype.kind == want.dtype.kind, (c, got.dtype, want.dtype)
        np.testing.assert_array_equal(got, want[want_exists], err_msg=c)


def assert_selection(res, rel: Relation, lo, hi, predicates, projection=None):
    projection = tuple(rel.columns) if projection is None else projection
    want_keys, want_values = ref_select(rel.keys, rel.columns, lo, hi, predicates, projection)
    np.testing.assert_array_equal(res.keys, want_keys)
    assert set(res.values) == set(projection)
    for c in projection:
        got = np.asarray(res.values[c])
        assert got.dtype.kind == want_values[c].dtype.kind, (c, got.dtype)
        np.testing.assert_array_equal(got, want_values[c], err_msg=c)


# ------------------------------------------------------------- operators
def op_point_present(s: Served, store):
    keys = s.rel.sample(512)
    values, exists = store.lookup(keys)
    assert_rows(keys, values, exists, s.rel)


def op_point_absent(s: Served, store):
    keys = s.rel.absent_keys(256)
    res = store.query().where_keys(keys).execute()
    assert not res.exists.any()
    assert_rows(keys, res.values, res.exists, s.rel)


def _mixed(s: Served) -> np.ndarray:
    keys = np.concatenate([s.rel.sample(384), s.rel.absent_keys(128)])
    return np.random.default_rng(SEED + 2).permutation(keys)


def op_project_one(s: Served, store):
    keys = _mixed(s)
    res = store.query().select(s.shape.column).where_keys(keys).execute()
    assert_rows(keys, res.values, res.exists, s.rel, (s.shape.column,))


def op_every_column(s: Served, store):
    keys = _mixed(s)
    cols = tuple(reversed(store.columns))
    res = store.query().select(*cols).where_keys(keys).execute()
    np.testing.assert_array_equal(res.keys, keys)
    assert_rows(keys, res.values, res.exists, s.rel, cols)


def op_lookup_many(s: Served, store):
    present = s.rel.sample(256)
    requests = [present[:128], np.concatenate([present[64:], present[:32]]),
                np.concatenate([present[:16], present[:16], s.rel.absent_keys(16)])]
    out = LookupServer(store, max_batch=256).lookup_many(requests)
    assert len(out) == len(requests)
    for keys, (values, exists) in zip(requests, out):
        assert_rows(keys, values, exists, s.rel)


def _range(s: Served) -> Tuple[int, int]:
    n = s.rel.keys.size
    return int(s.rel.keys[n // 4]), int(s.rel.keys[n // 4 + 700])


def op_range(s: Served, store):
    lo, hi = _range(s)
    assert_selection(store.query().where_range(lo, hi).execute(), s.rel, lo, hi, ())


def op_range_empty(s: Served, store):
    lo = int(s.rel.keys[-1]) + 1
    res = store.query().where_range(lo, lo + 1000).execute()
    assert res.keys.size == 0
    assert_selection(res, s.rel, lo, lo + 1000, ())


def _everything(s: Served) -> Tuple[int, int]:
    return int(s.rel.keys[0]), int(s.rel.keys[-1]) + 1


def op_scan(s: Served, store):
    assert_selection(store.query().scan().execute(), s.rel, *_everything(s), ())


def _op_where(op: str):
    def run(s: Served, store):
        value = s.shape.members if op == "in" else s.shape.pivot
        res = store.query().scan().where(s.shape.column, op, value).execute()
        assert res.keys.size
        assert_selection(res, s.rel, *_everything(s), ((s.shape.column, op, value),))
    return run


def op_q12(s: Served, store):
    n = s.rel.keys.size
    lo, hi = int(s.rel.keys[n // 8]), int(s.rel.keys[n - n // 8])
    q = store.query().select(*s.shape.q12_projection).where_range(lo, hi)
    for column, op, value in s.shape.q12:
        q = q.where(column, op, value)
    res = q.execute()
    assert res.keys.size
    assert_selection(res, s.rel, lo, hi, s.shape.q12, s.shape.q12_projection)


def op_group_count(s: Served, store):
    res = store.query().group_by(*s.shape.group).agg("count").scan().execute()
    assert_aggregate_equal(res, *ref_group_aggregate(s.rel.columns, s.shape.group,
                                                     (("count", None),)))


def op_group_sum(s: Served, store):
    group, summed = s.shape.group[:1], s.shape.summed
    res = store.query().group_by(*group).agg(("sum", summed)).scan().execute()
    assert_aggregate_equal(res, *ref_group_aggregate(s.rel.columns, group,
                                                     (("sum", summed),)))


def op_join(s: Served, store):
    col = s.shape.join_column
    res = (store.query().join(store, key=s.shape.join_key, columns=(col,))
           .scan().execute())
    want_keys, want_right = ref_join(s.rel.keys, s.shape.join_key, s.rel.keys,
                                     {col: s.rel.columns[col]})
    assert 0 < want_keys.size < s.rel.keys.size
    np.testing.assert_array_equal(res.keys, want_keys)
    np.testing.assert_array_equal(np.asarray(res.values["r." + col]), want_right[col])
    want_left = s.rel.rows(want_keys)
    for c, want in want_left.items():
        np.testing.assert_array_equal(np.asarray(res.values[c]), want, err_msg=c)


def _copied_values(s: Served, n: int, seed: int) -> Dict[str, np.ndarray]:
    """Values of ``n`` existing rows, drawn from the relation."""
    pos = np.random.default_rng(seed).integers(0, s.rel.keys.size, n)
    return {c: v[pos] for c, v in s.rel.columns.items()}


def _after(s: Served, store, rel: Relation, touched: np.ndarray):
    keys = np.concatenate([touched, s.rel.sample(128)])
    values, exists = store.lookup(keys)
    assert_rows(keys, values, exists, rel)


def op_insert(s: Served, store):
    new = s.rel.gap_keys(48)
    vals = _copied_values(s, new.size, SEED + 3)
    store.insert(new, vals)
    keys = np.concatenate([s.rel.keys, new])
    order = np.argsort(keys)
    rel = Relation(keys[order], {c: np.concatenate([v, vals[c]])[order]
                                 for c, v in s.rel.columns.items()})
    _after(s, store, rel, new)


def op_delete(s: Served, store):
    gone = s.rel.sample(64)
    store.delete(gone)
    keep = ~np.isin(s.rel.keys, gone)
    rel = Relation(s.rel.keys[keep], {c: v[keep] for c, v in s.rel.columns.items()})
    _after(s, store, rel, gone)


def op_update(s: Served, store):
    keys = s.rel.sample(64)
    vals = _copied_values(s, keys.size, SEED + 4)
    store.update(keys, vals)
    pos = np.searchsorted(s.rel.keys, keys)
    cols = {}
    for c, v in s.rel.columns.items():
        cols[c] = v.copy()
        cols[c][pos] = vals[c]
    _after(s, store, Relation(s.rel.keys, cols), keys)


@dataclasses.dataclass(frozen=True)
class Case:
    """One operator: ``heads`` is ``"many"`` where the plan evaluates
    more heads than the streamed budget holds, ``"few"`` where it
    evaluates one or two, ``"none"`` where it runs no inference;
    ``writes`` serves it from a fresh copy."""

    name: str
    run: Callable
    heads: str
    writes: bool = False


CASES = (
    Case("point_present", op_point_present, "many"),
    Case("point_absent", op_point_absent, "many"),
    Case("project_one", op_project_one, "few"),
    Case("every_column", op_every_column, "many"),
    Case("lookup_many", op_lookup_many, "many"),
    Case("where_range", op_range, "many"),
    Case("where_range_empty", op_range_empty, "none"),
    Case("scan", op_scan, "many"),
    *(Case(f"scan_where[{op}]", _op_where(op), "many") for op in PREDICATE_OPS),
    Case("q12_range_predicates", op_q12, "many"),
    Case("group_by_count", op_group_count, "few"),
    Case("group_by_sum", op_group_sum, "few"),
    Case("join", op_join, "many"),
    Case("insert", op_insert, "many", writes=True),
    Case("delete", op_delete, "many", writes=True),
    Case("update", op_update, "many", writes=True),
)
CASE_IDS = [c.name for c in CASES]


def run_case(s: Served, case: Case) -> None:
    """Serve ``case`` and hold both its answer and its tiers."""
    store = s.fresh() if case.writes else s.store
    with served_tiers(s.setting, store) as tiers:
        case.run(s, store)
    assert all(took == want for took, want in tiers), tiers
    if case.heads == "none":
        assert not tiers, tiers
        return
    assert tiers, f"{case.name} ran no inference"
    streamed = any(took == "fused_streamed" for took, _ in tiers)
    assert streamed == (s.setting == "streamed" and case.heads == "many"), tiers
