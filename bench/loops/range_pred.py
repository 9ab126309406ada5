"""``range_pred``: one closed-loop caller running TPC-H Q12's lineitem
selection (specification v3.0.1, section 2.4.12) over primary-key-range
splits, one plan at a time, back to back:

    store.query().select(*projection).where_range(lo, hi)
        .where(mode_column, "in", [m1, m2])
        .where(date_column, ">=", d).where(date_column, "<", d_next)
        .execute()

Each split starts at a row position drawn uniformly from the seed: ``lo``
is the key there and ``hi`` the key ``split_rows`` rows on (half-open),
so every query has exactly ``split_rows`` present rows.  The split is
capped at half the relation's rows, so a relation cut to a few thousand
rows can run it.  Each query draws fresh substitution parameters as
section 2.4.12.3 says: two different values of the mode column out of
its domain, and January 1 of a year in ``years`` (``d_next`` is a year
later).  The window closes at the first query that finishes after the
time is up, counted in full.  A share ``check_share`` of the queries,
drawn from the seed, and always the last, keeps its answer for the
comparison after the window: the returned keys exactly (none missing,
none extra, ascending) and every projected value of every returned row.

Yields ``scan_rows_per_s``: ``split_rows`` times the queries run, over
all the time of the queries, the key source included.
"""

from __future__ import annotations

import sys
import time
from typing import Dict

import numpy as np

from bench import loop_lib

STAGES = ("route_s", "infer_s", "exist_s", "aux_s", "filter_s", "decode_s")
COUNTS = ("rows_matched", "rows_decoded", "filter_host_rows")


class Loop:
    def __init__(self, params, store, ref, config, seed):
        self.store, self.ref = store, ref
        self.split = min(int(params["split_rows"]), ref.num_rows // 2)
        self.projection = tuple(params["projection"])
        self.mode_col, self.date_col = params["mode_column"], params["date_column"]
        self.heads = tuple(sorted(set(self.projection) | {self.mode_col, self.date_col}))
        self.modes = np.asarray(ref.columns[self.mode_col][0])
        first, last = params["years"]
        self.years = np.arange(int(first), int(last) + 1)
        self.check_share = float(params["check_share"])
        self.rng = np.random.default_rng([seed, 1])
        self.check_rng = np.random.default_rng([seed, 2])

    def draw(self, rng) -> dict:
        """One query's split and substitution parameters."""
        keys, n = self.ref.keys, self.ref.num_rows
        p = int(rng.integers(0, n - self.split + 1))
        end = p + self.split
        year = int(rng.choice(self.years))
        modes = rng.choice(self.modes.size, 2, replace=False)
        return {"lo": int(keys[p]), "hi": int(keys[end]) if end < n else int(keys[-1]) + 1,
                "mode_column": self.mode_col, "modes": [self.modes[i].item() for i in modes],
                "date_column": self.date_col, "date": f"{year}-01-01",
                "date_next": f"{year + 1}-01-01"}

    def _query(self, q, morsel=None):
        query = (self.store.query().select(*self.projection).where_range(q["lo"], q["hi"])
                 .where(q["mode_column"], "in", q["modes"])
                 .where(q["date_column"], ">=", q["date"])
                 .where(q["date_column"], "<", q["date_next"]))
        if morsel is not None:
            query = query.morsel(morsel)
        with loop_lib.annotate("execute"):
            return query.execute()

    def warm_up(self, seed) -> None:
        """Queries on adaptive morsels, then one at each fixed power-of-two
        morsel from the engine's tile to its largest bucket: the executor
        sizes morsels from host timings, so a split's morsels and their
        tails can fall in any bucket of this head set and these
        predicate tables."""
        rng = np.random.default_rng([seed, 3])
        engine = self.store.engine
        with loop_lib.annotate("warmup"):
            for _ in range(3):
                self._query(self.draw(rng))
            bucket = engine.tile_n
            while bucket <= engine.max_bucket:
                self._query(self.draw(rng), morsel=bucket)
                bucket *= 2

    def run(self, seconds: float) -> loop_lib.Window:
        kept, dispatched = [], []
        spans: Dict[str, float] = {}
        t0 = time.perf_counter()
        deadline = t0 + seconds
        t_done = t0
        i = 0
        while t_done < deadline:
            q = self.draw(self.rng)
            res = self._query(q)
            t_done = time.perf_counter()
            answer = (q, res.keys, {c: res.values.get(c) for c in self.projection})
            if self.check_rng.random() < self.check_share:
                kept.append(answer)
            dispatched.append((self.heads, self.split))
            ex = res.explain
            for k in STAGES:
                spans["scan." + k] = spans.get("scan." + k, 0.0) + getattr(ex, k)
            for k in COUNTS:
                if hasattr(ex, k):  # a program without the count reports none
                    spans["scan." + k] = spans.get("scan." + k, 0) + getattr(ex, k)
            i += 1
        if not kept or kept[-1] is not answer:
            kept.append(answer)
        print("plan " + " ".join(res.explain.plan), file=sys.stderr, flush=True)
        elapsed = t_done - t0
        work = i * self.split
        return loop_lib.Window(elapsed, i, work, {"scan_rows_per_s": work / elapsed}, kept,
                               spans, dispatched)

    @staticmethod
    def compare(ref, kept) -> Dict[str, int]:
        missing = extra = order = values = failed = 0
        for q, keys, got in kept:
            want = select(ref, q)
            keys = np.asarray(keys, dtype=np.int64)
            pos = np.minimum(np.searchsorted(ref.keys, keys), ref.num_rows - 1)
            hit = (ref.keys[pos] == keys) & np.isin(pos, want)
            right = np.unique(pos[hit]).size
            miss, ext = int(want.size - right), int(keys.size - right)
            out_of_order = int(np.count_nonzero(keys[1:] <= keys[:-1]))
            wrong = 0
            for name, column in got.items():
                if column is None or len(column) != keys.size:
                    wrong += int(np.count_nonzero(hit))
                    continue
                column = np.asarray(column)[hit]
                expect = ref.values(name, pos[hit])
                if column.dtype.kind != expect.dtype.kind:
                    wrong += column.size
                else:
                    wrong += int(np.count_nonzero(column != expect))
            missing, extra, order, values = (missing + miss, extra + ext,
                                             order + out_of_order, values + wrong)
            failed += bool(miss or ext or out_of_order or wrong)
        return {"wrong_missing_rows": missing, "wrong_extra_rows": extra,
                "wrong_order_rows": order, "wrong_values": values,
                "checked_queries": len(kept), "failed": failed}


def select(ref, q) -> np.ndarray:
    """Positions in ``ref`` of the rows the query selects, ascending:
    keys in ``[lo, hi)`` whose mode is one of the two and whose date
    lies in the year, evaluated on the reference's own values."""
    a, b = np.searchsorted(ref.keys, [q["lo"], q["hi"]])
    pos = np.arange(a, b)
    mode = ref.values(q["mode_column"], pos)
    date = ref.values(q["date_column"], pos)
    keep = np.isin(mode, np.asarray(q["modes"])) & (date >= q["date"]) & (date < q["date_next"])
    return pos[keep]
