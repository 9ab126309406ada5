"""``groupby_scan``: a closed loop with one caller running
``store.query().group_by(col).agg("count").scan().execute()`` back to
back over the whole relation, ``col`` rotating through the
configuration's ``groupby_columns`` from an offset drawn from the seed.
This is the code-space aggregation: no row is decoded.  The window
closes at the end of the rotation in flight when the time is up, its
queries finished and counted in full, their time included: every seed
then runs each column equally often, in another order, since columns
differ in cost.  Every query's counts are compared with the reference.

Yields ``scan_rows_per_s``: rows aggregated over all the time of the
queries run.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench import loop_lib
from bench import reference as ref_lib

STAGES = ("infer_s", "exist_s", "aux_s", "decode_s", "agg_s", "rows_decoded")


class Loop:
    def __init__(self, params, store, ref, config, seed):
        self.params, self.store, self.ref = params, store, ref
        self.columns = list(config["groupby_columns"])
        self.offset = int(np.random.default_rng([seed, 1]).integers(len(self.columns)))

    def _query(self, column):
        with loop_lib.annotate("execute"):
            return self.store.query().group_by(column).agg("count").scan().execute()

    def warm_up(self, seed) -> None:
        """One query per column, then every power-of-two engine bucket of
        each column's head: the executor sizes morsels from host timings,
        so the tail chunk of a scan can fall in any bucket."""
        engine = self.store.engine
        for column in self.columns:
            with loop_lib.annotate("warmup"):
                self._query(column)
                bucket = engine.tile_n
                while bucket <= engine.max_bucket:
                    keys = np.resize(self.ref.keys, bucket)
                    engine.collect(engine.dispatch(keys, (column,), want_exists=True))
                    bucket *= 2

    def run(self, seconds: float) -> loop_lib.Window:
        kept, dispatched = [], []
        spans: Dict[str, float] = {}
        t0 = time.perf_counter()
        deadline = t0 + seconds
        t_done = t0
        i = 0
        while t_done < deadline or i % len(self.columns):
            column = self.columns[(self.offset + i) % len(self.columns)]
            res = self._query(column)
            t_done = time.perf_counter()
            kept.append((column, res.groups[column], res.aggregates["count"]))
            dispatched.append(((column,), self.ref.num_rows))
            for k in STAGES:
                spans["scan." + k] = spans.get("scan." + k, 0) + getattr(res.explain, k)
            i += 1
        elapsed = t_done - t0
        work = i * self.ref.num_rows
        return loop_lib.Window(elapsed, i, work, {"scan_rows_per_s": work / elapsed}, kept,
                               spans, dispatched)

    @staticmethod
    def compare(ref, kept) -> Dict[str, int]:
        wrong = failed = 0
        for column, groups, counts in kept:
            w = ref_lib.wrong_groups(ref, column, groups, counts)
            wrong += w
            failed += bool(w)
        return {"wrong_groups": wrong, "checked_queries": len(kept), "failed": failed}
