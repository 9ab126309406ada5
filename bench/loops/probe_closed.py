"""``probe_closed``: a closed loop of point lookups.

``callers`` callers each wait for their answer before sending their
next request of ``request_keys`` keys, all columns; each step hands the
outstanding requests to one ``LookupServer.lookup_many`` call
(``max_batch``).  Keys are drawn from the stored keys by ``key_dist``
(``bench/loop_lib.KeyDraw``), and an exact share ``absent_share`` of
each request from the gaps inside the key range.  A share
``check_share`` of the steps, drawn from the seed, keeps its answers for
the comparison with the reference after the window; the last step is
always kept.

Yields ``lookup_keys_per_s`` (keys requested in the window over the
window) and ``lookup_p95_ms`` (nearest-rank 95th percentile over every
request, each timed from its issue until its answer is on the host).
Warm-up draws from a generator of its own, so the window's draws are
the same whatever the warm-up did.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from bench import loop_lib
from bench import reference as ref_lib


def unique_keys_dispatched() -> float:
    """Sum of the server's merged unique-key batch sizes so far."""
    from repro import obs

    hist = obs.registry().get("deepmap_serve_batch_keys")
    state = hist.state() if hist is not None else None
    return state.sum if state is not None else 0.0


class Loop:
    def __init__(self, params, store, ref, config, seed):
        from repro.serve import LookupServer

        self.params, self.ref = params, ref
        self.tasks = tuple(store.columns)
        self.server = LookupServer(store, max_batch=int(params["max_batch"]))
        self.draw = loop_lib.KeyDraw(ref, params, seed)
        self.rng = np.random.default_rng([seed, 1])
        self.check_rng = np.random.default_rng([seed, 2])
        gap_rng = np.random.default_rng([seed, 3])
        self.gaps = (ref.gap_keys(gap_rng, 1 << 18)
                     if float(params["absent_share"]) > 0 else np.empty(0, np.int64))

    def requests(self, rng) -> List[np.ndarray]:
        callers, size = int(self.params["callers"]), int(self.params["request_keys"])
        absent = int(round(size * float(self.params["absent_share"])))
        keys = self.draw(rng, (callers, size - absent))
        if absent:
            keys = np.concatenate(
                [keys, self.gaps[rng.integers(0, self.gaps.size, (callers, absent))]], axis=1)
        return list(keys)

    def warm_up(self, seed) -> None:
        rng = np.random.default_rng([seed, 4])
        for _ in range(int(self.params["warmup_steps"])):
            with loop_lib.annotate("warmup"):
                self.server.lookup_many(self.requests(rng))

    def run(self, seconds: float) -> loop_lib.Window:
        server, share = self.server, float(self.params["check_share"])
        before = dataclasses.asdict(server.stats)
        uniq_before = unique_keys_dispatched()
        latencies, kept = [], []
        work = 0
        last = None
        t0 = time.perf_counter()
        deadline = t0 + seconds
        t_done = t0
        while t_done < deadline:
            requests = self.requests(self.rng)
            t_issue = time.perf_counter()
            with loop_lib.annotate("lookup_many"):
                answers = server.lookup_many(requests)
            t_done = time.perf_counter()
            latencies.extend([t_done - t_issue] * len(requests))
            work += sum(r.size for r in requests)
            step = (requests, answers)
            if self.check_rng.random() < share:
                kept.append(step)
                step = None
            last = step
        if last is not None:
            kept.append(last)
        elapsed = t_done - t0
        after = dataclasses.asdict(server.stats)
        spans = {"serve." + k: after[k] - before[k] for k in after}
        values = {"lookup_keys_per_s": work / elapsed,
                  "lookup_p95_ms": 1e3 * loop_lib.nearest_rank(latencies, 0.95)}
        dispatched = [(self.tasks, int(unique_keys_dispatched() - uniq_before))]
        return loop_lib.Window(elapsed, len(latencies), work, values, kept, spans, dispatched)

    @staticmethod
    def compare(ref, kept) -> Dict[str, int]:
        wrong_exists = wrong_cells = failed = checked = 0
        for requests, answers in kept:
            for keys, (values, exists) in zip(requests, answers):
                e, c = ref_lib.wrong_answers(ref, keys, values, exists)
                wrong_exists += e
                wrong_cells += c
                failed += bool(e or c)
                checked += 1
        return {"wrong_exists": wrong_exists, "wrong_cells": wrong_cells,
                "checked_requests": checked, "failed": failed}
