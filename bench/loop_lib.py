"""What every load loop shares.

A traffic mix is a data file, ``bench/traffic/<mix>.json``.  Its
``loop`` names a loop, ``bench/loops/<loop>.py``, found by name like
every other piece of the benchmark, and the rest of the file is that
loop's parameters.  A loop module defines ``Loop(params, store, ref,
config, seed)`` with ``warm_up(seed)``, ``run(seconds) -> Window`` and
the static ``compare(ref, kept)``.  The window carries the end-to-end
values the loop yields, by metric name, so the harness only collects
them.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np


def annotate(name: str):
    """A span of the benchmark's own in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """What one measured window did."""

    elapsed_s: float                   # first request issued .. last answer back
    attempted: int                     # requests (probes) or queries (scans)
    work: int                          # keys answered or rows aggregated
    values: Dict[str, float]           # end-to-end metrics the loop yields, by name
    kept: list                         # answers kept for the comparison
    spans: Dict[str, float]            # the program's own stage seconds, by "<layer>.<name>"
    #: keys handed to the model, by the heads that were asked for them
    dispatched: List[Tuple[Tuple[str, ...], int]]


class KeyDraw:
    """Stored keys drawn from ``rng``: ``uniform`` over the rows, or
    ``zipf`` (YCSB's scrambled Zipfian: rank ``r`` with weight
    ``r ** -zipf_theta``, ranks laid on rows by a permutation drawn from
    the seed)."""

    def __init__(self, ref, params: dict, seed: int):
        self.ref = ref
        self.dist = params.get("key_dist", "uniform")
        if self.dist == "zipf":
            theta = float(params["zipf_theta"])
            weights = np.arange(1, ref.num_rows + 1, dtype=np.float64) ** -theta
            self.cdf = np.cumsum(weights)
            self.cdf /= self.cdf[-1]
            self.rows = np.random.default_rng([seed, 5]).permutation(ref.num_rows)
        elif self.dist != "uniform":
            raise ValueError(f"unknown key_dist {self.dist!r}; known: uniform, zipf")

    def __call__(self, rng: np.random.Generator, shape) -> np.ndarray:
        if self.dist == "uniform":
            return self.ref.keys[rng.integers(0, self.ref.num_rows, shape)]
        ranks = np.searchsorted(self.cdf, rng.random(shape), side="right")
        return self.ref.keys[self.rows[np.minimum(ranks, self.ref.num_rows - 1)]]


def nearest_rank(values, q: float) -> float:
    ordered = sorted(values)
    return ordered[max(0, int(np.ceil(q * len(ordered))) - 1)]
