"""The plain reference: the relation as sorted numpy arrays.

It is built from the configuration's own generator and imports nothing
of the store under test.  Every answer the timed path returned is held
to it: the existence bit of every key, every column of every present
key, and the per-group counts of a group-by.  Each comparison returns a
count of wrong answers; a sound store reads 0 on all of them.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

Column = Tuple[np.ndarray, np.ndarray]  # (domain, index): value = domain[index]


class Reference:
    """Sorted keys plus each column as ``(domain, index)``."""

    def __init__(self, keys: np.ndarray, columns: Dict[str, Column]):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys, dtype=np.int64)[order]
        self.columns = {c: (dom, idx[order]) for c, (dom, idx) in columns.items()}

    @property
    def num_rows(self) -> int:
        return int(self.keys.size)

    def values(self, name: str, pos: np.ndarray) -> np.ndarray:
        dom, idx = self.columns[name]
        return dom[idx[pos]]

    def lookup(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``(position, present)`` of each key."""
        keys = np.asarray(keys, dtype=np.int64)
        pos = np.minimum(np.searchsorted(self.keys, keys), self.keys.size - 1)
        return pos, self.keys[pos] == keys

    def group_counts(self, name: str) -> Dict[object, int]:
        dom, idx = self.columns[name]
        counts = np.bincount(idx, minlength=dom.size)
        return {dom[i].item(): int(c) for i, c in enumerate(counts) if c}

    def gap_keys(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """``n`` keys drawn uniformly from the gaps inside the stored key
        range (with replacement)."""
        lo, hi = int(self.keys[0]), int(self.keys[-1])
        if hi - lo + 1 == self.keys.size:
            raise ValueError("the stored key range has no gaps")
        out = np.empty(0, dtype=np.int64)
        while out.size < n:
            cand = rng.integers(lo, hi, size=2 * n, dtype=np.int64)
            out = np.concatenate([out, cand[~self.lookup(cand)[1]]])
        return out[:n]


def wrong_answers(ref: Reference, keys: np.ndarray, values: Dict[str, np.ndarray],
                  exists: np.ndarray) -> Tuple[int, int]:
    """``(wrong existence bits, wrong column values of present keys)`` of
    one lookup answer.  A missing column counts every present row wrong;
    a value of another kind (a number for a string) counts as wrong."""
    pos, present = ref.lookup(keys)
    wrong_exists = int(np.count_nonzero(np.asarray(exists, dtype=bool) != present))
    hit = np.flatnonzero(present)
    wrong_cells = 0
    for name in ref.columns:
        if name not in values:
            wrong_cells += hit.size
            continue
        got = np.asarray(values[name])[hit]
        want = ref.values(name, pos[hit])
        if got.dtype.kind != want.dtype.kind:
            wrong_cells += hit.size
        else:
            wrong_cells += int(np.count_nonzero(got != want))
    return wrong_exists, wrong_cells


def wrong_groups(ref: Reference, name: str, groups: Sequence, counts: Sequence) -> int:
    """Groups whose count differs from the relation's, counting a group
    that is missing or extra as one wrong group each."""
    want = ref.group_counts(name)
    got: Dict[object, int] = {}
    for g, c in zip(np.asarray(groups).tolist(), np.asarray(counts).tolist()):
        got[g] = got.get(g, 0) + int(c)
    return sum(got.get(g) != want.get(g) for g in set(want) | set(got))
