"""Bounded LRU memory pool for decompressed partitions.

Models the paper's memory-constrained regime (§IV-B2): "we free up the
space of the least recently used (LRU) partition before loading the
subsequent partition ... when the memory becomes insufficient".  Every
store (DeepMapping aux table, AB/ABC/HB/HBC baselines) charges its
decompressed partitions against a shared pool so latency comparisons
see identical eviction pressure.
"""

from __future__ import annotations

import collections
import threading
from typing import Callable, Hashable, Tuple


class MemoryPool:
    """LRU cache of opaque objects with a byte budget.

    ``get(key, loader)`` returns the cached object or calls ``loader()``
    -> ``(obj, nbytes)`` and caches it, evicting least-recently-used
    entries until the budget holds.  Objects larger than the budget are
    returned uncached (pure streaming read — matches loading a partition,
    using it, and dropping it).
    """

    def __init__(self, budget_bytes: int):
        if budget_bytes <= 0:
            raise ValueError("budget must be positive")
        self.budget_bytes = int(budget_bytes)
        self._entries: "collections.OrderedDict[Hashable, Tuple[object, int]]" = (
            collections.OrderedDict()
        )
        self._used = 0
        self._lock = threading.Lock()
        # Statistics used by the latency-breakdown benchmark (paper Fig. 7).
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def used_bytes(self) -> int:
        return self._used

    def get(self, key: Hashable, loader: Callable[[], Tuple[object, int]]):
        obj = self.lookup(key)
        if obj is None:
            obj = self.put(key, *loader())
        return obj

    def lookup(self, key: Hashable):
        """The cached object under ``key`` (a hit, now most recently
        used), or None (a miss); never loads."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry[0]

    def put(self, key: Hashable, obj, nbytes: int):
        """Cache ``obj`` under ``key``, charged ``nbytes``, evicting
        least-recently-used entries until the budget holds, and return
        it.  Where another caller cached ``key`` meanwhile, that object
        is kept and returned; one over the whole budget is returned
        uncached."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                return entry[0]
            if nbytes > self.budget_bytes:
                return obj  # uncacheable: stream through
            while self._used + nbytes > self.budget_bytes and self._entries:
                _, (_, evicted) = self._entries.popitem(last=False)
                self._used -= evicted
                self.evictions += 1
            self._entries[key] = (obj, nbytes)
            self._used += nbytes
            return obj

    def peek(self, key: Hashable):
        """The cached object under ``key`` (a hit, now most recently
        used), or None; never loads and counts no miss."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry[0]

    def admit(
        self,
        key: Hashable,
        nbytes: int,
        build: Callable[[], object],
        releasable: Callable[[Hashable], bool],
    ):
        """Cache ``build()`` under ``key`` only where its ``nbytes`` fit
        the budget without evicting any entry but those ``releasable``
        marks, which are dropped to make room.  Returns the cached
        object, or None where it does not fit (checked before building,
        and again after, since ``build`` runs outside the lock; a build
        counts as one miss)."""
        with self._lock:
            if not self._room_for(nbytes, releasable):
                return None
            self.misses += 1
        obj = build()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:  # another thread admitted it meanwhile
                return entry[0]
            if not self._room_for(nbytes, releasable):
                return None
            for k in [k for k in self._entries if releasable(k)]:
                self._used -= self._entries.pop(k)[1]
            self._entries[key] = (obj, nbytes)
            self._used += nbytes
            return obj

    def _room_for(self, nbytes: int, releasable: Callable[[Hashable], bool]) -> bool:
        if nbytes > self.budget_bytes:
            return False
        freed = sum(n for k, (_, n) in self._entries.items() if releasable(k))
        return self._used - freed + nbytes <= self.budget_bytes

    def invalidate(self, key: Hashable) -> None:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._used -= entry[1]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._used = 0

    def reset_stats(self) -> None:
        self.hits = self.misses = self.evictions = 0
