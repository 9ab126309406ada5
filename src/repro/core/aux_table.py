"""Auxiliary accuracy-assurance table ``T_aux`` (paper §IV-B1).

Misclassified key-value pairs are sorted by key, range-partitioned, and
each partition is compressed (Z-Standard or LZMA).  We NEVER re-key
(paper's emphasis) — original key order is preserved.

A probe takes one of two paths, picked per call from what the shared
LRU :class:`~repro.storage.pool.MemoryPool` holds:

* **resident** — when the whole table, decompressed, fits in the pool
  without evicting another entry (this table's own partition entries
  may be released for it), every partition is decompressed once into
  one contiguous sorted view, ``keys (N,) int64`` and ``codes (N, m)
  int32``, held in the pool as one entry charged its real bytes.  The
  probe is then one ``searchsorted`` of the ordered queries over the
  view: no Python loop per key or per partition.
* **partitioned** — otherwise (a table over the pool, or a pool crowded
  by other tables), each probed key's partition is located by binary
  search over partition-boundary keys, decompressed through the pool
  (paper §IV-B2: LRU partitions are evicted when memory is
  insufficient), and binary-searched inside, once per partition a batch
  hits.  The partitions are taken in waves of at most half the pool's
  budget decompressed; a wave's pool misses are decompressed together,
  on a process-wide pool of worker threads (one a usable core) where
  there are two or more, so one call holds at most half the budget
  besides what the pool holds.

Both give the same answers and count partition visits alike.

Modifications (Algorithms 3–5) land in a sorted in-memory delta overlay
(inserts/updates) and a tombstone set (deletes of rows that live in
compacted partitions); ``compact()`` folds both back into partitions
and drops the old resident view.  The delta is charged to Eq. 1 at its
*compressed serialized* size, i.e. exactly what a flush would cost on
disk.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.storage import MemoryPool, get_codec

_EMPTY_I64 = np.zeros(0, dtype=np.int64)

# (pid, workers, executor): see _decompress_workers.
_WORKERS: Optional[Tuple[int, int, ThreadPoolExecutor]] = None
_WORKERS_LOCK = threading.Lock()


def _decompress_workers() -> Tuple[ThreadPoolExecutor, int]:
    """The process-wide worker threads that decompress partitioned
    probes' pool misses, one for each core this process may use, and
    their number.  Started on first use, and again in a forked child
    (which inherits no threads); every table and caller shares them."""
    global _WORKERS
    with _WORKERS_LOCK:
        if _WORKERS is None or _WORKERS[0] != os.getpid():
            try:
                n = len(os.sched_getaffinity(0))
            except AttributeError:  # a platform without affinity masks
                n = os.cpu_count() or 1
            _WORKERS = (os.getpid(), n,
                        ThreadPoolExecutor(n, thread_name_prefix="aux-decompress"))
        return _WORKERS[2], _WORKERS[1]


def _pack_partition(keys: np.ndarray, codes: np.ndarray) -> bytes:
    n, m = codes.shape
    header = np.array([n, m], dtype=np.int64).tobytes()
    return header + keys.astype(np.int64).tobytes() + codes.astype(np.int32).tobytes()


def _unpack_partition(blob: bytes) -> Tuple[np.ndarray, np.ndarray]:
    n, m = np.frombuffer(blob[:16], dtype=np.int64)
    n, m = int(n), int(m)
    keys = np.frombuffer(blob[16 : 16 + 8 * n], dtype=np.int64)
    codes = np.frombuffer(blob[16 + 8 * n :], dtype=np.int32).reshape(n, m)
    return keys, codes


def _decompress_run(codec, blobs: List[bytes]) -> List[bytes]:
    return [codec.decompress(b) for b in blobs]


def _answer(pkeys, pcodes, qk, idx, tomb, found, out) -> None:
    """Binary-search the sorted ``qk`` in the sorted ``pkeys``; mark each
    hit that no tombstone hides as found at ``idx`` and copy its codes."""
    pos = np.searchsorted(pkeys, qk)
    hit = pkeys[np.minimum(pos, pkeys.shape[0] - 1)] == qk
    if tomb is not None:
        hit &= ~np.isin(qk, tomb)
    sel = idx[hit]
    found[sel] = True
    out[sel] = pcodes[pos[hit]]


class AuxTable:
    """Sorted / partitioned / compressed misclassified-row store."""

    def __init__(
        self,
        num_values: int,
        codec: str = "zstd",
        partition_bytes: int = 128 * 1024,
        pool: Optional[MemoryPool] = None,
    ):
        self.num_values = int(num_values)
        self.codec_name = codec
        self._codec = get_codec(codec)
        self.partition_bytes = int(partition_bytes)
        self.pool = pool if pool is not None else MemoryPool(1 << 30)
        # Immutable compacted state.
        self._partitions: list[bytes] = []
        self._boundaries = _EMPTY_I64  # first key of each partition
        self._part_rows: list[int] = []
        self._compacted_rows = 0
        # Mutable overlay.
        self._delta: Dict[int, np.ndarray] = {}
        self._tombstones: set[int] = set()
        self._delta_size_cache: Optional[int] = None
        self._generation = 0  # pool-key namespace; bumped by compact()

    # -- construction --------------------------------------------------------
    @classmethod
    def build(
        cls,
        keys: np.ndarray,
        codes: np.ndarray,
        codec: str = "zstd",
        partition_bytes: int = 128 * 1024,
        pool: Optional[MemoryPool] = None,
    ) -> "AuxTable":
        keys = np.asarray(keys, dtype=np.int64)
        codes = np.asarray(codes, dtype=np.int32)
        if codes.ndim != 2 or codes.shape[0] != keys.shape[0]:
            raise ValueError("codes must be (n, m) aligned with keys")
        t = cls(codes.shape[1], codec, partition_bytes, pool)
        t._rebuild(keys, codes)
        return t

    def _rebuild(self, keys: np.ndarray, codes: np.ndarray) -> None:
        order = np.argsort(keys, kind="stable")
        keys, codes = keys[order], codes[order]
        row_bytes = 8 + 4 * self.num_values
        rows_per_part = max(1, self.partition_bytes // row_bytes)
        self._partitions, self._part_rows, bounds = [], [], []
        for start in range(0, keys.shape[0], rows_per_part):
            k = keys[start : start + rows_per_part]
            c = codes[start : start + rows_per_part]
            self._partitions.append(self._codec.compress(_pack_partition(k, c)))
            self._part_rows.append(int(k.shape[0]))
            bounds.append(int(k[0]))
        self._boundaries = np.asarray(bounds, dtype=np.int64)
        self._compacted_rows = int(keys.shape[0])
        self.pool.invalidate(self._view_key())
        self._generation += 1

    # -- partition access ------------------------------------------------------
    def _part_key(self, idx: int) -> tuple:
        return ("aux", id(self), self._generation, idx)

    def _probe_partitions(
        self, parts: List[int], starts: List[int], ends: List[int],
        rkeys: np.ndarray, idx: np.ndarray, tomb, found: np.ndarray, out: np.ndarray,
    ) -> Tuple[int, int]:
        """Answer the ordered keys ``starts[i]:ends[i]`` from partition
        ``parts[i]``, in waves of consecutive partitions whose decompressed
        bytes take at most half the pool's budget (one partition at the
        least).  A wave looks each partition up in the pool; its misses
        are decompressed under one ``aux.decompress`` span (args
        ``parts``, and ``workers``: the worker threads they were spread
        over, 0 where one miss was decompressed on this thread), cached
        in key order, and every partition of the wave is then searched
        here in order.  Returns the misses decompressed and those of them
        decompressed on the workers."""
        sizes = [self._part_rows[p] * (8 + 4 * self.num_values) for p in parts]
        half = self.pool.budget_bytes // 2
        decompressed = parallel = 0
        lo = 0
        while lo < len(parts):
            hi, held = lo + 1, sizes[lo]
            while hi < len(parts) and held + sizes[hi] <= half:
                held += sizes[hi]
                hi += 1
            loaded = [self.pool.lookup(self._part_key(p)) for p in parts[lo:hi]]
            misses = [w for w, part in enumerate(loaded) if part is None]
            if misses:
                todo = [parts[lo + w] for w in misses]
                workers = 0
                if len(todo) > 1:
                    executor, cores = _decompress_workers()
                    workers = min(len(todo), cores)
                with obs.span("aux.decompress", parts=len(todo), workers=workers):
                    blobs = [self._partitions[p] for p in todo]
                    if workers:
                        # One contiguous run of misses a worker: few tasks
                        # and GIL hand-offs.  The workers run only the
                        # codec, which drops the GIL; unpacking holds it,
                        # so it stays on this thread.
                        cuts = [len(todo) * i // workers for i in range(workers + 1)]
                        runs = [executor.submit(_decompress_run, self._codec, blobs[a:b])
                                for a, b in zip(cuts, cuts[1:])]
                        blobs = (blob for run in runs for blob in run.result())
                    else:
                        blobs = map(self._codec.decompress, blobs)
                    for w, p, blob in zip(misses, todo, blobs):
                        part = _unpack_partition(blob)
                        loaded[w] = self.pool.put(self._part_key(p), part,
                                                  part[0].nbytes + part[1].nbytes)
                decompressed += len(todo)
                parallel += len(todo) if workers else 0
            for part, s, e in zip(loaded, starts[lo:hi], ends[lo:hi]):
                _answer(*part, rkeys[s:e], idx[s:e], tomb, found, out)
            lo = hi
        return decompressed, parallel

    def _decompress_all(self) -> Tuple[np.ndarray, np.ndarray]:
        """Every partition decompressed once, in key order, into one
        contiguous ``(keys, codes)`` pair."""
        keys = np.empty(self._compacted_rows, dtype=np.int64)
        codes = np.empty((self._compacted_rows, self.num_values), dtype=np.int32)
        with obs.span("aux.decompress"):
            at = 0
            for blob, rows in zip(self._partitions, self._part_rows):
                k, c = _unpack_partition(self._codec.decompress(blob))
                keys[at : at + rows] = k
                codes[at : at + rows] = c
                at += rows
        return keys, codes

    def _view_key(self) -> tuple:
        return ("aux-flat", id(self), self._generation)

    def _resident_view(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The resident sorted view, built on first use where it fits the
        pool without evicting another entry; None where it does not."""
        key = self._view_key()
        view = self.pool.peek(key)
        if view is None:
            me = id(self)
            view = self.pool.admit(
                key,
                self._compacted_rows * (8 + 4 * self.num_values),
                self._decompress_all,
                lambda k: k[0] == "aux" and k[1] == me,
            )
        return view

    # -- batched lookup ----------------------------------------------------------
    def get(self, keys: np.ndarray, stats=None) -> Tuple[np.ndarray, np.ndarray]:
        """Batched aux lookup.

        Returns ``(found_mask (n,) bool, codes (n, m) int32)``; rows not
        present in T_aux have arbitrary codes and found=False.  The delta
        overlay answers first, tombstones hide rows after; the rest are
        ordered once by key and searched on the resident path (one
        ``searchsorted`` over the pool-resident sorted view) where the
        view is or can be resident, else on the partitioned path (each
        partition hit decompressed at most once per batch, paper
        §IV-B2).

        Timed by the ``aux.get`` span, whose args carry the keys probed
        (``keys``), the partitions their key ranges fall in (``visits``,
        counted alike on both paths), the keys answered on the
        resident path (``resident``: all of them or none), and the
        partitioned path's pool misses: those decompressed
        (``decompressed``) and those of them decompressed on the worker
        threads (``parallel``).  Those counts are taken once per call:
        into ``deepmap_aux_keys_total{outcome}`` (found or absent),
        ``deepmap_aux_path_keys_total{path}`` (resident or partitioned)
        and ``deepmap_aux_partition_visits_total``, and into ``stats`` (an
        :class:`~repro.api.plan.ExplainStats`: ``aux_keys``,
        ``aux_visits``, ``aux_resident_keys``, ``aux_decompressed``,
        ``aux_parallel``) when one is given.
        """
        keys = np.asarray(keys, dtype=np.int64)
        n = keys.shape[0]
        found = np.zeros(n, dtype=bool)
        out = np.zeros((n, self.num_values), dtype=np.int32)
        if n == 0:
            return found, out
        with obs.span("aux.get") as span:
            visits, resident, decompressed, parallel = self._probe(keys, found, out)
        span.args.update(keys=n, visits=visits, resident=resident,
                         decompressed=decompressed, parallel=parallel)
        hits = int(np.count_nonzero(found))
        reg = obs.registry()
        probed = reg.counter(
            "deepmap_aux_keys_total", "Keys probed in T_aux, by outcome."
        )
        probed.inc(hits, outcome="found")
        probed.inc(n - hits, outcome="absent")
        by_path = reg.counter(
            "deepmap_aux_path_keys_total",
            "Keys probed in T_aux, by path: the resident sorted view or "
            "the partitions.",
        )
        by_path.inc(resident, path="resident")
        by_path.inc(n - resident, path="partitioned")
        reg.counter(
            "deepmap_aux_partition_visits_total",
            "T_aux partitions visited by lookups (one per partition a "
            "batch hits).",
        ).inc(visits)
        if stats is not None:
            stats.aux_keys += n
            stats.aux_visits += visits
            stats.aux_resident_keys += resident
            stats.aux_decompressed += decompressed
            stats.aux_parallel += parallel
        return found, out

    def _probe(
        self, keys: np.ndarray, found: np.ndarray, out: np.ndarray
    ) -> Tuple[int, int, int, int]:
        """Fill ``found``/``out`` for ``keys``; returns the partitions
        visited, the keys answered on the resident path, and the pool
        misses decompressed, all and on the worker threads."""
        view = self._resident_view() if self._partitions else None
        resident = keys.shape[0] if view is not None else 0

        # Overlay first: delta wins over partitions; tombstones kill rows.
        if self._delta:
            for i, k in enumerate(keys.tolist()):
                row = self._delta.get(k)
                if row is not None:
                    found[i] = True
                    out[i] = row
        remaining = np.flatnonzero(~found)
        if not remaining.size or not self._partitions:
            return 0, resident, 0, 0
        tomb = (
            np.fromiter(self._tombstones, dtype=np.int64, count=len(self._tombstones))
            if self._tombstones
            else None
        )

        # Order the queries once (scan morsels and server batches arrive
        # sorted); each key's partition id is then non-decreasing.
        rkeys = keys[remaining]
        if not (rkeys[1:] >= rkeys[:-1]).all():
            order = np.argsort(rkeys, kind="stable")
            remaining, rkeys = remaining[order], rkeys[order]
        pid = np.searchsorted(self._boundaries, rkeys, side="right") - 1
        first = int(np.searchsorted(pid, 0))  # keys below every partition
        cuts = np.flatnonzero(np.diff(pid[first:])) + (first + 1)
        starts = np.concatenate(([first], cuts)) if first < pid.size else cuts

        if view is not None:
            _answer(*view, rkeys, remaining, tomb, found, out)
            return int(starts.size), resident, 0, 0
        ends = np.append(starts[1:], pid.size)
        return (int(starts.size), resident) + self._probe_partitions(
            pid[starts].tolist(), starts.tolist(), ends.tolist(),
            rkeys, remaining, tomb, found, out)

    def contains(self, keys: np.ndarray) -> np.ndarray:
        return self.get(keys)[0]

    # -- modification overlay (Algorithms 3-5) ------------------------------------
    def add(self, keys: np.ndarray, codes: np.ndarray) -> None:
        keys = np.asarray(keys, dtype=np.int64)
        codes = np.asarray(codes, dtype=np.int32)
        for k, row in zip(keys.tolist(), codes):
            self._delta[k] = row.copy()
            self._tombstones.discard(k)
        self._delta_size_cache = None

    def remove(self, keys: np.ndarray) -> None:
        keys = np.asarray(keys, dtype=np.int64)
        for k in keys.tolist():
            self._delta.pop(k, None)
            self._tombstones.add(k)
        self._delta_size_cache = None

    def update(self, keys: np.ndarray, codes: np.ndarray) -> None:
        # Same mechanics as add: delta overrides compacted partitions.
        self.add(keys, codes)

    def compact(self) -> None:
        """Fold delta + tombstones into fresh sorted compressed partitions;
        the old generation's resident view leaves the pool."""
        keys, codes = self._decompress_all()
        if self._tombstones or self._delta:
            drop = np.fromiter(
                set(self._tombstones) | set(self._delta), dtype=np.int64
            )
            keep = ~np.isin(keys, drop)
            keys, codes = keys[keep], codes[keep]
        if self._delta:
            dkeys = np.fromiter(self._delta.keys(), dtype=np.int64, count=len(self._delta))
            dcodes = np.stack([self._delta[int(k)] for k in dkeys]).astype(np.int32)
            keys = np.concatenate([keys, dkeys])
            codes = np.concatenate([codes, dcodes])
        self._delta.clear()
        self._tombstones.clear()
        self._delta_size_cache = None
        self._rebuild(keys, codes)

    # -- accounting ---------------------------------------------------------------
    @property
    def num_rows(self) -> int:
        # Callers (Algorithm 4) only tombstone keys actually present, so this
        # is exact under the documented contract; used for retrain triggering.
        return max(0, self._compacted_rows + len(self._delta) - len(self._tombstones))

    def _delta_bytes(self) -> int:
        if self._delta_size_cache is None:
            if not self._delta and not self._tombstones:
                self._delta_size_cache = 0
            else:
                dkeys = np.fromiter(
                    self._delta.keys(), dtype=np.int64, count=len(self._delta)
                )
                dcodes = (
                    np.stack([self._delta[int(k)] for k in dkeys]).astype(np.int32)
                    if self._delta
                    else np.zeros((0, self.num_values), dtype=np.int32)
                )
                blob = _pack_partition(dkeys, dcodes)
                blob += np.fromiter(
                    self._tombstones, dtype=np.int64, count=len(self._tombstones)
                ).tobytes()
                self._delta_size_cache = len(self._codec.compress(blob))
        return self._delta_size_cache

    def size_bytes(self) -> int:
        """Compressed at-rest size — the Eq. 1 contribution."""
        return (
            sum(len(p) for p in self._partitions)
            + self._boundaries.nbytes
            + self._delta_bytes()
        )

    # -- serialization --------------------------------------------------------------
    def to_state(self) -> dict:
        self.compact()
        return {
            "codec": self.codec_name,
            "partition_bytes": self.partition_bytes,
            "num_values": self.num_values,
            "partitions": list(self._partitions),
            "boundaries": self._boundaries.copy(),
            "part_rows": list(self._part_rows),
            "rows": self._compacted_rows,
        }

    @classmethod
    def from_state(cls, state: dict, pool: Optional[MemoryPool] = None) -> "AuxTable":
        t = cls(
            state["num_values"],
            state["codec"],
            state["partition_bytes"],
            pool,
        )
        t._partitions = list(state["partitions"])
        t._boundaries = np.asarray(state["boundaries"], dtype=np.int64)
        t._part_rows = list(state["part_rows"])
        t._compacted_rows = int(state["rows"])
        return t
