"""The DeepMapping hybrid structure ``M̂ = ⟨M, T_aux, V_exist, f_decode⟩``
(paper §IV) with Algorithm 1 lookup and Algorithm 3/4/5 modifications.

A :class:`DeepMappingStore` owns:

* ``params``/``spec``  — the multi-task memorization MLP ``M``;
* ``aux``              — :class:`~repro.core.aux_table.AuxTable` (``T_aux``);
* ``vexist``           — :class:`~repro.core.bitvector.BitVector`;
* ``codecs``           — per-column :class:`~repro.core.encoding.ValueCodec`
                         (``f_decode``);
* ``encoder``          — digit featurizer for keys.

Eq. 1 of the paper is :meth:`compression_ratio`:
``(size(M)+size(T_aux)+size(V_exist)+size(f_decode)) / size(D)``.

Modification semantics follow the paper exactly: inserts/updates/deletes
are materialized in the auxiliary structures without touching ``M``;
:meth:`should_retrain` triggers lazily once modified bytes exceed a
threshold (the paper's DM-Z1 retrains after 200 MB of modifications).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import numpy as np

from repro import obs
from repro.api.plan import ExplainStats, agg_partials, fold_agg_partials
from repro.api.protocol import MappingStore
from repro.core import model as model_lib
from repro.core import trainer as trainer_lib
from repro.core.aux_table import AuxTable
from repro.core.bitvector import BitVector
from repro.core.encoding import KeyEncoder, ValueCodec, build_codecs
from repro.core.inference import InferenceEngine
from repro.core.model import MLPSpec
from repro.core.table import Table
from repro.storage import MemoryPool


@dataclasses.dataclass(frozen=True)
class DeepMappingConfig:
    """Build-time knobs. ``shared``/``private`` give the default manual
    architecture; MHAS (``repro.core.mhas``) searches these instead."""

    base: int = 10
    # Beyond-paper: residue feature positions (multi-digit key % r).
    # Empty + auto_residues=False = paper-faithful encoding.  See
    # DESIGN.md §Perf / EXPERIMENTS §Perf.
    residues: Tuple[int, ...] = ()
    auto_residues: bool = False   # detect per-column periods at build
    shared: Tuple[int, ...] = (256, 256)
    private: Tuple[int, ...] = (64,)
    codec: str = "zstd"                    # DM-Z; "lzma" = DM-L
    partition_bytes: int = 128 * 1024
    dtype: str = "float32"
    train: trainer_lib.TrainConfig = dataclasses.field(
        default_factory=trainer_lib.TrainConfig
    )
    # Retrain once this many raw bytes have been inserted/deleted/updated
    # (paper's DM-Z1 uses 200 MB). None disables auto-trigger.
    retrain_after_modified_bytes: Optional[int] = None
    inference_batch: int = 1 << 16
    # Route inference through the fused Pallas kernel (TPU hot path).
    # The SAME path is used for build-time misclassification evaluation
    # and lookup, so T_aux always corrects exactly the deployed model.
    use_pallas: bool = False


#: Device chunks in flight ahead of the host half.  Bounds device
#: residency for huge scan/range batches (the window slides forward as
#: chunks are collected) while still double-buffering the pipeline.
DISPATCH_WINDOW = 2


@dataclasses.dataclass
class _PendingLookup:
    """Handle returned by ``_dispatch_lookup``: device inference for
    the first ``DISPATCH_WINDOW`` chunks is enqueued; the host half
    (existence fallback, aux merge, decode) runs at ``_collect_lookup``
    time, which tops the window up as it drains — device inference of
    chunk *i+1* overlaps the host half of chunk *i*, with at most
    ``DISPATCH_WINDOW`` chunks resident on device."""

    keys: np.ndarray
    wanted: Tuple[str, ...]            # heads to evaluate (selected + predicate)
    decode: Tuple[str, ...]            # columns to decode (selected only)
    skipped: Tuple[str, ...]
    preds: tuple                       # [(wanted idx, code table, describe), ...]
    tickets: list                      # [(start, InferTicket), ...] in flight
    next_start: int                    # first key offset not yet dispatched
    dispatch_s: float
    #: ((column, bool code table), ...) shipped to the engine so the
    #: fused kernel can evaluate the predicate conjunction in-kernel.
    kernel_tables: tuple = ()


class DeepMappingStore(MappingStore):
    """Hybrid learned KV store for one relation (single packed key)."""

    def __init__(
        self,
        encoder: KeyEncoder,
        spec: MLPSpec,
        params: Dict,
        codecs: Dict[str, ValueCodec],
        aux: AuxTable,
        vexist: BitVector,
        raw_bytes: int,
        num_rows: int,
        config: DeepMappingConfig,
    ):
        self.encoder = encoder
        self.spec = spec
        self.params = params
        self.codecs = codecs
        self.aux = aux
        self.vexist = vexist
        self.raw_bytes = int(raw_bytes)
        self.num_rows = int(num_rows)
        self.config = config
        self.modified_bytes = 0
        self._bytes_per_row = raw_bytes / max(1, num_rows)
        # Device inference engine: padded-weight cache per task subset,
        # bucketed batch compiles, dispatch/collect pipeline.  Lazy —
        # build() attaches the warm engine it evaluated T_aux with; a
        # cluster attaches engines from its shared EngineCache.
        self._engine: Optional[InferenceEngine] = None

    @property
    def engine(self) -> InferenceEngine:
        if self._engine is None:
            self._engine = InferenceEngine.for_store(self)
        return self._engine

    def attach_engine(self, engine: InferenceEngine) -> None:
        """Adopt an externally-built engine (build-time warm cache, or
        a cluster's shared-stats engine); the engine's bitvector binding
        (and its device word cache) is refreshed to this store's."""
        engine.bind_vexist(self.vexist)
        self._engine = engine

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        table: Table,
        config: DeepMappingConfig = DeepMappingConfig(),
        pool: Optional[MemoryPool] = None,
        spec: Optional[MLPSpec] = None,
        params: Optional[Dict] = None,
        verbose: bool = False,
    ) -> "DeepMappingStore":
        """Train (or accept) a mapping model and assemble the hybrid.

        Passing ``spec``+``params`` (e.g. from MHAS) skips training.
        """
        residues = config.residues
        if config.auto_residues:
            from repro.core.encoding import detect_residues

            residues = tuple(sorted(set(residues) | set(
                detect_residues(table.keys, table.columns, config.base)
            )))
            if verbose and residues:
                print(f"[build] auto-detected residue periods: {residues}")
        encoder = KeyEncoder(table.max_key, base=config.base, residues=residues)
        codecs = build_codecs(table.columns)
        if spec is None:
            spec = MLPSpec(
                base=config.base,
                width=encoder.width,
                shared=tuple(config.shared),
                private={n: tuple(config.private) for n in table.columns},
                out_cards={n: codecs[n].cardinality for n in table.columns},
                dtype=config.dtype,
            )
        digits = encoder.digits(table.keys)
        codes = np.stack([codecs[t].codes for t in spec.tasks], axis=1)
        if params is None:
            params, _, hist = trainer_lib.train(spec, digits, codes, config.train)
            if verbose:
                print(f"[build] trained {len(hist)} epochs, final loss {hist[-1]:.5f}")
        # Misclassification evaluation runs through the SAME engine that
        # will serve lookups (fused Pallas kernel or jit twin), so T_aux
        # always corrects exactly the deployed model; the warm weight
        # cache is adopted by the store below.
        engine = InferenceEngine(
            encoder, spec, params,
            use_pallas=config.use_pallas, max_bucket=config.inference_batch,
        )
        wrong = trainer_lib.evaluate_misclassified_engine(
            engine, table.keys, codes, batch=config.inference_batch
        )
        aux = AuxTable.build(
            table.keys[wrong],
            codes[wrong],
            codec=config.codec,
            partition_bytes=config.partition_bytes,
            pool=pool,
        )
        vexist = BitVector.from_keys(table.keys)
        store = cls(
            encoder=encoder,
            spec=spec,
            params=params,
            codecs=codecs,
            aux=aux,
            vexist=vexist,
            raw_bytes=table.raw_size_bytes(),
            num_rows=table.num_rows,
            config=config,
        )
        store.attach_engine(engine)
        if verbose:
            memorized = 1.0 - wrong.mean() if wrong.size else 1.0
            print(
                f"[build] memorized {memorized:.1%} of {table.num_rows} rows; "
                f"ratio {store.compression_ratio():.4f}"
            )
        return store

    # ---------------------------------------------------------------- lookup
    def _infer_codes(
        self, keys: np.ndarray, tasks: Optional[Tuple[str, ...]] = None
    ) -> np.ndarray:
        """Model predictions for (possibly out-of-capacity) keys.

        ``tasks`` restricts evaluation to a subset of heads (columns of
        the result follow ``tasks`` order); ``None`` evaluates all.
        Delegates to the :class:`InferenceEngine` (cached padded
        weights, bucketed compiles, pipelined chunks).
        """
        keys = np.asarray(keys, dtype=np.int64)
        return self.engine.infer(keys, tasks)

    @property
    def columns(self) -> Tuple[str, ...]:
        return self.spec.tasks

    def _dispatch_lookup(
        self,
        keys: np.ndarray,
        columns: Optional[Tuple[str, ...]] = None,
        fanout: Optional[bool] = None,
        predicates: tuple = (),
        keys_exist: bool = False,
        on_error: str = "raise",
    ) -> _PendingLookup:
        """Stage 1 of Algorithm 1: enqueue device inference (+ fused
        existence test) for the first chunks of the batch and return.
        The host half runs in :meth:`_collect_lookup`; a caller that
        dispatches batch *i+1* before collecting batch *i* overlaps
        device inference with host aux-merge + decode.  At most
        ``DISPATCH_WINDOW`` chunks are in flight (collect tops the
        window up), so a full-relation scan never pins the whole key
        set on device.  ``fanout`` is accepted for protocol parity
        (nothing to fan out here).

        ``predicates`` are pushed below decode: each compiles here to a
        boolean *code table* over the column's decode map (one
        vectorized evaluation per distinct value, not per row), the
        predicate head joins the inference task set even when the
        projection excludes it, and at collect time rows are filtered
        on their aux-corrected argmax codes — non-matching rows are
        never decoded.  ``keys_exist`` is accepted for hook parity (the
        fused existence test is already device-cheap here); so is
        ``on_error`` — a single-owner store has no healthy subset to
        degrade to, so the executor owns its partial fallback."""
        keys = np.asarray(keys, dtype=np.int64)
        t0 = time.perf_counter()
        selected, wanted, skipped, preds, ktables = self._plan_lookup(
            columns, predicates
        )
        pending = _PendingLookup(
            keys=keys, wanted=wanted, decode=selected, skipped=skipped,
            preds=preds, tickets=[], next_start=0, dispatch_s=0.0,
            kernel_tables=ktables,
        )
        if keys.shape[0] and wanted:
            while (
                len(pending.tickets) < DISPATCH_WINDOW
                and pending.next_start < keys.shape[0]
            ):
                self._dispatch_next_chunk(pending)
        pending.dispatch_s = time.perf_counter() - t0
        return pending

    def _plan_lookup(
        self, columns: Optional[Tuple[str, ...]], predicates: tuple
    ) -> tuple:
        """Shared planning half of :meth:`_dispatch_lookup`: resolve the
        projection/predicate head sets and compile the predicate code
        tables once.  Returns ``(selected, wanted, skipped, preds,
        kernel_tables)`` where ``kernel_tables`` pairs each predicate
        column with its boolean table for the in-kernel filter path."""
        all_tasks = self.spec.tasks
        selected = tuple(t for t in all_tasks if columns is None or t in columns)
        pred_cols = frozenset(p.column for p in predicates)
        wanted = tuple(
            t for t in all_tasks if t in pred_cols or t in selected
        )
        skipped = tuple(t for t in all_tasks if t not in wanted)
        preds = tuple(
            (wanted.index(p.column), self._pred_table(p), p.describe())
            for p in predicates
        )
        ktables = tuple(
            (p.column, preds[i][1]) for i, p in enumerate(predicates)
        )
        return selected, wanted, skipped, preds, ktables

    def _pred_table(self, pred) -> np.ndarray:
        """Memoized boolean code table for one predicate (see
        ``Predicate.code_table``), resident in the store's
        :class:`~repro.api.cache.PlanCache`: a morselized plan
        dispatches per chunk, but the full-vocabulary predicate
        evaluation is paid once per (predicate, decode map), not per
        morsel, and survives across repeated plans.  Invalidated by the
        mutation version AND decode-map identity (``extend()`` swaps in
        a new array); benign race under the shard fan-out — worst case
        is one duplicate compute."""
        codec = self.codecs[pred.column]
        return self.plan_cache().pred_table(
            pred, codec.decode_map, self.mutation_version()
        )

    def _dispatch_next_chunk(self, pending: _PendingLookup) -> None:
        bs = self.config.inference_batch
        start = pending.next_start
        pending.tickets.append((
            start,
            self.engine.dispatch(
                pending.keys[start : start + bs], pending.wanted,
                want_exists=True,
                pred_tables=pending.kernel_tables or None,
                aux=self.aux.device_view(),
            ),
        ))
        pending.next_start = min(start + bs, pending.keys.shape[0])

    def _collect_next(self, pending: _PendingLookup, task_idx, stats: ExplainStats):
        """Lines 3-8 of Algorithm 1 for the oldest chunk in flight: top
        the device window up, block on the chunk's result, test
        existence on the host where the tier did not, and apply the
        ``T_aux`` override to the existing keys.  Where the engine
        corrected the codes on the device from a view that is still
        current, only the delta overlay and the counts run here
        (``AuxTable.merge_device``); otherwise the host probes
        (``AuxTable.get``): mesh tickets, tiers beyond int32, tables
        whose view is not resident or that hold tombstones.  Returns
        ``(ticket, codes, exists, aux_rows)``: the rows ``T_aux``
        answered."""
        _, ticket = pending.tickets.pop(0)
        t0 = time.perf_counter()
        # keep the device window full before blocking on this chunk
        while (
            len(pending.tickets) < DISPATCH_WINDOW - 1
            and pending.next_start < pending.keys.shape[0]
        ):
            self._dispatch_next_chunk(pending)
        if not self.aux.device_current(ticket.aux_view):
            ticket.aux_dev = None  # none given, or written since: the model's codes
        codes, exists = self.engine.collect(ticket)      # line 3 (inference)
        stats.infer_s += time.perf_counter() - t0
        stats.dispatch_s += ticket.dispatch_s
        stats.wait_s += ticket.wait_s
        if exists is None:                               # line 5 (existence)
            with obs.span("store.exist") as span:
                exists = self.vexist.test(ticket.keys)
            stats.exist_s += span.seconds
        t3 = time.perf_counter()
        # lines 6-8: aux override for existing keys only.  T_aux rows
        # carry codes for ALL tasks; project to the evaluated ones.
        exist_idx = np.flatnonzero(exists)
        if ticket.aux_hit is not None:
            aux_rows = self.aux.merge_device(
                ticket.keys, exist_idx, codes, ticket.aux_hit, task_idx, stats)
        else:
            found, aux_codes = self.aux.get(ticket.keys[exist_idx], stats)
            aux_rows = exist_idx[found]
            codes[aux_rows] = aux_codes[:, task_idx][found]
        stats.aux_s += time.perf_counter() - t3
        return ticket, codes, exists, aux_rows

    def supports_kernel_filter(
        self, predicates: tuple = (), columns: Optional[Tuple[str, ...]] = None
    ) -> bool:
        """True when ``predicates`` would be evaluated in-kernel for a
        plan projecting ``columns`` (None: every column): every
        predicate column is a model head and the plan's own heads (the
        projection and the predicate columns, the set
        :meth:`_dispatch_lookup` evaluates) fit the resident ``fused``
        tier (the streamed and jit tiers filter on the host).  Checked
        per plan by the executor to skip its host ``Filter`` stage."""
        if not self.config.use_pallas or not predicates:
            return False
        if any(p.column not in self.spec.tasks for p in predicates):
            return False
        pred_cols = {p.column for p in predicates}
        return self.engine.kernel_filter_capable(tuple(
            t for t in self.spec.tasks
            if t in pred_cols or columns is None or t in columns
        ))

    def _dispatch_precomputed(
        self,
        keys: np.ndarray,
        ticket,
        columns: Optional[Tuple[str, ...]] = None,
        predicates: tuple = (),
    ) -> _PendingLookup:
        """Pending lookup whose device inference already happened
        elsewhere — the mesh shard scatter computes codes + exist bits
        for all shards in one ``shard_map`` launch and hands each shard
        store a ready :class:`~repro.core.inference.InferTicket` here.
        The host half of Algorithm 1 (existence fallback, aux merge,
        predicate filter, decode) still runs in this store's
        :meth:`_collect_lookup`, so modification overlays and byte
        contracts are identical to the thread-pool path."""
        keys = np.asarray(keys, dtype=np.int64)
        selected, wanted, skipped, preds, _ = self._plan_lookup(
            columns, predicates
        )
        # The scatter computes every head; narrow the ticket to the
        # wanted subset — collect() selects/permutes via task_order.
        ticket.tasks = wanted
        return _PendingLookup(
            keys=keys, wanted=wanted, decode=selected, skipped=skipped,
            preds=preds, tickets=[(0, ticket)], next_start=keys.shape[0],
            dispatch_s=0.0,
        )

    def _collect_lookup(
        self, pending: _PendingLookup
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray, Optional[np.ndarray], ExplainStats]:
        """Stage 2 of Algorithm 1: per chunk, block on the device
        result, apply the aux-table override, filter on argmax codes
        (value-predicate pushdown), and decode the surviving rows —
        while later chunks keep executing on the device.  Returns
        ``(values, exists, match, stats)``; ``match`` is ``None``
        without predicates."""
        keys, wanted, skipped = pending.keys, pending.wanted, pending.skipped
        decode_cols, preds = pending.decode, pending.preds
        all_tasks = self.spec.tasks
        n_chunks = max(
            1, -(-keys.shape[0] // self.config.inference_batch)
        ) if pending.tickets else 0
        fused = bool(pending.tickets) and pending.tickets[0][1].path == "fused"
        kfilter = (
            fused and bool(preds)
            and pending.tickets[0][1].match_dev is not None
        )
        stats = ExplainStats(
            heads_evaluated=wanted,
            heads_skipped=skipped,
            columns_decoded=decode_cols,
            columns_skipped=tuple(t for t in all_tasks if t not in decode_cols),
            predicates=tuple(d for _, _, d in preds),
            plan=(
                f"infer[{len(wanted)}/{len(all_tasks)} heads,"
                f"{pending.tickets[0][1].path if pending.tickets else 'none'}]",
                "exist[fused]" if fused else "exist",
                "aux_merge",
            )
            + (
                (
                    f"filter[{'kernel,' if kfilter else ''}"
                    f"{','.join(d for _, _, d in preds)}]",
                )
                if preds
                else ()
            )
            + (
                f"decode[{','.join(decode_cols)}]",
                f"pipeline[{max(1, n_chunks)} chunks]",
            ),
        )
        stats.kernel_filtered = kfilter
        stats.infer_s = pending.dispatch_s

        if not pending.tickets:
            # Zero keys or empty projection: typed empty/zero columns,
            # host existence only — never reaches JAX.
            with obs.span("store.exist") as exist_span:
                exists = self.vexist.test(keys)
            with obs.span("store.decode") as decode_span:
                values = {
                    t: self.codecs[t].decode(np.zeros(keys.shape[0], dtype=np.int32))
                    for t in decode_cols
                }
            stats.exist_s = exist_span.seconds
            stats.decode_s = decode_span.seconds
            return values, exists, exists.copy() if preds else None, stats

        task_idx = [all_tasks.index(t) for t in wanted]
        dec_idx = [wanted.index(t) for t in decode_cols]
        exists_parts, match_parts = [], []
        value_parts = {t: [] for t in decode_cols}
        while pending.tickets:
            ticket, pred, exists, aux_rows = self._collect_next(pending, task_idx, stats)
            if preds:
                match = self._filter_chunk(
                    ticket, pred, exists, aux_rows, preds, stats
                )
                hit = np.flatnonzero(match)
                stats.rows_matched += int(hit.size)
                # line 13: decode ONLY the matching rows.
                with obs.span("store.decode") as span:
                    for t, wi in zip(decode_cols, dec_idx):
                        codec = self.codecs[t]
                        out = np.zeros(
                            exists.shape[0], dtype=codec.decode_map.dtype
                        )
                        if hit.size:
                            out[hit] = codec.decode(pred[hit, wi])
                        value_parts[t].append(out)
                stats.rows_decoded += int(hit.size)
                match_parts.append(match)
            else:
                # line 13: decode — selected columns only.
                with obs.span("store.decode") as span:
                    for t, wi in zip(decode_cols, dec_idx):
                        safe = np.where(exists, pred[:, wi], 0)
                        value_parts[t].append(self.codecs[t].decode(safe))
                stats.rows_decoded += int(exists.shape[0])
            stats.decode_s += span.seconds
            exists_parts.append(exists)

        exists = (
            exists_parts[0]
            if len(exists_parts) == 1
            else np.concatenate(exists_parts)
        )
        match = None
        if preds:
            match = (
                match_parts[0]
                if len(match_parts) == 1
                else np.concatenate(match_parts)
            )
        values = {
            t: (parts[0] if len(parts) == 1 else np.concatenate(parts))
            for t, parts in value_parts.items()
        }
        return values, exists, match, stats

    def _iter_corrected_chunks(self, pending: _PendingLookup, stats: ExplainStats):
        """Yield ``(codes, exists, match)`` per chunk of a pending
        lookup — the shared front half of Algorithm 1 (device collect,
        existence fallback, aux override, predicate code-table filter)
        WITHOUT the decode tail.  ``codes`` are the aux-corrected argmax
        codes ``(rows, len(wanted))``; ``match`` is ``None`` without
        predicates.  The aggregate path consumes these directly: for
        existing rows the corrected codes are exact (the aux table
        overrides every model miss), so any reduction over them equals
        the same reduction over decoded values."""
        preds = pending.preds
        task_idx = [self.spec.tasks.index(t) for t in pending.wanted]
        while pending.tickets:
            ticket, codes, exists, aux_rows = self._collect_next(pending, task_idx, stats)
            match = None
            if preds:
                match = self._filter_chunk(
                    ticket, codes, exists, aux_rows, preds, stats
                )
                stats.rows_matched += int(match.sum())
            yield codes, exists, match

    @staticmethod
    def _filter_chunk(ticket, codes, exists, aux_rows, preds, stats) -> np.ndarray:
        """Predicate filter on one chunk's aux-corrected argmax codes:
        one boolean gather per predicate, BEFORE any decode.  Where the
        fused kernel already ANDed the predicate code tables over the
        model codes and exist bits (``ticket.match``), only the
        aux-overridden rows ``aux_rows`` can have changed codes, so just
        those are re-evaluated on their corrected codes via the full
        host tables; otherwise the host filters every row.  Records
        ``filter_s`` and ``filter_host_rows`` on ``stats``."""
        with obs.span("store.filter") as span:
            if ticket.match is not None:
                match = ticket.match
                if aux_rows.size:
                    patched = np.ones(aux_rows.shape[0], dtype=bool)
                    for wi, table, _ in preds:
                        patched &= table[codes[aux_rows, wi]]
                    match[aux_rows] = patched
                host_rows = int(aux_rows.size)
            else:
                stats.kernel_filtered = False
                match = exists.copy()
                for wi, table, _ in preds:
                    match &= table[np.where(exists, codes[:, wi], 0)]
                host_rows = int(np.count_nonzero(exists))
        stats.filter_s += span.seconds
        stats.filter_host_rows += host_rows
        return match

    def _collect_aggregate(self, pending: _PendingLookup, group_by, aggregates):
        """Code-space ``group_by(...).agg(...)``: consume aux-corrected
        argmax codes, never rows.

        Rows group by their raw code vectors (mixed-radix packed over
        the codec cardinalities); ``count`` is a ``bincount`` over the
        packed codes, ``sum``/``min``/``max`` gather per-row values
        through the cached code→value tables
        (:meth:`~repro.api.cache.PlanCache.agg_table` — the decode map
        cast once per vocabulary, version-fenced like the predicate
        tables).  Only the *distinct group labels* are decoded, so
        ``rows_decoded`` stays 0 no matter how many rows aggregate —
        the below-decode claim the TPC-H harness asserts.  State keys
        are decoded group values, mergeable across shards/members with
        independent codecs."""
        keys, wanted, preds = pending.keys, pending.wanted, pending.preds
        all_tasks = self.spec.tasks
        gidx = [wanted.index(c) for c in group_by]
        gdims = [self.codecs[c].cardinality for c in group_by]
        specs = []
        for spec in aggregates:
            if spec.column is None:
                specs.append((None, None))
            else:
                table = self.plan_cache().agg_table(
                    spec.column,
                    self.codecs[spec.column].decode_map,
                    self.mutation_version(),
                )
                specs.append((wanted.index(spec.column), table))
        n_chunks = max(
            1, -(-keys.shape[0] // self.config.inference_batch)
        ) if pending.tickets else 0
        stats = ExplainStats(
            heads_evaluated=wanted,
            heads_skipped=pending.skipped,
            columns_skipped=tuple(t for t in all_tasks if t not in wanted),
            predicates=tuple(d for _, _, d in preds),
            plan=(
                f"infer[{len(wanted)}/{len(all_tasks)} heads,"
                f"{pending.tickets[0][1].path if pending.tickets else 'none'}]",
                "exist",
                "aux_merge",
            )
            + (
                (f"filter[{','.join(d for _, _, d in preds)}]",) if preds else ()
            )
            + (
                f"aggregate[code,{len(group_by)} keys,{len(aggregates)} aggs]",
                f"pipeline[{max(1, n_chunks)} chunks]",
            ),
        )
        stats.infer_s = pending.dispatch_s
        state: Dict[tuple, list] = {}

        def fold(codes: Optional[np.ndarray], sel: np.ndarray) -> None:
            """Fold one chunk's selected rows (code-space) into state."""
            with obs.span("exec.agg") as span:
                if sel.size:
                    if gidx:
                        if len(gidx) > 1:
                            packed = np.ravel_multi_index(
                                [codes[sel, wi] for wi in gidx], gdims
                            )
                        else:
                            packed = codes[sel, gidx[0]]
                        ug, ginv = np.unique(packed, return_inverse=True)
                        coords = np.unravel_index(ug, gdims)
                        # decode per DISTINCT group, not per row: this is
                        # label materialization, not row decode
                        labels = [
                            self.codecs[c].decode(np.asarray(coord)).tolist()
                            for c, coord in zip(group_by, coords)
                        ]
                        group_tuples = list(zip(*labels))
                    else:
                        ug = np.zeros(1, dtype=np.int64)
                        ginv = np.zeros(sel.size, dtype=np.int64)
                        group_tuples = [()]
                    value_arrays = [
                        None if table is None else table[codes[sel, wi]]
                        for wi, table in specs
                    ]
                    partials = agg_partials(aggregates, ginv, len(ug), value_arrays)
                    fold_agg_partials(state, group_tuples, aggregates, partials)
            stats.agg_s += span.seconds

        if not pending.tickets:
            # Zero keys, or a count-only global aggregate with no
            # predicate heads: host existence test answers everything.
            with obs.span("store.exist") as span:
                exists = self.vexist.test(keys)
            stats.exist_s = span.seconds
            fold(None, np.flatnonzero(exists))
            return state, stats

        for codes, exists, match in self._iter_corrected_chunks(pending, stats):
            sel = np.flatnonzero(exists if match is None else match)
            fold(codes, sel)
        return state, stats

    def _lookup_with_stats(
        self,
        keys: np.ndarray,
        columns: Optional[Tuple[str, ...]] = None,
        fanout: Optional[bool] = None,
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray, ExplainStats]:
        """Algorithm 1 with projection pushdown and per-call stats —
        the dispatch/collect pair run back-to-back (all chunks' device
        work enqueued up front, host half trailing chunk by chunk)."""
        values, exists, _, stats = self._collect_lookup(
            self._dispatch_lookup(keys, columns, fanout)
        )
        return values, exists, stats

    def lookup(
        self, keys: np.ndarray, columns: Optional[Tuple[str, ...]] = None
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """Algorithm 1 — batched exact-match lookup.

        Returns ``(values, exists)``: per-column decoded arrays (rows
        where ``exists`` is False are NULL — filled with the column's
        code-0 value, callers must respect the mask) plus the existence
        mask.  For per-call stats use ``store.query(...).execute().explain``
        (the ``last_stats`` side-channel was removed — the metrics
        registry and ``ExplainStats`` supersede it).
        """
        values, exists, _stats = self._lookup_with_stats(keys, columns)
        return values, exists

    # ------------------------------------------------ modifications (Alg 3-5)
    def _encode_rows(self, columns: Dict[str, np.ndarray]) -> np.ndarray:
        """Encode raw values to codes, extending codecs for unseen values.

        Codes beyond a head's out_card can never be predicted by ``M``,
        so such rows are automatically routed to T_aux — exactly the
        paper's semantics for values the model cannot express.
        """
        cols = []
        for t in self.spec.tasks:
            codec = self.codecs[t]
            codec.extend(columns[t])
            codes, known = codec.encode(columns[t])
            if not known.all():
                raise RuntimeError("extend() must make every value encodable")
            cols.append(codes)
        return np.stack(cols, axis=1)

    def insert(self, keys: np.ndarray, columns: Dict[str, np.ndarray]) -> None:
        """Algorithm 3. Pairs the model already generalizes to are NOT
        stored; the rest land in T_aux."""
        keys = np.asarray(keys, dtype=np.int64)
        if keys.size == 0:
            return
        if np.unique(keys).size != keys.size:
            raise ValueError("duplicate keys in insert batch")
        if self.vexist.test(keys).any():
            raise ValueError("insert of existing key; use update()")
        codes = self._encode_rows(columns)
        self.vexist.set(keys, True)                      # line 4
        pred = self._infer_codes(keys)                   # line 5 (inference check)
        wrong = (pred != codes).any(axis=1) | (keys >= self.encoder.capacity)
        if wrong.any():
            self.aux.add(keys[wrong], codes[wrong])      # line 9
        self.num_rows += keys.shape[0]
        self.raw_bytes += int(keys.shape[0] * self._bytes_per_row)
        self.modified_bytes += int(keys.shape[0] * self._bytes_per_row)
        self._note_mutation()  # invalidate cached plans (and, via the
        # version stamp, code tables over a possibly-extended decode map)

    def delete(self, keys: np.ndarray) -> None:
        """Algorithm 4. Existence bit off; purge from T_aux if present."""
        # unique: a key repeated in one batch deletes one row, not two
        keys = np.unique(np.asarray(keys, dtype=np.int64))
        present = self.vexist.test(keys)
        keys = keys[present]
        if keys.size == 0:
            return
        self.vexist.set(keys, False)                     # line 4
        in_aux = self.aux.contains(keys)                 # line 5
        if in_aux.any():
            self.aux.remove(keys[in_aux])
        self.num_rows -= keys.shape[0]
        self.raw_bytes -= int(keys.shape[0] * self._bytes_per_row)
        self.modified_bytes += int(keys.shape[0] * self._bytes_per_row)
        self._note_mutation()

    def update(self, keys: np.ndarray, columns: Dict[str, np.ndarray]) -> None:
        """Algorithm 5. Correctly-predicted updates drop any aux entry;
        the rest are upserted into T_aux."""
        keys = np.asarray(keys, dtype=np.int64)
        if keys.size == 0:
            return
        if not self.vexist.test(keys).all():
            raise ValueError("update of non-existing key; use insert()")
        codes = self._encode_rows(columns)
        pred = self._infer_codes(keys)
        right = (pred == codes).all(axis=1) & (keys < self.encoder.capacity)
        if right.any():
            in_aux = self.aux.contains(keys[right])      # line 4
            if in_aux.any():
                self.aux.remove(keys[right][in_aux])
        wrong = ~right
        if wrong.any():
            self.aux.update(keys[wrong], codes[wrong])   # lines 7-11
        self.modified_bytes += int(keys.shape[0] * self._bytes_per_row)
        self._note_mutation()

    def _range_keys(self, lo: int, hi: Optional[int]) -> np.ndarray:
        """Existence-index range filter (§IV-E) — key source for the
        protocol's ``range_lookup``/``scan`` and the plan executor."""
        return self.vexist.keys_in_range(lo, hi)

    def should_retrain(self) -> bool:
        thr = self.config.retrain_after_modified_bytes
        return thr is not None and self.modified_bytes >= thr

    def materialize(self) -> Table:
        """Reconstruct the full logical table (used by retrain)."""
        keys, values = self.scan()
        return Table(keys=keys, columns=values)

    def retrain(self, verbose: bool = False) -> "DeepMappingStore":
        """Rebuild model + auxiliary structures on current logical data
        (paper: lazily, offline/background/non-peak)."""
        return DeepMappingStore.build(
            self.materialize(), self.config, pool=self.aux.pool, verbose=verbose
        )

    # ---------------------------------------------------------- persistence
    def save(self, path: str) -> None:
        """Protocol persistence — the ``core.serialize`` directory
        format (atomic tmp+rename)."""
        from repro.core import serialize  # local: serialize imports us

        serialize.save_store(self, path)

    @classmethod
    def load(cls, path: str, pool: Optional[MemoryPool] = None) -> "DeepMappingStore":
        from repro.core import serialize

        return serialize.load_store(path, pool=pool)

    # ------------------------------------------------------------- accounting
    def size_breakdown(self) -> Dict[str, int]:
        """Bytes per component — the paper's Fig. 6 storage breakdown."""
        return {
            "model": model_lib.model_size_bytes(self.params),
            "aux_table": self.aux.size_bytes(),
            "exist_bitvector": self.vexist.size_bytes(),
            "decode_map": sum(c.size_bytes() for c in self.codecs.values())
            + self.encoder.size_bytes(),
        }

    def model_bytes(self, columns: Optional[Tuple[str, ...]] = None) -> int:
        """Bytes of the shared trunk and of the heads of ``columns``
        (None: every head): what a plan reading them evaluates."""
        heads = self.params["heads"]
        return model_lib.model_size_bytes({
            "shared": self.params["shared"],
            "heads": {t: heads[t] for t in self.spec.tasks
                      if columns is None or t in columns},
        })

    def size_bytes(self) -> int:
        return sum(self.size_breakdown().values())

    def compression_ratio(self) -> float:
        """Paper Eq. 1 — lower is better; 1.0 means no compression."""
        return self.size_bytes() / max(1, self.raw_bytes)

    def memorized_fraction(self) -> float:
        """Fraction of rows answered by ``M`` alone (paper reports 66-81%)."""
        return 1.0 - self.aux.num_rows / max(1, self.num_rows)
