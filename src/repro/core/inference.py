"""Device-resident lookup inference engine (ISSUE 3 tentpole).

The paper's latency claim (Fig. 7) assumes model evaluation is ONE
dense batched device pass — but the seed hot path paid four hidden
host costs per call: digit featurization in numpy, re-padding every
weight tensor, a serial host existence check, and a fresh jit compile
for every distinct batch size.  :class:`InferenceEngine` owns the whole
device side of Algorithm 1 and removes all four:

* **Cached padded weights.**  Per task-subset (projection pushdown),
  the padded/flattened device weights and the subset spec/params view
  are built once and reused by every subsequent call — the seed's
  ``ops._pad_flat_weights``-per-call cost is gone from the hot path.
* **Bucketed batch compiles.**  Batch sizes round up to powers of two
  at or above ``tile_n``, so a workload with O(N) distinct batch sizes
  compiles O(log N) programs.  ``EngineStats.compiles`` counts distinct
  compiled (path, spec, bucket) signatures, deduplicated cluster-wide.
* **Fused key-encode + existence kernel.**  With ``use_pallas`` the
  engine ships RAW int32 keys; digit/residue decomposition happens
  in-kernel from compile-time ``(modulus, divisor)`` constants and the packed
  existence words are tested in the same jitted program — codes and
  exist bits come back in one device round trip
  (``repro.kernels.fused_mlp.fused_lookup_call``).  On the jit path the
  decomposition moves in-graph instead (``_codes_from_keys_jit``).
* **dispatch()/collect() pipeline.**  ``dispatch`` enqueues device
  work and returns immediately (JAX async dispatch); ``collect``
  blocks on the result.  Callers dispatch chunk ``i+1`` before
  collecting chunk ``i``, so host aux-merge + decode of one chunk
  overlaps device inference of the next — the two-stage software
  pipeline ``serve/engine.py`` promises.

Fallback ladder (never raises on eligibility, always answers):
``fused`` needs ``use_pallas``, an attached :class:`BitVector`, key
and word domains within int32, and the VMEM budget; ``pallas_digits``
drops the in-kernel encode/exist (host digits, host exist);
``fused_streamed`` covers over-budget models — head weights are
partitioned into VMEM-sized pages (``kops.plan_head_pages``) and each
page runs its own ``fused_lookup`` call on the same device key buffer,
so JAX async dispatch overlaps page *i+1*'s weight transfer with page
*i*'s compute and a large multi-task model never falls back to jit;
``jit_keys`` is the non-Pallas twin with in-graph decomposition;
``jit_digits`` is the legacy host-featurized path for >int32 domains.
Every path produces byte-identical codes/exists (tested in
``tests/test_kernels.py::TestFusedLookupConformance`` and
``tests/test_vmem_streaming.py``).

On the ``fused``, ``fused_streamed`` and ``jit_keys`` tiers, ``dispatch(...,
aux=view)`` also enqueues the ``T_aux`` override of Algorithm 1 (lines
6-8) on the device, right after the model's call, as a program of its
own (``DeviceView.correct``, in ``core/aux_table.py``): the codes come
back already corrected from the device copy of the resident view, with
a hit mask (``InferTicket.aux_hit``).

The fused tier can additionally evaluate pushdown predicates in-kernel:
``dispatch(..., pred_tables=...)`` ships the boolean code tables into
the ``pallas_call`` and ``InferTicket.match`` carries the match bits
back (None when the chosen path could not kernel-filter — the caller
falls back to host filtering).
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import weakref
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import model as model_lib
from repro.core.model import MLPSpec
from repro.fault import injection as fault_injection
from repro.kernels import bitvector as bv_kernel
from repro.kernels import fused_mlp as fm_kernel
from repro.kernels import ops as kops

INT32_MAX = 2**31 - 1

# Every enabled ``with``-span of repro.obs also lands on the profiler's
# host plane, on the clock of the device's ops.
obs.set_profiler_hook(jax.profiler.TraceAnnotation)

#: Host work trails device dispatch by this many in-flight chunks.
PIPELINE_DEPTH = 2


@dataclasses.dataclass
class EngineStats:
    """Aggregate engine counters.  One instance may be shared by every
    shard engine of a cluster (``EngineCache``), so compile signatures
    are deduplicated cluster-wide — shards with identical architecture
    and bucket shapes share one XLA program."""

    dispatches: int = 0
    fused_calls: int = 0
    fused_streamed_calls: int = 0
    pallas_calls: int = 0
    jit_calls: int = 0
    host_featurize_calls: int = 0
    weight_cache_misses: int = 0
    word_uploads: int = 0
    #: Resolved VMEM residency budget (bytes) of the engine(s) sharing
    #: this stats object — not a counter; surfaced so ExplainStats/bench
    #: metadata can report which budget drove tier selection.
    vmem_budget_bytes: int = 0

    def __post_init__(self) -> None:
        self._seen: set = set()  # guarded-by: _lock
        self._lock = threading.Lock()

    @property
    def compiles(self) -> int:
        """Distinct compiled program signatures observed."""
        return len(self._seen)

    def note_compile(self, key: Tuple) -> None:
        with self._lock:
            new = key not in self._seen
            self._seen.add(key)
        if new:
            obs.counter(
                "deepmap_engine_compiles_total",
                "Distinct compiled program signatures (bucketed shapes "
                "dedupe; shared EngineCache dedupes cluster-wide).",
            ).inc()

    def bump(self, field: str, amount: int = 1) -> None:
        """Locked counter increment — shard engines under the fan-out
        thread pool share this object, and a plain ``+=`` would lose
        updates across threads.  Mirrored into the metrics registry as
        ``deepmap_engine_events_total{event=<field>}`` (dispatches,
        fused/fused_streamed/pallas/jit calls = the fallback-ladder
        tier taken, weight-cache misses, word uploads)."""
        with self._lock:
            setattr(self, field, getattr(self, field) + amount)
        obs.counter(
            "deepmap_engine_events_total",
            "Engine events by type: dispatches, fallback-ladder tier "
            "taken (fused_calls/pallas_calls/jit_calls), host featurize, "
            "weight-cache misses, bitvector word uploads.",
        ).inc(amount, event=field)


@functools.partial(jax.jit, static_argnames=("spec", "pos_ops", "capacity"))
def _codes_from_keys_jit(
    params: Dict,
    keys: jnp.ndarray,
    spec: MLPSpec,
    pos_ops: Tuple[Tuple[int, int], ...],
    capacity: int,
) -> jnp.ndarray:
    """jit twin of the fused kernel's key path: digit/residue
    decomposition in-graph (no host featurization, HBM input is the
    (n,) key vector), gather-forward, per-task argmax.  Rows outside
    ``[0, capacity)`` are masked to code 0 — the ``_infer_codes``
    zero-fill contract."""
    in_cap = (keys >= 0) & (keys < capacity)
    safe = jnp.where(in_cap, keys, 0)
    cols = [
        (((safe % mod) // div) % spec.base).astype(jnp.int32)[:, None]
        for mod, div in pos_ops
    ]
    digits = jnp.concatenate(cols, axis=1)
    codes = model_lib.predict_codes(params, digits, spec)
    return jnp.where(in_cap[:, None], codes, 0)


class _TaskEntry:
    """Per-task-subset cache: subset spec + params view, the heads'
    indices in the whole spec, and (lazily) the padded flat device
    weights the Pallas paths reuse."""

    __slots__ = ("spec", "params", "heads", "_flat", "_wbytes")

    def __init__(self, spec: MLPSpec, params: Dict, heads: Tuple[int, ...]):
        self.spec = spec
        self.params = params
        self.heads = heads
        self._flat: Optional[Tuple[jnp.ndarray, ...]] = None
        self._wbytes = 0

    def flat(self) -> Tuple[Tuple[jnp.ndarray, ...], int]:
        if self._flat is None:
            self._flat, self._wbytes = kops.pad_flat_weights(self.params, self.spec)
        return self._flat, self._wbytes

    def card_pads(self) -> Tuple[Tuple[str, int], ...]:
        cards = self.spec.card_map
        return tuple(
            (t, kops._round_up(cards[t], kops.LANE)) for t in self.spec.tasks
        )


@dataclasses.dataclass
class InferTicket:
    """In-flight device work handle returned by ``dispatch``."""

    n: int
    tasks: Tuple[str, ...]                 # requested column order
    path: str
    keys: np.ndarray                       # original int64 chunk keys
    want_exists: bool = False
    keys_dev: object = None                # (n_pad,) int32 keys, -1 padded (int32 tiers)
    codes_dev: object = None               # device array / tuple, path-shaped
    exists_dev: object = None              # (n_pad,) int32 device array (fused)
    match_dev: object = None               # (n_pad,) int32 kernel match bits
    in_cap: Optional[np.ndarray] = None    # host mask (digits paths only)
    task_order: Tuple[str, ...] = ()       # device result order (spec canonical)
    #: Host copy of the in-kernel predicate match bits, filled by
    #: ``collect`` — None when the kernel did not filter (caller runs
    #: the host filter instead).  Aux-overridden rows still need the
    #: host patch: the kernel matched on the *model* code.
    match: Optional[np.ndarray] = None
    #: The device ``T_aux`` override (``DeviceView.correct``'s outputs)
    #: and the view it read; None where the tier or the caller gave none.
    aux_dev: object = None
    aux_view: object = None
    #: Host copy of its hit mask, filled by ``collect`` where it
    #: returned the corrected codes.
    aux_hit: Optional[np.ndarray] = None
    #: Seconds of the ``engine.dispatch`` and ``engine.wait`` spans.
    dispatch_s: float = 0.0
    wait_s: float = 0.0


class InferenceEngine:
    """Per-store device inference: weight cache, bucketing, pipeline.

    One engine per :class:`~repro.core.hybrid.DeepMappingStore`
    (weights are store-specific); a cluster shares one
    :class:`EngineStats` across its shard engines via
    :class:`EngineCache`.  ``vexist`` may be attached after
    construction (build-time misclassification evaluation runs before
    the bitvector exists).
    """

    def __init__(
        self,
        encoder,
        spec: MLPSpec,
        params: Dict,
        vexist=None,
        *,
        use_pallas: bool = False,
        tile_n: int = kops.DEFAULT_TILE_N,
        max_bucket: int = 1 << 16,
        interpret: Optional[bool] = None,
        stats: Optional[EngineStats] = None,
    ):
        self.encoder = encoder
        self.spec = spec
        self.params = params
        self.vexist = vexist
        self.use_pallas = bool(use_pallas)
        self.tile_n = int(tile_n)
        self.max_bucket = max(int(max_bucket), self.tile_n)
        self.interpret = kops._auto_interpret(interpret)
        # Resolved once per engine: tier selection must be stable across
        # a store's lifetime (REPRO_VMEM_BUDGET changes need a rebuild).
        self.vmem_budget = kops.vmem_budget_bytes()
        self.stats = stats if stats is not None else EngineStats()
        self.stats.vmem_budget_bytes = self.vmem_budget
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, ...], _TaskEntry] = {}  # guarded-by: _lock
        self._pos_ops = tuple(encoder.position_ops())
        self._words_cache: Optional[Tuple[int, jnp.ndarray]] = None  # guarded-by: _lock

    def bind_vexist(self, vexist) -> None:
        """Swap the engine's bitvector binding, dropping the device
        word cache — its version key is only meaningful per bitvector
        instance, so a stale entry could otherwise serve another
        store's existence bits."""
        with self._lock:
            self.vexist = vexist
            self._words_cache = None

    @classmethod
    def for_store(cls, store, stats: Optional[EngineStats] = None) -> "InferenceEngine":
        cfg = store.config
        return cls(
            store.encoder,
            store.spec,
            store.params,
            store.vexist,
            use_pallas=cfg.use_pallas,
            max_bucket=cfg.inference_batch,
            stats=stats,
        )

    # ------------------------------------------------------------- caches
    def _entry(self, tasks: Tuple[str, ...]) -> _TaskEntry:
        entry = self._entries.get(tasks)
        if entry is None:
            with self._lock:
                entry = self._entries.get(tasks)
                if entry is None:
                    if tasks == self.spec.tasks:
                        spec, params = self.spec, self.params
                    else:
                        spec = MLPSpec(
                            base=self.spec.base,
                            width=self.spec.width,
                            shared=self.spec.shared,
                            private={t: self.spec.private_map[t] for t in tasks},
                            out_cards={t: self.spec.card_map[t] for t in tasks},
                            dtype=self.spec.dtype,
                        )
                        params = {
                            "shared": self.params["shared"],
                            "heads": {t: self.params["heads"][t] for t in tasks},
                        }
                    entry = _TaskEntry(spec, params, tuple(
                        self.spec.tasks.index(t) for t in spec.tasks))
                    self._entries[tasks] = entry
                    self.stats.bump("weight_cache_misses")
        return entry

    def _device_words(self) -> jnp.ndarray:
        """Device copy of the packed existence words, re-uploaded only
        when the bitvector's mutation counter moves."""
        v = self.vexist
        with self._lock:
            cached = self._words_cache
            if cached is None or cached[0] != v.version:
                words32 = bv_kernel.pack_words32(v.words)
                self._words_cache = (v.version, jnp.asarray(words32))
                self.stats.bump("word_uploads")
            return self._words_cache[1]

    def _bucket(self, n: int) -> int:
        b = self.tile_n
        while b < n:
            b <<= 1
        return b

    # -------------------------------------------------------- path choice
    # Eligibility uses shape-derived byte counts (kops.resident_bytes),
    # NOT entry.flat(): deciding against the Pallas path must not
    # materialize — and permanently cache — a padded device weight copy
    # the jit fallback never touches.
    def _words_in_domain(self) -> bool:
        v = self.vexist
        return v is not None and v.capacity <= INT32_MAX + 1

    def _fused_eligible(self, entry: _TaskEntry) -> bool:
        return (
            self._words_in_domain()
            and self.encoder.capacity <= INT32_MAX
            and self._pallas_eligible(entry)
        )

    def _pallas_eligible(self, entry: _TaskEntry) -> bool:
        return kops.resident_bytes(entry.spec, self.tile_n) <= self.vmem_budget

    def kernel_filter_capable(
        self, tasks: Optional[Tuple[str, ...]] = None
    ) -> bool:
        """True when ``dispatch(..., want_exists=True, pred_tables=...)``
        for this task subset would take the resident ``fused`` tier —
        the only tier that evaluates predicate code tables in-kernel.
        Streamed and jit tiers report False (they filter on the host)."""
        if not self.use_pallas:
            return False
        if tasks is None:
            canon = self.spec.tasks
        else:
            keep = frozenset(tasks)
            canon = tuple(t for t in self.spec.tasks if t in keep)
        if not canon:
            return False
        return self._fused_eligible(self._entry(canon))

    def _streamed_plan(
        self, entry: _TaskEntry, want_exists: bool
    ) -> Optional[Tuple[Tuple[Tuple[str, ...], ...], bool]]:
        """Page plan for the ``fused_streamed`` tier, or None when it
        cannot apply.  Returns ``(pages, with_exists)`` — existence
        rides with page 0; without ``want_exists`` (or without a
        bitvector in the int32 domain) every page is codes-only and the
        caller tests existence on the host like the other non-fused
        tiers."""
        if self.encoder.capacity > INT32_MAX:
            return None
        pages = kops.plan_head_pages(
            entry.spec, self.tile_n, budget=self.vmem_budget
        )
        if pages is None:
            return None
        return pages, want_exists and self._words_in_domain()

    # ---------------------------------------------------- dispatch/collect
    def dispatch(
        self,
        keys: np.ndarray,
        tasks: Optional[Tuple[str, ...]] = None,
        want_exists: bool = False,
        pred_tables: Optional[Tuple[Tuple[str, np.ndarray], ...]] = None,
        aux=None,
    ) -> InferTicket:
        """Enqueue device inference for one key chunk; returns
        immediately (JAX async dispatch).  ``want_exists`` additionally
        requests existence bits — in-kernel on the fused path, host
        ``BitVector.test`` at collect time otherwise.

        ``aux`` — a :class:`~repro.core.aux_table.DeviceView` — asks for
        the ``T_aux`` override on the device (``aux.correct``) after the
        model's call, on the tiers that upload int32 keys; the others
        leave ``aux_dev`` None and the caller probes on the host.

        ``pred_tables`` — ``((column, bool_code_table), ...)`` — asks
        the fused kernel to evaluate the pushdown predicate conjunction
        in-kernel; the resulting match bits land on
        ``InferTicket.match`` at collect time.  Best-effort: any path
        other than resident ``fused`` (or a table for a column outside
        the dispatched task set) leaves ``match`` None and the caller
        filters on the host."""
        keys = np.asarray(keys, dtype=np.int64)
        tasks = self.spec.tasks if tasks is None else tuple(tasks)
        n = keys.shape[0]
        if n == 0 or not tasks:
            return InferTicket(n=n, tasks=tasks, path="empty", keys=keys,
                               want_exists=want_exists)
        with obs.span("engine.dispatch") as span:
            # Fault-injection site: after the zero-length early-out so
            # the executor's typed-empty probes (used to build
            # placeholder columns in degraded mode) are never failed.
            fault_injection.maybe_fail("engine_dispatch")
            self.stats.bump("dispatches")
            # MLPSpec canonicalizes task order, so the subset entry (and
            # the device result columns) follow spec order; collect()
            # permutes back to the requested order.
            canon = tuple(t for t in self.spec.tasks if t in frozenset(tasks))
            entry = self._entry(canon)
            bucket = self._bucket(n)

            if self.use_pallas and want_exists and self._fused_eligible(entry):
                ticket = self._dispatch_fused(keys, tasks, entry, bucket,
                                              pred_tables)
            elif self.use_pallas and self._pallas_eligible(entry):
                ticket = self._dispatch_pallas_digits(keys, tasks, entry, bucket,
                                                      want_exists)
            elif self.use_pallas and (
                plan := self._streamed_plan(entry, want_exists)
            ) is not None:
                ticket = self._dispatch_fused_streamed(keys, tasks, entry, bucket,
                                                       *plan)
            elif self.encoder.capacity <= INT32_MAX:
                ticket = self._dispatch_jit_keys(keys, tasks, entry, bucket,
                                                 want_exists)
            else:
                ticket = self._dispatch_jit_digits(keys, tasks, entry, bucket,
                                                   want_exists)
            ticket.task_order = entry.spec.tasks
            if aux is not None and ticket.keys_dev is not None:
                codes = ticket.codes_dev
                self.stats.note_compile(
                    ("aux_correct", bucket, len(entry.heads), aux.signature,
                     len(codes) if isinstance(codes, tuple) else 0)
                )
                ticket.aux_dev = aux.correct(codes, ticket.keys_dev, entry.heads)
                ticket.aux_view = aux
        ticket.dispatch_s = span.seconds
        return ticket

    def _keys_i32(self, keys: np.ndarray, bucket: int) -> np.ndarray:
        """int32 view with a -1 sentinel for unrepresentable keys (they
        are masked to code 0 / exist 0 in-graph, which matches the host
        contract because the gated domains fit int32); padding rows get
        the same sentinel."""
        kp = np.full(bucket, -1, dtype=np.int32)
        valid = (keys >= 0) & (keys <= INT32_MAX)
        kp[: keys.shape[0]] = np.where(valid, keys, -1).astype(np.int32)
        return kp

    def _kernel_pred_tables(
        self, entry: _TaskEntry, pred_tables
    ) -> Tuple[Tuple[int, ...], Tuple[jnp.ndarray, ...]]:
        """Padded int32 device tables + head indices for in-kernel
        filtering, or ``((), ())`` when any table's column is outside
        the dispatched task subset (host filter handles it).  Model
        codes never exceed the head cardinality, so only the first
        ``card`` entries of the (possibly longer, codec-extended) host
        table are shipped."""
        if not pred_tables:
            return (), ()
        spec = entry.spec
        cards = spec.card_map
        ptasks, ptabs = [], []
        for col, table in pred_tables:
            if col not in cards:
                return (), ()
            card = cards[col]
            padded = np.zeros(kops._round_up(card, kops.LANE), dtype=np.int32)
            padded[:card] = np.asarray(table[:card], dtype=np.int32)
            ptasks.append(spec.tasks.index(col))
            ptabs.append(jnp.asarray(padded))
        return tuple(ptasks), tuple(ptabs)

    def _dispatch_fused(self, keys, tasks, entry, bucket,
                        pred_tables=None) -> InferTicket:
        flat, _ = entry.flat()
        words = self._device_words()
        ptasks, ptabs = self._kernel_pred_tables(entry, pred_tables)
        self.stats.bump("fused_calls")
        self.stats.note_compile(
            ("fused", entry.spec, self._pos_ops, bucket,
             words.shape[0], ptasks, tuple(t.shape[0] for t in ptabs))
        )
        keys_dev = jnp.asarray(self._keys_i32(keys, bucket))
        codes, exists, match = kops.fused_lookup(
            flat, entry.spec, keys_dev,
            self._pos_ops, words, self.encoder.capacity,
            tile_n=self.tile_n, interpret=self.interpret,
            pred_tables=ptabs, pred_tasks=ptasks,
        )
        return InferTicket(n=keys.shape[0], tasks=tasks, path="fused",
                           keys=keys, want_exists=True, keys_dev=keys_dev,
                           codes_dev=codes, exists_dev=exists,
                           match_dev=match)

    def _dispatch_fused_streamed(
        self, keys, tasks, entry, bucket, pages, with_exists
    ) -> InferTicket:
        """Over-budget fused path: one ``fused_lookup`` per head page.

        All pages share the one device key buffer; JAX async dispatch
        enqueues them back-to-back, so page *i+1*'s weight upload
        overlaps page *i*'s compute (the streaming contract in DESIGN.md
        §Device execution).  The shared trunk is re-sent and recomputed
        per page — each page is exactly the resident fused kernel on a
        task subset, so byte-identity follows from the per-subset
        conformance the resident tier already guarantees.  Existence
        rides with page 0 when ``with_exists``."""
        keys_dev = jnp.asarray(self._keys_i32(keys, bucket))
        words = self._device_words() if with_exists else None
        self.stats.bump("fused_streamed_calls")
        codes_pages = []
        exists_dev = None
        for i, page in enumerate(pages):
            page_entry = self._entry(page)
            flat, _ = page_entry.flat()
            page_exists = with_exists and i == 0
            self.stats.note_compile(
                ("fused_streamed", page_entry.spec, self._pos_ops,
                 bucket, words.shape[0] if page_exists else 0, page_exists)
            )
            codes, ex, _ = kops.fused_lookup(
                flat, page_entry.spec, keys_dev, self._pos_ops,
                words if page_exists else None, self.encoder.capacity,
                tile_n=self.tile_n, interpret=self.interpret,
            )
            codes_pages.append(codes)
            if page_exists:
                exists_dev = ex
        return InferTicket(n=keys.shape[0], tasks=tasks,
                           path="fused_streamed", keys=keys,
                           want_exists=with_exists, keys_dev=keys_dev,
                           codes_dev=tuple(codes_pages),
                           exists_dev=exists_dev)

    def _dispatch_jit_keys(self, keys, tasks, entry, bucket, want_exists):
        self.stats.bump("jit_calls")
        self.stats.note_compile(
            ("jit_keys", entry.spec, self._pos_ops, bucket)
        )
        keys_dev = jnp.asarray(self._keys_i32(keys, bucket))
        codes = _codes_from_keys_jit(
            entry.params, keys_dev,
            entry.spec, self._pos_ops, self.encoder.capacity,
        )
        return InferTicket(n=keys.shape[0], tasks=tasks, path="jit_keys",
                           keys=keys, want_exists=want_exists, keys_dev=keys_dev,
                           codes_dev=codes)

    def _host_digits(self, keys: np.ndarray, bucket: int):
        """Legacy host featurization for >int32 domains: digits of
        in-capacity keys, zero rows elsewhere."""
        self.stats.bump("host_featurize_calls")
        in_cap = (keys >= 0) & (keys < self.encoder.capacity)
        dp = np.zeros((bucket, self.encoder.width), dtype=np.int32)
        idx = np.flatnonzero(in_cap)
        if idx.size:
            dp[idx] = self.encoder.digits(keys[idx])
        return dp, in_cap

    def _dispatch_pallas_digits(self, keys, tasks, entry, bucket, want_exists):
        flat, _ = entry.flat()
        dp, in_cap = self._host_digits(keys, bucket)
        self.stats.bump("pallas_calls")
        self.stats.note_compile(("pallas_digits", entry.spec, bucket))
        outs = fm_kernel.fused_mlp_call(
            jnp.asarray(dp), flat, entry.spec, self.tile_n,
            kops._round_up(entry.spec.base, kops.LANE), entry.card_pads(),
            emit_codes=True, interpret=self.interpret,
            vmem_limit_bytes=kops.kernel_vmem_limit(self.interpret),
        )
        return InferTicket(n=keys.shape[0], tasks=tasks, path="pallas_digits",
                           keys=keys, want_exists=want_exists,
                           codes_dev=outs, in_cap=in_cap)

    def _dispatch_jit_digits(self, keys, tasks, entry, bucket, want_exists):
        from repro.core import trainer as trainer_lib  # local: trainer imports us

        dp, in_cap = self._host_digits(keys, bucket)
        self.stats.bump("jit_calls")
        self.stats.note_compile(("jit_digits", entry.spec, bucket))
        codes = trainer_lib.predict_codes_jit(
            entry.params, jnp.asarray(dp), entry.spec
        )
        return InferTicket(n=keys.shape[0], tasks=tasks, path="jit_digits",
                           keys=keys, want_exists=want_exists,
                           codes_dev=codes, in_cap=in_cap)

    def collect(self, ticket: InferTicket):
        """Block on a ticket -> ``(codes (n, m) int32, exists | None)``.
        ``exists`` is a bool array ONLY when the fused kernel computed
        it on-device; on every other path it is None and the caller
        runs (and times) the host ``BitVector.test`` itself — keeping
        the existence stage visible in per-stage stats.

        Where the ticket carries the device ``T_aux`` override
        (``aux_dev``, which a caller whose view went stale clears), the
        codes are the corrected ones and ``ticket.aux_hit`` is filled;
        otherwise they are the model's."""
        n = ticket.n
        if ticket.path == "empty":
            return np.zeros((n, len(ticket.tasks)), dtype=np.int32), None

        with obs.span("engine.wait") as span:
            codes_dev, aux = ticket.codes_dev, ticket.aux_dev
            if aux is not None:
                codes_dev = aux[0]
            elif ticket.path in ("pallas_digits", "fused_streamed"):
                # one (n_pad, k) block per kernel output or page, spec order
                codes_dev = tuple(codes_dev)
            # every output the host reads, copied out together
            host = jax.device_get((codes_dev, ticket.exists_dev, ticket.match_dev,
                                   aux[1] if aux is not None else None))
            codes_h, exists_h, match_h, aux_h = host
            if isinstance(codes_h, tuple):
                codes = np.concatenate([c[:n] for c in codes_h], axis=1)
            else:
                codes = codes_h[:n]
            if ticket.task_order and ticket.tasks != ticket.task_order:
                # requested projection order differs from spec canonical
                perm = [ticket.task_order.index(t) for t in ticket.tasks]
                codes = codes[:, perm]
            if not codes.flags.writeable:
                codes = codes.copy()  # callers patch the aux override in place
            if ticket.in_cap is not None and not ticket.in_cap.all():
                codes[~ticket.in_cap] = 0

            exists = None
            if exists_h is not None:
                exists = exists_h[:n].astype(bool)
            if match_h is not None:
                ticket.match = match_h[:n].astype(bool)
            if aux_h is not None:
                ticket.aux_hit = aux_h[:n]
        ticket.wait_s = span.seconds
        return codes, exists

    # ------------------------------------------------------- convenience
    def stream(
        self,
        chunks,
        tasks: Optional[Tuple[str, ...]] = None,
        want_exists: bool = False,
        depth: int = PIPELINE_DEPTH,
    ):
        """Windowed dispatch/collect over an iterable of key chunks —
        the engine-level morsel pipeline the store hooks and the
        streaming executor build on.  Chunk *i+1*'s device work is
        enqueued before chunk *i*'s result is copied out, with at most
        ``depth`` chunks resident on device.  Yields
        ``(ticket, codes, exists)`` per chunk in input order
        (``exists`` is None unless the fused path computed it)."""
        tasks = self.spec.tasks if tasks is None else tuple(tasks)
        pending: list = []
        for chunk in chunks:
            pending.append(self.dispatch(chunk, tasks, want_exists=want_exists))
            if len(pending) >= depth:
                t = pending.pop(0)
                codes, exists = self.collect(t)
                yield t, codes, exists
        for t in pending:
            codes, exists = self.collect(t)
            yield t, codes, exists

    def infer(
        self, keys: np.ndarray, tasks: Optional[Tuple[str, ...]] = None
    ) -> np.ndarray:
        """Codes for a key batch of any size: chunks of ``max_bucket``
        flow through the dispatch/collect pipeline (host copy-out of
        chunk *i* overlaps device inference of chunk *i+1*)."""
        keys = np.asarray(keys, dtype=np.int64)
        tasks = self.spec.tasks if tasks is None else tuple(tasks)
        n = keys.shape[0]
        out = np.zeros((n, len(tasks)), dtype=np.int32)
        if n == 0 or not tasks:
            return out
        chunks = (
            keys[start : start + self.max_bucket]
            for start in range(0, n, self.max_bucket)
        )
        start = 0
        for ticket, codes, _ in self.stream(chunks, tasks):
            out[start : start + ticket.n] = codes
            start += ticket.n
        return out


class EngineCache:
    """Store -> engine map with ONE shared :class:`EngineStats`.

    A sharded cluster attaches this to every shard so (a) compile
    signatures dedupe cluster-wide — same architecture + bucket = one
    XLA program — and (b) operators read one counter set for the whole
    fleet.  Weak keys: dropping a shard drops its engine."""

    def __init__(self) -> None:
        self.stats = EngineStats()
        self._engines: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()  # guarded-by: _lock
        self._lock = threading.Lock()

    def engine_for(self, store) -> InferenceEngine:
        eng = self._engines.get(store)
        if eng is None:
            with self._lock:
                eng = self._engines.get(store)
                if eng is None:
                    eng = InferenceEngine.for_store(store, stats=self.stats)
                    self._engines[store] = eng
        return eng

    def adopt(self, store) -> InferenceEngine:
        """Bind ``store``'s engine into this cache.  A store that
        already owns an engine (e.g. warm from build) keeps its weight
        cache and just switches to the shared stats; otherwise a fresh
        engine is attached."""
        eng = getattr(store, "_engine", None)
        if eng is not None:
            eng.stats = self.stats
            with self._lock:
                self._engines[store] = eng
            return eng
        eng = self.engine_for(store)
        store.attach_engine(eng)
        return eng
