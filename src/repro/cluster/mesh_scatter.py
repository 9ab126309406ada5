"""Multi-device shard scatter: K store shards on an N-device mesh.

The thread-pool fan-out in ``sharded_store`` overlaps per-shard *host*
halves, but every shard's device inference still runs through one
device queue.  When real devices exist (or virtual ones, via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), this module
maps the K shards onto a 1-D ``("shard",)`` mesh
(:func:`make_shard_mesh`) and answers a scattered
lookup batch in ONE ``shard_map`` launch: each device runs the model
forward + packed-word existence test for its ``ceil(K/N)`` shards
(``vmap`` over the local shard block), and an ``all_gather`` collects
every shard's codes + exist bits back to each host view.

Stacking contract (what makes one program serve K heterogeneous
shards): all shards share one architecture (same base / shared /
private dims / task set — guaranteed when the cluster was built from
one ``DeepMappingConfig``), while per-shard *sizes* differ and are
padded to fleet maxima:

* digit width   — extra positions get ``(mod=1, div=1)`` ops (digit 0)
  and zero first-layer weight rows, contributing nothing;
* head cardinality — extra logit columns are masked to ``-inf`` before
  the argmax, so a padded column can never win;
* existence words — zero-padded; in-domain keys never index the pad.

The host half of Algorithm 1 (existence fallback, aux merge, predicate
filter, decode) still runs per shard through the store's ordinary
collect path: the runner only replaces *device inference*, handing each
shard a precomputed :class:`~repro.core.inference.InferTicket`
(``path="mesh"``).  Retries after a failure re-dispatch through the
thread-pool path, so fault semantics are unchanged.  Byte-identity of
the full lookup vs the thread-pool path is enforced by the cluster
conformance suite (``tests/test_mesh_scatter.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.inference import INT32_MAX, InferTicket
from repro.kernels import bitvector as bv_kernel

#: Minimum padded batch length — one lane-ish tile, keeps tiny batches
#: from compiling one program per length.
MIN_BATCH_PAD = 128


def make_shard_mesh(n: int | None = None):
    """1-D ``("shard",)`` mesh over the host's devices — the cluster
    scatter lays K store shards out along this axis, ``ceil(K / n)`` per
    device.  ``n`` caps the device count (default: all devices,
    including ``xla_force_host_platform_device_count``-virtualized
    ones)."""
    n_dev = len(jax.devices())
    n = n_dev if n is None else max(1, min(int(n), n_dev))
    return jax.make_mesh((n,), ("shard",), axis_types=(jax.sharding.AxisType.Auto,))


@dataclasses.dataclass(frozen=True)
class _Layout:
    """Static structure of the stacked parameter list: how many
    ``(w, b)`` pairs belong to the trunk and to each head (spec task
    order).  Hashable so it can close into the jitted scatter fn."""

    base: int
    n_shared: int
    hidden: Tuple[int, ...]      # private layers per task, spec order
    n_tasks: int


def _apply_stacked(w, b, x, digits):
    """One dense layer, mirroring ``model._apply`` exactly (the gather
    path for rank-3 first-from-input layers, matmul otherwise) so the
    per-shard forward stays numerically aligned with the jit ladder."""
    if w.ndim == 3:
        gathered = jax.vmap(lambda wp, dp: wp[dp], in_axes=(0, 1))(w, digits)
        return gathered.sum(axis=0) + b
    return x @ w + b


def _one_shard(keys, mods, divs, cap, vcap, words, cards, flat, layout):
    """Fused key->codes->exists for ONE shard (vmapped over the local
    shard block inside the shard_map body).

    ``keys`` (B,) int32 with -1 sentinels; returns ``codes`` (B, m)
    int32 (out-of-capacity rows 0 — the ``_infer_codes`` contract) and
    ``exists`` (B,) int32 0/1 (the host ``BitVector.test`` contract).
    """
    in_cap = (keys >= 0) & (keys < cap)
    safe = jnp.where(in_cap, keys, 0)
    digits = (
        ((safe[:, None] % mods[None, :]) // divs[None, :]) % layout.base
    ).astype(jnp.int32)

    it = iter(flat)
    x = None
    for _ in range(layout.n_shared):
        w, b = next(it), next(it)
        x = jax.nn.relu(_apply_stacked(w, b, x, digits))
    codes_cols = []
    neg_inf = jnp.asarray(-jnp.inf, dtype=jnp.float32)
    for ti in range(layout.n_tasks):
        h = x
        for _ in range(layout.hidden[ti]):
            w, b = next(it), next(it)
            h = jax.nn.relu(_apply_stacked(w, b, h, digits))
        w, b = next(it), next(it)
        logits = _apply_stacked(w, b, h, digits)
        # Mask the cardinality pad: a zero-weight padded column must
        # never beat a real (possibly negative) logit.
        col = jnp.arange(logits.shape[-1], dtype=jnp.int32)
        logits = jnp.where(col[None, :] < cards[ti], logits, neg_inf)
        code = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        codes_cols.append(jnp.where(in_cap, code, 0))
    codes = jnp.stack(codes_cols, axis=1)

    # vcap is the *inclusive* top key (capacity - 1, int32-safe even
    # for the 2^31-slot edge the fused tier also supports).
    in_dom = (keys >= 0) & (keys <= vcap)
    safe2 = jnp.where(in_dom, keys, 0)
    word = jnp.take(words, jax.lax.shift_right_logical(safe2, 5), axis=0)
    bit = jnp.bitwise_and(
        jax.lax.shift_right_logical(
            word, jnp.bitwise_and(safe2, 31).astype(jnp.uint32)
        ),
        jnp.uint32(1),
    )
    exists = jnp.where(in_dom, bit.astype(jnp.int32), 0)
    return codes, exists


def _build_scatter_fn(mesh, layout: _Layout, n_flat: int):
    """jitted ``shard_map`` program: shard-axis-stacked inputs in,
    all-gathered (replicated) codes + exists out."""

    def body(keys, mods, divs, cap, vcap, words, cards, *flat):
        def per_shard(k, m, d, c, v, w, cd, *fl):
            return _one_shard(k, m, d, c, v, w, cd, fl, layout)

        codes, exists = jax.vmap(per_shard)(
            keys, mods, divs, cap, vcap, words, cards, *flat
        )
        codes = jax.lax.all_gather(codes, "shard", axis=0, tiled=True)
        exists = jax.lax.all_gather(exists, "shard", axis=0, tiled=True)
        return codes, exists

    in_specs = (P("shard"),) * (7 + n_flat)
    # check_vma=False: a tiled all_gather's output is typed as varying
    # over "shard", so the checker rejects the (correct) replicated P()
    # out_specs without it.
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
            check_vma=False,
        )
    )


def _pow2_at_least(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


class MeshShardRunner:
    """Device-parallel inference for a shard fleet.

    Build via :meth:`maybe_build` (returns None when the mesh path
    cannot apply); per lookup call :meth:`run` with the router's
    scattered batches — it returns per-shard ``(codes, exists)`` host
    arrays, or None when the fleet drifted out of eligibility (retrain
    changed a shard's architecture, a shard was quarantined) and the
    caller should fall back to the thread-pool path.
    """

    def __init__(self, shards: Sequence, mesh, n_dev: int):
        self.shards = list(shards)
        self.mesh = mesh
        self.n_dev = int(n_dev)
        self.k = len(self.shards)
        self.k_pad = -(-self.k // self.n_dev) * self.n_dev
        self._stacked = None          # (version, layout, dict of arrays)
        self._fn = None               # jitted scatter fn (per layout)
        # stacked inputs live split along the shard axis, one block per
        # device, so a launch moves only the key block
        self._sharding = NamedSharding(mesh, P("shard"))

    # ------------------------------------------------------------ build
    @classmethod
    def maybe_build(cls, shards: Sequence) -> Optional["MeshShardRunner"]:
        n_dev = len(jax.devices())
        if n_dev < 2 or len(shards) < 2:
            return None
        if not all(cls._shard_eligible(s) for s in shards):
            return None
        first = shards[0].spec
        for s in shards[1:]:
            sp = s.spec
            if (
                sp.tasks != first.tasks
                or sp.base != first.base
                or sp.shared != first.shared
                or sp.private != first.private
                or sp.dtype != first.dtype
            ):
                return None
        mesh = make_shard_mesh()
        return cls(shards, mesh, n_dev)

    @staticmethod
    def _shard_eligible(s) -> bool:
        # The scatter runs the jit ladder's XLA forward.  On the TPU the
        # Pallas tiers round differently, and a shard's T_aux corrects
        # only the codes of the tiers that built it, so ``use_pallas``
        # shards stay on their own engines.
        return (
            getattr(s, "vexist", None) is not None
            and getattr(s, "params", None) is not None
            and hasattr(s, "engine")
            and not s.engine.use_pallas
            and s.encoder.capacity <= INT32_MAX
            and s.vexist.capacity <= INT32_MAX + 1
            and s.spec.dtype == "float32"
        )

    # ---------------------------------------------------------- stacking
    def stacked_devices(self) -> set:
        """Devices holding the stacked weights and words (empty before
        the first launch)."""
        if self._stacked is None:
            return set()
        return {
            d for a in jax.tree.leaves(self._stacked[2])
            for d in a.sharding.device_set
        }

    def _version(self) -> tuple:
        return tuple((id(s.params), s.vexist.version) for s in self.shards)

    def _stack(self):
        """(Re)build the stacked device arrays when any shard's params
        or bitvector moved.  Returns ``(layout, arrays)`` or None when
        the fleet is no longer stackable (fall back upstream)."""
        version = self._version()
        if self._stacked is not None and self._stacked[0] == version:
            return self._stacked[1], self._stacked[2]
        shards = self.shards
        if not all(self._shard_eligible(s) for s in shards):
            return None
        first = shards[0].spec
        for s in shards[1:]:
            sp = s.spec
            if (
                sp.tasks != first.tasks
                or sp.base != first.base
                or sp.shared != first.shared
                or sp.private != first.private
            ):
                return None

        pos_ops = [tuple(s.engine._pos_ops) for s in shards]
        w_max = max(len(p) for p in pos_ops)
        mods = np.ones((self.k_pad, w_max), dtype=np.int32)
        divs = np.ones((self.k_pad, w_max), dtype=np.int32)
        for i, ops in enumerate(pos_ops):
            for j, (mod, div) in enumerate(ops):
                if mod > INT32_MAX or div > INT32_MAX:
                    return None  # top digit op overflows int32 math
                mods[i, j], divs[i, j] = mod, div
        cap = np.zeros(self.k_pad, dtype=np.int32)
        # inclusive top existing key: capacity - 1 fits int32 even at
        # the 2^31-slot edge (x64 is disabled, so no int64 in-graph)
        vcap = np.full(self.k_pad, -1, dtype=np.int32)
        cap[: self.k] = [s.encoder.capacity for s in shards]
        vcap[: self.k] = [s.vexist.capacity - 1 for s in shards]

        tasks = first.tasks
        cards_max = {
            t: max(s.spec.card_map[t] for s in shards) for t in tasks
        }
        cards = np.zeros((self.k_pad, len(tasks)), dtype=np.int32)
        for i, s in enumerate(shards):
            cards[i] = [s.spec.card_map[t] for t in tasks]

        words_list = [bv_kernel.pack_words32(s.vexist.words) for s in shards]
        nw_max = max(w.shape[0] for w in words_list)
        words = np.zeros((self.k_pad, nw_max), dtype=np.uint32)
        for i, w in enumerate(words_list):
            words[i, : w.shape[0]] = w

        def stack_layer(select, pad_axis=None, pad_to=0):
            """Stack one (w, b) across shards, zero-padding ``w`` along
            ``pad_axis`` (0 = width rows, -1 = cardinality columns)."""
            ws = [np.asarray(select(s)["w"], dtype=np.float32) for s in shards]
            bs = [np.asarray(select(s)["b"], dtype=np.float32) for s in shards]
            if pad_axis is not None:
                padded = []
                for w in ws:
                    if w.shape[pad_axis] < pad_to:
                        pad = [(0, 0)] * w.ndim
                        pad[pad_axis] = (0, pad_to - w.shape[pad_axis])
                        w = np.pad(w, pad)
                    padded.append(w)
                ws = padded
                if pad_axis in (-1, ws[0].ndim - 1):
                    bs = [
                        np.pad(b, (0, pad_to - b.shape[0]))
                        if b.shape[0] < pad_to else b
                        for b in bs
                    ]
            shapes = {w.shape for w in ws}
            if len(shapes) != 1:
                return None
            w_stack = np.stack(ws + [ws[0]] * (self.k_pad - self.k))
            b_stack = np.stack(bs + [bs[0]] * (self.k_pad - self.k))
            return w_stack, b_stack

        flat: List[np.ndarray] = []
        n_shared = len(first.shared)
        for li in range(n_shared):
            pair = stack_layer(
                lambda s, li=li: s.params["shared"][li],
                pad_axis=0 if li == 0 else None, pad_to=w_max,
            )
            if pair is None:
                return None
            flat.extend(pair)
        hidden = []
        for t in tasks:
            n_hidden = len(first.private_map[t])
            hidden.append(n_hidden)
            for li in range(n_hidden):
                pair = stack_layer(
                    lambda s, t=t, li=li: s.params["heads"][t]["hidden"][li],
                    pad_axis=0 if n_shared == 0 and li == 0 else None,
                    pad_to=w_max,
                )
                if pair is None:
                    return None
                flat.extend(pair)
            first_from_input = n_shared == 0 and n_hidden == 0
            pair = stack_layer(
                lambda s, t=t: s.params["heads"][t]["out"],
                pad_axis=0 if first_from_input else -1,
                pad_to=w_max if first_from_input else cards_max[t],
            )
            if pair is None:
                return None
            if first_from_input:
                # rank-3 out layer also needs its cardinality padded
                w_stack, b_stack = pair
                cpad = cards_max[t] - w_stack.shape[-1]
                if cpad:
                    w_stack = np.pad(
                        w_stack, [(0, 0)] * (w_stack.ndim - 1) + [(0, cpad)]
                    )
                    b_stack = np.pad(b_stack, [(0, 0), (0, cpad)])
                pair = (w_stack, b_stack)
            flat.extend(pair)

        layout = _Layout(
            base=first.base, n_shared=n_shared,
            hidden=tuple(hidden), n_tasks=len(tasks),
        )
        arrays = jax.device_put(
            {
                "mods": mods, "divs": divs, "cap": cap, "vcap": vcap,
                "words": words, "cards": cards, "flat": tuple(flat),
            },
            self._sharding,
        )
        if self._stacked is None or self._stacked[1] != layout:
            self._fn = None  # layout changed: rebuild the scatter program
        self._stacked = (version, layout, arrays)
        obs.counter(
            "deepmap_mesh_stack_total",
            "Mesh scatter weight/word (re)stackings.",
        ).inc()
        return layout, arrays

    # -------------------------------------------------------------- run
    def run(
        self, batches: Sequence
    ) -> Optional[Dict[int, Tuple[np.ndarray, np.ndarray]]]:
        """One scattered lookup: ``batches`` are the router's per-shard
        key batches.  Returns ``{shard_id: (codes (n_pad, m) int32,
        exists (n_pad,) int32)}`` host-visible arrays (callers slice to
        the true batch length), or None on ineligibility."""
        stacked = self._stack()
        if stacked is None:
            return None
        layout, arrays = stacked
        if self._fn is None:
            self._fn = _build_scatter_fn(
                self.mesh, layout, len(arrays["flat"])
            )
        b_pad = _pow2_at_least(
            max(int(b.keys.shape[0]) for b in batches), MIN_BATCH_PAD
        )
        keys_blk = np.full((self.k_pad, b_pad), -1, dtype=np.int32)
        for b in batches:
            k = np.asarray(b.keys, dtype=np.int64)
            valid = (k >= 0) & (k <= INT32_MAX)
            keys_blk[b.shard_id, : k.shape[0]] = np.where(
                valid, k, -1
            ).astype(np.int32)
        codes, exists = self._fn(
            jax.device_put(keys_blk, self._sharding),
            arrays["mods"], arrays["divs"],
            arrays["cap"], arrays["vcap"], arrays["words"],
            arrays["cards"], *arrays["flat"],
        )
        obs.counter(
            "deepmap_mesh_scatter_total",
            "Scattered lookup batches answered via the device mesh.",
        ).inc()
        codes_np = np.asarray(codes)
        exists_np = np.asarray(exists)
        return {
            int(b.shard_id): (
                codes_np[b.shard_id], exists_np[b.shard_id]
            )
            for b in batches
        }

    def tickets(
        self, batches: Sequence
    ) -> Optional[Dict[int, InferTicket]]:
        """Run one scatter and wrap each shard's result as a ready
        :class:`InferTicket` (``path="mesh"``) for
        ``DeepMappingStore._dispatch_precomputed``."""
        results = self.run(batches)
        if results is None:
            return None
        out: Dict[int, InferTicket] = {}
        for b in batches:
            codes, exists = results[int(b.shard_id)]
            keys = np.asarray(b.keys, dtype=np.int64)
            out[int(b.shard_id)] = InferTicket(
                n=keys.shape[0],
                tasks=self.shards[b.shard_id].spec.tasks,
                path="mesh",
                keys=keys,
                want_exists=True,
                codes_dev=codes,
                exists_dev=exists,
                task_order=self.shards[b.shard_id].spec.tasks,
            )
        return out
