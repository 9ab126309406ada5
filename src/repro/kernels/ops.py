"""Public jit'd wrappers around the Pallas kernels.

Responsibilities: MXU-alignment padding (zero-padding is exact for
dense+ReLU chains: padded inputs are zero, padded weight rows/cols are
zero, ReLU(0)=0 propagates), batch tiling, the VMEM residency budget
check, and interpret-mode selection (interpret on non-TPU backends so
the same tests run everywhere).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.model import MLPSpec
from repro.kernels import bitvector as bv_kernel
from repro.kernels import fused_mlp as fm_kernel

LANE = 128          # MXU lane width
DEFAULT_TILE_N = 256
#: Residency cap off the TPU.  Interpret-mode backends (cpu/gpu, see
#: ``_auto_interpret``) keep it so tier selection on CPU runs the same
#: eligibility ladder a small TPU budget would take.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: Per-``device_kind`` residency budgets, also handed to Mosaic as the
#: kernels' scoped VMEM limit (``vmem_limit_bytes``), so a model the
#: budget admits is a model the compiler accepts.  ``TPU v5 lite``
#: (v5e, 128 MiB VMEM per core): 64 MiB, half the core's VMEM.  Source:
#: compiles for a described ``v5e:2x2`` topology at ``tile_n=256``,
#: searching (in 0.25 MiB steps) the least limit under which both
#: ``fused_lookup_call`` and ``fused_mlp_call`` compile.  Every shape
#: needed less than its :func:`resident_bytes` (least / counted, MiB):
#: paper widths 2.75 / 6.2; shared 1024x1024 14.25 / 16.7; 2048x2048
#: 35.75 / 40.7; 2816x2816 (the widest trunk admitted) 54.25 / 64.0;
#: 16 heads of 1000 11.5 / 15.1; 8 heads of 4000 behind 256-wide
#: private layers 36.25 / 48.1, and 11 such heads (the most admitted)
#: 49.0 / 60.9.  ``tests/test_chip_compile.py`` compiles both admitted
#: maxima in both kernels.  The compiler's default scoped limit (16 MiB
#: on v5e) is what refused larger models before.
_TPU_VMEM_BUDGETS = {"TPU v5 lite": 64 * 1024 * 1024}


def _tpu_budget(device_kind: str) -> int:
    try:
        return _TPU_VMEM_BUDGETS[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM budget for TPU kind {device_kind!r}; add one to "
            f"repro.kernels.ops._TPU_VMEM_BUDGETS once its compiler has "
            f"accepted the largest model it admits"
        ) from None


def vmem_budget_bytes() -> int:
    """Resolved VMEM residency budget in bytes.

    Resolution order: ``REPRO_VMEM_BUDGET`` (env, always wins — also the
    hook the boundary tests use to pin exact budgets), then the TPU
    ``device_kind`` table (an unknown kind is an error), then
    :data:`VMEM_BUDGET_BYTES` off the TPU.  Re-read on every call: it is
    consulted at engine construction / eligibility time, not in the hot
    loop.
    """
    env = os.environ.get("REPRO_VMEM_BUDGET", "").strip()
    if env:
        return max(int(env), 1)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return VMEM_BUDGET_BYTES
    return _tpu_budget(dev.device_kind)


def vmem_limit_bytes() -> int:
    """Scoped VMEM limit for compiled kernels on this TPU: the device
    kind's budget, whatever ``REPRO_VMEM_BUDGET`` squeezes eligibility
    to."""
    return _tpu_budget(jax.devices()[0].device_kind)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def kernel_vmem_limit(interpret: Optional[bool]) -> Optional[int]:
    """The ``vmem_limit_bytes`` every compiled ``pallas_call`` of the
    lookup path gets: None in interpret mode, else this TPU's limit."""
    return None if _auto_interpret(interpret) else vmem_limit_bytes()


def _pad2(w: jnp.ndarray) -> jnp.ndarray:
    return jnp.pad(
        w,
        ((0, _round_up(w.shape[0], LANE) - w.shape[0]),
         (0, _round_up(w.shape[1], LANE) - w.shape[1])),
    )


def _pad_flat_weights(params: Dict, spec: MLPSpec) -> Tuple[Tuple[jnp.ndarray, ...], int]:
    """Flatten + pad weights in kernel plan order. Returns (flat, bytes)."""
    flat = []

    def add(layer):
        w, b = layer["w"], layer["b"]
        if w.ndim == 3:
            base_pad = _round_up(w.shape[1], LANE)
            h_pad = _round_up(w.shape[2], LANE)
            wp = jnp.pad(w, ((0, 0), (0, base_pad - w.shape[1]), (0, h_pad - w.shape[2])))
        else:
            wp = _pad2(w)
            h_pad = wp.shape[1]
        bp = jnp.pad(b, (0, h_pad - b.shape[0]))
        flat.append(wp.astype(jnp.float32))
        flat.append(bp.astype(jnp.float32))

    for layer in params["shared"]:
        add(layer)
    for t in spec.tasks:
        for layer in params["heads"][t]["hidden"]:
            add(layer)
        add(params["heads"][t]["out"])
    nbytes = sum(int(np.prod(x.shape)) * 4 for x in flat)
    return tuple(flat), nbytes


#: Public alias — the inference engine caches this call's result per
#: task subset so the hot path never re-pads (see repro.core.inference).
pad_flat_weights = _pad_flat_weights


def resident_parts(spec: MLPSpec) -> Tuple[int, Dict[str, int]]:
    """Shape-only VMEM bytes of the padded weights, split
    ``(trunk_bytes, {task: head_bytes})`` — the streaming page planner
    budgets the shared trunk once per page and packs heads greedily
    against the remainder.  Rank-3 first-from-input layers count twice:
    the kernel loads each position's ``(base_pad, h_pad)`` slice into a
    VMEM value for the MXU on top of the resident block."""

    def dense(in_dim: int, out_dim: int, embed: bool) -> int:
        o = _round_up(out_dim, LANE)
        if embed:  # rank-3 (width, base_pad, h_pad) + bias
            return 2 * spec.width * _round_up(spec.base, LANE) * o + o
        return _round_up(in_dim, LANE) * o + o

    trunk_total = 0
    d = None
    for h in spec.shared:
        trunk_total += dense(d or 0, h, embed=d is None)
        d = h
    trunk = d
    priv, cards = spec.private_map, spec.card_map
    heads: Dict[str, int] = {}
    for t in spec.tasks:
        d = trunk
        total = 0
        for h in priv[t]:
            total += dense(d or 0, h, embed=d is None)
            d = h
        total += dense(d or 0, cards[t], embed=d is None)
        heads[t] = total * 4  # fp32
    return trunk_total * 4, heads


def activation_bytes(spec: MLPSpec, tile_n: int) -> int:
    """Per-tile activation VMEM footprint: three live ``(tile_n, w)``
    fp32 values at the widest layer, logits included."""
    widths = [spec.feature_dim, *spec.shared, *spec.card_map.values()]
    for t, sizes in spec.private:
        widths.extend(sizes)
    return tile_n * _round_up(max(widths), LANE) * 4 * 3


def resident_bytes(spec: MLPSpec, tile_n: int) -> int:
    """VMEM the resident kernels need for ``spec`` at ``tile_n`` rows
    per tile — what the engine's tier eligibility compares to the
    budget.  From shapes alone: eligibility must not materialize (and
    cache) a padded device copy that the chosen path never uses."""
    trunk, heads = resident_parts(spec)
    return trunk + sum(heads.values()) + activation_bytes(spec, tile_n)


def plan_head_pages(
    spec: MLPSpec,
    tile_n: int,
    budget: Optional[int] = None,
) -> Optional[Tuple[Tuple[str, ...], ...]]:
    """Partition ``spec.tasks`` into consecutive head groups ("pages")
    that each fit the VMEM budget — the ``fused_streamed`` tier runs one
    :func:`fused_lookup` per page, so a model whose padded weights
    exceed the budget still takes the fused kernel instead of jit.

    Every page pays the shared trunk + activation overhead (the trunk
    is re-sent and recomputed per page).  Returns a tuple of task tuples
    covering ``spec.tasks`` in canonical order, or None when even a
    single head cannot fit on a fresh page — the caller falls back to
    the jit ladder.
    """
    budget = vmem_budget_bytes() if budget is None else int(budget)
    trunk_b, head_b = resident_parts(spec)
    base = trunk_b + activation_bytes(spec, tile_n)
    pages: list = []
    cur: list = []
    used = base
    for t in spec.tasks:
        hb = head_b[t]
        if cur and used + hb > budget:
            pages.append(tuple(cur))
            cur = []
            used = base
        if used + hb > budget:
            return None
        cur.append(t)
        used += hb
    pages.append(tuple(cur))
    return tuple(pages)


def check_vmem_budget(spec: MLPSpec, tile_n: int) -> None:
    """Raise if the resident kernel for ``spec`` exceeds the VMEM cap."""
    budget = vmem_budget_bytes()
    total = resident_bytes(spec, tile_n)
    if total > budget:
        raise ValueError(
            f"model too large for VMEM-resident fused kernel "
            f"({total / 2**20:.1f} MiB > "
            f"{budget / 2**20:.1f} MiB); use the streamed or jnp path"
        )


def _prep(digits: jnp.ndarray, tile_n: int) -> Tuple[jnp.ndarray, int]:
    n = digits.shape[0]
    n_pad = _round_up(max(n, tile_n), tile_n)
    dp = jnp.pad(digits.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    return dp, n


def fused_mlp_logits(
    params: Dict,
    spec: MLPSpec,
    digits: jnp.ndarray,
    tile_n: int = DEFAULT_TILE_N,
    interpret: Optional[bool] = None,
) -> Dict[str, jnp.ndarray]:
    """Per-task logits via the fused kernel. digits (n, width) int."""
    check_vmem_budget(spec, tile_n)
    flat, _ = _pad_flat_weights(params, spec)
    dp, n = _prep(digits, tile_n)
    cards = spec.card_map
    card_pads = tuple((t, _round_up(cards[t], LANE)) for t in spec.tasks)
    outs = fm_kernel.fused_mlp_call(
        dp, flat, spec, tile_n, _round_up(spec.base, LANE), card_pads,
        emit_codes=False, interpret=_auto_interpret(interpret),
        vmem_limit_bytes=kernel_vmem_limit(interpret),
    )
    return {t: o[:n, : cards[t]] for t, o in zip(spec.tasks, outs)}


def fused_mlp_codes(
    params: Dict,
    spec: MLPSpec,
    digits: jnp.ndarray,
    tile_n: int = DEFAULT_TILE_N,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(n, num_tasks) int32 argmax codes — Algorithm 1's inference output.
    The argmax happens in-kernel: HBM sees one int32 per task per row."""
    check_vmem_budget(spec, tile_n)
    flat, _ = _pad_flat_weights(params, spec)
    dp, n = _prep(digits, tile_n)
    cards = spec.card_map
    card_pads = tuple((t, _round_up(cards[t], LANE)) for t in spec.tasks)
    outs = fm_kernel.fused_mlp_call(
        dp, flat, spec, tile_n, _round_up(spec.base, LANE), card_pads,
        emit_codes=True, interpret=_auto_interpret(interpret),
        vmem_limit_bytes=kernel_vmem_limit(interpret),
    )
    return jnp.concatenate([o[:n] for o in outs], axis=1)


def fused_lookup(
    flat_weights: Tuple[jnp.ndarray, ...],
    spec: MLPSpec,
    keys_i32: jnp.ndarray,
    pos_ops: Tuple[Tuple[int, int], ...],
    words32: Optional[jnp.ndarray],
    capacity: int,
    tile_n: int = DEFAULT_TILE_N,
    interpret: Optional[bool] = None,
    pred_tables: Tuple[jnp.ndarray, ...] = (),
    pred_tasks: Tuple[int, ...] = (),
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """One-round-trip lookup kernel call: padded int32 keys in,
    ``(codes (N_pad, m) int32, exists (N_pad,) int32 | None,
    match (N_pad,) int32 | None)`` out.

    Unlike :func:`fused_mlp_codes` this takes ALREADY-padded device
    weights (the engine's per-task-subset cache), device-resident
    ``words32``, and an already bucket-padded key batch —
    the wrapper adds no per-call host work.  Caller slices padding off.

    ``words32=None`` drops the existence test — the ``fused_streamed``
    tier uses it for pages past the first.  ``pred_tables`` ships
    per-predicate boolean code tables (as padded int32 vectors);
    ``pred_tasks[j]`` names the head (index into ``spec.tasks``) whose
    code indexes table ``j``.  Match bits are the AND of the existence
    bit and every table lookup — predicate filtering requires
    ``words32``.
    """
    if keys_i32.shape[0] % tile_n != 0:
        raise ValueError(
            f"padded batch size {keys_i32.shape[0]} must be a multiple of "
            f"tile_n={tile_n}"
        )
    return fm_kernel.fused_lookup_call(
        keys_i32, words32, tuple(flat_weights), spec, tile_n,
        _round_up(spec.base, LANE), tuple(pos_ops), int(capacity),
        _auto_interpret(interpret),
        pred_tables=tuple(pred_tables), pred_tasks=tuple(pred_tasks),
        vmem_limit_bytes=kernel_vmem_limit(interpret),
    )


def bitvector_test(
    words64: np.ndarray,
    keys: jnp.ndarray,
    tile_n: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Existence bits for int keys against a packed uint64 word array
    (the BitVector runtime form). Returns (n,) bool.

    The kernel works on uint32 words.  The 64->32 split happens host-side
    (``.view``) — JAX without x64 would silently TRUNCATE uint64 on
    ``jnp.asarray``, losing every odd 32-bit word.
    """
    words32 = jnp.asarray(np.asarray(words64, dtype=np.uint64).view(np.uint32))
    n = keys.shape[0]
    n_pad = _round_up(max(n, tile_n), tile_n)
    kp = jnp.pad(keys.astype(jnp.int32), (0, n_pad - n))
    bits = bv_kernel.bitvector_call(
        kp, words32, tile_n, _auto_interpret(interpret)
    )
    return bits[:n].astype(bool)
