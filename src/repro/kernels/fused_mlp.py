"""Fused multi-task MLP inference kernel (pl.pallas_call + BlockSpec).

TPU adaptation of the paper's ONNX-on-GPU batch inference: all model
weights stay resident in VMEM across the batch (mapping models are
small — KBs to a few MB); the grid walks batch tiles, so activations
make exactly ONE HBM round trip instead of one per layer.  The one-hot
encoding of key digits is materialized per-tile in VMEM as an
(TILE_N, base) compare-with-iota and immediately consumed by the MXU —
it never exists in HBM (DESIGN.md §3).

Two entrypoints share the forward body:

* ``fused_mlp_call``    — digits in, logits/codes out (the original
  kernel; still the reference-shaped staged path).
* ``fused_lookup_call`` — RAW int32 keys in, per-task argmax codes AND
  existence bits out.  Digit/residue decomposition happens in-kernel
  from per-position ``(modulus, divisor)`` compile-time constants, so the
  HBM input shrinks from ``(N, width)`` int32 to ``(N, 1)`` keys; the
  existence-word and predicate-table gathers run beside the kernel in
  the same jitted program (Algorithm 1 lines 3+5 in one dispatch).

Layout contract (enforced by ops.py):
* every dense dimension padded to multiples of 128 (MXU lane width);
* batch tiles of ``tile_n`` rows (multiple of 8, default 256);
* rank-3 first-layer weights are (width, base_pad, h_pad);
* with ``emit_codes=True`` each head reduces to int32 argmax codes
  in-kernel (padded logit columns masked to -inf), shrinking the HBM
  write from O(Σ cards) floats to one int32 per task per row;
* ``vmem_limit_bytes`` (from ``ops.kernel_vmem_limit``) raises the
  compiler's scoped VMEM limit to what the residency budget admits.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.model import MLPSpec


def _compiler_params(vmem_limit_bytes: Optional[int]):
    """Mosaic params raising the scoped VMEM limit (None: compiler
    default; interpret mode ignores them)."""
    if vmem_limit_bytes is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))


def _plan(spec: MLPSpec) -> Tuple[List[str], Dict[str, List[str]]]:
    """Layer kinds for trunk and heads: 'embed' (rank-3 from input) or
    'dense'."""
    trunk = ["embed" if i == 0 else "dense" for i in range(len(spec.shared))]
    heads = {}
    priv = spec.private_map
    for t in spec.tasks:
        kinds = []
        first = len(trunk) == 0
        for _ in priv[t]:
            kinds.append("embed" if first else "dense")
            first = False
        kinds.append("embed_out" if first else "dense_out")
        heads[t] = kinds
    return trunk, heads


def _apply_embed(w_ref, b_ref, digits, base_pad):
    """One-hot-in-VMEM gather-matmul: sum_p onehot(d_p) @ W[p]."""
    width = w_ref.shape[0]
    acc = None
    iota = jax.lax.broadcasted_iota(jnp.int32, (digits.shape[0], base_pad), 1)
    for p in range(width):
        onehot = (digits[:, p][:, None] == iota).astype(w_ref.dtype)
        part = jnp.dot(onehot, w_ref[p], preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc + b_ref[...]


def _forward_tile(
    digits,
    w_refs,
    spec: MLPSpec,
    trunk_kinds,
    head_kinds,
    base_pad: int,
    emit_codes: bool,
) -> List[jnp.ndarray]:
    """Whole-model forward on one batch tile: per-task codes (n, 1)
    int32 when ``emit_codes`` else logits (n, card_pad).  Shared by the
    digits-in and keys-in kernels so both paths compute bit-identical
    results."""
    cards = spec.card_map
    it = iter(w_refs)
    outs: List[jnp.ndarray] = []

    x = None
    for kind in trunk_kinds:
        w_ref, b_ref = next(it), next(it)
        if kind == "embed":
            x = _apply_embed(w_ref, b_ref, digits, base_pad)
        else:
            x = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32) + b_ref[...]
        x = jnp.maximum(x, 0.0)

    for t in spec.tasks:
        h = x
        for kind in head_kinds[t]:
            w_ref, b_ref = next(it), next(it)
            if kind == "embed":
                h = jnp.maximum(_apply_embed(w_ref, b_ref, digits, base_pad), 0.0)
            elif kind == "dense":
                h = jnp.maximum(
                    jnp.dot(h, w_ref[...], preferred_element_type=jnp.float32)
                    + b_ref[...],
                    0.0,
                )
            elif kind == "embed_out":
                h = _apply_embed(w_ref, b_ref, digits, base_pad)
            else:  # dense_out
                h = (
                    jnp.dot(h, w_ref[...], preferred_element_type=jnp.float32)
                    + b_ref[...]
                )
        if emit_codes:
            # mask padded logit columns, reduce to codes in-kernel
            card = cards[t]
            col = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
            masked = jnp.where(col < card, h, -jnp.inf)
            outs.append(jnp.argmax(masked, axis=-1).astype(jnp.int32)[:, None])
        else:
            outs.append(h)
    return outs


def make_fused_kernel(
    spec: MLPSpec,
    base_pad: int,
    card_pads: Dict[str, int],
    emit_codes: bool,
):
    """Build the kernel body for this model structure (static closure)."""
    trunk_kinds, head_kinds = _plan(spec)

    def kernel(digits_ref, *refs):
        n_heads = len(spec.tasks)
        out_refs = refs[len(refs) - n_heads :]
        w_refs = list(refs[: len(refs) - n_heads])
        outs = _forward_tile(
            digits_ref[...], w_refs, spec, trunk_kinds, head_kinds, base_pad,
            emit_codes,
        )
        for ti in range(n_heads):
            out_refs[ti][...] = outs[ti]

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "tile_n", "base_pad", "card_pads", "emit_codes", "interpret",
        "vmem_limit_bytes",
    ),
)
def fused_mlp_call(
    digits: jnp.ndarray,
    flat_weights: Tuple[jnp.ndarray, ...],
    spec: MLPSpec,
    tile_n: int,
    base_pad: int,
    card_pads: Tuple[Tuple[str, int], ...],
    emit_codes: bool,
    interpret: bool,
    vmem_limit_bytes: Optional[int] = None,
):
    """digits (N_pad, width) int32; flat_weights in plan order (padded).

    Returns tuple per task: (N_pad, 1) int32 codes if emit_codes else
    (N_pad, card_pad) float32 logits.
    """
    card_pads_d = dict(card_pads)
    n = digits.shape[0]
    if n % tile_n != 0:
        raise ValueError(f"batch size {n} must be a multiple of tile_n={tile_n}")
    grid = (n // tile_n,)
    kernel = make_fused_kernel(spec, base_pad, card_pads_d, emit_codes)

    in_specs = [pl.BlockSpec((tile_n, digits.shape[1]), lambda i: (i, 0))]
    for w in flat_weights:
        # weights are grid-invariant: whole tensor resident per step
        in_specs.append(pl.BlockSpec(w.shape, lambda i, nd=w.ndim: (0,) * nd))

    out_shapes, out_specs = [], []
    for t in spec.tasks:
        if emit_codes:
            out_shapes.append(jax.ShapeDtypeStruct((n, 1), jnp.int32))
            out_specs.append(pl.BlockSpec((tile_n, 1), lambda i: (i, 0)))
        else:
            cp = card_pads_d[t]
            out_shapes.append(jax.ShapeDtypeStruct((n, cp), jnp.float32))
            out_specs.append(pl.BlockSpec((tile_n, cp), lambda i: (i, 0)))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
        name="fused_mlp",
    )(digits, *flat_weights)


# --------------------------------------------------------------------------
# Fused key-encode + inference + existence (one dispatch lookup)
# --------------------------------------------------------------------------
def make_fused_lookup_kernel(
    spec: MLPSpec,
    base_pad: int,
    pos_ops: Tuple[Tuple[int, int], ...],
    capacity: int,
):
    """Kernel body answering Algorithm 1 line 3 from raw int32 keys.

    Per tile: decompose keys into digit/residue positions by the static
    ``(modulus, divisor)`` pairs, run the whole multi-task model, and
    argmax to codes.  Keys outside ``[0, capacity)`` are clamped to 0
    for the decomposition; :func:`fused_lookup_call` masks their codes.
    """
    trunk_kinds, head_kinds = _plan(spec)
    base = spec.base
    n_heads = len(spec.tasks)

    def kernel(keys_ref, *refs):
        w_refs = list(refs[: len(refs) - n_heads])
        out_refs = refs[len(refs) - n_heads :]
        keys = keys_ref[...]                       # (tile_n, 1)
        safe = jnp.where((keys >= 0) & (keys < capacity), keys, 0)

        # In-kernel digit/residue decomposition.  Every position is the
        # same three integer ops by compile-time constants (Mosaic
        # emulates division by a runtime scalar at several times the
        # code size); main digit positions carry modulus == capacity (a
        # no-op for keys already clamped into [0, capacity)).
        cols = [((safe % mod) // div) % base for mod, div in pos_ops]
        digits = jnp.concatenate(cols, axis=1)

        outs = _forward_tile(
            digits, w_refs, spec, trunk_kinds, head_kinds, base_pad,
            emit_codes=True,
        )
        for ti in range(n_heads):
            out_refs[ti][...] = outs[ti]

    return kernel


def _test_words(words32: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """(n,) int32 existence bits of int32 keys against packed uint32
    words.  Bits past ``BitVector.capacity`` are never set, so the
    word-domain mask alone reproduces ``BitVector.test`` byte-for-byte."""
    in_dom = (keys >= 0) & (
        jax.lax.shift_right_logical(keys, 5) < words32.shape[0]
    )
    sk = jnp.where(in_dom, keys, 0)
    w = jnp.take(words32, jax.lax.shift_right_logical(sk, 5), axis=0)
    bits = jnp.bitwise_and(
        jax.lax.shift_right_logical(w, jnp.bitwise_and(sk, 31).astype(jnp.uint32)),
        jnp.uint32(1),
    )
    return bits.astype(jnp.int32) * in_dom.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "tile_n", "base_pad", "pos_ops", "capacity", "interpret",
        "pred_tasks", "vmem_limit_bytes",
    ),
)
def fused_lookup_call(
    keys: jnp.ndarray,
    words32,
    flat_weights: Tuple[jnp.ndarray, ...],
    spec: MLPSpec,
    tile_n: int,
    base_pad: int,
    pos_ops: Tuple[Tuple[int, int], ...],
    capacity: int,
    interpret: bool,
    pred_tables: Tuple[jnp.ndarray, ...] = (),
    pred_tasks: Tuple[int, ...] = (),
    vmem_limit_bytes: Optional[int] = None,
):
    """keys (N_pad,) int32; pos_ops ``((mod, div), …)`` per digit
    position (``KeyEncoder.position_ops``); words32 (n_words32,) uint32, or None to skip the existence test;
    flat_weights in plan order (padded); pred_tables one padded int32
    0/1 vector per pushdown predicate, indexed by the code of head
    ``pred_tasks[j]``.

    Returns ``(codes, exists, match)``: codes (N_pad, num_tasks) int32;
    exists (N_pad,) int32 0/1 or None without ``words32``; match
    (N_pad,) int32 0/1 or None without ``pred_tables``.

    The model runs in the ``pallas_call``; the existence-word and
    predicate-table gathers run in the same jitted program around it
    (Mosaic lowers no 1-D vector gather), so a lookup is still one
    dispatch and one device round trip.  ``match = exists AND
    table_j[code(pred_tasks[j])]`` for all j — the conjunction the host
    filter evaluates — so pushdown plans leave the device with their
    filtering already done.
    """
    n = keys.shape[0]
    if n % tile_n != 0:
        raise ValueError(f"batch size {n} must be a multiple of tile_n={tile_n}")
    if pred_tables and words32 is None:
        raise ValueError("in-kernel predicate filtering requires existence words")
    kernel = make_fused_lookup_kernel(spec, base_pad, pos_ops, capacity)
    # Keys enter as an (N, 1) column: a 1-D block's tiling must match
    # XLA's layout of the whole array, which a (tile_n,) block does not.
    in_specs = [
        pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
    ]
    for w in flat_weights:
        in_specs.append(pl.BlockSpec(w.shape, lambda i, nd=w.ndim: (0,) * nd))
    outs = pl.pallas_call(
        kernel,
        grid=(n // tile_n,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((tile_n, 1), lambda i: (i, 0)) for _ in spec.tasks],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32) for _ in spec.tasks],
        compiler_params=_compiler_params(vmem_limit_bytes),
        interpret=interpret,
        name="fused_lookup",
    )(keys[:, None], *flat_weights)
    # Out-of-capacity keys answer code 0 (the ``_infer_codes`` contract).
    in_cap = (keys >= 0) & (keys < capacity)
    codes = jnp.where(in_cap[:, None], jnp.concatenate(outs, axis=1), 0)
    if words32 is None:
        return codes, None, None
    exists = _test_words(words32, keys)
    match = None
    if pred_tables:
        match = exists
        for table, ti in zip(pred_tables, pred_tasks):
            match = match * jnp.take(table, codes[:, ti], axis=0)
    return codes, exists, match
