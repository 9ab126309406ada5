"""Existence-bitvector test kernel (Algorithm 1 line 5).

The packed words array (uint32) is VMEM-resident across the whole
batch (a 10^8-slot domain is ~12.5 MB — at the VMEM budget edge; the
ops wrapper falls back to the jnp path beyond it).  Each grid step
tests a tile of keys: ``bit = (words[k >> 5] >> (k & 31)) & 1``.

On GPU this would be a warp ballot; on TPU it is a vectorized
shift/mask over VREG lanes after a dynamic gather of the word array
(Mosaic lowers the 1-D ``jnp.take``).  int32 keys only — the wrapper
splits 64-bit domains.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def pack_words32(words) -> np.ndarray:
    """Contiguous uint32 view of a packed existence bit buffer (a
    ``BitVector.words`` array) — the word layout every device existence
    path consumes (``bit = (words[k >> 5] >> (k & 31)) & 1``): this
    module's kernel, the fused lookup kernel, and the mesh shard
    scatter.  One definition so the host packing can never drift from
    the kernels' indexing."""
    return np.ascontiguousarray(words).view(np.uint32)


def _kernel(keys_ref, words_ref, out_ref):
    keys = keys_ref[...]
    words = words_ref[...]
    word_idx = jax.lax.shift_right_logical(keys, 5)
    bit_idx = jnp.bitwise_and(keys, 31).astype(jnp.uint32)
    w = jnp.take(words, word_idx, axis=0)
    bits = jnp.bitwise_and(
        jax.lax.shift_right_logical(w, bit_idx), jnp.uint32(1)
    )
    out_ref[...] = bits.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def bitvector_call(
    keys: jnp.ndarray, words: jnp.ndarray, tile_n: int, interpret: bool
) -> jnp.ndarray:
    """keys (N_pad,) int32 in [0, 32*len(words)); words (n_words,) uint32.

    Returns (N_pad,) int32 0/1.
    """
    n = keys.shape[0]
    if n % tile_n != 0:
        raise ValueError(f"batch size {n} must be a multiple of tile_n={tile_n}")
    grid = (n // tile_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n,), lambda i: (i,)),
            pl.BlockSpec(words.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((tile_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
        name="bitvector_test",
    )(keys, words)
