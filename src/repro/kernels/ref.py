"""Pure-jnp oracles for every kernel in this package.

These define the mathematical contract: kernels must ``allclose`` these
over shape/dtype sweeps (see ``tests/test_kernels.py``).
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.core.encoding import onehot_digits
from repro.core.model import MLPSpec, forward_digits


def ref_fused_mlp_logits(
    params: Dict, digits: jnp.ndarray, spec: MLPSpec
) -> Dict[str, jnp.ndarray]:
    """Oracle for the fused kernel's logits: the plain model forward."""
    return forward_digits(params, digits, spec)


def ref_fused_mlp_codes(params: Dict, digits: jnp.ndarray, spec: MLPSpec) -> jnp.ndarray:
    logits = forward_digits(params, digits, spec)
    return jnp.stack(
        [jnp.argmax(logits[t], axis=-1).astype(jnp.int32) for t in spec.tasks], axis=1
    )


def ref_fused_lookup(params: Dict, keys, encoder, vexist, spec: MLPSpec):
    """Oracle for the fused key->codes+exists kernel: host digit
    featurization + plain model forward + host BitVector test — the
    seed repo's staged reference path.  Returns ``(codes (n, m) int32
    numpy, exists (n,) bool numpy)``; out-of-capacity rows carry code 0
    (the ``_infer_codes`` zero-fill contract)."""
    import numpy as np

    keys = np.asarray(keys, dtype=np.int64)
    codes = np.zeros((keys.shape[0], len(spec.tasks)), dtype=np.int32)
    in_cap = (keys >= 0) & (keys < encoder.capacity)
    idx = np.flatnonzero(in_cap)
    if idx.size:
        digits = jnp.asarray(encoder.digits(keys[idx]))
        codes[idx] = np.asarray(ref_fused_mlp_codes(params, digits, spec))
    return codes, vexist.test(keys)


def ref_bitvector_test(words: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """words (n_words,) uint32 packed LSB-first; keys (n,) int32."""
    w = words[keys >> 5]
    return ((w >> (keys & 31).astype(jnp.uint32)) & jnp.uint32(1)).astype(jnp.int32)


def ref_onehot_first_layer(
    w3: jnp.ndarray, b: jnp.ndarray, digits: jnp.ndarray
) -> jnp.ndarray:
    """Oracle for the in-VMEM one-hot gather-matmul: materialized one-hot
    times the flattened weight."""
    base = w3.shape[1]
    oh = onehot_digits(digits, base)
    return oh @ w3.reshape(-1, w3.shape[-1]) + b
