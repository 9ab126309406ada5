"""Pallas TPU kernels for the DeepMapping lookup hot path.

The paper's Algorithm 1 line 3 — batched inference of the multi-task
memorization MLP — dominates device time.  Kernels:

* ``fused_mlp``   — the WHOLE multi-task model (one-hot-free first layer,
  shared trunk computed once, every head) in a single VMEM-resident
  kernel; optionally emits argmax codes instead of logits so HBM writes
  are O(tasks) int32 per row instead of O(Σ card) floats.  Its
  ``fused_lookup_call`` variant takes RAW int32 keys — digit/residue
  decomposition happens in-kernel by compile-time constants — and runs
  the existence-bitvector test in the same jitted program, so Algorithm
  1 lines 3+5 are one dispatch (driven by
  ``repro.core.inference.InferenceEngine``).
* ``bitvector``   — standalone packed-word existence test (line 5).
* ``ref``         — pure-jnp oracles for all of the above.

``ops`` holds the jit'd public wrappers with MXU-alignment padding and
the VMEM-budget check.  Kernels are validated in ``interpret=True`` on
CPU.
"""

from repro.kernels.ops import (  # noqa: F401
    bitvector_test,
    fused_lookup,
    fused_mlp_codes,
    fused_mlp_logits,
    pad_flat_weights,
)
