"""repro — DeepMapping: learned data mapping for lossless compression and
efficient lookup, as a key-value and analytics store whose hot path runs
on the TPU.

Top-level entrypoints (lazy — see ``repro.api.entry``):

- ``repro.open(path)``          — load any saved store (sniffs
                                  single / sharded / baseline formats).
- ``repro.build(table, config)`` — build a single or sharded store.

Subpackages:

- ``repro.api``       — the ``MappingStore`` protocol + plan-based query
                        layer shared by every store implementation.
- ``repro.core``      — the paper's hybrid learned structure (model, T_aux,
                        V_exist, f_decode, MHAS search, modifications).
- ``repro.cluster``   — range/hash-sharded stores and the mesh scatter.
- ``repro.baselines`` — AB/ABC/HB/HBC comparison stores.
- ``repro.data``      — dataset generators.
- ``repro.serve``     — the batched lookup server.
- ``repro.kernels``   — Pallas TPU kernels for the lookup hot path.
- ``repro.storage``   — codecs and the partition memory pool.
- ``repro.fault``     — typed failures, retries, health and injection.
- ``repro.obs``       — metrics, spans and their exporters.

``repro.compile_cache`` points the entry points' persistent XLA cache at
the checkout.

Import of this package must stay side-effect free w.r.t. JAX device state:
never touch ``jax.devices()`` at import time, so a caller can still set
the platform or a virtual device count first.
"""

__version__ = "1.0.0"


def __getattr__(name):
    # Lazy so `import repro` never drags in JAX (keeps import
    # side-effect free for a caller's device pinning).
    if name in ("open", "build"):
        from repro.api import entry

        return getattr(entry, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
