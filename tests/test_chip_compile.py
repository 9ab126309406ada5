"""Compile the lookup hot path for a described TPU v5e, with no chip.

The TPU compiler refuses kernels that interpret mode runs happily: a
vector gather Mosaic cannot lower, a shape cast it does not support, a
kernel that needs more VMEM than its scoped limit.  Each test here
compiles one program of the main path at the paper's widths for a
``v5e:2x2`` topology described in the ``topo`` fixture — the topology
is never described at import time, so every pytest-xdist worker
collects the same tests and only the one that runs this file loads the
TPU library.  Nothing runs: these tests say nothing about results.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.cluster import mesh_scatter
from repro.core.encoding import KeyEncoder
from repro.core.model import MLPSpec, init_params
from repro.kernels import fused_mlp as fm
from repro.kernels import ops as kops

TILE = kops.DEFAULT_TILE_N
BATCH = 4 * TILE
#: Paper defaults (DeepMappingConfig) over a 1.5M-row key domain.
SHARED = (256, 256)
PRIVATE = (64,)
CARDS = (3, 7, 50, 1000)
MAX_KEY = 1_500_000


@pytest.fixture(scope="module")
def topo():
    """The described ``v5e:2x2`` topology, with JAX's persistent cache
    off: a compile for a described chip is written to it but cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shared=SHARED, private=PRIVATE, cards=CARDS):
    return MLPSpec(
        base=10,
        width=KeyEncoder(MAX_KEY).width,
        shared=shared,
        private={f"t{i}": private for i in range(len(cards))},
        out_cards={f"t{i}": c for i, c in enumerate(cards)},
    )


def _flat(spec, sharding):
    """Shapes of the engine's padded flat weights (no arrays made)."""
    shapes = jax.eval_shape(
        lambda: kops.pad_flat_weights(init_params(spec), spec)[0]
    )
    return tuple(
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
        for s in shapes
    )


def _lookup_args(spec, sharding, with_words=True, n_preds=0):
    keys = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=sharding)
    words = (
        jax.ShapeDtypeStruct((MAX_KEY // 32 + 1,), jnp.uint32, sharding=sharding)
        if with_words else None
    )
    cards = spec.card_map
    tables = tuple(
        jax.ShapeDtypeStruct(
            (kops._round_up(cards[t], kops.LANE),), jnp.int32, sharding=sharding
        )
        for t in spec.tasks[:n_preds]
    )
    return keys, words, _flat(spec, sharding), tables


def _compile_lookup(spec, sharding, vmem_limit=None, **kw):
    keys, words, flat, tables = _lookup_args(spec, sharding, **kw)
    enc = KeyEncoder(MAX_KEY)
    return fm.fused_lookup_call.lower(
        keys, words, flat, spec, TILE, kops.LANE, enc.position_ops(),
        enc.capacity, False,
        pred_tables=tables, pred_tasks=tuple(range(len(tables))),
        vmem_limit_bytes=vmem_limit,
    ).compile()


def test_fused_lookup_with_exists_and_predicates(one_chip):
    text = _compile_lookup(_spec(), one_chip, n_preds=2).as_text()
    assert "tpu_custom_call" in text
    # the module keeps the jitted name; the kernel's op takes its own
    assert text.startswith("HloModule jit_fused_lookup_call,")
    assert "%fused_lookup.1 = " in text


def test_fused_lookup_q12_selection(one_chip):
    """TPC-H Q12's lineitem selection at SF1: the four heads it reads
    (l_shipmode's 7 modes, three dates of up to 2,557 days) over the
    existence words of a 48M-slot key domain (``orderkey * 8 +
    linenumber``), with three predicate tables, two on one head."""
    max_key = 6_000_000 * 8 + 7
    spec = MLPSpec(
        base=10, width=KeyEncoder(max_key).width, shared=SHARED,
        private={t: PRIVATE for t in ("mode", "ship", "commit", "receipt")},
        out_cards={"mode": 7, "ship": 2526, "commit": 2466, "receipt": 2557},
    )
    tasks = (spec.tasks.index("mode"),) + (spec.tasks.index("receipt"),) * 2
    keys = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    words = jax.ShapeDtypeStruct((max_key // 32 + 1,), jnp.uint32, sharding=one_chip)
    tables = tuple(
        jax.ShapeDtypeStruct(
            (kops._round_up(spec.card_map[spec.tasks[i]], kops.LANE),), jnp.int32,
            sharding=one_chip,
        )
        for i in tasks
    )
    enc = KeyEncoder(max_key)
    text = fm.fused_lookup_call.lower(
        keys, words, _flat(spec, one_chip), spec, TILE, kops.LANE,
        enc.position_ops(), enc.capacity, False,
        pred_tables=tables, pred_tasks=tasks,
    ).compile().as_text()
    assert "tpu_custom_call" in text and "%fused_lookup.1 = " in text


def test_fused_lookup_codes_only(one_chip):
    """The ``fused_streamed`` pages past the first."""
    text = _compile_lookup(_spec(), one_chip, with_words=False).as_text()
    assert "tpu_custom_call" in text


def _compile_mlp_codes(spec, sharding, vmem_limit=None):
    digits = jax.ShapeDtypeStruct((BATCH, spec.width), jnp.int32, sharding=sharding)
    card_pads = tuple(
        (t, kops._round_up(c, kops.LANE)) for t, c in spec.card_map.items()
    )
    return fm.fused_mlp_call.lower(
        digits, _flat(spec, sharding), spec, TILE, kops.LANE, card_pads,
        True, False, vmem_limit_bytes=vmem_limit,
    ).compile().as_text()


def test_fused_mlp_codes(one_chip):
    """The ``pallas_digits`` tier."""
    text = _compile_mlp_codes(_spec(), one_chip)
    assert "tpu_custom_call" in text and "%fused_mlp" in text


def _largest_admitted(grow):
    """``grow(n)`` -> spec; the spec at the largest ``n`` whose
    :func:`~repro.kernels.ops.resident_bytes` the v5e budget admits."""
    budget = kops._TPU_VMEM_BUDGETS["TPU v5 lite"]
    n = 1
    while kops.resident_bytes(grow(n + 1), TILE) <= budget:
        n += 1
    return grow(n)


@pytest.mark.parametrize("shape", ["wide_trunk", "wide_heads"])
def test_largest_admitted_model_compiles(one_chip, shape):
    """The largest model the v5e budget admits compiles, in both
    resident kernels, under the scoped VMEM limit the engine hands
    them: the paper's shape with the widest trunk, and with the most
    4000-way heads behind 256-wide private layers.  The budget is read
    from the table: under ``JAX_PLATFORMS=cpu`` ``vmem_budget_bytes()``
    returns the CPU default."""
    budget = kops._TPU_VMEM_BUDGETS["TPU v5 lite"]
    if shape == "wide_trunk":
        spec = _largest_admitted(lambda n: _spec(shared=(n * kops.LANE,) * 2))
    else:
        spec = _largest_admitted(
            lambda n: _spec(private=(256,), cards=(4000,) * n)
        )
    assert kops.resident_bytes(spec, TILE) <= budget
    text = _compile_lookup(spec, one_chip, vmem_limit=budget, n_preds=1).as_text()
    assert "tpu_custom_call" in text
    assert "tpu_custom_call" in _compile_mlp_codes(spec, one_chip, budget)


def test_mesh_scatter_four_devices(topo):
    """``MeshShardRunner``'s one-launch scatter over 4 described chips,
    4 shards of the paper model: one shard per device, all-gathered."""
    k = 4
    mesh = jax.sharding.Mesh(np.array(topo.devices[:k]), ("shard",))
    stacked = NamedSharding(mesh, P("shard"))
    spec = _spec()
    params = jax.eval_shape(lambda: init_params(spec))
    flat = []
    for layer in params["shared"]:
        flat += [layer["w"], layer["b"]]
    for t in spec.tasks:
        for layer in params["heads"][t]["hidden"]:
            flat += [layer["w"], layer["b"]]
        out = params["heads"][t]["out"]
        pad = max(CARDS) - out["w"].shape[-1]  # fleet-max cardinality
        flat += [
            jax.ShapeDtypeStruct(out["w"].shape[:-1] + (out["w"].shape[-1] + pad,),
                                 jnp.float32),
            jax.ShapeDtypeStruct((out["b"].shape[0] + pad,), jnp.float32),
        ]

    def stack(shape, dtype):
        return jax.ShapeDtypeStruct((k, *shape), dtype, sharding=stacked)

    layout = mesh_scatter._Layout(
        base=spec.base, n_shared=len(SHARED),
        hidden=(len(PRIVATE),) * len(CARDS), n_tasks=len(CARDS),
    )
    fn = mesh_scatter._build_scatter_fn(mesh, layout, len(flat))
    text = fn.lower(
        stack((1024,), jnp.int32),                  # keys
        stack((spec.width,), jnp.int32),            # mods
        stack((spec.width,), jnp.int32),            # divs
        stack((), jnp.int32),                       # cap
        stack((), jnp.int32),                       # vcap
        stack((MAX_KEY // 32 // k + 1,), jnp.uint32),  # words
        stack((len(CARDS),), jnp.int32),            # cards
        *(stack(a.shape, a.dtype) for a in flat),
    ).compile().as_text()
    assert "all-gather" in text


@pytest.mark.parametrize("keys,rows,heads,pages", [
    (1_920_800, 1_870_000, 8, 1),    # customer_demographics' T_aux, every head (fused)
    (1_920_800, 1_870_000, 1, 1),    # a group-by's one head
    (48_000_008, 6_001_215, 10, 1),  # lineitem's T_aux, every head (jit_keys)
    (48_000_008, 6_001_215, 10, 3),  # the same from three streamed pages
])
def test_aux_correction_at_the_benchmark_sizes(one_chip, keys, rows, heads, pages):
    """The device ``T_aux`` override over a device copy of the resident
    view at the benchmark relations' sizes: a module of its own, that
    gathers only the wanted heads' codes (its scratch holds no copy of
    the view)."""
    from repro.core.aux_table import _aux_correct

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    batch = 1 << 16
    cuts = [heads * i // pages for i in range(pages + 1)]
    codes = tuple(arg((batch, b - a)) for a, b in zip(cuts, cuts[1:]))
    nbits = keys // 32 + 2
    compiled = _aux_correct.lower(
        codes if pages > 1 else codes[0], arg((batch,)), arg((nbits,), jnp.uint32),
        arg((nbits,)), tuple(arg((rows,)) for _ in range(heads)),
    ).compile()
    assert compiled.as_text().startswith("HloModule jit__aux_correct,")
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows
