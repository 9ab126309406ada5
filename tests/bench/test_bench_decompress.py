"""The readers of ``aux_parallel_share.lookup`` and
``aux_miss_share.lookup`` on hand-made inputs and their ``None`` cases,
and the ``lineitem_probe_overpool`` cell run on the CPU at a tiny size
with partitions small enough that a wave of pool misses holds two, so
the worker threads decompress."""

import json
import os
import tempfile

import pytest

import benchtiny
from bench import harness
from bench import reference as ref_lib
from bench import trace as trace_lib

SEED = 2**31 + 29  # seeds reach past 32 signed bits
READERS = os.path.join(benchtiny.REPO, "bench", "layer_metrics")
NEW_READERS = ("aux_parallel_share.lookup", "aux_miss_share.lookup")


def reader(name):
    return harness.load_module(os.path.join(READERS, name + ".py"))


@pytest.fixture(autouse=True)
def restore_compile_cache():
    """The harness turns on JAX's persistent cache inside its root;
    put the process's settings back for the tests that follow."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny tree with the over-pool pool, like the real one, at 1/8
    of ``T_aux`` decompressed (every row of the tiny lineitem is in
    ``T_aux``: 8 bytes of key and 4 a column), and 2,048-byte partitions
    (42 rows): half the pool holds two of them, as half the real pool
    holds 137 of its 131,072-byte partitions."""
    root = benchtiny.make_root(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "bench", "configs", "tpch_lineitem_sf1_overpool.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["store"]["aux_pool_bytes"] = cfg["rows"] * (8 + 4 * len(cfg["columns"])) // 8
    cfg["store"]["partition_bytes"] = 2048
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


@pytest.mark.parametrize("name,spans,value", [
    ("aux_parallel_share.lookup", {"serve.aux_parallel": 90, "serve.aux_decompressed": 120},
     75.0),
    ("aux_parallel_share.lookup", {"serve.aux_parallel": 0, "serve.aux_decompressed": 3},
     0.0),
    ("aux_miss_share.lookup", {"serve.aux_decompressed": 120, "serve.aux_visits": 160},
     75.0),
    ("aux_miss_share.lookup", {"serve.aux_decompressed": 0, "serve.aux_visits": 160}, 0.0),
])
def test_share_from_serve_stats(name, spans, value):
    assert reader(name).read({"spans": spans}) == pytest.approx(value)


@pytest.mark.parametrize("name,spans", [
    ("aux_parallel_share.lookup", {"serve.aux_decompressed": 10}),  # a program without it
    ("aux_parallel_share.lookup", {"serve.aux_parallel": 0, "serve.aux_decompressed": 0}),
    ("aux_miss_share.lookup", {"serve.aux_visits": 10}),            # a program without it
    ("aux_miss_share.lookup", {"serve.aux_decompressed": 0, "serve.aux_visits": 0}),
])
def test_share_none_without_its_counts(name, spans):
    """A program that counts nothing of the kind (the parent of these
    counters), or a window that decompressed or visited nothing."""
    assert reader(name).read({"spans": spans}) is None


def test_shares_read_from_a_window_of_the_program(root):
    spec = harness.load_cell(root, "lineitem_probe_overpool")
    cfg = spec["config"]
    keys, columns = spec["generator"].generate(cfg, SEED)
    ref = ref_lib.Reference(keys, columns)
    with tempfile.TemporaryDirectory() as d:
        store, _ = harness.build_store(cfg, ref.keys, ref.columns, d)
    loop = spec["loop"].Loop(spec["traffic"], store, ref, cfg, SEED)
    loop.warm_up(SEED)
    window = loop.run(0.3)
    ctx = {"spans": window.spans, "work": window.work, "dispatched": window.dispatched,
           "elapsed_s": window.elapsed_s}
    assert {m["name"] for m in spec["per_layer"]} >= set(NEW_READERS)
    spans = window.spans
    assert 0 < spans["serve.aux_parallel"] <= spans["serve.aux_decompressed"]
    assert spans["serve.aux_decompressed"] <= spans["serve.aux_visits"]
    assert spans["serve.aux_resident_keys"] == 0
    parallel = reader("aux_parallel_share.lookup").read(ctx)
    miss = reader("aux_miss_share.lookup").read(ctx)
    assert 0 < parallel <= 100 and 0 < miss <= 100
    assert parallel == pytest.approx(
        100 * spans["serve.aux_parallel"] / spans["serve.aux_decompressed"])


def test_traced_overpool_run_reports_both_shares(root, monkeypatch):
    """The harness's ``--trace 1`` path hands the new readers what they
    read.  The CPU trace has no device plane, so its reduction is
    stubbed; everything else runs as on the chip."""
    from repro import obs

    reduced = trace_lib.Reduced(window_s=1.0, busy_s=0.5, idle_share=0.5, modules={},
                                top_ops=[], idle_gaps=[])
    monkeypatch.setattr(trace_lib, "reduce", lambda flat, top=10: reduced)
    prev = obs.set_tracer(obs.Tracer())
    try:
        result = harness.run("lineitem_probe_overpool", SEED, 0.5, True, root=root,
                             require_tpu=False)
    finally:
        obs.set_tracer(prev)
    assert result["correct"] is True and result["failed"] == 0
    assert set(NEW_READERS) <= set(result["metrics"])
    assert all(result["metrics"][n]["unit"] == "%" for n in NEW_READERS)
    assert result["metrics"]["aux_parallel_share.lookup"]["value"] > 0
