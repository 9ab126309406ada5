"""The readers of ``aux_resident_share.{lookup,scan}`` on hand-made
inputs, their ``None`` cases, and the ``lineitem_probe_overpool`` cell
run end to end on the CPU at a tiny size, where the resident view never
engages."""

import json
import os
import tempfile

import pytest

import benchtiny
from bench import harness
from bench import reference as ref_lib

SEED = 2**31 + 23  # seeds reach past 32 signed bits
READERS = os.path.join(benchtiny.REPO, "bench", "layer_metrics")


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


@pytest.fixture()
def tracer():
    from repro import obs

    trc = obs.Tracer()
    prev = obs.set_tracer(trc)
    yield trc
    obs.set_tracer(prev)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny tree, with the over-pool configuration's pool cut, like
    the real one, to 1/8 of its ``T_aux`` decompressed (every row of the
    tiny lineitem is in ``T_aux``: 8 bytes of key and 4 a column)."""
    root = benchtiny.make_root(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "bench", "configs", "tpch_lineitem_sf1_overpool.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["store"]["aux_pool_bytes"] = cfg["rows"] * (8 + 4 * len(cfg["columns"])) // 8
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


def test_lookup_share_from_serve_stats():
    ctx = {"spans": {"serve.aux_resident_keys": 750, "serve.aux_keys": 1000}}
    assert reader("aux_resident_share.lookup").read(ctx) == pytest.approx(75.0)
    ctx["spans"]["serve.aux_resident_keys"] = 0
    assert reader("aux_resident_share.lookup").read(ctx) == 0.0


@pytest.mark.parametrize("spans", [
    {"serve.aux_keys": 1000},                                 # a program without the count
    {"serve.aux_resident_keys": 0, "serve.aux_keys": 0},      # no key probed
])
def test_lookup_share_none_without_its_counts(spans):
    assert reader("aux_resident_share.lookup").read({"spans": spans}) is None


def test_scan_share_from_the_ring(tracer):
    for i in range(3):   # three plans; the ring holds the last two whole
        tracer.add_span("plan", 10.0 * i, 10.0 * i + 5, track="plans")
        tracer.add_span("aux.get", 10.0 * i + 1, 10.0 * i + 2,
                        keys=100, visits=2, resident=100 * (i > 0))
        tracer.add_span("aux.get", 10.0 * i + 2, 10.0 * i + 3, keys=300, visits=5,
                        resident=0)
    ctx = {"dispatched": [(("c",), 400)] * 2}
    assert reader("aux_resident_share.scan").read(ctx) == pytest.approx(100 * 100 / 400)


@pytest.mark.parametrize("args", [None, {"keys": 100, "visits": 2}])
def test_scan_share_none_without_resident_spans(tracer, args):
    """No span at all, or only the spans of a program whose ``aux.get``
    carries no ``resident`` arg."""
    if args is not None:
        for i in range(3):
            tracer.add_span("plan", 10.0 * i, 10.0 * i + 5, track="plans")
            tracer.add_span("aux.get", 10.0 * i + 1, 10.0 * i + 2, **args)
    ctx = {"dispatched": [(("c",), 100)] * 2}
    assert reader("aux_resident_share.scan").read(ctx) is None


@pytest.mark.parametrize("workload,share", [("lineitem_probe", 100.0),
                                            ("lineitem_probe_overpool", 0.0),
                                            ("cd_groupby", 100.0)])
def test_share_read_from_a_window_of_the_program(root, workload, share, tracer):
    spec = harness.load_cell(root, workload)
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
    (name,) = [m["name"] for m in spec["per_layer"] if m["name"].startswith("aux_resident")]
    assert reader(name).read(ctx) == share


def test_overpool_cell_runs_and_reads_correct(root):
    result = harness.run("lineitem_probe_overpool", SEED, 0.5, False, root=root,
                         require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "stored_bytes_per_user_byte",
                                      "lookup_keys_per_s", "lookup_p95_ms"}
    spec = harness.load_cell(root, "lineitem_probe_overpool")
    probe = harness.load_cell(root, "lineitem_probe")
    assert spec["traffic"] == probe["traffic"]
    differ = {k for k in spec["config"] if spec["config"][k] != probe["config"][k]}
    assert differ == {"source", "store"}
    assert {k for k in spec["config"]["store"]
            if spec["config"]["store"][k] != probe["config"]["store"][k]} == {"aux_pool_bytes"}
