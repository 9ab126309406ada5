"""The reader of ``aux_device_share.lookup`` on hand-made inputs, its
``None`` cases, and read from a window of the program in the probe cells
it lists, run on the CPU at a tiny size: every probed key corrected on
the device where the resident view fits the pool, none over it."""

import json
import os
import tempfile

import pytest

import benchtiny
from bench import harness
from bench import reference as ref_lib

SEED = 2**31 + 29  # seeds reach past 32 signed bits
NAME = "aux_device_share.lookup"


def reader():
    return harness.load_module(os.path.join(benchtiny.REPO, "bench", "layer_metrics",
                                            NAME + ".py"))


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
    """The tiny tree, with the over-pool configuration's pool cut, like
    the real one, to 1/8 of its ``T_aux`` decompressed."""
    root = benchtiny.make_root(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "bench", "configs", "tpch_lineitem_sf1_overpool.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["store"]["aux_pool_bytes"] = cfg["rows"] * (8 + 4 * len(cfg["columns"])) // 8
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


@pytest.mark.parametrize("device,share", [(1000, 100.0), (250, 25.0), (0, 0.0)])
def test_share_from_serve_stats(device, share):
    ctx = {"spans": {"serve.aux_device_keys": device, "serve.aux_keys": 1000}}
    assert reader().read(ctx) == pytest.approx(share)


@pytest.mark.parametrize("spans", [
    {"serve.aux_keys": 1000, "serve.aux_resident_keys": 1000},   # a program without the count
    {"serve.aux_device_keys": 0, "serve.aux_keys": 0},           # no key probed
])
def test_share_none_without_its_counts(spans):
    assert reader().read({"spans": spans}) is None


def test_listed_in_the_probe_cells():
    with open(os.path.join(benchtiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["moves"] == "lookup_keys_per_s" and entry["unit"] == "%"
    lookups = next(m for m in bench["end_to_end"] if m["name"] == "lookup_keys_per_s")
    assert set(entry["workloads"]) <= set(lookups["workloads"])


@pytest.mark.parametrize("workload,share", [("cd_probe", 100.0),
                                            ("lineitem_probe", 100.0),
                                            ("lineitem_probe_overpool", 0.0)])
def test_share_read_from_a_window_of_the_program(root, workload, share):
    spec = harness.load_cell(root, workload)
    assert NAME in spec["readers"]
    cfg = spec["config"]
    keys, columns = spec["generator"].generate(cfg, SEED)
    ref = ref_lib.Reference(keys, columns)
    with tempfile.TemporaryDirectory() as d:
        store, _ = harness.build_store(cfg, ref.keys, ref.columns, d)
    loop = spec["loop"].Loop(spec["traffic"], store, ref, cfg, SEED)
    loop.warm_up(SEED)
    window = loop.run(0.3)
    assert window.spans["serve.aux_keys"] > 0
    assert reader().read({"spans": window.spans}) == share
