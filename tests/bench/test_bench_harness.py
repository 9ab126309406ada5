"""The benchmark harness driven end to end on the CPU at a tiny size:
every cell runs and reads correct, a cell added from new files and an
entry alone runs, an answer altered where the store produces it reads
not correct, and so does the control (the model served with bfloat16
weights against the ``T_aux`` of its float32 build)."""

import json
import os

import pytest

import benchtiny  # noqa: F401  (puts the repository root on sys.path)
from bench import harness

CELLS = ("cd_probe", "lineitem_probe", "cd_groupby", "lineitem_groupby")
SEED = 2**31 + 11  # the driver's seeds are this large


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
    return benchtiny.make_root(str(tmp_path_factory.mktemp("bench")))


def run(root, workload, **kw):
    return harness.run(workload, SEED, 0.5, False, root=root, require_tpu=False, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_reads_correct(root, workload, capsys):
    result = run(root, workload)
    out, err = capsys.readouterr()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["end_to_end"] if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1].startswith("check ")


def test_loads_config_and_traffic_by_name(root):
    spec = harness.load_cell(root, "lineitem_probe")
    assert spec["config"]["name"] == "tpch_lineitem_sf1"
    assert spec["traffic"]["loop"] == "probe_closed"
    assert spec["traffic"]["absent_share"] == 0.0625
    assert spec["loop"].Loop.__module__.endswith("probe_closed_py")
    keys, columns = spec["generator"].generate(spec["config"], 3)
    assert keys.size == spec["config"]["rows"] and set(columns) == set(spec["config"]["columns"])
    assert {m["name"] for m in spec["per_layer"]} == set(spec["readers"])
    assert all(os.path.exists(p) for p in spec["readers"].values())
    with pytest.raises(KeyError):
        harness.load_cell(root, "no_such_cell")


def test_cell_added_from_new_files_and_an_entry(tmp_path):
    root = benchtiny.make_root(str(tmp_path))
    with open(tmp_path / "bench" / "traffic" / "probe_tiny_mixed.json", "w") as f:
        json.dump({"loop": "probe_closed", "callers": 3, "request_keys": 40,
                   "key_dist": "zipf", "zipf_theta": 0.99, "absent_share": 0.25,
                   "max_batch": 65536, "warmup_steps": 1, "check_share": 1.0}, f)
    bench_path = tmp_path / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())
    bench["workloads"].append({"name": "lineitem_probe_mixed", "config": "tpch_lineitem_sf1",
                               "traffic": "probe_tiny_mixed", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("lookup_"):
            m["workloads"].append("lineitem_probe_mixed")
    bench_path.write_text(json.dumps(bench))
    result = run(root, "lineitem_probe_mixed")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "stored_bytes_per_user_byte",
                                      "lookup_keys_per_s", "lookup_p95_ms"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["checks"]["checked_requests"]["value"] >= 3


def test_probe_answer_altered_where_produced_reads_not_correct(root, monkeypatch):
    from repro.core.encoding import ValueCodec

    decode = ValueCodec.decode

    def altered(self, codes):
        out = decode(self, codes)
        if out.size:
            out = out.copy()
            out[0] = self.decode_map[(list(self.decode_map).index(out[0]) + 1)
                                     % len(self.decode_map)]
        return out

    monkeypatch.setattr(ValueCodec, "decode", altered)
    result = run(root, "cd_probe")
    assert result["correct"] is False
    assert result["checks"]["wrong_cells"]["value"] > 0


@pytest.mark.parametrize("workload", ["cd_groupby", "lineitem_groupby"])
def test_groupby_code_altered_where_produced_reads_not_correct(root, monkeypatch, workload):
    from repro.core.hybrid import DeepMappingStore

    chunks = DeepMappingStore._iter_corrected_chunks

    def altered(self, pending, stats):
        for codes, exists, match in chunks(self, pending, stats):
            if codes.size:
                card = self.spec.card_map[pending.wanted[0]]
                codes[0, 0] = (codes[0, 0] + 1) % card
            yield codes, exists, match

    monkeypatch.setattr(DeepMappingStore, "_iter_corrected_chunks", altered)
    result = run(root, workload)
    assert result["correct"] is False
    assert result["checks"]["wrong_groups"]["value"] > 0


def test_existence_bit_altered_reads_not_correct(root, monkeypatch):
    from repro.core.inference import InferenceEngine

    collect = InferenceEngine.collect

    def altered(self, ticket):
        codes, exists = collect(self, ticket)
        if exists is not None and exists.size:
            exists = exists.copy()
            exists[0] = not exists[0]
        return codes, exists

    monkeypatch.setattr(InferenceEngine, "collect", altered)
    result = run(root, "lineitem_probe")
    assert result["correct"] is False
    assert result["checks"]["wrong_exists"]["value"] > 0


def test_control_bf16_weights_reads_not_correct(tmp_path):
    """At this size the model memorizes about 5% of the rows, and
    serving them through bfloat16 weights changes some answers."""
    root = benchtiny.make_root(str(tmp_path))
    path = tmp_path / "bench" / "configs" / "tpcds_customer_demographics.json"
    cfg = json.loads(path.read_text())
    cfg["store"].update(shared=[64, 64], private=[16])
    cfg["train"].update(epochs=30, batch_size=256)
    path.write_text(json.dumps(cfg))
    sound = harness.run("cd_probe", 5, 1.0, False, root=root, require_tpu=False)
    control = harness.run("cd_probe", 5, 1.0, False, root=root, require_tpu=False,
                          control=True)
    assert sound["correct"] is True
    assert control["correct"] is False
    assert control["checks"]["wrong_cells"]["value"] > 0


def test_half_of_each_batch_left_out_reads_not_correct(root, monkeypatch):
    """The engine answers the first half of every batch it is handed and
    leaves the codes of the rest at 0."""
    from repro.core.inference import InferenceEngine

    collect = InferenceEngine.collect

    def halved(self, ticket):
        codes, exists = collect(self, ticket)
        codes = codes.copy()
        codes[codes.shape[0] // 2:] = 0
        return codes, exists

    monkeypatch.setattr(InferenceEngine, "collect", halved)
    result = run(root, "cd_probe")
    assert result["correct"] is False
    assert result["checks"]["wrong_cells"]["value"] > 0
