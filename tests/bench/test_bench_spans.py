"""The program's own spans as the benchmark reads them: gaps named by
the innermost program span on hand-made events, a CPU profiler trace of
a real lookup, the readers of the new per-layer metrics, and the
``lineitem_probe_zipf`` cell run end to end on the CPU at a tiny size."""

import json
import os
import tempfile

import numpy as np
import pytest

import benchtiny
from bench import harness
from bench import host_spans
from bench import reference as ref_lib
from bench import trace as trace_lib

SEED = 2**31 + 11  # seeds reach past 32 signed bits
MS = 1_000_000
READERS = os.path.join(benchtiny.REPO, "bench", "layer_metrics")
NEW_READERS = ("merge_us_per_key.lookup", "scatter_us_per_key.lookup",
               "dispatch_us_per_key.lookup", "wait_us_per_key.lookup",
               "aux_keys_per_visit.lookup", "dispatch_us_per_row.scan",
               "wait_us_per_row.scan", "aux_keys_per_visit.scan")


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
def fresh_tracer():
    from repro import obs

    trc = obs.Tracer()
    prev = obs.set_tracer(trc)
    yield trc
    obs.set_tracer(prev)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = benchtiny.make_root(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "bench", "traffic", "probe_closed_zipf.json")
    with open(path) as f:
        params = json.load(f)
    assert params["key_dist"] == "zipf"
    params.update(callers=2, request_keys=64, warmup_steps=2, check_share=0.5)
    with open(path, "w") as f:
        json.dump(params, f)
    return root


def test_gaps_named_by_innermost_program_span():
    flat = trace_lib.Flat(
        devices={"/device:TPU:0": {"XLA Ops": [("fusion", 10 * MS, 20 * MS),
                                               ("fusion", 60 * MS, 70 * MS)]},
                 "/device:TPU:0 extra": {}},
        spans=[])
    spans = [("window", 0, 100 * MS),
             ("lookup_many", 6 * MS, 95 * MS),
             ("collect", 20 * MS, 58 * MS),
             ("aux.get", 25 * MS, 55 * MS),
             ("serve.scatter", 72 * MS, 110 * MS),   # runs past the window
             ("aux.get", 120 * MS, 130 * MS)]        # after it
    top, totals = host_spans.idle_by_span(flat, spans)
    assert top == [("aux.get", pytest.approx(0.04)),
                   ("serve.scatter", pytest.approx(0.03)),
                   ("between_calls", pytest.approx(0.01))]
    assert totals == {"aux.get": pytest.approx(0.04),
                      "serve.scatter": pytest.approx(0.03),
                      "between_calls": pytest.approx(0.01)}
    sums = host_spans.seconds_in_window(spans)
    assert sums["aux.get"] == (pytest.approx(0.03), 1)
    assert sums["serve.scatter"] == (pytest.approx(0.028), 1)
    assert sums["lookup_many"] == (pytest.approx(0.089), 1)
    assert "window" not in sums
    # lookup_many 89 ms; program spans cover 20-58 and 72-95 of it
    share = host_spans.uncovered_share(spans, ["lookup_many"], ["collect", "aux.get",
                                                                "serve.scatter"])
    assert share == pytest.approx(1 - 61 / 89)
    assert host_spans.uncovered_share(spans, ["execute"], ["collect"]) is None


def test_innermost_open_is_the_latest_opened():
    spans = [("a", 0, 100), ("b", 10, 50), ("c", 20, 30), ("d", 60, 70)]
    assert host_spans.innermost_open(spans, [5, 25, 35, 55, 65, 100]) == [
        "a", "c", "b", "a", "d", None]


def test_program_spans_in_a_cpu_profiler_trace(root, tmp_path):
    """A lookup through the server, traced by the JAX profiler on the
    CPU: the program's spans land on the host plane, nested as run."""
    import jax

    from repro import obs
    from repro.serve import LookupServer

    spec = harness.load_cell(root, "lineitem_probe")
    keys, columns = spec["generator"].generate(spec["config"], 3)
    store, _ = harness.build_store(spec["config"], keys, columns, str(tmp_path / "store"))
    server = LookupServer(store, max_batch=256)
    requests = [keys[:300], keys[200:400]]
    server.lookup_many(requests)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("lookup_many"):
            server.lookup_many(requests)
    jax.profiler.stop_trace()
    path = trace_lib.find_xplane(str(tmp_path / "trace"))
    spans = host_spans.load_spans(path, obs.PROFILER_SPANS + trace_lib.SPANS)
    by = {}
    for name, s, e in spans:
        by.setdefault(name, []).append((s, e))
    assert {"serve.merge", "serve.scatter", "collect", "engine.dispatch",
            "engine.wait", "aux.get", "store.decode"} <= set(by)
    assert len(by["collect"]) == 2                      # 400 unique keys, morsels of 256

    def inside(child, parents):
        return all(any(ps <= s and e <= pe for ps, pe in by[parents])
                   for s, e in by[child])

    assert inside("engine.wait", "collect") and inside("aux.get", "collect")
    assert inside("store.decode", "collect")
    (lm,) = by["lookup_many"]
    assert all(lm[0] <= s and e <= lm[1]
               for n in ("serve.merge", "serve.scatter", "collect") for s, e in by[n])
    (merge,) = by["serve.merge"]
    assert not any(merge[0] <= s < merge[1] for s, _ in by["collect"])
    sums = host_spans.seconds_in_window(spans)
    assert sums["aux.get"][1] == 2
    assert host_spans.uncovered_share(spans, ["lookup_many"], obs.PROFILER_SPANS) < 1.0


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_returns_none_without_its_span_or_counter(name, fresh_tracer):
    """What a run of the parent program gives: no such stage seconds,
    counts or spans (an empty ring)."""
    ctx = {"spans": {"serve.total_s": 1.0, "serve.infer_s": 0.5}, "work": 1000,
           "dispatched": [(("c",), 10)] * 3, "elapsed_s": 1.0}
    assert reader(name).read(ctx) is None


def test_ring_window_counts_only_plans_the_ring_holds_whole():
    from repro import obs

    trc = obs.Tracer(capacity=8)
    prev = obs.set_tracer(trc)
    try:
        for i in range(5):   # 3 spans a plan: the ring holds the last 2 whole
            trc.add_span("engine.wait", 10.0 * i + 1, 10.0 * i + 2)
            trc.add_span("aux.get", 10.0 * i + 2, 10.0 * i + 3)
            trc.add_span("plan", 10.0 * i, 10.0 * i + 5, track="plans")
        spans, plans = host_spans.ring_window(2, ("engine.wait",))
        assert plans == 2 and [s.start for s in spans] == [31.0, 41.0]
        spans, plans = host_spans.ring_window(4, ("engine.wait",))
        assert plans == 2 and [s.start for s in spans] == [31.0, 41.0]
        assert host_spans.ring_window(1, ("aux.get",))[1] == 1
        assert host_spans.ring_window(0, ("engine.wait",)) == ([], 0)
        ctx = {"work": 400, "dispatched": [(("c",), 100)] * 4}
        assert reader("wait_us_per_row.scan").read(ctx) == pytest.approx(1e6 * 2 / 200)
    finally:
        obs.set_tracer(prev)


@pytest.mark.parametrize("workload", ["lineitem_probe_zipf", "cd_groupby"])
def test_new_readers_read_a_window_of_the_program(root, workload, fresh_tracer):
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
    names = [m["name"] for m in spec["per_layer"] if m["name"] in NEW_READERS]
    assert len(names) == (5 if workload.endswith("zipf") else 3)
    values = {n: reader(n).read(ctx) for n in names}
    assert all(v is not None and v > 0 for v in values.values()), values
    per_visit = values[next(n for n in names if n.startswith("aux_keys_per_visit"))]
    assert per_visit >= 1.0
    if workload == "cd_groupby":
        # one visit per partition a morsel's sorted keys fall in
        rows_per_part = cfg["store"]["partition_bytes"] // (8 + 4 * len(columns))
        assert per_visit <= rows_per_part


def test_zipf_cell_runs_and_reads_correct(root):
    result = harness.run("lineitem_probe_zipf", SEED, 0.5, False, root=root,
                         require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "stored_bytes_per_user_byte",
                                      "lookup_keys_per_s", "lookup_p95_ms"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    spec = harness.load_cell(root, "lineitem_probe_zipf")
    assert spec["traffic"]["loop"] == "probe_closed"
    assert {m["name"] for m in spec["per_layer"]} == {
        n for n in NEW_READERS if n.endswith(".lookup")}
    assert np.isclose(spec["traffic"]["zipf_theta"], 0.99)


@pytest.mark.parametrize("workload", ["lineitem_probe_zipf", "cd_groupby"])
def test_traced_harness_run_reports_every_new_metric(root, workload, monkeypatch,
                                                     fresh_tracer):
    """The harness's ``--trace 1`` path hands the new readers what they
    read.  The CPU trace has no device plane, so its reduction is
    stubbed; everything else runs as on the chip."""
    reduced = trace_lib.Reduced(window_s=1.0, busy_s=0.5, idle_share=0.5, modules={},
                                top_ops=[], idle_gaps=[])
    monkeypatch.setattr(trace_lib, "reduce", lambda flat, top=10: reduced)
    result = harness.run(workload, SEED, 0.5, True, root=root, require_tpu=False)
    spec = harness.load_cell(root, workload)
    new = {m["name"] for m in spec["per_layer"] if m["name"] in NEW_READERS}
    assert new and new <= set(result["metrics"])
    assert all(result["metrics"][n]["value"] > 0 for n in new)
    assert result["correct"] is True
