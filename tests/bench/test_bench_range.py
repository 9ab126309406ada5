"""The ``lineitem_range_pred`` cell (TPC-H Q12's lineitem selection over
key-range splits) run through the harness on the CPU at a tiny size, its
new readers, and what they give without the program's span and count."""

import os

import pytest

import benchtiny
from bench import harness
from bench import trace as trace_lib

SEED = 2**31 + 11  # seeds reach past 32 signed bits
CELL = "lineitem_range_pred"
NEW_READERS = ("key_source_us_per_row.scan", "filter_us_per_row.scan",
               "host_filter_share.scan")
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
def fresh_tracer():
    from repro import obs

    trc = obs.Tracer()
    prev = obs.set_tracer(trc)
    yield trc
    obs.set_tracer(prev)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_range_cell_runs_traced_and_reads_correct(root, monkeypatch, fresh_tracer, capsys):
    """The traced path: every check holds, the splits were capped to
    half the tiny relation, no compile fell in the window, and the new
    readers return numbers.  The CPU trace has no device plane, so its
    reduction is stubbed."""
    reduced = trace_lib.Reduced(window_s=1.0, busy_s=0.5, idle_share=0.5, modules={},
                                top_ops=[], idle_gaps=[])
    monkeypatch.setattr(trace_lib, "reduce", lambda flat, top=10: reduced)
    result = harness.run(CELL, SEED, 1.0, True, root=root, require_tpu=False)
    out, err = capsys.readouterr()
    assert result["correct"] is True and result["failed"] == 0
    checks = result["checks"]
    assert {"wrong_values", "wrong_missing_rows", "wrong_extra_rows"} <= set(checks)
    assert checks["checked_queries"]["value"] >= 1
    info = next(line for line in out.splitlines() if line.startswith("info "))
    assert '"backend_compiles_in_window": 0' in info
    assert "filter[kernel:" in next(line for line in err.splitlines()
                                    if line.startswith("plan "))
    spec = harness.load_cell(root, CELL)
    assert spec["config"]["rows"] == benchtiny.ROWS["tpch_lineitem_sf1"]
    values = {n: result["metrics"][n]["value"] for n in NEW_READERS}
    assert all(v > 0 for v in values.values()), values
    assert values["host_filter_share.scan"] <= 100.0
    assert {"infer_us_per_row.scan", "aux_us_per_row.scan", "dispatch_us_per_row.scan",
            "wait_us_per_row.scan", "device_idle_share.scan"} <= set(result["metrics"])


def test_range_cell_untraced_reports_the_scan_rate(root):
    result = harness.run(CELL, SEED + 1, 0.5, False, root=root, require_tpu=False)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "stored_bytes_per_user_byte",
                                      "scan_rows_per_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_without_its_span_or_count(name, fresh_tracer):
    """What a program without the ``exec.key_source`` span and the
    ``filter_host_rows`` count gives: no such span in the ring, no such
    stage count."""
    ctx = {"spans": {"scan.infer_s": 0.5, "scan.aux_s": 0.2}, "work": 3000,
           "dispatched": [(("c",), 1000)] * 3, "elapsed_s": 1.0}
    assert reader(name).read(ctx) is None


def test_range_answer_altered_where_produced_reads_not_correct(root, monkeypatch):
    """A row dropped from the kernel's matches where the store produces
    them is caught as a missing row."""
    from repro.core.hybrid import DeepMappingStore

    filter_chunk = DeepMappingStore._filter_chunk

    def dropped(*args):
        match = filter_chunk(*args)
        hit = match.nonzero()[0]
        if hit.size:
            match[hit[0]] = False
        return match

    monkeypatch.setattr(DeepMappingStore, "_filter_chunk", staticmethod(dropped))
    result = harness.run(CELL, SEED, 0.5, False, root=root, require_tpu=False)
    assert result["correct"] is False
    assert result["checks"]["wrong_missing_rows"]["value"] > 0
