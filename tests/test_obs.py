"""Observability layer: registry/tracer units, exporter formats, the
PlanCache thread-safety regression, and the cross-layer invariants
suite (operator sums, monotone snapshots, trace round-trips, and the
dispatch/collect pipeline-overlap smoke test on a real hybrid store)."""

import contextlib
import json
import threading

import numpy as np
import pytest

from conftest import make_periodic_table
from repro import obs
from repro.api.cache import PlanCache
from repro.core import DeepMappingConfig, DeepMappingStore
from repro.core.trainer import TrainConfig


@pytest.fixture()
def fresh_obs():
    """Isolated registry + tracer installed as the process defaults
    (restored on teardown), so tests see only their own telemetry."""
    reg, trc = obs.MetricsRegistry(), obs.Tracer()
    prev_reg, prev_trc = obs.set_registry(reg), obs.set_tracer(trc)
    yield reg, trc
    obs.set_registry(prev_reg)
    obs.set_tracer(prev_trc)


@pytest.fixture(scope="module")
def obs_store():
    """Small trained store for the wiring/invariants tests."""
    table = make_periodic_table(n=2000)
    store = DeepMappingStore.build(
        table,
        DeepMappingConfig(shared=(64,), private=(16,),
                          train=TrainConfig(epochs=15, batch_size=512)),
    )
    return table, store


class TestMetricsRegistry:
    def test_counter_labels_and_values(self, fresh_obs):
        reg, _ = fresh_obs
        c = reg.counter("x_total", "help text")
        c.inc(kind="a")
        c.inc(3, kind="b")
        c.inc(kind="a")
        assert c.value(kind="a") == 2
        assert c.value(kind="b") == 3
        assert c.value(kind="never") == 0

    def test_counter_rejects_negative(self, fresh_obs):
        reg, _ = fresh_obs
        with pytest.raises(ValueError):
            reg.counter("x_total").inc(-1)

    def test_gauge_set_inc_dec(self, fresh_obs):
        reg, _ = fresh_obs
        g = reg.gauge("depth")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value() == 4

    def test_histogram_quantiles_bracket_observations(self, fresh_obs):
        reg, _ = fresh_obs
        h = reg.histogram("lat_seconds")
        for v in (0.001, 0.002, 0.004, 0.008, 0.1):
            h.observe(v)
        p50, p99 = h.quantile(0.5), h.quantile(0.99)
        # log-bucket interpolation: within a factor of 2 of the truth
        assert 0.001 < p50 < 0.008
        assert 0.05 < p99 <= 0.2
        assert p50 <= p99

    def test_get_or_create_returns_same_family(self, fresh_obs):
        reg, _ = fresh_obs
        assert reg.counter("a_total") is reg.counter("a_total")

    def test_kind_mismatch_raises(self, fresh_obs):
        reg, _ = fresh_obs
        reg.counter("name")
        with pytest.raises(TypeError):
            reg.gauge("name")

    def test_enabled_flag_is_a_no_op_switch(self, fresh_obs):
        reg, _ = fresh_obs
        c = reg.counter("x_total")
        c.inc()
        reg.enabled = False
        c.inc()
        reg.histogram("h").observe(1.0)
        reg.enabled = True
        assert c.value() == 1
        assert reg.histogram("h").value() == 0

    def test_registry_injection(self, fresh_obs):
        reg, _ = fresh_obs
        assert obs.registry() is reg
        obs.counter("via_module_total").inc()
        assert reg.counter("via_module_total").value() == 1

    def test_concurrent_increments_lose_nothing(self, fresh_obs):
        reg, _ = fresh_obs
        c = reg.counter("hammer_total")
        h = reg.histogram("hammer_seconds")
        n_threads, per_thread = 8, 500

        def work():
            for _ in range(per_thread):
                c.inc(shard=1)
                h.observe(0.001)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert c.value(shard=1) == n_threads * per_thread
        assert h.state().count == n_threads * per_thread


class TestTracer:
    def test_span_context_manager_records(self, fresh_obs):
        _, trc = fresh_obs
        with trc.span("work", track="host", morsel=3):
            pass
        (s,) = trc.spans("work")
        assert s.track == "host" and s.args["morsel"] == 3
        assert s.end >= s.start

    def test_add_span_clamps_negative_duration(self, fresh_obs):
        _, trc = fresh_obs
        trc.add_span("x", 2.0, 1.0)
        (s,) = trc.spans("x")
        assert s.duration == 0.0

    def test_ring_buffer_bounds_memory(self):
        trc = obs.Tracer(capacity=16)
        for i in range(100):
            trc.add_span(f"s{i}", 0.0, 1.0)
        assert len(trc) == 16
        assert trc.spans()[0].name == "s84"  # oldest survivors

    def test_disabled_tracer_records_nothing(self, fresh_obs):
        _, trc = fresh_obs
        trc.enabled = False
        with trc.span("nope"):
            pass
        trc.add_span("nope", 0.0, 1.0)
        assert len(trc) == 0

    def test_span_recorded_even_when_body_raises(self, fresh_obs):
        _, trc = fresh_obs
        with pytest.raises(RuntimeError):
            with trc.span("boom"):
                raise RuntimeError()
        assert len(trc.spans("boom")) == 1

    def test_span_records_parent_and_inherits_morsel(self, fresh_obs):
        _, trc = fresh_obs
        with trc.span("collect", morsel=7):
            with trc.span("aux.get"):
                with trc.span("aux.decompress"):
                    pass
        with trc.span("serve.merge"):
            pass
        by_name = {s.name: s for s in trc.spans()}
        assert by_name["collect"].parent == ""
        assert by_name["aux.get"].parent == "collect"
        assert by_name["aux.decompress"].parent == "aux.get"
        assert by_name["aux.decompress"].args["morsel"] == 7
        assert by_name["serve.merge"].parent == ""
        assert "morsel" not in by_name["serve.merge"].args

    def test_span_mirrors_name_only_into_profiler_hook(self, fresh_obs):
        _, trc = fresh_obs
        opened = []

        class Annotation:
            def __init__(self, *args, **kwargs):
                opened.append((args, kwargs))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                opened.append("exit")

        prev = obs.set_profiler_hook(Annotation)
        try:
            with trc.span("engine.wait", morsel=2):
                pass
            trc.add_span("plan", 0.0, 1.0, track="plans")
        finally:
            obs.set_profiler_hook(prev)
        assert opened == [(("engine.wait",), {}), "exit"]

    @pytest.mark.parametrize("hook", [True, False])
    def test_disabled_tracer_mirrors_nothing(self, fresh_obs, hook):
        _, trc = fresh_obs
        opened = []
        prev = obs.set_profiler_hook(
            (lambda name: opened.append(name) or contextlib.nullcontext())
            if hook else None
        )
        try:
            trc.enabled = not hook   # hooked but disabled, or enabled but unhooked
            with trc.span("aux.get") as sp:
                pass
        finally:
            obs.set_profiler_hook(prev)
        assert opened == []
        assert len(trc) == (0 if hook else 1)
        assert sp.seconds >= 0.0 and sp.end >= sp.start > 0.0

    def test_histogram_observes_many_at_once(self, fresh_obs):
        reg, _ = fresh_obs
        h = reg.histogram("lat_seconds")
        h.observe(0.01, times=3, kind="a")
        h.observe(0.02, kind="a")
        state = h.state(kind="a")
        assert state.count == 4 and state.sum == pytest.approx(0.05)


class TestExporters:
    def test_prometheus_text_format(self, fresh_obs):
        reg, _ = fresh_obs
        reg.counter("c_total", "counts things").inc(2, kind="a")
        reg.histogram("h_seconds").observe(0.003)
        text = obs.to_prometheus(reg)
        assert "# HELP c_total counts things" in text
        assert "# TYPE c_total counter" in text
        assert 'c_total{kind="a"} 2' in text
        assert "# TYPE h_seconds histogram" in text
        assert 'h_seconds_bucket{le="+Inf"} 1' in text
        assert "h_seconds_count 1" in text

    def test_json_snapshot_round_trips(self, fresh_obs):
        reg, _ = fresh_obs
        reg.counter("c_total").inc(kind="a")
        reg.histogram("h_seconds").observe(0.01, stage="infer")
        snap = json.loads(obs.to_json_snapshot(reg))
        assert snap["c_total"]["values"] == [
            {"labels": {"kind": "a"}, "value": 1.0}
        ]
        hist = snap["h_seconds"]["values"][0]
        assert hist["count"] == 1 and hist["p50"] > 0

    def test_chrome_trace_round_trips_and_names_tracks(self, fresh_obs):
        _, trc = fresh_obs
        trc.add_span("plan", 1.0, 2.0, track="plans", kind="scan")
        trc.add_span("collect", 1.5, 1.8, track="host", morsel=0)
        doc = json.loads(json.dumps(obs.to_chrome_trace(trc)))
        events = doc["traceEvents"]
        names = {e["args"]["name"] for e in events if e["ph"] == "M"}
        assert {"plans", "host"} <= names
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 2
        # tracks get tids in first-seen order; timestamps rebased to 0 in µs
        plan = next(e for e in xs if e["cat"] == "plans")
        assert plan["tid"] == 0 and plan["ts"] == 0.0 and plan["dur"] == 1e6
        assert plan["args"] == {"kind": "scan"}

    def test_chrome_trace_carries_span_parent(self, fresh_obs):
        _, trc = fresh_obs
        with trc.span("collect", morsel=1):
            with trc.span("engine.wait"):
                pass
        xs = [e for e in obs.to_chrome_trace(trc)["traceEvents"] if e["ph"] == "X"]
        wait = next(e for e in xs if e["name"] == "engine.wait")
        assert wait["args"] == {"morsel": "1", "parent": "collect"}

    def test_write_helpers_produce_loadable_files(self, fresh_obs, tmp_path):
        reg, trc = fresh_obs
        reg.counter("c_total").inc()
        trc.add_span("s", 0.0, 1.0)
        prom = obs.write_prometheus(str(tmp_path / "m.prom"), reg)
        snap = obs.write_json_snapshot(str(tmp_path / "m.json"), reg)
        trace = obs.write_chrome_trace(str(tmp_path / "t.json"), trc)
        assert "c_total 1" in open(prom).read()
        assert json.load(open(snap))["c_total"]["kind"] == "counter"
        assert json.load(open(trace))["traceEvents"]

    def test_write_helpers_create_missing_directories(self, fresh_obs, tmp_path):
        """Regression: ``quickstart --telemetry-dir NEW_DIR`` crashed
        because the sinks assumed the directory already existed."""
        reg, trc = fresh_obs
        reg.counter("c_total").inc()
        out = tmp_path / "not" / "yet" / "there"
        assert obs.write_prometheus(str(out / "m.prom"), reg) == str(out / "m.prom")
        assert obs.write_chrome_trace(str(out / "t.json"), trc)
        assert (out / "m.prom").exists()


class TestPlanCacheThreadSafety:
    def test_hammered_hit_count_is_exact(self):
        """Regression: hits/misses were unlocked ``+=`` while sharded
        collect runs on fan-out pool threads — under contention the
        counts silently under-reported."""
        cache = PlanCache()
        fp = ("scan", None, (), True)
        cache.put(fp, 0, np.arange(64, dtype=np.int64), None)
        n_threads, per_thread = 8, 400

        def work():
            for _ in range(per_thread):
                assert cache.get(fp, 0) is not None

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert cache.hits == n_threads * per_thread
        assert cache.misses == 0

    def test_bypass_counted_and_exact_under_threads(self):
        cache = PlanCache()
        n_threads, per_thread = 4, 250

        def work():
            for _ in range(per_thread):
                cache.get(None, 0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert cache.bypass == n_threads * per_thread

    def test_concurrent_put_get_evict_is_crash_free(self):
        cache = PlanCache(plan_entries=4)
        stop = threading.Event()
        errors = []

        def reader():
            try:
                while not stop.is_set():
                    for i in range(8):
                        cache.get(("range", i, i + 1, None, (), True), 0)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def writer():
            try:
                for i in range(400):
                    cache.put(("range", i % 8, i % 8 + 1, None, (), True), 0,
                              np.arange(32, dtype=np.int64), None)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        readers = [threading.Thread(target=reader) for _ in range(3)]
        writers = [threading.Thread(target=writer) for _ in range(2)]
        [t.start() for t in readers + writers]
        [t.join() for t in writers]
        stop.set()
        [t.join() for t in readers]
        assert not errors

    def test_cache_events_mirrored_to_registry(self, fresh_obs):
        reg, _ = fresh_obs
        cache = PlanCache()
        fp = ("scan", None, (), True)
        cache.get(fp, 0)            # miss
        cache.put(fp, 0, None, None)
        cache.get(fp, 0)            # hit
        cache.get(None, 0)          # bypass
        ev = reg.counter("deepmap_plan_cache_events_total")
        assert ev.value(outcome="miss") == 1
        assert ev.value(outcome="hit") == 1
        assert ev.value(outcome="bypass") == 1


class TestInvariants:
    """Cross-layer invariants the telemetry must preserve."""

    def test_operator_rows_sum_to_plan_total(self, obs_store):
        _, store = obs_store
        res = store.query().scan().execute()
        s = res.explain
        op_sum = sum(o.seconds for o in s.operators)
        assert op_sum > 0
        # Stage timings are measured inside the (serial) host half plus
        # route/gather, so their sum approximates the plan wall time;
        # generous slack for timer granularity and pipeline overlap.
        assert op_sum <= s.total_s * 1.5
        assert op_sum >= s.total_s * 0.2

    def test_registry_snapshots_monotone_across_repeated_plans(
        self, fresh_obs, obs_store
    ):
        _, store = obs_store

        def counter_values(snap):
            out = {}
            for name, fam in snap.items():
                if fam["kind"] != "counter":
                    continue
                for v in fam["values"]:
                    out[(name, tuple(sorted(v["labels"].items())))] = v["value"]
            return out

        store.query().scan().execute()
        first = counter_values(obs.snapshot())
        store.query().scan().execute()
        second = counter_values(obs.snapshot())
        assert first  # the executor actually recorded something
        for key, val in first.items():
            assert second.get(key, 0) >= val
        morsel_key = ("deepmap_executor_morsels_total", (("kind", "scan"),))
        assert second[morsel_key] > first[morsel_key]

    def test_engine_and_morsel_metrics_recorded(self, fresh_obs, obs_store):
        reg, _ = fresh_obs
        table, store = obs_store
        store.query().where_keys(table.keys[:256]).execute()
        assert reg.counter("deepmap_executor_morsels_total").value(kind="point") > 0
        assert reg.counter("deepmap_engine_events_total").value(
            event="dispatches") > 0
        assert reg.counter("deepmap_plan_cache_events_total").items()

    def test_next_dispatch_starts_before_collect_ends(self, fresh_obs, obs_store):
        """The pipelining, from real spans: the streaming executor
        dispatches morsel i+1 (its ``engine.dispatch`` span, opened
        outside any collect) before the ``collect`` span of morsel i
        ends, so the device computes i+1 while the host drains i."""
        _, store = obs_store
        store.query().morsel(256).scan().execute()
        _, trc = fresh_obs
        # one chunk per morsel: the i-th top-level dispatch is morsel i's
        dispatch = sorted((s for s in trc.spans("engine.dispatch") if not s.parent),
                          key=lambda s: s.start)
        collect = {s.args["morsel"]: s for s in trc.spans("collect")}
        assert len(dispatch) == len(collect) >= 4  # multiple morsels streamed
        for i in range(len(collect) - 1):
            assert dispatch[i + 1].start < collect[i].end
        waits = trc.spans("engine.wait")
        assert len(waits) == len(collect)
        assert all(w.parent == "collect" for w in waits)

    def test_chrome_trace_of_real_plan_is_perfetto_shaped(
        self, fresh_obs, obs_store
    ):
        _, store = obs_store
        store.query().morsel(256).scan().execute()
        doc = json.loads(json.dumps(obs.to_chrome_trace()))
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        cats = {e["cat"] for e in xs}
        assert cats == {"host", "plans"}
        for e in xs:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)

    def test_set_enabled_kills_all_recording(self, fresh_obs, obs_store):
        reg, trc = fresh_obs
        table, store = obs_store
        obs.set_enabled(False)
        try:
            store.query().where_keys(table.keys[:64]).execute()
        finally:
            obs.set_enabled(True)
        assert len(trc) == 0
        assert reg.counter("deepmap_executor_morsels_total").value(
            kind="point") == 0


class TestKeySourceAndHostFilter:
    """The key source's ``exec.key_source`` span and the host filter's
    ``filter_host_rows`` count, with their Prometheus counters."""

    def test_range_plan_emits_one_key_source_span(self, fresh_obs, obs_store):
        reg, trc = fresh_obs
        table, store = obs_store
        lo, hi = int(table.keys[100]), int(table.keys[900])
        keys = store._range_keys(lo, hi)
        query = store.query().where_range(lo, hi).where("col0", "==", 2)
        res = query.execute()
        (span,) = trc.spans("exec.key_source")
        assert "exec.key_source" in obs.PROFILER_SPANS
        assert span.args["rows"] == len(keys) == res.explain.num_keys == 800
        assert span.args["slots"] == hi - lo
        assert span.args["kind"] == "range" and span.args["cache"] == "miss"
        assert span.duration == pytest.approx(res.explain.route_s)
        assert reg.counter("deepmap_executor_key_source_rows_total").value(
            kind="range") == len(keys)
        assert reg.counter("deepmap_executor_key_source_slots_total").value(
            kind="range") == hi - lo
        query.execute()  # the plan cache holds the key stream: no walk
        again = trc.spans("exec.key_source")[-1]
        assert (again.args["cache"], again.args["slots"], again.args["rows"]) == (
            "hit", 0, len(keys))

    def test_host_filter_counts_every_existing_row(self, fresh_obs, obs_store):
        reg, _ = fresh_obs
        table, store = obs_store
        res = store.query().where_keys(table.keys[:300]).where("col1", "<", 2).execute()
        assert not res.explain.kernel_filtered
        assert res.explain.filter_host_rows == 300
        assert reg.counter("deepmap_executor_filter_host_rows_total").value(
            kind="point") == 300

    def test_kernel_filter_counts_the_aux_overridden_rows(self, fresh_obs):
        """On the fused tier the host re-runs only the match bits of rows
        that ``T_aux`` overrode; planting rows there moves the count by
        exactly those rows."""
        reg, _ = fresh_obs
        table = make_periodic_table(n=1200, period=16, cards=(5, 3))
        store = DeepMappingStore.build(table, DeepMappingConfig(
            shared=(32,), private=(8,), train=TrainConfig(epochs=10, batch_size=512),
            use_pallas=True,
        ))
        planted = table.keys[10:70:2]
        store.update(planted, {"col0": np.full(30, 4, np.int32),
                               "col1": np.full(30, 2, np.int32)})
        lo, hi = int(table.keys[0]), int(table.keys[600])
        in_aux = int(store.aux.contains(store._range_keys(lo, hi)).sum())
        res = store.query().where_range(lo, hi).where("col0", "==", 4).execute()
        assert res.explain.kernel_filtered
        assert in_aux >= int(store.aux.contains(planted).sum()) > 0
        assert res.explain.filter_host_rows == in_aux
        assert reg.counter("deepmap_executor_filter_host_rows_total").value(
            kind="range") == in_aux
        assert set(planted.tolist()) <= set(res.keys.tolist())
