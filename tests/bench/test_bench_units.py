"""The benchmark's yardstick pieces: operation and byte counts, the
peaks table, the plain reference's comparisons, and the trace
reduction (on events made by hand and on a trace recorded here)."""

import os

import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the repository root on sys.path)
from bench import flops, peaks
from bench import reference as ref_lib
from bench import trace as trace_lib


def test_flops_of_a_small_spec_by_hand():
    # feature_dim 3*10=30 -> shared 8 -> heads a (private 4, card 3), b (no private, card 5)
    layers = flops.mlp_layers(30, [8], {"a": [4], "b": []}, {"a": 3, "b": 5})
    assert layers == [(30, 8), (8, 4), (4, 3), (8, 5)]
    assert flops.ops_per_key(layers) == 2 * (240 + 32 + 12 + 40)
    assert flops.weight_bytes(layers) == 4 * (248 + 36 + 15 + 45)
    assert flops.bytes_per_key(2) == 4 + 8 + 4 + 4
    model = (30, [8], {"a": [4], "b": []}, {"a": 3, "b": 5})
    assert flops.mlp_layers(*model, ["b"]) == [(30, 8), (8, 5)]
    assert flops.model_ops(model, [(("a", "b"), 10), (("b",), 3)]) == 648 * 10 + 2 * 280 * 3
    peak = {"flops_bf16": 1e6, "hbm_bytes_per_s": 1e3}
    t, bound = flops.roofline_seconds(model, [(("a", "b"), 10)], calls=1, peak=peak)
    assert bound == "memory" and t == pytest.approx((20 * 10 + 4 * 344) / 1e3)
    t, bound = flops.roofline_seconds(model, [(("a", "b"), 10)], calls=1,
                                      peak={"flops_bf16": 1.0, "hbm_bytes_per_s": 1e9})
    assert bound == "compute" and t == 648 * 10
    # each call is charged the weights of the fewest heads asked for
    t, _ = flops.roofline_seconds(model, [(("a", "b"), 10), (("b",), 3)], calls=2, peak=peak)
    assert t == pytest.approx((20 * 10 + 16 * 3 + 2 * 4 * (248 + 45)) / 1e3)


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def small_reference():
    keys = np.array([10, 3, 7, 20], dtype=np.int64)
    columns = {
        "s": (np.array(["x", "yy"]), np.array([1, 0, 0, 1], dtype=np.int32)),
        "n": (np.array([5, 6, 7], dtype=np.int32), np.array([2, 2, 0, 1], dtype=np.int32)),
    }
    return ref_lib.Reference(keys, columns)


def test_reference_counts_one_corrupted_value():
    ref = small_reference()
    keys = np.array([3, 4, 20, 10], dtype=np.int64)
    exists = np.array([True, False, True, True])
    values = {"s": np.array(["x", "x", "yy", "yy"]), "n": np.array([7, 5, 6, 7], np.int32)}
    assert ref_lib.wrong_answers(ref, keys, values, exists) == (0, 0)
    values["n"][2] = 5
    assert ref_lib.wrong_answers(ref, keys, values, exists) == (0, 1)
    exists[1] = True
    assert ref_lib.wrong_answers(ref, keys, values, exists) == (1, 1)
    del values["s"]
    assert ref_lib.wrong_answers(ref, keys, values, exists)[1] == 1 + 3


def test_reference_group_counts_and_gaps():
    ref = small_reference()
    assert ref.group_counts("s") == {"x": 2, "yy": 2}
    assert ref_lib.wrong_groups(ref, "s", ["x", "yy"], [2, 2]) == 0
    assert ref_lib.wrong_groups(ref, "s", ["x", "yy"], [3, 2]) == 1
    assert ref_lib.wrong_groups(ref, "s", ["x"], [2]) == 1
    gaps = ref.gap_keys(np.random.default_rng(0), 50)
    assert gaps.size == 50 and not ref.lookup(gaps)[1].any()
    assert gaps.min() >= 3 and gaps.max() <= 20


def test_trace_reduction_on_events_made_by_hand():
    ms = 1_000_000
    flat = trace_lib.Flat(
        devices={"/device:TPU:0": {
            "XLA Ops": [("fusion", 10 * ms, 20 * ms), ("copy", 15 * ms, 30 * ms),
                        ("fusion", 60 * ms, 70 * ms), ("late", 120 * ms, 130 * ms)],
            "XLA Modules": [("jit_fused_lookup_call(1)", 10 * ms, 30 * ms),
                            ("jit_other", 60 * ms, 70 * ms)],
        }},
        spans=[("window", 0, 100 * ms), ("lookup_many", 5 * ms, 55 * ms),
               ("reference", 100 * ms, 140 * ms)],
    )
    r = trace_lib.reduce(flat)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.03)
    assert r.idle_share == pytest.approx(0.7)
    assert r.modules["jit_fused_lookup_call"] == (pytest.approx(0.02), 1)
    assert r.modules["jit_other"] == (pytest.approx(0.01), 1)
    assert r.top_ops[0] == ("fusion", pytest.approx(0.02))
    assert r.idle_gaps == [("lookup_many", pytest.approx(0.03)),
                           ("between_calls", pytest.approx(0.03)),
                           ("lookup_many", pytest.approx(0.01))]


def test_trace_device_plane_without_ops_is_not_a_chip():
    """A TPU trace holds device planes besides the chip's that run no op;
    they must not halve the busy time or add a window-long idle gap."""
    ms = 1_000_000
    ops = {"XLA Ops": [("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 10 * ms, 40 * ms)]}
    flat = trace_lib.Flat(devices={"/device:TPU:0": ops, "/device:TPU:0 extra": {}},
                          spans=[("window", 0, 100 * ms)])
    r = trace_lib.reduce(flat)
    assert r.busy_s == pytest.approx(0.03)
    assert r.idle_share == pytest.approx(0.7)
    assert r.top_ops == [("%fusion.1", pytest.approx(0.03))]
    assert r.idle_gaps[0] == ("between_calls", pytest.approx(0.06))


def test_trace_spans_from_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("lookup_many"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    flat = trace_lib.load(trace_lib.find_xplane(str(tmp_path)))
    names = [s[0] for s in flat.spans]
    assert names.count("window") == 1 and names.count("lookup_many") == 3
    (_, lo, hi), = [s for s in flat.spans if s[0] == "window"]
    assert all(lo <= s <= e <= hi for n, s, e in flat.spans if n == "lookup_many")
    # the CPU has no device plane; give the window one op to reduce
    flat.devices = {"/device:TPU:0": {"XLA Ops": [("op", lo, lo + (hi - lo) // 2)]}}
    r = trace_lib.reduce(flat)
    assert r.idle_share == pytest.approx(0.5, abs=1e-3)


def load_generator(name):
    from bench import harness

    return harness.load_module(os.path.join(benchtiny.REPO, "bench", "configs", name + ".py"))


def test_customer_demographics_rows_as_dsdgen_nests_them():
    gen = load_generator("tpcds_customer_demographics")
    keys, columns = gen.generate({"rows": 1_920_800}, 0)
    row = lambda i: [dom[idx[i]].item() for dom, idx in columns.values()]  # noqa: E731
    assert row(0) == ["M", "M", "Primary", 500, "Good", 0, 0, 0]
    assert row(1) == ["F", "M", "Primary", 500, "Good", 0, 0, 0]
    assert row(10) == ["M", "M", "Secondary", 500, "Good", 0, 0, 0]
    assert row(1_920_799) == ["F", "U", "Unknown", 10000, "Unknown", 6, 6, 6]
    assert keys[0] == 1 and keys[-1] == 1_920_800


def test_lineitem_follows_dbgen_rules():
    gen = load_generator("tpch_lineitem_sf1")
    rows = 40_000
    keys, columns = gen.generate({"rows": rows, "scale_factor": rows / 6_001_215}, 2**31 + 7)
    assert keys.size == rows and np.unique(keys).size == rows
    order, line = keys // 8, keys % 8
    assert line.min() == 1 and line.max() == 7
    assert np.all(order % 32 < 8)  # sparse orderkeys: 8 of every 32
    value = {n: dom[idx] for n, (dom, idx) in columns.items()}
    day = {n: value[n].astype("datetime64[D]") for n in ("l_shipdate", "l_commitdate",
                                                          "l_receiptdate")}
    receipt_lag = (day["l_receiptdate"] - day["l_shipdate"]).astype(int)
    assert receipt_lag.min() >= 1 and receipt_lag.max() <= 30
    current = np.datetime64("1995-06-17")
    assert set(value["l_returnflag"][day["l_receiptdate"] > current]) == {"N"}
    assert set(value["l_returnflag"][day["l_receiptdate"] <= current]) == {"R", "A"}
    assert set(value["l_linestatus"][day["l_shipdate"] > current]) == {"O"}
    assert set(value["l_linestatus"][day["l_shipdate"] <= current]) == {"F"}
    parts, supps = value["l_partkey"], value["l_suppkey"]
    s = max(1, round(10_000 * rows / 6_001_215))
    bridge = {(p + i * (s // 4 + (p - 1) // s)) % s + 1 for p in parts[:1] for i in range(4)}
    assert supps[0] in bridge
    assert value["l_quantity"].min() >= 1 and value["l_quantity"].max() <= 50


def test_zipf_keys_skew_and_stay_stored():
    from bench import loop_lib

    ref = small_reference()
    draw = loop_lib.KeyDraw(ref, {"key_dist": "zipf", "zipf_theta": 0.99}, seed=3)
    keys = draw(np.random.default_rng(0), (20_000,))
    assert set(np.unique(keys)) <= set(ref.keys.tolist())
    counts = np.sort(np.unique(keys, return_counts=True)[1])[::-1]
    assert counts[0] > 1.5 * counts[1]  # rank 1 drawn about twice as often as rank 2
    with pytest.raises(ValueError):
        loop_lib.KeyDraw(ref, {"key_dist": "hotspot"}, seed=3)
