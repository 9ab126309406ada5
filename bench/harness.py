"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

A run finds the cell by name, loads its configuration file and the
generator beside it, and its traffic file, all by name.  It generates
the relation from ``--seed``, builds the store through ``repro.build``,
saves it and serves the copy that ``repro.open`` reads back, warms up on
the cell's own traffic, measures for ``--seconds``, and compares the
answers the window returned with the plain reference.  The last line of
standard output is the result; the numbers compared, each beside its
limit, are the last lines of standard error.

Set-up (``setup_s``) runs from the start of the process to the first
timed request.  The window is measured with the profiler off; with
``--trace 1`` it is traced instead and the run reports the cell's
per-layer metrics.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from bench import flops, peaks
from bench import reference as ref_lib
from bench import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class RefusedDevice(RuntimeError):
    """JAX finds no accelerator the benchmark can measure."""


def load_module(path: str):
    name = "bench_ext_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root: str, workload: str) -> dict:
    """The cell, its entries and files, found by name under ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config_path = os.path.join(root, entry["file"])
    with open(config_path) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    loop_path = os.path.join(root, "bench", "loops", traffic["loop"] + ".py")
    if not os.path.exists(loop_path):
        raise KeyError(f"traffic {cell['traffic']!r} names no loop file {loop_path}")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "generator": load_module(os.path.join(os.path.dirname(config_path),
                                              config["generator"])),
        "traffic": traffic,
        "loop": load_module(loop_path),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "readers": {m["name"]: os.path.join(root, "bench", "layer_metrics", m["name"] + ".py")
                    for m in bench["per_layer"] if applies(m)},
    }


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<root>/.jax_cache`` (a
    fixed path: it is part of what a later run must find), unless
    ``JAX_COMPILATION_CACHE_DIR`` names one.  Every program is cached,
    however small, so only the first run in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles, from JAX's own compile events."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def check_device(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise RefusedDevice(f"JAX finds no TPU (platform {dev.platform!r})")
        if len(devices) < chips:
            raise RefusedDevice(f"the cell needs {chips} chips, JAX finds {len(devices)}")
        try:
            peaks.peaks(dev.device_kind)
        except KeyError as e:
            raise RefusedDevice(str(e)) from None
    return devices


def user_bytes(ref: ref_lib.Reference) -> int:
    """Bytes of the relation as a user holds it: an 8-byte key per row,
    4 bytes per integer value, the UTF-8 bytes of each string value."""
    total = 8 * ref.num_rows
    for dom, idx in ref.columns.values():
        if dom.dtype.kind == "U":
            sizes = np.array([len(str(v).encode()) for v in dom])
        else:
            sizes = np.full(dom.size, dom.dtype.itemsize)
        total += int(np.bincount(idx, minlength=dom.size) @ sizes)
    return total


def dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path) for f in files)


def build_store(cfg: dict, keys, columns, path: str):
    """Build through ``repro.build``, save to ``path``, and return the
    store ``repro.open`` reads back, with the fresh build's seconds.  The
    model's initialisation is the configuration's ``train.seed``, not the
    run's: the same relation gives the same store, and so the same work,
    whatever seed draws the traffic."""
    import repro
    from repro.core import DeepMappingConfig, Table
    from repro.core.trainer import TrainConfig
    from repro.storage import MemoryPool

    store_cfg, train = cfg["store"], cfg["train"]
    config = DeepMappingConfig(
        base=store_cfg["base"], shared=tuple(store_cfg["shared"]),
        private=tuple(store_cfg["private"]), codec=store_cfg["codec"],
        partition_bytes=store_cfg["partition_bytes"], dtype=store_cfg["dtype"],
        use_pallas=store_cfg["use_pallas"], inference_batch=store_cfg["inference_batch"],
        train=TrainConfig(
            epochs=train["epochs"], batch_size=train["batch_size"], lr=train["lr"],
            lr_decay=train["lr_decay"], early_stop_tol=train["early_stop_tol"],
            seed=int(train["seed"]),
        ),
    )
    t0 = time.perf_counter()
    table = Table(keys=keys, columns={c: dom[idx] for c, (dom, idx) in columns.items()})
    built = repro.build(table, config)
    build_s = time.perf_counter() - t0
    built.save(path)
    del built, table
    store = repro.open(path, pool=MemoryPool(int(store_cfg["aux_pool_bytes"])))
    return store, build_s


def lower_precision_weights(store) -> None:
    """The control: the store's model served with its weights rounded to
    bfloat16, against the ``T_aux`` built for the float32 model."""
    import jax
    import jax.numpy as jnp
    from repro.core.inference import InferenceEngine

    store.params = jax.tree.map(
        lambda w: w.astype(jnp.bfloat16).astype(w.dtype), store.params)
    store.attach_engine(InferenceEngine.for_store(store))


def engine_counts(store) -> Dict[str, int]:
    s = store.engine.stats
    return {"fused_calls": s.fused_calls, "fused_streamed_calls": s.fused_streamed_calls,
            "pallas_calls": s.pallas_calls, "jit_calls": s.jit_calls,
            "signatures": s.compiles}


def run(workload: str, seed: int, seconds: float, trace: bool, *, root: str = ROOT,
        t_start: Optional[float] = None, require_tpu: bool = True,
        control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict (after
    printing it).  Raises :class:`RefusedDevice` before any work when
    ``require_tpu`` and JAX finds no usable TPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_cell(root, workload)
    cell, cfg, params = spec["cell"], spec["config"], spec["traffic"]
    seed = seed % (1 << 64)
    cache_dir = enable_compile_cache(root)
    devices = check_device(int(cell["chips"]), require_tpu)
    dev = devices[0]
    import jax

    compiles = CompileCounter()
    keys, columns = spec["generator"].generate(cfg, seed)
    ref = ref_lib.Reference(keys, columns)
    del keys
    store_dir = tempfile.mkdtemp(prefix="bench-store-")
    try:
        store, build_s = build_store(cfg, ref.keys, ref.columns, store_dir)
        stored = dir_bytes(store_dir)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    del columns
    if control:
        lower_precision_weights(store)
    loop = spec["loop"].Loop(params, store, ref, cfg, seed)
    loop.warm_up(seed)
    pool = store.aux.pool
    pool_before = (pool.hits, pool.misses)
    engine_before = engine_counts(store)
    compiles_before = compiles.count
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    # Set-up's garbage is collected now, and what it keeps is left out of
    # the collections the window pays for.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    try:
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host spans only, no per-call events
            options.host_tracer_level = 2
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            window = loop.run(seconds)
        if trace:
            jax.profiler.stop_trace()
            reduced = trace_lib.reduce(trace_lib.load(trace_lib.find_xplane(trace_dir)))
    finally:
        gc.unfreeze()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    engine_after = engine_counts(store)
    info = {
        "workload": workload, "seed": seed,
        "build_s": build_s, "memorized_fraction": store.memorized_fraction(),
        "aux_rows": store.aux.num_rows, "rows": ref.num_rows,
        "stored_bytes": stored, "user_bytes": user_bytes(ref),
        "engine_in_window": {k: engine_after[k] - engine_before[k] for k in engine_after},
        "backend_compiles_in_window": compiles.count - compiles_before,
        "backend_compile_s_total": compiles.seconds,
        "aux_pool_hits_misses_in_window": [pool.hits - pool_before[0],
                                           pool.misses - pool_before[1]],
        "program_spans": window.spans, "compile_cache": cache_dir,
        "control_bf16_weights": control,
    }
    store_spec, compare = store.spec, type(loop).compare
    del loop, store
    with jax.profiler.TraceAnnotation("reference"):
        verdict = compare(ref, window.kept)
    failed = verdict.pop("failed")
    checks = {name: {"value": v, "limit": 0, "holds": "<="}
              for name, v in verdict.items() if name.startswith("wrong_")}
    checks.update({name: {"value": v, "limit": 1, "holds": ">="}
                   for name, v in verdict.items() if name.startswith("checked_")})
    correct = all(c["value"] <= c["limit"] if c["holds"] == "<=" else c["value"] >= c["limit"]
                  for c in checks.values())

    model = (store_spec.feature_dim, store_spec.shared, store_spec.private_map,
             store_spec.card_map)
    peak = peaks.PEAKS.get(dev.device_kind)
    values = {
        "setup_s": setup_s,
        "stored_bytes_per_user_byte": info["stored_bytes"] / info["user_bytes"],
        **window.values,
    }
    result_metrics = {}
    breakdown = None
    if not trace:
        for m in spec["end_to_end"]:
            if m["name"] not in values:
                raise KeyError(f"loop {params['loop']!r} yields no {m['name']!r}; "
                               f"it yields {sorted(values)}")
            result_metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = {
            "spans": window.spans, "work": window.work, "elapsed_s": window.elapsed_s,
            "values": values, "trace": reduced, "peak": peak, "model": model,
            "dispatched": window.dispatched, "engine": info["engine_in_window"],
            "model_ops": flops.model_ops(model, window.dispatched),
        }
        info["trace"] = {"modules": reduced.modules, "dispatched": window.dispatched}
        for m in spec["per_layer"]:
            v = load_module(spec["readers"][m["name"]]).read(ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": [list(x) for x in reduced.top_ops],
                     "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    result = {
        "correct": bool(correct),
        "attempted": window.attempted,
        "failed": failed,
        "metrics": result_metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = breakdown
    result["checks"] = checks
    print("info " + json.dumps(info, default=float), flush=True)
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['holds']} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run(args.workload, args.seed, args.seconds, bool(args.trace), t_start=t_start)
    except RefusedDevice as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    return 0
