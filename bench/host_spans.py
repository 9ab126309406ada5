#!/usr/bin/env python3
"""The program's own spans beside the device's operations.

``repro.obs`` mirrors every ``with``-span of the served path
(``repro.obs.PROFILER_SPANS``: ``serve.merge``, ``engine.wait``,
``aux.get``, ...) into the JAX profiler's host plane, on the clock of
the device's ``XLA Ops``.  This module reads them back from a trace
(:func:`load_spans`) and reduces them with the device's busy intervals:

* every idle gap of the device named by the innermost span open at its
  midpoint, the program's spans included (:func:`idle_by_span`);
* each span's seconds and count inside the window
  (:func:`seconds_in_window`);
* the share of the benchmark's own spans (``lookup_many``, ``execute``)
  that no program span covers (:func:`uncovered_share`).

The harness's traced run names gaps by the benchmark's spans alone.  Run
as a script, this measures one cell traced and prints the breakdown by
the program's spans as one JSON line (also written to ``--out``); it
checks no answer, ``bench/run.py`` does:

    python3 bench/host_spans.py --workload cd_probe --seed <n> --seconds 51

The per-layer readers of group-by cells, whose loop reports no
per-stage seconds of the new spans, read the program's tracer ring
instead (:func:`ring_window`).
"""

from __future__ import annotations

import heapq
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":  # run as a script: put the checkout on the path
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from bench import trace as trace_lib  # noqa: E402

Event = trace_lib.Event
#: the benchmark's spans that enclose one call into the store
CALLS = ("lookup_many", "execute")


def load_spans(path: str, names: Sequence[str]) -> List[Event]:
    """Events of the host plane whose name is in ``names``."""
    from jax.profiler import ProfileData

    keep = frozenset(names)
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace_lib.HOST_PLANE:
            for line in plane.lines:
                out.extend((e.name, int(e.start_ns), int(e.end_ns))
                           for e in line.events if e.name in keep)
    return out


def innermost_open(spans: Sequence[Event], times: Sequence[int]) -> List[Optional[str]]:
    """For each of the ascending ``times``, the name of the innermost
    span open then (the one opened last), or None; ``window`` is never
    a name."""
    order = sorted((s for s in spans if s[0] != "window"), key=lambda s: s[1])
    heap: List[Tuple[int, int, str]] = []  # (-start, end, name)
    out: List[Optional[str]] = []
    i = 0
    for t in times:
        while i < len(order) and order[i][1] <= t:
            name, start, end = order[i]
            heapq.heappush(heap, (-start, end, name))
            i += 1
        while heap and heap[0][1] <= t:   # closed for every later time too
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else None)
    return out


def _window(spans: Sequence[Event]) -> Tuple[int, int]:
    windows = [s for s in spans if s[0] == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one 'window' span, found {len(windows)}")
    return windows[0][1], windows[0][2]


def idle_by_span(flat: trace_lib.Flat, spans: Sequence[Event],
                 top: int = 10) -> Tuple[List[Tuple[str, float]], Dict[str, float]]:
    """The ``top`` longest idle gaps of the device in the window, and
    the idle seconds summed by name, each gap named by the innermost
    of ``spans`` open at its midpoint (``between_calls`` where none)."""
    lo, hi = _window(spans)
    gaps: List[Tuple[int, int]] = []
    for lines in flat.devices.values():
        ops = lines.get(trace_lib.OPS_LINE)
        if not ops:
            continue  # a device plane that runs no op is not a chip
        busy = trace_lib._union(trace_lib._clip(ops, lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps.extend((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s)
    gaps.sort()
    names = innermost_open(spans, [(s + e) // 2 for s, e in gaps])
    named = [(n or "between_calls", (e - s) / 1e9) for n, (s, e) in zip(names, gaps)]
    totals: Dict[str, float] = {}
    for n, sec in named:
        totals[n] = totals.get(n, 0.0) + sec
    return sorted(named, key=lambda x: -x[1])[:top], totals


def seconds_in_window(spans: Sequence[Event]) -> Dict[str, Tuple[float, int]]:
    """Each span name's seconds inside the window, clipped to it, and
    the number of its spans that overlap the window."""
    lo, hi = _window(spans)
    out: Dict[str, Tuple[float, int]] = {}
    for name, s, e in spans:
        if name != "window" and e > lo and s < hi:
            sec, n = out.get(name, (0.0, 0))
            out[name] = (sec + (min(e, hi) - max(s, lo)) / 1e9, n + 1)
    return out


def _overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def uncovered_share(spans: Sequence[Event], outer: Sequence[str],
                    inner: Sequence[str]) -> Optional[float]:
    """Share of the window's time under ``outer`` spans that no
    ``inner`` span covers; None without an ``outer`` span."""
    lo, hi = _window(spans)

    def union_of(names):
        names = frozenset(names)
        return trace_lib._union(trace_lib._clip([s for s in spans if s[0] in names], lo, hi))

    outer_iv = union_of(outer)
    total = sum(e - s for s, e in outer_iv)
    if not total:
        return None
    return 1.0 - _overlap_ns(outer_iv, union_of(inner)) / total


def ring_window(n_plans: int, names: Sequence[str]) -> Tuple[list, int]:
    """The program's spans named in ``names`` inside its latest plans,
    at most ``n_plans`` of them and only those its tracer's ring
    (``repro.obs``) still holds whole, with the number of plans they
    cover.  A plan is held whole while the ring holds the plan span
    recorded before it: every span of a later plan was recorded after
    that one.  A 51-second group-by window records more spans than the
    ring keeps, so readers count the work of the plans covered alone."""
    from repro import obs

    spans = obs.tracer().spans()
    plans = [s for s in spans if s.name == "plan"]
    k = min(n_plans, len(plans) - 1)
    if k < 1:
        return [], 0
    lo, hi = plans[-k].start, plans[-1].end
    keep = frozenset(names)
    return [s for s in spans if s.name in keep and s.start >= lo and s.end <= hi], k


def run(workload: str, seed: int, seconds: float, *, root: Optional[str] = None,
        require_tpu: bool = True) -> dict:
    """One traced window of one cell, reduced by the program's spans,
    with the values of the cell's ``program_span`` readers."""
    import gc
    import shutil
    import tempfile
    import time

    import jax

    from bench import harness
    from bench import reference as ref_lib
    from repro import obs

    root = root or harness.ROOT
    spec = harness.load_cell(root, workload)
    cfg = spec["config"]
    harness.enable_compile_cache(root)
    harness.check_device(int(spec["cell"]["chips"]), require_tpu)
    keys, columns = spec["generator"].generate(cfg, seed)
    ref = ref_lib.Reference(keys, columns)
    store_dir = tempfile.mkdtemp(prefix="bench-store-")
    try:
        store, _ = harness.build_store(cfg, ref.keys, ref.columns, store_dir)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    loop = spec["loop"].Loop(spec["traffic"], store, ref, cfg, seed)
    loop.warm_up(seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    gc.collect()
    gc.freeze()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            window = loop.run(seconds)
        jax.profiler.stop_trace()
        traced_s = time.perf_counter() - t0
        path = trace_lib.find_xplane(trace_dir)
        flat = trace_lib.load(path)
        names = tuple(obs.PROFILER_SPANS) + trace_lib.SPANS
        spans = load_spans(path, names)
    finally:
        gc.unfreeze()
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"spans": window.spans, "work": window.work, "dispatched": window.dispatched,
           "elapsed_s": window.elapsed_s, "values": window.values}
    readers = {m["name"]: harness.load_module(spec["readers"][m["name"]]).read(ctx)
               for m in spec["per_layer"] if m["source"] == "program_span"}
    out = {"workload": workload, "seed": seed, "traced_window_s": traced_s,
           "values_traced": window.values, "work": window.work,
           "program_span_metrics": readers, "stage_seconds": window.spans,
           "host_spans": {n: list(v) for n, v in sorted(seconds_in_window(spans).items())},
           "uncovered_share": {c: uncovered_share(spans, [c], obs.PROFILER_SPANS)
                               for c in CALLS}}
    if any(lines.get(trace_lib.OPS_LINE) for lines in flat.devices.values()):
        top, totals = idle_by_span(flat, spans)
        reduced = trace_lib.reduce(flat)
        out.update(idle_share=reduced.idle_share, busy_s=reduced.busy_s,
                   window_s=reduced.window_s, top_ops=reduced.top_ops,
                   idle_gaps=top,
                   idle_s_by_span=dict(sorted(totals.items(), key=lambda x: -x[1])))
    return out


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds)
    line = json.dumps(result, default=float)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
