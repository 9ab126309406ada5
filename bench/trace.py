"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time and idle share in the traced
window, the device time and call count of one XLA module, the device
operations that took most time, and the longest idle gaps, each named by
the benchmark's own host span that was open at the time.

The trace is first flattened to plain tuples (:func:`load`), so the
reduction (:func:`reduce`) can be checked on events made by hand.
Module launches are summed by the name before its ``(<id>)`` suffix.

On a TPU the device planes are named ``/device:TPU:<n>``; their line
``XLA Ops`` holds one event per operation that ran and ``XLA Modules``
one per program launch.  The benchmark's spans are
``jax.profiler.TraceAnnotation`` events on the host plane ``/host:CPU``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's own spans; ``window`` encloses the measured window
SPANS = ("window", "lookup_many", "execute", "reference", "warmup")

Event = Tuple[str, int, int]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Flat:
    #: device plane name -> line name -> events
    devices: Dict[str, Dict[str, List[Event]]]
    #: the benchmark's host spans
    spans: List[Event]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float               # mean over device planes
    idle_share: float
    #: module name (its ``(<id>)`` suffix cut) -> (device seconds, launches),
    #: summed over device planes
    modules: Dict[str, Tuple[float, int]]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, found {paths}")
    return paths[0]


def load(path: str) -> Flat:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {
                line.name: [(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]
                for line in plane.lines
            }
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events if e.name in SPANS)
    return Flat(devices, spans)


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for _, s, e in events if e > lo and s < hi]


def _open_span(spans: Sequence[Event], t: int) -> str:
    """The innermost benchmark span open at ``t`` inside the window;
    ``between_calls`` where the loop was between its calls."""
    best: Optional[Event] = None
    for span in spans:
        if (span[0] != "window" and span[1] <= t < span[2]
                and (best is None or span[1] >= best[1])):
            best = span
    return best[0] if best else "between_calls"


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """An op event on a TPU is named by its whole HLO instruction
    (``%fusion.3 = s32[...] fusion(...)``); keep the name before ``=``."""
    return event_name.split(" = ", 1)[0]


def reduce(flat: Flat, top: int = 10) -> Reduced:
    windows = [s for s in flat.spans if s[0] == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one 'window' span in the trace, found {len(windows)}")
    _, lo, hi = windows[0]
    # A TPU trace also holds device planes that run no op; they are not chips.
    chips = [lines for lines in flat.devices.values() if lines.get(OPS_LINE)]
    if not chips:
        raise RuntimeError("the trace holds no device plane with an op")
    busy_ns = []
    modules: Dict[str, List[int]] = {}
    op_ns: Dict[str, int] = {}
    gaps: List[Tuple[str, float]] = []
    for lines in chips:
        ops = lines.get(OPS_LINE, [])
        busy = _union(_clip(ops, lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            if e > lo and s < hi:
                name = op_name(name)
                op_ns[name] = op_ns.get(name, 0) + min(e, hi) - max(s, lo)
        for name, s, e in lines.get(MODULES_LINE, []):
            if e > lo and s < hi:
                m = modules.setdefault(module_name(name), [0, 0])
                m[0] += min(e, hi) - max(s, lo)
                m[1] += 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_open_span(flat.spans, (s + e) // 2), (e - s) / 1e9))
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    return Reduced(
        window_s=window_s,
        busy_s=busy_s,
        idle_share=1.0 - busy_s / window_s,
        modules={n: (t / 1e9, c) for n, (t, c) in modules.items()},
        top_ops=sorted(((n, t / 1e9) for n, t in op_ns.items()), key=lambda x: -x[1])[:top],
        idle_gaps=sorted(gaps, key=lambda x: -x[1])[:top],
    )
