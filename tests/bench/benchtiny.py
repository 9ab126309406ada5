"""A copy of the benchmark's tree at a size a CPU test run can hold.

``make_root`` writes ``BENCHMARK.json``, the configuration files and
their generators, the traffic files, the loops and the per-layer readers
of the real benchmark into a directory, with the relations cut to a few
thousand rows, a narrow model, two training epochs and small requests.
The harness then runs its cells from that directory exactly as it runs
the real ones, found by name.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

ROWS = {"tpcds_customer_demographics": 3000, "tpch_lineitem_sf1": 2000}
TRAFFIC = {
    "probe_closed": {"callers": 2, "request_keys": 64, "warmup_steps": 2, "check_share": 0.5},
    "probe_closed_gaps": {"callers": 2, "request_keys": 64, "warmup_steps": 2,
                          "check_share": 0.5},
}


def make_root(tmp: str, *, rows=None) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for sub in ("configs", "traffic", "layer_metrics"):
        os.makedirs(os.path.join(tmp, "bench", sub), exist_ok=True)
    for entry in bench["configs"]:
        src = os.path.join(REPO, entry["file"])
        with open(src) as f:
            cfg = json.load(f)
        cfg["rows"] = (rows or ROWS)[cfg["name"]]
        if "scale_factor" in cfg:  # keep the rows per order of the full size
            cfg["scale_factor"] = cfg["rows"] / 6_001_215
        cfg["store"].update(shared=[32, 32], private=[16], inference_batch=1024)
        cfg["train"].update(epochs=2, batch_size=256)
        with open(os.path.join(tmp, entry["file"]), "w") as f:
            json.dump(cfg, f)
        gen = os.path.join(os.path.dirname(src), cfg["generator"])
        shutil.copy(gen, os.path.join(tmp, "bench", "configs"))
    for name in os.listdir(os.path.join(REPO, "bench", "traffic")):
        with open(os.path.join(REPO, "bench", "traffic", name)) as f:
            params = json.load(f)
        params.update(TRAFFIC.get(name[: -len(".json")], {}))
        params.setdefault("max_batch", 65536)
        with open(os.path.join(tmp, "bench", "traffic", name), "w") as f:
            json.dump(params, f)
    readers = os.path.join(REPO, "bench", "layer_metrics")
    for name in os.listdir(readers):
        if name.endswith(".py"):
            shutil.copy(os.path.join(readers, name), os.path.join(tmp, "bench", "layer_metrics"))
    shutil.copytree(os.path.join(REPO, "bench", "loops"), os.path.join(tmp, "bench", "loops"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
