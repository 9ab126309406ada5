"""Paper Fig. 9 (compression ratio over the MHAS search) and Fig. 10
(ratio/latency trade-off of sampled architectures): runs a scaled MHAS
search and dumps the sampled-architecture history."""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict

from benchmarks import common as C
from repro.core.mhas import MHASConfig, run_mhas

#: The paper's §V-A6 search (the ``MHASConfig`` defaults otherwise),
#: scaled to a CPU: narrower layers, fewer iterations, epochs and rows
#: a batch, and a longer fine-tune.
BENCH_MHAS = MHASConfig(
    layer_sizes=(32, 64, 128, 256),
    total_iters=120,
    model_iters=120,
    controller_iters=6,
    model_epochs_per_iter=2,
    model_batch=4096,
    finetune_epochs=40,
)


def run(dataset="synth_multi_high", iters=None) -> Dict:
    import dataclasses

    table = C.DATASETS[dataset]()
    cfg = BENCH_MHAS
    if iters:
        cfg = dataclasses.replace(cfg, total_iters=iters, model_iters=iters,
                                  controller_iters=max(1, iters // 20))
    t0 = time.perf_counter()
    res = run_mhas(table, cfg)
    search_s = time.perf_counter() - t0

    os.makedirs("results", exist_ok=True)
    out = {
        "dataset": dataset,
        "search_s": search_s,
        "best_ratio_estimate": res.best_ratio,
        "best_arch": {
            "trunk_depth": res.best_arch["trunk_depth"],
            "trunk_sizes": [int(s) for s in res.best_arch["trunk_sizes"]],
            "heads": {
                t: {"depth": h["depth"], "sizes": [int(s) for s in h["sizes"]]}
                for t, h in res.best_arch["heads"].items()
            },
        },
        "history": res.history,
    }
    with open(f"results/mhas_{dataset}.json", "w") as f:
        json.dump(out, f, indent=1)

    # convergence summary: mean ratio of first vs last quartile of samples
    hist = [h["ratio"] for h in res.history]
    q = max(1, len(hist) // 4)
    first, last = sum(hist[:q]) / q, sum(hist[-q:]) / q
    C.emit(
        f"mhas/{dataset}",
        search_s * 1e6,
        f"first_quartile_ratio={first:.4f};last_quartile_ratio={last:.4f};"
        f"best={res.best_ratio:.4f};samples={len(hist)}",
    )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synth_multi_high")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args()
    run(args.dataset, args.iters)


if __name__ == "__main__":
    main()
