"""Quickstart: compress a table into a DeepMapping hybrid structure,
query it through the streaming plan API — projection and value-
predicate pushdown, cross-store federation — modify, and measure Eq. 1.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --shards 4 --policy range

Every store (single, sharded, baselines, federated) implements the
same ``MappingStore`` protocol; ``repro.build`` picks single-vs-sharded
from the cluster config and ``repro.open`` re-loads whatever was saved.
"""

import argparse
import os
import tempfile

import numpy as np

import repro
from repro.core import DeepMappingConfig, Table
from repro.core.trainer import TrainConfig


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shards", type=int, default=1,
                    help="number of cluster shards (1 = single store)")
    ap.add_argument("--policy", default="range", choices=("range", "hash"),
                    help="cluster partition policy (with --shards > 1)")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="where to write metrics.prom / metrics.json / "
                         "trace.json (default: a temp dir)")
    args = ap.parse_args()

    # A small relation: order_id -> (status, priority).  Values follow a
    # periodic pattern along the key (the paper's high-correlation regime).
    n = 20_000
    keys = np.arange(n, dtype=np.int64) * 2  # sparse even keys
    table = Table(
        keys=keys,
        columns={
            "status": np.array(["F", "O", "P"])[(keys // 64) % 3],
            "priority": ((keys // 128) % 5).astype(np.int32),
        },
    )

    cfg = DeepMappingConfig(
        shared=(128, 64),
        private=(16,),
        codec="zstd",
        train=TrainConfig(epochs=40, batch_size=4096),
    )
    cluster = None
    if args.shards > 1:
        from repro.cluster import ClusterConfig

        cluster = ClusterConfig(num_shards=args.shards, policy=args.policy)
    store = repro.build(table, cfg, cluster=cluster, verbose=True)
    if args.shards > 1:
        print(f"  {store.num_shards} {args.policy} shards, "
              f"rows/shard: {[s.num_rows for s in store.shards]}")

    print("\n-- Eq.1 accounting ------------------------------")
    for k, v in store.size_breakdown().items():
        print(f"  {k:>16}: {v:,} bytes")
    print(f"  compression ratio: {store.compression_ratio():.4f}")
    print(f"  memorized by model: {store.memorized_fraction():.1%}")

    print("\n-- Point query (Algorithm 1) ---------------------")
    q = np.array([0, 2, 128, 3, 999_999], dtype=np.int64)
    res = store.query().where_keys(q).execute()
    for i, k in enumerate(q):
        if res.exists[i]:
            print(f"  key {k}: status={res.values['status'][i]} "
                  f"priority={res.values['priority'][i]}")
        else:
            print(f"  key {k}: NULL (existence bitvector)")
    print(f"  plan: {' -> '.join(res.explain.plan)}")

    print("\n-- Projection pushdown ---------------------------")
    res = store.query().select("status").where_keys(table.keys[:1000]).execute()
    print(f"  heads evaluated: {res.explain.heads_evaluated}, "
          f"skipped: {res.explain.heads_skipped}")
    print(f"  columns decoded: {res.explain.columns_decoded}, "
          f"skipped: {res.explain.columns_skipped}")

    print("\n-- Range query (§IV-E) ---------------------------")
    res = store.query().select("priority").where_range(0, 1024).execute()
    print(f"  [0, 1024) -> {res.keys.shape[0]} rows, "
          f"priorities {sorted(set(res.values['priority'].tolist()))}")

    print("\n-- Value-predicate pushdown (.where) -------------")
    # Pushed below decode: the predicate evaluates on argmax codes, so
    # non-matching rows are never decoded (see rows_decoded evidence).
    res = (
        store.query().select("priority")
        .where("status", "==", "F").where("priority", ">=", 3)
        .scan().execute()
    )
    ref = (
        store.query().select("priority")
        .where("status", "==", "F").where("priority", ">=", 3)
        .pushdown(False).scan().execute()  # post-hoc reference filter
    )
    assert res.keys.tobytes() == ref.keys.tobytes()
    print(f"  status=='F' AND priority>=3 -> {res.keys.shape[0]} rows")
    print(f"  pushdown decoded {res.explain.rows_decoded}/{res.explain.num_keys} "
          f"rows; post-hoc decoded {ref.explain.rows_decoded}")
    print("  operators: " + " -> ".join(
        f"{o.name}[{o.rows_in}->{o.rows_out}]" for o in res.explain.operators
    ))

    print("\n-- Plan cache (adaptive execution) ---------------")
    # Repeated plans reuse the materialized key stream + compiled
    # predicate code tables; mutations invalidate via the store's
    # mutation version (DESIGN.md §Adaptive execution).
    repeated = lambda: (  # noqa: E731
        store.query().where("status", "==", "F").scan().execute()
    )
    cold, warm = repeated(), repeated()
    print(f"  first run:  plan_cache={cold.explain.plan_cache!r}")
    print(f"  second run: plan_cache={warm.explain.plan_cache!r} "
          f"(key stream + code tables resident)")
    assert warm.keys.tobytes() == cold.keys.tobytes()

    print("\n-- Modifications (Algorithms 3-5) ----------------")
    store.insert(
        np.array([10**6], dtype=np.int64),
        {"status": np.array(["X"]), "priority": np.array([9], np.int32)},
    )
    v, e = store.lookup(np.array([10**6]))
    print(f"  inserted unseen category: status={v['status'][0]} (exists={e[0]})")
    store.update(
        np.array([0], dtype=np.int64),
        {"status": np.array(["P"]), "priority": np.array([4], np.int32)},
    )
    v, _ = store.lookup(np.array([0]))
    print(f"  updated key 0: status={v['status'][0]} priority={v['priority'][0]}")
    store.delete(np.array([2], dtype=np.int64))
    _, e = store.lookup(np.array([2]))
    print(f"  deleted key 2: exists={e[0]}")

    print("\n-- save / repro.open round-trip ------------------")
    path = os.path.join(tempfile.mkdtemp(), "store")
    store.save(path)
    restored = repro.open(path)
    res = restored.query().where_keys(np.array([0, 2, 10**6])).execute()
    print(f"  reopened as {type(restored).__name__}; "
          f"exists={res.exists.tolist()}")

    print("\n-- Cross-store federation ------------------------")
    # Two stores over disjoint key spaces behind one plan surface: the
    # DeepMapping store keeps its keys, a HashStore replica owns a
    # second key range starting at 10**7.
    from repro.api import FederatedStore
    from repro.baselines import HashStore

    hi_keys = np.arange(10**7, 10**7 + 5000, 2, dtype=np.int64)
    hi_table = Table(
        keys=hi_keys,
        columns={
            "status": np.array(["F", "O", "P"])[(hi_keys // 64) % 3],
            "priority": ((hi_keys // 128) % 5).astype(np.int32),
        },
    )
    fed = FederatedStore(
        [store, HashStore.build(hi_table)],
        mode="partition",
        boundaries=[10**7],
    )
    res = fed.query().where("priority", "==", 4).where_range(0, 10**8).execute()
    print(f"  {fed.num_rows:,} rows across {len(fed.members)} member stores")
    print(f"  priority==4 over both members -> {res.keys.shape[0]} rows "
          f"(min key {res.keys.min()}, max key {res.keys.max()})")
    print(f"  plan: {' -> '.join(res.explain.plan[:3])} ...")

    if args.shards > 1:
        print("\n-- Per-shard lazy retrain ------------------------")
        print(f"  dirty shards after modifications: {store.dirty_shards() or 'none'}")
        print(f"  range scatter [0, 1000): shards "
              f"{store.partitioner.shards_for_range(0, 1000).tolist()}")

    print("\n-- Observability (metrics + trace export) --------")
    # Everything above already recorded into the process-global metrics
    # registry and span tracer (always on).  Run one more multi-morsel
    # scan — small morsels force many dispatch/collect rounds, so the
    # executor's pipelining (device infer of morsel i+1 overlapping the
    # host half of morsel i) is visible in the trace — then export all
    # three sinks from this one process.
    from repro import obs

    store.query().morsel(2048).scan().execute()
    out_dir = args.telemetry_dir or tempfile.mkdtemp(prefix="deepmap_obs_")
    prom = obs.write_prometheus(os.path.join(out_dir, "metrics.prom"))
    snap = obs.write_json_snapshot(os.path.join(out_dir, "metrics.json"))
    trace = obs.write_chrome_trace(os.path.join(out_dir, "trace.json"))
    morsels = obs.registry().get("deepmap_executor_morsels_total")
    plan_lat = obs.registry().get("deepmap_executor_plan_seconds")
    print(f"  morsels executed: {int(sum(v for _, v in morsels.items()))}; "
          f"scan plan p50 {plan_lat.quantile(0.5, kind='scan')*1e3:.1f} ms")
    # Within the scan's plan span, morsel i+1 is dispatched (an
    # engine.dispatch span outside any collect) before the collect span
    # of morsel i ends.
    plan = obs.tracer().spans("plan")[-1]

    def in_plan(name):
        return sorted((s for s in obs.tracer().spans(name)
                       if plan.start <= s.start and s.end <= plan.end),
                      key=lambda s: s.start)

    dispatch = [s for s in in_plan("engine.dispatch") if not s.parent]
    collect = in_plan("collect")
    overlaps = sum(1 for d, c in zip(dispatch[1:], collect) if d.start < c.end)
    print(f"  trace: {len(dispatch)} engine.dispatch spans, "
          f"{len(collect)} collect spans, "
          f"{overlaps} pipelined overlaps (dispatch i+1 before collect i ends)")
    print(f"  Prometheus text:   {prom}")
    print(f"  JSON snapshot:     {snap}")
    print(f"  Chrome trace:      {trace}  (open at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
