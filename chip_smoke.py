#!/usr/bin/env python3
"""End-to-end smoke run of the DeepMapping store on a TPU.

    python chip_smoke.py             # one chip: TPC-H lineitem at SF1
    python chip_smoke.py --chips 4   # four chips: mesh scatter vs thread pool

One chip: builds ``lineitem_like`` at the SF1 row count (6,001,215)
through ``repro.build`` with the default ``DeepMappingConfig`` widths
and ``use_pallas=True``, then drives the store's entry points and holds
every answer to the source table byte for byte — every stored key plus
65,536 absent ones, a range query, a predicate query filtered in the
kernel, a count-only group-by, inserts/updates/deletes, save and
``repro.open``, and ``LookupServer`` requests.  It then runs the
``fused_streamed`` and ``jit_keys`` tiers on the same store and prints
how many codes each tier's argmax gives differently from ``fused``.

Four chips: a 4-shard cluster of the same relation answers every key
once through the one-launch mesh scatter and once through the thread
pool; both must equal the table.  The scatter runs the jit ladder's XLA
forward, so that cluster is built with ``use_pallas=False``; a second
4-shard cluster built with ``use_pallas=True`` must decline the scatter
(its aux tables correct Pallas codes) and still equal the table.

Everything is made from ``--seed``; nothing is read from disk.  The last
line of a passing run is one JSON object naming the device.  Off the TPU
the script stops at once with exit code 1; ``--rehearse --rows N`` runs
every phase on whatever backend JAX finds (for a CPU rehearsal at a
small size) and still exits 1 without printing a result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

SF1_LINEITEM_ROWS = 6_001_215
ABSENT_KEYS = 65_536
MUTATED_KEYS = 300
SERVER_REQUESTS = 8
SERVER_REQUEST_KEYS = 4096


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class Clock:
    """Host wall time per phase, plus backend compile seconds from
    JAX's own compile events (set-up, not device time)."""

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"phase {name}", flush=True)
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        print(f"  {name}: host wall {time.perf_counter() - t0:.3f}s "
              f"(compile {self.compile_s - c0:.3f}s)", flush=True)


# ------------------------------------------------------------ reference
class Reference:
    """The relation as plain sorted numpy arrays — the oracle every
    answer is compared with, kept in step with each mutation."""

    def __init__(self, keys: np.ndarray, columns: dict):
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.columns = {c: v[order] for c, v in columns.items()}

    def lookup(self, keys: np.ndarray):
        pos = np.searchsorted(self.keys, keys)
        pos = np.minimum(pos, self.keys.shape[0] - 1)
        exists = self.keys[pos] == keys
        return pos, exists

    def insert(self, keys, columns):
        self.__init__(
            np.concatenate([self.keys, keys]),
            {c: np.concatenate([v, columns[c]]) for c, v in self.columns.items()},
        )

    def update(self, keys, columns):
        pos, exists = self.lookup(keys)
        assert exists.all()
        for c, v in columns.items():
            self.columns[c][pos] = v

    def delete(self, keys):
        keep = ~np.isin(self.keys, keys)
        self.keys = self.keys[keep]
        self.columns = {c: v[keep] for c, v in self.columns.items()}


def check_values(ref: Reference, keys, values, exists, what: str) -> None:
    """Exists bits and every column of every existing row equal the
    reference (dtype kind included); absent rows must say so."""
    pos, want = ref.lookup(keys)
    check(np.array_equal(np.asarray(exists, dtype=bool), want),
          f"{what}: existence of {keys.shape[0]} keys ({int(want.sum())} present)")
    hit = np.flatnonzero(want)
    for c, col in ref.columns.items():
        got = np.asarray(values[c])
        if got.dtype.kind != col.dtype.kind or not np.array_equal(
            got[hit], col[pos[hit]]
        ):
            raise SmokeFailure(f"{what}: column {c} differs from the table")
    print(f"  ok: {what}: {hit.size} rows x {len(ref.columns)} columns equal the table",
          flush=True)


def absent_keys(rng, ref: Reference, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` distinct keys in ``[lo, hi)`` that the relation lacks."""
    if hi - lo - ref.keys.size < 2 * n:
        raise ValueError(f"[{lo}, {hi}) is too dense to hold {n} absent keys")
    out = np.empty(0, dtype=np.int64)
    while out.size < n:
        cand = rng.integers(lo, hi, size=2 * n, dtype=np.int64)
        cand = cand[~ref.lookup(cand)[1]]
        out = np.unique(np.concatenate([out, cand]))
    return rng.permutation(out)[:n]


def random_rows(rng, ref: Reference, n: int) -> dict:
    """``n`` rows of values drawn from each column's own domain."""
    domains = {c: np.unique(v) for c, v in ref.columns.items()}
    return {c: d[rng.integers(0, d.size, n)] for c, d in domains.items()}


def query_values(store, keys, what, ref):
    res = store.query().where_keys(keys).execute()
    check_values(ref, keys, res.values, res.exists, what)
    return res


# ------------------------------------------------------------ one chip
def one_chip(args, clock: Clock, on_tpu: bool) -> None:
    import repro
    from repro.core import DeepMappingConfig
    from repro.core.inference import InferenceEngine
    from repro.core.trainer import TrainConfig
    from repro.data.tpch import lineitem_like
    from repro.kernels import ops as kops
    from repro.serve import LookupServer

    rng = np.random.default_rng(args.seed)
    with clock.phase("data"):
        table = lineitem_like(n=args.rows, seed=args.seed)
        ref = Reference(table.keys, table.columns)
        print(f"  lineitem_like rows={table.num_rows} columns={list(table.columns)} "
              f"max_key={int(table.keys.max())}", flush=True)

    cfg = DeepMappingConfig(
        use_pallas=True, train=TrainConfig(epochs=args.epochs, seed=args.seed)
    )
    print(f"reduction: training capped at {args.epochs} epochs "
          f"(TrainConfig default {TrainConfig().epochs}); widths are the "
          f"DeepMappingConfig defaults shared={cfg.shared} private={cfg.private}",
          flush=True)
    with clock.phase("build (set-up: train + T_aux + compile)"):
        store = repro.build(table, cfg)
        print(f"  memorized {store.memorized_fraction():.4f} of rows; "
              f"aux rows {store.aux.num_rows}; ratio {store.compression_ratio():.4f}",
              flush=True)
    engine = store.engine
    check(engine.interpret is (not on_tpu),
          f"engine.interpret={engine.interpret} on {jax.devices()[0].platform}")

    max_key = int(table.keys.max())
    absent = absent_keys(rng, ref, ABSENT_KEYS, -1000, 2 * max_key + 4 * ABSENT_KEYS)
    with clock.phase("lookup every stored key + absent keys"):
        keys = np.concatenate([ref.keys, absent])
        query_values(store, keys, "all stored + absent keys", ref)

    with clock.phase("range query"):
        lo = int(ref.keys[ref.keys.size // 3])
        hi = int(ref.keys[min(ref.keys.size - 1, ref.keys.size // 3 + 100_000)])
        res = store.query().where_range(lo, hi).execute()
        want = ref.keys[(ref.keys >= lo) & (ref.keys < hi)]
        check(np.array_equal(res.keys, want), f"range [{lo}, {hi}) keys ({want.size})")
        check_values(ref, res.keys, res.values, res.exists, "range values")

    with clock.phase("predicate query (kernel filter)"):
        mode = ref.columns["l_shipmode"][0]
        res = (store.query().where("l_shipmode", "==", mode)
               .where_range(lo, hi).execute())
        sel = (ref.keys >= lo) & (ref.keys < hi) & (ref.columns["l_shipmode"] == mode)
        check(res.explain.kernel_filtered, "explain.kernel_filtered")
        check(np.array_equal(res.keys, ref.keys[sel]),
              f"l_shipmode == {mode!r} keys ({int(sel.sum())})")
        check_values(ref, res.keys, res.values, res.exists, "predicate values")

    with clock.phase("count-only group_by"):
        res = store.query().group_by("l_returnflag").agg("count").scan().execute()
        flags, counts = np.unique(ref.columns["l_returnflag"], return_counts=True)
        check(res.explain.rows_decoded == 0, "group_by count: rows_decoded == 0")
        check(np.array_equal(res.groups["l_returnflag"], flags)
              and np.array_equal(res.aggregates["count"], counts),
              f"group_by counts {dict(zip(flags.tolist(), counts.tolist()))}")

    with clock.phase("insert / update / delete"):
        new = absent_keys(rng, ref, MUTATED_KEYS, 0, max_key)
        new_rows = random_rows(rng, ref, MUTATED_KEYS)
        store.insert(new, new_rows)
        ref.insert(new, new_rows)
        upd = rng.choice(ref.keys, MUTATED_KEYS, replace=False)
        upd_rows = random_rows(rng, ref, MUTATED_KEYS)
        store.update(upd, upd_rows)
        ref.update(upd, upd_rows)
        gone = rng.choice(np.setdiff1d(ref.keys, np.concatenate([new, upd])),
                          MUTATED_KEYS, replace=False)
        store.delete(gone)
        ref.delete(gone)
        touched = np.concatenate([new, upd, gone])
        query_values(store, touched, "mutated keys", ref)

    sample = np.concatenate([
        rng.choice(ref.keys, min(ref.keys.size, 1 << 18), replace=False),
        touched, absent,
    ])
    stats = engine.stats
    print(f"  engine stats: fused_calls={stats.fused_calls} "
          f"pallas_calls={stats.pallas_calls} jit_calls={stats.jit_calls} "
          f"fused_streamed_calls={stats.fused_streamed_calls} "
          f"compiles={stats.compiles} vmem_budget={stats.vmem_budget_bytes}",
          flush=True)
    check(stats.fused_calls > 0 and stats.jit_calls == 0,
          "EngineStats: fused_calls > 0 and jit_calls == 0")

    path = os.path.join(ROOT, "results", "chip_smoke_store")
    shutil.rmtree(path, ignore_errors=True)
    try:
        with clock.phase("save + repro.open"):
            store.save(path)
            reopened = repro.open(path)
            query_values(reopened, sample, "reopened store", ref)
            rs = reopened.engine.stats
            check(reopened.engine.interpret is (not on_tpu)
                  and rs.fused_calls > 0 and rs.jit_calls == 0,
                  f"reopened EngineStats fused_calls={rs.fused_calls} "
                  f"jit_calls={rs.jit_calls}")
    finally:
        shutil.rmtree(path, ignore_errors=True)

    with clock.phase("LookupServer"):
        server = LookupServer(reopened)
        requests = [rng.choice(sample, SERVER_REQUEST_KEYS)
                    for _ in range(SERVER_REQUESTS)]
        for i, ((values, exists), req) in enumerate(
            zip(server.lookup_many(requests), requests)
        ):
            check_values(ref, req, values, exists, f"server request {i}")
        check(server.stats.requests == SERVER_REQUESTS, "server answered every request")

    with clock.phase("tier disagreement"):
        keys = ref.keys
        old = os.environ.get("REPRO_VMEM_BUDGET")
        os.environ["REPRO_VMEM_BUDGET"] = str(
            kops.resident_bytes(store.spec, engine.tile_n) - 1
        )
        try:
            streamed = InferenceEngine.for_store(store)
        finally:
            if old is None:
                os.environ.pop("REPRO_VMEM_BUDGET")
            else:
                os.environ["REPRO_VMEM_BUDGET"] = old
        jit = InferenceEngine(store.encoder, store.spec, store.params,
                              store.vexist, use_pallas=False)
        fused = tier_codes(store.engine, keys, True, "fused")
        others = {
            "pallas_digits": tier_codes(store.engine, keys, False, "pallas_digits"),
            "fused_streamed": tier_codes(streamed, keys, True, "fused_streamed"),
            "jit_keys": tier_codes(jit, keys, True, "jit_keys"),
        }
        diff = {t: int((c != fused).sum()) for t, c in others.items()}
        print(f"tiers: codes differing from fused over {keys.size} keys x "
              f"{fused.shape[1]} heads: "
              + " ".join(f"{t}={d}" for t, d in diff.items()), flush=True)
        check(diff["pallas_digits"] == 0,
              "pallas_digits (insert/update inference) agrees with fused")


def tier_codes(engine, keys, want_exists, tier) -> np.ndarray:
    """All heads' codes for ``keys`` through one engine, asserting the
    tier it took."""
    chunks = [keys[i : i + engine.max_bucket]
              for i in range(0, keys.size, engine.max_bucket)]
    parts = []
    for ticket, codes, _ in engine.stream(chunks, want_exists=want_exists):
        if ticket.path != tier:
            raise SmokeFailure(f"expected tier {tier}, engine took {ticket.path}")
        parts.append(codes)
    return np.concatenate(parts)


# ---------------------------------------------------------- four chips
def four_chips(args, clock: Clock, on_tpu: bool) -> None:
    import repro
    from repro import obs
    from repro.cluster import ClusterConfig, ShardedDeepMappingStore
    from repro.core import DeepMappingConfig
    from repro.core.trainer import TrainConfig
    from repro.data.tpch import lineitem_like

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices visible")
    rng = np.random.default_rng(args.seed)
    with clock.phase("data"):
        table = lineitem_like(n=args.rows, seed=args.seed)
        ref = Reference(table.keys, table.columns)
        print(f"  lineitem_like rows={table.num_rows}", flush=True)
    cfg = DeepMappingConfig(train=TrainConfig(epochs=args.epochs, seed=args.seed))
    print(f"reduction: training capped at {args.epochs} epochs "
          f"(TrainConfig default {TrainConfig().epochs})", flush=True)
    with clock.phase("build 4-shard cluster (set-up)"):
        mesh_store = repro.build(table, cfg, cluster=ClusterConfig(num_shards=4))
        check(len(mesh_store.shards) == 4, "4 shards")
        check(not any(s.engine.use_pallas for s in mesh_store.shards),
              "shards serve through the jit ladder (use_pallas=False)")
    pool_store = ShardedDeepMappingStore(
        mesh_store.partitioner, mesh_store.shards,
        dataclasses.replace(mesh_store.cluster, mesh_scatter=False),
        mesh_store.pool,
    )
    # shuffled, so that every morsel routes to all four shards
    keys = rng.permutation(np.concatenate([
        ref.keys,
        absent_keys(rng, ref, ABSENT_KEYS, -1000,
                    2 * int(ref.keys.max()) + 4 * ABSENT_KEYS),
    ]))
    reg = obs.registry()
    with clock.phase("mesh scatter"):
        res = query_values(mesh_store, keys, "mesh scatter", ref)
        check(any(p == "mesh" for p in res.explain.plan),
              f"plan evidence says mesh: {res.explain.plan[:3]}")
        fails = reg.get("deepmap_mesh_scatter_failures_total")
        check(fails is None or fails.value() == 0,
              "deepmap_mesh_scatter_failures_total == 0")
        launches = reg.get("deepmap_mesh_scatter_total")
        check(launches is not None and launches.value() > 0,
              f"mesh launches: {launches.value() if launches else 0}")
        devices = mesh_store._mesh_runner().stacked_devices()
        check(len(devices) == 4, f"stacked arrays on {len(devices)} distinct devices")
    with clock.phase("thread pool"):
        res_pool = query_values(pool_store, keys, "thread pool", ref)
        check(not any(p == "mesh" for p in res_pool.explain.plan),
              f"plan evidence says {res_pool.explain.plan[:3]}")
    for c in res.values:
        if not np.array_equal(res.values[c], res_pool.values[c]):
            raise SmokeFailure(f"mesh and thread pool differ in column {c}")
    check(np.array_equal(res.exists, res_pool.exists), "mesh == thread pool byte for byte")

    with clock.phase("use_pallas cluster declines the mesh (set-up + lookup)"):
        pallas_store = repro.build(table, dataclasses.replace(cfg, use_pallas=True),
                                   cluster=ClusterConfig(num_shards=4))
        check(pallas_store._mesh_runner() is None, "mesh runner declined")
        sample = rng.permutation(np.concatenate([
            rng.choice(ref.keys, min(ref.keys.size, 1 << 18), replace=False),
            keys[: 1 << 14],
        ]))
        res_pal = query_values(pallas_store, sample, "use_pallas cluster", ref)
        check(not any(p == "mesh" for p in res_pal.explain.plan),
              f"plan evidence says {res_pal.explain.plan[:3]}")
        st = pallas_store.shards[0].engine.stats
        check(st.fused_calls > 0 and st.jit_calls == 0,
              f"shard EngineStats fused_calls={st.fused_calls} jit_calls={st.jit_calls}")
        fails = reg.get("deepmap_mesh_scatter_failures_total")
        check(fails is None or fails.value() == 0,
              "deepmap_mesh_scatter_failures_total == 0")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=SF1_LINEITEM_ROWS)
    ap.add_argument("--epochs", type=int, default=5,
                    help="training epoch cap (printed as a reduction)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on a backend without a TPU; "
                         "the run still exits 1")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    if not on_tpu and not args.rehearse:
        print("FAIL: JAX finds no TPU (pass --rehearse to run the phases "
              "anyway)", file=sys.stderr, flush=True)
        return 1

    clock = Clock()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args, clock, on_tpu)
    print(f"total host wall {time.perf_counter() - t0:.3f}s, of which backend "
          f"compile {clock.compile_s:.3f}s (set-up)", flush=True)
    if not on_tpu:
        print("FAIL: phases passed, but not on a TPU", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
