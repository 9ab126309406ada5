"""Cluster mesh-scatter conformance: K-shard lookups answered by one
``shard_map`` launch must be byte-identical to the thread-pool fan-out
on every query shape, degrade cleanly, and restack on mutation drift.

The multi-device cases need ≥ 2 devices — CI provides them with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; under a plain
single-device run they skip and the fallback tests still execute."""

import dataclasses
import os

import numpy as np
import pytest

import jax

from conftest import make_periodic_table, make_random_table
from repro.cluster import ClusterConfig, ShardedDeepMappingStore
from repro.cluster.mesh_scatter import MeshShardRunner, _pow2_at_least, make_shard_mesh
from repro.core import DeepMappingConfig
from repro.core.trainer import TrainConfig
from repro.kernels import bitvector as bv_kernel

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=N)",
)

FAST = DeepMappingConfig(
    shared=(32,), private=(8,), train=TrainConfig(epochs=10, batch_size=512)
)


@pytest.fixture()
def threadpool_env(monkeypatch):
    """Force the thread-pool path via the env kill switch."""
    monkeypatch.setenv("REPRO_MESH_SCATTER", "0")


@pytest.fixture(scope="module")
def cluster():
    table = make_periodic_table(n=2400, period=16, cards=(5, 3))
    return table, ShardedDeepMappingStore.build(
        table, FAST, ClusterConfig(num_shards=4, policy="range")
    )


def probe_keys(table, seed=7):
    rng = np.random.default_rng(seed)
    return np.concatenate([
        table.keys,
        rng.integers(-50, int(table.keys.max()) + 500, 600),
        np.array([-1, 0, 2**31 - 1, 2**31, 2**40], dtype=np.int64),
    ]).astype(np.int64)


def direct_lookup(store, keys):
    pend = store._dispatch_lookup(keys, fanout=True)
    return store._collect_lookup(pend)


class TestUnits:
    def test_pow2_at_least(self):
        assert _pow2_at_least(1, 128) == 128
        assert _pow2_at_least(128, 128) == 128
        assert _pow2_at_least(129, 128) == 256
        assert _pow2_at_least(1000, 128) == 1024

    def test_make_shard_mesh(self):
        mesh = make_shard_mesh()
        assert mesh.axis_names == ("shard",)
        assert mesh.devices.shape == (N_DEV,)
        assert make_shard_mesh(1).devices.shape == (1,)
        assert make_shard_mesh(0).devices.shape == (1,)
        assert make_shard_mesh(N_DEV + 5).devices.shape == (N_DEV,)

    def test_pack_words32_layout(self):
        words = np.arange(4, dtype=np.uint64)
        packed = bv_kernel.pack_words32(words)
        assert packed.dtype == np.uint32
        assert packed.shape[0] == 8
        # little-endian split: low word first — the k>>5 indexing contract
        np.testing.assert_array_equal(packed[0::2], np.arange(4, dtype=np.uint32))
        np.testing.assert_array_equal(packed[1::2], np.zeros(4, dtype=np.uint32))

    def test_maybe_build_rejects_small_fleets(self, cluster):
        _, c = cluster
        assert MeshShardRunner.maybe_build(c.shards[:1]) is None

    def test_pallas_shards_decline(self):
        """``use_pallas`` shards keep their own Pallas engines: their
        T_aux corrects Pallas codes, not the scatter's XLA forward."""
        table = make_periodic_table(n=1200, period=16, cards=(5, 3))
        c = ShardedDeepMappingStore.build(
            table, dataclasses.replace(FAST, use_pallas=True),
            ClusterConfig(num_shards=2, policy="range"),
        )
        assert not any(MeshShardRunner._shard_eligible(s) for s in c.shards)
        assert MeshShardRunner.maybe_build(c.shards) is None
        keys = probe_keys(table)
        values, exists, _, stats = direct_lookup(c, keys)
        assert not any("mesh" in p for p in stats.plan), stats.plan
        np.testing.assert_array_equal(exists, np.isin(keys, table.keys))

    def test_failing_mesh_program_raises(self, cluster, monkeypatch):
        """A mesh program that raises fails the plan and is counted —
        the thread pool does not answer in its place."""
        from repro import obs

        table, c = cluster

        class BrokenRunner:
            def tickets(self, batches):
                raise RuntimeError("mesh program failed")

        monkeypatch.setattr(c, "_mesh_runner", lambda: BrokenRunner())
        counter = obs.counter("deepmap_mesh_scatter_failures_total")
        before = counter.value()
        with pytest.raises(RuntimeError, match="mesh program failed"):
            c.lookup(table.keys)
        assert counter.value() == before + 1

    @pytest.mark.skipif(N_DEV != 1, reason="single-device fallback case")
    def test_single_device_fallback_is_noop(self, cluster):
        table, c = cluster
        assert MeshShardRunner.maybe_build(c.shards) is None
        _, _, _, stats = direct_lookup(c, probe_keys(table))
        assert not any("mesh" in p for p in stats.plan)

    def test_config_kill_switch(self, cluster, monkeypatch):
        _, c = cluster
        monkeypatch.setenv("REPRO_MESH_SCATTER", "0")
        assert not c._mesh_enabled()
        monkeypatch.delenv("REPRO_MESH_SCATTER")
        assert c._mesh_enabled() == c.cluster.mesh_scatter


@multi_device
class TestMeshConformance:
    def test_runner_builds(self, cluster):
        _, c = cluster
        runner = MeshShardRunner.maybe_build(c.shards)
        assert runner is not None
        assert runner.k == 4
        assert runner.k_pad % runner.n_dev == 0

    def test_lookup_byte_identical(self, cluster, monkeypatch):
        table, c = cluster
        keys = probe_keys(table)
        vm, em, _, sm = direct_lookup(c, keys)
        assert any("mesh" in p for p in sm.plan), sm.plan
        monkeypatch.setenv("REPRO_MESH_SCATTER", "0")
        vt, et, _, st = direct_lookup(c, keys)
        assert not any("mesh" in p for p in st.plan), st.plan
        np.testing.assert_array_equal(em, et)
        for col in vm:
            np.testing.assert_array_equal(vm[col][em], vt[col][et])

    def test_scan_and_range_byte_identical(self, cluster, monkeypatch):
        table, c = cluster
        lo, hi = int(table.keys[100]), int(table.keys[-100])
        rm_scan = c.query().scan().execute()
        rm_rng = c.query().where_range(lo, hi).execute()
        monkeypatch.setenv("REPRO_MESH_SCATTER", "0")
        rt_scan = c.query().scan().execute()
        rt_rng = c.query().where_range(lo, hi).execute()
        for rm, rt in ((rm_scan, rt_scan), (rm_rng, rt_rng)):
            np.testing.assert_array_equal(rm.keys, rt.keys)
            for col in rm.values:
                np.testing.assert_array_equal(rm.values[col], rt.values[col])

    def test_predicates_and_projection(self, cluster, monkeypatch):
        _, c = cluster
        q = lambda: (  # noqa: E731
            c.query().scan().where("col0", "<=", 2).select("col1").execute()
        )
        rm = q()
        monkeypatch.setenv("REPRO_MESH_SCATTER", "0")
        rt = q()
        np.testing.assert_array_equal(rm.keys, rt.keys)
        for col in rm.values:
            np.testing.assert_array_equal(rm.values[col], rt.values[col])

    def test_mutation_drift_restacks(self, monkeypatch):
        table = make_periodic_table(n=1600, period=16, cards=(4,))
        c = ShardedDeepMappingStore.build(
            table, FAST, ClusterConfig(num_shards=4, policy="range")
        )
        keys = probe_keys(table, seed=11)
        direct_lookup(c, keys)  # prime the runner + stacked arrays
        c.delete(table.keys[10:40])
        new_keys = np.array(
            [10**6 + 2 * i for i in range(30)], dtype=np.int64
        )
        c.insert(
            new_keys, {"col0": np.ones(30, dtype=np.int32)}
        )
        probe = np.concatenate([keys, new_keys])
        vm, em, _, sm = direct_lookup(c, probe)
        assert any("mesh" in p for p in sm.plan), sm.plan
        monkeypatch.setenv("REPRO_MESH_SCATTER", "0")
        vt, et, _, _ = direct_lookup(c, probe)
        np.testing.assert_array_equal(em, et)
        for col in vm:
            np.testing.assert_array_equal(vm[col][em], vt[col][et])

    def test_trunkless_hash_cluster(self, monkeypatch):
        table = make_random_table(n=900, cards=(7, 4))
        c = ShardedDeepMappingStore.build(
            table,
            DeepMappingConfig(
                shared=(), private=(12,),
                train=TrainConfig(epochs=8, batch_size=256),
            ),
            ClusterConfig(num_shards=3, policy="hash"),
        )
        keys = np.concatenate(
            [table.keys, np.arange(0, 6000, 7, dtype=np.int64)]
        )
        vm, em, _, sm = direct_lookup(c, keys)
        assert any("mesh" in p for p in sm.plan), sm.plan
        monkeypatch.setenv("REPRO_MESH_SCATTER", "0")
        vt, et, _, _ = direct_lookup(c, keys)
        np.testing.assert_array_equal(em, et)
        for col in vm:
            np.testing.assert_array_equal(vm[col][em], vt[col][et])

    def test_kill_switch_mid_flight(self, cluster):
        """Flipping the env between lookups swaps paths per dispatch."""
        table, c = cluster
        keys = table.keys[::5]  # strided: spans every range shard
        _, _, _, s1 = direct_lookup(c, keys)
        assert any("mesh" in p for p in s1.plan)
        os.environ["REPRO_MESH_SCATTER"] = "0"
        try:
            _, _, _, s2 = direct_lookup(c, keys)
            assert not any("mesh" in p for p in s2.plan)
        finally:
            del os.environ["REPRO_MESH_SCATTER"]
        _, _, _, s3 = direct_lookup(c, keys)
        assert any("mesh" in p for p in s3.plan)
