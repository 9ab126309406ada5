"""Residency-tier ladder at the VMEM budget boundary, streamed-tier
byte-equality (interpret mode on CPU), in-kernel predicate filtering
vs the host filter on every predicate op, and the cost-model morsel
seed rule — the ISSUE-9 conformance additions."""

import numpy as np
import pytest

from conftest import make_periodic_table
from repro.api.executor import (
    ADAPT_MAX,
    ADAPT_MIN,
    seed_morsel_rows,
)
from repro.api.plan import DEFAULT_MORSEL, PREDICATE_OPS
from repro.core import DeepMappingConfig, DeepMappingStore
from repro.core.inference import InferenceEngine
from repro.core.trainer import TrainConfig
from repro.kernels import ops as kops
from repro.kernels.ref import ref_fused_lookup
from test_kernels import make_lookup_setup

TILE = 64


def _engine(enc, spec, params, bv, monkeypatch, budget=None):
    """Engine with an explicit VMEM budget (read at construction)."""
    if budget is None:
        monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
    else:
        monkeypatch.setenv("REPRO_VMEM_BUDGET", str(int(budget)))
    return InferenceEngine(
        enc, spec, params, bv, use_pallas=True, tile_n=TILE
    )


def _fused_vmem(eng) -> int:
    """Bytes the resident fused tier needs for the full task set —
    the exact quantity ``_fused_eligible`` compares to the budget."""
    return kops.resident_bytes(eng._entry(eng.spec.tasks).spec, eng.tile_n)


def _assert_ref_identical(eng, enc, spec, params, bv, keys):
    t = eng.dispatch(keys, want_exists=True)
    path = t.path
    codes, exists = eng.collect(t)
    if exists is None:
        exists = bv.test(keys)
    ref_codes, ref_exists = ref_fused_lookup(params, keys, enc, bv, spec)
    np.testing.assert_array_equal(codes, ref_codes)
    np.testing.assert_array_equal(exists, ref_exists)
    return path


class TestVmemBoundaryTiers:
    """Tier selection must flip exactly at the budget boundary: the
    resident fused kernel at budget and budget+1, a non-resident tier
    one byte under — with byte-identical results on either side."""

    def setup_method(self):
        self.setup = make_lookup_setup(tasks=2)

    def test_budget_surfaces_in_stats(self, monkeypatch):
        enc, spec, params, bv = self.setup
        eng = _engine(enc, spec, params, bv, monkeypatch, budget=123456)
        assert eng.vmem_budget == 123456
        assert eng.stats.vmem_budget_bytes == 123456

    @pytest.mark.parametrize("delta", [0, 1])
    def test_at_and_above_budget_stays_fused(self, monkeypatch, delta):
        enc, spec, params, bv = self.setup
        probe = _engine(enc, spec, params, bv, monkeypatch)
        eng = _engine(
            enc, spec, params, bv, monkeypatch,
            budget=_fused_vmem(probe) + delta,
        )
        keys = np.random.default_rng(0).integers(0, 10000, 300).astype(np.int64)
        path = _assert_ref_identical(eng, enc, spec, params, bv, keys)
        assert path == "fused"
        assert eng.stats.fused_calls >= 1

    def test_one_byte_under_budget_leaves_fused(self, monkeypatch):
        enc, spec, params, bv = self.setup
        probe = _engine(enc, spec, params, bv, monkeypatch)
        eng = _engine(
            enc, spec, params, bv, monkeypatch,
            budget=_fused_vmem(probe) - 1,
        )
        keys = np.random.default_rng(1).integers(0, 10000, 300).astype(np.int64)
        path = _assert_ref_identical(eng, enc, spec, params, bv, keys)
        assert path != "fused"
        assert eng.stats.fused_calls == 0

    def test_streamed_tier_byte_identical(self, monkeypatch):
        """Below the digits tier's weight budget the engine must stream
        head pages (not fail, not fall to jit) and stay byte-identical
        — the kernel runs in interpret mode on CPU."""
        enc, spec, params, bv = self.setup
        probe = _engine(enc, spec, params, bv, monkeypatch)
        entry = probe._entry(spec.tasks)
        eng = _engine(
            enc, spec, params, bv, monkeypatch, budget=_fused_vmem(probe) - 1
        )
        # the squeezed budget must still admit a single-head page
        assert eng._streamed_plan(entry, True) is not None
        for n in (1, 63, 64, 65, 200):
            keys = (
                np.random.default_rng(n).integers(0, 10000, n).astype(np.int64)
            )
            path = _assert_ref_identical(eng, enc, spec, params, bv, keys)
            assert path == "fused_streamed"
        assert eng.stats.fused_streamed_calls >= 5

    def test_streamed_handles_out_of_domain_keys(self, monkeypatch):
        enc, spec, params, bv = self.setup
        probe = _engine(enc, spec, params, bv, monkeypatch)
        eng = _engine(
            enc, spec, params, bv, monkeypatch, budget=_fused_vmem(probe) - 1
        )
        keys = np.array(
            [0, 1, 9999, 10000, 10001, 2**31 - 1, 2**31, 2**40, -1, -7],
            dtype=np.int64,
        )
        _assert_ref_identical(eng, enc, spec, params, bv, keys)

    def test_kernel_filter_capability_follows_tier(self, monkeypatch):
        enc, spec, params, bv = self.setup
        probe = _engine(enc, spec, params, bv, monkeypatch)
        full = _fused_vmem(probe)
        assert _engine(
            enc, spec, params, bv, monkeypatch, budget=full
        ).kernel_filter_capable()
        assert not _engine(
            enc, spec, params, bv, monkeypatch, budget=full - 1
        ).kernel_filter_capable()


class TestVmemBudgetTable:
    """The budget comes from the TPU's ``device_kind``; an unknown TPU
    kind is an error, and off the TPU the CPU default holds."""

    def _on(self, monkeypatch, platform, kind):
        dev = type("Dev", (), {"platform": platform, "device_kind": kind})()
        monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
        monkeypatch.setattr(kops.jax, "devices", lambda *a: [dev])

    def test_cpu_keeps_default(self, monkeypatch):
        self._on(monkeypatch, "cpu", "cpu")
        assert kops.vmem_budget_bytes() == kops.VMEM_BUDGET_BYTES

    def test_tpu_kind_from_table(self, monkeypatch):
        self._on(monkeypatch, "tpu", "TPU v5 lite")
        want = kops._TPU_VMEM_BUDGETS["TPU v5 lite"]
        assert kops.vmem_budget_bytes() == want
        assert kops.vmem_limit_bytes() == want

    def test_unknown_tpu_kind_is_an_error(self, monkeypatch):
        self._on(monkeypatch, "tpu", "TPU v0 imaginary")
        with pytest.raises(ValueError, match="no VMEM budget"):
            kops.vmem_budget_bytes()


PRED_CASES = [
    ("==", 2),
    ("!=", 0),
    ("<", 3),
    ("<=", 1),
    (">", 2),
    (">=", 4),
    ("in", (0, 2, 4)),
]


class TestKernelPredicateFilter:
    """In-kernel predicate filtering must be byte-identical to the
    host filter for every predicate op, report ``kernel_filtered``
    evidence, and survive aux-overridden rows (mutations)."""

    @pytest.fixture(scope="class")
    def stores(self):
        table = make_periodic_table(n=1200, period=16, cards=(5, 3))
        cfg = DeepMappingConfig(
            shared=(32,), private=(8,),
            train=TrainConfig(epochs=10, batch_size=512),
        )
        kernel = DeepMappingStore.build(
            table,
            DeepMappingConfig(
                shared=cfg.shared, private=cfg.private, train=cfg.train,
                use_pallas=True,
            ),
        )
        host = DeepMappingStore.build(table, cfg)
        return table, kernel, host

    def test_capability_flag(self, stores):
        _, kernel, host = stores
        pred = [type("P", (), {"column": "col0"})()]
        assert kernel.supports_kernel_filter(pred)
        assert not host.supports_kernel_filter(pred)
        assert not kernel.supports_kernel_filter(())
        assert not kernel.supports_kernel_filter(
            [type("P", (), {"column": "nope"})()]
        )

    @pytest.mark.parametrize("op,value", PRED_CASES, ids=[c[0] for c in PRED_CASES])
    def test_ops_byte_identical(self, stores, op, value):
        assert op in PREDICATE_OPS
        _, kernel, host = stores
        rk = (
            kernel.query().scan().where("col0", op, value).execute()
        )
        rh = host.query().scan().where("col0", op, value).execute()
        rp = (
            kernel.query().scan().where("col0", op, value)
            .pushdown(False).execute()
        )
        assert rk.explain.kernel_filtered
        assert any("filter[kernel" in p for p in rk.explain.plan)
        np.testing.assert_array_equal(rk.keys, rh.keys)
        np.testing.assert_array_equal(rk.keys, rp.keys)
        for c in rk.values:
            np.testing.assert_array_equal(rk.values[c], rh.values[c])
            np.testing.assert_array_equal(rk.values[c], rp.values[c])

    def test_aux_overridden_rows_patched(self, stores):
        """Rows answered by the aux table carry build-time-corrected
        codes the kernel never saw — the collect-time patch must
        re-filter exactly those."""
        table, kernel, host = stores
        up = table.keys[5:25]
        cols = {
            "col0": np.full(20, 4, dtype=np.int32),
            "col1": np.full(20, 2, dtype=np.int32),
        }
        kernel.update(up, cols)
        host.update(up, cols)
        for op, value in (("==", 4), ("!=", 4), ("<=", 3)):
            rk = kernel.query().scan().where("col0", op, value).execute()
            rh = host.query().scan().where("col0", op, value).execute()
            np.testing.assert_array_equal(rk.keys, rh.keys)
            for c in rk.values:
                np.testing.assert_array_equal(rk.values[c], rh.values[c])


def test_kernel_filter_judged_on_the_plan_heads(monkeypatch):
    """A store with one head too wide for the resident fused tier: a
    plan whose projection and predicate columns leave that head out is
    promised the in-kernel filter and its label reads
    ``filter[kernel:...]``; a plan over every head is not.  Both give
    the table's answers."""
    from repro.api.executor import PlanStream
    from repro.core import Table

    base = make_periodic_table(n=1200, period=16, cards=(5, 3))
    table = Table(keys=base.keys, columns={
        **base.columns, "wide": (np.arange(1200) % 1000).astype(np.int32)})
    store = DeepMappingStore.build(table, DeepMappingConfig(
        shared=(32,), private=(8,), train=TrainConfig(epochs=2, batch_size=512),
        use_pallas=True,
    ))
    eng = store.engine
    narrow, full = (
        kops.resident_bytes(eng._entry(tasks).spec, eng.tile_n)
        for tasks in (("col0", "col1"), store.spec.tasks)
    )
    monkeypatch.setenv("REPRO_VMEM_BUDGET", str((narrow + full) // 2))
    store.attach_engine(InferenceEngine.for_store(store))
    want = np.sort(table.keys[table.columns["col1"] == 1])
    narrow_q = store.query().select("col0").scan().where("col1", "==", 1)
    wide_q = store.query().scan().where("col1", "==", 1)
    assert PlanStream(store, narrow_q.plan()).kernel_filter
    assert not PlanStream(store, wide_q.plan()).kernel_filter
    assert not store.supports_kernel_filter(narrow_q.plan().predicates)
    rn, rw = narrow_q.execute(), wide_q.execute()
    assert any(s.startswith("filter[kernel:") for s in rn.explain.plan)
    assert not any(s.startswith("filter[kernel:") for s in rw.explain.plan)
    np.testing.assert_array_equal(rn.keys, want)
    np.testing.assert_array_equal(rw.keys, want)
    np.testing.assert_array_equal(rn.values["col0"], rw.values["col0"])
    np.testing.assert_array_equal(
        rw.values["col0"], table.columns["col0"][np.searchsorted(table.keys, want)]
    )


class TestMorselSeed:
    """Pure seeding rule: pick the initial morsel from the model's
    weight bytes instead of always starting at ``DEFAULT_MORSEL``."""

    def test_no_model_seeds_default(self):
        assert seed_morsel_rows(0) == DEFAULT_MORSEL
        assert seed_morsel_rows(-5) == DEFAULT_MORSEL

    def test_calibration_anchor(self):
        # ~300 KB of weights lands on the historical default, so the
        # seed only moves stores that are far from that anchor.
        assert seed_morsel_rows(300_000) == DEFAULT_MORSEL

    def test_tiny_model_seeds_large(self):
        assert seed_morsel_rows(1_000) == ADAPT_MAX

    def test_huge_model_seeds_small(self):
        assert seed_morsel_rows(1 << 30) == ADAPT_MIN

    def test_power_of_two_and_bounds(self):
        for nbytes in (1, 10_000, 123_456, 5_000_000, 1 << 28):
            rows = seed_morsel_rows(nbytes)
            assert ADAPT_MIN <= rows <= ADAPT_MAX
            assert rows & (rows - 1) == 0  # power of two

    def test_max_rows_caps_seed(self):
        assert seed_morsel_rows(1_000, max_rows=1 << 14) == 1 << 14
        # a cap below ADAPT_MIN clamps up, never under
        assert seed_morsel_rows(1_000, max_rows=16) == ADAPT_MIN

    def test_seed_judged_on_the_plan_heads(self, tmp_path):
        """A plan is seeded from the weights it evaluates, the trunk and
        its projection and predicate heads, not the store's whole model:
        a plan that leaves out a wide head starts on larger morsels."""
        from repro.api.executor import PlanStream
        from repro.core import Table

        base = make_periodic_table(n=4000, period=16, cards=(5, 3))
        table = Table(keys=base.keys, columns={
            **base.columns, "wide": (np.arange(4000) % 3000).astype(np.int32)})
        store = DeepMappingStore.build(table, DeepMappingConfig(
            shared=(32,), private=(64,), train=TrainConfig(epochs=1, batch_size=512),
        ))
        whole = store.model_bytes()
        assert whole == store.size_breakdown()["model"]
        reopened_dir = str(tmp_path / "s")
        store.save(reopened_dir)
        assert DeepMappingStore.load(reopened_dir).model_bytes() == whole
        narrow = store.model_bytes(("col0", "col1"))
        assert narrow < store.model_bytes(("col0", "wide")) < whole
        cap = store.config.inference_batch
        plan = store.query().select("col0").scan().where("col1", "==", 1).plan()
        assert PlanStream(store, plan)._morsel_rows == seed_morsel_rows(narrow, cap)
        wide = store.query().scan().where("col1", "==", 1).plan()
        assert PlanStream(store, wide)._morsel_rows == seed_morsel_rows(whole, cap)
        assert seed_morsel_rows(narrow, cap) > seed_morsel_rows(whole, cap)

    def test_monotone_in_model_size(self):
        sizes = [1 << s for s in range(10, 31, 2)]
        seeds = [seed_morsel_rows(s) for s in sizes]
        assert all(a >= b for a, b in zip(seeds, seeds[1:]))
