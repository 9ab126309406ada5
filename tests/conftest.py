"""Shared fixtures. NOTE: no XLA device-count overrides here — smoke
tests and benches must see the single real CPU device; multi-device
cases get virtual devices from the caller's ``XLA_FLAGS``."""

import numpy as np
import pytest

from repro.core import DeepMappingConfig, DeepMappingStore, Table
from repro.core.trainer import TrainConfig


def make_periodic_table(n=1500, period=16, cards=(5, 3), stride=2, seed=0):
    """High-correlation table in the paper's sense: values are periodic
    along the key dimension (like TPC-DS customer_demographics)."""
    keys = np.arange(0, n * stride, stride, dtype=np.int64)
    cols = {}
    for i, c in enumerate(cards):
        cols[f"col{i}"] = ((keys // (period * (i + 1))) % c).astype(np.int32)
    return Table(keys=keys, columns=cols)


def make_random_table(n=1000, cards=(7,), key_space=None, seed=0):
    """Low-correlation table: values are independent of keys (like the
    TPC-H OrderStatus sample — Pearson ~1e-4)."""
    rng = np.random.default_rng(seed)
    space = key_space or (4 * n)
    keys = rng.permutation(space)[:n].astype(np.int64)
    cols = {
        f"col{i}": rng.integers(0, c, size=n).astype(np.int32)
        for i, c in enumerate(cards)
    }
    return Table(keys=keys, columns=cols)


@pytest.fixture(scope="session")
def small_store():
    """One trained store shared by read-only tests (training is the
    expensive part; mutating tests build their own)."""
    table = make_periodic_table()
    cfg = DeepMappingConfig(
        shared=(96, 96),
        private=(32,),
        train=TrainConfig(epochs=40, batch_size=512),
    )
    return table, DeepMappingStore.build(table, cfg)
