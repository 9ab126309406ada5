"""Dataset substrate: synthetic workload generators mirroring the
paper's evaluation datasets (§V-A1)."""

from repro.data.datasets import (  # noqa: F401
    cropland_like,
    synthetic_multi_column,
    synthetic_single_column,
)
from repro.data.tpch import lineitem_like, orders_like, part_like  # noqa: F401
from repro.data.tpcds import (  # noqa: F401
    catalog_returns_like,
    catalog_sales_like,
    customer_demographics_like,
)
