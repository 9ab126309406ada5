"""Plain generator of TPC-DS ``customer_demographics``.

The table is the full cross product of its eight attribute domains,
keyed by a dense ``cd_demo_sk`` from 1: 2 x 5 x 7 x 20 x 4 x 7 x 7 x 7 =
1,920,800 rows at every scale factor.  Rows are nested as dsdgen writes
them (``mk_w_customer_demographics`` in the TPC-DS tools): ``cd_gender``
varies fastest and ``cd_dep_college_count`` slowest, each domain in
dsdgen's list order, so row 1 is ``M|M|Primary|500|Good|0|0|0`` and row 2
``F|M|Primary|500|Good|0|0|0``.  Every column is a periodic function of
the key, which makes it the high-correlation case of the DeepMapping
paper (arXiv:2307.05861, section V-B1).

The relation does not depend on ``seed``; the seed sets the model's
initialisation and the traffic.  Columns come back as ``(domain,
index)`` pairs: the value of row ``i`` is ``domain[index[i]]``.
"""

from __future__ import annotations

import numpy as np

#: fastest-varying first, each in dsdgen's list order
DOMAINS = (
    ("cd_gender", np.array(["M", "F"])),
    ("cd_marital_status", np.array(["M", "S", "D", "W", "U"])),
    ("cd_education_status", np.array(
        ["Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
         "Advanced Degree", "Unknown"])),
    ("cd_purchase_estimate", np.arange(500, 10500, 500, dtype=np.int32)),
    ("cd_credit_rating", np.array(["Good", "High Risk", "Low Risk", "Unknown"])),
    ("cd_dep_count", np.arange(0, 7, dtype=np.int32)),
    ("cd_dep_employed_count", np.arange(0, 7, dtype=np.int32)),
    ("cd_dep_college_count", np.arange(0, 7, dtype=np.int32)),
)


def generate(config: dict, seed: int):
    """``(keys, {column: (domain, index)})`` for the first
    ``config["rows"]`` rows of the cross product (all 1,920,800 for the
    full table)."""
    del seed  # the relation is fixed by the specification
    rows = int(config["rows"])
    full = int(np.prod([d.size for _, d in DOMAINS]))
    if not 0 < rows <= full:
        raise ValueError(f"rows must be in 1..{full}, got {rows}")
    keys = np.arange(1, rows + 1, dtype=np.int64)
    idx = keys - 1
    columns = {}
    stride = 1
    for name, domain in DOMAINS:
        columns[name] = (domain, ((idx // stride) % domain.size).astype(np.int32))
        stride *= domain.size
    return keys, columns
