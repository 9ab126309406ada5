"""Naive decode-then-aggregate oracle for the differential harness.

Pure numpy/python on raw table columns — deliberately independent of
``repro.api.plan`` (no shared factorization, packing, or accumulator
code), so a bug in the code-space aggregation machinery cannot cancel
out in the reference.  ``tests/test_aggregate_join.py`` and
``tests/test_tpch_queries.py`` compare every executor path against
these functions value-for-value.
"""

import operator

import numpy as np

#: The predicate comparisons ``ref_select`` knows, by ``Query.where`` op.
_OPS = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge,
        "in": lambda v, values: v in values}


def agg_name(func, column):
    """Result key for one aggregate, mirroring ``AggSpec.name()``."""
    return "count" if column is None else f"{func}({column})"


def ref_group_aggregate(columns, group_by, aggregates, sel=None):
    """Group-aggregate a plain column dict the slow, obvious way.

    ``aggregates`` is a sequence of ``(func, column)`` pairs (``column
    is None`` for count).  Returns ``(groups, aggs)`` dicts shaped like
    :class:`repro.api.plan.AggregateResult` — one array per group-by
    column and per aggregate, rows sorted by group-value tuple.  An
    empty ``group_by`` is a global aggregate: exactly one group.
    ``sel`` restricts to a boolean row mask (predicate oracle).
    """
    cols = {c: np.asarray(v) for c, v in columns.items()}
    some = next(iter(cols.values()), None)
    n = 0 if some is None else len(some)
    idx = np.arange(n) if sel is None else np.flatnonzero(np.asarray(sel))
    if group_by:
        per_col = [cols[c][idx].tolist() for c in group_by]
        tuples = list(zip(*per_col)) if len(idx) else []
    else:
        tuples = [()] * len(idx)
    state = {}
    for row, g in zip(idx.tolist(), tuples):
        accs = state.get(g)
        if accs is None:
            accs = state[g] = [None] * len(aggregates)
        for j, (func, column) in enumerate(aggregates):
            if column is None:
                accs[j] = 1 if accs[j] is None else accs[j] + 1
                continue
            v = cols[column][row]
            v = float(v) if np.asarray(v).dtype.kind == "f" else int(v)
            if accs[j] is None:
                accs[j] = v
            elif func == "sum":
                accs[j] = accs[j] + v
            elif func == "min":
                accs[j] = min(accs[j], v)
            elif func == "max":
                accs[j] = max(accs[j], v)
            else:
                raise ValueError(func)
    order = sorted(state)
    groups = {
        c: np.asarray([g[i] for g in order]) for i, c in enumerate(group_by)
    }
    aggs = {
        agg_name(func, column): np.asarray([state[g][j] for g in order])
        for j, (func, column) in enumerate(aggregates)
    }
    return groups, aggs


def ref_select(keys, columns, lo, hi, predicates, projection):
    """A range selection the slow, obvious way, row by row: the keys in
    ``[lo, hi)`` whose values satisfy every ``(column, op, value)`` of
    ``predicates``, ascending, with their values of each ``projection``
    column.  Returns ``(keys, {column: values})``."""
    keys = np.asarray(keys, dtype=np.int64)
    cols = {c: np.asarray(v) for c, v in columns.items()}
    rows = [
        i for i in np.argsort(keys, kind="stable").tolist()
        if lo <= int(keys[i]) < hi
        and all(_OPS[op](cols[c][i], value) for c, op, value in predicates)
    ]
    return keys[rows], {c: cols[c][rows] for c in projection}


def ref_join_mask(left_keys, key_fn, right_keys):
    """Boolean mask of left rows whose mapped key exists on the right
    (the inner key-equi join semantics), via a plain python set."""
    left_keys = np.asarray(left_keys, dtype=np.int64)
    probe = left_keys if key_fn is None else np.asarray(
        key_fn(left_keys), dtype=np.int64
    )
    right = set(np.asarray(right_keys, dtype=np.int64).tolist())
    return np.asarray([int(k) in right for k in probe.tolist()], dtype=bool)


def norm_strings(arr):
    """Normalize a (possibly bytes-decoded) string column for
    comparison: everything through ``astype(str)``."""
    arr = np.asarray(arr)
    if arr.dtype.kind in ("S", "U", "O"):
        return arr.astype(str)
    return arr


def assert_aggregate_equal(result, ref_groups, ref_aggs):
    """Value-identity between an :class:`AggregateResult` and the
    oracle's ``(groups, aggs)`` — same group rows, same order, same
    aggregate values (string group labels normalized)."""
    assert set(result.groups) == set(ref_groups)
    assert set(result.aggregates) == set(ref_aggs)
    for c, want in ref_groups.items():
        np.testing.assert_array_equal(
            norm_strings(result.groups[c]), norm_strings(want), err_msg=c
        )
    for name, want in ref_aggs.items():
        np.testing.assert_array_equal(
            np.asarray(result.aggregates[name]), want, err_msg=name
        )
