"""The comparison that decides ``correct``: the rows the timed path returned
against the plain reference's answer to the same text.

Two numbers come out of every compared query, each held to a limit of its
own (``limits.json``):

``rows_wrong``
    rows that say the wrong thing whatever the rounding: a wrong row count,
    a row whose exact columns (integers, strings, nulls) match no group of
    the reference, a row twice, rows out of the ORDER BY's order, or a row
    kept by LIMIT that a left-out row has to precede.  Exact; its limit is 0.
``max_rel_err``
    the widest gap of a double column against the reference's, as a share
    of the reference's value (of 1.0 where that is smaller).

Order and the LIMIT cut are judged on the reference's values with a
tolerance, because two groups whose sums differ in the last bit may stand in
either order: a row is out of order only where the reference says it comes
strictly later by more than ``ORDER_TOLERANCE``.
"""

from __future__ import annotations

import math

#: two doubles closer than this (relative) may stand in either order
ORDER_TOLERANCE = 1e-9


def _sort_key(row, order):
    key = []
    for name, direction in order:
        v = row[name]
        if v is None:
            key.append((0, 0) if direction == "asc" else (2, 0))
        else:
            key.append((1, -v if direction == "desc" else v))
    return tuple(key)


def ordered(answer):
    """Reference rows in the ORDER BY's order (nulls first ascending)."""
    return sorted(answer["rows"], key=lambda r: _sort_key(r, answer["order"]))


def _strictly_before(a, b, answer) -> bool:
    """Whether reference row ``a`` has to come before ``b``."""
    floats = set(answer["float_columns"])
    for name, direction in answer["order"]:
        x, y = a[name], b[name]
        if x is None or y is None:
            if x is None and y is None:
                continue
            first_is_null = x is None
            return first_is_null == (direction == "asc")
        if name in floats:
            if abs(x - y) <= ORDER_TOLERANCE * max(abs(x), abs(y), 1.0):
                return False            # either order is right
            return (x < y) == (direction == "asc")
        if x != y:
            return (x < y) == (direction == "asc")
    return False


def _exact_key(row, answer):
    floats = set(answer["float_columns"])
    return tuple(row.get(c) for c in answer["columns"] if c not in floats)


def compare(got_rows, answer) -> dict:
    """``got_rows``: list of dicts as the client received them."""
    floats = answer["float_columns"]
    ref_rows = ordered(answer)
    by_key = {}
    for r in ref_rows:
        by_key.setdefault(_exact_key(r, answer), r)
    want = len(ref_rows)
    if answer["limit"] is not None:
        want = min(want, answer["limit"])
    wrong = abs(len(got_rows) - want)
    worst = 0.0
    matched, seen = [], set()
    for row in got_rows:
        if set(row) != set(answer["columns"]):
            wrong += 1
            continue
        key = _exact_key(row, answer)
        ref = by_key.get(key)
        if ref is None or key in seen:
            wrong += 1
            continue
        seen.add(key)
        matched.append(ref)
        for c in floats:
            g, r = row[c], ref[c]
            if g is None or r is None:
                wrong += (g is None) != (r is None)
                continue
            gap = abs(float(g) - r) / max(abs(r), 1.0)
            if math.isnan(gap):
                wrong += 1
            else:
                worst = max(worst, gap)
    for a, b in zip(matched, matched[1:]):
        wrong += _strictly_before(b, a, answer)
    if matched and len(ref_rows) > len(got_rows):
        last = matched[-1]
        for r in ref_rows[:want]:
            if _exact_key(r, answer) not in seen:
                wrong += _strictly_before(r, last, answer)
    return {"rows_wrong": int(wrong), "max_rel_err": worst,
            "rows": len(got_rows), "groups": len(ref_rows)}
