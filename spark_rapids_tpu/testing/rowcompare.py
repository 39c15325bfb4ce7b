"""Row-level result comparison shared by the pytest differential asserts
and chip_smoke.py's checks (reference:
integration_tests/src/main/python/asserts.py:579 — the oracle deep-
compares collected rows, never just row counts)."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple


def val_eq(a, b, approx: bool) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        if approx:
            return a == b or abs(a - b) <= max(1e-9, 1e-6 * max(abs(a),
                                                                abs(b)))
        return a == b
    return a == b


def rows_equal(expected: List[dict], actual: List[dict],
               check_order: bool = False, approx_float: bool = True
               ) -> Optional[str]:
    """None when the row sets agree; else a human-readable first diff."""
    if len(expected) != len(actual):
        return f"row count differs: {len(expected)} vs {len(actual)}"
    if not check_order:
        keyfn = lambda r: tuple(str(v) for v in r.values())
        expected = sorted(expected, key=keyfn)
        actual = sorted(actual, key=keyfn)
    for i, (er, ar) in enumerate(zip(expected, actual)):
        if er.keys() != ar.keys():
            return f"row {i}: columns differ {list(er)} vs {list(ar)}"
        for k in er:
            if not val_eq(er[k], ar[k], approx_float):
                return f"row {i} col {k!r}: {er[k]!r} vs {ar[k]!r}"
    return None
