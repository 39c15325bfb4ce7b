"""The few relational steps the plain reference needs, in plain numpy.

Independent of the engine: it imports nothing of ``spark_rapids_tpu`` and
reads only the generated columns (``db`` is the configuration's generator:
``db.col(table, name)``, ``db.n(table)``).  ``dtype`` is the precision in which money
is held and accumulated: float64 as the configurations state, float32 only
for the control that has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen.tpcds import FIRST_DATE_SK, NULL_SK, Coded


def flags_by_sk(n_rows: int, mask, first_sk: int = 1):
    """Lookup ``sk -> mask`` that answers False for NULL_SK."""
    flags = np.zeros(n_rows + 1, dtype=bool)      # [-1] stays False
    flags[:n_rows] = mask
    return lambda fk: flags[np.where(fk == NULL_SK, -1, fk - first_sk)]


def date_flags(db, mask):
    return flags_by_sk(db.n("date_dim"), mask, FIRST_DATE_SK)


def gather(column, fk, first_sk: int = 1):
    """``column`` of the dimension row each (non-null) ``fk`` points at."""
    idx = fk - first_sk
    if isinstance(column, Coded):
        return Coded(column.codes[idx], column.dictionary)
    return np.asarray(column)[idx]


def group_rows(keys):
    """Group ids of rows by a list of key columns (ints or Coded).
    Returns (group id per row, one representative row index per group)."""
    cols = [k.codes if isinstance(k, Coded) else np.asarray(k) for k in keys]
    if not len(cols[0]):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    stacked = np.stack([c.astype(np.int64) for c in cols], axis=1)
    _, first, inverse = np.unique(stacked, axis=0, return_index=True,
                                  return_inverse=True)
    return inverse.reshape(-1), first


def group_sum(values, gid, n_groups: int, dtype):
    """Sum per group, held and accumulated in ``dtype``."""
    if not n_groups:
        return np.zeros(0, dtype)
    order = np.argsort(gid, kind="stable")
    starts = np.searchsorted(gid[order], np.arange(n_groups))
    return np.add.reduceat(np.asarray(values)[order].astype(dtype), starts,
                           dtype=dtype)


def group_avg(values, gid, n_groups: int, dtype):
    count = np.bincount(gid, minlength=n_groups)
    return (group_sum(values, gid, n_groups, dtype) / count.astype(dtype))


def texts(column):
    """Result column of python strings from a Coded column."""
    return [str(s) for s in column.values()]


def answer(columns: dict, float_columns, order, limit):
    """One reference answer: every group, not yet ordered or cut."""
    names = list(columns)
    cols = [list(v.tolist() if isinstance(v, np.ndarray) else v)
            for v in columns.values()]
    rows = [dict(zip(names, vals)) for vals in zip(*cols)]
    return {"columns": names, "float_columns": list(float_columns),
            "order": list(order), "limit": limit, "rows": rows}
