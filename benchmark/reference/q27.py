"""TPC-DS q27, plainly: q7's averages by item and state, rolled up."""
import numpy as np

from benchmark.reference import relational as R
from benchmark.reference.q7 import MEASURES, demographic


def run(db, p, dtype=np.float64):
    ss = lambda c: db.col("store_sales", c)
    date_ok = R.date_flags(db, db.col("date_dim", "d_year") == p["YEAR"])
    store_ok = R.flags_by_sk(db.n("store"),
                             db.col("store", "s_state").equals(p["STATE"]))
    keep = np.nonzero(date_ok(ss("ss_sold_date_sk"))
                      & (ss("ss_item_sk") != R.NULL_SK)
                      & store_ok(ss("ss_store_sk"))
                      & demographic(db, p)(ss("ss_cdemo_sk")))[0]
    item_id = R.gather(db.col("item", "i_item_id"), ss("ss_item_sk")[keep])
    state = R.gather(db.col("store", "s_state"), ss("ss_store_sk")[keep])
    levels = []
    # rollup(i_item_id, s_state): both, the item alone, the grand total
    for keys, g_state in (([item_id, state], 0), ([item_id], 1), ([], 1)):
        if keys:
            gid, first = R.group_rows(keys)
        else:
            # the grand total; over no rows there is none (Spark's rollup is
            # an expand under a group-by, which is the semantics the engine
            # states, not the standard's one row over nothing)
            gid = np.zeros(len(keep), np.int64)
            first = np.zeros(min(1, len(keep)), np.int64)
        n = len(first)
        names = ([str(s) for s in item_id.values()[first]] if keys
                 else [None] * n)
        states = ([str(s) for s in state.values()[first]] if len(keys) == 2
                  else [None] * n)
        level = {"i_item_id": names, "s_state": states,
                 "g_state": [g_state] * n}
        for name, column in MEASURES:
            level[name] = R.group_avg(ss(column)[keep], gid, n,
                                      dtype).tolist()
        levels.append(level)
    merged = {k: sum((lv[k] for lv in levels), []) for k in levels[0]}
    return R.answer(merged, [m for m, _ in MEASURES],
                    [("i_item_id", "asc"), ("s_state", "asc")], 100)
