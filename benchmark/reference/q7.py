"""TPC-DS q7, plainly: average line of one demographic under promotions."""
import numpy as np

from benchmark.reference import relational as R

MEASURES = (("agg1", "ss_quantity"), ("agg2", "ss_list_price"),
            ("agg3", "ss_coupon_amt"), ("agg4", "ss_sales_price"))


def demographic(db, p):
    cd = lambda c: db.col("customer_demographics", c)
    return R.flags_by_sk(
        db.n("customer_demographics"),
        cd("cd_gender").equals(p["GEN"])
        & cd("cd_marital_status").equals(p["MS"])
        & cd("cd_education_status").equals(p["ES"]))


def run(db, p, dtype=np.float64):
    ss = lambda c: db.col("store_sales", c)
    date_ok = R.date_flags(db, db.col("date_dim", "d_year") == p["YEAR"])
    item_ok = R.flags_by_sk(db.n("item"), np.ones(db.n("item"), bool))
    promo_ok = R.flags_by_sk(
        db.n("promotion"),
        db.col("promotion", "p_channel_email").equals("N")
        | db.col("promotion", "p_channel_event").equals("N"))
    keep = np.nonzero(date_ok(ss("ss_sold_date_sk"))
                      & item_ok(ss("ss_item_sk"))
                      & demographic(db, p)(ss("ss_cdemo_sk"))
                      & promo_ok(ss("ss_promo_sk")))[0]
    item_id = R.gather(db.col("item", "i_item_id"), ss("ss_item_sk")[keep])
    gid, first = R.group_rows([item_id])
    out = {"i_item_id": R.texts(R.Coded(item_id.codes[first],
                                        item_id.dictionary))}
    for name, column in MEASURES:
        out[name] = R.group_avg(ss(column)[keep], gid, len(first), dtype)
    return R.answer(out, [m for m, _ in MEASURES], [("i_item_id", "asc")],
                    100)
