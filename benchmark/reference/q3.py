"""TPC-DS q3, plainly: brand revenue of one manufacturer in one month."""
import numpy as np

from benchmark.reference import relational as R


def run(db, p, dtype=np.float64):
    date_ok = R.date_flags(db, db.col("date_dim", "d_moy") == p["MONTH"])
    item_ok = R.flags_by_sk(db.n("item"),
                            db.col("item", "i_manufact_id") == p["MANUFACT"])
    date, item = (db.col("store_sales", "ss_sold_date_sk"),
                  db.col("store_sales", "ss_item_sk"))
    keep = np.nonzero(date_ok(date) & item_ok(item))[0]
    year = R.gather(db.col("date_dim", "d_year"), date[keep], R.FIRST_DATE_SK)
    brand_id = R.gather(db.col("item", "i_brand_id"), item[keep])
    brand = R.gather(db.col("item", "i_brand"), item[keep])
    gid, first = R.group_rows([year, brand_id, brand])
    total = R.group_sum(db.col("store_sales", "ss_ext_sales_price")[keep],
                        gid, len(first), dtype)
    return R.answer(
        {"d_year": year[first], "brand_id": brand_id[first],
         "brand": R.texts(R.Coded(brand.codes[first], brand.dictionary)),
         "sum_agg": total},
        ["sum_agg"], [("d_year", "asc"), ("sum_agg", "desc"),
                      ("brand_id", "asc")], 100)
