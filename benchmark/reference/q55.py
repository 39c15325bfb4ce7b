"""TPC-DS q55, plainly: brand revenue of one manager's items in a month."""
import numpy as np

from benchmark.reference import relational as R


def run(db, p, dtype=np.float64):
    date_ok = R.date_flags(db, (db.col("date_dim", "d_moy") == p["MONTH"])
                           & (db.col("date_dim", "d_year") == p["YEAR"]))
    item_ok = R.flags_by_sk(db.n("item"),
                            db.col("item", "i_manager_id") == p["MANAGER"])
    date, item = (db.col("store_sales", "ss_sold_date_sk"),
                  db.col("store_sales", "ss_item_sk"))
    keep = np.nonzero(date_ok(date) & item_ok(item))[0]
    brand_id = R.gather(db.col("item", "i_brand_id"), item[keep])
    brand = R.gather(db.col("item", "i_brand"), item[keep])
    gid, first = R.group_rows([brand_id, brand])
    total = R.group_sum(db.col("store_sales", "ss_ext_sales_price")[keep],
                        gid, len(first), dtype)
    return R.answer(
        {"brand_id": brand_id[first],
         "brand": R.texts(R.Coded(brand.codes[first], brand.dictionary)),
         "ext_price": total},
        ["ext_price"], [("ext_price", "desc"), ("brand_id", "asc")], 100)
