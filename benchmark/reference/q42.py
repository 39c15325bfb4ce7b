"""TPC-DS q42, plainly: category revenue of manager 1's items in a month."""
import numpy as np

from benchmark.reference import relational as R


def run(db, p, dtype=np.float64):
    date_ok = R.date_flags(db, (db.col("date_dim", "d_moy") == p["MONTH"])
                           & (db.col("date_dim", "d_year") == p["YEAR"]))
    item_ok = R.flags_by_sk(db.n("item"),
                            db.col("item", "i_manager_id") == 1)
    date, item = (db.col("store_sales", "ss_sold_date_sk"),
                  db.col("store_sales", "ss_item_sk"))
    keep = np.nonzero(date_ok(date) & item_ok(item))[0]
    year = R.gather(db.col("date_dim", "d_year"), date[keep], R.FIRST_DATE_SK)
    cat_id = R.gather(db.col("item", "i_category_id"), item[keep])
    cat = R.gather(db.col("item", "i_category"), item[keep])
    gid, first = R.group_rows([year, cat_id, cat])
    total = R.group_sum(db.col("store_sales", "ss_ext_sales_price")[keep],
                        gid, len(first), dtype)
    return R.answer(
        {"d_year": year[first], "i_category_id": cat_id[first],
         "i_category": R.texts(R.Coded(cat.codes[first], cat.dictionary)),
         "s": total},
        ["s"], [("s", "desc"), ("d_year", "asc"), ("i_category_id", "asc"),
                ("i_category", "asc")], 100)
