"""TPC-DS q19, plainly: brand revenue from customers of another zip."""
import numpy as np

from benchmark.reference import relational as R


def run(db, p, dtype=np.float64):
    ss = lambda c: db.col("store_sales", c)
    date_ok = R.date_flags(db, (db.col("date_dim", "d_moy") == p["MONTH"])
                           & (db.col("date_dim", "d_year") == p["YEAR"]))
    item_ok = R.flags_by_sk(db.n("item"),
                            db.col("item", "i_manager_id") == p["MANAGER"])
    cust, store = ss("ss_customer_sk"), ss("ss_store_sk")
    keep = np.nonzero(date_ok(ss("ss_sold_date_sk"))
                      & item_ok(ss("ss_item_sk"))
                      & (cust != R.NULL_SK) & (store != R.NULL_SK))[0]
    addr = R.gather(db.col("customer", "c_current_addr_sk"), cust[keep])
    keep, addr = keep[addr != R.NULL_SK], addr[addr != R.NULL_SK]
    ca_zip = R.gather(db.col("customer_address", "ca_zip"), addr).values()
    s_zip = R.gather(db.col("store", "s_zip"), store[keep]).values()
    other = np.array([a[:5] != s[:5] for a, s in zip(ca_zip, s_zip)], bool)
    keep = keep[other]
    item = ss("ss_item_sk")[keep]
    brand_id = R.gather(db.col("item", "i_brand_id"), item)
    brand = R.gather(db.col("item", "i_brand"), item)
    manu_id = R.gather(db.col("item", "i_manufact_id"), item)
    manu = R.gather(db.col("item", "i_manufact"), item)
    gid, first = R.group_rows([brand_id, brand, manu_id, manu])
    total = R.group_sum(ss("ss_ext_sales_price")[keep], gid, len(first),
                        dtype)
    return R.answer(
        {"brand_id": brand_id[first],
         "brand": R.texts(R.Coded(brand.codes[first], brand.dictionary)),
         "i_manufact_id": manu_id[first],
         "i_manufact": R.texts(R.Coded(manu.codes[first], manu.dictionary)),
         "ext_price": total},
        ["ext_price"], [("ext_price", "desc"), ("brand", "asc"),
                        ("brand_id", "asc"), ("i_manufact_id", "asc"),
                        ("i_manufact", "asc")], 100)
