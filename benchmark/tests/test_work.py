"""``work.py``'s least bytes against values worked by hand."""

import json
import os

from benchmark import work
from benchmark.datagen import tpcds as G
from benchmark.literals import Query
from test_datagen import SMALL


def test_q3_reads_three_fact_columns_and_two_small_dimensions():
    gen = G.StoreChannel(SMALL, 1)
    cols = work.query_columns(Query("q3"), G)
    assert cols == {
        "store_sales": ["ss_sold_date_sk", "ss_item_sk",
                        "ss_ext_sales_price"],
        "date_dim": ["d_date_sk", "d_year", "d_moy"],
        "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"]}
    # int32 keys 4 + 4, double 8; three int32 of date_dim; item: three
    # int32 and "brand #<id>" with 4 bytes of offset
    brands = sum(len("brand #") + len(str(b)) + 4
                 for b in gen.column("item", "i_brand_id"))
    by_hand = 28804 * (4 + 4 + 8) + 73049 * 12 + 180 * 12 + brands
    assert work.query_bytes(Query("q3"), gen, G) == by_hand


def test_q7_reads_eight_fact_columns():
    gen = G.StoreChannel(dict(SMALL, customer_demographics=1920800), 1)
    cols = work.query_columns(Query("q7"), G)
    assert cols["store_sales"] == [
        "ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
        "ss_quantity", "ss_list_price", "ss_sales_price", "ss_coupon_amt"]
    fact = 28804 * (5 * 4 + 3 * 8)
    # cd_demo_sk 4; gender and marital status 1 + 4; education status by its
    # seven words, each a seventh of the rows (the cross product's digits)
    words = [len(w) for w in G.EDUCATION]
    demographics = 1920800 * (4 + 5 + 5 + sum(words) / 7 + 4)
    dates = 73049 * 8
    items = 180 * (4 + 16 + 4)
    promo = 30 * (4 + 5 + 5)
    by_hand = fact + demographics + dates + items + promo
    assert abs(work.query_bytes(Query("q7"), gen, G) - by_hand) < 1e-6 * by_hand


def test_q3_at_the_committed_scale_reads_46_mb_of_fact_columns():
    # SF1: 2,880,404 rows x (4 + 4 + 8) bytes at the configuration's types
    rows = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "configs",
        "tpcds_store_resident.json")))["rows"]
    assert rows["store_sales"] * 16 == 46086464
