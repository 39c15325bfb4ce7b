import json
import os

import numpy as np
import pytest

from benchmark.datagen import tpcds as G

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = dict(store_sales=28804, store_returns=2875, customer=1000,
             customer_address=500, customer_demographics=19208, item=180,
             date_dim=73049, household_demographics=7200, store=12,
             promotion=30)


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_config_names_the_specifications_rows_and_its_one_cut():
    c = config("tpcds_store_resident")
    source, rows = c["source_rows"], c["rows"]
    assert (c["source_scale_factor"], c["scale_factor"]) == (10, 1)
    assert source["store_sales"] == 28800991
    assert set(rows) == set(source) == set(G.StoreChannel.TABLES)
    # what is run is the specification's SF1, every table whole
    assert rows == dict(
        store_sales=2880404, store_returns=287514, customer=100000,
        customer_address=50000, item=18000, store=12, promotion=300,
        customer_demographics=2 * 5 * 7 * 20 * 4 * 7 * 7 * 7,
        household_demographics=20 * 6 * 10 * 6, date_dim=73049)
    assert set(c["cuts"]) == {"scale_factor", "what_comes_back"}
    assert set(c["fixed_tables"]) <= set(rows)


def test_the_generator_module_is_found_by_the_configs_name():
    import importlib
    c = config("tpcds_store_resident")
    module = importlib.import_module("benchmark.datagen." + c["datagen"])
    gen = module.make(SMALL, 3, **c["datagen_args"])
    assert gen.n("item") == SMALL["item"]
    assert (gen.col("item", "i_item_sk") == np.arange(1, 181)).all()
    wanted = module.columns_named({"ss_item_sk", "d_year", "select", "sum"})
    assert wanted == {"store_sales": ["ss_item_sk"], "date_dim": ["d_year"]}
    tables = module.arrow_tables(gen, wanted, c["integer_type"])
    assert tables["date_dim"].column_names == ["d_date_sk", "d_year"]
    assert tables["store_sales"].num_rows == SMALL["store_sales"]


@pytest.mark.parametrize("table", G.StoreChannel.TABLES)
def test_every_column_of_the_schema_is_generated(table):
    gen = G.StoreChannel(SMALL, 11)
    t = G.arrow_table(gen, table, G.SCHEMA[table], "int32")
    assert t.num_rows == SMALL[table]
    assert t.column_names == list(G.SCHEMA[table])


def test_schema_has_the_specifications_column_counts():
    counts = {t: len(c) for t, c in G.SCHEMA.items()}
    assert counts == {"store_sales": 23, "store_returns": 20, "date_dim": 28,
                      "item": 22, "store": 29, "customer": 18,
                      "customer_address": 13, "customer_demographics": 9,
                      "household_demographics": 5, "promotion": 19}


@pytest.mark.parametrize("column,target", sorted(G.FOREIGN_KEYS.items()))
def test_foreign_keys_are_valid_or_null(column, target):
    gen = G.StoreChannel(SMALL, 2**31 + 12345)
    fk = gen.column(G.TABLE_OF_COLUMN[column], column)
    key = gen.column(target, G.SCHEMA[target][0])
    assert np.isin(fk[fk != G.NULL_SK], key).all()
    assert (fk != G.NULL_SK).any()


def test_nullable_fact_keys_are_null_at_the_stated_share():
    gen = G.StoreChannel(SMALL, 5)
    share = (gen.column("store_sales", "ss_promo_sk") == G.NULL_SK).mean()
    assert 0.03 < share < 0.06
    assert (gen.column("store_sales", "ss_item_sk") != G.NULL_SK).all()


def test_same_seed_same_data_other_seed_other_data():
    a = G.StoreChannel(SMALL, 3000000019)
    b = G.StoreChannel(SMALL, 3000000019)
    c = G.StoreChannel(SMALL, 3000000020)
    for col in ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"):
        assert np.array_equal(a.column("store_sales", col),
                              b.column("store_sales", col))
    assert not np.array_equal(a.column("store_sales", "ss_ext_sales_price"),
                              c.column("store_sales", "ss_ext_sales_price"))


def test_a_column_does_not_depend_on_which_others_were_made():
    a = G.StoreChannel(SMALL, 9)
    b = G.StoreChannel(SMALL, 9)
    for col in G.SCHEMA["store_sales"]:
        a.column("store_sales", col)
    assert np.array_equal(a.column("store_sales", "ss_net_profit"),
                          b.column("store_sales", "ss_net_profit"))


def test_sales_dates_follow_the_three_zones():
    gen = G.StoreChannel(dict(SMALL, store_sales=400000), 3)
    sold = gen.column("store_sales", "ss_sold_date_sk")
    sold = sold[sold != G.NULL_SK]
    month = gen.column("date_dim", "d_moy")[sold - G.FIRST_DATE_SK]
    year = gen.column("date_dim", "d_year")[sold - G.FIRST_DATE_SK]
    assert year.min() == 1998 and year.max() <= 2003
    per_day = lambda lo, hi, days: ((month >= lo) & (month <= hi)).sum() / days
    low, medium, high = per_day(1, 7, 212), per_day(8, 10, 92), per_day(11, 12, 61)
    assert 1.3 < medium / low < 1.7 and 2.2 < high / low < 2.8


def test_a_ticket_shares_its_customer_and_names_different_items():
    gen = G.StoreChannel(SMALL, 4)
    ticket = gen.column("store_sales", "ss_ticket_number")
    customer = gen.column("store_sales", "ss_customer_sk")
    item = gen.column("store_sales", "ss_item_sk")
    same = ticket[1:] == ticket[:-1]
    assert (customer[1:][same] == customer[:-1][same]).all()
    pairs = np.stack([ticket, item], axis=1)
    assert len(np.unique(pairs, axis=0)) == len(pairs)


def test_returns_name_sold_lines():
    gen = G.StoreChannel(SMALL, 6)
    sold = set(zip(gen.column("store_sales", "ss_ticket_number").tolist(),
                   gen.column("store_sales", "ss_item_sk").tolist()))
    back = list(zip(gen.column("store_returns", "sr_ticket_number").tolist(),
                    gen.column("store_returns", "sr_item_sk").tolist()))
    assert set(back) <= sold and len(set(back)) == len(back)


def test_identifiers_are_int32_and_tickets_int64_as_nds_types_them():
    import pyarrow as pa
    gen = G.StoreChannel(SMALL, 8)
    t = G.arrow_table(gen, "store_sales",
                      ["ss_item_sk", "ss_ticket_number", "ss_list_price",
                       "ss_promo_sk"], "int32")
    assert t.schema.field("ss_item_sk").type == pa.int32()
    assert t.schema.field("ss_ticket_number").type == pa.int64()
    assert t.schema.field("ss_list_price").type == pa.float64()
    assert t.column("ss_promo_sk").null_count > 0
