"""The tables keep to the specification's shapes, and a seed gives the same
tables again."""

import numpy as np

from benchmark import datagen

SEED = 2147483659


def test_same_seed_same_tables_and_another_seed_others():
    a, b = datagen.tpcds(0.05, SEED), datagen.tpcds(0.05, SEED)
    assert all(a[t].equals(b[t]) for t in a)
    c = datagen.tpcds(0.05, SEED + 1)
    assert not a["store_sales"].equals(c["store_sales"])
    assert a["date_dim"].equals(c["date_dim"])      # the calendar has no seed


def test_date_dim_is_the_specifications_calendar():
    dd = datagen.tpcds(0.01, SEED)["date_dim"]
    assert len(dd) == 73049
    first, last = dd.iloc[0], dd.iloc[-1]
    assert (first.d_date_sk, str(first.d_date)) == (2415022, "1900-01-02")
    assert (last.d_date_sk, str(last.d_date)) == (2488070, "2100-01-01")
    jan2000 = dd[(dd.d_year == 2000) & (dd.d_moy == 1)]
    assert set(jan2000.d_month_seq) == {1200} and len(jan2000) == 31
    assert dd[dd.d_date_sk == 2450815].iloc[0].d_day_name == "Thursday"  # 1998-01-01


def test_store_sales_shapes():
    ss = datagen.tpcds(0.1, SEED)["store_sales"]
    assert len(ss) == round(2_880_404 * 0.1)
    assert not ss.duplicated(["ss_item_sk", "ss_ticket_number"]).any()
    lines = ss.groupby("ss_ticket_number").size()
    assert lines.iloc[:-1].between(8, 16).all()
    for c in ("ss_sold_date_sk", "ss_customer_sk", "ss_store_sk"):
        per_ticket = ss.groupby("ss_ticket_number")[c].nunique()
        assert (per_ticket <= 1).all()      # shared by the ticket's lines
    dates = ss.ss_sold_date_sk.dropna()
    assert dates.is_monotonic_increasing
    assert dates.min() >= datagen.SALES_FIRST_SK and dates.max() <= datagen.SALES_LAST_SK
    nulls = ss.isna().mean()
    assert nulls[["ss_item_sk", "ss_ticket_number"]].eq(0).all()
    assert nulls.drop(["ss_item_sk", "ss_ticket_number"]).between(0.035, 0.055).all()
    money = ss.dropna()
    assert (money.ss_ext_sales_price == money.ss_sales_price * money.ss_quantity).all()
    assert (money.ss_net_paid == money.ss_ext_sales_price - money.ss_coupon_amt).all()
    assert (money.ss_net_profit == money.ss_net_paid - money.ss_ext_wholesale_cost).all()
    assert money.ss_ext_list_price.max() <= 9_999_999      # DECIMAL(7,2) in cents


def test_sales_calendar_has_three_zones():
    f = datagen.tpcds(0.2, SEED)
    m = f["store_sales"].merge(f["date_dim"][["d_date_sk", "d_moy"]],
                               left_on="ss_sold_date_sk", right_on="d_date_sk")
    share = m.d_moy.value_counts(normalize=True)
    low, mid, high = share[[1, 4, 7]].mean(), share[[8, 9, 10]].mean(), share[[11, 12]].mean()
    assert 1.7 < mid / low < 2.3 and 2.6 < high / low < 3.4


def test_item_is_history_keeping_and_brand_follows_brand_id():
    it = datagen.tpcds(0.01, SEED)["item"]
    assert len(it) == 18000 and it.i_item_sk.is_unique
    assert it.groupby("i_item_id").size().between(1, 3).all()
    assert it.i_rec_end_date.isna().sum() == it.i_item_id.nunique()
    known = it.dropna(subset=["i_brand_id", "i_brand"])
    assert (known.groupby("i_brand_id").i_brand.nunique() == 1).all()
    assert known.i_manufact_id.dropna().between(1, 1000).all()
    assert np.isclose(it.i_brand.isna().mean(), 0.0025, atol=0.002)
