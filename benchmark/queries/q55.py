"""TPC-DS query 55 with the specification's qualification parameters
(MANAGER 28, MONTH 11, YEAR 1999), as the two Spark stages of ``benchmark/star_plan.py``:

    select i_brand_id brand_id, i_brand brand,
           sum(ss_ext_sales_price) ext_price
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manager_id = 28 and d_moy = 11 and d_year = 1999
    group by i_brand, i_brand_id
    order by ext_price desc, i_brand_id
    limit 100

The reference is plain pandas over whole cents; it imports nothing of the
program.
"""

from __future__ import annotations

import decimal
import functools

import pandas as pd

from benchmark import star_plan

PLAN = {
    "name": "q55",
    "date_filter": {"d_moy": 11, "d_year": 1999},
    "item_filter": {"i_manager_id": 28},
    "keys": [("item", "i_brand", "brand"), ("item", "i_brand_id", "brand_id")],
    "sum": ("ss_ext_sales_price", "ext_price"),
    "output": ["brand_id", "brand", "ext_price"],
    "order": [("ext_price", False), ("brand_id", True)],
    "limit": 100,
}
ORDER = tuple(c for c, _ in PLAN["order"])
ASCENDING = tuple(a for _, a in PLAN["order"])
LIMIT = PLAN["limit"]
#: the answer's rows come in the ORDER BY's order, made by the driver's side
IN_ORDER = True
#: what the query's text must read once, whatever plan the engine builds
SCAN_COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"],
    "date_dim": ["d_date_sk", "d_year", "d_moy"],
    "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manager_id"],
}
ingest = star_plan.ingest
run = functools.partial(star_plan.run, PLAN)


def reference(frames: dict, params: dict | None = None) -> pd.DataFrame:
    """The answer's rows before ORDER BY and LIMIT, sums as exact decimals."""
    dd, it = frames["date_dim"], frames["item"]
    m = dd[(dd.d_moy == 11) & (dd.d_year == 1999)].merge(
        frames["store_sales"].dropna(subset=["ss_sold_date_sk"]),
        left_on="d_date_sk", right_on="ss_sold_date_sk")
    m = m.merge(it[it.i_manager_id == 28], left_on="ss_item_sk", right_on="i_item_sk")
    g = (m.groupby(["i_brand_id", "i_brand"], as_index=False, dropna=False)
          .agg(cents=("ss_ext_sales_price", lambda s: s.sum(min_count=1))))
    g["ext_price"] = [None if pd.isna(c) else decimal.Decimal(int(round(c))).scaleb(-2)
                  for c in g.pop("cents")]
    return g.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand"})
