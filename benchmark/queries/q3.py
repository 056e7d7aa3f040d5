"""SQL text q3: TPC-DS query 3 verbatim (two joins, a shuffle, the
sort-segmented aggregate, ORDER BY/LIMIT), with its pandas reference (the
repo's ``sqlgate`` case of that name)."""

from __future__ import annotations

import pandas as pd

SQL = """
select dt.d_year
      ,item.i_brand_id brand_id
      ,item.i_brand brand
      ,sum(ss_ext_sales_price) sum_agg
 from date_dim dt
     ,store_sales
     ,item
 where dt.d_date_sk = store_sales.ss_sold_date_sk
   and store_sales.ss_item_sk = item.i_item_sk
   and item.i_manufact_id = 128
   and dt.d_moy = 11
 group by dt.d_year
         ,item.i_brand_id
         ,item.i_brand
 order by dt.d_year
         ,sum_agg desc
         ,brand_id
 limit 100
"""
ORDER = ("d_year", "sum_agg", "brand_id")
ASCENDING = (True, False, True)
LIMIT = 100
SCAN_COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"],
    "date_dim": ["d_date_sk", "d_year", "d_moy"],
    "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"],
}


def reference(t: dict) -> pd.DataFrame:
    dd, it = t["date_dim"], t["item"]
    m = dd[dd.d_moy == 11].merge(t["store_sales"], left_on="d_date_sk",
                                 right_on="ss_sold_date_sk")
    m = m.merge(it[it.i_manufact_id == 128], left_on="ss_item_sk",
                right_on="i_item_sk")
    g = (m.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
          .agg(sum_agg=("ss_ext_sales_price", "sum")))
    return g.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand"})
