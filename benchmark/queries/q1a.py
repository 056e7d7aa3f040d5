"""SQL text q1a: one join and a global aggregate (the repo's ``sqlgate`` case
of that name, with its pandas reference)."""

from __future__ import annotations

import numpy as np
import pandas as pd

SQL = """
select count(*) cnt
      ,sum(ss_ext_sales_price) total
      ,avg(ss_ext_sales_price) mean
 from store_sales, date_dim
 where ss_sold_date_sk = d_date_sk
   and d_year = 2000
"""
ORDER: tuple = ()
ASCENDING: tuple = ()
LIMIT = None
SCAN_COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_ext_sales_price"],
    "date_dim": ["d_date_sk", "d_year"],
}


def reference(t: dict) -> pd.DataFrame:
    dd = t["date_dim"]
    m = t["store_sales"].merge(dd[dd.d_year == 2000],
                               left_on="ss_sold_date_sk", right_on="d_date_sk")
    return pd.DataFrame({
        "cnt": [np.int64(len(m))],
        "total": [m.ss_ext_sales_price.sum(min_count=1)],
        "mean": [m.ss_ext_sales_price.mean()],
    })
