"""SQL text q93a: an outer join, a shuffle and an ORDER BY (the repo's
``sqlgate`` case of that name, with its pandas reference)."""

from __future__ import annotations

import numpy as np
import pandas as pd

SQL = """
select i_category
      ,sum(case when p_channel_email = 'Y' then ss_ext_sales_price
                else 0.0 end) promo_sales
      ,sum(ss_ext_sales_price) total_sales
 from store_sales left join promotion
        on ss_promo_sk = p_promo_sk and p_channel_event = 'N'
     ,item
 where ss_item_sk = i_item_sk
 group by i_category
 order by i_category
"""
ORDER: tuple = ()
ASCENDING: tuple = ()
LIMIT = None
SCAN_COLUMNS = {
    "store_sales": ["ss_promo_sk", "ss_item_sk", "ss_ext_sales_price"],
    "promotion": ["p_promo_sk", "p_channel_email", "p_channel_event"],
    "item": ["i_item_sk", "i_category"],
}


def reference(t: dict) -> pd.DataFrame:
    p = t["promotion"]
    j = t["store_sales"].merge(p[p.p_channel_event == "N"],
                               left_on="ss_promo_sk", right_on="p_promo_sk",
                               how="left")
    j = j.merge(t["item"], left_on="ss_item_sk", right_on="i_item_sk")
    zero = j.ss_ext_sales_price.dtype.type(0)
    j["_promo"] = np.where(j.p_channel_email == "Y", j.ss_ext_sales_price, zero)
    return (j.groupby("i_category", as_index=False)
             .agg(promo_sales=("_promo", "sum"),
                  total_sales=("ss_ext_sales_price", "sum")))
