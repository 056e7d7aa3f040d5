"""Query 65's own derived table ``sb`` as a statement (``sql/q65_sb.sql``):

    select ss_store_sk, avg(revenue) as ave
    from (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
          from store_sales, date_dim
          where ss_sold_date_sk = d_date_sk
            and d_month_seq between 1176 and 1176 + 11
          group by ss_store_sk, ss_item_sk) sa
    group by ss_store_sk

One row a store (the NULL store's group among them), ``ave`` DECIMAL(21,6)
rounded half up. The reference is ``q65.store_average`` of
``q65.pair_revenue``: what ``q65.reference`` hangs on its frame as
``attrs["sb"]``. Every one of the year's (store, item) sums is in an average:
at sf=24 a store has about 18,000 pairs, so a cent on one of them moves its
average by 5.6e-7, seen in the sixth place in about half the cases and always
from two cents on (a cent always shows at the tests' small scales); money
summed in float32 moves every store's. The 13 rows are what holds the pair
sums where query 65's own top 100 holds few rows or none.
The text has no ORDER BY: the rows are compared as a set.
"""

from __future__ import annotations

import pandas as pd

from benchmark.harness import load_module

_q65 = load_module("queries", "q65")

DMS = _q65.DMS
ORDER: tuple = ()
ASCENDING: tuple = ()
LIMIT = None
IN_ORDER = False
#: what the statement's text must read once, whatever plan the engine builds
SCAN_COLUMNS = {
    "store_sales": _q65.SCAN_COLUMNS["store_sales"],
    "date_dim": _q65.SCAN_COLUMNS["date_dim"],
}


def reference(frames: dict, params: dict | None = None) -> pd.DataFrame:
    return _q65.store_average(_q65.pair_revenue(frames))
