"""TPC-DS query 65 with the specification's qualification parameter (DMS 1176),
as the four Spark stages of ``benchmark/agg_plan.py``:

    select s_store_name, i_item_desc, sc.revenue, i_current_price,
           i_wholesale_cost, i_brand
    from store, item,
         (select ss_store_sk, avg(revenue) as ave
          from (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
                from store_sales, date_dim
                where ss_sold_date_sk = d_date_sk
                  and d_month_seq between 1176 and 1176 + 11
                group by ss_store_sk, ss_item_sk) sa
          group by ss_store_sk) sb,
         (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
          from store_sales, date_dim
          where ss_sold_date_sk = d_date_sk
            and d_month_seq between 1176 and 1176 + 11
          group by ss_store_sk, ss_item_sk) sc
    where sb.ss_store_sk = sc.ss_store_sk and sc.revenue <= 0.1 * sb.ave
      and s_store_sk = sc.ss_store_sk and i_item_sk = sc.ss_item_sk
    order by s_store_name, i_item_desc
    limit 100

Types are Spark's: ``revenue`` DECIMAL(17,2), ``ave`` DECIMAL(21,6) (rounded
half up), ``0.1 * ave`` DECIMAL(23,7), compared exactly. ``ORDER BY
s_store_name, i_item_desc`` ties where two revisions of a store share their
name and both hold one item under the threshold: both sides break ties by the
remaining output columns in the text's order, so that the top 100 is one
answer (``assumed`` in the configuration).

The reference is plain pandas over whole cents, with Python ``decimal`` for
the average and the threshold; it imports nothing of the program.
"""

from __future__ import annotations

import decimal

import pandas as pd

from benchmark import agg_plan

DMS = 1176
OUTPUT = ["s_store_name", "i_item_desc", "revenue", "i_current_price",
          "i_wholesale_cost", "i_brand"]
#: the text's ORDER BY, then the remaining output columns as the tie-break
ORDER = tuple(OUTPUT)
ASCENDING = (True,) * len(OUTPUT)
LIMIT = 100
#: the answer's rows come in the ORDER BY's order, made by the driver's side
IN_ORDER = True
#: what the query's text must read once, whatever plan the engine builds
SCAN_COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_store_sk", "ss_item_sk", "ss_sales_price"],
    "date_dim": ["d_date_sk", "d_month_seq"],
    "store": ["s_store_sk", "s_store_name"],
    "item": ["i_item_sk", "i_item_desc", "i_current_price", "i_wholesale_cost",
             "i_brand"],
}
PLAN = {"name": "q65", "dms": DMS, "output": OUTPUT,
        "order": [(c, True) for c in ORDER], "limit": LIMIT}
ingest = agg_plan.ingest
require_program = agg_plan.require_program


def run(resident: dict, params: dict, work_dir: str, span) -> tuple:
    return agg_plan.run(PLAN, resident, params, work_dir, span)


_SIX = decimal.Decimal(1).scaleb(-6)
_CTX = decimal.Context(prec=60, rounding=decimal.ROUND_HALF_UP)


def _money(cents) -> decimal.Decimal | None:
    return None if pd.isna(cents) else decimal.Decimal(int(round(cents))).scaleb(-2)


def pair_revenue(frames: dict) -> pd.DataFrame:
    """``sa`` and ``sc``: whole cents of revenue by (store, item) over the
    year; a NULL store is a group of its own, a group whose prices are all
    NULL has a NULL revenue."""
    dd = frames["date_dim"]
    year = dd[(dd.d_month_seq >= DMS) & (dd.d_month_seq <= DMS + 11)]
    ss = frames["store_sales"][["ss_sold_date_sk", "ss_store_sk", "ss_item_sk",
                                "ss_sales_price"]]
    ss = ss.dropna(subset=["ss_sold_date_sk"])
    ss = ss[ss.ss_sold_date_sk.isin(year.d_date_sk)]
    return (ss.groupby(["ss_store_sk", "ss_item_sk"], as_index=False, dropna=False)
              .agg(cents=("ss_sales_price", lambda s: s.sum(min_count=1))))


def store_average(pairs: pd.DataFrame) -> pd.DataFrame:
    """``sb``: ``ave`` DECIMAL(21,6), the exact quotient of the store's
    revenues (NULL ones left out) rounded half up, as a ``decimal.Decimal``.
    The store's total is summed in the column's own precision (whole cents
    are exact in pandas' 64-bit sums up to 2**53; the control's float32 money
    is summed in float32), the quotient taken in ``decimal``."""
    rows = []
    for store, g in pairs.groupby("ss_store_sk", dropna=False):
        n = int(g.cents.count())
        ave = None
        if n:
            ave = _CTX.quantize(_CTX.divide(decimal.Decimal(int(g.cents.sum())),
                                            decimal.Decimal(100 * n)), _SIX)
        rows.append({"ss_store_sk": store, "ave": ave})
    out = pd.DataFrame(rows, columns=["ss_store_sk", "ave"])
    out["ss_store_sk"] = out.ss_store_sk.astype("Int64")
    return out


def reference(frames: dict, params: dict | None = None) -> pd.DataFrame:
    """The answer's rows before ORDER BY and LIMIT; ``attrs["sb"]`` holds
    the stores' averages (the NULL store's among them), which the comparison
    holds the program's broadcast to as well."""
    pairs = pair_revenue(frames)
    averages = store_average(pairs)
    sb = averages.dropna(subset=["ss_store_sk", "ave"])
    m = pairs.dropna(subset=["ss_store_sk", "cents"]).merge(sb, on="ss_store_sk")
    # revenue DECIMAL(17,2) <= 0.1 * ave DECIMAL(23,7), both at scale 7: whole
    # numbers on both sides
    keep = [int(c) * 10**5 <= int(a.scaleb(6)) for c, a in zip(m.cents, m.ave)]
    m = m[keep]
    m = m.merge(frames["store"][["s_store_sk", "s_store_name"]],
                left_on="ss_store_sk", right_on="s_store_sk")
    m = m.merge(frames["item"][["i_item_sk", "i_item_desc", "i_current_price",
                                "i_wholesale_cost", "i_brand"]],
                left_on="ss_item_sk", right_on="i_item_sk")
    out = pd.DataFrame({
        "s_store_name": m.s_store_name.tolist(),
        "i_item_desc": m.i_item_desc.tolist(),
        "revenue": [_money(c) for c in m.cents],
        "i_current_price": [_money(c) for c in m.i_current_price],
        "i_wholesale_cost": [_money(c) for c in m.i_wholesale_cost],
        "i_brand": m.i_brand.tolist(),
    }, columns=OUTPUT)
    for c in ("s_store_name", "i_item_desc", "i_brand"):
        out[c] = [None if pd.isna(v) else v for v in out[c]]
    out.attrs["sb"] = averages
    return out
