"""TPC-DS query 93 with the specification's qualification parameter (REASON
'reason 28'), as the four Spark stages of ``benchmark/returns_plan.py``:

    select ss_customer_sk, sum(act_sales) sumsales
    from (select ss_item_sk, ss_ticket_number, ss_customer_sk,
                 case when sr_return_quantity is not null
                      then (ss_quantity - sr_return_quantity) * ss_sales_price
                      else (ss_quantity * ss_sales_price) end act_sales
          from store_sales left outer join store_returns
               on (sr_item_sk = ss_item_sk and sr_ticket_number = ss_ticket_number),
               reason
          where sr_reason_sk = r_reason_sk and r_reason_desc = 'reason 28') t
    group by ss_customer_sk
    order by sumsales, ss_customer_sk
    limit 100

Types are Spark's: ``act_sales`` DECIMAL(18,2), ``sumsales`` DECIMAL(28,2),
exact. ``ORDER BY sumsales, ss_customer_sk`` puts NULL sums first (a
customer all of whose lines have a NULL quantity or price) and breaks every
tie by the customer, one group each: the top 100 is one answer, and it is
mostly NULL and zero sums (a line returned whole sells nothing). So the
reference also hangs every customer's sum on its frame (``attrs["sums"]``),
and the plan hangs the groups its driver holds under the same key.

The reference evaluates the text as written, in plain pandas over whole
cents: the LEFT OUTER join, the WHERE clause (which no row the outer join
extended with NULLs passes), the CASE on a NULL ``sr_return_quantity``, the
sums in the money's own precision (whole cents are exact in 64-bit integers;
the control's lower-precision money is multiplied and summed in that
precision). It imports nothing of the program.
"""

from __future__ import annotations

import decimal

import pandas as pd

from benchmark import returns_plan

REASON = "reason 28"
OUTPUT = ["ss_customer_sk", "sumsales"]
#: the text's ORDER BY, whose second key already breaks every tie
ORDER = ("sumsales", "ss_customer_sk")
ASCENDING = (True, True)
LIMIT = 100
#: the answer's rows come in the ORDER BY's order, made by the driver's side
IN_ORDER = True
#: what the query's text must read once, whatever plan the engine builds
SCAN_COLUMNS = {
    "store_sales": list(returns_plan.FACT_COLUMNS),
    "store_returns": list(returns_plan.RETURN_COLUMNS),
    "reason": ["r_reason_sk", "r_reason_desc"],
}
PLAN = {"name": "q93", "reason": REASON, "output": OUTPUT,
        "order": [(c, True) for c in ORDER], "limit": LIMIT}
ingest = returns_plan.ingest
require_program = returns_plan.require_program


def run(resident: dict, params: dict, work_dir: str, span) -> tuple:
    return returns_plan.run(PLAN, resident, params, work_dir, span)


def _money(cents) -> decimal.Decimal | None:
    return None if pd.isna(cents) else decimal.Decimal(int(round(cents))).scaleb(-2)


def customer_cents(frames: dict) -> pd.Series:
    """``sum(act_sales)`` by ``ss_customer_sk`` in whole cents (a NULL
    customer a group of its own, a group whose act_sales are all NULL a
    NULL sum)."""
    ss = frames["store_sales"][list(returns_plan.FACT_COLUMNS)]
    sr = frames["store_returns"][list(returns_plan.RETURN_COLUMNS)]
    m = ss.merge(sr, how="left", left_on=["ss_item_sk", "ss_ticket_number"],
                 right_on=["sr_item_sk", "sr_ticket_number"])
    r = frames["reason"]
    r = r[r.r_reason_desc == REASON][["r_reason_sk"]]
    # sr_reason_sk = r_reason_sk: a NULL on either side never equals
    m = m.dropna(subset=["sr_reason_sk"]).merge(r, left_on="sr_reason_sk",
                                                right_on="r_reason_sk")
    price = m.ss_sales_price
    quantity, back = m.ss_quantity, m.sr_return_quantity
    if pd.api.types.is_float_dtype(price.dtype):
        quantity, back = quantity.astype(price.dtype), back.astype(price.dtype)
    act = ((quantity - back) * price).where(back.notna(), quantity * price)
    return act.groupby(m.ss_customer_sk, dropna=False).sum(min_count=1)


def reference(frames: dict, params: dict | None = None) -> pd.DataFrame:
    """The answer's rows before ORDER BY and LIMIT: every customer's sum;
    ``attrs["sums"]`` holds the same rows, which the comparison holds the
    program's groups to as well."""
    cents = customer_cents(frames)
    out = pd.DataFrame({
        "ss_customer_sk": pd.array(cents.index, dtype="Int64"),
        "sumsales": [_money(c) for c in cents.to_numpy()],
    }, columns=OUTPUT)
    out.attrs["sums"] = out.copy()
    return out
