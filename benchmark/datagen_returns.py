"""``store_returns`` and ``reason``, made from ``--seed``, beside ``datagen.py``'s
tables.

``tpcds_returns`` makes what ``datagen.tpcds`` makes (``store_sales``,
``date_dim``, ``item``: the very same frames at one seed) and adds the two
tables in the shapes of the TPC-DS specification (v3.2.0): ``store_returns``
with its 20 columns (clause 2.3.2) and ``reason`` with its 3 (clause 2.4.9);
names, order, types and nullability are in ``benchmark/schemas/
tpcds_returns.json``. Money is DECIMAL(7,2) kept as whole cents (nullable
``Int64``), as ``datagen.py`` keeps it.

A table comes as files of its own (``README-batch_agg.md``): this module
brings ``make`` and ``schemas``, and the configuration names it
(``data.module``).

What follows dsdgen's documented behaviour, not its random streams or
distribution files (neither is at hand; ``assumed`` in the configuration):

- a return is a sold line, ``(ss_item_sk, ss_ticket_number)``, drawn with
  dsdgen's return rate of about 10 % of the lines (``RETURN_SHARE``), so the
  table's primary key is unique and every return has its sale; the returns
  come in the order of their sales;
- ``sr_return_quantity`` is uniform over 1 .. the sold quantity (1 .. 100
  where the sale's quantity is NULL); ``sr_reason_sk`` is uniform over the
  reasons; the customer, demographics, address and store are the sale's;
  the return comes 1 .. 90 days after the sale;
- the money: ``sr_return_amt`` is the quantity returned at the sale's price,
  tax at the sale's 0 .. 9 %, a fee of 0.50 .. 100.00, a shipping cost of up
  to half the wholesale cost of what comes back, the amount with its tax
  split at random between cash refunded, charge reversed and store credit,
  and ``sr_net_loss`` the fee, the shipping and the tax;
- 9 % of the rows draw a random bitmap of NULLs over every column but the
  primary key, as the fact table's do;
- ``reason`` has the 35 rows of scale factor 1, ``r_reason_desc`` spelled as
  dsdgen's ``return_reasons`` list spells them: 22 reasons in words, then
  ``reason 23`` .. ``reason 35``, so that query 93's ``'reason 28'`` names
  exactly one row.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pandas as pd

from benchmark import datagen

HERE = os.path.dirname(os.path.abspath(__file__))

#: the share of sold lines that come back (dsdgen's return rate)
RETURN_SHARE = 0.10
#: ``reason`` at scale factor 1, in r_reason_sk order
REASONS = [
    "Package was damaged", "Stopped working", "Did not get it on time",
    "Not the product that was ordred", "Parts missing",
    "Does not work with a product that I have", "Gift exchange",
    "Did not like the color", "Did not like the model", "Did not like the make",
    "Did not like the warranty", "No service location in my area",
    "Found a better price in a store",
    "Found a better extended warranty in a store", "Not working any more",
    "Did not fit", "Wrong size", "Lost my job", "unauthoized purchase",
    "duplicate purchase", "its is a boy", "it is a girl",
] + [f"reason {k}" for k in range(23, 36)]


def schemas() -> dict:
    """``{table: [[column, type, nullable], ...]}``: ``schemas/tpcds.json``'s
    tables and ``schemas/tpcds_returns.json``'s."""
    with open(os.path.join(HERE, "schemas", "tpcds_returns.json")) as f:
        more = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
    return {**datagen.schemas(), **more}


def make(config: dict, seed: int) -> dict:
    """The frames a configuration's ``data`` entry names, from the seed."""
    return globals()[config["data"]["generator"]](config["data"]["sf"], seed)


def tpcds_returns(sf: float, seed: int) -> dict:
    frames = dict(datagen.tpcds(sf, seed))
    frames["store_returns"] = store_returns(frames["store_sales"], seed)
    frames["reason"] = reason()
    for table in ("store_returns", "reason"):
        if list(frames[table].columns) != [c for c, _, _ in schemas()[table]]:
            raise AssertionError(
                f"datagen_returns and schemas/tpcds_returns.json disagree on {table}")
    return frames


def reason() -> pd.DataFrame:
    sk = np.arange(1, len(REASONS) + 1, dtype=np.int64)
    return pd.DataFrame({
        "r_reason_sk": sk,
        "r_reason_id": np.array([datagen._bkey(k) for k in sk], dtype=object),
        "r_reason_desc": np.array(REASONS, dtype=object),
    })


def _plane(ss: pd.DataFrame, column: str, rows: np.ndarray):
    """A fact column's values (NULL lanes hold 0) and NULL mask at ``rows``."""
    a = ss[column].array
    if hasattr(a, "_mask"):
        return a._data[rows], a._mask[rows]
    return np.asarray(a)[rows], np.zeros(len(rows), bool)


def store_returns(ss: pd.DataFrame, seed: int) -> pd.DataFrame:
    rng = datagen._rng(seed, "store_returns")
    rows = np.flatnonzero(rng.random(len(ss)) < RETURN_SHARE)
    m = len(rows)
    sale = {c: _plane(ss, c, rows) for c in (
        "ss_sold_date_sk", "ss_customer_sk", "ss_cdemo_sk", "ss_hdemo_sk",
        "ss_addr_sk", "ss_store_sk", "ss_quantity", "ss_sales_price",
        "ss_wholesale_cost")}
    qty, qty_null = sale["ss_quantity"]
    sold = np.where(qty_null, 100, qty).astype(np.int64)
    back = rng.integers(1, sold + 1)
    date, date_null = sale["ss_sold_date_sk"]
    date = np.where(date_null, rng.integers(datagen.SALES_FIRST_SK,
                                            datagen.SALES_LAST_SK + 1, m), date)
    price = sale["ss_sales_price"][0].astype(np.int64)
    amt = back * price
    tax = (amt * rng.integers(0, 10, m) + 50) // 100
    fee = rng.integers(50, 10_001, m)
    ship = (back * sale["ss_wholesale_cost"][0] * rng.integers(0, 51, m) + 50) // 100
    due = amt + tax
    cut = np.sort(rng.random((m, 2)), axis=1)
    cash = (due * cut[:, 0]).astype(np.int64)
    charge = (due * cut[:, 1]).astype(np.int64) - cash
    values = {
        "sr_returned_date_sk": date + rng.integers(1, 91, m),
        "sr_return_time_sk": rng.integers(28800, 75600, m),
        "sr_item_sk": ss["ss_item_sk"].to_numpy()[rows],
        "sr_customer_sk": sale["ss_customer_sk"][0],
        "sr_cdemo_sk": sale["ss_cdemo_sk"][0],
        "sr_hdemo_sk": sale["ss_hdemo_sk"][0],
        "sr_addr_sk": sale["ss_addr_sk"][0],
        "sr_store_sk": sale["ss_store_sk"][0],
        "sr_reason_sk": rng.integers(1, len(REASONS) + 1, m),
        "sr_ticket_number": ss["ss_ticket_number"].to_numpy()[rows],
        "sr_return_quantity": back,
        "sr_return_amt": amt,
        "sr_return_tax": tax,
        "sr_return_amt_inc_tax": due,
        "sr_fee": fee,
        "sr_return_ship_cost": ship,
        "sr_refunded_cash": cash,
        "sr_reversed_charge": charge,
        "sr_store_credit": due - cash - charge,
        "sr_net_loss": fee + ship + tax,
    }
    # a sale's NULL customer (or demographics, address, store) stays NULL
    # on its return, beside the return's own NULL bitmap
    inherited = {"sr_customer_sk": "ss_customer_sk", "sr_cdemo_sk": "ss_cdemo_sk",
                 "sr_hdemo_sk": "ss_hdemo_sk", "sr_addr_sk": "ss_addr_sk",
                 "sr_store_sk": "ss_store_sk"}
    cols = schemas()["store_returns"]
    keys = [j for j, (_, _, nullable) in enumerate(cols) if not nullable]
    null = datagen._null_bitmap(rng, m, 0.09, len(cols), keep=tuple(keys))
    out = {}
    for j, (c, t, nullable) in enumerate(cols):
        if not nullable:
            out[c] = values[c].astype(np.int64)
            continue
        nul = null[:, j] | (sale[inherited[c]][1] if c in inherited else False)
        out[c] = datagen._masked(values[c], nul, "Int32" if t == "int32" else "Int64")
    return pd.DataFrame(out)
