"""Two small tables, neither of them ``store_sales``, made from the seed: the
fixture deployment of ``benchmark/tests/fixture_dupjoin`` (no cell of
``BENCHMARK.json``).

- ``line``: order lines, ``LINES`` a scale factor, each an item of
  ``ITEMS`` and a quantity of 1 .. 100 (NULL in 5 % of the lines): a fact
  table with about ``LINES / ITEMS`` lines to a key;
- ``stock``: every item's quantity on hand of 0 .. 120 (NULL in 5 %) in each
  of ``WAREHOUSES`` warehouses in each of ``WEEKS`` weeks, the warehouse by
  its name: ``WAREHOUSES x WEEKS`` rows to a key.

Joined on the item, a line meets every stock row of its item, and the
residual ``k_on_hand < l_quantity`` keeps about two fifths of the pairs, as
TPC-DS query 72 joins ``catalog_sales`` to ``inventory``. No column holds
money.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

LINES = 40_000
ITEMS = 200
WAREHOUSES = 8
WEEKS = 2
NULL_SHARE = 0.05

#: ``{table: [[column, type, nullable], ...]}``
SCHEMAS = {
    "line": [["l_item_sk", "int64", False], ["l_quantity", "int32", True]],
    "stock": [["k_item_sk", "int64", False], ["k_warehouse", "string", False],
              ["k_on_hand", "int32", True]],
}


def schemas() -> dict:
    return SCHEMAS


def _quantity(rng, n: int, low: int, high: int) -> pd.Series:
    q = pd.Series(rng.integers(low, high + 1, n), dtype="Int32")
    q[rng.random(n) < NULL_SHARE] = pd.NA
    return q


def make(config: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n = round(LINES * config["data"]["sf"])
    line = pd.DataFrame({"l_item_sk": rng.integers(1, ITEMS + 1, n),
                         "l_quantity": _quantity(rng, n, 1, 100)})
    keys = np.repeat(np.arange(1, ITEMS + 1), WAREHOUSES * WEEKS)
    names = np.tile(np.repeat([f"Warehouse {w}" for w in range(1, WAREHOUSES + 1)],
                              WEEKS), ITEMS)
    stock = pd.DataFrame({"k_item_sk": keys, "k_warehouse": names,
                          "k_on_hand": _quantity(rng, len(keys), 0, 120)})
    return {"line": line, "stock": stock}
