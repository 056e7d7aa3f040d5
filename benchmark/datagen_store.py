"""The ``store`` dimension, made from ``--seed``, beside ``datagen.py``'s tables.

``tpcds_store`` makes what ``datagen.tpcds`` makes (``store_sales``,
``date_dim``, ``item``: the very same frames at one seed) and adds ``store``
in the shape of the TPC-DS specification (v3.2.0, clause 2.4.2): 29 columns,
12 rows at scale factor 1 and at every ``sf`` under 10, names, order, types
and nullability in ``benchmark/schemas/tpcds_store.json``. Money-like columns
(``s_gmt_offset``, ``s_tax_precentage``, DECIMAL(5,2)) are kept as whole
hundredths (nullable ``Int64``), as ``datagen.py`` keeps money.

A table comes as files of its own: ``datagen.make`` looks a generator up in
``datagen.py``'s own globals and ``datagen.schemas()`` reads one file, so this
module brings ``make`` and ``schemas`` of its own, and the configuration names
it (``data.module``). ``ss_store_sk`` is what ``datagen.py`` makes today: a
uniform draw of 1..12 a ticket.

What follows dsdgen's documented behaviour, not its random streams or
distribution files (neither is at hand; ``assumed`` in the configuration):

- ``store`` is history keeping: runs of one to three revisions share the
  business key ``s_store_id``, with ``s_rec_start_date`` / ``s_rec_end_date``
  chained and the last revision open-ended;
- ``s_store_name`` is spelled from dsdgen's ten syllables by the digits of the
  business key's number, so the revisions of a store share their name and the
  table holds fewer names than rows;
- a revision redraws the columns that change between revisions (manager,
  employees, floor space, hours, market, tax), and keeps the address;
- 30 % of the rows carry a closing date; division and company are 1 /
  ``Unknown``; ``s_gmt_offset`` is -5.00 or -6.00, tax 0.00 .. 0.11;
- 0.5 % of the rows draw a NULL bitmap over all but the two key columns, as
  ``item`` does.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pandas as pd

from benchmark import datagen

HERE = os.path.dirname(os.path.abspath(__file__))

#: dsdgen's syllables, by digit (``mk_word`` over the "syllables" distribution)
SYLLABLES = ["bar", "ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "n st"]
_FIRST = ["William", "Scott", "Brett", "Raymond", "Edwin", "Robert", "Michael",
          "Larry", "Thomas", "Charles", "Dustin", "David"]
_LAST = ["Ward", "Smith", "Spears", "Jacobs", "Adams", "Thompson", "Melendez",
         "Mccoy", "Tollefson", "Bartels", "Kizer", "Sharp"]
_STREETS = ["Spring", "Oak", "Park", "Main", "Lake", "Hill", "Maple", "Cedar",
            "View", "Walnut", "Sunset", "Railroad"]
_STREET_TYPES = ["Street", "Ave", "Blvd", "Road", "Court", "Drive", "Lane",
                 "Way", "Pkwy", "Circle", "Ct.", "Dr."]
_CITIES = ["Midway", "Fairview", "Oak Grove", "Five Points", "Pleasant Hill",
           "Centerville", "Riverside", "Salem", "Mount Zion", "Union"]
_COUNTIES = ["Williamson County", "Walker County", "Ziebach County",
             "Daviess County", "Barrow County", "Franklin Parish"]
_STATES = ["TN", "AL", "SD", "IN", "GA", "LA"]
_HOURS = ["8AM-4PM", "8AM-12AM", "8AM-8AM"]
_WORDS = ["able", "about", "above", "account", "across", "act", "actual",
          "add", "administration", "afraid", "after", "again", "against",
          "agency", "ago", "agree", "aim", "air", "all", "allow", "almost"]


def schemas() -> dict:
    """``{table: [[column, type, nullable], ...]}``: ``schemas/tpcds.json``'s
    tables and ``schemas/tpcds_store.json``'s."""
    with open(os.path.join(HERE, "schemas", "tpcds_store.json")) as f:
        more = {k: v for k, v in json.load(f).items() if not k.startswith("_")}
    return {**datagen.schemas(), **more}


def make(config: dict, seed: int) -> dict:
    """The frames a configuration's ``data`` entry names, from the seed."""
    return globals()[config["data"]["generator"]](config["data"]["sf"], seed)


def tpcds_store(sf: float, seed: int) -> dict:
    frames = dict(datagen.tpcds(sf, seed))
    frames["store"] = store(seed)
    cols = schemas()["store"]
    if list(frames["store"].columns) != [c for c, _, _ in cols]:
        raise AssertionError("datagen_store and schemas/tpcds_store.json disagree")
    return frames


def store_name(business_key: int) -> str:
    """The digits of the business key's number, least significant first, as
    dsdgen's syllables: 1 -> ought, 2 -> able, 10 -> barought."""
    k, out = int(business_key), []
    while True:
        out.append(SYLLABLES[k % 10])
        k //= 10
        if not k:
            return "".join(out)


def store(seed: int) -> pd.DataFrame:
    rng = datagen._rng(seed, "store")
    n = datagen.SF1["store"]
    sk = np.arange(1, n + 1, dtype=np.int64)
    # history keeping: runs of 1..3 revisions share a business key, which is
    # the surrogate key of the run's first row
    run = rng.integers(1, 4, n)
    first = np.repeat(np.cumsum(run) - run, run)[:n]
    bkey = first + 1
    rev = np.arange(n) - first
    starts = np.array(["1997-03-13", "2000-03-13", "2001-03-13"],
                      dtype="datetime64[D]")
    last = np.append(bkey[1:] != bkey[:-1], True)
    rec_end = np.where(last, np.datetime64("NaT"),
                       starts[np.minimum(rev + 1, 2)] - np.timedelta64(1, "D"))
    # the address belongs to the store, not to the revision
    per_store = {c: rng.integers(0, m, n)[first] for c, m in (
        ("street_no", 1000), ("street", len(_STREETS)), ("type", len(_STREET_TYPES)),
        ("suite", 50), ("city", len(_CITIES)), ("county", len(_COUNTIES)),
        ("zip", 90000))}
    cols = schemas()["store"]
    null = datagen._null_bitmap(rng, n, 0.005, len(cols), keep=(0, 1))
    nul = {c: null[:, j] for j, (c, _, _) in enumerate(cols)}
    closed = rng.random(n) < 0.30
    closed_sk = rng.integers(datagen.SALES_FIRST_SK, datagen.SALES_LAST_SK + 1, n)

    def pick(values: list, idx: np.ndarray) -> np.ndarray:
        return np.array(values, dtype=object)[idx]

    def people() -> np.ndarray:
        return (pick(_FIRST, rng.integers(0, len(_FIRST), n)) + " "
                + pick(_LAST, rng.integers(0, len(_LAST), n)))

    def text(words: int) -> np.ndarray:
        return np.array([" ".join(pick(_WORDS, rng.integers(0, len(_WORDS), words)))
                         for _ in range(n)], dtype=object)

    county = per_store["county"]
    unknown = np.full(n, "Unknown", dtype=object)
    ones = np.ones(n, np.int32)
    s, m = datagen._strings, datagen._masked
    return pd.DataFrame({
        "s_store_sk": sk,
        "s_store_id": np.array([datagen._bkey(k) for k in bkey], dtype=object),
        "s_rec_start_date": pd.Series(starts[rev]).dt.date.to_numpy(),
        "s_rec_end_date": pd.Series(rec_end).dt.date.to_numpy(),
        "s_closed_date_sk": m(closed_sk, nul["s_closed_date_sk"] | ~closed, "Int64"),
        "s_store_name": s(np.array([store_name(k) for k in bkey], dtype=object),
                          nul["s_store_name"]),
        "s_number_employees": m(rng.integers(200, 301, n), nul["s_number_employees"],
                                "Int32"),
        "s_floor_space": m(rng.integers(5_000_000, 10_000_001, n),
                           nul["s_floor_space"], "Int32"),
        "s_hours": s(pick(_HOURS, rng.integers(0, len(_HOURS), n)), nul["s_hours"]),
        "s_manager": s(people(), nul["s_manager"]),
        "s_market_id": m(rng.integers(1, 11, n), nul["s_market_id"], "Int32"),
        "s_geography_class": s(unknown, nul["s_geography_class"]),
        "s_market_desc": s(text(12), nul["s_market_desc"]),
        "s_market_manager": s(people(), nul["s_market_manager"]),
        "s_division_id": m(ones, nul["s_division_id"], "Int32"),
        "s_division_name": s(unknown, nul["s_division_name"]),
        "s_company_id": m(ones, nul["s_company_id"], "Int32"),
        "s_company_name": s(unknown, nul["s_company_name"]),
        "s_street_number": s(per_store["street_no"].astype(str).astype(object),
                             nul["s_street_number"]),
        "s_street_name": s(pick(_STREETS, per_store["street"]), nul["s_street_name"]),
        "s_street_type": s(pick(_STREET_TYPES, per_store["type"]),
                           nul["s_street_type"]),
        "s_suite_number": s(np.array([f"Suite {k * 10}" for k in per_store["suite"]],
                                     dtype=object), nul["s_suite_number"]),
        "s_city": s(pick(_CITIES, per_store["city"]), nul["s_city"]),
        "s_county": s(pick(_COUNTIES, county), nul["s_county"]),
        "s_state": s(pick(_STATES, county), nul["s_state"]),
        "s_zip": s(np.array([f"{10000 + z:05d}" for z in per_store["zip"]],
                            dtype=object), nul["s_zip"]),
        "s_country": s(np.full(n, "United States", dtype=object), nul["s_country"]),
        "s_gmt_offset": m(np.where(county % 2 == 0, -500, -600), nul["s_gmt_offset"],
                          "Int64"),
        "s_tax_precentage": m(rng.integers(0, 12, n), nul["s_tax_precentage"], "Int64"),
    })
