"""The benchmark's data, made from ``--seed``: data takes the place of weights.

``tpcds`` makes the three tables that the benchmark's queries read, in the
shapes of the TPC-DS specification (v3.2.0): ``store_sales`` with its 23
columns (clause 2.3.1), ``date_dim`` with its 28 columns and 73,049 rows
(1900-01-02 to 2100-01-01), ``item`` with its 22 columns. Column names, order,
types and nullability are in ``benchmark/schemas/tpcds.json``; row counts are
the specification's at scale factor 1 (2,880,404 fact rows, 18,000 items),
the fact table scaled by ``sf``. Money is DECIMAL(7,2), kept in the frames as
whole cents (nullable ``Int64``) so that the plain references compute exactly.

What follows dsdgen, the specification's generator, by its documented
behaviour and not by its random streams (neither dsdgen nor its distribution
files are at hand here; ``assumed`` in the configuration files says the same):

- sales come in tickets of 8 to 16 lines that share date, time, customer,
  demographics, address and store; the lines of a ticket have distinct items;
  tickets come in the order of their dates (a date-based table);
- sold dates lie in 1998-01-02 .. 2003-01-02 and follow the sales calendar's
  three zones: January to July low, August to October medium, November and
  December high (per-day weights 1 : 2 : 3);
- 9 % of the fact rows draw a random bitmap of NULLs over every column but the
  primary key (``ss_item_sk``, ``ss_ticket_number``), so each other column is
  NULL in about 4.5 % of the rows, and NULLs cluster in rows;
- prices follow ``set_pricing``: quantity 1..100, wholesale cost 1.00..100.00,
  markup 0..200 %, discount 0..100 %, a coupon on a fifth of the lines, tax
  0..9 %, and the extended and net columns derived from them in whole cents;
- ``item`` is history keeping: one to three revisions share an ``i_item_id``;
  brands hang under classes under the ten categories, ``i_brand`` is a
  function of ``i_brand_id``; ``i_manufact_id`` is 1..1000, ``i_manager_id``
  1..100; 0.5 % of the rows draw a NULL bitmap over the descriptive columns.

Frames are plain pandas; the drivers hand them to the program and the
references read the same frames.
"""

from __future__ import annotations

import json
import os
import zlib

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))

#: d_date_sk is the Julian day number; 1900-01-02, the first row, is 2415022
SK_1900_01_02 = 2415022
N_DATES = 73049
SALES_FIRST_SK, SALES_LAST_SK = 2450816, 2452642  # 1998-01-02 .. 2003-01-02
#: row counts at scale factor 1 (specification, table 3-2); the dimensions the
#: fact table points into keep them at every ``sf`` under 10
SF1 = {"store_sales": 2_880_404, "item": 18_000, "customer": 100_000,
       "customer_address": 50_000, "customer_demographics": 1_920_800,
       "household_demographics": 7_200, "store": 12, "promotion": 300}

_SYLLABLES = ["amalg", "importo", "edu pack", "exporti", "scholar", "corp",
              "univ", "nameless", "brand", "maxi"]
_CATEGORIES = ["Women", "Men", "Children", "Shoes", "Music", "Jewelry", "Home",
               "Sports", "Books", "Electronics"]
_COLORS = ["almond", "azure", "beige", "bisque", "black", "blue", "brown",
           "burlywood", "chartreuse", "coral", "cornsilk", "cyan", "dark",
           "dim", "dodger", "firebrick", "forest", "frosted", "gainsboro",
           "ghost", "goldenrod", "green", "grey", "honeydew", "hot", "indian",
           "ivory", "khaki", "lace", "lavender", "lawn", "lemon", "light",
           "lime", "linen", "magenta", "maroon", "medium", "metallic",
           "midnight", "mint", "misty", "moccasin", "navajo", "navy", "olive",
           "orange", "orchid", "pale", "papaya", "peach", "peru", "pink",
           "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal",
           "saddle", "salmon", "sandy", "seashell", "sienna", "sky", "slate",
           "smoke", "snow", "spring", "steel", "tan", "thistle", "tomato",
           "turquoise", "violet", "wheat", "white", "yellow"]
_SIZES = ["petite", "small", "medium", "large", "extra large", "economy", "N/A"]
_UNITS = ["Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Carton",
          "Box", "Bunch", "Bundle", "Oz", "Lb", "Ton", "Ounce", "Pound",
          "Tsp", "Tbl", "Cup", "Dram", "Gram", "N/A"]
_DAY_NAMES = np.array(["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
                       "Friday", "Saturday"], dtype=object)


def schemas() -> dict:
    """``{table: [[column, type, nullable], ...]}`` of ``schemas/tpcds.json``."""
    with open(os.path.join(HERE, "schemas", "tpcds.json")) as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def make(config: dict, seed: int) -> dict:
    """The frames a configuration's ``data`` entry names, from the seed."""
    return globals()[config["data"]["generator"]](config["data"]["sf"], seed)


def column_bytes(frames: dict, columns: dict) -> int:
    """Bytes of ``{table: [column, ...]}``: fixed-width columns at their
    width, strings at their length. What a query's text must read once."""
    total = 0
    for table, cols in columns.items():
        for c in cols:
            s = frames[table][c]
            if pd.api.types.is_numeric_dtype(s.dtype):
                total += int(s.dtype.itemsize) * len(s)
            else:
                total += int(s.str.len().sum())
    return total


def _bkey(k: int) -> str:
    """A 16-character business key, as dsdgen's mk_bkey spells a number."""
    return "AAAAAAAA" + "".join("ABCDEFGHIJKLMNOP"[(int(k) >> s) & 15]
                                for s in range(0, 32, 4))


def _rng(seed: int, table: str) -> np.random.Generator:
    # zlib.crc32, not hash(): the builtin is salted per process
    return np.random.default_rng([seed, zlib.crc32(table.encode())])


def tpcds(sf: float, seed: int) -> dict:
    frames = {"store_sales": _store_sales(sf, seed), "date_dim": _date_dim(),
              "item": _item(seed)}
    for table, cols in schemas().items():
        if list(frames[table].columns) != [c for c, _, _ in cols]:
            raise AssertionError(f"datagen and schemas/tpcds.json disagree on {table}")
    return frames


# ---------------------------------------------------------------------------
# date_dim: a pure function of the calendar
# ---------------------------------------------------------------------------


def _date_dim() -> pd.DataFrame:
    i = np.arange(N_DATES, dtype=np.int64)
    dates = np.datetime64("1900-01-02") + i.astype("timedelta64[D]")
    ts = pd.DatetimeIndex(dates)
    year = ts.year.to_numpy(np.int32)
    moy = ts.month.to_numpy(np.int32)
    dom = ts.day.to_numpy(np.int32)
    qoy = ((moy - 1) // 3 + 1).astype(np.int32)
    dow = ((ts.dayofweek.to_numpy() + 1) % 7).astype(np.int32)  # Sunday = 0
    sk = SK_1900_01_02 + i
    month_seq = ((year - 1900) * 12 + moy - 1).astype(np.int32)
    week_seq = ((i + 1) // 7 + 1).astype(np.int32)   # 1900-01-01 was a Monday
    quarter_seq = ((year - 1900) * 4 + qoy).astype(np.int32)
    first_dom = sk - (dom - 1)
    last_dom = first_dom + ts.days_in_month.to_numpy(np.int64) - 1
    leap_before = ((ts - pd.DateOffset(years=1)).to_numpy() - dates
                   ).astype("timedelta64[D]").astype(np.int64)
    lq_before = ((ts - pd.DateOffset(months=3)).to_numpy() - dates
                 ).astype("timedelta64[D]").astype(np.int64)
    holiday = (((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4))
               | ((moy == 12) & (dom == 25)) | ((moy == 11) & (dom == 11)))
    yn = np.array(["N", "Y"], dtype=object)
    # dsdgen's "current" day is 2003-01-08
    cur = np.datetime64("2003-01-08")
    cur_y, cur_m = 2003, 1
    return pd.DataFrame({
        "d_date_sk": sk,
        "d_date_id": np.array([_bkey(k) for k in sk], dtype=object),
        "d_date": ts.date,
        "d_month_seq": month_seq,
        "d_week_seq": week_seq,
        "d_quarter_seq": quarter_seq,
        "d_year": year,
        "d_dow": dow,
        "d_moy": moy,
        "d_dom": dom,
        "d_qoy": qoy,
        "d_fy_year": year,
        "d_fy_quarter_seq": quarter_seq,
        "d_fy_week_seq": week_seq,
        "d_day_name": _DAY_NAMES[dow],
        "d_quarter_name": np.array([f"{y}Q{q}" for y, q in zip(year, qoy)],
                                   dtype=object),
        "d_holiday": yn[holiday.astype(int)],
        "d_weekend": yn[((dow == 0) | (dow == 6)).astype(int)],
        "d_following_holiday": yn[np.roll(holiday, 1).astype(int)],
        "d_first_dom": first_dom.astype(np.int32),
        "d_last_dom": last_dom.astype(np.int32),
        "d_same_day_ly": (sk + leap_before).astype(np.int32),
        "d_same_day_lq": (sk + lq_before).astype(np.int32),
        "d_current_day": yn[(dates == cur).astype(int)],
        "d_current_week": yn[(week_seq == week_seq[dates == cur][0]).astype(int)],
        "d_current_month": yn[((year == cur_y) & (moy == cur_m)).astype(int)],
        "d_current_quarter": yn[((year == cur_y) & (qoy == 1)).astype(int)],
        "d_current_year": yn[(year == cur_y).astype(int)],
    })


# ---------------------------------------------------------------------------
# item
# ---------------------------------------------------------------------------


def _null_bitmap(rng, n: int, share: float, n_cols: int, keep: tuple) -> np.ndarray:
    """``[n, n_cols]`` booleans, True where a cell is NULL: ``share`` of the
    rows draw a random bitmap, and the columns in ``keep`` never go NULL."""
    hit = rng.random(n) < share
    bits = rng.integers(0, 1 << n_cols, n, dtype=np.int64)
    nulls = ((bits[:, None] >> np.arange(n_cols)) & 1).astype(bool) & hit[:, None]
    nulls[:, list(keep)] = False
    return nulls


def _masked(values: np.ndarray, null: np.ndarray, dtype: str):
    """A nullable integer column (``Int32``/``Int64``) with zeroed NULL lanes."""
    return pd.arrays.IntegerArray(
        np.where(null, 0, values).astype(dtype.lower()), null.copy())


def _strings(values: np.ndarray, null: np.ndarray) -> np.ndarray:
    out = np.asarray(values, dtype=object).copy()
    out[null] = None
    return out


def _item(seed: int) -> pd.DataFrame:
    rng = _rng(seed, "item")
    n = SF1["item"]
    sk = np.arange(1, n + 1, dtype=np.int64)
    # history keeping: runs of 1..3 revisions share a business key
    run = rng.integers(1, 4, n)
    bkey = np.repeat(np.arange(n), run)[:n]
    rev = np.arange(n) - np.searchsorted(bkey, bkey, side="left")
    starts = np.array(["1997-10-27", "2000-10-27", "2001-10-27"],
                      dtype="datetime64[D]")
    last = np.append(bkey[1:] != bkey[:-1], True)
    rec_start = starts[rev]
    rec_end = np.where(last, np.datetime64("NaT"),
                       starts[np.minimum(rev + 1, 2)] - np.timedelta64(1, "D"))
    category_id = rng.integers(1, 11, n).astype(np.int32)
    class_id = rng.integers(1, 17, n).astype(np.int32)
    brand_n = rng.integers(1, 11, n).astype(np.int32)
    brand_id = (category_id.astype(np.int64) * 1_000_000 + class_id * 1_000
                + brand_n).astype(np.int32)
    syl = np.array(_SYLLABLES, dtype=object)
    brand = syl[(class_id - 1) % 10] + syl[category_id - 1] + " #" + \
        brand_n.astype(str).astype(object)
    manufact_id = rng.integers(1, 1001, n).astype(np.int32)
    manufact = syl[manufact_id % 10] + syl[(manufact_id // 10) % 10] + \
        syl[(manufact_id // 100) % 10]
    wholesale = rng.integers(2, 8800, n).astype(np.int64)            # cents
    price = (wholesale * (100 + rng.integers(5, 200, n)) // 100).astype(np.int64)
    cols = schemas()["item"]
    null = _null_bitmap(rng, n, 0.005, len(cols), keep=(0, 1, 2))
    nul = {c: null[:, j] for j, (c, _, _) in enumerate(cols)}
    cat = np.array(_CATEGORIES, dtype=object)
    return pd.DataFrame({
        "i_item_sk": sk,
        "i_item_id": np.array([_bkey(k) for k in bkey + 1], dtype=object),
        "i_rec_start_date": pd.Series(rec_start).dt.date.to_numpy(),
        "i_rec_end_date": pd.Series(rec_end).dt.date.to_numpy(),
        "i_item_desc": _strings(np.array(
            [f"item description {k:06d} of revision {r}" for k, r in zip(sk, rev)],
            dtype=object), nul["i_item_desc"]),
        "i_current_price": _masked(price, nul["i_current_price"], "Int64"),
        "i_wholesale_cost": _masked(wholesale, nul["i_wholesale_cost"], "Int64"),
        "i_brand_id": _masked(brand_id, nul["i_brand_id"], "Int32"),
        "i_brand": _strings(brand, nul["i_brand"]),
        "i_class_id": _masked(class_id, nul["i_class_id"], "Int32"),
        "i_class": _strings(np.array([f"class{c:02d}" for c in class_id],
                                     dtype=object), nul["i_class"]),
        "i_category_id": _masked(category_id, nul["i_category_id"], "Int32"),
        "i_category": _strings(cat[category_id - 1], nul["i_category"]),
        "i_manufact_id": _masked(manufact_id, nul["i_manufact_id"], "Int32"),
        "i_manufact": _strings(manufact, nul["i_manufact"]),
        "i_size": _strings(np.array(_SIZES, dtype=object)[rng.integers(0, len(_SIZES), n)],
                           nul["i_size"]),
        "i_formulation": _strings(np.array(
            [f"{a:010d}{c}{b:05d}" for a, b, c in zip(
                rng.integers(0, 10**10, n), rng.integers(0, 10**5, n),
                np.array(_COLORS, dtype=object)[rng.integers(0, len(_COLORS), n)])],
            dtype=object), nul["i_formulation"]),
        "i_color": _strings(np.array(_COLORS, dtype=object)[rng.integers(0, len(_COLORS), n)],
                            nul["i_color"]),
        "i_units": _strings(np.array(_UNITS, dtype=object)[rng.integers(0, len(_UNITS), n)],
                            nul["i_units"]),
        "i_container": _strings(np.full(n, "Unknown", dtype=object), nul["i_container"]),
        "i_manager_id": _masked(rng.integers(1, 101, n).astype(np.int32),
                                nul["i_manager_id"], "Int32"),
        "i_product_name": _strings(np.array(
            [_SYLLABLES[k % 10] + _SYLLABLES[(k // 10) % 10]
             + _SYLLABLES[(k // 100) % 10] + _SYLLABLES[(k // 1000) % 10]
             for k in sk], dtype=object), nul["i_product_name"]),
    })


# ---------------------------------------------------------------------------
# store_sales
# ---------------------------------------------------------------------------


def _sales_day_weights() -> np.ndarray:
    days = np.datetime64("1900-01-02") + np.arange(
        SALES_FIRST_SK - SK_1900_01_02, SALES_LAST_SK - SK_1900_01_02 + 1
    ).astype("timedelta64[D]")
    moy = pd.DatetimeIndex(days).month.to_numpy()
    w = np.where(moy <= 7, 1.0, np.where(moy <= 10, 2.0, 3.0))
    return w / w.sum()


#: the fact table is made in this many slices of its tickets, each from a
#: random stream of its own and on a thread of its own; part of the data's
#: definition, so never a function of the machine
_SLICES = 16


def _store_sales(sf: float, seed: int) -> pd.DataFrame:
    from concurrent.futures import ThreadPoolExecutor

    rng = _rng(seed, "store_sales")
    n = int(round(SF1["store_sales"] * sf))
    n_t = n // 8 + 2
    lines = rng.integers(8, 17, n_t)
    n_t = int(np.searchsorted(np.cumsum(lines), n)) + 1
    lines = lines[:n_t]
    ends = np.minimum(np.cumsum(lines), n)
    w = _sales_day_weights()
    ticket = {
        "ss_sold_date_sk": np.sort(rng.choice(len(w), n_t, p=w)) + SALES_FIRST_SK,
        "ss_sold_time_sk": rng.integers(28800, 75600, n_t),
        "ss_customer_sk": rng.integers(1, SF1["customer"] + 1, n_t),
        "ss_cdemo_sk": rng.integers(1, SF1["customer_demographics"] + 1, n_t),
        "ss_hdemo_sk": rng.integers(1, SF1["household_demographics"] + 1, n_t),
        "ss_addr_sk": rng.integers(1, SF1["customer_address"] + 1, n_t),
        "ss_store_sk": rng.integers(1, SF1["store"] + 1, n_t),
    }
    item0 = rng.integers(0, SF1["item"], n_t)
    cols = schemas()["store_sales"]
    data = {c: np.empty(n, np.int32 if t == "int32" else np.int64)
            for c, t, _ in cols}
    null = {c: np.empty(n, bool) for c, _, nullable in cols if nullable}

    def fill(k: int) -> None:
        t0, t1 = n_t * k // _SLICES, n_t * (k + 1) // _SLICES
        r0 = int(ends[t0 - 1]) if t0 else 0
        r1 = int(ends[t1 - 1])
        m = r1 - r0
        if m <= 0:
            return
        rk = np.random.default_rng([seed, zlib.crc32(b"store_sales"), k])
        size = np.diff(np.concatenate(([r0], ends[t0:t1])))
        tid = np.repeat(np.arange(t0, t1), size)
        line = np.arange(r0, r1) - np.repeat(ends[t0:t1] - size, size)
        v = {c: a[tid] for c, a in ticket.items()}
        # the lines of a ticket walk the items by a stride: distinct in a ticket
        v["ss_item_sk"] = (item0[tid] + line * 1009) % SF1["item"] + 1
        v["ss_ticket_number"] = tid + 1
        v["ss_promo_sk"] = rk.integers(1, SF1["promotion"] + 1, m)
        qty = rk.integers(1, 101, m)
        wholesale = rk.integers(100, 10_001, m)                   # cents
        list_price = (wholesale * (100 + rk.integers(0, 201, m)) + 50) // 100
        sales_price = (list_price * (100 - rk.integers(0, 101, m)) + 50) // 100
        ext_list, ext_sales = list_price * qty, sales_price * qty
        ext_wholesale = wholesale * qty
        coupon = np.where(rk.integers(1, 101, m) <= 20,
                          (ext_sales * rk.integers(0, 101, m) + 50) // 100, 0)
        net_paid = ext_sales - coupon
        ext_tax = (net_paid * rk.integers(0, 10, m) + 50) // 100
        v.update(
            ss_quantity=qty, ss_wholesale_cost=wholesale, ss_list_price=list_price,
            ss_sales_price=sales_price, ss_ext_discount_amt=ext_list - ext_sales,
            ss_ext_sales_price=ext_sales, ss_ext_wholesale_cost=ext_wholesale,
            ss_ext_list_price=ext_list, ss_ext_tax=ext_tax, ss_coupon_amt=coupon,
            ss_net_paid=net_paid, ss_net_paid_inc_tax=net_paid + ext_tax,
            ss_net_profit=net_paid - ext_wholesale)
        hit = rk.random(m) < 0.09
        bits = rk.integers(0, 1 << len(cols), m)
        for j, (c, _, nullable) in enumerate(cols):
            if nullable:
                nul = hit & ((bits >> j) & 1).astype(bool)
                null[c][r0:r1] = nul
                v[c][nul] = 0
            data[c][r0:r1] = v[c]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(_SLICES)))
    return pd.DataFrame(
        {c: pd.arrays.IntegerArray(data[c], null[c]) if nullable else data[c]
         for c, _, nullable in cols}, copy=False)
