"""The benchmark's data, made from ``--seed``: data takes the place of weights.

Copies of the repo's generators (``auron_tpu/models/tpcds.py generate`` and
``auron_tpu/sql/catalog.py build_tables``), kept here so that no later PR can
move the yardstick. ``star`` is the three-table star schema the batch classes
read; ``store_catalog`` widens it to the ten tables of the SQL server's
store-channel catalog. Frames are plain pandas; the drivers hand them to the
program (``Batch.from_pandas`` / ``SqlServer``), the references read the same
frames.
"""

from __future__ import annotations

import datetime as _dt

import numpy as np
import pandas as pd

_BASE_DATE = _dt.date(1998, 1, 1)

N_HD = 720
N_CD = 1921
N_TIME = 86400
N_PROMO = 30
N_CUSTOMER = 100_000  # matches the generator's ss_customer_sk range
N_CA = 25_000
#: d_week_seq of the first generated day (1998-01-01); the real generator
#: counts weeks from 1900, which puts early 1998 at ~5112
WEEK_SEQ_BASE = 5112



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


def _n_stores(sf: float) -> int:
    return max(3, int(12 * min(sf, 1.0)) or 3)



def star(sf: float, seed: int) -> dict:
    """Synthetic star schema; sf=1 ~ 2.88M fact rows (TPC-DS sf=1 scale)."""
    rng = np.random.default_rng(seed)
    n_fact = int(2_880_000 * sf)
    n_dates = 365 * 5
    n_items = max(int(18_000 * min(sf * 10, 1.0)), 100)

    date_sk = 2_450_815 + np.arange(n_dates)
    years = 1998 + (np.arange(n_dates) // 365)
    moy = (np.arange(n_dates) % 365) // 31 + 1
    date_dim = pd.DataFrame(
        {
            "d_date_sk": date_sk.astype(np.int64),
            "d_year": years.astype(np.int32),
            "d_moy": np.minimum(moy, 12).astype(np.int32),
        }
    )

    tag_pool = np.array(["new", "sale", "clearance", "eco", "import", "bulk"])
    item = pd.DataFrame(
        {
            "i_item_sk": np.arange(1, n_items + 1, dtype=np.int64),
            "i_brand_id": rng.integers(1_000_000, 1_010_000, n_items).astype(np.int32),
            "i_category_id": rng.integers(1, 11, n_items).astype(np.int32),
            "i_category": rng.choice(
                ["Books", "Home", "Electronics", "Music", "Sports"], n_items
            ),
            # comma-joined tag list (appended last: earlier pipelines index
            # item columns positionally)
            "i_tags": [
                ",".join(rng.choice(tag_pool, rng.integers(1, 4), replace=False))
                for _ in range(n_items)
            ],
        }
    )

    prices = np.round(rng.gamma(2.0, 25.0, n_fact), 2)
    store_sales = pd.DataFrame(
        {
            "ss_sold_date_sk": rng.choice(date_sk, n_fact).astype(np.int64),
            "ss_item_sk": rng.integers(1, n_items + 1, n_fact).astype(np.int64),
            "ss_customer_sk": np.where(
                rng.random(n_fact) < 0.04, -1, rng.integers(1, 100_000, n_fact)
            ).astype(np.int64),
            "ss_quantity": rng.integers(1, 100, n_fact).astype(np.int32),
            "ss_ext_sales_price": prices,
        }
    )
    store_sales.loc[store_sales.ss_customer_sk == -1, "ss_customer_sk"] = pd.NA
    store_sales["ss_customer_sk"] = store_sales["ss_customer_sk"].astype("Int64")
    return {"store_sales": store_sales, "date_dim": date_dim, "item": item}



def store_catalog(sf: float, seed: int) -> dict:
    """Widened frames for the SQL gate, derived deterministically from the
    generated star schema + (seed, table) — the oracle and the engine read
    the SAME frames, so enrichment randomness cancels out of the diff."""
    data = star(sf, seed)
    out: dict[str, pd.DataFrame] = {}
    out["store_sales"] = _enrich_store_sales(data, seed, sf)
    out["date_dim"] = _enrich_date_dim(data)
    out["item"] = _enrich_item(data, seed)
    out["store"] = _build_store(seed, sf)
    out["customer"] = _build_customer(seed)
    out["household_demographics"] = _build_hd(seed)
    out["customer_demographics"] = _build_cd(seed)
    out["time_dim"] = _build_time_dim()
    out["promotion"] = _build_promotion(seed)
    out["customer_address"] = _build_customer_address(seed)
    return out


def _rng(seed: int, table: str) -> np.random.Generator:
    # zlib.crc32, not hash(): the builtin is salted per process and would
    # make "deterministic enrichment" a lie across runs
    import zlib

    return np.random.default_rng([seed, zlib.crc32(table.encode())])


def _enrich_store_sales(data: dict, seed: int, sf: float) -> pd.DataFrame:
    rng = _rng(seed, "store_sales")
    ss = data["store_sales"]
    n = len(ss)
    qty = ss.ss_quantity.to_numpy(np.int64)
    ext = ss.ss_ext_sales_price.to_numpy(np.float64)
    sales_price = np.round(ext / np.maximum(qty, 1), 2)
    # Ticket (basket) structure like the real generator: variable-size
    # baskets of 1..7 rows sharing customer/date/store/hdemo/addr — the
    # per-ticket count queries (q34/q73/q79-class) are vacuous without
    # real baskets. This intentionally REPLACES the per-row
    # ss_customer_sk/ss_sold_date_sk of the seed frame inside the widened
    # copy (same null fraction, same date pool); the SQL gate's oracles
    # read the same widened frames, so the diff is unaffected.
    tsize = (np.arange(n, dtype=np.int64) * 2654435761 % 7) + 1
    tid = np.repeat(np.arange(n, dtype=np.int64), tsize)[:n]
    n_t = int(tid[-1]) + 1 if n else 0
    t_customer = rng.integers(1, N_CUSTOMER + 1, n_t, dtype=np.int64)
    t_null = rng.random(n_t) < 0.04
    t_date = (rng.choice(data["date_dim"].d_date_sk.to_numpy(np.int64), n_t)
              if n_t else np.array([], np.int64))
    t_store = rng.integers(1, _n_stores(sf) + 1, n_t, dtype=np.int64)
    t_hd = rng.integers(1, N_HD + 1, n_t, dtype=np.int64)
    t_addr = rng.integers(1, N_CA + 1, n_t, dtype=np.int64)
    customer = pd.Series(t_customer[tid] if n else [], dtype="Int64")
    if n:
        customer[t_null[tid]] = pd.NA
    df = pd.DataFrame(
        {
            "ss_sold_date_sk": t_date[tid] if n else np.array([], np.int64),
            "ss_item_sk": ss.ss_item_sk.to_numpy(np.int64),
            "ss_customer_sk": customer,
            "ss_quantity": ss.ss_quantity.to_numpy(np.int32),
            "ss_ext_sales_price": ext,
            "ss_store_sk": t_store[tid] if n else np.array([], np.int64),
            "ss_sold_time_sk": rng.integers(0, N_TIME, n, dtype=np.int64),
            "ss_hdemo_sk": t_hd[tid] if n else np.array([], np.int64),
            "ss_cdemo_sk": rng.integers(1, N_CD + 1, n, dtype=np.int64),
            "ss_promo_sk": rng.integers(1, N_PROMO + 1, n, dtype=np.int64),
            "ss_ticket_number": tid + 1,
            "ss_sales_price": sales_price,
            "ss_list_price": np.round(sales_price * rng.uniform(1.0, 1.5, n), 2),
            "ss_coupon_amt": np.round(
                np.where(rng.random(n) < 0.2, rng.uniform(0.5, 30.0, n), 0.0), 2
            ),
            "ss_wholesale_cost": np.round(sales_price * rng.uniform(0.4, 0.9, n), 2),
            "ss_net_profit": np.round(ext * rng.uniform(-0.2, 0.4, n), 2),
            "ss_addr_sk": t_addr[tid] if n else np.array([], np.int64),
            "ss_ext_list_price": np.round(
                sales_price * rng.uniform(1.0, 1.5, n) * np.maximum(qty, 1), 2
            ),
            "ss_ext_tax": np.round(ext * rng.uniform(0.0, 0.09, n), 2),
        }
    )
    return df


def _enrich_date_dim(data: dict) -> pd.DataFrame:
    dd = data["date_dim"]
    i = np.arange(len(dd))
    moy = dd.d_moy.to_numpy(np.int32)
    names = np.array(["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
                      "Friday", "Saturday"])
    return pd.DataFrame(
        {
            "d_date_sk": dd.d_date_sk.to_numpy(np.int64),
            "d_year": dd.d_year.to_numpy(np.int32),
            "d_moy": moy,
            "d_date": np.array(
                [_BASE_DATE + _dt.timedelta(days=int(k)) for k in i], dtype=object
            ),
            "d_dom": ((i % 365) % 31 + 1).astype(np.int32),
            "d_qoy": ((moy - 1) // 3 + 1).astype(np.int32),
            "d_day_name": names[i % 7],
            "d_month_seq": (
                (dd.d_year.to_numpy(np.int64) - 1900) * 12 + moy - 1
            ).astype(np.int32),
            "d_week_seq": (WEEK_SEQ_BASE + i // 7).astype(np.int32),
            "d_dow": (i % 7).astype(np.int32),
        }
    )


def _enrich_item(data: dict, seed: int) -> pd.DataFrame:
    rng = _rng(seed, "item")
    it = data["item"]
    n = len(it)
    sk = it.i_item_sk.to_numpy(np.int64)
    brand_id = it.i_brand_id.to_numpy(np.int64)
    class_id = rng.integers(1, 17, n).astype(np.int32)
    manufact_id = rng.integers(1, 1001, n).astype(np.int32)
    manager_id = rng.integers(1, 101, n).astype(np.int32)
    return pd.DataFrame(
        {
            "i_item_sk": sk,
            "i_brand_id": it.i_brand_id.to_numpy(np.int32),
            "i_category_id": it.i_category_id.to_numpy(np.int32),
            "i_category": it.i_category.to_numpy(object),
            "i_tags": it.i_tags.to_numpy(object),
            "i_item_id": np.array([f"AAAAAAAA{k:08d}" for k in sk], dtype=object),
            # unique per item: ORDER BY ... LIMIT boundaries tie-break on
            # it in several queries (q65) — a shared desc could leave the
            # boundary tie class ambiguous
            "i_item_desc": np.array(
                [f"item description {k:06d}" for k in sk], dtype=object
            ),
            # a pure function of brand_id: GROUP BY (i_brand_id, i_brand)
            # has exactly brand_id's cardinality, like the real generator
            "i_brand": np.array(
                [f"corpbrand #{b % 1000}" for b in brand_id], dtype=object
            ),
            "i_class_id": class_id,
            "i_class": np.array([f"class{c:02d}" for c in class_id], dtype=object),
            "i_manufact_id": manufact_id,
            "i_manufact": np.array(
                [f"manufact#{m}" for m in manufact_id], dtype=object
            ),
            "i_manager_id": manager_id,
            "i_current_price": np.round(rng.uniform(0.5, 99.0, n), 2),
            "i_wholesale_cost": np.round(rng.uniform(0.3, 70.0, n), 2),
        }
    )


def _build_store(seed: int, sf: float) -> pd.DataFrame:
    rng = _rng(seed, "store")
    n = _n_stores(sf)
    names = np.array(["ought", "able", "ese", "anti", "cally", "ation", "eing",
                      "bar"])
    counties = np.array(["Williamson County", "Ziebach County", "Walker County",
                         "Daviess County", "Barrow County"])
    sk = np.arange(1, n + 1, dtype=np.int64)
    return pd.DataFrame(
        {
            "s_store_sk": sk,
            "s_store_id": np.array([f"S{k:010d}" for k in sk], dtype=object),
            "s_store_name": names[(sk - 1) % len(names)],
            "s_number_employees": rng.integers(200, 301, n).astype(np.int32),
            "s_state": rng.choice(["TN", "SD", "SC", "KY", "OH"], n),
            "s_county": counties[(sk - 1) % len(counties)],
            "s_gmt_offset": rng.choice([-5.0, -6.0], n),
            "s_city": _CITY_POOL[(sk - 1) % len(_CITY_POOL)],
            "s_zip": np.array([f"{28000 + 137 * k % 70000:05d}" for k in sk],
                              dtype=object),
        }
    )


def _build_customer(seed: int) -> pd.DataFrame:
    rng = _rng(seed, "customer")
    n = N_CUSTOMER
    sk = np.arange(1, n + 1, dtype=np.int64)
    # wide pools (10 x 50 numbered variants): q68-style ORDER BY
    # (c_last_name, ticket) LIMIT boundaries must not tie across
    # customers that differ in other output columns
    first = np.array([f"{b}{i:02d}" for b in
                      ("James", "Mary", "John", "Linda", "Robert", "Ann",
                       "Michael", "Susan", "David", "Karen")
                      for i in range(50)])
    last = np.array([f"{b}{i:02d}" for b in
                     ("Smith", "Jones", "Brown", "White", "Green", "Hall",
                      "Clark", "Lewis", "Young", "King")
                     for i in range(50)])
    return pd.DataFrame(
        {
            "c_customer_sk": sk,
            "c_customer_id": np.array([f"C{k:015d}" for k in sk], dtype=object),
            "c_salutation": rng.choice(["Mr.", "Mrs.", "Ms.", "Dr."], n),
            "c_first_name": first[rng.integers(0, len(first), n)],
            "c_last_name": last[rng.integers(0, len(last), n)],
            "c_preferred_cust_flag": rng.choice(["Y", "N"], n),
            "c_birth_year": rng.integers(1930, 1996, n).astype(np.int32),
            "c_current_addr_sk": rng.integers(1, N_CA + 1, n, dtype=np.int64),
        }
    )


def _build_hd(seed: int) -> pd.DataFrame:
    rng = _rng(seed, "household_demographics")
    sk = np.arange(1, N_HD + 1, dtype=np.int64)
    pots = np.array(["0-500", "501-1000", "1001-5000", "5001-10000", ">10000",
                     "Unknown"])
    return pd.DataFrame(
        {
            "hd_demo_sk": sk,
            "hd_buy_potential": pots[(sk - 1) % len(pots)],
            "hd_dep_count": rng.integers(0, 10, N_HD).astype(np.int32),
            "hd_vehicle_count": rng.integers(-1, 5, N_HD).astype(np.int32),
        }
    )


def _build_cd(seed: int) -> pd.DataFrame:
    rng = _rng(seed, "customer_demographics")
    sk = np.arange(1, N_CD + 1, dtype=np.int64)
    return pd.DataFrame(
        {
            "cd_demo_sk": sk,
            "cd_gender": rng.choice(["M", "F"], N_CD),
            "cd_marital_status": rng.choice(["M", "S", "D", "W", "U"], N_CD),
            "cd_education_status": rng.choice(
                ["Primary", "Secondary", "College", "2 yr Degree",
                 "4 yr Degree", "Advanced Degree", "Unknown"], N_CD),
            "cd_dep_count": rng.integers(0, 7, N_CD).astype(np.int32),
        }
    )


def _build_time_dim() -> pd.DataFrame:
    sk = np.arange(N_TIME, dtype=np.int64)
    hour = (sk // 3600).astype(np.int32)
    meal = np.where(hour < 9, "breakfast",
                    np.where(hour < 14, "lunch",
                             np.where(hour < 21, "dinner", "night")))
    return pd.DataFrame(
        {
            "t_time_sk": sk,
            "t_hour": hour,
            "t_minute": ((sk % 3600) // 60).astype(np.int32),
            "t_meal_time": meal.astype(object),
        }
    )


def _build_promotion(seed: int) -> pd.DataFrame:
    rng = _rng(seed, "promotion")
    sk = np.arange(1, N_PROMO + 1, dtype=np.int64)
    return pd.DataFrame(
        {
            "p_promo_sk": sk,
            "p_channel_email": rng.choice(["Y", "N"], N_PROMO),
            "p_channel_event": rng.choice(["Y", "N"], N_PROMO),
        }
    )


_CITY_POOL = np.array(["Midway", "Fairview", "Oak Grove", "Salem", "Glendale",
                       "Riverside", "Centerville", "Pleasant Hill"])


def _build_customer_address(seed: int) -> pd.DataFrame:
    rng = _rng(seed, "customer_address")
    sk = np.arange(1, N_CA + 1, dtype=np.int64)
    counties = np.array(["Williamson County", "Ziebach County", "Walker County",
                         "Daviess County", "Barrow County"])
    return pd.DataFrame(
        {
            "ca_address_sk": sk,
            "ca_city": _CITY_POOL[rng.integers(0, len(_CITY_POOL), N_CA)],
            "ca_county": counties[rng.integers(0, len(counties), N_CA)],
            "ca_state": rng.choice(["TN", "SD", "SC", "KY", "OH", "TX", "GA"],
                                   N_CA),
            "ca_zip": np.array(
                [f"{28000 + 137 * k % 70000:05d}" for k in sk], dtype=object
            ),
            "ca_country": np.array(["United States"] * N_CA, dtype=object),
            "ca_gmt_offset": rng.choice([-5.0, -6.0], N_CA),
        }
    )
