"""The comparison that decides ``correct``: an answer against its reference.

Two numbers come out of every comparison, each held to a limit of its own:

- ``rows_wrong``: rows whose exact cells (keys, counts, strings, NULLs) differ
  from the reference's, a missing column or a different row count counted as
  every row. The configurations promise exact results: the limit is 0.
- ``float_gap``: the widest gap of a float cell, ``|got - want| / max(1,
  |want|)``. A configuration whose answers hold no float (DECIMAL sums are
  exact, and compared as exact cells) states no limit for it and it is not
  reported.

``in_order`` compares row i with row i (an ORDER BY whose order the driver
itself produces); otherwise both frames are first put into one total order by
their exact columns, which are the grouping keys and so unique.
"""

from __future__ import annotations

import decimal
import math

import numpy as np
import pandas as pd


def is_null(x) -> bool:
    if isinstance(x, (list, tuple, dict, np.ndarray)):
        return False
    try:
        return bool(pd.isna(x))
    except (TypeError, ValueError):
        return False


def _key(x) -> tuple:
    if is_null(x):
        return (0, "")
    if isinstance(x, (bool, np.bool_)):
        return (1, str(int(x)))
    if isinstance(x, (int, np.integer)):
        return (1, f"{int(x):+024d}")
    return (1, str(x))


def _is_float_col(s: pd.Series) -> bool:
    return pd.api.types.is_float_dtype(s.dtype)


def _sorted_by(df: pd.DataFrame, cols: list) -> pd.DataFrame:
    if len(df) <= 1 or not cols:
        return df.reset_index(drop=True)
    rows = list(zip(*[df[c].tolist() for c in cols]))
    order = sorted(range(len(df)), key=lambda i: tuple(_key(v) for v in rows[i]))
    return df.iloc[order].reset_index(drop=True)


def frame_gap(got: pd.DataFrame, want: pd.DataFrame, in_order: bool) -> dict:
    """``{"rows_wrong": int, "float_gap": float}`` of one answer."""
    every = max(len(got), len(want), 1)
    if len(got) != len(want) or any(c not in got.columns for c in want.columns):
        return {"rows_wrong": every, "float_gap": 0.0}
    floats = [c for c in want.columns if _is_float_col(want[c])]
    exact = [c for c in want.columns if c not in floats]
    got = got[list(want.columns)]
    if not in_order:
        got, want = _sorted_by(got, exact), _sorted_by(want, exact)
    bad = np.zeros(len(want), dtype=bool)
    for c in exact:
        for i, (a, b) in enumerate(zip(got[c].tolist(), want[c].tolist())):
            an, bn = is_null(a), is_null(b)
            if an or bn:
                bad[i] |= an != bn
            elif a != b:
                bad[i] = True
    gap = 0.0
    for c in floats:
        for i, (a, b) in enumerate(zip(got[c].tolist(), want[c].tolist())):
            an, bn = is_null(a), is_null(b)
            if an or bn:
                bad[i] |= an != bn
                continue
            a, b = float(a), float(b)
            if math.isinf(a) or math.isinf(b):
                bad[i] |= a != b
                continue
            gap = max(gap, abs(a - b) / max(1.0, abs(b)))
    return {"rows_wrong": int(bad.sum()), "float_gap": gap}


class TieError(AssertionError):
    """A LIMIT boundary falls inside a class of rows that tie on the ORDER BY
    keys and are not identical: the reference's top-k is not determined."""


def head(df: pd.DataFrame, order: tuple, ascending: tuple, limit) -> pd.DataFrame:
    """The reference's rows under ORDER BY ... LIMIT, NULLs ordered as Spark
    orders them: first where a key ascends, last where it descends."""
    if not order:
        return df.reset_index(drop=True)
    rows = list(zip(*[df[c].tolist() for c in order]))

    def key(i: int) -> tuple:
        out = []
        for v, asc in zip(rows[i], ascending):
            null = is_null(v)
            rank = (0 if null else 1) if asc else (1 if null else 0)
            num = 0 if null else v           # keys are numbers, Decimal among them
            out.append((rank, num if asc else -num))
        return tuple(out)

    idx = sorted(range(len(df)), key=key)
    if limit is None or len(df) <= limit:
        return df.iloc[idx].reset_index(drop=True)
    if key(idx[limit]) == key(idx[limit - 1]):
        tie = df.iloc[[i for i in idx if key(i) == key(idx[limit])]]
        if len(tie.astype(str).drop_duplicates()) > 1:
            raise TieError("non-identical rows tie at the LIMIT boundary")
    return df.iloc[idx[:limit]].reset_index(drop=True)


def _holds_money(s: pd.Series) -> bool:
    """DECIMAL or floating-point cells, or a column of objects with no cell
    to tell by."""
    arrow = getattr(s.dtype, "pyarrow_dtype", None)
    if arrow is not None:
        import pyarrow as pa

        return pa.types.is_decimal(arrow) or pa.types.is_floating(arrow)
    if pd.api.types.is_float_dtype(s.dtype):
        return True
    if s.dtype != object:
        return False
    cells = s.dropna()
    return len(cells) == 0 or any(
        isinstance(v, (decimal.Decimal, float, np.floating)) for v in cells)


def money_columns(answer: pd.DataFrame) -> list:
    """The columns of a reference's answer, and of each part it hangs on its
    frame (``attrs``, as ``part.column``), that hold money: DECIMAL or
    floating-point cells, or no cell at all to tell by."""
    parts = [("", answer)] + [(f"{k}.", v) for k, v in answer.attrs.items()
                              if isinstance(v, pd.DataFrame)]
    return [p + str(c) for p, df in parts for c in df.columns
            if _holds_money(df[c])]


def halved(table: str, frames: dict) -> dict:
    """Every other row of ``table`` dropped, the other tables as they are."""
    if table not in frames:
        raise ValueError(f"no table {table!r} to halve: the tables are {sorted(frames)}")
    return {**frames, table: frames[table].iloc[::2].reset_index(drop=True)}


def _same(a: pd.DataFrame, b: pd.DataFrame) -> bool:
    """The two answers hold the same cells, and so do the parts they hang on
    their frames (``attrs``)."""
    return a.equals(b) and a.attrs.keys() == b.attrs.keys() and all(
        not isinstance(v, pd.DataFrame) or v.equals(b.attrs[k])
        for k, v in a.attrs.items())


def control_frames(traffic: dict, queries: dict, params: dict, frames: dict,
                   schemas: dict) -> dict:
    """The data of the control that the traffic file names, for the
    references to run over in the program's place.

    ``control_money`` names the lower precision that every DECIMAL column is
    rounded to (``money_in``). ``null`` there, with the reason in
    ``control_why``, says that no lower precision moves the answer; the
    control then breaks the guarantee that every row is read, dropping every
    other row of the table that ``control_halved`` names. The null is an
    error where a reference's answer, or a part it hangs on its frame, holds
    money, and where the references over money in bfloat16 answer otherwise
    than over exact money (a count filtered on a price): a precision control
    exists there and has to be named."""
    precision = traffic["control_money"]
    if precision is not None:
        return money_in(precision, frames, schemas)
    if not traffic.get("control_why") or not traffic.get("control_halved"):
        raise ValueError("control_money is null: control_why gives no reason "
                         "or control_halved names no table")
    low = money_in("bfloat16", frames, schemas)
    for name, q in queries.items():
        exact = q.reference(frames, params)
        money = money_columns(exact)
        if money:
            raise ValueError(
                f"control_money is null, but the answer of {name} holds money "
                f"in {money}: name the lower precision its control computes in")
        if not _same(exact, q.reference(low, params)):
            raise ValueError(
                f"control_money is null, but money in bfloat16 moves the answer "
                f"of {name}: name the lower precision its control computes in")
    return halved(traffic["control_halved"], frames)


def money_in(precision: str, frames: dict, schemas: dict) -> dict:
    """The control's data: every DECIMAL column (whole cents in the frames)
    rounded to ``float32`` or ``bfloat16`` and kept as float32, so that the
    reference run over it sums money in that precision where the
    configuration promises exact DECIMAL sums."""
    def low(s: pd.Series) -> pd.Series:
        f = s.astype("Float32")
        if precision == "bfloat16":
            u = f.array._data.view(np.uint32)          # round to nearest even
            u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
            f = pd.Series(pd.arrays.FloatingArray(u.view(np.float32), f.array._mask),
                          index=s.index)
        elif precision != "float32":
            raise ValueError(f"no control in {precision!r}")
        return f

    out = {}
    for name, df in frames.items():
        money = {c for c, t, _ in schemas[name] if t.startswith("decimal")}
        out[name] = pd.DataFrame(
            {c: (low(df[c]) if c in money else df[c]) for c in df.columns},
            copy=False)
    return out
