"""Handing the benchmark's frames to the program: schemas and batches.

The tables' schemas come from ``benchmark/schemas/<name>.json`` (the
specification's names, types and nullability), not from what pandas infers.
Money is DECIMAL(7,2): the frames keep it as whole cents, and the program's
DECIMAL of up to 18 digits is that same unscaled int64 on the device
(``auron_tpu/types.py``), so a batch is built from the integer planes and
labelled with the table's schema. ``Batch.from_pandas`` of a DECIMAL column
would convert it cell by cell in Python (1.7 s per million cells, PERF.md
section 7), which at 23 columns and millions of rows no run can pay.
"""

from __future__ import annotations

import re

from benchmark import datagen


def dtype_of(name: str):
    from auron_tpu import types as T

    m = re.fullmatch(r"decimal\((\d+),(\d+)\)", name)
    if m:
        return T.decimal(int(m.group(1)), int(m.group(2)))
    return {"int64": T.INT64, "int32": T.INT32, "string": T.STRING,
            "date": T.DATE32}[name]


def schema_of(table: str, physical: bool = False):
    """The table's schema; ``physical`` spells DECIMAL as the int64 it is kept
    as in the frames."""
    from auron_tpu import types as T

    return T.Schema(tuple(
        T.Field(c, T.INT64 if physical and t.startswith("decimal") else dtype_of(t),
                nullable)
        for c, t, nullable in datagen.schemas()[table]))


def batch_of(df, table: str, capacity: int | None = None):
    from auron_tpu.columnar.batch import Batch

    b = Batch.from_pandas(df, schema=schema_of(table, physical=True),
                          capacity=capacity)
    return Batch(schema_of(table), b.device, b.dicts)


def to_batches(df, table: str, n_partitions: int, batch_rows: int) -> list:
    """Per-partition lists of batches: row ranges, as a host engine's scan
    tasks split a table."""
    parts = []
    per = (len(df) + n_partitions - 1) // n_partitions
    for p in range(n_partitions):
        chunk = df.iloc[p * per:(p + 1) * per]
        parts.append([batch_of(chunk.iloc[i:i + batch_rows], table)
                      for i in range(0, len(chunk), batch_rows)]
                     or [batch_of(chunk, table)])
    return parts
