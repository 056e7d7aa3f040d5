"""The fixture's one query, the shape of TPC-DS query 72's first join: a fact
table with many rows to a key sort-merge-joined to another with as many, on
that key and a residual, then counted by a string key:

    select k_warehouse, count(*) cnt
    from line join stock on l_item_sk = k_item_sk and k_on_hand < l_quantity
    group by k_warehouse
    order by cnt desc, k_warehouse
    limit 100

as Spark plans it, built through ``plan.builders`` and driven through
``bridge.api``:

    stage 1 (n_map tasks):    scan of line -> shuffle write by l_item_sk
    stage 2 (n_map tasks):    scan of stock -> shuffle write by k_item_sk
    stage 3 (n_reduce tasks): read both -> Sort + Sort -> SortMergeJoin inner
                              on the item, condition k_on_hand < l_quantity
                              -> partial count(*) by k_warehouse -> shuffle
                              write by k_warehouse
    stage 4 (n_reduce tasks): read -> final count
    driver:                   top 100 by (cnt desc, k_warehouse)

The answer is a string and a BIGINT: it holds no money, and its traffic file
states that it has no precision control. The reference is plain pandas and
imports nothing of the program.
"""

from __future__ import annotations

import os

import pandas as pd

from benchmark.star_plan import _finalize_quietly, take_ordered

OUTPUT = ["k_warehouse", "cnt"]
ORDER = ("cnt", "k_warehouse")
ASCENDING = (False, True)
LIMIT = 100
IN_ORDER = True
SCAN_COLUMNS = {"line": ["l_item_sk", "l_quantity"],
                "stock": ["k_item_sk", "k_warehouse", "k_on_hand"]}


def _schema(table: str):
    from auron_tpu import types as T
    from benchmark.tests.fixture_dupjoin import datagen_dupjoin

    kinds = {"int64": T.INT64, "int32": T.INT32, "string": T.STRING}
    return T.Schema(tuple(T.Field(c, kinds[t], nullable)
                          for c, t, nullable in datagen_dupjoin.schemas()[table]))


def _partitions(df: pd.DataFrame, table: str, n: int, rows: int) -> list:
    """Per-partition lists of batches: row ranges, as a scan's tasks split a
    table."""
    from auron_tpu.columnar.batch import Batch

    per = (len(df) + n - 1) // n
    out = []
    for p in range(n):
        chunk = df.iloc[p * per:(p + 1) * per]
        out.append([Batch.from_pandas(chunk.iloc[i:i + rows], schema=_schema(table))
                    for i in range(0, len(chunk), rows)]
                   or [Batch.from_pandas(chunk, schema=_schema(table))])
    return out


def ingest(frames: dict, params: dict) -> dict:
    """Upload once: both tables' partitions, resident on the device."""
    import jax

    n, rows = params["n_map"], params["batch_rows"]
    out = {"fact": _partitions(frames["line"], "line", n, rows),
           "stock": _partitions(frames["stock"], "stock", n, rows)}
    jax.block_until_ready([b.device for k in ("fact", "stock")
                           for p in out[k] for b in p])
    return out


def run(resident: dict, params: dict, work_dir: str, span) -> tuple:
    """One query: ``(answer, shuffle_bytes)``."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider
    from auron_tpu.exprs.ir import BinaryOp, col
    from auron_tpu.ops.sortkeys import SortSpec
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.planner import plan_from_proto

    n_map, n_reduce = len(resident["fact"]), params["n_reduce"]
    rid = {k: f"qdup_{k}" for k in ("fact", "stock", "ex1", "ex2", "ex3")}
    api.put_resource(rid["fact"], resident["fact"])
    api.put_resource(rid["stock"], resident["stock"])

    def stage(nodes: list, stage_id: int) -> list:
        """The stage's tasks submitted together, then each drained to its
        last batch; the batches as frames."""
        handles, frames = [], []
        try:
            for p, node in enumerate(nodes):
                handles.append(api.call_native(
                    B.task(node, stage_id=stage_id, partition_id=p)
                    .SerializeToString()))
            for h in handles:
                while (rb := api.next_batch(h)) is not None:
                    frames.append(rb.to_pandas())
                api.finalize_native(h)
        except BaseException:
            _finalize_quietly(api, handles)
            raise
        return frames

    def write(node, keys: list, n_tasks: int, tag: str) -> tuple:
        pairs = [(os.path.join(work_dir, f"{tag}{p}.data"),
                  os.path.join(work_dir, f"{tag}{p}.index")) for p in range(n_tasks)]
        part = B.hash_partitioning(keys, n_reduce)
        return [B.shuffle_writer(node, part, *pairs[p]) for p in range(n_tasks)], pairs

    try:
        line = B.memory_scan(_schema("line"), rid["fact"])
        stock = B.memory_scan(_schema("stock"), rid["stock"])
        with span("bench:stage12_exchange"):
            nodes1, pairs1 = write(line, [col(0)], n_map, "l")
            nodes2, pairs2 = write(stock, [col(0)], n_map, "k")
            stage(nodes1, 1)
            stage(nodes2, 2)
        api.put_resource(rid["ex1"], MultiMapBlockProvider(pairs1))
        api.put_resource(rid["ex2"], MultiMapBlockProvider(pairs2))

        with span("bench:stage3_join"):
            first = SortSpec(asc=True, nulls_first=True)
            left = B.sort(B.ipc_reader(plan_from_proto(line).schema, rid["ex1"]),
                          [(col(0), first)])
            right = B.sort(B.ipc_reader(plan_from_proto(stock).schema, rid["ex2"]),
                           [(col(0), first)])
            # l_item_sk, l_quantity | k_item_sk, k_warehouse, k_on_hand
            smj = B.sort_merge_join(left, right, [col(0)], [col(0)], "inner",
                                    condition=BinaryOp("lt", col(4), col(1)))
            keys = [(col(0), "k_warehouse")]
            partial = B.hash_agg(B.project(smj, [(col(3), "k_warehouse")]), keys,
                                 [("count_star", None, "cnt")], "partial")
            nodes3, pairs3 = write(partial, [col(0)], n_reduce, "w")
            stage(nodes3, 3)
        api.put_resource(rid["ex3"], MultiMapBlockProvider(pairs3))

        with span("bench:stage4_final"):
            final = B.hash_agg(
                B.ipc_reader(plan_from_proto(partial).inter_schema, rid["ex3"]),
                keys, [("count_star", None, "cnt")], "final")
            frames = stage([final] * n_reduce, 4)
        shuffle_bytes = sum(os.path.getsize(f) for f, _ in pairs1 + pairs2 + pairs3)
        with span("bench:topk"):
            counts = (pd.concat(frames).reset_index(drop=True) if frames
                      else pd.DataFrame({c: [] for c in OUTPUT}))
            answer = take_ordered(counts[OUTPUT], list(zip(ORDER, ASCENDING)), LIMIT)
        return answer, shuffle_bytes
    finally:
        for k in rid.values():
            api.remove_resource(k)


def reference(frames: dict, params: dict | None = None) -> pd.DataFrame:
    """The answer's rows before ORDER BY and LIMIT: the pairs of a line and a
    stock row of its item whose quantity on hand is below the line's (a NULL
    on either side never is), counted by warehouse."""
    pairs = frames["line"].merge(frames["stock"], left_on="l_item_sk",
                                 right_on="k_item_sk")
    keep = (pairs.k_on_hand < pairs.l_quantity).fillna(False).astype(bool)
    cnt = pairs[keep].groupby("k_warehouse").size()
    return pd.DataFrame({"k_warehouse": cnt.index.astype(object),
                         "cnt": cnt.to_numpy(dtype="int64")}, columns=OUTPUT)
