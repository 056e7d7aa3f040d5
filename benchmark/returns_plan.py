"""Spark's physical plan of TPC-DS query 93 (sales against their returns), built
through the protobuf builders and driven through ``bridge.api`` as a host
engine drives it:

    stage 1 (n_map tasks):    scan of store_sales pruned to the five columns
                              the text reads -> shuffle write by
                              (ss_item_sk, ss_ticket_number)        [exchange 1]
    stage 2 (n_map tasks):    scan of store_returns -> filter isnotnull on
                              (sr_item_sk, sr_ticket_number, sr_reason_sk) ->
                              project the four columns the text reads ->
                              shuffle write by (sr_item_sk, sr_ticket_number)
                                                                    [exchange 2]
    stage 3 (n_reduce tasks): read both -> Sort + Sort -> SortMergeJoin on
                              (item, ticket), inner -> BHJ reason (r_reason_desc
                              = 'reason 28', no build column) on sr_reason_sk
                              -> project act_sales -> partial sum(act_sales)
                              by ss_customer_sk -> shuffle write    [exchange 3]
    stage 4 (n_reduce tasks): read exchange 3 -> final sum = sumsales
    driver:                   top 100 by (sumsales, ss_customer_sk) (Spark's
                              TakeOrderedAndProject)

The join type is inner. The text joins ``store_sales LEFT OUTER JOIN
store_returns``, but its WHERE clause holds ``sr_reason_sk = r_reason_sk``,
which no row with a NULL ``sr_reason_sk`` (so none that the outer join
extended with NULLs) passes: Spark's optimizer infers ``isnotnull
(sr_reason_sk)`` from that condition (InferFiltersFromConstraints), and
EliminateOuterJoin then makes the join inner; the inferred filters on the
keys and the reason go to the scan of ``store_returns``. The CASE on a NULL
``sr_return_quantity`` stays: a return with its reason may still lack its
quantity. Both ``Sort`` nodes stay in the plan, as Spark plans them: what
the engine drops is its own business (``plan/optimizer.py``).

Types are Spark's: ``ss_quantity - sr_return_quantity`` INT, times
``ss_sales_price`` DECIMAL(7,2) gives DECIMAL(18,2) (the INT as
DECIMAL(10,0)), and its sum DECIMAL(28,2). Stages 1 and 2 feed one stage and
depend on nothing, so their four tasks are submitted together, as Spark's
scheduler submits both parents of a stage; a stage's tasks are submitted
together and then drained, and a stage starts when those it reads have ended.
The driver holds every group of stage 4 before it takes the top 100, and
hands them on as ``answer.attrs["sums"]``: the comparison holds every
customer's sum to the reference's as well (the top 100 is mostly NULL and
zero sums).
"""

from __future__ import annotations

import os

import pandas as pd

from benchmark.star_plan import _finalize_quietly, take_ordered

FACT_COLUMNS = ("ss_item_sk", "ss_ticket_number", "ss_customer_sk", "ss_quantity",
                "ss_sales_price")
RETURN_COLUMNS = ("sr_item_sk", "sr_ticket_number", "sr_reason_sk",
                  "sr_return_quantity")


def require_program() -> None:
    """What the plan needs of the program beyond what the star queries need;
    a program that lacks it fails here, at once, before any table is made."""
    from auron_tpu.ops import hashmap  # noqa: F401


def schema_of(table: str, physical: bool = False):
    """``ingest.schema_of`` over the schema files of both generators."""
    from auron_tpu import types as T
    from benchmark import datagen_returns
    from benchmark.ingest import dtype_of

    return T.Schema(tuple(
        T.Field(c, T.INT64 if physical and t.startswith("decimal") else dtype_of(t),
                nullable)
        for c, t, nullable in datagen_returns.schemas()[table]))


def batch_of(df, table: str):
    from auron_tpu.columnar.batch import Batch

    b = Batch.from_pandas(df, schema=schema_of(table, physical=True))
    return Batch(schema_of(table), b.device, b.dicts)


def partitions(df, table: str, n: int, batch_rows: int) -> list:
    """Per-partition lists of batches: row ranges, as a scan's tasks split a
    table (``ingest.to_batches`` over this module's schemas)."""
    per = (len(df) + n - 1) // n
    out = []
    for p in range(n):
        chunk = df.iloc[p * per:(p + 1) * per]
        out.append([batch_of(chunk.iloc[i:i + batch_rows], table)
                    for i in range(0, len(chunk), batch_rows)]
                   or [batch_of(chunk, table)])
    return out


def ingest(frames: dict, params: dict) -> dict:
    """Upload once: the fact table's and the returns' partitions and the
    reasons, every column of each, resident in HBM."""
    import jax

    n, rows = params["n_map"], params["batch_rows"]
    out = {"fact": partitions(frames["store_sales"], "store_sales", n, rows),
           "returns": partitions(frames["store_returns"], "store_returns", n, rows),
           "reason": [batch_of(frames["reason"], "reason")]}
    jax.block_until_ready([b.device for k in ("fact", "returns")
                           for p in out[k] for b in p])
    jax.block_until_ready(out["reason"][0].device)
    out["schemas"] = {t: schema_of(t) for t in
                      ("store_sales", "store_returns", "reason")}
    return out


def run(plan: dict, resident: dict, params: dict, work_dir: str, span) -> tuple:
    """One query. Returns ``(answer, shuffle_bytes)``: the driver owns
    ``work_dir``, so the bytes the tasks wrote there are its to count (all
    three exchanges). ``answer.attrs["sums"]`` holds every customer's sum."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider
    from auron_tpu.exprs.ir import BinaryOp, Case, IsNotNull, col, lit
    from auron_tpu.ops.sortkeys import SortSpec
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.planner import plan_from_proto

    sch = resident["schemas"]
    at = {t: {f.name: i for i, f in enumerate(s)} for t, s in sch.items()}
    n_map, n_reduce = len(resident["fact"]), params["n_reduce"]
    q = plan["name"]
    rid = {k: f"{q}_{k}" for k in ("fact", "returns", "reason", "r_build", "ex1",
                                   "ex2", "ex3")}
    api.put_resource(rid["fact"], resident["fact"])
    api.put_resource(rid["returns"], resident["returns"])
    api.put_resource(rid["reason"], [resident["reason"]] * n_reduce)
    try:
        ss, sr, r = at["store_sales"], at["store_returns"], at["reason"]
        sales = B.project(B.memory_scan(sch["store_sales"], rid["fact"]),
                          [(col(ss[c]), c) for c in FACT_COLUMNS])
        returns = B.project(
            B.filter_(B.memory_scan(sch["store_returns"], rid["returns"]),
                      [IsNotNull(col(sr[c])) for c in
                       ("sr_item_sk", "sr_ticket_number", "sr_reason_sk")]),
            [(col(sr[c]), c) for c in RETURN_COLUMNS])
        by_line = B.hash_partitioning([col(0), col(1)], n_reduce)

        def submit(nodes: list, stage_id: int) -> list:
            return [api.call_native(B.task(node, stage_id=stage_id, partition_id=p)
                                    .SerializeToString()) for p, node in nodes]

        def drain(handles: list) -> list:
            """Each task drained to its last batch; the batches as frames."""
            frames = []
            try:
                for h in handles:
                    while (rb := api.next_batch(h)) is not None:
                        frames.append(rb.to_pandas())
                    api.finalize_native(h)
            except BaseException:
                _finalize_quietly(api, handles)
                raise
            return frames

        def writers(node, part, n_tasks: int, tag: str) -> tuple:
            pairs = [(os.path.join(work_dir, f"{tag}{p}.data"),
                      os.path.join(work_dir, f"{tag}{p}.index"))
                     for p in range(n_tasks)]
            return [(p, B.shuffle_writer(node, part, *pairs[p]))
                    for p in range(n_tasks)], pairs

        with span("bench:stage12_exchange"):
            nodes1, pairs1 = writers(sales, by_line, n_map, "ss")
            nodes2, pairs2 = writers(returns, by_line, n_map, "sr")
            handles = submit(nodes1, 1)
            try:
                handles += submit(nodes2, 2)
            except BaseException:
                _finalize_quietly(api, handles)
                raise
            drain(handles)
        api.put_resource(rid["ex1"], MultiMapBlockProvider(pairs1))
        api.put_resource(rid["ex2"], MultiMapBlockProvider(pairs2))

        with span("bench:stage3_join"):
            first = SortSpec(asc=True, nulls_first=True)
            left = B.sort(B.ipc_reader(plan_from_proto(sales).schema, rid["ex1"]),
                          [(col(0), first), (col(1), first)])
            right = B.sort(B.ipc_reader(plan_from_proto(returns).schema, rid["ex2"]),
                           [(col(0), first), (col(1), first)])
            # item, ticket, customer, quantity, price | sr_item, sr_ticket,
            # sr_reason, sr_return_quantity
            smj = B.sort_merge_join(left, right, [col(0), col(1)], [col(0), col(1)],
                                    "inner")
            why = B.project(
                B.filter_(B.memory_scan(sch["reason"], rid["reason"]),
                          [BinaryOp("eq", col(r["r_reason_desc"]),
                                    lit(plan["reason"]))]),
                [(col(r["r_reason_sk"]), "r_reason_sk")])
            joined = B.hash_join(smj, why, [col(7)], [col(0)], "inner",
                                 build_side="right", cached_build_id=rid["r_build"])
            act_sales = Case(
                ((IsNotNull(col(8)),
                  BinaryOp("mul", BinaryOp("sub", col(3), col(8)), col(4))),),
                BinaryOp("mul", col(3), col(4)))
            acts = B.project(joined, [(col(2), "ss_customer_sk"),
                                      (act_sales, "act_sales")])
            keys = [(col(0), "ss_customer_sk")]
            partial = B.hash_agg(acts, keys, [("sum", col(1), "sumsales")],
                                 "partial")
            nodes3, pairs3 = writers(
                partial, B.hash_partitioning([col(0)], n_reduce), n_reduce, "cu")
            drain(submit(nodes3, 3))
        api.put_resource(rid["ex3"], MultiMapBlockProvider(pairs3))

        with span("bench:stage4_final"):
            final = B.hash_agg(
                B.ipc_reader(plan_from_proto(partial).inter_schema, rid["ex3"]),
                keys, [("sum", col(1), "sumsales")], "final")
            frames = drain(submit([(p, final) for p in range(n_reduce)], 4))
        shuffle_bytes = sum(os.path.getsize(f) for f, _ in pairs1 + pairs2 + pairs3)
        with span("bench:topk"):
            sums = (pd.concat(frames).reset_index(drop=True) if frames
                    else pd.DataFrame({c: [] for c in plan["output"]}))
            sums = sums[plan["output"]]
            # a NULL customer makes Arrow's int64 a float64 in pandas
            sums["ss_customer_sk"] = sums.ss_customer_sk.astype("Int64")
            merged = take_ordered(sums, plan["order"], plan["limit"])
            merged.attrs["sums"] = sums
        return merged, shuffle_bytes
    finally:
        for k in rid.values():
            api.remove_resource(k)
