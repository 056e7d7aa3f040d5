"""Spark's physical plan of TPC-DS query 65 (revenue by (store, item) over a
year, held against a tenth of the store's average revenue), built through the
protobuf builders and driven through ``bridge.api`` as a host engine drives it:

    stage 1 (n_map tasks):    scan of store_sales pruned to the four columns the
                              text reads -> BHJ date_dim (d_month_seq between DMS
                              and DMS + 11; no build column) -> partial
                              sum(ss_sales_price) by (ss_store_sk, ss_item_sk)
                              -> shuffle write by the keys              [exchange 1]
    stage 2 (n_reduce tasks): read exchange 1 -> final sum = revenue [sa]
                              -> partial avg(revenue) by ss_store_sk
                              -> shuffle write by ss_store_sk           [exchange 2]
    stage 3 (n_reduce tasks): read exchange 2 -> final avg = ave [sb] -> the
                              broadcast's channel (Spark's BroadcastExchange)
    stage 4 (n_reduce tasks): read exchange 1 again (Spark's ReusedExchange)
                              -> final sum = revenue [sc] -> BHJ sb on
                              ss_store_sk -> filter revenue <= 0.1 * ave -> BHJ
                              store -> BHJ item -> project the six output columns
    driver:                   top 100 by s_store_name, i_item_desc (Spark's
                              TakeOrderedAndProject)

Nothing but the top 100 is computed on the driver's side: every aggregate,
join, comparison and the DECIMAL average run in the program's tasks. A stage's
tasks are submitted together and then drained (an executor with a core a
task), as ``star_plan`` submits its map tasks; a stage starts when the one
before it has ended.
"""

from __future__ import annotations

import decimal
import os

import pandas as pd

from benchmark.star_plan import _finalize_quietly, take_ordered

FACT_COLUMNS = ("ss_sold_date_sk", "ss_store_sk", "ss_item_sk", "ss_sales_price")
ITEM_COLUMNS = ("i_item_sk", "i_item_desc", "i_current_price", "i_wholesale_cost",
                "i_brand")


def require_program() -> None:
    """What the plan needs of the program beyond what the star queries need;
    a program that lacks it fails here, at once, before any table is made."""
    from auron_tpu.exec.shuffle.reader import BroadcastBlockProvider  # noqa: F401


def schema_of(table: str, physical: bool = False):
    """``ingest.schema_of`` over the schema files of both generators."""
    from auron_tpu import types as T
    from benchmark import datagen_store
    from benchmark.ingest import dtype_of

    return T.Schema(tuple(
        T.Field(c, T.INT64 if physical and t.startswith("decimal") else dtype_of(t),
                nullable)
        for c, t, nullable in datagen_store.schemas()[table]))


def batch_of(df, table: str):
    from auron_tpu.columnar.batch import Batch

    b = Batch.from_pandas(df, schema=schema_of(table, physical=True))
    return Batch(schema_of(table), b.device, b.dicts)


def ingest(frames: dict, params: dict) -> dict:
    """Upload once: the fact table's partitions and the three dimensions,
    every column of each, resident in HBM."""
    import jax

    from benchmark import ingest as ing

    fact = ing.to_batches(frames["store_sales"], "store_sales",
                          params["n_map"], params["batch_rows"])
    dims = {"dd": [ing.batch_of(frames["date_dim"], "date_dim")],
            "it": [ing.batch_of(frames["item"], "item")],
            "st": [batch_of(frames["store"], "store")]}
    jax.block_until_ready([b.device for p in fact for b in p])
    jax.block_until_ready([d[0].device for d in dims.values()])
    return {"fact": fact, **dims, "schemas": {t: schema_of(t) for t in frames}}


def run(plan: dict, resident: dict, params: dict, work_dir: str, span) -> tuple:
    """One query. Returns ``(answer, shuffle_bytes)``: the driver owns
    ``work_dir``, so the bytes the tasks wrote there are its to count (both
    exchanges; the first is written once and read twice). The broadcast's
    rows, which the driver's side holds as Spark's driver holds a collected
    broadcast, go along as ``answer.attrs["sb"]``: the comparison holds the
    stores' averages to the reference's as well."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.shuffle.format import decode_blocks
    from auron_tpu.exec.shuffle.reader import (
        BroadcastBlockProvider,
        MultiMapBlockProvider,
    )
    from auron_tpu.exprs.ir import BinaryOp, col, lit
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.planner import plan_from_proto
    from auron_tpu import types as T

    fact = resident["fact"]
    sch = resident["schemas"]
    at = {t: {f.name: i for i, f in enumerate(s)} for t, s in sch.items()}
    n_map, n_reduce = len(fact), params["n_reduce"]
    q = plan["name"]
    rid = {k: f"{q}_{k}" for k in ("fact", "dd", "item", "store", "dd_build",
                                   "it_build", "st_build", "sb_build", "ex1", "ex2",
                                   "sb_chan", "sb")}
    api.put_resource(rid["fact"], fact)
    api.put_resource(rid["dd"], [resident["dd"]] * n_map)
    api.put_resource(rid["item"], [resident["it"]] * n_reduce)
    api.put_resource(rid["store"], [resident["st"]] * n_reduce)
    try:
        ss, dd = at["store_sales"], at["date_dim"]
        scan = B.project(B.memory_scan(sch["store_sales"], rid["fact"]),
                         [(col(ss[c]), c) for c in FACT_COLUMNS])
        year = B.project(
            B.filter_(B.memory_scan(sch["date_dim"], rid["dd"]),
                      [BinaryOp("gteq", col(dd["d_month_seq"]), lit(plan["dms"])),
                       BinaryOp("lteq", col(dd["d_month_seq"]),
                                lit(plan["dms"] + 11))]),
            [(col(dd["d_date_sk"]), "d_date_sk")])
        # date_sk, store_sk, item_sk, price | d_date_sk
        j1 = B.hash_join(scan, year, [col(0)], [col(0)], "inner",
                         build_side="right", cached_build_id=rid["dd_build"])
        p1 = B.project(j1, [(col(1), "ss_store_sk"), (col(2), "ss_item_sk"),
                            (col(3), "ss_sales_price")])
        pair_keys = [(col(0), "ss_store_sk"), (col(1), "ss_item_sk")]
        pair_sum = [("sum", col(2), "revenue")]
        partial = B.hash_agg(p1, pair_keys, pair_sum, "partial")
        part1 = B.hash_partitioning([col(0), col(1)], n_reduce)

        def run_stage(node_of, stage_id: int, n_tasks: int) -> list:
            """A stage: its ``n_tasks`` tasks submitted together, as an
            executor with a core a task runs them, then each drained to its
            last batch; the batches they gave as frames."""
            handles, frames = [], []
            try:
                for p in range(n_tasks):
                    handles.append(api.call_native(
                        B.task(node_of(p), stage_id=stage_id, partition_id=p)
                        .SerializeToString()))
                for h in handles:
                    while (rb := api.next_batch(h)) is not None:
                        frames.append(rb.to_pandas())
                    api.finalize_native(h)
            except BaseException:
                _finalize_quietly(api, handles)
                raise
            return frames

        def write_stage(node, part, stage_id: int, n_tasks: int, tag: str) -> list:
            """``n_tasks`` tasks that end in a shuffle writer; the (data,
            index) pairs they wrote."""
            pairs = [(os.path.join(work_dir, f"{tag}{p}.data"),
                      os.path.join(work_dir, f"{tag}{p}.index"))
                     for p in range(n_tasks)]
            run_stage(lambda p: B.shuffle_writer(node, part, *pairs[p]), stage_id,
                      n_tasks)
            return pairs

        with span("bench:stage1_map"):
            pairs1 = write_stage(partial, part1, 1, n_map, "map")
        api.put_resource(rid["ex1"], MultiMapBlockProvider(pairs1))

        inter1 = plan_from_proto(partial).inter_schema

        def revenue():
            """The first exchange's reader under the final sum: ``sa`` in
            stage 2 and ``sc`` in stage 4 (one exchange, read twice)."""
            return B.hash_agg(B.ipc_reader(inter1, rid["ex1"]), pair_keys, pair_sum,
                              "final")

        ave_keys = [(col(0), "ss_store_sk")]
        ave_agg = [("avg", col(2), "ave")]
        with span("bench:stage2_average"):
            partial_ave = B.hash_agg(revenue(), ave_keys, ave_agg, "partial")
            pairs2 = write_stage(partial_ave, B.hash_partitioning([col(0)], n_reduce),
                                 2, n_reduce, "ave")
        api.put_resource(rid["ex2"], MultiMapBlockProvider(pairs2))

        with span("bench:stage3_broadcast"):
            channel: list = []
            api.put_resource(rid["sb_chan"], channel)
            reader2 = B.ipc_reader(plan_from_proto(partial_ave).inter_schema,
                                   rid["ex2"])
            final_ave = B.hash_agg(reader2, [(col(0), "ss_store_sk")],
                                   [("avg", col(1), "ave")], "final")
            sb_schema = plan_from_proto(final_ave).schema
            run_stage(lambda p: B.ipc_writer(final_ave, rid["sb_chan"]), 3, n_reduce)
            api.put_resource(rid["sb"], BroadcastBlockProvider(channel))
            rbs = [rb for blk in channel for rb in decode_blocks(blk)]
            sb = (pd.concat([rb.to_pandas() for rb in rbs]).reset_index(drop=True)
                  if rbs else pd.DataFrame({"ss_store_sk": [], "ave": []}))
            sb["ss_store_sk"] = sb.ss_store_sk.astype("Int64")

        with span("bench:stage4_join"):
            st, it = at["store"], at["item"]
            # store_sk, item_sk, revenue | ss_store_sk, ave
            j_sb = B.hash_join(revenue(), B.ipc_reader(sb_schema, rid["sb"]),
                               [col(0)], [col(0)], "inner", build_side="right",
                               cached_build_id=rid["sb_build"])
            tenth = lit(decimal.Decimal("0.1"), T.decimal(1, 1))
            low = B.filter_(j_sb, [BinaryOp("lteq", col(2),
                                            BinaryOp("mul", tenth, col(4)))])
            p_sb = B.project(low, [(col(0), "ss_store_sk"), (col(1), "ss_item_sk"),
                                   (col(2), "revenue")])
            store = B.project(B.memory_scan(sch["store"], rid["store"]),
                              [(col(st["s_store_sk"]), "s_store_sk"),
                               (col(st["s_store_name"]), "s_store_name")])
            # store_sk, item_sk, revenue | s_store_sk, s_store_name
            j_st = B.hash_join(p_sb, store, [col(0)], [col(0)], "inner",
                               build_side="right", cached_build_id=rid["st_build"])
            p_st = B.project(j_st, [(col(1), "ss_item_sk"), (col(2), "revenue"),
                                    (col(4), "s_store_name")])
            item = B.project(B.memory_scan(sch["item"], rid["item"]),
                             [(col(it[c]), c) for c in ITEM_COLUMNS])
            # item_sk, revenue, s_store_name | i_item_sk, desc, price, cost, brand
            j_it = B.hash_join(p_st, item, [col(0)], [col(0)], "inner",
                               build_side="right", cached_build_id=rid["it_build"])
            have = ["ss_item_sk", "revenue", "s_store_name"] + list(ITEM_COLUMNS)
            out = B.project(j_it, [(col(have.index(c)), c) for c in plan["output"]])
            frames = run_stage(lambda p: out, 4, n_reduce)
        shuffle_bytes = sum(os.path.getsize(f) for f, _ in pairs1 + pairs2)
        with span("bench:topk"):
            merged = (pd.concat(frames).reset_index(drop=True) if frames
                      else pd.DataFrame({c: [] for c in plan["output"]}))
            merged = take_ordered(merged[plan["output"]], plan["order"],
                                  plan["limit"])
            merged.attrs["sb"] = sb
        return merged, shuffle_bytes
    finally:
        for k in rid.values():
            api.remove_resource(k)
