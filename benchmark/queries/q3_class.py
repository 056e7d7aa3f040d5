"""Batch class q3: TPC-DS query 3 as the two Spark stages the engine runs.

    SELECT d_year, i_brand_id, sum(ss_ext_sales_price) s
    FROM store_sales JOIN date_dim ON ss_sold_date_sk = d_date_sk
                     JOIN item     ON ss_item_sk = i_item_sk
    WHERE d_moy = <moy> AND i_category_id = <category_id>
    GROUP BY d_year, i_brand_id ORDER BY d_year, s DESC LIMIT <limit>

A copy of the repo's drive (``auron_tpu/models/tpcds.py run_q3_class``,
``ingest_q3``) and of its pandas reference (``q3_class_oracle``): the plan is
built through the protobuf builders and driven through ``bridge.api`` as a
host engine would: ``n_map`` map tasks (scan -> BHJ date_dim -> BHJ item ->
partial aggregate -> shuffle writer), a file shuffle, ``n_reduce`` reduce
tasks (IPC read -> final aggregate), and the top-k on the driver's side.
"""

from __future__ import annotations

import os

import pandas as pd

#: the answer's rows come in the ORDER BY's order, made by this driver
IN_ORDER = True
#: what the query's text must read once, whatever plan the engine builds
SCAN_COLUMNS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"],
    "date_dim": ["d_date_sk", "d_year", "d_moy"],
    "item": ["i_item_sk", "i_brand_id", "i_category_id"],
}
_RESOURCES = ("q3_fact", "q3_dd", "q3_item", "q3_dd_build", "q3_it_build",
              "q3_blocks")


def _schema_of(df: pd.DataFrame):
    import pyarrow as pa

    from auron_tpu import types as T

    rb = pa.RecordBatch.from_pandas(df.iloc[:1], preserve_index=False)
    return T.Schema.from_arrow(rb.schema)


def _to_batches(df: pd.DataFrame, n_partitions: int, batch_rows: int) -> list:
    from auron_tpu.columnar.batch import Batch

    parts = []
    per = (len(df) + n_partitions - 1) // n_partitions
    for p in range(n_partitions):
        chunk = df.iloc[p * per:(p + 1) * per]
        parts.append([Batch.from_pandas(chunk.iloc[i:i + batch_rows])
                      for i in range(0, len(chunk), batch_rows)]
                     or [Batch.from_pandas(chunk)])
    return parts


def ingest(frames: dict, params: dict) -> dict:
    """Upload once: fact partitions and the two dimensions resident in HBM,
    as a host engine hands over a materialised columnar segment."""
    import jax

    from auron_tpu.columnar.batch import Batch

    fact = _to_batches(frames["store_sales"], params["n_map"],
                       params["batch_rows"])
    dd = [Batch.from_pandas(frames["date_dim"])]
    it = [Batch.from_pandas(frames["item"])]
    jax.block_until_ready([b.device for p in fact for b in p])
    jax.block_until_ready((dd[0].device, it[0].device))
    return {"fact": fact, "dd": dd, "it": it,
            "schemas": {k: _schema_of(v) for k, v in frames.items()}}


def _finalize_quietly(api, handles: list) -> None:
    for h in handles:
        try:
            api.finalize_native(h)
        except Exception:  # noqa: BLE001 -- unwind: the first error is the one to raise
            pass


def _drain_all(api, handles: list) -> None:
    """Drain every started task and finalize it; on an error finalize the
    rest too, so that a failing map task leaks no sibling's runtime."""
    try:
        for h in handles:
            while api.next_batch(h) is not None:
                pass
            api.finalize_native(h)
    except BaseException:
        _finalize_quietly(api, handles)
        raise


def run(resident: dict, params: dict, work_dir: str, span) -> tuple:
    """One query. Returns ``(answer, shuffle_bytes)``: the driver owns
    ``work_dir``, so the bytes the map tasks wrote there are its to count."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider
    from auron_tpu.exprs.ir import BinaryOp, col, lit
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.planner import plan_from_proto

    fact, dd, it = resident["fact"], resident["dd"], resident["it"]
    sch = resident["schemas"]
    n_map, n_reduce = len(fact), params["n_reduce"]
    api.put_resource("q3_fact", fact)
    api.put_resource("q3_dd", [dd] * n_map)
    api.put_resource("q3_item", [it] * n_map)
    try:
        scan = B.memory_scan(sch["store_sales"], "q3_fact")
        dscan = B.filter_(B.memory_scan(sch["date_dim"], "q3_dd"),
                          [BinaryOp("eq", col(2), lit(params["moy"]))])
        iscan = B.filter_(B.memory_scan(sch["item"], "q3_item"),
                          [BinaryOp("eq", col(2), lit(params["category_id"]))])
        j1 = B.hash_join(scan, dscan, [col(0)], [col(0)], "inner",
                         build_side="right", cached_build_id="q3_dd_build")
        # fact(5 cols) + date_dim(3) -> ss_item_sk at 1, price 4, d_year 6
        j2 = B.hash_join(j1, iscan, [col(1)], [col(0)], "inner",
                         build_side="right", cached_build_id="q3_it_build")
        # + item -> i_brand_id at 9
        proj = B.project(j2, [(col(6), "d_year"), (col(9), "i_brand_id"),
                              (col(4), "price")])
        keys = [(col(0), "d_year"), (col(1), "i_brand_id")]
        partial = B.hash_agg(proj, keys, [("sum", col(2), "s")], "partial")
        part = B.hash_partitioning([col(0), col(1)], n_reduce)
        pairs, handles = [], []
        with span("bench:submit_map"):
            try:
                for p in range(n_map):
                    data_f = os.path.join(work_dir, f"map{p}.data")
                    index_f = os.path.join(work_dir, f"map{p}.index")
                    w = B.shuffle_writer(partial, part, data_f, index_f)
                    handles.append(api.call_native(
                        B.task(w, stage_id=1, partition_id=p).SerializeToString()))
                    pairs.append((data_f, index_f))
            except BaseException:
                _finalize_quietly(api, handles)
                raise
        with span("bench:drain_map"):
            _drain_all(api, handles)
        shuffle_bytes = sum(os.path.getsize(d) for d, _ in pairs)

        frames = []
        with span("bench:reduce"):
            api.put_resource("q3_blocks", MultiMapBlockProvider(pairs))
            reader = B.ipc_reader(plan_from_proto(partial).inter_schema,
                                  "q3_blocks")
            final = B.hash_agg(reader, keys, [("sum", col(2), "s")], "final")
            for p in range(n_reduce):
                with api.native_task(B.task(final, stage_id=2, partition_id=p)
                                     .SerializeToString()) as h:
                    while (rb := api.next_batch(h)) is not None:
                        frames.append(rb.to_pandas())
        with span("bench:topk"):
            if not frames:
                return pd.DataFrame({"d_year": [], "i_brand_id": [], "s": []}), \
                    shuffle_bytes
            merged = pd.concat(frames).reset_index(drop=True)
            merged = merged.sort_values(
                ["d_year", "s"], ascending=[True, False], kind="stable"
            ).head(params["limit"]).reset_index(drop=True)
        return merged, shuffle_bytes
    finally:
        for k in _RESOURCES:
            api.remove_resource(k)


def reference(frames: dict, params: dict) -> pd.DataFrame:
    """Plain pandas, of the same frames; imports nothing of the program."""
    dd, it = frames["date_dim"], frames["item"]
    m = frames["store_sales"].merge(
        dd[dd.d_moy == params["moy"]], left_on="ss_sold_date_sk",
        right_on="d_date_sk",
    ).merge(
        it[it.i_category_id == params["category_id"]], left_on="ss_item_sk",
        right_on="i_item_sk",
    )
    g = (m.groupby(["d_year", "i_brand_id"])
          .agg(s=("ss_ext_sales_price", "sum")).reset_index())
    return (g.sort_values(["d_year", "s"], ascending=[True, False], kind="stable")
             .head(params["limit"]).reset_index(drop=True))
