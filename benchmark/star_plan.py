"""Spark's physical plan of the store-channel star queries (TPC-DS templates
3, 42, 52, 55: ``store_sales`` joined to a filtered ``date_dim`` and a filtered
``item``, one grouped sum, ORDER BY ... LIMIT 100), built through the protobuf
builders and driven through ``bridge.api`` as a host engine drives it:

- ``n_map`` map tasks: scan of store_sales pruned to the columns the text
  reads -> BHJ date_dim -> BHJ item -> partial aggregate -> shuffle writer;
- a file shuffle, hash partitioned by the grouping keys;
- ``n_reduce`` reduce tasks: IPC read -> final aggregate;
- the top 100 on the driver's side (Spark's TakeOrderedAndProject).

A query file gives the plan its parameters as a ``PLAN`` dict: ``name``, the
equality filters on date_dim and item, the grouping keys as ``(table, column,
output name)``, the summed fact column and its output name, the output columns
in the text's order, and the ORDER BY as ``(output name, ascending)``.
"""

from __future__ import annotations

import os

import pandas as pd

FACT_KEYS = ("ss_sold_date_sk", "ss_item_sk")


def ingest(frames: dict, params: dict) -> dict:
    """Upload once: the fact table's partitions and the two dimensions, every
    column of each, resident in HBM."""
    import jax

    from benchmark import ingest as ing

    fact = ing.to_batches(frames["store_sales"], "store_sales",
                          params["n_map"], params["batch_rows"])
    dd = [ing.batch_of(frames["date_dim"], "date_dim")]
    it = [ing.batch_of(frames["item"], "item")]
    jax.block_until_ready([b.device for p in fact for b in p])
    jax.block_until_ready((dd[0].device, it[0].device))
    return {"fact": fact, "dd": dd, "it": it,
            "schemas": {t: ing.schema_of(t) for t in frames}}


def take_ordered(df: pd.DataFrame, order: list, limit: int) -> pd.DataFrame:
    """The driver's side of the timed path: ORDER BY ... LIMIT over the
    reduce tasks' rows, NULLs as Spark orders them (first where a key
    ascends, last where it descends). One stable sort per key, last key
    first."""
    idx = list(range(len(df)))
    for c, asc in reversed(order):
        vals = [(0, 0) if pd.isna(v) else (1, v) for v in df[c].tolist()]
        idx.sort(key=vals.__getitem__, reverse=not asc)
    return df.iloc[idx[:limit]].reset_index(drop=True)


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


def run(plan: dict, resident: dict, params: dict, work_dir: str, span) -> tuple:
    """One query. Returns ``(answer, shuffle_bytes)``: the driver owns
    ``work_dir``, so the bytes the map tasks wrote there are its to count."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider
    from auron_tpu.exprs.ir import BinaryOp, col, lit
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.planner import plan_from_proto

    fact, dd, it = resident["fact"], resident["dd"], resident["it"]
    sch = resident["schemas"]
    at = {t: {f.name: i for i, f in enumerate(s)} for t, s in sch.items()}
    n_map, n_reduce = len(fact), params["n_reduce"]
    q = plan["name"]
    rid = {k: f"{q}_{k}" for k in ("fact", "dd", "item", "dd_build", "it_build",
                                   "blocks")}
    api.put_resource(rid["fact"], fact)
    api.put_resource(rid["dd"], [dd] * n_map)
    api.put_resource(rid["item"], [it] * n_map)
    try:
        def dim(table, resource, filters, out):
            t = at[table]
            scan = B.memory_scan(sch[table], resource)
            preds = [BinaryOp("eq", col(t[c]), lit(v)) for c, v in filters.items()]
            return B.project(B.filter_(scan, preds), [(col(t[c]), c) for c in out])

        d_out = ["d_date_sk"] + [c for t, c, _ in plan["keys"] if t == "date_dim"]
        i_out = ["i_item_sk"] + [c for t, c, _ in plan["keys"] if t == "item"]
        f_out = list(FACT_KEYS) + [plan["sum"][0]]
        ss = at["store_sales"]
        scan = B.project(B.memory_scan(sch["store_sales"], rid["fact"]),
                         [(col(ss[c]), c) for c in f_out])
        # date_sk, item_sk, money | d_date_sk, date keys...
        j1 = B.hash_join(scan, dim("date_dim", rid["dd"], plan["date_filter"], d_out),
                         [col(0)], [col(0)], "inner", build_side="right",
                         cached_build_id=rid["dd_build"])
        c1 = ["ss_item_sk", plan["sum"][0]] + d_out[1:]
        p1 = B.project(j1, [(col((f_out + d_out).index(c)), c) for c in c1])
        # item_sk, money, date keys... | i_item_sk, item keys...
        j2 = B.hash_join(p1, dim("item", rid["item"], plan["item_filter"], i_out),
                         [col(0)], [col(0)], "inner", build_side="right",
                         cached_build_id=rid["it_build"])
        c2 = c1 + i_out
        proj = B.project(j2, [(col(c2.index(c)), name) for _, c, name in plan["keys"]]
                         + [(col(1), "money")])
        n_keys = len(plan["keys"])
        keys = [(col(k), name) for k, (_, _, name) in enumerate(plan["keys"])]
        aggs = [("sum", col(n_keys), plan["sum"][1])]
        partial = B.hash_agg(proj, keys, aggs, "partial")
        part = B.hash_partitioning([col(k) for k in range(n_keys)], n_reduce)
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
        shuffle_bytes = sum(os.path.getsize(f) for f, _ in pairs)

        frames = []
        with span("bench:reduce"):
            api.put_resource(rid["blocks"], MultiMapBlockProvider(pairs))
            reader = B.ipc_reader(plan_from_proto(partial).inter_schema,
                                  rid["blocks"])
            final = B.hash_agg(reader, keys, aggs, "final")
            for p in range(n_reduce):
                with api.native_task(B.task(final, stage_id=2, partition_id=p)
                                     .SerializeToString()) as h:
                    while (rb := api.next_batch(h)) is not None:
                        frames.append(rb.to_pandas())
        with span("bench:topk"):
            cols = [name for _, _, name in plan["keys"]] + [plan["sum"][1]]
            merged = (pd.concat(frames).reset_index(drop=True) if frames
                      else pd.DataFrame({c: [] for c in cols}))
            merged = take_ordered(merged[plan["output"]], plan["order"],
                                  plan["limit"])
        return merged, shuffle_bytes
    finally:
        for k in rid.values():
            api.remove_resource(k)
