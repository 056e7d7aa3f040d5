"""Column-pruning optimizer pass: pruned plans must be result-identical
and actually shrink join outputs (reference: common/column_pruning.rs)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import types as T
from auron_tpu.columnar import Batch
from auron_tpu.exprs.ir import BinaryOp, col, lit
from auron_tpu.plan import builders as B
from auron_tpu.plan.optimizer import prune_columns
from auron_tpu.plan.planner import plan_from_proto
from auron_tpu.proto import plan_pb2 as pb


def _mk_batch(df):
    return [Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))]


def _schema(df):
    return T.Schema.from_arrow(
        pa.RecordBatch.from_pandas(df.iloc[:1], preserve_index=False).schema
    )


@pytest.fixture()
def star():
    rng = np.random.default_rng(11)
    fact = pd.DataFrame(
        {
            "f_date": rng.integers(0, 50, 3000).astype(np.int64),
            "f_item": rng.integers(0, 40, 3000).astype(np.int64),
            "f_junk1": rng.normal(size=3000),
            "f_junk2": rng.integers(0, 9, 3000).astype(np.int64),
            "f_price": rng.integers(1, 500, 3000).astype(np.int64),
        }
    )
    dim = pd.DataFrame(
        {
            "d_sk": np.arange(50, dtype=np.int64),
            "d_year": (1998 + np.arange(50) % 5).astype(np.int64),
            "d_junk": rng.normal(size=50),
        }
    )
    return fact, dim


def _q(fact_schema, dim_schema):
    """scan(fact) JOIN dim ON f_date=d_sk -> project(year, price)
    -> partial agg sum(price) by year."""
    scan = B.memory_scan(fact_schema, "opt_fact")
    dscan = B.memory_scan(dim_schema, "opt_dim")
    j = B.hash_join(scan, dscan, [col(0)], [col(0)], "inner", build_side="right")
    proj = B.project(j, [(col(6), "year"), (col(4), "price")])
    return B.hash_agg(proj, [(col(0), "year")], [("sum", col(1), "s")], "partial")


def _run_plan(plan, fact, dim):
    from auron_tpu.exec.base import ExecutionContext

    ctx = ExecutionContext(
        resources={"opt_fact": [_mk_batch(fact)], "opt_dim": [_mk_batch(dim)]}
    )
    op = plan_from_proto(plan)
    return op.collect(ctx=ctx).to_pandas().sort_values("year").reset_index(drop=True)


def test_prune_shrinks_join_and_preserves_results(star):
    fact, dim = star
    plan = _q(_schema(fact), _schema(dim))
    pruned = prune_columns(plan)

    # the join now carries a projection and the project references remapped
    j = pruned.hash_agg.child.project.child.hash_join
    assert j.has_projection
    assert len(j.projection) < 8  # 5 fact + 3 dim columns before pruning
    op = plan_from_proto(pruned)
    join_op = op.children[0].children[0]
    assert len(join_op.schema) == len(j.projection)

    got_orig = _run_plan(plan, fact, dim)
    got_pruned = _run_plan(pruned, fact, dim)
    pd.testing.assert_frame_equal(got_orig, got_pruned)

    want = (
        fact.merge(dim, left_on="f_date", right_on="d_sk")
        .groupby("d_year").agg(s=("f_price", "sum")).reset_index()
        .rename(columns={"d_year": "year"})
        .sort_values("year").reset_index(drop=True)
    )
    assert got_pruned["year"].tolist() == want["year"].tolist()
    assert got_pruned["s#sum"].astype(np.int64).tolist() == want["s"].tolist()


@pytest.mark.parametrize("join_type", ["left", "right", "full", "left_semi",
                                       "left_anti", "existence"])
def test_prune_all_join_types_result_identical(star, join_type):
    fact, dim = star
    fs, ds = _schema(fact), _schema(dim)
    scan = B.memory_scan(fs, "opt_fact")
    dscan = B.memory_scan(ds, "opt_dim")
    j = B.hash_join(scan, dscan, [col(0)], [col(0)], join_type, build_side="right")
    if join_type in ("left_semi", "left_anti"):
        proj = B.project(j, [(col(0), "k"), (col(4), "price")])
    elif join_type == "existence":
        proj = B.project(j, [(col(0), "k"), (col(5), "ex")])
    else:
        proj = B.project(j, [(col(0), "k"), (col(6), "year")])
    pruned = prune_columns(proj)
    got_orig = _run_plan_nosort(proj, fact, dim)
    got_pruned = _run_plan_nosort(pruned, fact, dim)
    pd.testing.assert_frame_equal(got_orig, got_pruned)


def _run_plan_nosort(plan, fact, dim):
    from auron_tpu.exec.base import ExecutionContext

    ctx = ExecutionContext(
        resources={"opt_fact": [_mk_batch(fact)], "opt_dim": [_mk_batch(dim)]}
    )
    op = plan_from_proto(plan)
    df = op.collect(ctx=ctx).to_pandas()
    return df.sort_values(list(df.columns)).reset_index(drop=True)


# ---------------------------------------------------------------------------
# the Sorts under a sort-merge join (elide_smj_input_sorts)
# ---------------------------------------------------------------------------

FACT = T.Schema(tuple(T.Field(c, T.INT64, True) for c in
                      ("item", "ticket", "customer", "quantity", "price")))
RETURNS = T.Schema(tuple(T.Field(c, T.INT64, True) for c in
                         ("sr_item", "sr_ticket", "sr_reason", "sr_quantity")))
REASON = T.Schema((T.Field("r_reason_sk", T.INT64, False),))


def _by_line(child):
    from auron_tpu.ops.sortkeys import SortSpec

    return B.sort(child, [(col(0), SortSpec()), (col(1), SortSpec())])


def _line_smj(left, right):
    return B.sort_merge_join(left, right, [col(0), col(1)], [col(0), col(1)],
                             "inner")


def _acts(smj):
    """Query 93's stage 3 above its join: BHJ reason on the probe side,
    the customer and the act_sales' operands projected."""
    joined = B.hash_join(smj, B.memory_scan(REASON, "reason"), [col(7)], [col(0)],
                         "inner", build_side="right")
    return B.project(joined, [(col(2), "customer"), (col(3), "quantity")])


def _stage3(root, smj=None):
    smj = smj or _line_smj(_by_line(B.ipc_reader(FACT, "ex1")),
                           _by_line(B.ipc_reader(RETURNS, "ex2")))
    return root(_acts(smj))


def _partial_sum(node):
    return B.hash_agg(node, [(col(0), "customer")], [("sum", col(1), "s")],
                      "partial")


def _writer(node, part=None):
    return B.shuffle_writer(node, part or B.hash_partitioning([col(0)], 2),
                            "d", "i")


def _smj_inputs(plan) -> list:
    """(left, right) node kinds of every sort-merge join, root first."""
    from auron_tpu.plan.protowalk import child_nodes

    out, todo = [], [plan]
    while todo:
        node = todo.pop(0)
        if node.WhichOneof("plan") == "sort_merge_join":
            j = node.sort_merge_join
            out.append((j.left.WhichOneof("plan"), j.right.WhichOneof("plan")))
        todo.extend(child_nodes(node))
    return out


@pytest.mark.parametrize("name, plan, mode, want", [
    # query 93's stage 3: the writer hashes, the partial sum has no order
    ("q93_stage3", lambda: _stage3(lambda n: _writer(_partial_sum(n))), "build",
     [("ipc_reader", "ipc_reader")]),
    ("ends_at_the_aggregate", lambda: _stage3(_partial_sum), "build",
     [("ipc_reader", "ipc_reader")]),
    # a head-N above: nothing is rewritten
    ("under_limit", lambda: _stage3(lambda n: _writer(_partial_sum(B.limit(n, 10)))),
     "build", [("sort", "sort")]),
    # the task's root hands its rows on in order (a broadcast, the driver)
    ("root_keeps_order", lambda: _stage3(lambda n: B.ipc_writer(n, "out")), "build",
     [("sort", "ipc_reader")]),
    ("range_partitioned", lambda: _stage3(lambda n: _writer(
        n, pb.Partitioning(kind=pb.Partitioning.RANGE, num_partitions=2))),
     "build", [("sort", "ipc_reader")]),
    # SMJ -> SMJ on the same keys: the inner join's order is what the outer
    # one could read, so only the outer one's probe Sort goes
    ("smj_over_smj", lambda: _stage3(
        lambda n: _writer(_partial_sum(n)),
        smj=_line_smj(_line_smj(_by_line(B.ipc_reader(FACT, "ex1")),
                                _by_line(B.ipc_reader(RETURNS, "ex2"))),
                      _by_line(B.ipc_reader(RETURNS, "ex3")))),
     "build", [("sort_merge_join", "ipc_reader"), ("sort", "ipc_reader")]),
    # the host's own word: full drops both anywhere, off none
    ("full_keeps_its_meaning", lambda: _stage3(lambda n: B.ipc_writer(n, "out")),
     "full", [("ipc_reader", "ipc_reader")]),
    ("off_keeps_its_meaning", lambda: _stage3(lambda n: _writer(_partial_sum(n))),
     "off", [("sort", "sort")]),
])
def test_probe_sort_goes_only_where_the_plan_proves_its_order_dies(name, plan,
                                                                   mode, want):
    from auron_tpu.plan.optimizer import elide_smj_input_sorts

    assert _smj_inputs(elide_smj_input_sorts(plan(), mode=mode)) == want
