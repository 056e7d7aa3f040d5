"""auronlint gate: rule-family fixtures + whole-tree cleanliness.

Each rule family R1-R5 is exercised three ways — firing on a violating
fixture, honoring a suppression comment (with its required reason), and
staying quiet on clean code. The final test runs the real suite over the
real tree and fails on any unsuppressed finding, which is what makes the
engine invariants (host-sync hygiene, bounded compile cache, capacity
bucketing, registry lockstep, vectorization) regressions instead of
style advice.
"""

import json
import os
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.auronlint import ALL_RULES, REPO_ROOT, lint_source, run_tree
from tools.auronlint.report import Finding, Report
from tools.auronlint.rules import (
    HostSyncRule,
    RegistrySyncRule,
    RetraceRule,
    ShapeBucketRule,
    SortPayloadRule,
    VectorizeRule,
)


def _lint(src: str, rule, rel: str = "fixture.py"):
    return lint_source(textwrap.dedent(src), rel, [rule])


def _hits(report: Report, rule_name: str):
    return [f for f in report.findings if f.rule == rule_name and not f.suppressed]


def _suppressed(report: Report, rule_name: str):
    return [f for f in report.findings if f.rule == rule_name and f.suppressed]


# ---------------------------------------------------------------------------
# R1 host-sync hygiene
# ---------------------------------------------------------------------------


def test_r1_fires_on_item_read():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs):
            s = jnp.sum(xs)
            return s.item()
        """,
        HostSyncRule(),
    )
    assert len(_hits(rep, "R1")) == 1
    assert ".item()" in rep.findings[0].message


def test_r1_fires_on_scalar_coercion_and_iteration():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs):
            dev = jnp.cumsum(xs)
            n = int(dev[-1])
            for row in dev:
                pass
            if dev.any():
                n += 1
            return n
        """,
        HostSyncRule(),
    )
    msgs = " | ".join(f.message for f in _hits(rep, "R1"))
    assert len(_hits(rep, "R1")) == 3
    assert "int()" in msgs and "iterating" in msgs and "bool()" in msgs


def test_r1_suppression_honored_and_reason_required():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs):
            s = jnp.sum(xs)
            return s.item()  # auronlint: disable=R1 -- test fixture reason
        """,
        HostSyncRule(),
    )
    assert not _hits(rep, "R1")
    (sup,) = _suppressed(rep, "R1")
    assert sup.reason == "test fixture reason"

    # a reasonless suppression is itself a finding
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs):
            s = jnp.sum(xs)
            return s.item()  # auronlint: disable=R1
        """,
        HostSyncRule(),
    )
    assert [f for f in rep.findings if f.rule == "lint.suppression"]


def test_r1_sync_point_declares_allowed_boundary():
    rep = _lint(
        """
        import jax
        import jax.numpy as jnp

        def f(xs):
            total = jax.device_get(jnp.sum(xs))  # auronlint: sync-point -- one count per batch
            return total
        """,
        HostSyncRule(),
    )
    assert not rep.findings  # declared sync points are not findings at all


def test_r1_clean_code_stays_clean():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs):
            n = int(xs.shape[0])     # static metadata, not a sync
            out = jnp.zeros(n)
            cols = [xs, out]
            for c in cols:           # python container, not a device array
                pass
            return out
        """,
        HostSyncRule(),
    )
    assert not rep.findings


def test_r1_allowlisted_paths_are_exempt():
    src = """
    import jax.numpy as jnp

    def f(xs):
        return jnp.sum(xs).item()
    """
    rep = lint_source(textwrap.dedent(src),
                      "auron_tpu/exec/shuffle/writer.py", [HostSyncRule()])
    assert not rep.findings


# ---------------------------------------------------------------------------
# R2 retrace / compile-cache discipline
# ---------------------------------------------------------------------------


def test_r2_fires_on_undeclared_scalar_param():
    rep = _lint(
        """
        import jax

        @jax.jit
        def kernel(x, reverse=False):
            return x
        """,
        RetraceRule(),
    )
    assert len(_hits(rep, "R2")) == 1
    assert "static" in rep.findings[0].message


def test_r2_fires_on_unhashable_default_and_stale_static_name():
    rep = _lint(
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("renamed_flag",))
        def kernel(x, opts=[]):
            return x
        """,
        RetraceRule(),
    )
    msgs = " | ".join(f.message for f in _hits(rep, "R2"))
    assert "unhashable" in msgs and "stale" in msgs


def test_r2_fires_on_device_closure_capture():
    rep = _lint(
        """
        import jax
        import jax.numpy as jnp

        def outer(data):
            big = jnp.asarray(data)

            @jax.jit
            def inner(y):
                return y + big

            return inner
        """,
        RetraceRule(),
    )
    assert any("closes over device array 'big'" in f.message
               for f in _hits(rep, "R2"))


def test_r2_suppression_honored():
    rep = _lint(
        """
        import jax

        @jax.jit  # auronlint: disable=R2 -- traced once at import, fixture
        def kernel(x, reverse=False):
            return x
        """,
        RetraceRule(),
    )
    assert not _hits(rep, "R2") and _suppressed(rep, "R2")


def test_r2_clean_jit_site():
    rep = _lint(
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("reverse",))
        def kernel(x, reverse=False):
            return x
        """,
        RetraceRule(),
    )
    assert not rep.findings


# ---------------------------------------------------------------------------
# R3 shape-bucket discipline
# ---------------------------------------------------------------------------


def test_r3_fires_on_data_derived_shape():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs: jnp.ndarray):
            n = int(jnp.sum(xs))
            return jnp.zeros(n)
        """,
        ShapeBucketRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert len(_hits(rep, "R3")) == 1
    assert "data-dependent" in rep.findings[0].message


def test_r3_fires_on_item_shape():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(counts):
            total = jnp.cumsum(counts)[-1].item()
            return jnp.empty(total)
        """,
        ShapeBucketRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert len(_hits(rep, "R3")) == 1


def test_r3_suppression_honored():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs: jnp.ndarray):
            n = int(jnp.sum(xs))
            return jnp.zeros(n)  # auronlint: disable=R3 -- fixture: bounded by test harness
        """,
        ShapeBucketRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert not _hits(rep, "R3") and _suppressed(rep, "R3")


def test_r3_clean_capacity_shapes():
    rep = _lint(
        """
        import jax.numpy as jnp

        CAP = 4096

        def f(xs: jnp.ndarray):
            a = jnp.zeros(CAP)
            b = jnp.zeros(xs.shape[0])
            c = jnp.zeros((CAP, 2))
            return a, b, c
        """,
        ShapeBucketRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert not rep.findings


# ---------------------------------------------------------------------------
# R4 registry completeness
# ---------------------------------------------------------------------------

_MINI_PROTO = """
syntax = "proto3";
message PhysicalPlanNode {
  oneof plan {
    ScanNode scan = 1;
    FilterNode filter = 2;
  }
}
message PhysicalExprNode {
  oneof expr {
    ColumnExpr column = 1;
  }
}
"""

_MINI_PLANNER_OK = """
def plan_from_proto(p):
    which = p.WhichOneof("plan")
    if which == "scan":
        return 1
    if which == "filter":
        return 2


def expr_from_proto(p):
    which = p.WhichOneof("expr")
    if which == "column":
        return 1
"""

_MINI_PLANNER_DRIFTED = """
def plan_from_proto(p):
    which = p.WhichOneof("plan")
    if which == "scan":
        return 1


def expr_from_proto(p):
    which = p.WhichOneof("expr")
    if which == "column":
        return 1
"""

_MINI_EXPLAIN = """
PLAN_DETAILS = {"scan": (), "filter": ()}
"""

_MINI_BUILDERS = """
def expr_to_proto(e):
    n = X()
    n.column.index = 0
    return n


def scan():
    return W(scan=1)


def filter_():
    return W(filter=1)
"""


def _write_mini_tree(tmp_path, planner_src, explain_src=_MINI_EXPLAIN):
    at = tmp_path / "auron_tpu"
    for d in ("proto", "plan", "convert", "functions"):
        (at / d).mkdir(parents=True, exist_ok=True)
    (at / "proto" / "plan.proto").write_text(_MINI_PROTO)
    (at / "plan" / "planner.py").write_text(planner_src)
    (at / "plan" / "explain.py").write_text(explain_src)
    (at / "plan" / "builders.py").write_text(_MINI_BUILDERS)
    (at / "convert" / "exprs.py").write_text("_FN_RENAME = {}\n")
    return str(tmp_path)


def test_r4_fires_on_registry_drift(tmp_path):
    root = _write_mini_tree(tmp_path, _MINI_PLANNER_DRIFTED)
    findings = list(RegistrySyncRule().check_tree(root))
    msgs = " | ".join(m for _, _, m in findings)
    assert "plan variant 'filter' has no plan_from_proto dispatch" in msgs


def test_r4_fires_on_missing_explain_entry(tmp_path):
    root = _write_mini_tree(
        tmp_path, _MINI_PLANNER_OK, explain_src='PLAN_DETAILS = {"scan": ()}\n'
    )
    findings = list(RegistrySyncRule().check_tree(root))
    msgs = " | ".join(m for _, _, m in findings)
    assert "plan variant 'filter' missing from PLAN_DETAILS" in msgs


def test_r4_clean_mini_tree(tmp_path):
    root = _write_mini_tree(tmp_path, _MINI_PLANNER_OK)
    findings = [
        (rel, line, m)
        for rel, line, m in RegistrySyncRule().check_tree(root)
        if "function registry unimportable" not in m
    ]
    assert findings == []


def test_r4_suppression_honored(tmp_path):
    from tools.auronlint.core import lint_paths

    drifted = _MINI_PLANNER_DRIFTED.replace(
        "def plan_from_proto(p):",
        "def plan_from_proto(p):  # auronlint: disable=R4 -- fixture: drift acknowledged",
    )
    root = _write_mini_tree(tmp_path, drifted)
    rep = lint_paths([os.path.join(root, "auron_tpu")], root,
                     [RegistrySyncRule()])
    r4 = [f for f in rep.findings if f.rule == "R4"
          and "plan_from_proto dispatch" in f.message]
    assert r4 and all(f.suppressed for f in r4)


def test_r4_real_tree_registries_in_lockstep():
    """The real repo's registries must be drift-free right now."""
    findings = [
        (rel, line, m)
        for rel, line, m in RegistrySyncRule().check_tree(REPO_ROOT)
        if "function registry unimportable" not in m
    ]
    assert findings == [], "\n".join(m for _, _, m in findings)


# ---------------------------------------------------------------------------
# R5 vectorization ban
# ---------------------------------------------------------------------------


def test_r5_fires_on_per_row_loop():
    rep = _lint(
        """
        def f(batch):
            out = []
            for i in range(batch.num_rows):
                out.append(i)
            return out
        """,
        VectorizeRule(),
        rel="auron_tpu/exec/fixture.py",
    )
    assert len(_hits(rep, "R5")) == 1


def test_r5_fires_on_capacity_wide_loop_over_device():
    rep = _lint(
        """
        import jax.numpy as jnp

        def f(xs):
            vals = jnp.abs(xs)
            return [vals[i] for i in range(vals.shape[0])]
        """,
        VectorizeRule(),
        rel="auron_tpu/exec/fixture.py",
    )
    assert len(_hits(rep, "R5")) == 1


def test_r5_suppression_honored():
    rep = _lint(
        """
        def f(batch):
            for i in range(batch.num_rows):  # auronlint: disable=R5 -- fixture: per-run loop
                pass
        """,
        VectorizeRule(),
        rel="auron_tpu/exec/fixture.py",
    )
    assert not _hits(rep, "R5") and _suppressed(rep, "R5")


def test_r5_clean_loops_pass():
    rep = _lint(
        """
        def f(batches, cols):
            for b in batches:          # per-batch orchestration
                pass
            for c in cols:             # per-column
                pass
            for i in range(0, 100, 8):  # stepped chunk loop
                pass
        """,
        VectorizeRule(),
        rel="auron_tpu/exec/fixture.py",
    )
    assert not rep.findings


def test_r5_only_scopes_hot_paths():
    src = """
    def f(batch):
        for i in range(batch.num_rows):
            pass
    """
    rep = lint_source(textwrap.dedent(src), "auron_tpu/models/tpcds.py",
                      [VectorizeRule()])
    assert not rep.findings


# ---------------------------------------------------------------------------
# shared report schema
# ---------------------------------------------------------------------------


def test_report_json_schema_shared_with_jvm_lint():
    from tools import jvm_lint

    rep = run_tree(rules=[HostSyncRule()])
    doc = json.loads(rep.to_json())
    assert doc["schema"] == 1 and doc["tool"] == "auronlint"
    assert set(doc["counts"]) == {"total", "unsuppressed", "suppressed"}

    jrep = jvm_lint.run_report()
    jdoc = json.loads(jrep.to_json())
    assert jdoc["schema"] == 1 and jdoc["tool"] == "jvm_lint"
    assert set(jdoc["counts"]) == set(doc["counts"])
    # both serialize the same Finding fields
    f = Finding("t", "r", "p", 1, "m")
    keys = set(f.to_dict())
    for d in doc["findings"] + jdoc["findings"]:
        assert set(d) == keys
    assert Finding.from_dict(f.to_dict()) == f


# ---------------------------------------------------------------------------
# R6 sort-payload discipline
# ---------------------------------------------------------------------------


def test_r6_fires_on_column_scaling_operands():
    rep = _lint(
        """
        from jax import lax
        import jax.numpy as jnp

        def group(words, sel):
            dead = jnp.where(sel, 0, 1)
            iota = jnp.arange(sel.shape[0])
            operands = [dead, *words, iota]
            return lax.sort(tuple(operands), num_keys=len(operands) - 1)
        """,
        SortPayloadRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert len(_hits(rep, "R6")) == 1
    assert "fingerprint" in rep.findings[0].message


def test_r6_fires_on_comprehension_and_impl_choice():
    rep = _lint(
        """
        from jax import lax
        from auron_tpu.ops import bitonic

        def group(cols, n_keys, cap):
            impl = bitonic.sort_impl_for(n_keys + 1, cap)
            return lax.sort(tuple(c for c in cols), num_keys=1)
        """,
        SortPayloadRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert len(_hits(rep, "R6")) == 2


def test_r6_suppression_honored():
    rep = _lint(
        """
        from jax import lax

        def order_by(operands):
            ops = [*operands]
            return lax.sort(tuple(ops), num_keys=len(ops) - 1)  # auronlint: sort-payload -- ORDER BY sorts every user key by definition
        """,
        SortPayloadRule(),
        rel="auron_tpu/exec/fixture.py",
    )
    assert not _hits(rep, "R6")
    assert _suppressed(rep, "R6")


def test_r6_self_referential_reassignment_no_recursion():
    """`operands = operands + (iota,)` maps the name to an expression
    mentioning itself; the resolver must flag it as scaling (self-append
    grows the list), not recurse forever (regression: RecursionError
    aborted the whole lint run)."""
    rep = _lint(
        """
        from jax import lax

        def group(operands, n):
            operands = operands + (n,)
            return lax.sort(operands, num_keys=1)
        """,
        SortPayloadRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert len(_hits(rep, "R6")) == 1


def test_r6_fixed_arity_sorts_pass():
    rep = _lint(
        """
        from jax import lax
        import jax.numpy as jnp

        def cluster(fp, sel):
            dead = jnp.where(sel, 0, 1)
            iota = jnp.arange(sel.shape[0])
            return lax.sort((dead, fp, iota), num_keys=3)
        """,
        SortPayloadRule(),
        rel="auron_tpu/ops/fixture.py",
    )
    assert not rep.findings


# ---------------------------------------------------------------------------
# the gate: whole tree, zero unsuppressed findings
# ---------------------------------------------------------------------------


def test_whole_tree_zero_unsuppressed_findings():
    rep = run_tree(rules=ALL_RULES)
    bad = rep.unsuppressed
    assert not bad, "\n" + "\n".join(f.render() for f in bad)
    # every suppression in the tree carries a reason
    assert all(f.reason for f in rep.suppressed)


# ---------------------------------------------------------------------------
# sync-point multiplicity budgets (syncbudget.py + perfcheck contract)
# ---------------------------------------------------------------------------


def test_r1_sync_point_budget_declares_boundary():
    rep = _lint(
        """
        import jax
        import jax.numpy as jnp

        def f(xs):
            total = jax.device_get(jnp.sum(xs))  # auronlint: sync-point(1/batch) -- one count per batch
            seed = jax.device_get(xs)  # auronlint: sync-point(2/task) -- stream seed read
            ext = jax.device_get(xs)  # auronlint: sync-point(call) -- external API contract
            return total, seed, ext
        """,
        HostSyncRule(),
    )
    assert not rep.findings  # budgeted sync points are clean declarations


def test_malformed_sync_point_budget_is_a_finding():
    rep = _lint(
        """
        import jax
        import jax.numpy as jnp

        def f(xs):
            a = jax.device_get(xs)  # auronlint: sync-point(weekly) -- nonsense unit
            b = jax.device_get(xs)  # auronlint: disable(1/batch)=R1 -- budget on a disable
            return a, b
        """,
        HostSyncRule(),
    )
    assert len([f for f in rep.findings if f.rule == "lint.suppression"]) == 2


def test_parse_sync_budget_grammar():
    from tools.auronlint.core import parse_sync_budget

    assert parse_sync_budget("1/batch") == (1, "batch")
    assert parse_sync_budget(" 8 / task ") == (8, "task")
    assert parse_sync_budget("call") == (0, "call")
    assert parse_sync_budget("1/flush") is None
    assert parse_sync_budget("batch") is None
    assert parse_sync_budget("") is None


def test_syncbudget_collects_engine_declarations():
    """Every sync-point in the live tree parses to a budget, and the known
    hot-path sites resolve through the runtime-site matcher."""
    from tools.auronlint.syncbudget import (
        budget_for_site, collect_sync_points, site_allowlisted,
    )

    points = collect_sync_points(REPO_ROOT)
    assert len(points) > 20
    assert all(p.unit in ("batch", "task", "call") for p in points)
    # the joins' seed read (the compaction boundary's, exec/selectivity.py:
    # the one blocking read of chain and driver) must be task-budgeted —
    # a per-batch budget there would mask the whole tentpole regressing
    seed_pts = [p for p in points if p.rel.endswith("exec/selectivity.py")]
    assert len(seed_pts) == 1 and seed_pts[0].unit == "task"
    assert not [p for p in points if p.rel.endswith(
        ("joins/chain.py", "joins/driver.py"))]
    hit = budget_for_site(f"{seed_pts[0].rel.split('auron_tpu/')[1]}:{seed_pts[0].line}", points)
    assert hit is not None and hit.unit == "task"
    assert site_allowlisted("exec/shuffle/writer.py:330")
    assert not site_allowlisted("exec/joins/chain.py:1")


# ---------------------------------------------------------------------------
# interprocedural substrate (callgraph + summaries)
# ---------------------------------------------------------------------------


def _graph(sources: dict):
    from tools.auronlint.callgraph import build_graph_from_sources

    return build_graph_from_sources(
        {rel: textwrap.dedent(src) for rel, src in sources.items()}
    )


def test_callgraph_cycle_and_recursion_guard():
    """Recursion, mutual recursion and a base-class cycle must not hang
    any traversal (the R6 resolver-cycle lesson, applied to the graph)."""
    g = _graph({
        "pkg/a.py": """
        class A(object):
            def ping(self):
                self.pong()

            def pong(self):
                self.ping()

        def rec(n):  # auronlint: thread-root(foreign) -- test fixture
            from auron_tpu.utils.config import active_conf
            rec(n - 1)
            return active_conf()
        """,
        "pkg/b.py": """
        from pkg.a import A

        class B(A):
            pass

        class C(B):
            def ping(self):
                super().ping()
        """,
    })
    # every analysis terminates and the recursive root sees itself
    states = g.foreign_conf_states()
    assert any(q.endswith("::rec") for q in states)
    g.roots_reaching()
    g.batch_depths()
    g.jit_reachable()


def test_summaries_batch_loop_and_iter_attribution():
    """`for b in child_stream(...)`: the stream-constructing call sits at
    the surrounding depth, the body runs per batch."""
    from tools.auronlint.core import SourceModule
    from tools.auronlint.summaries import summarize_module

    src = textwrap.dedent("""
    def run(self, ctx):
        prelude()
        for b in self.child_stream(0, 0, ctx):
            body(b)
        for x in range(10):
            bounded(x)
    """)
    ms = summarize_module(SourceModule("m.py", "m.py", src))
    fs = ms.functions["m.py::run"]
    depths = {c.name: c.batch_depth for c in fs.calls}
    assert depths["prelude"] == 0
    assert depths["child_stream"] == 0      # iter position: evaluated once
    assert depths["body"] == 1              # per pumped batch
    assert depths["bounded"] == 0           # plain bounded loop


# ---------------------------------------------------------------------------
# R7 thread-context escape
# ---------------------------------------------------------------------------


def _r7(sources: dict):
    from tools.auronlint.rules.threadctx import analyze

    return list(analyze(_graph(sources)))


def test_r7_fires_on_bare_active_conf_from_foreign_root():
    hits = _r7({
        "pkg/spill.py": """
        from pkg.conf import codec

        class Staging:
            def spill(self):  # auronlint: thread-root(foreign) -- test fixture
                return codec()
        """,
        "pkg/conf.py": """
        from auron_tpu.utils.config import active_conf

        def codec():
            return active_conf().get("spill.codec")
        """,
    })
    assert len(hits) == 1
    rel, line, msg = hits[0]
    assert rel == "pkg/conf.py" and "Staging.spill" in msg


def test_r7_quiet_when_conf_threaded_and_guarded():
    hits = _r7({
        "pkg/spill.py": """
        from pkg.conf import codec

        class Staging:
            def __init__(self, ctx):
                self.ctx = ctx

            def spill(self):  # auronlint: thread-root(foreign) -- test fixture
                return codec(conf=self.ctx.conf)
        """,
        "pkg/conf.py": """
        from auron_tpu.utils.config import active_conf

        def codec(conf=None):
            return (conf if conf is not None else active_conf()).get("x")
        """,
    })
    assert hits == []


def test_r7_guarded_fallback_fires_when_a_path_drops_conf():
    hits = _r7({
        "pkg/spill.py": """
        from pkg.conf import codec

        class Staging:
            def spill(self):  # auronlint: thread-root(foreign) -- test fixture
                return codec()
        """,
        "pkg/conf.py": """
        from auron_tpu.utils.config import active_conf

        def codec(conf=None):
            return (conf if conf is not None else active_conf()).get("x")
        """,
    })
    assert len(hits) == 1
    assert "WITHOUT passing conf" in hits[0][2]


def test_r7_conf_scoped_root_is_exempt():
    hits = _r7({
        "pkg/pump.py": """
        from auron_tpu.utils.config import active_conf

        def pump():  # auronlint: thread-root(conf-scoped) -- installs scope
            return active_conf()
        """,
    })
    assert hits == []


def test_r7_conf_scope_block_neutralizes_downstream():
    hits = _r7({
        "pkg/svc.py": """
        from auron_tpu.utils.config import active_conf, conf_scope

        def helper():
            return active_conf()

        def handle(conf):  # auronlint: thread-root(foreign) -- test fixture
            with conf_scope(conf):
                return helper()
        """,
    })
    assert hits == []


# ---------------------------------------------------------------------------
# R8 lock discipline
# ---------------------------------------------------------------------------


def _r8(sources: dict):
    from tools.auronlint.rules.lockguard import analyze

    return list(analyze(_graph(sources)))


_R8_SHARED = """
import threading

class Mgr:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def bump(self):
        {write}

class Consumer:
    def spill(self):  # auronlint: thread-root(foreign) -- test fixture
        shrink()

def shrink():
    m = Mgr()
    m.bump()

def pump():  # auronlint: thread-root(conf-scoped) -- test fixture
    m = Mgr()
    m.bump()

_GLOBAL_MGR = Mgr()
"""


def test_r8_fires_on_unlocked_cross_root_write():
    hits = _r8({"pkg/m.py": _R8_SHARED.format(write="self.n += 1")})
    assert len(hits) == 1
    assert "Mgr.n" in hits[0][2] and "2 thread roots" in hits[0][2]


def test_r8_quiet_under_lock_and_with_guarded_by():
    hits = _r8({"pkg/m.py": _R8_SHARED.format(
        write="with self._lock:\n            self.n += 1"
    )})
    assert hits == []
    # guarded-by declaration: the lock is held by the caller
    hits = _r8({"pkg/m.py": _R8_SHARED.format(
        write="self.n += 1  # auronlint: guarded-by(self._lock) -- callers hold it"
    )})
    assert hits == []


def test_r8_single_root_and_local_objects_are_quiet():
    # single root: per-task state needs no lock
    src = _R8_SHARED.format(write="self.n += 1").replace(
        "def spill(self):  # auronlint: thread-root(foreign) -- test fixture",
        "def spill(self):",
    )
    assert _r8({"pkg/m.py": src}) == []
    # function-local parser objects never escape -> never shared
    hits = _r8({"pkg/p.py": """
    class Cursor:
        def __init__(self, buf):
            self.pos = 0

        def take(self):
            self.pos += 1

    class Consumer:
        def spill(self):  # auronlint: thread-root(foreign) -- test fixture
            c = Cursor(b"x")
            c.take()

    def pump():  # auronlint: thread-root(conf-scoped) -- test fixture
        c = Cursor(b"y")
        c.take()
    """})
    assert hits == []


def test_r8_thread_owned_class_declaration_exempts_writes():
    """A class declared thread-owned (single-thread instance ownership —
    the serving-layer pattern: per-query operator instances reachable
    from both the pump root and the POST /sql handler root) is exempt."""
    src = _R8_SHARED.format(write="self.n += 1").replace(
        "class Mgr:",
        "# auronlint: thread-owned -- fixture: one instance per query, "
        "one driving thread\nclass Mgr:",
    )
    assert _r8({"pkg/m.py": src}) == []


def test_r8_detached_thread_owned_is_a_finding():
    """A thread-owned that anchors to a non-class line is inert — R8
    reports the detached declaration instead of silently dropping it,
    AND still reports the unexempted write."""
    src = _R8_SHARED.format(
        write="self.n += 1  # auronlint: thread-owned -- wrong anchor"
    )
    hits = _r8({"pkg/m.py": src})
    msgs = [h[2] for h in hits]
    assert any("does not anchor to a `class`" in m for m in msgs)
    assert any("Mgr.n" in m for m in msgs)


def test_thread_owned_rides_the_lint_ratchet():
    """thread-owned declarations count as declared debt (LINT_RATCHET)."""
    from tools.auronlint import ratchet

    assert "thread-owned" in ratchet.load(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# R9 static sync-budget verification
# ---------------------------------------------------------------------------


def _r9(sources: dict):
    from tools.auronlint.rules.budgetproof import analyze

    return list(analyze(_graph(sources)))


def test_r9_fires_on_call_budget_inside_batch_loop():
    hits = _r9({"pkg/op.py": """
    import jax

    def read(b):
        return jax.device_get(b)  # auronlint: sync-point(call) -- caller-owned

    class Op:
        def pump(self, ctx):  # auronlint: thread-root(conf-scoped) -- test fixture
            for b in self.child_stream(0, 0, ctx):
                read(b)
    """})
    assert len(hits) == 1
    assert "caller-owned" in hits[0][2]


def test_r9_fires_on_task_budget_in_local_batch_loop():
    hits = _r9({"pkg/op.py": """
    import jax

    class Op:
        def pump(self, ctx):  # auronlint: thread-root(conf-scoped) -- test fixture
            for b in self.child_stream(0, 0, ctx):
                n = jax.device_get(b)  # auronlint: sync-point(2/task) -- wrongly task-budgeted
    """})
    assert len(hits) == 1
    assert "task-bounded" in hits[0][2]


def test_r9_batch_budget_in_batch_loop_is_proven():
    hits = _r9({"pkg/op.py": """
    import jax

    class Op:
        def pump(self, ctx):  # auronlint: thread-root(conf-scoped) -- test fixture
            prep = jax.device_get(0)  # auronlint: sync-point(4/task) -- once per task
            for b in self.child_stream(0, 0, ctx):
                n = jax.device_get(b)  # auronlint: sync-point(1/batch) -- per batch by design
    """})
    assert hits == []


def test_r9_batch_budget_squared_fires():
    hits = _r9({"pkg/op.py": """
    import jax

    class Op:
        def pump(self, ctx):  # auronlint: thread-root(conf-scoped) -- test fixture
            for b in self.child_stream(0, 0, ctx):
                for c in self.child_stream(1, 0, ctx):
                    n = jax.device_get(c)  # auronlint: sync-point(1/batch) -- nested!
    """})
    assert len(hits) == 1
    assert "SQUARED" in hits[0][2]


# ---------------------------------------------------------------------------
# R10 jit-boundary purity
# ---------------------------------------------------------------------------


def _r10(sources: dict):
    from tools.auronlint.rules.jitpurity import analyze

    return list(analyze(_graph(sources)))


def test_r10_fires_on_conf_read_and_transfer_inside_jit():
    hits = _r10({"pkg/k.py": """
    import jax
    from auron_tpu.utils.config import active_conf

    @jax.jit
    def kernel(x):
        mode = active_conf().get("exec.mode")
        n = x.item()
        return x + 1
    """})
    msgs = " | ".join(h[2] for h in hits)
    assert len(hits) == 2
    assert "active_conf" in msgs and ".item()" in msgs


def test_r10_traced_helper_and_captured_mutation():
    hits = _r10({"pkg/k.py": """
    import jax
    from functools import partial

    _CACHE = {}

    def helper(x):
        _CACHE[1] = x
        return x

    @partial(jax.jit, static_argnames=("n",))
    def kernel(x, *, n):
        return helper(x) + n
    """})
    assert len(hits) == 1
    assert "subscript write to captured '_CACHE'" in hits[0][2]
    assert "traced via" in hits[0][2]


def test_r10_fires_on_obs_recorder_call_inside_jit():
    """Span-recording calls are host-side only: inside a jit they fire at
    trace time and never replay — every import shape must be caught."""
    hits = _r10({"pkg/k.py": """
    import jax
    from auron_tpu import obs
    from auron_tpu.obs import note_sync

    @jax.jit
    def kernel(x):
        obs.note_op("FilterExec", "elapsed_compute", 1)
        note_sync(1, False)
        return x + 1

    def helper(y):
        with obs.span("inner"):
            return y

    @jax.jit
    def kernel2(x):
        return helper(x)
    """})
    msgs = [h[2] for h in hits]
    assert len(hits) == 3, msgs
    assert all("host-side only" in m for m in msgs)
    assert any("'note_op'" in m for m in msgs)
    assert any("'note_sync'" in m for m in msgs)
    assert any("'span'" in m and "traced via" in m for m in msgs)


def test_r10_obs_call_outside_jit_quiet():
    hits = _r10({"pkg/k.py": """
    import jax
    from auron_tpu import obs

    @jax.jit
    def kernel(x):
        return x + 1

    def pump(x):
        with obs.span("task"):
            return kernel(x)
    """})
    assert not hits


def test_r10_pure_callback_target_not_traced_and_pure_fn_quiet():
    hits = _r10({"pkg/k.py": """
    import jax
    import numpy as np

    def _host_sort(x):
        out = []
        out.append(1)   # local list: fine
        return np.lexsort(x)

    @jax.jit
    def kernel(x):
        order = jax.pure_callback(_host_sort, x, x)
        return x[order]
    """})
    assert hits == []


# ---------------------------------------------------------------------------
# annotation grammar: thread-root / guarded-by
# ---------------------------------------------------------------------------


def test_thread_root_grammar_validation():
    rep = _lint(
        """
        def ok():  # auronlint: thread-root(foreign) -- net thread
            pass

        def bad_kind():  # auronlint: thread-root(weekly) -- nonsense
            pass

        def no_reason():  # auronlint: thread-root(foreign)
            pass
        """,
        HostSyncRule(),
    )
    sup = [f for f in rep.findings if f.rule == "lint.suppression"]
    # bad kind -> malformed argument; missing reason -> reasonless finding
    assert len(sup) == 2


def test_guarded_by_grammar_requires_lock_and_reason():
    rep = _lint(
        """
        class C:
            def f(self):
                self.n = 1  # auronlint: guarded-by(self._lock) -- caller holds
                self.m = 2  # auronlint: guarded-by -- no lock named
        """,
        HostSyncRule(),
    )
    sup = [f for f in rep.findings if f.rule == "lint.suppression"]
    assert len(sup) == 1  # the lockless guarded-by


def test_standalone_annotations_stack_to_next_code_line():
    """Two standalone declarations above one statement both anchor to the
    statement (the R9-over-sync-point interplay regression)."""
    from tools.auronlint.core import SourceModule

    src = textwrap.dedent("""
    import jax

    def f(xs):
        # auronlint: sync-point(call) -- declared boundary
        # auronlint: disable=R9 -- bounded by spill pressure
        return jax.device_get(xs)
    """)
    mod = SourceModule("m.py", "m.py", src)
    sync = [s for s in mod.suppressions if s.kind == "sync-point"][0]
    assert mod.anchor_line(sync) == 7  # the return line, not the comment
    assert mod.is_sync_point(7)
    assert mod.suppression_for("R9", 7) is not None


# ---------------------------------------------------------------------------
# lint ratchet
# ---------------------------------------------------------------------------


def test_lint_ratchet_seed_improve_regress(tmp_path):
    from tools.auronlint.ratchet import check_and_update, load, save
    from tools.auronlint.report import Finding, Report

    root = str(tmp_path)
    (tmp_path / "auron_tpu").mkdir()

    def report_with(n_suppressed):
        rep = Report(tool="auronlint")
        for i in range(n_suppressed):
            rep.findings.append(Finding(
                "auronlint", "R7", "auron_tpu/x.py", i + 1, "m",
                suppressed=True, reason="r",
            ))
        return rep

    # seed: first sighting records current debt
    assert check_and_update(report_with(3), root) == []
    assert load(root)["R7"] == 3
    # improvement: ratchet tightens automatically
    assert check_and_update(report_with(2), root) == []
    assert load(root)["R7"] == 2
    # regression: fails, file unchanged
    problems = check_and_update(report_with(5), root)
    assert problems and "R7" in problems[0]
    assert load(root)["R7"] == 2
    # explicit conscious raise is honored
    counts = load(root)
    counts["R7"] = 5
    save(root, counts)
    assert check_and_update(report_with(5), root) == []


def test_live_tree_ratchet_matches_current_debt():
    """LINT_RATCHET.json is committed and must match (or exceed) the
    tree's actual suppression counts — `make lint` enforces it."""
    from tools.auronlint.ratchet import current_counts, load
    from tools.auronlint import run_tree

    ratchet = load(REPO_ROOT)
    assert ratchet.get("sync-point", 0) > 20
    rep = run_tree()
    counts = current_counts(rep, REPO_ROOT)
    for key, n in counts.items():
        assert n <= ratchet.get(key, 0), (
            f"{key} debt {n} exceeds LINT_RATCHET.json "
            f"{ratchet.get(key, 0)} — make lint would fail"
        )


# ---------------------------------------------------------------------------
# SARIF emitter (shared by auronlint and jvm_lint)
# ---------------------------------------------------------------------------


def test_sarif_schema_shape():
    from tools.auronlint.report import Finding, Report

    rep = Report(tool="auronlint")
    rep.findings.append(Finding("auronlint", "R7", "a.py", 3, "boom"))
    rep.findings.append(Finding(
        "auronlint", "R9", "b.py", 0, "waived", suppressed=True, reason="why",
    ))
    doc = json.loads(rep.to_sarif())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "auronlint"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == {"R7", "R9"}
    res = run["results"]
    assert res[0]["locations"][0]["physicalLocation"]["region"]["startLine"] == 3
    # line 0 (file-level) clamps to 1 for SARIF validity
    assert res[1]["locations"][0]["physicalLocation"]["region"]["startLine"] == 1
    assert res[1]["suppressions"][0]["justification"] == "why"


def test_engine_thread_roots_are_declared():
    """The known thread entry points carry thread-root declarations — the
    interprocedural rules are only as good as their roots."""
    from tools.auronlint.callgraph import build_graph

    g = build_graph(REPO_ROOT)
    roots = {q.split("::", 1)[1]: k for q, k in g.roots.items()}
    assert roots.get("TaskRuntime._pump") == "conf-scoped"
    assert roots.get("_Handler.do_GET") == "foreign"
    assert roots.get("RssNetServer._handle") == "foreign"
    assert roots.get("_ShuffleStaging.spill") == "foreign"
    assert roots.get("_AggTableConsumer.spill") == "foreign"
    assert roots.get("_SorterConsumer.spill") == "foreign"
    assert roots.get("harvest") == "foreign"


def test_thread_root_standalone_above_decorated_def_registers():
    """The anchor of a standalone root above a decorated def is the
    decorator line — the root must still register (a silently-dropped
    root would disable reachability)."""
    hits = _r7({"pkg/svc.py": """
    from auron_tpu.utils.config import active_conf

    def deco(f):
        return f

    # auronlint: thread-root(foreign) -- handler thread
    @deco
    def handler():
        return worker()

    def worker():
        return active_conf()
    """})
    assert len(hits) == 1 and "handler" in hits[0][2]


def test_unanchored_thread_root_is_a_loud_finding():
    hits = _r7({"pkg/svc.py": """
    # auronlint: thread-root(foreign) -- floats above nothing
    X = 1
    """})
    assert len(hits) == 1
    assert "does not anchor to a function definition" in hits[0][2]


def test_lint_ratchet_failing_run_does_not_tighten(tmp_path):
    """A transiently-broken tree (suppressions detached -> unsuppressed
    findings) must not lower the debt ceiling."""
    from tools.auronlint.ratchet import check_and_update, load
    from tools.auronlint.report import Finding, Report

    root = str(tmp_path)
    (tmp_path / "auron_tpu").mkdir()

    def report(n_sup, n_unsup=0):
        rep = Report(tool="auronlint")
        for i in range(n_sup):
            rep.findings.append(Finding(
                "auronlint", "R7", "auron_tpu/x.py", i + 1, "m",
                suppressed=True, reason="r"))
        for i in range(n_unsup):
            rep.findings.append(Finding(
                "auronlint", "R7", "auron_tpu/x.py", 100 + i, "loose"))
        return rep

    check_and_update(report(5), root)
    assert load(root)["R7"] == 5
    # 3 suppressions detach: run FAILS (2 unsuppressed) — ceiling stays
    check_and_update(report(2, n_unsup=3), root)
    assert load(root)["R7"] == 5
    # restoring the suppressions is NOT a regression
    assert check_and_update(report(5), root) == []


def test_changed_mode_rejects_vacuous_and_ambiguous_invocations(capsys):
    from tools.auronlint.__main__ import main

    # tree-only rule selection under --changed would run zero rules
    assert main(["--changed", "--rules", "R7"]) == 2
    assert "vacuous" in capsys.readouterr().err
    # explicit paths would be silently ignored
    assert main(["--changed", "auron_tpu/exec"]) == 2
    assert "picks its own files" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# R2 fused-segment cache-key discipline (whole-stage fusion, docs/fusion.md)
# ---------------------------------------------------------------------------


def test_r2_fires_on_jit_wrapper_built_in_batch_loop():
    """A jit wrapper constructed per batch (or per segment instance inside
    the batch loop) starts an empty compile cache each iteration — the
    fused-segment retrace explosion the stage-program cache key exists to
    prevent."""
    rep = _lint(
        """
        import jax

        def drive(stream, fn):
            for b in stream:
                prog = jax.jit(fn)
                yield prog(b)
        """,
        RetraceRule(),
    )
    hits = _hits(rep, "R2")
    assert len(hits) == 1
    assert "inside a loop" in hits[0].message


def test_r2_fires_on_jit_decorated_def_in_loop():
    rep = _lint(
        """
        import jax

        def build(segments):
            out = []
            for seg in segments:
                @jax.jit
                def prog(dev):
                    return dev
                out.append(prog)
            return out
        """,
        RetraceRule(),
    )
    hits = _hits(rep, "R2")
    assert len(hits) == 1
    assert "defined inside a loop" in hits[0].message


def test_r2_module_level_stage_program_quiet():
    """The sanctioned pattern (plan/fusion.py): ONE module-level jit whose
    cache keys on static (schema, segment signature) args, dispatched from
    the batch loop — a call inside the loop is fine, construction is not."""
    rep = _lint(
        """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("steps",))
        def _stage_program(dev, *, steps):
            return dev

        def drive(stream, steps):
            for b in stream:
                yield _stage_program(b, steps=steps)
        """,
        RetraceRule(),
    )
    assert not _hits(rep, "R2")


# ---------------------------------------------------------------------------
# R10 teeth for fused-stage closures: the trace-safe machinery the stage
# compiler reuses must keep being checked for conf reads, host transfers
# and captured-state mutation through the whole traced closure
# ---------------------------------------------------------------------------


def test_r10_fused_stage_shaped_closure_conf_read():
    """A helper reachable from a stage-program-shaped jit entry reading
    active_conf(): the resolved knob would be baked into every cached
    (schema, signature, bucket) program."""
    hits = _r10({"pkg/stage.py": """
    import jax
    from functools import partial
    from auron_tpu.utils.config import active_conf

    def _eval_step(dev, steps):
        if active_conf().get("exec.fuse.enable") == "off":
            return dev
        return dev

    @partial(jax.jit, static_argnames=("steps",))
    def stage_program(dev, *, steps):
        return _eval_step(dev, steps)
    """})
    assert len(hits) == 1
    assert "active_conf" in hits[0][2] and "traced via" in hits[0][2]


def test_r10_fused_stage_shaped_closure_host_transfer_and_mutation():
    """Host transfers and compile-counter mutation inside the traced
    closure: both fire once at trace time only — the exact hazards the
    fusion pass keeps OUTSIDE the program (_note_dispatch runs host-side
    before dispatch)."""
    hits = _r10({"pkg/stage.py": """
    import jax
    from functools import partial

    _COMPILES = {}

    def _count_and_read(dev, sig):
        _COMPILES[sig] = _COMPILES.get(sig, 0) + 1
        return int(dev.sum().item())

    @partial(jax.jit, static_argnames=("sig",))
    def stage_program(dev, *, sig):
        n = _count_and_read(dev, sig)
        return dev[:n]
    """})
    msgs = " | ".join(h[2] for h in hits)
    assert len(hits) == 2
    assert ".item()" in msgs and "_COMPILES" in msgs


def test_r2_call_form_decorator_in_loop_reports_once():
    """@partial(jax.jit, ...) decorators are ast.Call nodes too — the
    loop scan must report the site exactly once (decorator branch), not
    double-count it through the bare-call branch."""
    rep = _lint(
        """
        import jax
        from functools import partial

        def build(segments):
            out = []
            for seg in segments:
                @partial(jax.jit, static_argnames=("n",))
                def prog(dev, *, n):
                    return dev
                out.append(prog)
            return out
        """,
        RetraceRule(),
    )
    hits = _hits(rep, "R2")
    assert len(hits) == 1
    assert "defined inside a loop" in hits[0].message


# ---------------------------------------------------------------------------
# the CFG layer (exception edges) — the R11/R12 substrate
# ---------------------------------------------------------------------------


def _cfg_of(src: str):
    import ast as _ast

    from tools.auronlint.cfg import build_cfg

    tree = _ast.parse(textwrap.dedent(src))
    fn = next(n for n in _ast.walk(tree) if isinstance(n, _ast.FunctionDef))
    return fn, build_cfg(fn)


def test_cfg_try_finally_covers_exception_edges():
    """A release in a finally is on EVERY path; without the finally the
    exception edge out of the loop leaks."""
    from tools.auronlint.cfg import leak_paths

    fn, cfg = _cfg_of(
        """
        def f():
            h = acquire()
            try:
                for x in stream():
                    use(h, x)
            finally:
                h.release()
        """
    )
    acq = next(n for n in cfg.stmt_nodes() if n.line == 3)
    rel = {n.idx for n in cfg.stmt_nodes() if n.line == 8}
    assert leak_paths(cfg, acq.idx, rel) == []

    fn, cfg = _cfg_of(
        """
        def f():
            h = acquire()
            for x in stream():
                use(h, x)
            h.release()
        """
    )
    acq = next(n for n in cfg.stmt_nodes() if n.line == 3)
    rel = {n.idx for n in cfg.stmt_nodes() if n.line == 6}
    assert leak_paths(cfg, acq.idx, rel) == ["an exception path"]


def test_cfg_narrow_handler_lets_exceptions_escape():
    """`except ValueError` does not stop a TypeError: the exception edge
    continues outward past narrow handlers, stops at broad ones."""
    from tools.auronlint.cfg import leak_paths

    fn, cfg = _cfg_of(
        """
        def f():
            h = acquire()
            try:
                use(h)
            except ValueError:
                h.release()
            h.release()
        """
    )
    acq = next(n for n in cfg.stmt_nodes() if n.line == 3)
    rel = {n.idx for n in cfg.stmt_nodes() if n.line in (7, 8)}
    assert leak_paths(cfg, acq.idx, rel) == ["an exception path"]

    fn, cfg = _cfg_of(
        """
        def f():
            h = acquire()
            try:
                use(h)
            except Exception:
                h.release()
            else:
                h.release()
        """
    )
    acq = next(n for n in cfg.stmt_nodes() if n.line == 3)
    rel = {n.idx for n in cfg.stmt_nodes() if n.line in (7, 9)}
    assert leak_paths(cfg, acq.idx, rel) == []


def test_cfg_return_through_finally_and_with_exit():
    """A return inside try/finally traverses the finally; a with-exit
    does not invent a path straight to the function exit."""
    from tools.auronlint.cfg import leak_paths

    fn, cfg = _cfg_of(
        """
        def f():
            h = acquire()
            with lock:
                use(h)
            h.release()
            return 1
        """
    )
    acq = next(n for n in cfg.stmt_nodes() if n.line == 3)
    rel = {n.idx for n in cfg.stmt_nodes() if n.line == 6}
    # the with body can raise -> exception leak; but NO normal-path leak
    # through the with-exit (the split-exit-node property)
    assert leak_paths(cfg, acq.idx, rel) == ["an exception path"]


# ---------------------------------------------------------------------------
# R11 resource lifecycle
# ---------------------------------------------------------------------------


def _r11(src: str, rel: str = "fixture.py"):
    from tools.auronlint.rules.lifecycle import ResourceLifecycleRule

    return _lint(src, ResourceLifecycleRule(), rel)


def test_r11_rediscovers_pr12_taskruntime_leak_shape():
    """The exact pre-fix PR-12 collect drain: a failing next_batch leaks
    the runtime (handle + pump thread). R11 must find it."""
    rep = _r11(
        """
        from auron_tpu.bridge import api

        def _execute(task_bytes):
            h = api.call_native(task_bytes)
            dfs = []
            while (rb := api.next_batch(h)) is not None:
                dfs.append(rb.to_pandas())
            api.finalize_native(h)
            return dfs
        """
    )
    hits = _hits(rep, "R11")
    assert len(hits) == 1
    assert "task runtime" in hits[0].message
    assert "an exception path" in hits[0].message


def test_r11_quiet_on_pr12_fixed_shape_and_context_manager():
    """The post-fix shape (finalize in the except unwind) and the
    native_task context manager are both clean."""
    rep = _r11(
        """
        from auron_tpu.bridge import api

        def _execute(task_bytes):
            h = api.call_native(task_bytes)
            dfs = []
            try:
                while (rb := api.next_batch(h)) is not None:
                    dfs.append(rb.to_pandas())
            except BaseException:
                try:
                    api.finalize_native(h)
                except Exception:
                    pass
                raise
            api.finalize_native(h)
            return dfs

        def _execute2(task_bytes):
            out = []
            with api.native_task(task_bytes) as h:
                while (rb := api.next_batch(h)) is not None:
                    out.append(rb)
            return out
        """
    )
    assert not _hits(rep, "R11")


def test_r11_spill_container_fire_and_fixed():
    rep = _r11(
        """
        from auron_tpu.memory.memmgr import make_spill

        def park(self, tbl):
            ds = make_spill(conf=self.conf)
            ds.write_table(tbl)
            self.parked.append(ds)
        """
    )
    hits = _hits(rep, "R11")
    assert len(hits) == 1 and "spill container" in hits[0].message

    rep = _r11(
        """
        from auron_tpu.memory.memmgr import make_spill

        def park(self, tbl):
            ds = make_spill(conf=self.conf)
            try:
                ds.write_table(tbl)
            except BaseException:
                ds.release()
                raise
            self.parked.append(ds)
        """
    )
    assert not _hits(rep, "R11")


def test_r11_mm_registration_fire_and_fixed():
    """register() before the protecting try leaks on a setup failure —
    the agg_exec shape this PR fixed."""
    rep = _r11(
        """
        def _execute(self, ctx):
            mm = get_manager()
            table = TableConsumer(self, ctx)
            mm.register(table)
            win = TransferWindow(ctx.conf)
            try:
                for b in stream():
                    table.add(b)
            finally:
                mm.unregister(table)
        """
    )
    hits = _hits(rep, "R11")
    assert len(hits) == 1 and "register -> unregister" in hits[0].message

    rep = _r11(
        """
        def _execute(self, ctx):
            mm = get_manager()
            table = TableConsumer(self, ctx)
            win = TransferWindow(ctx.conf)
            try:
                mm.register(table)
                for b in stream():
                    table.add(b)
            finally:
                mm.unregister(table)
        """
    )
    assert not _hits(rep, "R11")


def test_r11_conditional_release_idiom_is_quiet():
    """`if guard is not None: mm.unregister(guard)` in the finally is
    the dynamic ownership check — not a leak path around the release."""
    rep = _r11(
        """
        def _execute(self, ctx):
            mm = get_manager()
            guard = None
            try:
                build = self._build(ctx)
                guard = BuildGuard(self, build)
                mm.register(guard, spillable=False)
                for b in stream():
                    probe(build, b)
            finally:
                if guard is not None:
                    mm.unregister(guard)
        """
    )
    assert not _hits(rep, "R11")


def test_r11_inflight_event_stuck_waiter_fire_and_fixed():
    """The PR-12 upload-event class: a builder that fails before set()
    wedges every waiter. Storing the event does NOT transfer ownership;
    waiting on it proves the waiter side."""
    rep = _r11(
        """
        import threading

        def _table_view(self, key):
            with self._res_lock:
                ent = self._res_cache.get(key)
                if ent is None:
                    ent = self._res_cache[key] = {"done": threading.Event(), "val": None}
                    builder = True
                else:
                    builder = False
            if builder:
                ent["val"] = self._build(key)
                ent["done"].set()
                return ent["val"]
            ent["done"].wait()
            return ent["val"]
        """
    )
    hits = _hits(rep, "R11")
    assert len(hits) == 1 and "in-flight event" in hits[0].message

    rep = _r11(
        """
        import threading

        def _table_view(self, key):
            with self._res_lock:
                ent = self._res_cache.get(key)
                if ent is None:
                    ent = self._res_cache[key] = {"done": threading.Event(), "val": None}
                    builder = True
                else:
                    builder = False
            if builder:
                try:
                    ent["val"] = self._build(key)
                finally:
                    ent["done"].set()
                return ent["val"]
            ent["done"].wait()
            return ent["val"]
        """
    )
    assert not _hits(rep, "R11")


def test_r11_owned_by_declaration_suppresses_with_reason():
    rep = _r11(
        """
        from auron_tpu.memory.memmgr import make_spill

        def park(self, tbl):
            ds = make_spill(conf=self.conf)  # auronlint: owned-by(self.parked) -- drained and released by drain()
            ds.write_table(tbl)
            self.parked.append(ds)
        """
    )
    assert not _hits(rep, "R11")
    (sup,) = _suppressed(rep, "R11")
    assert "drained and released" in sup.reason


def test_r11_owned_by_requires_holder_argument():
    rep = _r11(
        """
        from auron_tpu.memory.memmgr import make_spill

        def park(self, tbl):
            ds = make_spill(conf=self.conf)  # auronlint: owned-by -- someone releases it
            ds.write_table(tbl)
        """
    )
    assert [f for f in rep.findings if f.rule == "lint.suppression"]


def test_r11_normal_path_leak_reported():
    """A release only in the except arm misses the normal path."""
    rep = _r11(
        """
        from auron_tpu.memory.memmgr import make_spill

        def park(self, tbl):
            ds = make_spill(conf=self.conf)
            try:
                ds.write_table(tbl)
            except Exception:
                ds.release()
                raise
            return None
        """
    )
    hits = _hits(rep, "R11")
    assert len(hits) == 1 and "a normal path" in hits[0].message


def test_r11_transfers_end_tracking():
    """Returning, yielding, storing and with-managing all hand the
    resource off — no finding."""
    rep = _r11(
        """
        from auron_tpu.memory.memmgr import make_spill
        from auron_tpu import obs

        def make(self):
            ds = make_spill(conf=self.conf)
            return ds

        def stash(self):
            ds = make_spill(conf=self.conf)
            self._spill = ds

        def managed(self):
            sp = obs.span("x")
            with sp:
                work()
        """
    )
    assert not _hits(rep, "R11")


def test_r11_snapshot_temp_fire():
    """A checkpoint temp created but neither published (os.replace) nor
    torn down (os.unlink) on the exception path is a half-written file a
    future restore could mistake for progress."""
    rep = _r11(
        """
        import os
        from auron_tpu.stream.checkpoint import snapshot_tmp

        def write_one(final, data):
            tmp = snapshot_tmp(final)
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, final)
        """
    )
    hits = _hits(rep, "R11")
    assert len(hits) == 1
    assert "checkpoint temp file" in hits[0].message


def test_r11_snapshot_temp_quiet_on_replace_or_unlink_unwind():
    """The shipped shape — publish on success, unlink on the unwind —
    releases the temp on every path."""
    rep = _r11(
        """
        import os
        from auron_tpu.stream.checkpoint import snapshot_tmp

        def write_one(final, data):
            tmp = snapshot_tmp(final)
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, final)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        """
    )
    assert not _hits(rep, "R11")


# ---------------------------------------------------------------------------
# R12 error-path discipline
# ---------------------------------------------------------------------------


def _r12(sources: dict):
    from tools.auronlint.rules.errorpath import analyze

    return list(analyze(_graph(sources)))


def test_r12_fires_on_swallowed_broad_in_foreign_reachable():
    finds = _r12({
        "pkg/svc.py": """
        class Svc:
            def handle(self):  # auronlint: thread-root(foreign) -- test fixture
                self.work()

            def work(self):
                try:
                    step()
                except Exception:
                    pass
        """,
    })
    assert len([f for f in finds if "swallowed" in f[2]]) == 1


def test_r12_narrow_swallow_and_unreachable_are_quiet():
    finds = _r12({
        "pkg/svc.py": """
        class Svc:
            def handle(self):  # auronlint: thread-root(foreign) -- test fixture
                self.work()

            def work(self):
                try:
                    self.sock.close()
                except OSError:
                    pass

        def unreachable_helper():
            try:
                step()
            except Exception:
                pass
        """,
    })
    assert not [f for f in finds if "swallowed" in f[2]]


def test_r12_thread_target_escape_fire_and_routed():
    finds = _r12({
        "pkg/daemon.py": """
        import threading

        class Daemon:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                while self.running():
                    self.step()
        """,
    })
    assert len([f for f in finds if "kills its thread" in f[2]]) == 1

    finds = _r12({
        "pkg/daemon.py": """
        import threading

        class Daemon:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                try:
                    while self.running():
                        self.step()
                except BaseException as e:
                    self._error = e
        """,
    })
    assert not [f for f in finds if "kills its thread" in f[2]]


def test_r12_http_handler_entry_checked():
    finds = _r12({
        "pkg/http.py": """
        from http.server import BaseHTTPRequestHandler

        class H(BaseHTTPRequestHandler):
            def do_GET(self):
                payload = self.render()
                self.wfile.write(payload)
        """,
    })
    assert len([f for f in finds if "handler entry" in f[2]]) == 1


def test_r12_manual_lock_release_skipped_on_raise():
    finds = _r12({
        "pkg/locky.py": """
        class T:
            def handle(self):  # auronlint: thread-root(foreign) -- test fixture
                self.work()

            def work(self):
                self._lock.acquire()
                step()
                self._lock.release()
        """,
    })
    assert len([f for f in finds if "not released" in f[2]]) == 1

    finds = _r12({
        "pkg/locky.py": """
        class T:
            def handle(self):  # auronlint: thread-root(foreign) -- test fixture
                self.work()

            def work(self):
                self._lock.acquire()
                try:
                    step()
                finally:
                    self._lock.release()
        """,
    })
    assert not [f for f in finds if "not released" in f[2]]


def test_r12_annotated_swallow_rides_suppression():
    """A reasoned disable=R12 keeps the deliberate swallow out of the
    failing set (and in the ratchet's suppressed counts)."""
    from tools.auronlint.core import SourceModule, lint_paths
    import os as _os
    import tempfile as _tf

    src = textwrap.dedent("""
        class Svc:
            def handle(self):  # auronlint: thread-root(foreign) -- test fixture
                self.work()

            def work(self):
                try:
                    step()
                except Exception:  # auronlint: disable=R12 -- probe isolation: fallthrough is the contract
                    pass
    """)
    with _tf.TemporaryDirectory() as td:
        pkg = _os.path.join(td, "auron_tpu")
        _os.makedirs(pkg)
        path = _os.path.join(pkg, "svc.py")
        with open(path, "w") as f:
            f.write(src)
        from tools.auronlint.rules.errorpath import ErrorPathRule

        rep = lint_paths([pkg], td, [ErrorPathRule()])
        assert not [f for f in rep.unsuppressed if f.rule == "R12"]
        assert [f for f in rep.suppressed if f.rule == "R12"]


# ---------------------------------------------------------------------------
# R13 retrace stability
# ---------------------------------------------------------------------------


def _r13(sources: dict):
    from tools.auronlint.rules.retracestab import analyze

    return analyze(_graph(sources))


def test_r13_fires_on_lambda_float_rowcount_and_identity_keys():
    finds, stats = _r13({
        "pkg/kern.py": """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("emit", "scale", "n", "cfg"))
        def prog(dev, *, emit, scale, n, cfg):
            return dev
        """,
        "pkg/use.py": """
        from pkg.kern import prog

        class Driver:
            def run(self, b):
                return prog(b.device, emit=lambda x: x, scale=0.5,
                            n=b.num_rows(), cfg=FreshConfig())
        """,
    })
    msgs = " | ".join(m for _, _, m in finds)
    assert "lambda" in msgs
    assert "float literal" in msgs
    assert "row count" in msgs
    assert "per-call object identity" in msgs
    assert stats["proved"] == 0 and stats["covered"] == 1


def test_r13_finite_keys_prove_and_shape_only_entries_count():
    finds, stats = _r13({
        "pkg/kern.py": """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("steps", "bucket", "flags"))
        def prog(dev, *, steps, bucket, flags):
            return dev

        @jax.jit
        def shape_only(dev):
            return dev
        """,
        "pkg/use.py": """
        from pkg.kern import prog

        def run(b, conf):
            steps = tuple(sig for sig in b.schema)
            return prog(b.device, steps=steps,
                        bucket=compaction_bucket(b.capacity),
                        flags=conf.get("exec.knob"))
        """,
    })
    assert not finds
    assert stats["covered"] == 2 and stats["proved"] == 2


def test_r13_closure_over_rebound_module_state_fires():
    finds, stats = _r13({
        "pkg/kern.py": """
        import jax

        _MODE = "a"
        _MODE = "b"

        @jax.jit
        def prog(dev):
            return dev if _MODE == "a" else dev + 1
        """,
    })
    assert len([m for _, _, m in finds if "rebound" in m]) == 1
    assert stats["proved"] == 0


def test_r13_live_tree_coverage_and_floors():
    """Vacuity teeth: the analysis must see every module-level jit entry
    in plan/fusion.py and exec/ that an independent AST scan finds, and
    the proved floor must hold on the live tree."""
    import ast as _ast

    from tools.auronlint.callgraph import build_graph
    from tools.auronlint.rules.retracestab import (
        R13_MIN_COVERED, R13_MIN_PROVED, _JIT_RE, analyze,
    )

    finds, stats = analyze(build_graph(REPO_ROOT))
    assert stats["covered"] >= R13_MIN_COVERED
    assert stats["proved"] >= R13_MIN_PROVED

    # independent discovery: decorated module-level defs + module-level
    # jit-wrapped assigns under plan/fusion.py and exec/
    expected = set()
    for rel in list(stats["entries"]):
        pass
    import os as _os

    for base, _, files in _os.walk(_os.path.join(REPO_ROOT, "auron_tpu")):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = _os.path.join(base, fname)
            rel = _os.path.relpath(path, REPO_ROOT).replace("\\", "/")
            if not (rel == "auron_tpu/plan/fusion.py"
                    or rel.startswith("auron_tpu/exec/")):
                continue
            tree = _ast.parse(open(path).read())
            for node in tree.body:
                if isinstance(node, _ast.FunctionDef) and any(
                    _JIT_RE.search(_ast.unparse(d))
                    for d in node.decorator_list
                ):
                    expected.add(f"{rel}::{node.name}")
                elif isinstance(node, _ast.Assign) and isinstance(
                    node.value, _ast.Call
                ) and _JIT_RE.search(_ast.unparse(node.value.func)) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], _ast.Name):
                    expected.add(f"{rel}::{node.targets[0].id}")
    assert expected, "independent scan found no jit entries — scan broken"
    missing = expected - set(stats["entries"])
    assert not missing, f"R13 lost sight of jit entries: {sorted(missing)}"


def test_r13_vacuity_floor_fails_loudly(monkeypatch):
    from tools.auronlint.rules import retracestab

    rule = retracestab.RetraceStabilityRule()
    monkeypatch.setattr(retracestab, "R13_MIN_COVERED", 10_000)
    finds = list(rule.check_tree(REPO_ROOT))
    assert any("vacuity" in m for _, _, m in finds)


# ---------------------------------------------------------------------------
# incremental parse/summary cache (tools/auronlint/filecache.py)
# ---------------------------------------------------------------------------

_CACHE_FIXTURE = """
import jax.numpy as jnp

def f(xs):
    s = jnp.sum(xs)
    return s.item()
"""


def _fresh_cache(root):
    """A FileCache as a NEW process would see it: drop the in-process
    instance so the next lookup must come from disk."""
    from tools.auronlint import filecache

    filecache._caches.pop(root, None)
    return filecache.file_cache(root)


def test_filecache_warm_run_replays_identical_findings(tmp_path):
    from tools.auronlint import filecache

    root = str(tmp_path)
    pkg = tmp_path / "auron_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(_CACHE_FIXTURE))
    cold = run_tree(root)
    assert _hits(cold, "R1"), "fixture should fire R1"
    assert os.path.exists(os.path.join(root, filecache.CACHE_BASENAME))
    fc = _fresh_cache(root)
    warm = run_tree(root)
    assert fc.hits >= 1 and fc.misses == 0
    assert warm.to_json() == cold.to_json()


def test_filecache_invalidates_on_file_edit(tmp_path):
    root = str(tmp_path)
    pkg = tmp_path / "auron_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(_CACHE_FIXTURE))
    cold = run_tree(root)
    assert len(_hits(cold, "R1")) == 1
    # the edit adds a second violation; a stale cache would still say 1
    (pkg / "mod.py").write_text(textwrap.dedent(_CACHE_FIXTURE) + textwrap.dedent("""
def g(xs):
    return jnp.max(xs).item()
"""))
    _fresh_cache(root)
    warm = run_tree(root)
    assert len(_hits(warm, "R1")) == 2


def test_filecache_invalidates_on_mid_process_rewrite(tmp_path):
    """The in-process memo must re-validate signatures too: a fixture
    tree rewritten between two run_tree calls in ONE process (exactly
    what this test does) must not serve stale summaries."""
    root = str(tmp_path)
    pkg = tmp_path / "auron_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(_CACHE_FIXTURE))
    assert len(_hits(run_tree(root), "R1")) == 1
    (pkg / "mod.py").write_text(
        "def clean():\n    return 1\n")
    assert len(_hits(run_tree(root), "R1")) == 0


def test_filecache_invalidates_on_linter_source_change(tmp_path, monkeypatch):
    from tools.auronlint import filecache

    root = str(tmp_path)
    pkg = tmp_path / "auron_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(_CACHE_FIXTURE))
    run_tree(root)
    # a rule edit changes the package digest: every entry must go cold
    monkeypatch.setattr(filecache, "_tools_digest", lambda: "rule-edited")
    fc = _fresh_cache(root)
    run_tree(root)
    assert fc.hits == 0 and fc.misses >= 1


def test_filecache_corruption_and_disable_are_nonfatal(tmp_path, monkeypatch):
    from tools.auronlint import filecache

    root = str(tmp_path)
    pkg = tmp_path / "auron_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(_CACHE_FIXTURE))
    cache_path = tmp_path / filecache.CACHE_BASENAME
    cache_path.write_bytes(b"\x80garbage, not a pickle")
    _fresh_cache(root)
    rep = run_tree(root)  # advisory: corruption = cold run, not a crash
    assert len(_hits(rep, "R1")) == 1
    # temp + os.replace left no partial files behind
    strays = [p for p in os.listdir(root)
              if p.startswith(filecache.CACHE_BASENAME + ".")]
    assert not strays
    # and the rewritten cache is loadable again
    fc = _fresh_cache(root)
    run_tree(root)
    assert fc.hits >= 1

    other = tmp_path / "disabled"
    (other / "auron_tpu").mkdir(parents=True)
    (other / "auron_tpu" / "mod.py").write_text(
        textwrap.dedent(_CACHE_FIXTURE))
    monkeypatch.setenv("AURONLINT_CACHE", "0")
    rep = run_tree(str(other))
    assert len(_hits(rep, "R1")) == 1
    assert not os.path.exists(other / filecache.CACHE_BASENAME)


def test_sarif_out_artifact_and_time_budget(tmp_path, capsys):
    from tools.auronlint.__main__ import main

    target = os.path.join(REPO_ROOT, "auron_tpu", "utils", "httpsvc.py")
    out = tmp_path / "artifacts" / "lint.sarif"  # dir must be created
    assert main([target, "--sarif-out", str(out)]) == 0
    data = json.loads(out.read_text())
    assert data["version"] == "2.1.0"
    capsys.readouterr()

    # a zero budget always trips: exit 1, loud stderr, artifact STILL
    # written (CI wants the report most when the gate fails)
    out2 = tmp_path / "b.sarif"
    assert main([target, "--sarif-out", str(out2), "--time-budget", "0"]) == 1
    assert json.loads(out2.read_text())["version"] == "2.1.0"
    assert "exceeded the --time-budget" in capsys.readouterr().err
    strays = [p for p in os.listdir(tmp_path) if p.startswith("b.sarif.")]
    assert not strays  # temp + os.replace left nothing behind


# ---------------------------------------------------------------------------
# R14 config-knob contract
# ---------------------------------------------------------------------------


_R14_CONFIG = """
def str_conf(key, default=None, doc=""):
    return (key, default, doc)

def resolve_tri(mode, auto):
    if mode == "on":
        return True
    if mode == "off":
        return False
    return auto

FUSE_MODE = str_conf("exec.fuse.mode", "auto",
                     doc="on | off | auto = on when compacting")
PARTS = str_conf("sql.parts", "8", doc="partition count")
DEAD = str_conf("sql.dead", "x", doc="declared, read by nobody")
"""


def _r14(sources: dict):
    from tools.auronlint.rules.confcontract import analyze

    return analyze(
        _graph(sources),
        anchor_rels=("pkg/lowering.py",),
        digest_rel="pkg/digest.py",
    )


def test_r14_fires_on_raw_get_dead_knob_and_tri_bypass():
    finds, stats = _r14({
        "pkg/config.py": _R14_CONFIG,
        "pkg/digest.py": """
        from pkg.config import PARTS

        PLAN_KNOBS = (PARTS,)
        """,
        "pkg/lowering.py": """
        from pkg.config import FUSE_MODE, PARTS

        def lower(conf):
            legacy = conf.get("sql.raw.key")
            mode = conf.get(FUSE_MODE)
            if mode == "off":
                return None
            return conf.get(PARTS)
        """,
    })
    msgs = " | ".join(m for _, _, m in finds)
    assert "raw-string conf read conf.get('sql.raw.key')" in msgs
    assert "knob DEAD ('sql.dead') is declared but never read" in msgs
    assert "tri-state knob FUSE_MODE read without resolve_tri" in msgs
    # the teeth: FUSE_MODE is read during lowering but not cache-keyed
    assert "plan-affecting knob FUSE_MODE" in msgs
    assert "MISSING from sql/digest.py PLAN_KNOBS" in msgs
    assert stats["declared"] == 3 and stats["tri"] == 1
    assert stats["plan_proved"] == 1  # PARTS is keyed; FUSE_MODE is not


def test_r14_contract_clean_when_keyed_and_resolved():
    finds, stats = _r14({
        "pkg/config.py": _R14_CONFIG.replace(
            'DEAD = str_conf("sql.dead", "x", doc="declared, read by nobody")\n',
            "",
        ),
        "pkg/digest.py": """
        from pkg.config import FUSE_MODE, PARTS

        PLAN_KNOBS = (PARTS, FUSE_MODE)
        """,
        "pkg/lowering.py": """
        from pkg.config import FUSE_MODE, PARTS, resolve_tri

        def lower(conf):
            fuse = resolve_tri(conf.get(FUSE_MODE), True)
            parts = conf.get(PARTS)
            return parts if fuse else None
        """,
    })
    assert finds == []
    assert stats["plan_proved"] == 2


def test_r14_knob_object_passed_to_helper_still_counts_as_plan_read():
    """The knob need not feed conf.get() in the anchor module itself —
    loading the knob OBJECT inside the closure (passing it down to a
    helper that reads it) is the same contract obligation."""
    finds, _stats = _r14({
        "pkg/config.py": _R14_CONFIG.replace(
            'DEAD = str_conf("sql.dead", "x", doc="declared, read by nobody")\n',
            "",
        ),
        "pkg/digest.py": """
        from pkg.config import PARTS

        PLAN_KNOBS = (PARTS,)
        """,
        "pkg/helper.py": """
        def read_knob(conf, knob):
            return conf.get(knob)
        """,
        "pkg/lowering.py": """
        from pkg.config import FUSE_MODE, PARTS, resolve_tri
        from pkg.helper import read_knob

        def lower(conf):
            fuse = resolve_tri(read_knob(conf, FUSE_MODE), True)
            return read_knob(conf, PARTS) if fuse else None
        """,
    })
    assert any("plan-affecting knob FUSE_MODE" in m for _, _, m in finds)


def test_r14_declaration_suppression_honored_in_tree(tmp_path):
    """Reference-parity debt: a reasoned disable=R14 on the declaration
    line keeps a never-read knob out of the gate (and in the ratchet)."""
    from tools.auronlint.core import lint_paths
    from tools.auronlint.rules.confcontract import ConfContractRule

    at = tmp_path / "auron_tpu" / "utils"
    at.mkdir(parents=True)
    (tmp_path / "auron_tpu" / "__init__.py").write_text("")
    (at / "config.py").write_text(textwrap.dedent("""
        def str_conf(key, default=None, doc=""):
            return (key, default, doc)

        PARITY = str_conf("upstream.parity.knob", "x")  # auronlint: disable=R14 -- upstream-parity surface, fixture
        LOUD = str_conf("dead.loud.knob", "y")
    """))
    rep = lint_paths([os.path.join(str(tmp_path), "auron_tpu")],
                     str(tmp_path), [ConfContractRule()])
    dead = [f for f in rep.findings if "declared but never read" in f.message]
    assert {f.suppressed for f in dead} == {True, False}
    sup = next(f for f in dead if f.suppressed)
    assert "PARITY" in sup.message and "upstream-parity" in (sup.reason or "")


def test_r14_vacuity_floors_fail_loudly(monkeypatch):
    from tools.auronlint.rules import confcontract

    rule = confcontract.ConfContractRule()
    monkeypatch.setattr(confcontract, "R14_MIN_DECLARED", 10_000)
    finds = list(rule.check_tree(REPO_ROOT))
    assert any("R14 vacuity check" in m for _, _, m in finds)

    rule2 = confcontract.ConfContractRule()
    monkeypatch.setattr(confcontract, "R14_MIN_DECLARED", 1)
    monkeypatch.setattr(confcontract, "R14_MIN_PLAN_PROVED", 10_000)
    finds2 = list(rule2.check_tree(REPO_ROOT))
    assert any("plan-path knobs proved" in m for _, _, m in finds2)


def test_r14_live_tree_proves_fuse_knobs_into_plan_knobs():
    """The serving-cache teeth on the real tree: the closure from
    lowering/fusion must reach the fuse family and prove every
    plan-affecting knob into PLAN_KNOBS (this PR's live findings — the
    FUSE_*/HOST_SORT_MODE cache-split bugs — stay fixed)."""
    from tools.auronlint.callgraph import build_graph
    from tools.auronlint.rules.confcontract import (
        R14_MIN_DECLARED, R14_MIN_PLAN_PROVED, analyze,
    )

    _finds, stats = analyze(build_graph(REPO_ROOT))
    assert stats["declared"] >= R14_MIN_DECLARED
    assert stats["plan_proved"] >= R14_MIN_PLAN_PROVED
    assert {"FUSE_ENABLE", "HOST_SORT_MODE"} <= set(stats["plan_read"])
    assert set(stats["plan_read"]) <= set(stats["plan_knobs"])


def test_config_doc_drift_gate_detects_stale_doc(monkeypatch, tmp_path):
    """The generated-artifact gate: byte-level doc drift is a finding,
    and the clean regen is drift-free."""
    from tools.auronlint.rules.confcontract import config_doc_drift
    from tools.gen_config_doc import regenerate

    assert list(config_doc_drift(REPO_ROOT)) == []

    doc = os.path.join(REPO_ROOT, "docs", "CONFIG.md")
    with open(doc, encoding="utf-8") as fh:
        original = fh.read()
    try:
        with open(doc, "a", encoding="utf-8") as fh:
            fh.write("| fake.knob | x | drift |\n")
        finds = list(config_doc_drift(REPO_ROOT))
        assert any("stale" in m for _, _, m in finds)
    finally:
        with open(doc, "w", encoding="utf-8") as fh:
            fh.write(original)
    # regenerate() is idempotent on a clean tree
    regenerate()
    with open(doc, encoding="utf-8") as fh:
        assert fh.read() == original


# ---------------------------------------------------------------------------
# R15 FFI/ABI lockstep
# ---------------------------------------------------------------------------


_MINI_NATIVE_CPP = """
#include <cstdint>

extern "C" {

static int32_t private_helper(int32_t a) { return a; }

int32_t add_i32(const int32_t* xs, int64_t n) { return 0; }

void scale_f64(double* xs, int64_t n, double f) { }

uint64_t helper_sym(int32_t a) { return 0; }

}  // extern "C"
"""

_MINI_NATIVE_PY_DRIFTED = """
import ctypes


def _bind(lib):
    lib.add_i32.argtypes = [ctypes.POINTER(ctypes.c_int32)]
    lib.add_i32.restype = ctypes.c_int32
    lib.scale_f64.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_double,
    ]
    lib.gone_sym.argtypes = [ctypes.c_int32]
    lib.gone_sym.restype = ctypes.c_int32


def add_i32_host(xs):
    return 0


def scale_f64_host(xs, f):
    return xs
"""

_MINI_NATIVE_PY_OK = """
import ctypes

# auronlint: unbound-native(helper_sym) -- fixture: debug-only export, no engine caller


def _bind(lib):
    lib.add_i32.argtypes = [ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
    lib.add_i32.restype = ctypes.c_int32
    lib.scale_f64.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_double,
    ]
    lib.scale_f64.restype = None


def add_i32_host(xs):
    return 0


def scale_f64_host(xs, f):
    return xs
"""


def _write_native_tree(tmp_path, py_src, cpp_src=_MINI_NATIVE_CPP):
    (tmp_path / "native").mkdir()
    (tmp_path / "auron_tpu").mkdir()
    (tmp_path / "native" / "auron_native.cpp").write_text(cpp_src)
    (tmp_path / "auron_tpu" / "native.py").write_text(py_src)
    return str(tmp_path)


def _r15(root):
    from tools.auronlint.rules.ffilockstep import analyze

    return analyze(root)


def test_r15_fires_on_arity_restype_unbound_and_stale(tmp_path):
    finds, stats = _r15(_write_native_tree(tmp_path, _MINI_NATIVE_PY_DRIFTED))
    msgs = " | ".join(m for _, _, m in finds)
    assert "add_i32.argtypes has 1 entries but the C signature" in msgs
    assert "scale_f64 binding has no explicit restype" in msgs
    assert "exported native symbol helper_sym" in msgs
    assert "binds symbol gone_sym" in msgs
    assert "helper_sym has no numpy twin" in msgs
    # static functions are not exports; the parser saw the 3 real ones
    assert stats["exports"] == 3


def test_r15_clean_boundary_with_unbound_declaration(tmp_path):
    finds, stats = _r15(_write_native_tree(tmp_path, _MINI_NATIVE_PY_OK))
    assert finds == []
    assert stats["exports"] == 3 and stats["bound"] == 2
    assert ("add_i32", "add_i32_host") in stats["pairs"]


def test_r15_width_mismatch_and_stale_unbound_fire(tmp_path):
    drifted = _MINI_NATIVE_PY_OK.replace(
        "ctypes.c_int64]", "ctypes.c_int32]"
    ).replace(
        "unbound-native(helper_sym)", "unbound-native(add_i32)"
    )
    finds, _stats = _r15(_write_native_tree(tmp_path, drifted))
    msgs = " | ".join(m for _, _, m in finds)
    assert "add_i32.argtypes[1] is ctypes.c_int32" in msgs
    assert "unbound-native(add_i32) declaration is stale" in msgs
    assert "helper_sym" in msgs  # lost its declaration -> unbound again


def test_r15_vacuity_floor_fails_loudly(monkeypatch):
    from tools.auronlint.rules import ffilockstep

    rule = ffilockstep.FfiLockstepRule()
    monkeypatch.setattr(ffilockstep, "R15_MIN_TWINS", 10_000)
    finds = list(rule.check_tree(REPO_ROOT))
    assert any("R15 vacuity check" in m for _, _, m in finds)


def test_r15_live_tree_bindings_in_lockstep():
    finds, stats = _r15(REPO_ROOT)
    assert finds == [], "\n".join(m for _, _, m in finds)
    from tools.auronlint.rules.ffilockstep import (
        R15_MIN_BOUND, R15_MIN_BRIDGE_DECLS, R15_MIN_EXPORTS, R15_MIN_TWINS,
    )

    assert stats["exports"] >= R15_MIN_EXPORTS
    assert stats["bound"] >= R15_MIN_BOUND
    assert stats["bridge_decls"] >= R15_MIN_BRIDGE_DECLS
    assert stats["twins"] >= R15_MIN_TWINS


# ---------------------------------------------------------------------------
# R16 determinism taint
# ---------------------------------------------------------------------------


def _r16(sources: dict, anchors=("pkg/digest.py",), funcs=None):
    from tools.auronlint.rules.determinism import analyze

    return analyze(_graph(sources), anchor_rels=anchors,
                   anchor_funcs=funcs or {})


def test_r16_fires_on_set_dict_clock_and_id():
    finds, stats = _r16({
        "pkg/digest.py": """
        import time

        def digest(parts, opts):
            tags = {p.name for p in parts}
            body = ",".join(tags)
            for k, v in opts.items():
                body += k
            return body + str(time.time()) + str(id(opts))
        """,
    })
    msgs = " | ".join(m for _, _, m in finds)
    assert "set iterated into a join" in msgs
    assert "unsorted .items() iterated into a for loop" in msgs
    assert "wall-clock read time.time()" in msgs
    assert "id() on a digest-reachable path" in msgs
    assert stats["covered"] == 1


def test_r16_closure_scans_callees_but_not_unreachable_code():
    finds, stats = _r16({
        "pkg/digest.py": """
        from pkg.canon import canon

        def digest(parts, opts):
            tags = sorted({p.name for p in parts})
            body = ",".join(tags)
            for k, v in sorted(opts.items()):
                body += canon(k)
            return body
        """,
        "pkg/canon.py": """
        import time

        def canon(s):
            return s.lower()

        def untainted_elsewhere():
            return time.time()
        """,
    })
    assert finds == []  # sorted() wrappers pass; unreachable clock passes
    assert stats["covered"] == 2  # digest + canon, NOT untainted_elsewhere


def test_r16_entropy_env_and_uuid_fire_through_closure():
    finds, _stats = _r16({
        "pkg/digest.py": """
        from pkg.helper import salt

        def digest(parts):
            return salt() + len(parts)
        """,
        "pkg/helper.py": """
        import os
        import random
        import uuid

        def salt():
            a = random.random()
            b = uuid.uuid4()
            c = os.environ["HOME"]
            d = os.getenv("USER")
            return hash((a, b, c, d))
        """,
    })
    msgs = " | ".join(m for _, _, m in finds)
    assert "entropy read random()" in msgs
    assert "uuid.uuid4()" in msgs
    assert "os.environ read" in msgs
    assert "os.getenv()" in msgs


def test_r16_nondeterministic_declaration_suppresses_in_tree(tmp_path):
    """The dedicated R16 declaration: a reasoned ``nondeterministic``
    annotation keeps a sanctioned site out of the gate; an unannotated
    one still fires."""
    from tools.auronlint.core import lint_paths
    from tools.auronlint.rules.determinism import DeterminismRule

    at = tmp_path / "auron_tpu" / "sql"
    at.mkdir(parents=True)
    (at / "digest.py").write_text(textwrap.dedent("""
        def digest(parts):
            tags = {p for p in parts}
            return ",".join(tags)  # auronlint: nondeterministic -- fixture: caller folds with XOR, order-free

        def digest2(parts):
            tags = {p for p in parts}
            return ";".join(tags)
    """))
    rep = lint_paths([os.path.join(str(tmp_path), "auron_tpu")],
                     str(tmp_path), [DeterminismRule()])
    joins = [f for f in rep.findings if "set iterated" in f.message]
    assert {f.suppressed for f in joins} == {True, False}
    assert next(f for f in joins if f.suppressed).reason


def test_r16_vacuity_floor_fails_loudly(monkeypatch):
    from tools.auronlint.rules import determinism

    rule = determinism.DeterminismRule()
    monkeypatch.setattr(determinism, "R16_MIN_COVERED", 10_000)
    finds = list(rule.check_tree(REPO_ROOT))
    assert any("R16 vacuity check" in m for _, _, m in finds)


def test_r16_live_tree_closure_meets_floor():
    from tools.auronlint.callgraph import build_graph
    from tools.auronlint.rules.determinism import R16_MIN_COVERED, analyze

    _finds, stats = analyze(build_graph(REPO_ROOT))
    assert stats["covered"] >= R16_MIN_COVERED
    assert "auron_tpu/sql/digest.py" in stats["rels"]
    assert "auron_tpu/plan/builders.py" in stats["rels"]


def test_unbound_native_and_nondeterministic_route_to_their_rules():
    """Declaration routing: the dedicated R15/R16 annotations suppress
    ONLY their rule — a disable they are not must not leak across."""
    from tools.auronlint.core import SourceModule

    src = textwrap.dedent("""
        x = 1  # auronlint: unbound-native(foo_sym) -- dormant export
        y = 2  # auronlint: nondeterministic -- order folded away
    """)
    mod = SourceModule("f.py", "f.py", src)
    assert mod.suppression_for("R15", 2) is not None
    assert mod.suppression_for("R16", 2) is None
    assert mod.suppression_for("R16", 3) is not None
    assert mod.suppression_for("R15", 3) is None
    assert mod.suppression_for("R1", 3) is None
