"""R13 — retrace stability: jit cache keys come from finite sets.

The serving path's zero-compile guarantee (make perfcheck's replay
guards, make servegate's cached legs) rests on one precondition: every
module-level jit entry's cache key — its static arguments plus whatever
its closure captures — is drawn from a FINITE, enumerable set (schema
tuples, capacity buckets, tri-state knob resolutions). perfcheck proves
it dynamically for the classes it replays; R13 proves it statically for
the WHOLE tree, the same generalization SystemML makes for fusion-plan
validity (PAPERS.md 1801.00829): check the precondition, not the replay.

Per module-level jit entry (decorated def or ``name = jax.jit(fn)`` at
module top level), over every call site the package call graph resolves:

- **finite** key components pass: literal bool/int/str, tuples of the
  same, schema/dtype/capacity-bucket-shaped names and attributes, knob
  resolutions (``conf.get``, ``resolve_tri``), bucket helpers
  (``compaction_bucket``, ``bucket_capacity``), arithmetic over finite
  components;
- **infinite** components are findings: a ``lambda`` (fresh identity per
  call — the cache can never hit), a float literal (R3's continuous-key
  ban applied to static args), a raw row count (``len(...)``,
  ``num_rows`` — unbounded key space, one compile per distinct size),
  a data-derived (tainted) value, or a freshly constructed object
  (per-call identity);
- anything else is UNPROVEN — not a finding, but the entry does not
  count as proved.

An entry is PROVED when the analysis saw it, resolved its call sites,
and classified every static key component finite (entries with no
static arguments key on shapes/dtypes alone — the capacity-bucket
discipline R3 already enforces — and count as proved). The rule is
vacuity-checked: it KNOWS how many entries it covered and proved, and
fails the tree when either drops below the recorded floor — a refactor
that silently hides jit entries from the analysis fails loudly instead
of shrinking the guarantee.

Closure side: a module-level jit entry reading a module name that is
REBOUND (assigned more than once at module level, or written through
``global``) bakes whichever value tracing saw — flagged; single-binding
module constants and imports are the sanctioned capture shape.
"""

from __future__ import annotations

import ast
import re

from tools.auronlint.core import Rule, SourceModule

#: floors for the vacuity check: the analysis must keep seeing at least
#: this many module-level jit entries tree-wide, and keep proving at
#: least this many. Raise them as entries are added; a DROP means the
#: analysis lost sight of real entries (or a key regressed to unproven).
#: 51 -> 48 with the joins' host-index takes and the chain's separate
#: live count (_unique_compact_take_jit, _chain_take_jit, _sel_count_jit):
#: three entries deleted with their last caller, none lost from sight.
#: 48 -> 47 with the chain's _and_all, which had no caller (PR 30).
R13_MIN_COVERED = 47
R13_MIN_PROVED = 47

_JIT_RE = re.compile(r"\bjit\b")

#: names/attributes that denote finite key spaces: capacity buckets,
#: schema/dtype signatures, partition widths, knob resolutions
_FINITE_NAME_RE = re.compile(
    r"(cap|capacity|bucket|n_out|n_parts|width|steps|sig|signature|"
    r"schema|dtypes?|kinds?|cfgs?|flags?|impl|algo|seed|bits|mode|emit|"
    r"prep|probe|shuffle|interpret|device_sort|use_lut|probe_outer|pad|"
    r"chunk|size|depth|names|fields|enable|preds?|proj|pcol|bcol|dims?|"
    r"fingerprint|fp_bits|P|B|K|n|k)$",
    re.IGNORECASE,
)

#: boolean-flavored / arity-flavored name prefixes: tri-state knob
#: resolutions (need_/use_/host_...) and schema arities (n_keys) are
#: two-point or column-bounded key spaces
_FINITE_PREFIX_RE = re.compile(
    r"^(need|use|is|has|do|with|host|device|block|chunk|n)_"
)

#: functions whose RESULT is a finite key component (knob/bucket space)
_FINITE_RESOLVERS = {
    "resolve_tri", "compaction_bucket", "bucket_capacity", "get",
    "tuple", "frozenset", "bool", "int", "str", "min", "max", "sorted",
    "repartition_substrate", "use_host_sort", "sort_impl_for",
}

#: row-count smells: an unbounded key space, one compile per size
_ROWCOUNT_RE = re.compile(r"(num_rows|n_rows|row_count|nrows|rowcnt)")

GOOD, BAD, UNKNOWN = "finite", "infinite", "unproven"


def _unparse(node) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def classify(expr: ast.AST, scope=None) -> tuple[str, str]:
    """(verdict, why) for one static-argument expression."""
    if isinstance(expr, ast.Lambda):
        return BAD, "a lambda has fresh identity per call — the compile " \
                    "cache can never hit; hoist it to a module-level def"
    if isinstance(expr, ast.Constant):
        if isinstance(expr.value, float):
            return BAD, "float literal in a cache key — continuous key " \
                        "space; pass floats as traced operands"
        return GOOD, ""
    if isinstance(expr, (ast.Tuple, ast.List)):
        for e in expr.elts:
            v, why = classify(e, scope)
            if v != GOOD:
                return v, why
        return GOOD, ""
    if isinstance(expr, ast.Starred):
        return classify(expr.value, scope)
    if isinstance(expr, ast.Name):
        if scope is not None and expr.id in scope.tainted:
            return BAD, f"'{expr.id}' is data-derived (a host read of " \
                        "device data) — per-value retrace"
        if _ROWCOUNT_RE.search(expr.id):
            return BAD, f"'{expr.id}' looks like a raw row count — " \
                        "unbounded key space; use the capacity bucket"
        if _FINITE_NAME_RE.search(expr.id) or _FINITE_PREFIX_RE.search(expr.id):
            return GOOD, ""
        return UNKNOWN, ""
    if isinstance(expr, ast.Attribute):
        if _ROWCOUNT_RE.search(expr.attr):
            return BAD, f"'.{expr.attr}' looks like a raw row count — " \
                        "unbounded key space; use the capacity bucket"
        if _FINITE_NAME_RE.search(expr.attr) \
                or _FINITE_PREFIX_RE.search(expr.attr):
            return GOOD, ""
        return UNKNOWN, ""
    if isinstance(expr, ast.Call):
        f = expr.func
        fname = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if _ROWCOUNT_RE.search(fname):
            return BAD, f"'{fname}()' is a row count — unbounded key " \
                        "space; use the capacity bucket"
        if fname == "len":
            arg_text = _unparse(expr.args[0]) if expr.args else ""
            if re.search(r"schema|names|cols|columns|fields|dtypes",
                         arg_text):
                return GOOD, ""
            return BAD, "len(...) of data in a cache key is a raw row " \
                        "count — unbounded key space"
        if fname in _FINITE_RESOLVERS:
            return GOOD, ""
        if fname and fname[0].isupper():
            return BAD, f"freshly constructed '{fname}(...)' keys the " \
                        "cache on per-call object identity — every call " \
                        "compiles anew; pass a value-keyed tuple instead"
        return UNKNOWN, ""
    if isinstance(expr, ast.BinOp):
        lv, lw = classify(expr.left, scope)
        rv, rw = classify(expr.right, scope)
        for v, w in ((lv, lw), (rv, rw)):
            if v == BAD:
                return v, w
        return (GOOD, "") if lv == rv == GOOD else (UNKNOWN, "")
    if isinstance(expr, (ast.Compare, ast.BoolOp, ast.UnaryOp)):
        return GOOD, ""   # boolean-valued: two-point key space
    if isinstance(expr, ast.IfExp):
        bv, bw = classify(expr.body, scope)
        ov, ow = classify(expr.orelse, scope)
        for v, w in ((bv, bw), (ov, ow)):
            if v == BAD:
                return v, w
        return (GOOD, "") if bv == ov == GOOD else (UNKNOWN, "")
    return UNKNOWN, ""


# ---------------------------------------------------------------------------
# entry discovery
# ---------------------------------------------------------------------------


def _static_names_of_call(call: ast.Call) -> list[str] | None:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return [v.value]
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, str):
                        out.append(e.value)
                return out
    return None


def module_jit_entries(mod: SourceModule):
    """(name, fn_def, static_argnames, line) for every module-level jit
    entry: a top-level def with a jit decorator, or a top-level
    ``name = jax.jit(local_def, ...)`` binding."""
    defs = {n.name: n for n in mod.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    out = []
    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _JIT_RE.search(_unparse(dec)):
                    statics = _static_names_of_call(dec) if isinstance(
                        dec, ast.Call) else None
                    out.append((node.name, node, statics or [], node.lineno))
                    break
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if not _JIT_RE.search(_unparse(call.func)):
                continue
            target = None
            if call.args and isinstance(call.args[0], ast.Name) \
                    and call.args[0].id in defs:
                target = defs[call.args[0].id]
            if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
                out.append((
                    node.targets[0].id,
                    target,
                    _static_names_of_call(call) or [],
                    node.lineno,
                ))
    return out


def _param_index(fn: ast.FunctionDef | None, name: str) -> int | None:
    if fn is None:
        return None
    a = fn.args
    params = [p.arg for p in list(a.posonlyargs) + list(a.args)]
    return params.index(name) if name in params else None


def _rebound_module_names(mod: SourceModule, g=None) -> set:
    """Module-level names assigned MORE than once at module level, or
    written via ``global`` from inside a function — the closure captures
    a jit entry must not read."""
    counts: dict[str, int] = {}
    for node in mod.tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name):
                counts[t.id] = counts.get(t.id, 0) + 1
    rebound = {n for n, c in counts.items() if c > 1}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Global):
            rebound.update(n for n in node.names if n in counts)
    return rebound


class RetraceStabilityRule(Rule):
    name = "R13"
    doc = "retrace stability: jit cache keys drawn from finite sets"

    def __init__(self):
        self.last_stats: dict | None = None

    def check_tree(self, root: str):
        from tools.auronlint.callgraph import build_graph

        findings, stats = analyze(build_graph(root))
        self.last_stats = stats
        yield from findings
        if stats["covered"] < R13_MIN_COVERED:
            yield "auron_tpu", 0, (
                f"R13 vacuity check: only {stats['covered']} module-level "
                f"jit entries covered (floor {R13_MIN_COVERED}) — the "
                "analysis lost sight of real entries; fix the discovery "
                "or consciously lower R13_MIN_COVERED with review"
            )
        elif stats["proved"] < R13_MIN_PROVED:
            yield "auron_tpu", 0, (
                f"R13 vacuity check: only {stats['proved']} of "
                f"{stats['covered']} module-level jit entries proved "
                f"finite-keyed (floor {R13_MIN_PROVED}) — a cache key "
                "regressed to unproven; restore it or consciously lower "
                "R13_MIN_PROVED with review"
            )


def analyze(g):
    """(findings, stats) over a built CallGraph. ``stats``: covered /
    proved counts plus the per-entry verdict map tests pin coverage on."""
    findings: list = []
    entries: dict[str, dict] = {}

    for rel in sorted(g.modules):
        ms = g.modules[rel]
        mod = ms.mod
        jit_entries = module_jit_entries(mod)
        if not jit_entries:
            # the rebound-name scan walks the whole module tree — skip
            # it for the vast majority of modules with no jit entry
            continue
        rebound = _rebound_module_names(mod)
        for name, fn, statics, line in jit_entries:
            qual = f"{rel}::{name}"
            wrapped_qual = f"{rel}::{fn.name}" if fn is not None else None
            ent = entries[qual] = {
                "rel": rel, "name": name, "line": line, "statics": statics,
                "verdict": GOOD, "sites": 0,
            }
            # closure captures: free names of the entry that are rebound
            # module state
            if fn is not None and rebound:
                bound = _bound_names(fn)
                for n in ast.walk(fn):
                    if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                            and n.id in rebound and n.id not in bound:
                        findings.append((rel, n.lineno, (
                            f"jit entry '{name}' closes over module name "
                            f"'{n.id}' which is rebound after definition — "
                            "whichever value tracing saw is baked into the "
                            "compiled program; pass it as an argument or "
                            "make the binding single-assignment"
                        )))
                        ent["verdict"] = BAD
            if not statics:
                continue  # shape/dtype-keyed only: R3's bucket discipline
            # call sites across the package, via the resolved call graph
            for caller_q, edges in g.edges_out.items():
                caller = g.functions.get(caller_q)
                if caller is None:
                    continue
                cms = g.modules.get(caller.rel)
                for e in edges:
                    if e.callee not in (qual, wrapped_qual):
                        continue
                    site = _call_at(caller, e.line, name)
                    if site is None:
                        continue
                    ent["sites"] += 1
                    scope = None
                    if cms is not None:
                        scope = cms.mod.scope_of(site.node)
                    for sname in statics:
                        expr = _static_arg_expr(site.node, sname,
                                                _entry_fn_def(g, qual,
                                                              wrapped_qual))
                        if expr is None:
                            continue  # default applies: R2's domain
                        v, why = classify(expr, scope)
                        if v == BAD:
                            findings.append((caller.rel, site.line, (
                                f"jit entry '{name}' called with an "
                                f"infinite cache-key component for static "
                                f"arg '{sname}': {why}"
                            )))
                            ent["verdict"] = BAD
                        elif v == UNKNOWN and ent["verdict"] == GOOD:
                            ent["verdict"] = UNKNOWN

    covered = len(entries)
    proved = sum(1 for e in entries.values() if e["verdict"] == GOOD)
    stats = {
        "covered": covered,
        "proved": proved,
        "entries": {
            q: {"verdict": e["verdict"], "sites": e["sites"],
                "statics": e["statics"]}
            for q, e in entries.items()
        },
    }
    return findings, stats


def _bound_names(fn) -> set:
    a = fn.args
    bound = {p.arg for p in (list(a.posonlyargs) + list(a.args)
                             + list(a.kwonlyargs))}
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)
    for n in ast.walk(fn):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            bound.add(n.id)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and n is not fn:
            bound.add(n.name)
    return bound


def _entry_fn_def(g, qual, wrapped_qual):
    for q in (qual, wrapped_qual):
        if q is None:
            continue
        fs = g.functions.get(q)
        if fs is not None:
            ms = g.modules.get(fs.rel)
            if ms is not None:
                for n in ast.walk(ms.mod.tree):
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                            and n.lineno == fs.lineno and n.name == fs.name:
                        return n
    return None


def _call_at(caller, line, name):
    for c in caller.calls:
        if c.line == line and c.name == name:
            return c
    return None


def _static_arg_expr(call: ast.Call, sname: str, fn) -> ast.AST | None:
    for kw in call.keywords:
        if kw.arg == sname:
            return kw.value
    idx = _param_index(fn, sname)
    if idx is not None and idx < len(call.args) and not any(
        isinstance(a, ast.Starred) for a in call.args[: idx + 1]
    ):
        return call.args[idx]
    return None
