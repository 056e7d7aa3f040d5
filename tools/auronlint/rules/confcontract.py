"""R14 — config-knob contract: every knob declared, read, resolved, keyed.

The engine's config surface is a contract with four clauses, and a miss
on any of them is a serving bug, not a style nit:

1. **No raw-string reads.** ``conf.get("some.key")`` bypasses the
   ``ConfigOption`` registry: no default, no doc row, no session-override
   validation (serve/server.py rejects unknown keys against
   ``_REGISTRY``). Every read goes through a declared knob object.
   Only SINGLE-argument ``.get("literal")`` calls on conf-shaped
   receivers are flagged — two-argument ``.get(key, default)`` is the
   dict/proto-map protocol, a different animal (planner reads task
   proto conf maps that way).
2. **No dead knobs.** A knob declared but never read is documentation
   that lies. Declared-for-reference-parity debt carries a reasoned
   ``# auronlint: disable=R14`` on the declaration line and rides the
   ratchet down.
3. **Tri-state knobs resolve through ``resolve_tri``.** A knob whose
   domain is ``on | off | auto`` read with a manual ``== "off"`` chain
   silently drops the ``auto`` arm (the exact bug class PR 9's device
   sort fallback hit). Sanctioned shape: the enclosing function calls
   ``utils/config.resolve_tri``.
4. **Plan-affecting knobs appear in PLAN_KNOBS.** The teeth: any knob
   whose read is reachable — over the package call graph — from plan
   construction (``sql/lowering.py`` or ``plan/fusion.py``) must be a
   member of ``sql/digest.py`` PLAN_KNOBS, or the serving cache
   (serve/cache.py keys on PLAN_KNOBS) returns a plan compiled under a
   DIFFERENT tenant's settings. Proved over non-generic call edges so
   the closure is real reachability, not name-collision glue.

Plus the generated-artifact gate: ``docs/CONFIG.md`` must match
``utils/config.generate_doc()`` exactly (regen:
``python -m tools.gen_config_doc``). The drift check runs only against
the real repository root — fixture trees exercise the graph clauses
through ``analyze()`` directly.

Vacuity floors: the rule KNOWS how many knobs it saw declared and how
many plan-path knobs it proved into PLAN_KNOBS, and fails the tree when
either count drops below the recorded floor — a refactor that hides the
registry (or empties the closure) fails loudly instead of passing
emptily.
"""

from __future__ import annotations

import ast
import os
import re

from tools.auronlint.core import Rule

#: floors for the vacuity check. ``DECLARED``: statically-visible named
#: ConfigOption declarations tree-wide; ``PLAN_PROVED``: distinct knobs
#: whose reads the call-graph closure from plan construction reaches AND
#: that are PLAN_KNOBS members. Raise as knobs are added; a DROP means
#: the analysis lost the registry or the plan closure went empty.
#: 70 -> 69: PR 30 deleted seven declarations (the compaction boundary's
#: six knobs with their losing arms, and batch.capacity.buckets, which
#: nothing read) and 69 stay visible, none lost from sight.
R14_MIN_DECLARED = 69
R14_MIN_PLAN_PROVED = 6

#: where plan construction lives: the closure anchors every function in
#: these modules (lowering builds the LoweredQuery the serving cache
#: stores; fusion rewrites the exec tree it replays)
PLAN_ANCHOR_RELS = (
    "auron_tpu/sql/lowering.py",
    "auron_tpu/plan/fusion.py",
)

#: the module whose PLAN_KNOBS tuple IS the serving cache-key contract
DIGEST_REL = "auron_tpu/sql/digest.py"

#: ConfigOption builder call names (utils/config.py)
_BUILDERS = {"int_conf", "float_conf", "bool_conf", "str_conf", "ConfigOption"}

#: a str_conf whose doc names the on/off/auto domain is tri-state —
#: either the canonical "on | off | auto" spelling or the prose form
#: "auto = on for ..." (both in live use in utils/config.py)
_TRI_DOC_RE = re.compile(r"\bon\s*\|\s*off\b|\bauto\s*=\s*on\b")

#: conf-shaped receivers: the terminal name of the receiver chain
_CONFISH_RE = re.compile(r"(^|_)conf$|^config$")


def _recv_terminal(func: ast.Attribute) -> str | None:
    """Terminal name of the receiver of an attribute call: ``conf.get``
    -> "conf", ``self.conf.get`` -> "conf", ``task.conf.get`` -> "conf"."""
    v = func.value
    if isinstance(v, ast.Attribute):
        return v.attr
    if isinstance(v, ast.Name):
        return v.id
    return None


def _is_conf_get(node: ast.Call) -> bool:
    """A single-argument ``<conf>.get(x)`` call — the Configuration
    protocol (Configuration.get takes exactly one knob argument; the
    two-argument form is the dict/proto-map protocol, exempt)."""
    if not isinstance(node.func, ast.Attribute) or node.func.attr != "get":
        return False
    if len(node.args) != 1 or node.keywords:
        return False
    recv = _recv_terminal(node.func)
    return recv is not None and bool(_CONFISH_RE.search(recv))


def collect_declarations(g) -> dict:
    """name -> {rel, line, key, tri} for every statically-visible named
    knob declaration (``NAME = str_conf("key", ...)`` at module level).
    Dynamically built registries (dict comprehensions over builder
    calls) are exempt from the named-knob clauses; the CONFIG.md drift
    gate covers them at runtime-import level."""
    decls: dict[str, dict] = {}
    for rel in sorted(g.modules):
        tree = g.modules[rel].mod.tree
        for node in tree.body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            v = node.value
            if not isinstance(t, ast.Name) or not isinstance(v, ast.Call):
                continue
            callee = v.func
            name = callee.attr if isinstance(callee, ast.Attribute) else (
                callee.id if isinstance(callee, ast.Name) else None)
            if name not in _BUILDERS:
                continue
            key = None
            if v.args and isinstance(v.args[0], ast.Constant) \
                    and isinstance(v.args[0].value, str):
                key = v.args[0].value
            tri = name == "str_conf" and any(
                isinstance(a, ast.Constant) and isinstance(a.value, str)
                and _TRI_DOC_RE.search(a.value)
                for a in list(v.args) + [k.value for k in v.keywords]
            )
            decls[t.id] = {"rel": rel, "line": node.lineno, "key": key,
                           "tri": tri}
    return decls


def _iter_functions(tree):
    """Every def node in the tree, in source order."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def own_nodes(scope):
    """Nodes belonging to this scope itself — nested def bodies are
    their own scope's rows and are skipped (their lines would otherwise
    be attributed to the enclosing function)."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(node))


def _plan_closure(g, anchor_rels) -> set:
    """Function qualnames reachable from plan construction over
    NON-generic call edges (resolved imports/methods only — generic
    name-match edges would glue the whole package together)."""
    seen = {q for q, fs in g.functions.items() if fs.rel in anchor_rels}
    frontier = list(seen)
    while frontier:
        q = frontier.pop()
        for e in g.edges_out.get(q, ()):
            if e.generic or e.callee in seen:
                continue
            seen.add(e.callee)
            frontier.append(e.callee)
    return seen


def _scan_module(mod, decl_names: frozenset, tri_names: frozenset) -> dict:
    """Pure per-module extraction the interprocedural pass composes:
    ``loads`` (every Name-load id / Attribute attr — the never-read
    clause's evidence), ``raw_gets`` [(line, key)], ``tri_bad``
    [(line, knob)] (tri knob read with no resolve_tri in the enclosing
    scope), ``knob_loads`` [(scope def lineno, knob, line)] (declared
    knob objects loaded inside a function — the plan-read candidates the
    caller filters against the plan closure). Pure in the source +
    (decl_names, tri_names), so filecache.derived replays it warm."""
    loads: set[str] = set()
    raw_gets: list[tuple] = []
    tri_bad: list[tuple] = []
    knob_loads: list[tuple] = []
    for fn in [None] + list(_iter_functions(mod.tree)):
        body = mod.tree if fn is None else fn
        scope_line = None if fn is None else fn.lineno
        # lazily computed on the first tri-knob read in this scope:
        # walking every function body up front was the lint pass's
        # single hottest loop, and almost no function reads one
        has_resolve = None
        # one traversal per scope covers every node in the module
        # exactly once (own_nodes skips nested def bodies; those are
        # their own scope's rows)
        for n in own_nodes(body):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                loads.add(n.id)
                if scope_line is not None and n.id in decl_names:
                    knob_loads.append((scope_line, n.id, n.lineno))
            elif isinstance(n, ast.Attribute):
                loads.add(n.attr)
            if not isinstance(n, ast.Call) or not _is_conf_get(n):
                continue
            arg = n.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                raw_gets.append((n.lineno, arg.value))
                continue
            if not isinstance(arg, ast.Name) or arg.id not in tri_names:
                continue
            if has_resolve is None:
                has_resolve = any(
                    isinstance(w, ast.Call) and (
                        (isinstance(w.func, ast.Name)
                         and w.func.id == "resolve_tri")
                        or (isinstance(w.func, ast.Attribute)
                            and w.func.attr == "resolve_tri"))
                    for w in ast.walk(body)
                )
            if not has_resolve:
                tri_bad.append((n.lineno, arg.id))
    return {"loads": loads, "raw_gets": raw_gets, "tri_bad": tri_bad,
            "knob_loads": knob_loads}


def analyze(g, anchor_rels=PLAN_ANCHOR_RELS, digest_rel=DIGEST_REL,
            fc=None):
    """(findings, stats) over a built CallGraph — clauses 1–4 (the
    CONFIG.md drift gate is check_tree-only; it needs the real tree).
    ``fc``: optional FileCache whose ``derived`` store replays the
    per-module scans for unchanged files (fixture graphs pass None)."""
    findings: list = []
    decls = collect_declarations(g)
    tri_names = frozenset(n for n, d in decls.items() if d["tri"])
    decl_names = frozenset(decls)

    # PLAN_KNOBS membership, from the digest module's AST
    plan_knobs: set[str] = set()
    has_digest = digest_rel in g.modules
    if has_digest:
        for node in g.modules[digest_rel].mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == "PLAN_KNOBS" \
                    and isinstance(node.value, (ast.Tuple, ast.List)):
                plan_knobs = {e.id for e in node.value.elts
                              if isinstance(e, ast.Name)}

    closure = _plan_closure(g, anchor_rels)
    fn_at = {(fs.rel, fs.lineno): q for q, fs in g.functions.items()}

    loads: set[str] = set()            # knob names read anywhere
    plan_read: dict[str, tuple] = {}   # knob -> (rel, line) inside closure

    # the scan depends on the tree-wide declaration sets — fold them
    # into the cache key so a knob add/remove invalidates every replay
    import hashlib
    scan_key = "r14scan::" + hashlib.sha256(
        repr((sorted(decl_names), sorted(tri_names))).encode()
    ).hexdigest()[:16]

    for rel in sorted(g.modules):
        mod = g.modules[rel].mod
        if fc is not None:
            scan = fc.derived(
                rel, scan_key,
                lambda m=mod: _scan_module(m, decl_names, tri_names))
        else:
            scan = _scan_module(mod, decl_names, tri_names)
        loads |= scan["loads"]
        for line, key in scan["raw_gets"]:
            findings.append((rel, line, (
                f"raw-string conf read conf.get({key!r}) "
                "bypasses the ConfigOption registry (no default, "
                "no doc row, no session-override validation) — "
                "declare a knob in utils/config.py and read "
                "through it"
            )))
        for line, name in scan["tri_bad"]:
            findings.append((rel, line, (
                f"tri-state knob {name} read without "
                "resolve_tri in the enclosing function — a "
                "manual on/off chain drops the 'auto' arm; "
                "resolve with utils/config.resolve_tri(mode, "
                "<auto-default>)"
            )))
        # a knob OBJECT loaded inside a plan-construction-reachable
        # function is a plan-affecting read: the load either feeds
        # conf.get directly or passes the knob to a helper
        # (_should_fuse(cost, conf, knob=X))
        for scope_line, name, line in scan["knob_loads"]:
            qual = fn_at.get((rel, scope_line))
            if qual is not None and qual in closure:
                plan_read.setdefault(name, (rel, line))

    for name, d in sorted(decls.items()):
        if name not in loads:
            findings.append((d["rel"], d["line"], (
                f"knob {name} ({d['key']!r}) is declared but never read "
                "anywhere in the package — dead configuration surface; "
                "wire it up or remove it (reference-parity debt carries "
                "a reasoned disable on the declaration line)"
            )))

    proved = 0
    for name, (rel, line) in sorted(plan_read.items()):
        if name in plan_knobs:
            proved += 1
        elif has_digest:
            findings.append((rel, line, (
                f"plan-affecting knob {name} is read on a path reachable "
                "from plan construction (sql/lowering.py / "
                "plan/fusion.py) but is MISSING from sql/digest.py "
                "PLAN_KNOBS — the serving cache (serve/cache.py) would "
                "return a plan compiled under a different session's "
                "settings; add it to PLAN_KNOBS (docs/auronlint.md has "
                "the recipe)"
            )))

    stats = {
        "declared": len(decls),
        "tri": len(tri_names),
        "plan_knobs": sorted(plan_knobs),
        "plan_read": sorted(plan_read),
        "plan_proved": proved,
        "closure_fns": len(closure),
    }
    return findings, stats


# -- docs/CONFIG.md drift gate (real tree only) ------------------------------

_DECL_TEXT_RE = re.compile(
    r"\b(?:int_conf|float_conf|bool_conf|str_conf|ConfigOption)\s*\("
)


def declaring_modules(root: str) -> list[str]:
    """Dotted names of package modules that declare ConfigOptions,
    discovered statically so the drift gate imports exactly the modules
    that populate the registry (including dynamic declarations the named
    clauses cannot see)."""
    mods = []
    pkg = os.path.join(root, "auron_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            try:
                with open(path, encoding="utf-8") as fh:
                    text = fh.read()
            except OSError:
                continue
            if not _DECL_TEXT_RE.search(text):
                continue
            rel = os.path.relpath(path, root)
            mods.append(rel[:-3].replace(os.sep, "."))
    return mods


def config_doc_drift(root: str):
    """Findings when docs/CONFIG.md disagrees with generate_doc() over
    the statically-discovered declaring modules. Runs only against the
    real repository root: fixture trees have no importable registry."""
    from tools.auronlint import REPO_ROOT

    if os.path.realpath(root) != os.path.realpath(REPO_ROOT):
        return
    doc_path = os.path.join(root, "docs", "CONFIG.md")
    try:
        dotted_mods = declaring_modules(root)
        paths = [os.path.join(root, d.replace(".", os.sep) + ".py")
                 for d in dotted_mods]

        def _build() -> str:
            # the import pulls in the whole engine (jax included) — the
            # aux cache keys the result on the declaring modules' file
            # signatures so warm lint runs never pay it
            import importlib

            for dotted in dotted_mods:
                importlib.import_module(dotted)
            from auron_tpu.utils.config import generate_doc

            return generate_doc().strip()

        from tools.auronlint.filecache import file_cache

        expected = file_cache(root).aux("config_doc", sorted(paths), _build)
    except Exception as e:  # loud: a broken gate must not pass silently
        yield "docs/CONFIG.md", 0, (
            f"CONFIG.md drift gate could not build the expected table "
            f"({type(e).__name__}: {e}) — fix the declaring-module "
            "import, the gate cannot verify the doc"
        )
        return
    try:
        with open(doc_path, encoding="utf-8") as fh:
            lines = fh.read().splitlines()
    except OSError:
        yield "docs/CONFIG.md", 0, (
            "docs/CONFIG.md is missing — it is a generated artifact; "
            "run `python -m tools.gen_config_doc`"
        )
        return
    start = next((i for i, ln in enumerate(lines)
                  if ln.lstrip().startswith("| key |")), None)
    current = "" if start is None else "\n".join(lines[start:]).strip()
    if current != expected:
        yield "docs/CONFIG.md", (start or 0) + 1, (
            "docs/CONFIG.md is stale vs utils/config.generate_doc() — "
            "it is a generated artifact; run "
            "`python -m tools.gen_config_doc` and commit the result"
        )


class ConfContractRule(Rule):
    name = "R14"
    doc = "config-knob contract: declared, read, resolved, cache-keyed"

    def __init__(self):
        self.last_stats: dict | None = None

    def check_tree(self, root: str):
        from tools.auronlint.callgraph import build_graph
        from tools.auronlint.filecache import file_cache

        findings, stats = analyze(build_graph(root), fc=file_cache(root))
        self.last_stats = stats
        yield from findings
        yield from config_doc_drift(root)
        if stats["declared"] < R14_MIN_DECLARED:
            yield "auron_tpu", 0, (
                f"R14 vacuity check: only {stats['declared']} named knob "
                f"declarations visible (floor {R14_MIN_DECLARED}) — the "
                "analysis lost the ConfigOption registry; fix the "
                "discovery or consciously lower R14_MIN_DECLARED with "
                "review"
            )
        elif stats["plan_proved"] < R14_MIN_PLAN_PROVED:
            yield "auron_tpu", 0, (
                f"R14 vacuity check: only {stats['plan_proved']} "
                "plan-path knobs proved into PLAN_KNOBS (floor "
                f"{R14_MIN_PLAN_PROVED}) — the plan-construction closure "
                "went empty or PLAN_KNOBS shrank; a cache-key contract "
                "cannot be proved vacuously"
            )
