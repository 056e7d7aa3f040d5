"""SqlServer: concurrent multi-tenant query execution over one mesh.

One instance serves many concurrent queries (docs/serving.md):

- each query runs under its OWN query trace (obs.query_trace) and its
  own per-tenant session Configuration — conf is threaded explicitly
  through the mesh driver and into the collect task's TaskDefinition,
  never read from ambient thread state (the R7 discipline that made
  cross-thread conf handling safe);
- parse -> bind -> lower is skipped on a plan-digest cache hit
  (serve/cache.py); execution re-enters the fusion stage cache, so a
  replayed query adds zero new XLA compiles;
- the admission controller (serve/admission.py) bounds concurrency and
  applies memory-manager-aware backpressure BEFORE a query touches the
  executor pool;
- per-query isolation of the collect stage rides call_native's
  ``extra_resources`` overlay: concurrent queries hand their own stage
  output under the shared ``sql:__stage__`` rid without racing on the
  global resource map.

The server owns the tables as COLUMNAR batches of the catalog's declared
schemas, resident on the mesh's devices from construction: a table is
handed over as batches (a list, or any iterable that makes them one at a
time: a host engine's materialised segments), or as a pandas frame that
is converted once, here, through the same ingest (``Batch.from_pandas``
under the catalog's schema). The batches are a scan's splits, dealt to
the chips round robin in table order (split ``i`` on device ``i mod N``
of the default mesh), as a scheduler hands splits to free slots: a
date-ordered fact table's hot year is spread over the chips. A scanned
view is a regrouping of those same batches: partition ``p`` of the
partitioned ``sql:<table>`` is the splits on device ``p``, and the
replicated ``sql:<table>:all`` (a build side) holds one copy of the table
on every device, made the first time a plan scans it there and kept; a
partitioned view at another width than the server's copies what lies
elsewhere for its query alone. On a mesh of one device every view of
every width shares one upload: the Flare
compile-once/serve-many shape, applied to data residency too.
"""

from __future__ import annotations

import decimal
import threading
import time
from typing import Optional

import pandas as pd

from auron_tpu import obs
from auron_tpu.serve.admission import AdmissionController
from auron_tpu.serve.cache import PlanCache, plan_cache_key
from auron_tpu.utils.config import (
    EXCHANGE_MODE,
    SERVE_PLAN_CACHE_ENTRIES,
    SQL_SHUFFLE_PARTITIONS,
    Configuration,
    conf_scope,
)

#: session-conf keys tenants may NOT override: these mutate process-wide
#: state when a task conf carries them (obs.apply_conf flips the global
#: recording mode; the http service is the server's own front door) or
#: reconfigure the server/admission layer itself. A request naming one
#: fails loudly instead of silently bleeding into every other tenant.
_SESSION_DENIED_PREFIXES = ("obs.", "http.service.", "serve.")


class QueryError(RuntimeError):
    """A request-level error (bad SQL, bad conf key): HTTP 400."""


def _default_base_conf(conf: Optional[Configuration]) -> Configuration:
    import jax

    conf = (conf or Configuration()).copy()
    if jax.default_backend() == "cpu" and conf.get(EXCHANGE_MODE) == "auto":
        # same CPU default as the sqlgate: XLA:CPU cross-module all_to_all
        # rendezvous starves against host-sort callbacks on small-core
        # hosts; the durable file transport is the serving default there
        conf = conf.set(EXCHANGE_MODE, "file")
    return conf


#: rows a batch of a converted frame holds at most (the scan's unit)
TABLE_BATCH_ROWS = 1 << 20


def _columnar(name: str, table, schema, devices) -> list:
    """One table as the list of batches the server keeps, split ``i`` on
    ``devices[i mod N]``: batches pass through one at a time (their schema
    must be the catalog's; ``table`` may be a generator, so that a table
    larger than one chip never lies on one), a pandas frame is cut into
    equal row ranges — at most TABLE_BATCH_ROWS each, the same number for
    every device of the default mesh — and ingested under the catalog's
    schema."""
    import jax

    from auron_tpu.columnar.batch import Batch

    n_parts = len(devices)
    if isinstance(table, pd.DataFrame):
        n = len(table)
        per_part = max(1, -(-n // (n_parts * TABLE_BATCH_ROWS)))  # batches
        rows = max(1, -(-n // (n_parts * per_part)))
        out = []
        # an empty frame is one empty batch
        for i, lo in enumerate(range(0, max(n, 1), rows)):
            with jax.default_device(devices[i % n_parts]):
                out.append(Batch.from_pandas(table.iloc[lo:lo + rows],
                                             schema=schema))
        return out
    want = [(f.name, f.dtype) for f in schema]
    out = []
    batches = iter(table)
    while True:
        dev = devices[len(out) % n_parts]
        # a batch that is made as it is asked for lands on its own chip at
        # once: nothing passes through chip 0 on the way
        with jax.default_device(dev):
            b = next(batches, None)
        if b is None:
            return out
        got = [(f.name, f.dtype) for f in b.schema]
        if got != want:
            raise ValueError(
                f"table {name!r}: a batch's columns {got} are not the "
                f"catalog's {want}")
        out.append(b.on_device(dev))


class SqlServer:
    """In-process SQL serving front end (POST /sql's implementation)."""

    def __init__(self, catalog, tables: dict, conf: Configuration | None = None,
                 n_parts: int | None = None, mesh=None):
        self.catalog = catalog
        self.conf = _default_base_conf(conf)
        self.n_parts = (n_parts if n_parts is not None
                        else self.conf.get(SQL_SHUFFLE_PARTITIONS))
        self.conf = self.conf.set(SQL_SHUFFLE_PARTITIONS, self.n_parts)
        # meshes per width: a tenant overriding sql.shuffle.partitions
        # gets a DIFFERENT plan (the knob rides the plan-cache key) and
        # must execute at that width; meshes are cheap views over the
        # same devices. The default width goes through the SAME checked
        # _mesh_for path as tenant overrides (make_mesh's device-count
        # assert vanishes under python -O)
        self._mesh_lock = threading.Lock()
        self._meshes = {}
        if mesh is not None:
            self._meshes[self.n_parts] = mesh
        self.mesh = self._mesh_for(self.n_parts)
        self.plan_cache = PlanCache(self.conf.get(SERVE_PLAN_CACHE_ENTRIES))
        self.admission = AdmissionController(self.conf)
        # table -> its batches in row order, split i on device i mod N
        # of the default mesh, immutable after this line (views regroup
        # them). A table the catalog does not name can never be scanned
        # and is not kept
        devices = list(self.mesh.devices.flat)
        self.tables: dict[str, list] = {
            name: _columnar(name, t, catalog.schema(name), devices)
            for name, t in tables.items()
            if catalog.schema(name) is not None
        }
        # (table, device) -> a build side's copy of the whole table on that
        # device, made the first time a plan scans it there and kept: the
        # only copies the server pins beyond the resident splits
        self._replicas: dict[tuple, list] = {}
        self._replicas_lock = threading.Lock()
        # the hand-over made a staging copy of every split and freed it:
        # those pages go back to the system now, not in the middle of some
        # later query (memory/hostheap.py)
        _release_freed_heap()
        self._stats_lock = threading.Lock()
        self.queries_ok = 0
        self.queries_err = 0

    # ------------------------------------------------------------------
    # session confs

    def session_conf(self, overrides: dict | None,
                     tenant: str | None = None) -> Configuration:
        """Base conf + validated per-request overrides. Unknown keys and
        process-global keys refuse loudly (QueryError -> 400)."""
        from auron_tpu.utils.config import _REGISTRY

        conf = self.conf.copy()
        for k, v in (overrides or {}).items():
            if any(k.startswith(p) for p in _SESSION_DENIED_PREFIXES):
                raise QueryError(
                    f"conf key {k!r} is not session-settable (process-wide "
                    "or server-level state)")
            if k not in _REGISTRY:
                raise QueryError(f"unknown conf key {k!r}")
            conf = conf.set(k, str(v))
        return conf

    # ------------------------------------------------------------------
    # planning

    def plan(self, sql: str, conf: Configuration):
        """(LoweredQuery, digest-key, cache_hit) — the program-cache front
        door: a hit skips parse/bind/lower entirely."""
        from auron_tpu.sql import compile_text

        with obs.span("plan", cat="serve", arg={"cache_hit": True}) as sp:
            key = plan_cache_key(sql, conf)
            lq = self.plan_cache.lookup(key)
            if lq is not None:
                return lq, key, True
            if sp is not None:
                sp.arg["cache_hit"] = False
            lq = compile_text(sql, self.catalog,
                              n_parts=conf.get(SQL_SHUFFLE_PARTITIONS))
            self.plan_cache.insert(key, lq)
            return lq, key, False

    # ------------------------------------------------------------------
    # execution

    def _mesh_for(self, n_parts: int):
        import jax

        from auron_tpu.parallel.mesh import make_mesh

        with self._mesh_lock:
            mesh = self._meshes.get(n_parts)
            if mesh is None:
                # explicit check, not assert-sniffing: make_mesh's own
                # device-count assert vanishes under python -O and would
                # hand back a narrower mesh than the plan was lowered for
                n_dev = len(jax.devices())
                if n_parts > n_dev:
                    raise QueryError(
                        f"sql.shuffle.partitions={n_parts} exceeds the "
                        f"device count {n_dev}")
                mesh = make_mesh(n_parts)
                self._meshes[n_parts] = mesh
            return mesh

    def _view(self, table: str, n_parts: int, replicated: bool) -> list:
        """A table's batches by partition of an ``n_parts``-wide mesh, each
        partition's on its own device. Partitioned: the splits dealt round
        robin (partition ``p`` scans splits ``p, p + N, ...``); at the
        server's own width those are the resident splits and nothing is
        copied, at another width a split that lies elsewhere is copied for
        this query alone and freed with it (a fact table is never pinned
        twice). Replicated (a build side): the whole table on every
        device, each device's copy made once and kept."""
        batches = self.tables[table]
        devices = list(self._mesh_for(n_parts).devices.flat)
        if not replicated:
            return [[b.on_device(dev) for b in batches[p::n_parts]]
                    for p, dev in enumerate(devices)]
        view = []
        with self._replicas_lock:
            for dev in devices:
                copy = self._replicas.get((table, dev))
                if copy is None:
                    copy = self._replicas[table, dev] = [
                        b.on_device(dev) for b in batches]
                view.append(copy)
        return view

    def _build_resources(self, lq) -> dict:
        """Batch lists for every table the plan scans, partition ``p``'s
        on mesh device ``p``."""
        return {use.rid: self._view(use.table, lq.n_parts, use.replicated)
                for use in lq.tables}

    def _execute(self, lq, conf: Configuration) -> pd.DataFrame:
        """Run one lowered query under ``conf``: distributed stage on the
        shared mesh (fresh driver per query — drivers carry per-run
        state), then the optional collect stage as an isolated task."""
        from auron_tpu.parallel.mesh_driver import MeshQueryDriver

        with obs.span("execute", cat="serve"):
            resources = self._build_resources(lq)
            driver = MeshQueryDriver(self._mesh_for(lq.n_parts), conf=conf)
            outs = driver.run(lq.distributed, resources)
        # the partitions' outputs are gathered on the mesh's first device:
        # the collect stage is one task, and the answer leaves from there
        first = driver.mesh.devices.flat[0]
        batches = [b.on_device(first) for part in outs for b in part]
        with obs.span("collect", cat="serve"):
            return self._collect(lq, conf, batches)

    def _collect(self, lq, conf: Configuration, batches: list) -> pd.DataFrame:
        """The distributed stage's output as one frame: through the collect
        task where the plan has one (global merge, ORDER BY, LIMIT)."""
        import jax

        from auron_tpu.bridge import api
        from auron_tpu.plan import builders as B
        from auron_tpu.sql.lowering import STAGE_RID

        if lq.collect is None:
            dfs = [_frame(b.to_arrow()) for b in batches]
        else:
            # stage barrier, as in models/sqlgate.execute: retire the
            # distributed stage's async arrays before the collect task
            # competes for the XLA:CPU thread pool
            jax.block_until_ready([b.device for b in batches])
            # the collect task ships THIS query's conf (tenant knobs +
            # obs.trace.id) and reads its stage input through the
            # call-scoped resource overlay — no global-map rendezvous,
            # no cross-query bleed on the shared STAGE_RID
            task = B.task(lq.collect, conf=conf.as_dict())
            h = api.call_native(task.SerializeToString(),
                                extra_resources={STAGE_RID: [batches]})
            dfs = []
            try:
                while (rb := api.next_batch(h)) is not None:
                    dfs.append(_frame(rb))
            except BaseException:
                # a failing per-query collect must not leak its runtime
                # (handle in api._runtimes, pump thread blocked on the
                # bounded queue) — finalize cancels/joins; ITS error is
                # secondary to the one already propagating
                try:
                    api.finalize_native(h)
                except Exception:  # noqa: BLE001  # auronlint: disable=R12 -- unwind: the propagating collect error is primary; finalize's own is secondary
                    pass
                raise
            api.finalize_native(h)
        cols = list(lq.schema.names)
        dfs = [d for d in dfs if len(d)]
        if dfs:
            out = pd.concat(dfs, ignore_index=True)
            out.columns = cols
        else:
            out = pd.DataFrame({c: [] for c in cols})
        return out

    # ------------------------------------------------------------------
    # the front door

    def submit(self, sql: str, session: dict | None = None,
               tenant: str | None = None) -> tuple[pd.DataFrame, dict]:
        """Plan (or cache-hit) + admit + execute one query. Returns the
        result frame and a record (digest, cache_hit, timings, trace)."""
        t_arrive = time.perf_counter()
        try:
            # inside the try: a refused conf key (QueryError) and an
            # admission timeout must count on /serve's queries_err too
            conf = self.session_conf(session, tenant=tenant)
            with self.admission.admit() as slot:
                rec = {
                    "tenant": tenant,
                    "cache_hit": False,
                    "queue_wait_s": round(slot.wait_s, 4),
                }
                # conf_scope: everything below (ingest, drivers, jit
                # backend policies) resolves THIS query's conf, never a
                # sibling handler thread's
                with conf_scope(conf), obs.query_trace(
                    f"serve.{tenant or 'anon'}", conf=conf
                ) as qt:
                    lq, key, hit = self.plan(sql, qt.conf or conf)
                    rec["digest"] = key
                    rec["cache_hit"] = hit
                    df = self._execute(lq, qt.conf if qt.conf is not None
                                       else conf)
                if qt.summary is not None:
                    rec["trace_id"] = qt.summary["trace_id"]
                rec["rows"] = len(df)
                rec["wall_s"] = round(time.perf_counter() - t_arrive, 4)
                with self._stats_lock:
                    self.queries_ok += 1
                if not hit:
                    # this query traced, lowered and compiled (or loaded)
                    # its programs: what that freed is handed back in the
                    # query that was slow anyway
                    _release_freed_heap()
                return df, rec
        except Exception:
            with self._stats_lock:
                self.queries_err += 1
            raise

    def execute_json(self, body: dict) -> dict:
        """The POST /sql contract (docs/serving.md): body
        ``{"sql": ..., "conf": {...}?, "tenant": ...?}`` ->
        ``{"columns": [...], "rows": [[...]], ...record}``. Raises
        QueryError for request-level problems (handler answers 400)."""
        if not isinstance(body, dict) or not isinstance(body.get("sql"), str):
            raise QueryError('body must be a JSON object with a "sql" string')
        session = body.get("conf")
        if session is not None and not isinstance(session, dict):
            raise QueryError('"conf" must be an object of key -> value')
        from auron_tpu.sql.diagnostics import SqlDiagnostic

        try:
            df, rec = self.submit(body["sql"], session=session,
                                  tenant=body.get("tenant"))
        except SqlDiagnostic as e:
            raise QueryError(str(e)) from None
        # serve:encode, first half: cells to JSON-safe values (the second,
        # json.dumps, is the HTTP handler's: utils/httpsvc.py)
        with obs.span("encode", cat="serve"):
            rec["columns"] = list(df.columns)
            rec["rows"] = _json_rows(df)
        return rec

    def stats(self) -> dict:
        """The /serve endpoint's payload."""
        with self._stats_lock:
            ok, err = self.queries_ok, self.queries_err
        return {
            "n_parts": self.n_parts,
            "queries_ok": ok,
            "queries_err": err,
            "plan_cache": self.plan_cache.stats(),
            "admission": self.admission.stats(),
            "tables_resident": len(self.tables),
        }


def _release_freed_heap() -> None:
    """``serve:release``: the allocator's freed pages back to the system
    (argument ``bytes``: what the process's resident set shrank by)."""
    from auron_tpu.memory.hostheap import release_freed_heap

    with obs.span("release", cat="serve", arg={"bytes": 0}) as sp:
        freed = release_freed_heap()
        if sp is not None:
            sp.arg["bytes"] = freed


def _frame(rb) -> pd.DataFrame:
    """An Arrow batch of the answer as a frame; an integer column that holds
    a NULL keeps its integers (objects, ``None`` for NULL) where pandas'
    default would turn the column into floats: a key answers as ``7``, never
    ``7.0``, and an int64 past 2**53 stays exact."""
    return rb.to_pandas(integer_object_nulls=True)


def _json_rows(df: pd.DataFrame) -> list[list]:
    """JSON-safe row materialization: numpy scalars -> python, NaN/NaT ->
    null, a DECIMAL cell -> its exact decimal string at the column's scale
    (``"1234.50"``, never through a float: docs/serving.md). Deterministic
    (shortest-roundtrip float repr), so two identical result frames
    serialize byte-identically — the property the concurrency differential
    gate's HTTP leg compares on."""
    out = []
    for row in df.itertuples(index=False, name=None):
        vals = []
        for v in row:
            if v is None or (isinstance(v, float) and v != v) or pd.isna(v):
                vals.append(None)
            elif isinstance(v, decimal.Decimal):
                # Arrow hands a DECIMAL(p,s) cell over with exponent -s:
                # fixed-point notation keeps every digit of the scale
                vals.append(format(v, "f"))
            elif hasattr(v, "isoformat"):
                # datetime-like (pd.Timestamp, date): BEFORE .item() —
                # Timestamp.item does not exist and a raw Timestamp is
                # not JSON-serializable (a DATE32 projection would 500)
                vals.append(v.isoformat())
            elif hasattr(v, "item"):
                vals.append(v.item())
            else:
                vals.append(v)
        out.append(vals)
    return out
