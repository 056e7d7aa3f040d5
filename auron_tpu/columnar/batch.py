"""Fixed-shape columnar device batches.

The reference engine streams Arrow ``RecordBatch``es between operators
(variable-length, pointer-rich — e.g. rt.rs:150-207 pumps them through an
mpsc channel). XLA demands static shapes, so the TPU-native equivalent is a
**capacity-bucketed dense batch**:

- every column is a dense value array of length ``capacity`` (padded), plus
  a boolean validity array (SQL NULLs);
- the batch carries a boolean **selection mask** ``sel``: row *i* exists iff
  ``sel[i]``. Filters do not compact — they refine ``sel`` (compaction is a
  gather that only happens at blocking boundaries where it pays for itself);
- ``capacity`` is drawn from power-of-two buckets so the number of distinct
  compiled XLA programs stays bounded;
- STRING/BINARY columns are dictionary-encoded: the device sees int32 codes,
  the dictionary (a pyarrow array) rides on the host-side ``Batch`` wrapper
  and never enters jitted code (keeps pytrees array-only, so jit caching
  works on shapes alone).

``DeviceBatch`` is the pytree that jitted kernels consume; ``Batch`` is the
host-side handle (schema + dictionaries + the DeviceBatch).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from auron_tpu import types as T

MIN_CAPACITY = 128

# XLA:CPU aliases (zero-copy) host buffers handed to device_put when they
# are aligned to this boundary; unaligned buffers pay a full copy. Arrow
# allocates 64-aligned, numpy only 16 — so ingestion staging allocates
# deliberately aligned buffers and eligible Arrow/numpy views upload by
# reference (docs/shuffle.md, the Zerrow zero-copy playbook).
ZERO_COPY_ALIGN = 64


def aligned_empty(n: int, dtype) -> np.ndarray:
    """Uninitialized 1-D array whose data pointer is 64-byte aligned (the
    XLA:CPU zero-copy alias requirement; harmless elsewhere)."""
    dt = np.dtype(dtype)
    raw = np.empty(n * dt.itemsize + ZERO_COPY_ALIGN, dtype=np.uint8)
    ofs = (-raw.ctypes.data) % ZERO_COPY_ALIGN
    return raw[ofs : ofs + n * dt.itemsize].view(dt)


def zero_copy_enabled(conf=None) -> bool:
    """Resolve the exec.scan.zerocopy tri-state (auto = on)."""
    from auron_tpu.utils.config import SCAN_ZEROCOPY, active_conf, resolve_tri

    c = conf if conf is not None else active_conf()
    return resolve_tri(c.get(SCAN_ZEROCOPY), True)


import threading as _threading

_plane_lock = _threading.Lock()
# shared immutable host planes: all-true bool[cap], aliased by every clean
# full batch's validity/sel instead of a fresh fill + device copy per
# column. NEVER written after creation (mutating paths allocate their own).
_TRUE_PLANES: dict[int, np.ndarray] = {}
_INGEST_STATS = {"zerocopy_planes": 0, "copied_planes": 0}


def _true_plane(cap: int) -> np.ndarray:
    with _plane_lock:
        p = _TRUE_PLANES.get(cap)
        if p is None:
            p = aligned_empty(cap, bool)
            p[:] = True
            p.setflags(write=False)
            _TRUE_PLANES[cap] = p
        return p


def _count_plane(zero_copy: bool) -> None:
    with _plane_lock:
        _INGEST_STATS["zerocopy_planes" if zero_copy else "copied_planes"] += 1


def ingest_stats() -> dict:
    """Snapshot of the zero-copy ingestion counters (tests + bench)."""
    with _plane_lock:
        return dict(_INGEST_STATS)


def reset_ingest_stats() -> None:
    with _plane_lock:
        for k in _INGEST_STATS:
            _INGEST_STATS[k] = 0


def _is_zero_copy_view(a: np.ndarray) -> bool:
    """Would device_put alias this exact buffer on the CPU backend?"""
    return bool(
        a.flags["C_CONTIGUOUS"] and a.ctypes.data % ZERO_COPY_ALIGN == 0
    )


def bucket_capacity(n: int) -> int:
    """Static-shape bucket for a batch holding n rows: next power of two."""
    c = MIN_CAPACITY
    while c < n:
        c <<= 1
    return c


def compaction_bucket(
    n_live: int, in_capacity: int,
    dense_planes: int | None = None, taken_planes: int = 0,
) -> int | None:
    """THE compaction policy shared by every sparse-output boundary (join
    chain, BHJ unique-compact and its fused stage twin, the partial
    aggregate's deferred arm): the capacity bucket to compact ``n_live``
    rows into, or None when compaction would not pay and the batch should
    stay dense at ``in_capacity``. A rule over shapes alone.

    A join says what each side of its boundary gathers: ``dense_planes``
    arrays (values and validities of its build columns) at ``in_capacity``
    where the batch stays dense, ``taken_planes`` arrays (those, the probe
    columns and the build index) at the bucket where it compacts. On the
    TPU a gathered element costs 7-10 ns whatever the table's size and
    the gather's width (an int64 plane twice that), and
    ``compaction_index`` is a pass over the mask plus one such gather at
    the bucket's width for every bit of ``in_capacity`` (PERF.md section
    5, "unit costs": measured on the v5e), so the rule there counts
    gathered elements: compact iff
    ``bucket * (index passes + taken_planes) <= in_capacity * dense_planes``
    — at two to four build planes a bucket of a sixteenth of capacity or
    less. XLA:CPU keeps the break-even measured there, a quarter of
    capacity. A caller that names no ``dense_planes`` (the aggregate, for
    which staying dense means a sort-segmented reduce at capacity, tens of
    gathers a row) keeps the quarter rule on both back ends."""
    cap = bucket_capacity(max(n_live, 1))
    if dense_planes is not None and _gather_bound():
        passes = max(in_capacity - 1, 1).bit_length()
        if cap * (passes + taken_planes) > in_capacity * dense_planes:
            return None
        return cap
    if cap * 4 > in_capacity:
        return None
    return cap


def _gather_bound() -> bool:
    """Is this a back end whose gathers cost by the output element (the
    TPU), so that compaction_bucket counts elements?"""
    from auron_tpu.jaxenv import is_tpu

    return is_tpu()


#: the static widths a small build's live key list is padded to: a width
#: is a compile shape of every probe program, so a new seed's 15 or 22
#: live keys must land on the same one
COMPARE_WIDTHS = (64, 256, 1024)


def lookup_compare_width(n_live: int, search_capacity: int | None = None) -> int | None:
    """THE lookup policy of the unique-build probe (exec/joins/core.py):
    the width of the live key list a probe row is COMPARED against, or
    None where the build keeps its gathered map. A rule over what
    ``prepare_build`` already holds on the host: the build's live key
    count, and which map it has. ``search_capacity`` None says a LUT (one
    gathered element a row, keys compared as int32 offsets of its base);
    a number says the sorted words of that capacity (a binary search: one
    gathered element a row for every bit of it, keys compared as 64-bit
    words).

    Comparing costs one compare-select a row for every slot of the list,
    on the vector unit. So: compare iff
    ``width x (a compare-select) < gathers x (a gathered element)``, at
    the least width of ``COMPARE_WIDTHS`` that holds ``n_live`` keys. The
    unit costs are the back end's (``_lookup_costs``)."""
    gather_ns, compare32_ns, compare64_ns = _lookup_costs()
    if search_capacity is None:
        map_ns, slot_ns = gather_ns, compare32_ns
    else:
        passes = max(search_capacity - 1, 1).bit_length()
        map_ns, slot_ns = passes * gather_ns, compare64_ns
    for width in COMPARE_WIDTHS:
        if n_live <= width:
            return width if width * slot_ns < map_ns else None
    return None


def _lookup_costs() -> tuple[float, float, float]:
    """(a gathered element, a compare-select a list slot a row on int32
    keys, the same on 64-bit words) in ns on this back end. The TPU
    v5e's are PERF.md section 5's unit costs (PR 31): a LUT probe 7.2-8.5
    ns a row whatever its width, a list of 1,024 slots 1.0-1.3 ns a row
    on int32 offsets and 1.2-1.7 ns on 64-bit words, so every width of
    the ladder pays there, six times over at the widest; the break-even
    lies beyond it (about 9,000 slots at 1,048,576 rows, under 4,000 at
    4,194,304, where a slot's cost doubles past 1,024 slots). XLA:CPU
    gathers an element in 4 ns and compares in 1.4-1.45 ns a slot (this
    sandbox's CPU, 262,144 rows: a LUT probe 4.5 ns a row, a search over
    32,768 rows 60 ns, a list of 64 slots 88-93 ns), so there a LUT
    always stays, and a search gives way only over a build of 2^23 rows
    and more."""
    if _gather_bound():
        return 7.2, 0.0012, 0.0016
    return 4.0, 1.45, 1.4


class DeviceBatch(NamedTuple):
    """The array-only pytree consumed by jitted kernels."""

    sel: jnp.ndarray  # bool[capacity]; row exists iff sel[i]
    values: tuple[jnp.ndarray, ...]  # one dense array per column
    validity: tuple[jnp.ndarray, ...]  # bool[capacity] per column

    @property
    def capacity(self) -> int:
        return int(self.sel.shape[0])

    def num_rows(self) -> jnp.ndarray:
        """Dynamic count of live rows (device scalar)."""
        return jnp.sum(self.sel)


@dataclass
class Batch:
    """Host-side handle: schema + dictionaries + device arrays."""

    schema: T.Schema
    device: DeviceBatch
    dicts: tuple[pa.Array | None, ...]  # per column; non-None iff dict-encoded

    # ---- construction ----

    @staticmethod
    def from_arrow(rb: pa.RecordBatch, capacity: int | None = None,
                   conf=None) -> "Batch":
        schema = T.Schema.from_arrow(rb.schema)
        n = rb.num_rows
        cap = capacity or bucket_capacity(n)
        assert cap >= n, (cap, n)
        zc = zero_copy_enabled(conf)
        values, validity, dicts = [], [], []
        for i, f in enumerate(schema):
            arr = rb.column(i)
            v, m, d = _arrow_to_host(arr, f.dtype, cap, zc=zc)
            values.append(v)
            validity.append(m)
            dicts.append(d)
        return _seal_batch(schema, values, validity, dicts, n, cap, zc=zc)

    @staticmethod
    def from_pandas(df, schema: T.Schema | None = None,
                    capacity: int | None = None, conf=None) -> "Batch":
        """Ingest a pandas DataFrame without the Arrow round-trip for numeric
        columns: nullable-array data/mask buffers are viewed directly and
        null lanes zeroed in one vectorized pass; strings/decimals/nested
        fall back to the per-column Arrow path. One batched device transfer.
        (The reference's scan hands the engine materialized columnar buffers
        the same way — native-engine/datafusion-ext-plans scan path.)

        Under exec.scan.zerocopy, full clean numeric columns upload by
        buffer ALIAS on the CPU backend (no copy at all): the caller's
        frame must stay immutable while batches built from it are live —
        the same contract Arrow buffers already carry. exec.scan.zerocopy
        =off restores the copying upload."""
        from pandas.core.arrays.masked import BaseMaskedArray

        if schema is None:
            # infer over the whole frame (first-row-only inference would
            # type an object column with a leading null as Arrow null)
            schema = T.Schema.from_arrow(
                pa.Schema.from_pandas(df, preserve_index=False))
        n = len(df)
        cap = capacity or bucket_capacity(n)
        assert cap >= n, (cap, n)
        zc = zero_copy_enabled(conf)
        numeric = (T.TypeKind.BOOL, T.TypeKind.INT8, T.TypeKind.INT16,
                   T.TypeKind.INT32, T.TypeKind.INT64,
                   T.TypeKind.FLOAT32, T.TypeKind.FLOAT64)
        values, validity, dicts = [], [], []
        for f in schema:
            col = df[f.name]
            phys = np.dtype(f.dtype.physical_dtype().name)
            vals = valid = None
            d = None
            if not f.dtype.is_dict_encoded and f.dtype.kind in numeric:
                arr = col.array
                if isinstance(arr, BaseMaskedArray):
                    invalid = arr._mask
                    vals = arr._data
                    if invalid.any():
                        valid = ~invalid
                        vals = np.where(valid, vals, vals.dtype.type(0))
                elif isinstance(col.dtype, np.dtype) and col.dtype.kind in "biuf":
                    vals = col.to_numpy(copy=False)
                    if np.issubdtype(vals.dtype, np.floating):
                        invalid = np.isnan(vals)
                        if invalid.any():
                            valid = ~invalid
                            vals = np.where(valid, vals, 0.0)
            elif (not f.dtype.is_dict_encoded
                  and f.dtype.kind == T.TypeKind.TIMESTAMP
                  and isinstance(col.dtype, np.dtype)
                  and col.dtype.kind == "M"):
                raw = col.to_numpy(copy=False)
                invalid = np.isnat(raw)
                vals = raw.astype("datetime64[us]").astype(np.int64)
                if invalid.any():
                    valid = ~invalid
                    vals = np.where(valid, vals, 0)
            if vals is not None:
                if zc and valid is None and n == cap:
                    m = _true_plane(cap)
                else:
                    mask_np = aligned_empty(cap, bool) if zc else np.empty(cap, dtype=bool)
                    if valid is None:
                        mask_np[:n] = True
                    else:
                        mask_np[:n] = valid
                    mask_np[n:] = False
                    m = mask_np
                v = _pad_to_cap(vals.astype(phys, copy=False), cap, phys, zc=zc)
            else:
                a = pa.Array.from_pandas(col)
                v, m, d = _arrow_to_host(a, f.dtype, cap, zc=zc)
            values.append(v)
            validity.append(m)
            dicts.append(d)
        return _seal_batch(schema, values, validity, dicts, n, cap, zc=zc)

    @staticmethod
    def from_pydict(data: dict, schema: T.Schema | None = None, capacity: int | None = None) -> "Batch":
        if schema is not None:
            rb = pa.record_batch(
                [pa.array(data[f.name], type=f.dtype.to_arrow()) for f in schema],
                names=[f.name for f in schema],
            )
        else:
            rb = pa.RecordBatch.from_pydict(data)
        return Batch.from_arrow(rb, capacity)

    @staticmethod
    def empty(schema: T.Schema, capacity: int = MIN_CAPACITY) -> "Batch":
        values = tuple(
            jnp.zeros(capacity, dtype=f.dtype.physical_dtype()) for f in schema
        )
        validity = tuple(jnp.zeros(capacity, dtype=bool) for _ in schema)
        sel = jnp.zeros(capacity, dtype=bool)
        dicts = tuple(
            (_empty_dict(f.dtype) if f.dtype.is_dict_encoded else None)
            for f in schema
        )
        return Batch(schema, DeviceBatch(sel, values, validity), dicts)

    # ---- accessors ----

    @property
    def capacity(self) -> int:
        return self.device.capacity

    def num_rows(self) -> int:
        """Live row count — host sync."""
        # auronlint: disable=R9 -- caller-owned count-read API by design: converting to N/batch would mis-promise plans stacking several count-reading operators; rate stays visible per-caller in profiling
        return int(jax.device_get(self.device.num_rows()))  # auronlint: sync-point(call) -- num_rows() IS the engine's count-read API

    def col_values(self, i: int) -> jnp.ndarray:
        return self.device.values[i]

    def col_validity(self, i: int) -> jnp.ndarray:
        return self.device.validity[i]

    def with_device(self, dev: DeviceBatch, schema: T.Schema | None = None,
                    dicts: tuple | None = None) -> "Batch":
        return Batch(schema or self.schema, dev,
                     dicts if dicts is not None else self.dicts)

    def on_device(self, device) -> "Batch":
        """This batch with its planes on ``device``: itself where they lie
        there already, else a copy committed to it: a table's split placed
        on its chip, a build side's copy a chip, a stage's outputs gathered
        for the collect stage."""
        if self.device.sel.devices() == {device}:
            return self
        return Batch(self.schema, jax.device_put(self.device, device),
                     self.dicts)

    def prefetch_host(self) -> None:
        """Start non-blocking device->host copies of every array so a later
        ``to_arrow`` finds the data already landed (the task pump calls
        this for host-FFI consumers — the copy overlaps the NEXT batch's
        device compute instead of stalling inside ``device_get``)."""
        from auron_tpu.runtime.transfer import start_host_transfer

        dev = self.device
        start_host_transfer(dev.sel, *dev.values, *dev.validity)
        self._host_prefetched = True

    # ---- materialization ----

    def to_arrow(self, compact: bool = True,
                 preserve_dicts: bool = False) -> pa.RecordBatch:
        """Pull to host as an Arrow RecordBatch (live rows only).

        ``preserve_dicts=True`` keeps dict-encoded columns as Arrow
        DictionaryArrays (codes + one dictionary) instead of materializing
        values per row — the engine-to-engine interchange mode used by
        shuffle/spill, where the reader re-ingests codes directly. The
        default materializes, for external consumers (JVM sink, pandas)."""
        if getattr(self, "_host_prefetched", False):
            # the pump started this copy batches ago (prefetch_host):
            # account the landing as an async harvest, not a stall
            from auron_tpu.utils.profiling import async_read_scope

            with async_read_scope():
                dev = jax.device_get(self.device)  # auronlint: sync-point(1/batch) -- prefetched host materialization harvest (async-accounted)
        else:
            # auronlint: sync-point(call) -- to_arrow materializes for external consumers; one transfer for the whole pytree
            dev = jax.device_get(self.device)
        sel = np.asarray(dev.sel)
        idx = np.nonzero(sel)[0] if compact else np.arange(self.capacity)
        return host_rows_to_arrow(self.schema, self.dicts, dev.values,
                                  dev.validity, idx,
                                  preserve_dicts=preserve_dicts)

    def to_pydict(self) -> dict:
        return self.to_arrow().to_pydict()

    def to_pandas(self):
        return self.to_arrow().to_pandas()


# ---------------------------------------------------------------------------
# Arrow <-> device conversion
# ---------------------------------------------------------------------------


def _empty_dict(dtype: T.DataType) -> pa.Array:
    """One-entry sentinel dictionary (code 0 must always be decodable)."""
    if dtype.kind == T.TypeKind.BINARY:
        return pa.array([b""], type=pa.binary())
    if dtype.kind == T.TypeKind.DECIMAL:
        import decimal as pydec

        return pa.array([pydec.Decimal(0)], type=dtype.to_arrow())
    if dtype.kind == T.TypeKind.STRUCT:
        return pa.array(
            [{n: None for n in dtype.struct_names}], type=dtype.to_arrow()
        )
    if dtype.kind in (T.TypeKind.LIST, T.TypeKind.MAP):
        return pa.array([[]], type=dtype.to_arrow())
    return pa.array([""], type=pa.string())


def _vocab_key(v):
    """Hashable key for arbitrary dictionary values (lists -> tuples)."""
    if isinstance(v, list):
        return tuple(_vocab_key(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _vocab_key(x)) for k, x in v.items()))
    return v


def host_rows_to_arrow(schema: T.Schema, dicts, values, validity, idx,
                       preserve_dicts: bool = False) -> pa.RecordBatch:
    """Arrow RecordBatch from HOST-resident column arrays gathered at
    ``idx`` — the shared tail of Batch.to_arrow and the shuffle writer's
    host-clustering path (one conversion loop so preserve_dicts semantics
    can't drift between them)."""
    arrays = []
    for i, f in enumerate(schema):
        vals = np.asarray(values[i])[idx]
        mask = np.asarray(validity[i])[idx]
        arrays.append(_device_to_arrow(vals, mask, f.dtype, dicts[i],
                                       preserve_dicts=preserve_dicts))
    if preserve_dicts:
        # array types may be dictionary<...> where the declared schema
        # says the logical value type; let Arrow carry the actual types
        return pa.RecordBatch.from_arrays(
            arrays, names=[f.name for f in schema])
    return pa.RecordBatch.from_arrays(arrays, schema=schema.to_arrow())


def _seal_batch(schema, values, validity, dicts, n: int, cap: int,
                zc: bool = False) -> "Batch":
    """Finish ingestion: build the selection mask and ship the whole pytree
    in one batched device transfer (not 2 dispatches per column). Under
    zero-copy, aligned host planes in the pytree ALIAS into device arrays
    on the CPU backend instead of copying, and a full batch's sel is the
    shared all-true plane."""
    if zc and n == cap:
        sel = _true_plane(cap)
    else:
        sel = aligned_empty(cap, bool) if zc else np.empty(cap, dtype=bool)
        sel[:n] = True
        sel[n:] = False
    sel, values, validity = jax.device_put((sel, tuple(values), tuple(validity)))
    return Batch(schema, DeviceBatch(sel, values, validity), tuple(dicts))


def _pad_to_cap(a_np: np.ndarray, cap: int, phys: np.dtype,
                zc: bool = False) -> np.ndarray:
    """Pad to capacity zeroing only the dead tail (one write pass, not two).
    A full already-typed plane passes through as a view (zero-copy when the
    underlying buffer is aligned); padding allocates aligned staging under
    zero-copy so the device transfer aliases instead of copying."""
    n = len(a_np)
    if n == cap and a_np.dtype == phys:
        out = np.ascontiguousarray(a_np)
        if zc:
            _count_plane(_is_zero_copy_view(out))
        return out
    out = aligned_empty(cap, phys) if zc else np.empty(cap, dtype=phys)
    out[:n] = a_np
    if n < cap:
        out[n:] = 0
    if zc:
        _count_plane(False)
    return out


def _arrow_to_device(arr: pa.Array, dtype: T.DataType, cap: int):
    """Returns (values jnp[cap], validity jnp[cap] bool, dict or None)."""
    v, m, d = _arrow_to_host(arr, dtype, cap)
    return jnp.asarray(v), jnp.asarray(m), d


def _arrow_to_host(arr: pa.Array, dtype: T.DataType, cap: int,
                   zc: bool = False):
    """Returns (values np[cap], validity np[cap] bool, dict or None) — the
    host-side half of ingestion, so callers can batch the device transfer.

    ``zc``: zero-copy mode (exec.scan.zerocopy). Validity-clean full
    fixed-width planes stay VIEWS of the Arrow buffers (64-aligned by
    Arrow's allocator, so the device transfer aliases them on CPU), their
    validity is the shared all-true plane, and any staging this function
    does allocate is aligned. Arrow chunking, nulls, casts and bit-packed
    BOOL still force the copy path — exactly the cases the format forces."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    nulls = arr.null_count if n else 0
    # the DECIMAL branch below can retract validity (unscaled overflow ->
    # NULL), so it must never write into the shared all-true plane
    if zc and nulls == 0 and n == cap and dtype.kind != T.TypeKind.DECIMAL:
        mask_np = _true_plane(cap)
    else:
        mask_np = aligned_empty(cap, bool) if zc else np.empty(cap, dtype=bool)
        if nulls:
            mask_np[:n] = pc.is_valid(arr).to_numpy(zero_copy_only=False)
        else:
            mask_np[:n] = True
        mask_np[n:] = False
    phys = np.dtype(dtype.physical_dtype().name)
    d: pa.Array | None = None

    if dtype.kind in (T.TypeKind.LIST, T.TypeKind.MAP, T.TypeKind.STRUCT):
        # nested values ride as identity codes into a per-batch dictionary
        vals_np = _pad_to_cap(np.arange(n, dtype=phys), cap, phys, zc=zc)
        d = arr
        if len(d) == 0:
            d = _empty_dict(dtype)
        return vals_np, mask_np, d
    if dtype.is_dict_encoded:
        if pa.types.is_dictionary(arr.type):
            denc = arr
        elif dtype.kind == T.TypeKind.DECIMAL:
            # wide decimal: exact Decimal128 dictionary, codes on device
            wide = arr.cast(pa.decimal128(dtype.precision, dtype.scale))
            denc = pc.dictionary_encode(wide.fill_null(0))
        else:
            # encode first, then fill nulls on the cheap int32 indices: null
            # rows get code 0 with validity False (value never observed)
            denc = pc.dictionary_encode(arr)
        idx = denc.indices
        if idx.null_count:
            idx = idx.fill_null(0)
        codes = idx.to_numpy(zero_copy_only=False).astype(np.int32, copy=False)
        vals_np = _pad_to_cap(codes, cap, phys, zc=zc)
        d = denc.dictionary
        if pa.types.is_large_string(d.type):
            d = d.cast(pa.string())
        elif pa.types.is_large_binary(d.type):
            d = d.cast(pa.binary())
        if len(d) == 0:
            d = _empty_dict(dtype)
    elif dtype.kind == T.TypeKind.DECIMAL:
        # scaled int64 ("unscaled value"): decimal128 -> int64. Values whose
        # unscaled magnitude exceeds int64 (possible for p>18) become NULL —
        # matching Spark's non-ANSI overflow-to-null behavior rather than
        # crashing ingestion (documented decimal64 limitation, types.py).
        ints, fits = _decimal_unscaled(arr, dtype.scale)
        if not fits.all():
            np.logical_and(mask_np[:n], fits, out=mask_np[:n])
        vals_np = _pad_to_cap(ints, cap, phys, zc=zc)
    elif dtype.kind == T.TypeKind.TIMESTAMP:
        a = arr.cast(pa.timestamp("us"))
        if a.null_count:
            a = a.fill_null(0)
        raw = a.to_numpy(zero_copy_only=False)
        if raw.dtype != np.dtype("datetime64[us]"):
            raw = raw.astype("datetime64[us]")
        # same-width reinterpret, not astype: keeps the clean full-batch
        # plane a view of the Arrow buffer (zero-copy eligible)
        vals_np = _pad_to_cap(raw.view(np.int64), cap, phys, zc=zc)
    elif dtype.kind == T.TypeKind.DATE32:
        a = arr.cast(pa.int32())
        if a.null_count:
            a = a.fill_null(0)
        vals_np = _pad_to_cap(a.to_numpy(zero_copy_only=False), cap, phys, zc=zc)
    elif dtype.kind == T.TypeKind.NULL:
        vals_np = np.zeros(cap, dtype=phys)
    else:
        a = arr if arr.type == dtype.to_arrow() else arr.cast(dtype.to_arrow())
        if a.null_count:
            a = a.fill_null(T.numpy_zero(dtype))
        vals_np = _pad_to_cap(a.to_numpy(zero_copy_only=False), cap, phys, zc=zc)
    return vals_np, mask_np, d


def _decimal_unscaled(arr: pa.Array, scale: int):
    """(unscaled int64[n], fits bool[n]) of a decimal Arrow array at
    ``scale``, in one pass over the Decimal128 buffer: a value is two
    little-endian 64-bit words, and it fits int64 exactly when the high
    word is the sign extension of the low one. NULL slots and values that
    do not fit read 0; ``fits`` is False for the latter alone."""
    wide = arr.cast(pa.decimal128(38, scale))
    n = len(wide)
    buf = wide.buffers()[1]
    if n == 0 or buf is None:
        return np.zeros(n, dtype=np.int64), np.ones(n, dtype=bool)
    words = np.frombuffer(buf, dtype="<i8", count=2 * n,
                          offset=16 * wide.offset).reshape(n, 2)
    lo, hi = words[:, 0], words[:, 1]
    fits = hi == (lo >> 63)
    keep = fits
    if wide.null_count:
        valid = pc.is_valid(wide).to_numpy(zero_copy_only=False)
        keep = fits & valid
        fits = fits | ~valid
    return np.where(keep, lo, 0), fits


def _decimal_from_unscaled(vals: np.ndarray, mask: np.ndarray, dtype: T.DataType) -> pa.Array:
    """``decimal128(p, s)`` of unscaled int64 values, written as the
    Decimal128 buffer itself (``_decimal_unscaled``'s inverse): a value is
    two little-endian 64-bit words, the high one the sign extension of the
    low one. One pass, no Python object a cell: the shuffle writer hands a
    partial aggregate's DECIMAL sums through here by the 10^5 rows."""
    n = len(vals)
    words = np.empty((n, 2), dtype="<i8")
    words[:, 0] = np.where(mask, vals, 0)
    words[:, 1] = words[:, 0] >> 63
    valid = np.packbits(np.asarray(mask, dtype=bool), bitorder="little")
    return pa.Array.from_buffers(
        pa.decimal128(dtype.precision, dtype.scale), n,
        [pa.py_buffer(valid), pa.py_buffer(words)], null_count=int(n - mask.sum()))


def host_arrow_cols(cvs) -> list[pa.Array]:
    """Materialize column values (ColumnVal-shaped: .values/.validity/
    .dtype/.dict) as host arrow arrays for host-evaluation contracts
    (UDF/UDTF fallbacks, dictionary-transforming functions) — ONE batched
    device transfer for every column."""
    # auronlint: disable=R9 -- host-evaluation contract: the transfer rate equals the number of host-evaluated expressions the PLAN carries, owned by the expression tree, not an engine loop
    moved = jax.device_get(tuple((cv.values, cv.validity) for cv in cvs))  # auronlint: sync-point(call) -- host-evaluation contract; one batched transfer for all columns
    return [
        _device_to_arrow(np.asarray(v), np.asarray(m), cv.dtype, cv.dict)
        for cv, (v, m) in zip(cvs, moved)
    ]


def _device_to_arrow(vals: np.ndarray, mask: np.ndarray, dtype: T.DataType,
                     d: pa.Array | None, preserve_dicts: bool = False) -> pa.Array:
    k = dtype.kind
    if dtype.is_dict_encoded:
        assert d is not None
        codes = np.where(mask, vals, 0).astype(np.int32)
        if (preserve_dicts
                and k not in (T.TypeKind.LIST, T.TypeKind.MAP,
                              T.TypeKind.STRUCT)
                and len(d) <= 4096):
            # preserve only SMALL dictionaries (group-key-like columns):
            # every downstream per-partition slice carries the whole
            # dictionary, so a near-unique string column would blow up
            # staged-bytes accounting and write the dict once per slice —
            # materializing is cheaper there
            idx = pa.array(codes, type=pa.int32(), mask=~mask)
            return pa.DictionaryArray.from_arrays(idx, d)
        taken = d.take(pa.array(codes, type=pa.int32()))
        if k in (T.TypeKind.LIST, T.TypeKind.MAP, T.TypeKind.STRUCT):
            pl = taken.to_pylist()
            return pa.array(
                [v if m else None for v, m in zip(pl, mask)], type=dtype.to_arrow()
            )
        return pc.if_else(pa.array(mask), taken, pa.scalar(None, type=taken.type)).cast(
            dtype.to_arrow()
        )
    if k == T.TypeKind.DECIMAL:
        return _decimal_from_unscaled(vals, mask, dtype)
    if k == T.TypeKind.TIMESTAMP:
        return pa.array(vals.astype("datetime64[us]"), mask=~mask)
    if k == T.TypeKind.DATE32:
        return pa.array(vals.astype(np.int32), mask=~mask).cast(pa.date32())
    if k == T.TypeKind.NULL:
        return pa.nulls(len(vals))
    if k == T.TypeKind.BOOL:
        return pa.array(vals.astype(bool), mask=~mask)
    return pa.array(vals, mask=~mask).cast(dtype.to_arrow())


# ---------------------------------------------------------------------------
# Batch-level utilities
# ---------------------------------------------------------------------------


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Concatenate live rows of several batches into one (host-side gather).

    Used at blocking boundaries (sort/agg/join build). Dictionary columns are
    unified. Analog of the reference's coalesce/staging steps
    (common/execution_context.rs:146).
    """
    assert batches
    schema = batches[0].schema
    tables = [b.to_arrow() for b in batches]
    tbl = pa.Table.from_batches(tables, schema=schema.to_arrow())
    combined = tbl.combine_chunks()
    if combined.num_rows == 0:
        return Batch.empty(schema)
    rb = combined.to_batches()[0]
    return Batch.from_arrow(rb)


@jax.jit
def device_take(dev: DeviceBatch, order: jnp.ndarray) -> DeviceBatch:
    """Permute every column of a DeviceBatch by an index array in ONE fused
    program — the shared kernel behind sorted-run finalization, shuffle pid
    clustering and join-build clustering (keep ONE definition so gather
    semantics—clamping, index dtype, shardings—can't drift apart)."""
    return DeviceBatch(
        sel=dev.sel[order],
        values=tuple(v[order] for v in dev.values),
        validity=tuple(m[order] for m in dev.validity),
    )


@partial(jax.jit, static_argnames=("pad",))
def _device_concat_jit(sels, cols, masks, remaps, pad: int):
    """Fused multi-batch concatenation: every column of every input lands
    in the padded output in ONE compiled program (the eager per-column
    concat+pad chain was a measured sink on fact-sized join builds).
    ``remaps`` maps column index -> per-batch dict-code remap tables."""

    def cat(parts):
        out = jnp.concatenate(parts)
        return jnp.pad(out, (0, pad)) if pad else out

    sel = cat(sels)
    values = []
    validity = []
    for ci, (vs, ms) in enumerate(zip(cols, masks)):
        if remaps is not None and ci in remaps:
            vs = [
                r[jnp.clip(v, 0, r.shape[0] - 1)]
                for v, r in zip(vs, remaps[ci])
            ]
        values.append(cat(vs))
        validity.append(cat(ms))
    return sel, tuple(values), tuple(validity)


def device_concat(batches: Sequence[Batch]) -> Batch:
    """Concatenate batches on device without an Arrow round-trip.

    Output capacity is the sum of input capacities (dead rows keep sel=0).
    Dictionary columns are unified host-side (O(total dict size)) and codes
    remapped with one device gather per batch. This is the blocking-boundary
    concat used by aggregation/sort/join accumulation.
    """
    assert batches
    if len(batches) == 1:
        return batches[0]
    schema = batches[0].schema
    ncols = len(schema)
    new_dicts: list[pa.Array | None] = [None] * ncols
    remaps_by_col: dict[int, tuple] = {}
    for ci, f in enumerate(schema):
        if f.dtype.is_dict_encoded:
            unified, remaps = unify_dict(batches, ci)
            new_dicts[ci] = unified
            remaps_by_col[ci] = tuple(jnp.asarray(r) for r in remaps)
    total = sum(b.capacity for b in batches)
    cap = bucket_capacity(total)  # pad to a bucket so downstream jitted
    pad = cap - total  # programs see few distinct shapes
    sel, values, validity = _device_concat_jit(
        tuple(b.device.sel for b in batches),
        tuple(tuple(b.col_values(ci) for b in batches) for ci in range(ncols)),
        tuple(tuple(b.col_validity(ci) for b in batches) for ci in range(ncols)),
        # dict keyed by static column index must itself be hashable-stable
        # for jit: pass as a plain dict pytree (keys sort deterministically)
        remaps_by_col or None,
        pad=pad,
    )
    return Batch(schema, DeviceBatch(sel, values, validity), tuple(new_dicts))


from functools import partial as _partial


#: row length of _running_count's first level
_COUNT_BLOCK = 1024


def _running_count(sel: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running count of the mask (int32), in two levels: within
    rows of 1,024, then across the rows' totals. The same integers as one
    flat cumsum, which the TPU's compiler takes 25 s over at 1,048,576
    elements (12 s at 524,288, 4 s at 4,194,304) in every program that
    holds a compaction index; this form compiles in half a second
    (PERF.md, PR 29)."""
    ones = sel.astype(jnp.int32)
    if ones.shape[0] <= _COUNT_BLOCK:  # buckets are powers of two
        return jnp.cumsum(ones)
    rows = jnp.cumsum(ones.reshape(-1, _COUNT_BLOCK), axis=1)
    totals = rows[:, -1]
    return (rows + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)


def compaction_index(sel: jnp.ndarray, out_cap: int):
    """(idx[out_cap], sel_out[out_cap]): positions of the live rows, via
    cumsum + branchless binary search. Gather-based on purpose — XLA:CPU
    lowers scatters to serial loops (the platform even advertises
    prefer-no-scatter), while the log2(cap) searchsorted passes vectorize."""
    cap = sel.shape[0]
    pos = _running_count(sel)
    idx = jnp.searchsorted(
        pos, jnp.arange(1, out_cap + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    idx = jnp.clip(idx, 0, cap - 1)
    sel_out = jnp.arange(out_cap, dtype=jnp.int32) < pos[-1]
    return idx, sel_out


@_partial(jax.jit, static_argnames=("out_cap", "cols"))
def _compact_dev(
    dev: DeviceBatch, out_cap: int, cols: tuple[int, ...] | None = None,
) -> DeviceBatch:
    """Gather live rows into a dense prefix of a smaller buffer (O(n) +
    O(out log n), no sort). Used when selectivity collapses a batch
    (post-filter/join) so blocking ops (sort-segmentation, exchange pulls)
    pay for live rows only. ``cols`` names the columns to gather (None:
    all); any other comes back all NULL, a fill and not a gather."""
    idx, sel_out = compaction_index(dev.sel, out_cap)
    taken = range(len(dev.values)) if cols is None else cols
    # values first, then validities: with every column taken the program is
    # the one compiled before ``cols`` existed (a persistent-cache hit)
    values = tuple(
        v[idx] if ci in taken else jnp.zeros((out_cap,) + v.shape[1:], v.dtype)
        for ci, v in enumerate(dev.values)
    )
    validity = tuple(
        m[idx] & sel_out if ci in taken else jnp.zeros(out_cap, bool)
        for ci, m in enumerate(dev.validity)
    )
    return DeviceBatch(sel_out, values, validity)


def compact_batch(
    batch: Batch, out_capacity: int, cols: tuple[int, ...] | None = None,
) -> Batch:
    """Compact live rows into a batch of ``out_capacity`` slots (must be
    >= the live count — callers size it from a synced row count). A
    consumer that reads only some columns names them in ``cols``: the
    rest are not gathered and come back all NULL."""
    if out_capacity >= batch.capacity:
        return batch
    return Batch(
        batch.schema, _compact_dev(batch.device, out_capacity, cols), batch.dicts
    )


def prefix_slice(batch: Batch, new_capacity: int) -> Batch:
    """Keep only the first new_capacity slots (used to shrink prefix-packed
    group states back to a small capacity bucket)."""
    if new_capacity >= batch.capacity:
        return batch
    dev = batch.device
    return Batch(
        batch.schema,
        DeviceBatch(
            dev.sel[:new_capacity],
            tuple(v[:new_capacity] for v in dev.values),
            tuple(m[:new_capacity] for m in dev.validity),
        ),
        batch.dicts,
    )


def merge_vocab(
    entry_lists: Sequence[list], dtype: T.DataType
) -> tuple[pa.Array, list[np.ndarray]]:
    """Merge per-source dictionary entry lists into ONE vocabulary.

    Returns (unified_dict, per-source remap tables): new_code =
    remaps[src][old_code]. The single shared merge used by in-process
    unification (unify_dict) AND the SPMD cross-process exchange
    (mesh_driver._unify_dicts_global) — dict-type handling must never
    diverge between the two."""
    vocab: dict = {}
    values: list = []
    remaps: list[np.ndarray] = []
    for pylist in entry_lists:
        r = np.empty(len(pylist), dtype=np.int32)
        for i, s in enumerate(pylist):
            k = _vocab_key(s)
            if k in vocab:
                r[i] = vocab[k]
            else:
                r[i] = vocab[k] = len(values)
                values.append(s)
        remaps.append(r)
    if dtype.kind in (T.TypeKind.LIST, T.TypeKind.MAP, T.TypeKind.STRUCT,
                      T.TypeKind.DECIMAL):
        value_type = dtype.to_arrow()
    elif dtype.kind == T.TypeKind.BINARY:
        value_type = pa.binary()
    else:
        value_type = pa.string()
    unified = pa.array(values, type=value_type) if values else _empty_dict(dtype)
    return unified, remaps


def unify_dict(batches: Sequence[Batch], col: int) -> tuple[pa.Array, list[np.ndarray]]:
    """Build a unified dictionary for column `col` across batches.

    Returns (unified_dict, per-batch code remap tables). The remap table
    ``r`` satisfies: new_code = r[old_code]. Device-side remapping is then a
    single gather.
    """
    entry_lists = []
    for b in batches:
        d = b.dicts[col]
        assert d is not None
        entry_lists.append(d.to_pylist())
    return merge_vocab(entry_lists, batches[0].schema[col].dtype)
