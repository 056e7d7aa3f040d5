"""Expression evaluator: IR trees -> jnp columnar programs.

Evaluates ``exprs.ir`` trees over a ``Batch``, producing per-expression
``ColumnVal`` (values + validity + dtype + optional dictionary). Device math
is pure jnp; dictionary-encoded strings are handled by transforming the
*dictionary* host-side (small) and gathering by code on device — so string
equality/ordering/LIKE/casts stay on the TPU data path with only O(|dict|)
host work (analog of how the reference hashes/compares dictionary arrays,
spark_hash.rs:228-249).

Common subexpressions are evaluated once per batch via a structural memo —
the analog of the reference's CachedExprsEvaluator
(datafusion-ext-plans/src/common/cached_exprs_evaluator.rs). SQL
three-valued logic: AND/OR use Kleene semantics, arithmetic propagates
NULLs, division/modulo by zero produce NULL (Spark non-ANSI), decimal
overflow produces NULL via the checked kernels in decimal_math.py.
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import Batch
from auron_tpu.exprs import cast as C
from auron_tpu.exprs import decimal_math as D
from auron_tpu.exprs import ir


@dataclass
class ColumnVal:
    values: jnp.ndarray
    validity: jnp.ndarray
    dtype: T.DataType
    dict: pa.Array | None = None  # set iff dtype.is_dict_encoded


class Evaluator:
    def __init__(
        self,
        schema: T.Schema,
        partition_id: int | None = None,
        row_offset: int = 0,
        resources: dict | None = None,
    ):
        self.schema = schema
        if partition_id is None or resources is None:
            # default to the executing task's context (exec/base.py) so
            # partition-context expressions work at every evaluation site
            from auron_tpu.exec.base import current_context

            # cross-thread callers (the sort-spill run path) pass
            # partition_id + resources explicitly, so this thread-local
            # fallback only ever runs on the operator's own pump thread
            ctx = current_context()  # auronlint: disable=R7 -- guarded fallback: spill-reachable callers (sort_exec._sort_run) thread ctx explicitly
            if partition_id is None:
                partition_id = ctx.partition_id if ctx is not None else 0
            if resources is None and ctx is not None:
                resources = ctx.resources
        self.partition_id = partition_id
        self.row_offset = row_offset  # live rows already emitted upstream
        self.resources = resources or {}

    # ---- public ----

    def evaluate(self, batch: Batch, exprs: list[ir.Expr]) -> list[ColumnVal]:
        memo: dict = {}
        return [self._eval(e, batch, memo) for e in exprs]

    # ---- core dispatch ----

    def _eval(self, e: ir.Expr, b: Batch, memo: dict) -> ColumnVal:
        key = e
        try:
            if key in memo:
                return memo[key]
        except TypeError:  # unhashable (shouldn't happen, all nodes frozen)
            key = None
        out = self._eval_uncached(e, b, memo)
        if key is not None:
            memo[key] = out
        return out

    def _eval_uncached(self, e: ir.Expr, b: Batch, memo: dict) -> ColumnVal:
        if isinstance(e, ir.Column):
            f = self.schema[e.index]
            return ColumnVal(
                b.col_values(e.index), b.col_validity(e.index), f.dtype, b.dicts[e.index]
            )
        if isinstance(e, ir.Literal):
            return self._literal(e, b.capacity)
        if isinstance(e, ir.Cast):
            return self._cast(self._eval(e.child, b, memo), e.to)
        if isinstance(e, ir.BinaryOp):
            return self._binary(e, b, memo)
        if isinstance(e, ir.Not):
            c = self._eval(e.child, b, memo)
            return ColumnVal(~c.values.astype(bool), c.validity, T.BOOL)
        if isinstance(e, ir.IsNull):
            c = self._eval(e.child, b, memo)
            return ColumnVal(~c.validity, jnp.ones_like(c.validity), T.BOOL)
        if isinstance(e, ir.IsNotNull):
            c = self._eval(e.child, b, memo)
            return ColumnVal(c.validity, jnp.ones_like(c.validity), T.BOOL)
        if isinstance(e, ir.If):
            return self._case([(e.cond, e.then)], e.orelse, b, memo)
        if isinstance(e, ir.Case):
            return self._case(list(e.branches), e.orelse, b, memo)
        if isinstance(e, ir.Coalesce):
            return self._coalesce([self._eval(a, b, memo) for a in e.args])
        if isinstance(e, ir.In):
            return self._in(e, b, memo)
        if isinstance(e, ir.Like):
            return self._like(e, b, memo)
        if isinstance(e, ir.ScalarFunc):
            from auron_tpu.functions import registry

            args = [self._eval(a, b, memo) for a in e.args]
            return registry.dispatch(e.name, args, b.capacity)
        if isinstance(e, ir.HostUDF):
            return self._host_udf(e, b, memo)
        if isinstance(e, ir.SparkPartitionId):
            return ColumnVal(
                jnp.full(b.capacity, jnp.int32(self.partition_id)),
                jnp.ones(b.capacity, bool), T.INT32,
            )
        if isinstance(e, ir.MonotonicId):
            pos = jnp.cumsum(b.device.sel.astype(jnp.int64)) - 1
            base = jnp.int64(self.partition_id) << jnp.int64(33)
            return ColumnVal(
                base + self.row_offset + jnp.maximum(pos, 0),
                jnp.ones(b.capacity, bool), T.INT64,
            )
        if isinstance(e, ir.RowNum):
            pos = jnp.cumsum(b.device.sel.astype(jnp.int64))
            return ColumnVal(
                self.row_offset + pos, jnp.ones(b.capacity, bool), T.INT64
            )
        if isinstance(e, ir.ScalarSubquery):
            if e.resource_id not in self.resources:
                raise KeyError(
                    f"scalar subquery value '{e.resource_id}' not in the task "
                    "resource map (host engine must ship it before the task runs)"
                )
            value = self.resources[e.resource_id]
            return self._literal(ir.Literal(value, e.dtype), b.capacity)
        raise TypeError(f"unsupported expression {type(e).__name__}")

    def _host_udf(self, e: ir.HostUDF, b: Batch, memo: dict) -> ColumnVal:
        """Materialize args to Arrow, call the bridge callback, re-ingest."""
        from auron_tpu.bridge.udf import lookup_udf
        from auron_tpu.columnar.batch import _arrow_to_device, host_arrow_cols

        args = [self._eval(a, b, memo) for a in e.args]
        cap = b.capacity
        # host UDF evaluates on host by contract; host_arrow_cols makes the
        # one batched transfer for all args
        host_args = host_arrow_cols(args)
        result = lookup_udf(e.name)(host_args, cap)
        assert len(result) == cap, "host UDF must return one value per slot"
        v, m, d = _arrow_to_device(result, e.out_dtype, cap)
        return ColumnVal(v, m, e.out_dtype, d)

    # ---- literals ----

    def _literal(self, e: ir.Literal, cap: int) -> ColumnVal:
        dt = e.dtype
        if e.value is None or dt.kind == T.TypeKind.NULL:
            phys = dt.physical_dtype() if dt.kind != T.TypeKind.NULL else jnp.int8
            return ColumnVal(
                jnp.zeros(cap, phys), jnp.zeros(cap, bool), dt,
                _single_dict(dt, None) if dt.is_dict_encoded else None,
            )
        if dt.is_dict_encoded:
            return ColumnVal(
                jnp.zeros(cap, jnp.int32), jnp.ones(cap, bool), dt,
                _single_dict(dt, e.value),
            )
        if dt.kind == T.TypeKind.DECIMAL:
            import decimal as pd

            u = int(pd.Decimal(str(e.value)).scaleb(dt.scale).quantize(pd.Decimal(1)))
            v = jnp.full(cap, jnp.int64(u))
        elif dt.kind == T.TypeKind.BOOL:
            v = jnp.full(cap, bool(e.value))
        else:
            v = jnp.full(cap, e.value, dtype=dt.physical_dtype())
        return ColumnVal(v, jnp.ones(cap, bool), dt)

    # ---- casts ----

    def _cast(self, c: ColumnVal, to: T.DataType) -> ColumnVal:
        if c.dtype == to:
            return c
        if c.dtype.is_dict_encoded and to.is_dict_encoded:
            return self._cast_dict_to_dict(c, to)
        if c.dtype.is_dict_encoded and not to.is_dict_encoded:
            if to.is_string_like:
                return ColumnVal(c.values, c.validity, to, c.dict)
            dvals, dok = C.cast_string_dict(c.dict, to)
            codes = jnp.clip(c.values, 0, len(dvals) - 1)
            vals = jnp.asarray(dvals)[codes]
            ok = jnp.asarray(dok)[codes]
            return ColumnVal(vals, c.validity & ok, to)
        if to.is_dict_encoded:
            return self._cast_plain_to_dict(c, to)
        v, m = C.cast_values(c.values, c.validity, c.dtype, to)
        return ColumnVal(v, m, to)

    def _cast_dict_to_dict(self, c: ColumnVal, to: T.DataType) -> ColumnVal:
        """dict-encoded -> dict-encoded: transform the dictionary host-side
        (it is small), keep the device codes."""
        if c.dtype.is_string_like and to.is_string_like:
            return ColumnVal(c.values, c.validity, to, c.dict)
        entries = c.dict.to_pylist()
        out, ok = [], np.ones(len(entries), dtype=bool)
        for i, v in enumerate(entries):
            r = C.cast_scalar(v, c.dtype, to) if v is not None else None
            if v is not None and r is None:
                ok[i] = False  # invalid entry -> NULL rows (non-ANSI)
            out.append(r)
        new_dict = pa.array(out, type=to.to_arrow())
        codes = jnp.clip(c.values, 0, max(len(entries) - 1, 0))
        okv = jnp.asarray(ok)[codes] if len(entries) else jnp.zeros_like(c.validity)
        return ColumnVal(c.values, c.validity & okv, to, new_dict)

    def _cast_plain_to_dict(self, c: ColumnVal, to: T.DataType) -> ColumnVal:
        """fixed-width -> string/binary/wide-decimal: the one cast that must
        BUILD a dictionary from data. One host sync; unique-codes the values
        so the dictionary stays |distinct|-sized."""
        vals = np.asarray(c.values)
        valid = np.asarray(c.validity)
        if vals.dtype.kind == "f":
            # dedup on the BIT pattern: np.unique would collapse -0.0 == 0.0
            # (they display differently) and merge NaN payloads
            bits = vals.view(np.int32 if vals.dtype == np.float32 else np.int64)
            uniq_bits, inv = np.unique(bits, return_inverse=True)
            uniq = uniq_bits.view(vals.dtype)
        else:
            uniq, inv = np.unique(vals, return_inverse=True)
        ents = [C.cast_scalar(u.item(), c.dtype, to) for u in uniq]
        new_dict = pa.array(ents, type=to.to_arrow())
        ok = np.array([e is not None for e in ents], dtype=bool)[inv]
        return ColumnVal(
            jnp.asarray(inv.astype(np.int32)),
            c.validity & jnp.asarray(ok & valid),
            to,
            new_dict,
        )

    # ---- binary ops ----

    def _binary(self, e: ir.BinaryOp, b: Batch, memo: dict) -> ColumnVal:
        l = self._eval(e.left, b, memo)
        r = self._eval(e.right, b, memo)
        op = e.op
        if op in ("and", "or"):
            return self._logic(op, l, r)
        if op in ir._CMP_OPS:
            return self._compare(op, l, r)
        return self._arith(op, l, r)

    def _logic(self, op: str, l: ColumnVal, r: ColumnVal) -> ColumnVal:
        lv = l.values.astype(bool)
        rv = r.values.astype(bool)
        if op == "and":
            known = (l.validity & ~lv) | (r.validity & ~rv)  # a known False
            value = jnp.where(known, False, lv & rv)
            valid = (l.validity & r.validity) | known
        else:
            known = (l.validity & lv) | (r.validity & rv)  # a known True
            value = jnp.where(known, True, lv | rv)
            valid = (l.validity & r.validity) | known
        return ColumnVal(value, valid, T.BOOL)

    def _compare(self, op: str, l: ColumnVal, r: ColumnVal) -> ColumnVal:
        if l.dtype.is_string_like or r.dtype.is_string_like:
            return self._compare_strings(op, l, r)
        valid = l.validity & r.validity
        if l.dtype.is_wide_decimal or r.dtype.is_wide_decimal:
            return self._compare_wide_decimal(op, l, r)
        if l.dtype.kind == T.TypeKind.DECIMAL or r.dtype.kind == T.TypeKind.DECIMAL:
            lv, rv, fallback = self._align_decimals(l, r)
            res = _cmp_apply(op, lv, rv)
            if fallback is not None:
                res = jnp.where(fallback[0], _cmp_apply(op, fallback[1], fallback[2]), res)
            return ColumnVal(res, valid, T.BOOL)
        common = ir.numeric_common_type(l.dtype, r.dtype) if l.dtype != r.dtype else l.dtype
        lc = self._cast(l, common)
        rc = self._cast(r, common)
        return ColumnVal(_cmp_apply(op, lc.values, rc.values), valid, T.BOOL)

    def _align_decimals(self, l: ColumnVal, r: ColumnVal):
        ld = l if l.dtype.kind == T.TypeKind.DECIMAL else self._cast(l, ir._as_decimal(l.dtype))
        rd = r if r.dtype.kind == T.TypeKind.DECIMAL else self._cast(r, ir._as_decimal(r.dtype))
        s = max(ld.dtype.scale, rd.dtype.scale)
        lv, lok = D.rescale(ld.values, ld.dtype.scale, s)
        rv, rok = D.rescale(rd.values, rd.dtype.scale, s)
        bad = ~(lok & rok)
        # if aligning overflowed int64 (enormous values), compare as float64
        lf = ld.values.astype(jnp.float64) * (10.0 ** (-ld.dtype.scale))
        rf = rd.values.astype(jnp.float64) * (10.0 ** (-rd.dtype.scale))
        return lv, rv, (bad, lf, rf)

    # 13-digit words: 5 of them cover any wide unscaled value after scale
    # alignment (<= 38 + 18 shift digits), each word int64-safe
    _DEC_WORD_BASE = 10**13
    _DEC_WORDS = 5

    def _compare_wide_decimal(self, op: str, l: ColumnVal, r: ColumnVal) -> ColumnVal:
        """Exact comparison when either operand is a wide (dict-encoded)
        decimal: both sides decompose into base-1e13 words of the unscaled
        value at the common scale (wide via host tables, narrow via exact
        device div/mod), compared lexicographically. Floats compare via a
        float64 view of the dictionary."""
        valid = l.validity & r.validity
        if l.dtype.is_float or r.dtype.is_float:
            lf = self._wide_as_float(l)
            rf = self._wide_as_float(r)
            return ColumnVal(_cmp_apply(op, lf, rf), valid, T.BOOL)
        ls = l.dtype.scale if l.dtype.kind == T.TypeKind.DECIMAL else 0
        rs = r.dtype.scale if r.dtype.kind == T.TypeKind.DECIMAL else 0
        s = max(ls, rs)
        # word count from the ACTUAL scale spread: 38 digits + up-shift
        # (decimal(38,0) vs decimal(38,38) aligns to 76 digits — a fixed
        # 5-word budget would overflow the top word, ADVICE r2 #3)
        need_digits = 38 + max(s - ls, s - rs)
        n_words = max(self._DEC_WORDS, -(-need_digits // 13) + 1)
        lw = self._decimal_words(l, s, n_words)
        rw = self._decimal_words(r, s, n_words)
        lt = jnp.zeros(l.values.shape, bool)
        eq = jnp.ones(l.values.shape, bool)
        for j in reversed(range(n_words)):  # big-endian compare
            lt = lt | (eq & (lw[j] < rw[j]))
            eq = eq & (lw[j] == rw[j])
        res = {
            "eq": eq, "neq": ~eq, "lt": lt, "lteq": lt | eq,
            "gt": ~lt & ~eq, "gteq": ~lt,
        }[op]
        return ColumnVal(res, valid, T.BOOL)

    def _wide_literal_arith(
        self, op: str, l: ColumnVal, r: ColumnVal
    ) -> ColumnVal | None:
        """Exact wide-decimal arithmetic when one operand is a broadcast
        constant (a one-entry dictionary or a scalar-valued narrow side):
        the op evaluates once per DICTIONARY ENTRY with python Decimals —
        the dictionary-transform pattern string functions use. Returns
        None when neither side is constant (column-pair arithmetic)."""
        import decimal as pydec

        def const_of(cv: ColumnVal):
            if cv.dtype.is_wide_decimal:
                if cv.dict is not None and len(cv.dict) == 1:
                    return cv.dict.to_pylist()[0]
                return None
            if cv.dtype.kind not in (
                T.TypeKind.DECIMAL, T.TypeKind.INT8, T.TypeKind.INT16,
                T.TypeKind.INT32, T.TypeKind.INT64,
            ):
                return None
            import jax

            # auronlint: disable=R9 -- constant probe memoized per plan node: re-evaluations hit the cached literal, not this read
            host = np.asarray(jax.device_get(cv.values))  # auronlint: sync-point(2/task) -- scalar-subquery constant probe, once per plan
            if host.size == 0 or not (host == host.flat[0]).all():
                return None
            v = int(host.flat[0])
            if cv.dtype.kind == T.TypeKind.DECIMAL:
                return T.decimal_from_unscaled(v, cv.dtype.scale)
            return pydec.Decimal(v)

        wide, other, wide_is_left = (
            (l, r, True) if l.dtype.is_wide_decimal else (r, l, False)
        )
        const = const_of(other)
        if const is None or wide.dict is None:
            return None
        out_t = ir.arith_result_type(op, l.dtype, r.dtype)
        assert out_t.kind == T.TypeKind.DECIMAL
        q = pydec.Decimal(1).scaleb(-out_t.scale)
        bound = pydec.Decimal(10) ** (out_t.precision - out_t.scale)
        new_entries: list = []
        ok_tab = np.zeros(max(len(wide.dict), 1), dtype=bool)
        obs.note_decimal_host_cells(len(wide.dict), "arith")
        with pydec.localcontext() as hp:
            hp.prec = 100
            for i, e in enumerate(wide.dict.to_pylist()):
                if e is None:
                    new_entries.append(pydec.Decimal(0))
                    continue
                a, b = (e, const) if wide_is_left else (const, e)
                v = _decimal_binop_exact(op, a, b, q, bound)
                if v is None:
                    new_entries.append(pydec.Decimal(0))
                    continue
                new_entries.append(v)
                ok_tab[i] = True
        return _materialize_decimal_entries(
            new_entries, ok_tab, wide.values, l.validity & r.validity, out_t
        )

    def _wide_pair_arith(self, op: str, l: ColumnVal, r: ColumnVal) -> ColumnVal:
        """Exact arithmetic over PAIRS of wide-decimal (or wide x narrow)
        COLUMNS — the last wide-decimal gap (VERDICT r2 #9).

        Wide values are dictionary codes, so the result is a function of the
        (left code, right value) pair: pull both columns once, np.unique the
        pairs, evaluate each distinct pair exactly with python Decimals, and
        regather by the pair index. One host sync + O(distinct pairs) exact
        ops — the documented host-exact path (a device limb multiply would
        still need a cross-limb HALF_UP rescale that has no exact int64
        formulation for div/mod)."""
        import decimal as pydec

        import jax

        def host_side(cv: ColumnVal):
            vals = np.asarray(jax.device_get(cv.values)).astype(np.int64)  # auronlint: sync-point(1/batch) -- documented host-exact decimal path (one sync, O(distinct pairs))
            if cv.dtype.is_wide_decimal:
                entries = cv.dict.to_pylist()
                vals = np.clip(vals, 0, max(len(entries) - 1, 0))
                return vals, lambda c: entries[int(c)]
            if cv.dtype.kind == T.TypeKind.DECIMAL:
                sc = cv.dtype.scale
                return vals, lambda v: T.decimal_from_unscaled(int(v), sc)
            return vals, lambda v: pydec.Decimal(int(v))

        lv, lfn = host_side(l)
        rv, rfn = host_side(r)
        pairs = np.stack([lv, rv], axis=1)
        uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
        out_t = ir.arith_result_type(op, l.dtype, r.dtype)
        assert out_t.kind == T.TypeKind.DECIMAL
        q = pydec.Decimal(1).scaleb(-out_t.scale)
        bound = pydec.Decimal(10) ** (out_t.precision - out_t.scale)
        entries: list = []
        ok_tab = np.zeros(max(len(uniq), 1), dtype=bool)
        obs.note_decimal_host_cells(len(uniq), "arith")
        with pydec.localcontext() as hp:
            hp.prec = 100
            for i, (a_raw, b_raw) in enumerate(uniq):
                a = lfn(a_raw)
                b = rfn(b_raw)
                if a is None or b is None:
                    entries.append(pydec.Decimal(0))
                    continue
                v = _decimal_binop_exact(op, a, b, q, bound)
                if v is None:
                    entries.append(pydec.Decimal(0))
                    continue
                entries.append(v)
                ok_tab[i] = True
        return _materialize_decimal_entries(
            entries, ok_tab, jnp.asarray(inv.astype(np.int32)),
            l.validity & r.validity, out_t,
        )

    def _wide_as_float(self, cv: ColumnVal) -> jnp.ndarray:
        if not cv.dtype.is_wide_decimal:
            if cv.dtype.kind == T.TypeKind.DECIMAL:
                return cv.values.astype(jnp.float64) * (10.0 ** -cv.dtype.scale)
            return cv.values.astype(jnp.float64)
        tab = np.zeros(max(len(cv.dict), 1), dtype=np.float64)
        for i, e in enumerate(cv.dict.to_pylist()):
            if e is not None:
                tab[i] = float(e)
        return jnp.asarray(tab)[jnp.clip(cv.values, 0, len(tab) - 1)]

    def _decimal_words(
        self, cv: ColumnVal, s: int, n_words: int | None = None
    ) -> list[jnp.ndarray]:
        """Base-1e13 little-endian words of the unscaled value at scale s
        (floored decomposition: lower words in [0, 1e13), top word signed)."""
        W, BASE = n_words or self._DEC_WORDS, self._DEC_WORD_BASE
        if cv.dtype.is_wide_decimal:
            entries = cv.dict.to_pylist()
            obs.note_decimal_host_cells(len(entries), "compare")
            n = max(len(entries), 1)
            tabs = np.zeros((W, n), dtype=np.int64)
            shift = 10 ** (s - cv.dtype.scale)
            for i, e in enumerate(entries):
                if e is None:
                    continue
                u = T.unscaled_int(e, cv.dtype.scale) * shift
                for j in range(W - 1):
                    u, rem = divmod(u, BASE)
                    tabs[j, i] = rem
                tabs[W - 1, i] = u
            idx = jnp.clip(cv.values, 0, n - 1)
            return [jnp.asarray(tabs[j])[idx] for j in range(W)]
        # narrow side: scaled int64 at its own scale, shifted up by
        # k = s - ns digits. word j = floor(v * 10^(k-13j)) mod 1e13,
        # computed without overflow via exact div/mod identities.
        # Integers enter directly at scale 0 (never cast: _as_decimal of
        # INT64 is decimal(20,0), itself wide)
        if cv.dtype.kind == T.TypeKind.DECIMAL:
            v = cv.values.astype(jnp.int64)
            ns = cv.dtype.scale
        else:
            v = cv.values.astype(jnp.int64)
            ns = 0
        k = s - ns
        words = []
        sign_lo = jnp.where(v < 0, jnp.int64(BASE - 1), jnp.int64(0))
        sign_top = jnp.where(v < 0, jnp.int64(-1), jnp.int64(0))
        for j in range(W):
            e = k - 13 * j
            if -e > 18:
                # shift beyond int64's 10^18 range: the word is pure
                # floored sign extension
                words.append(sign_top if j == W - 1 else sign_lo)
            elif j == W - 1:
                # top word carries the sign: pure floored division
                words.append(
                    jnp.floor_divide(v, jnp.int64(10 ** (-e)))
                    if e < 0 else v * jnp.int64(10**e)
                )
            elif e >= 13:
                words.append(jnp.zeros_like(v))
            elif e >= 0:
                words.append(jnp.mod(v, jnp.int64(10 ** (13 - e))) * jnp.int64(10**e))
            else:
                words.append(
                    jnp.mod(jnp.floor_divide(v, jnp.int64(10 ** (-e))), jnp.int64(BASE))
                )
        return words

    def _compare_strings(self, op: str, l: ColumnVal, r: ColumnVal) -> ColumnVal:
        assert l.dtype.is_string_like and r.dtype.is_string_like, (l.dtype, r.dtype)
        lmap, rmap, rank = _unify_two_dicts(l.dict, r.dict)
        lu = jnp.asarray(lmap)[jnp.clip(l.values, 0, len(lmap) - 1)]
        ru = jnp.asarray(rmap)[jnp.clip(r.values, 0, len(rmap) - 1)]
        valid = l.validity & r.validity
        if op in ("eq", "neq"):
            res = lu == ru if op == "eq" else lu != ru
            return ColumnVal(res, valid, T.BOOL)
        rk = jnp.asarray(rank)
        return ColumnVal(_cmp_apply(op, rk[lu], rk[ru]), valid, T.BOOL)

    def _arith(self, op: str, l: ColumnVal, r: ColumnVal) -> ColumnVal:
        if l.dtype.is_wide_decimal or r.dtype.is_wide_decimal:
            if l.dtype.is_float or r.dtype.is_float:
                # Spark: decimal (op) double computes in double
                lf = self._wide_as_float(l)
                rf = self._wide_as_float(r)
                valid = l.validity & r.validity
                fv, fok = _float_arith(op, lf, rf)
                return ColumnVal(fv, valid & fok, T.FLOAT64)
            out = self._wide_literal_arith(op, l, r)
            if out is not None:
                return out
            return self._wide_pair_arith(op, l, r)
        out = ir.arith_result_type(op, l.dtype, r.dtype)
        valid = l.validity & r.validity
        if out.kind == T.TypeKind.DECIMAL:
            ld = l if l.dtype.kind == T.TypeKind.DECIMAL else self._cast(l, ir._as_decimal(l.dtype))
            rd = r if r.dtype.kind == T.TypeKind.DECIMAL else self._cast(r, ir._as_decimal(r.dtype))
            fn = {"add": D.add, "sub": D.sub, "mul": D.mul, "div": D.div, "mod": D.mod}[op]
            v, ok = fn(
                ld.values, ld.dtype.scale, rd.values, rd.dtype.scale,
                out.precision, out.scale,
            )
            return ColumnVal(v, valid & ld.validity & rd.validity & ok, out)
        lc = self._cast(l, out)
        rc = self._cast(r, out)
        lv, rv = lc.values, rc.values
        if op == "add":
            v = lv + rv
        elif op == "sub":
            v = lv - rv
        elif op == "mul":
            v = lv * rv
        elif op == "div":
            zero = rv == 0
            if out.is_float:
                v = lv / jnp.where(zero, 1, rv)
            else:
                from jax import lax

                v = lax.div(lv, jnp.where(zero, 1, rv))
            valid = valid & ~zero
        elif op == "mod":
            from jax import lax

            zero = rv == 0
            safe = jnp.where(zero, 1, rv)
            if out.is_float:
                # Java % keeps the dividend's sign
                v = lv - jnp.trunc(lv / safe) * safe
            else:
                v = lax.rem(lv, safe)
            valid = valid & ~zero
        else:
            raise ValueError(op)
        return ColumnVal(v, valid, out)

    # ---- conditionals ----

    def _case(
        self, branches: list[tuple[ir.Expr, ir.Expr]], orelse: ir.Expr | None,
        b: Batch, memo: dict,
    ) -> ColumnVal:
        conds = [self._eval(c, b, memo) for c, _ in branches]
        vals = [self._eval(v, b, memo) for _, v in branches]
        if orelse is not None:
            els = self._eval(orelse, b, memo)
        else:
            els = _null_like(vals[0], b.capacity)
        vals = _unify_vals(vals + [els])
        els = vals[-1]
        vals = vals[:-1]
        # NULL condition counts as false; first true branch wins
        taken = jnp.zeros(b.capacity, bool)
        out_v = els.values
        out_m = els.validity
        for c, v in zip(conds, vals):
            fire = c.validity & c.values.astype(bool) & ~taken
            out_v = jnp.where(fire, v.values, out_v)
            out_m = jnp.where(fire, v.validity, out_m)
            taken = taken | fire
        return ColumnVal(out_v, out_m, vals[0].dtype, vals[0].dict)

    def _coalesce(self, args: list[ColumnVal]) -> ColumnVal:
        args = _unify_vals(args)
        out_v = args[0].values
        out_m = args[0].validity
        for a in args[1:]:
            take = ~out_m & a.validity
            out_v = jnp.where(take, a.values, out_v)
            out_m = out_m | a.validity
        return ColumnVal(out_v, out_m, args[0].dtype, args[0].dict)

    # ---- membership / pattern ----

    def _in(self, e: ir.In, b: Batch, memo: dict) -> ColumnVal:
        c = self._eval(e.child, b, memo)
        has_null_item = any(i is None for i in e.items)
        if c.dtype.is_string_like:
            entries = c.dict.to_pylist()
            member = np.array(
                [s in set(i for i in e.items if i is not None) for s in entries],
                dtype=bool,
            )
            hit = jnp.asarray(member)[jnp.clip(c.values, 0, len(member) - 1)]
        else:
            hit = jnp.zeros(b.capacity, bool)
            for item in e.items:
                if item is None:
                    continue
                lv = self._literal(ir.lit(item) if not isinstance(item, ir.Literal) else item, b.capacity)
                hit = hit | jnp.asarray(
                    self._compare("eq", c, lv).values
                )
        if e.negated:
            value = ~hit
        else:
            value = hit
        # Spark: x IN (...) is NULL if x is NULL, or no match and list has NULL
        valid = c.validity & ~(jnp.asarray(~hit) & has_null_item)
        return ColumnVal(value, valid, T.BOOL)

    def _like(self, e: ir.Like, b: Batch, memo: dict) -> ColumnVal:
        c = self._eval(e.child, b, memo)
        assert c.dtype.is_string_like, "LIKE requires a string input"
        rx = _like_to_regex(e.pattern, e.escape)
        entries = c.dict.to_pylist()
        match = np.array(
            [bool(rx.fullmatch(s)) if s is not None else False for s in entries],
            dtype=bool,
        )
        hit = jnp.asarray(match)[jnp.clip(c.values, 0, len(match) - 1)]
        return ColumnVal(~hit if e.negated else hit, c.validity, T.BOOL)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def eval_exprs(batch: Batch, exprs: list[ir.Expr]) -> list[ColumnVal]:
    return Evaluator(batch.schema).evaluate(batch, exprs)


def _materialize_decimal_entries(entries, ok_tab, codes, valid, out_t) -> ColumnVal:
    """Decimal entry table + per-entry ok mask + device codes -> ColumnVal:
    wide results keep codes against a fresh dictionary, narrow results
    gather scaled int64 values (the one place this encoding is defined)."""
    idx = jnp.clip(codes, 0, max(len(ok_tab) - 1, 0))
    valid = valid & jnp.asarray(ok_tab)[idx]
    if out_t.is_wide_decimal:
        return ColumnVal(codes, valid, out_t, pa.array(entries, type=out_t.to_arrow()))
    tab = np.zeros(max(len(entries), 1), dtype=np.int64)
    for i, v in enumerate(entries):
        tab[i] = T.unscaled_int(v, out_t.scale)
    return ColumnVal(jnp.asarray(tab)[idx], valid, out_t)


def _decimal_binop_exact(op: str, a, b, q, bound):
    """One exact Spark-decimal op on python Decimals: HALF_UP quantize to
    the result scale, overflow/zero-division -> None (non-ANSI NULL).
    Decimal % keeps the dividend's sign, matching Spark."""
    import decimal as pydec

    try:
        if op == "add":
            v = a + b
        elif op == "sub":
            v = a - b
        elif op == "mul":
            v = a * b
        elif op == "div":
            if b == 0:
                return None
            v = a / b
        elif op == "mod":
            if b == 0:
                return None
            v = a % b
        else:
            raise ValueError(op)
        v = v.quantize(q, rounding=pydec.ROUND_HALF_UP)
    except (pydec.InvalidOperation, ZeroDivisionError):
        return None
    if abs(v) >= bound:
        return None
    return v


def _float_arith(op: str, lf: jnp.ndarray, rf: jnp.ndarray):
    """float64 arithmetic with Spark semantics; returns (values, ok)."""
    ok = jnp.ones(lf.shape, bool)
    if op == "add":
        return lf + rf, ok
    if op == "sub":
        return lf - rf, ok
    if op == "mul":
        return lf * rf, ok
    zero = rf == 0
    safe = jnp.where(zero, 1.0, rf)
    if op == "div":
        return lf / safe, ok & ~zero
    if op == "mod":
        return lf - jnp.trunc(lf / safe) * safe, ok & ~zero
    raise ValueError(op)


def _cmp_apply(op: str, l: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    if op == "eq":
        return l == r
    if op == "neq":
        return l != r
    if op == "lt":
        return l < r
    if op == "lteq":
        return l <= r
    if op == "gt":
        return l > r
    if op == "gteq":
        return l >= r
    raise ValueError(op)


#: literal dictionaries memoized by (kind, value): a string literal's
#: single-entry vocabulary must be the SAME pa.Array object every batch,
#: so the identity-keyed _unify_two_dicts memo hits on batch 2+ of a
#: column-vs-literal comparison (q43-class day-name CASE chains evaluate
#: 7 of these per batch) instead of re-unifying per batch. Bounded; one
#: lock (concurrent queries share literals, R8).
_SINGLE_DICT_MEMO: dict = {}
_SINGLE_DICT_LOCK = threading.Lock()


def _single_dict(dtype: T.DataType, value) -> pa.Array:
    key = (dtype.kind, dtype.to_arrow() if dtype.kind == T.TypeKind.DECIMAL
           else None, value)
    try:
        with _SINGLE_DICT_LOCK:
            arr = _SINGLE_DICT_MEMO.get(key)
    except TypeError:            # unhashable value: build uncached
        arr, key = None, None
    if arr is not None:
        return arr
    if dtype.kind == T.TypeKind.BINARY:
        arr = pa.array([value if value is not None else b""],
                       type=pa.binary())
    elif dtype.kind == T.TypeKind.DECIMAL:
        import decimal as pydec

        arr = pa.array(
            [value if value is not None else pydec.Decimal(0)],
            type=dtype.to_arrow(),
        )
    else:
        arr = pa.array([value if value is not None else ""],
                       type=pa.string())
    if key is not None:
        with _SINGLE_DICT_LOCK:
            if len(_SINGLE_DICT_MEMO) >= 512:
                _SINGLE_DICT_MEMO.pop(next(iter(_SINGLE_DICT_MEMO)))
            _SINGLE_DICT_MEMO[key] = arr
    return arr


def _null_like(proto: ColumnVal, cap: int) -> ColumnVal:
    return ColumnVal(
        jnp.zeros(cap, proto.values.dtype), jnp.zeros(cap, bool), proto.dtype, proto.dict
    )


def _unify_vals(vals: list[ColumnVal]) -> list[ColumnVal]:
    """Make CASE/COALESCE branch values physically mergeable (same dtype, and
    for strings, the same dictionary)."""
    if any(v.dtype.is_dict_encoded for v in vals):
        assert all(
            v.dtype.is_dict_encoded for v in vals
        ), "mixed dict-encoded / plain branches"
        first = vals[0].dtype
        if first.kind == T.TypeKind.DECIMAL:
            import decimal as pydec

            # Spark branch-type widening: max integer digits + max scale,
            # bounded at p38 with scale give-back (adjustPrecisionScale)
            s_max = max(v.dtype.scale for v in vals)
            i_max = max(v.dtype.precision - v.dtype.scale for v in vals)
            first = ir._bounded(i_max + s_max, s_max)
            _q = pydec.Decimal(1).scaleb(-first.scale)
            value_type, filler = first.to_arrow(), [pydec.Decimal(0)]
        elif first.kind == T.TypeKind.BINARY:
            value_type, filler = pa.binary(), [b""]
        else:
            value_type, filler = pa.string(), [""]
        is_dec = first.kind == T.TypeKind.DECIMAL
        vocab: dict = {}
        remaps = []
        for v in vals:
            pl = v.dict.to_pylist()
            r = np.empty(len(pl), dtype=np.int32)
            for i, s in enumerate(pl):
                if is_dec and s is not None:
                    import decimal as pydec

                    # cast-to-branch-type semantics: quantize HALF_UP at
                    # the widened target scale (exact when scale grew)
                    with pydec.localcontext() as _hp:
                        _hp.prec = 100
                        s = s.quantize(_q, rounding=pydec.ROUND_HALF_UP)
                r[i] = vocab.setdefault(s, len(vocab))
            remaps.append(r)
        unified = pa.array(list(vocab.keys()) or filler, type=value_type)
        out = []
        for v, r in zip(vals, remaps):
            codes = jnp.asarray(r)[jnp.clip(v.values, 0, len(r) - 1)]
            out.append(ColumnVal(codes, v.validity, first, unified))
        return out
    target = vals[0].dtype
    for v in vals[1:]:
        if v.dtype != target:
            target = ir.numeric_common_type(target, v.dtype)
    ev = Evaluator(T.Schema())  # casts don't need the schema
    return [ev._cast(v, target) for v in vals]


#: memo for _unify_two_dicts keyed by dictionary ARRAY IDENTITY: batch
#: dictionaries are immutable pa.Arrays reused across batches (and, under
#: the serving layer, across queries — uploaded table views are shared),
#: so the same (left, right) pair recurs for every batch of a string
#: comparison. Entries hold strong refs to both arrays, so an id() can
#: never alias a collected array; bounded LRU; one lock (concurrent
#: queries evaluate string comparisons from many threads, R8).
_UNIFY_MEMO: "dict[tuple[int, int], tuple]" = {}
_UNIFY_MEMO_LOCK = threading.Lock()
_UNIFY_MEMO_CAP = 1024  # pairs are per (batch dict, other dict); a large
# table contributes one dict object per uploaded batch, reused across
# queries — the cap bounds memory, not the working set


def _unify_two_dicts_py(ld: pa.Array, rd: pa.Array):
    """Python fallback (null-bearing vocabularies: arrow encode maps null
    to a null index, the engine's contract maps it to a vocab id)."""
    vocab: dict = {}
    maps = []
    for d in (ld, rd):
        pl = d.to_pylist()
        m = np.empty(len(pl), dtype=np.int32)
        for i, s in enumerate(pl):
            m[i] = vocab.setdefault(s, len(vocab))
        maps.append(m)
    keys = list(vocab.keys())
    order = np.argsort(np.array(keys, dtype=object), kind="stable")
    rank = np.empty(len(keys), dtype=np.int32)
    rank[order] = np.arange(len(keys), dtype=np.int32)
    return maps[0], maps[1], rank


def _unify_two_dicts(ld: pa.Array, rd: pa.Array):
    """Returns (lmap, rmap, rank): per-code unified ids and ordering ranks.

    Vectorized (arrow dictionary_encode over the concatenated vocabularies
    — first-occurrence ids, exactly the old setdefault semantics; UTF-8
    byte order equals code-point order, so the arrow sort ranks match the
    old python-object argsort) and memoized by array identity: the
    per-batch python vocab loop was a top GIL site under concurrent
    serving (models/servegate.py sampling)."""
    key = (id(ld), id(rd))
    with _UNIFY_MEMO_LOCK:
        ent = _UNIFY_MEMO.get(key)
        if ent is not None and ent[0] is ld and ent[1] is rd:
            return ent[2], ent[3], ent[4]
    if ld.null_count or rd.null_count:
        lmap, rmap, rank = _unify_two_dicts_py(ld, rd)
    else:
        import pyarrow.compute as pc

        typ = pa.large_string() if (
            pa.types.is_large_string(ld.type)
            or pa.types.is_large_string(rd.type)
        ) else ld.type
        both = pa.concat_arrays([ld.cast(typ), rd.cast(typ)])
        enc = both.dictionary_encode()
        codes = enc.indices.to_numpy(zero_copy_only=False).astype(np.int32)
        lmap, rmap = codes[: len(ld)], codes[len(ld):]
        order = pc.array_sort_indices(enc.dictionary).to_numpy(
            zero_copy_only=False)
        rank = np.empty(len(enc.dictionary), dtype=np.int32)
        rank[order] = np.arange(len(order), dtype=np.int32)
    with _UNIFY_MEMO_LOCK:
        if len(_UNIFY_MEMO) >= _UNIFY_MEMO_CAP:
            _UNIFY_MEMO.pop(next(iter(_UNIFY_MEMO)))
        _UNIFY_MEMO[key] = (ld, rd, lmap, rmap, rank)
    return lmap, rmap, rank


def _like_to_regex(pattern: str, escape: str) -> "re.Pattern":
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return re.compile("".join(out), re.DOTALL)
