"""The scatters' unit costs on the chip: what ``ops/segments.py``'s
``SCATTER_WIDE`` / ``SCATTER_NARROW`` / ``LIMBS_PAY_UP_TO`` and
``columnar/batch.py compaction_index_form`` rest on.

    python tools/scatter_costs.py [alone] [fold] [index]     (on a TPU host)

(no section named: all three)

Times, by the host clock around calls that end in ``block_until_ready``
(best of three sets), into a 262,144-slot table:

- alone: one 64-bit ``segment_sum``, one int32 one, and an int64 plane
  summed as k int32 limbs (k planes one by one, and ONE scatter of
  ``[rows, k]`` windows: the form that was not taken) at 4,194,304 and
  131,072 rows, the rows uniform over the slots, all to the drop segment,
  and a tenth live;
- inside ``agg_exec._dense_update_jit`` at query 65's shapes (the map side's
  DECIMAL(7,2) sum and average at 4,194,304 rows, an int64 sum, the reduce
  side's merge of DECIMAL(17,2) at 131,072), by limbs and, with
  ``LIMBS_PAY_UP_TO`` patched to 0, by the 64-bit scatter it replaced;
- ``compaction_index`` from capacity into a bucket at query 65's shapes
  (2,097,152 into 262,144 and 131,072; 4,194,304 into 524,288) and the star
  cells' (4,194,304 into 16,384; 1,048,576 into 8,192 and 32,768), the live
  rows a ninth under the bucket and spread at random: the running count
  alone, the ``search`` form, the ``scatter`` form and two variants of the
  scatter (dead rows sent to unique slots past the live ones, a stable
  partition, with ``unique_indices``; the dropped form claiming
  ``unique_indices``), and ``_compact_dev``'s take of four int64 columns
  by either form.

Runs on a TPU only; prints a line a reading and writes them all to
``chiprun_out/scatter_costs.json``. PERF.md section 5 ("unit costs") holds
the readings."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import auron_tpu  # noqa: F401  (x64)
from auron_tpu import types as T
from auron_tpu.columnar import batch as B
from auron_tpu.exec import agg_exec
from auron_tpu.ops import segments as S

SIZE = 1 << 18
NSEG = SIZE + 1
OUT: dict[str, float] = {}


def timed(f, *args, calls=20, sets=3) -> float:
    """Seconds a call, best of ``sets`` sets of ``calls`` (fewer of a slow one)."""
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(f(*args))
    if time.perf_counter() - t0 > 0.1:
        calls, sets = 5, 2
    best = float("inf")
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(calls):
            r = f(*args)
        jax.block_until_ready(r)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def note(name: str, secs: float, rows: int) -> None:
    OUT[name] = secs
    print(f"{name}: {secs * 1e3:.2f} ms = {secs / rows * 1e9:.1f} ns a row", flush=True)


def limb_scatter(v, ids, b: int, k: int, windowed: bool):
    limbs = S.split_limbs(v, b, k)
    if windowed:
        sums = jax.ops.segment_sum(jnp.stack(limbs, axis=1), ids, num_segments=NSEG)
        parts = [sums[:, i] for i in range(k)]
    else:
        parts = [jax.ops.segment_sum(limb, ids, num_segments=NSEG) for limb in limbs]
    return sum(p.astype(jnp.int64) << (i * b) for i, p in enumerate(parts))


def alone(rng) -> None:
    for rows in (1 << 22, 1 << 17):
        v = jnp.asarray(rng.integers(-(10 ** 7) + 1, 10 ** 7, rows, dtype=np.int64))
        spread = rng.integers(0, SIZE, rows)
        routes = {
            "uniform": spread,
            "dead": np.full(rows, SIZE),
            "tenth": np.where(rng.random(rows) < 0.1, spread, SIZE),
        }
        b = 31 - (rows - 1).bit_length()
        for route, ids in routes.items():
            ids = jnp.asarray(ids.astype(np.int32))
            tag = f"alone.rows{rows}.{route}"
            wide = jax.jit(lambda v, ids: jax.ops.segment_sum(v, ids, num_segments=NSEG))
            note(f"{tag}.int64", timed(wide, v, ids), rows)
            one = jax.jit(lambda v, ids: jax.ops.segment_sum(
                v.astype(jnp.int32), ids, num_segments=NSEG))
            note(f"{tag}.int32", timed(one, v, ids), rows)
            for k in (3, 5, 7, 8):
                if (k - 1) * b >= 64:
                    continue
                for windowed in (False, True):
                    f = jax.jit(lambda v, ids, k=k, w=windowed: limb_scatter(v, ids, b, k, w))
                    form = "window" if windowed else "planes"
                    note(f"{tag}.k{k}.{form}", timed(f, v, ids), rows)


def fold_program(limbs_pay_up_to: int):
    """``_dense_update_jit``'s function jitted anew with the constant patched
    while it is traced (0: every sum by its 64-bit scatter)."""
    inner = agg_exec._dense_update_jit.__wrapped__

    def f(*a, cfg, size):
        saved, S.LIMBS_PAY_UP_TO = S.LIMBS_PAY_UP_TO, limbs_pay_up_to
        try:
            return inner(*a, cfg=cfg, size=size)
        finally:
            S.LIMBS_PAY_UP_TO = saved

    return jax.jit(f, static_argnames=("cfg", "size"))


def in_the_fold(rng) -> None:
    dims = (14, 18002)
    base = jnp.asarray([1, 1], jnp.int64)
    hi = jnp.asarray([1 + 14 - 2, 1 + 18002 - 2], jnp.int64)
    cases = (
        ("map.sum_dec7_2", 1 << 22, True, "sum", T.decimal(7, 2)),
        ("map.sum_int64", 1 << 22, True, "sum", T.INT64),
        ("map.avg_dec7_2", 1 << 22, True, "avg", T.decimal(7, 2)),
        ("reduce.merge_dec17_2", 1 << 17, False, "sum", T.decimal(7, 2)),
    )
    for label, rows, raw, func, in_t in cases:
        cfg = (raw, ((func, in_t),), dims)
        keys = (jnp.asarray(rng.integers(1, 14, rows, dtype=np.int64)),
                jnp.asarray(rng.integers(1, 18001, rows, dtype=np.int64)))
        ok = jnp.ones((rows,), bool)
        val = jnp.asarray(rng.integers(0, 10 ** 6, rows, dtype=np.int64))
        fields = 2 if func == "avg" else 1
        sels = {"live": ok, "tenth": jnp.asarray(rng.random(rows) < 0.1),
                "dead": jnp.zeros((rows,), bool)}
        for sname, sel in sels.items():
            for form, pay in (("limbs", S.LIMBS_PAY_UP_TO), ("wide", 0)):
                f = fold_program(pay)
                state = (tuple(jnp.zeros((SIZE,), jnp.int64) for _ in range(fields)),
                         (jnp.zeros((SIZE,), bool),) + (None,) * (fields - 1),
                         jnp.zeros((SIZE,), bool))
                secs = timed(lambda: f(*state, base, hi, keys, (ok, ok), sel,
                                       (((val, ok),),), cfg=cfg, size=SIZE))
                note(f"fold.{label}.{sname}.{form}", secs, rows)


INDEX_SHAPES = ((1 << 21, 1 << 18), (1 << 21, 1 << 17), (1 << 22, 1 << 19),
                (1 << 21, 1 << 16), (1 << 22, 1 << 14), (1 << 20, 1 << 13),
                (1 << 20, 1 << 15))


def index_partition(sel, out_cap: int):
    """The scatter as a stable partition: live row i to its rank, dead row i
    to the live count plus its rank among the dead: every slot once."""
    cap = sel.shape[0]
    pos = B._running_count(sel)
    i = jnp.arange(cap, dtype=jnp.int32)
    slot = jnp.where(sel, pos - 1, pos[-1] + i - pos)
    perm = jnp.zeros(cap, jnp.int32).at[slot].set(i, unique_indices=True)
    idx = jnp.where(i[:out_cap] < pos[-1], perm[:out_cap], cap - 1)
    return idx, i[:out_cap] < pos[-1]


def index_drop_unique(sel, out_cap: int):
    """The dropped form claiming unique indices (its dropped ones are not)."""
    cap = sel.shape[0]
    pos = B._running_count(sel)
    idx = (jnp.full(out_cap, cap - 1, jnp.int32)
           .at[jnp.where(sel, pos - 1, out_cap)]
           .set(jnp.arange(cap, dtype=jnp.int32), mode="drop", unique_indices=True))
    return idx, jnp.arange(out_cap, dtype=jnp.int32) < pos[-1]


def index_in(form: str, fn=B.compaction_index):
    """``fn`` with ``compaction_index_form`` patched to ``form`` while it is
    traced."""
    def traced(*a):
        saved, B.compaction_index_form = B.compaction_index_form, lambda c, o: form
        try:
            return fn(*a)
        finally:
            B.compaction_index_form = saved

    return traced


def index_forms(rng) -> None:
    for cap, out_cap in INDEX_SHAPES:
        live = out_cap - out_cap // 9
        mask = np.zeros(cap, bool)
        mask[rng.choice(cap, live, replace=False)] = True
        sel = jnp.asarray(mask)
        want = None
        tag = f"index.cap{cap}.out{out_cap}"
        note(f"{tag}.count", timed(jax.jit(B._running_count), sel), cap)
        forms = {
            "search": index_in("search"),
            "scatter": index_in("scatter"),
            "partition": index_partition,
            "drop_unique": index_drop_unique,
        }
        for form, fn in forms.items():
            f = jax.jit(fn, static_argnums=1)
            got = [np.asarray(a) for a in f(sel, out_cap)]
            if want is None:
                want = got
            same = all(np.array_equal(a, b) for a, b in zip(got, want))
            print(f"{tag}.{form}: identical to search: {same}", flush=True)
            note(f"{tag}.{form}", timed(f, sel, out_cap), cap)
        cols = tuple(jnp.asarray(rng.integers(0, 1 << 40, cap)) for _ in range(4))
        dev = B.DeviceBatch(sel, cols, tuple(sel for _ in cols))
        for form in ("search", "scatter"):
            f = jax.jit(index_in(form, B._compact_dev.__wrapped__), static_argnums=1)
            note(f"{tag}.take4.{form}", timed(f, dev, out_cap), cap)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}: a CPU time is no unit cost")
    print(f"device: {dev.device_kind}", flush=True)
    rng = np.random.default_rng(7)
    sections = sys.argv[1:] or ["alone", "fold", "index"]
    if "alone" in sections:
        alone(rng)
    if "fold" in sections:
        in_the_fold(rng)
    if "index" in sections:
        index_forms(rng)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/scatter_costs.json", "w") as fh:
        json.dump({"device_kind": dev.device_kind, "seconds": OUT}, fh, indent=1)


if __name__ == "__main__":
    main()
