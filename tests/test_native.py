"""Native (C++) helper tests: parity with device kernels / numpy."""

import os

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu import native


def test_native_available():
    # the library builds in this environment; if this fails the fallbacks
    # still keep the engine correct, but we want CI to notice
    assert native.available()


def test_murmur3_i64_matches_device():
    import jax.numpy as jnp

    from auron_tpu.ops.hashing import murmur3_i64

    v = np.array([1, 0, -1, 2**63 - 1, -(2**63), 123456789], dtype=np.int64)
    got = native.murmur3_i64_host(v)
    want = np.asarray(murmur3_i64(jnp.asarray(v), jnp.uint32(42)).view(jnp.int32))
    assert (got == want).all()


def test_murmur3_bytes_matches_spark_vectors():
    strings = ["hello", "bar", "", "😁", "天地"]
    bufs = [s.encode() for s in strings]
    data = b"".join(bufs)
    offsets = np.zeros(len(bufs) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in bufs], out=offsets[1:])
    got = native.murmur3_bytes_host(data, offsets).tolist()
    want = [v - (1 << 32) if v >= (1 << 31) else v
            for v in [3286402344, 2486176763, 142593372, 885025535, 2395000894]]
    assert got == want


def test_radix_partition():
    rng = np.random.default_rng(17)
    pids = rng.integers(0, 7, 10_000).astype(np.int32)
    counts, order = native.radix_partition_host(pids, 7)
    assert counts.sum() == 10_000
    assert (counts == np.bincount(pids, minlength=7)).all()
    clustered = pids[order]
    assert (np.diff(clustered) >= 0).all()
    # stability: within each partition, original order preserved
    for p in range(7):
        rows = order[clustered == p]
        assert (np.diff(rows) > 0).all()


def test_loser_tree_merge_matches_lexsort():
    rng = np.random.default_rng(18)
    runs = []
    for _ in range(5):
        n = rng.integers(1, 500)
        w1 = np.sort(rng.integers(0, 50, n).astype(np.uint64))
        # secondary word sorted within w1 groups
        w2 = rng.integers(0, 50, n).astype(np.uint64)
        order = np.lexsort((w2, w1))
        runs.append([w1[order], w2[order]])
    out_run, out_idx = native.loser_tree_merge_host(runs)
    merged_w1 = np.array([runs[r][0][i] for r, i in zip(out_run, out_idx)])
    merged_w2 = np.array([runs[r][1][i] for r, i in zip(out_run, out_idx)])
    packed = merged_w1 * 10_000 + merged_w2
    assert (np.diff(packed.astype(np.int64)) >= 0).all()
    assert len(out_run) == sum(len(r[0]) for r in runs)


def test_pallas_partition_ids_interpret():
    """Pallas murmur3+pmod kernel matches the jnp reference (interpret mode
    on CPU; the same kernel compiles for TPU)."""
    import jax.numpy as jnp

    from auron_tpu.ops import hashing as H
    from auron_tpu.ops.pallas_kernels import partition_ids_pallas

    rng = np.random.default_rng(41)
    v = jnp.asarray(rng.integers(-(2**62), 2**62, 1000))
    got = np.asarray(partition_ids_pallas(v, 16, interpret=True))
    want = np.asarray(H.pmod(H.murmur3_i64(v, jnp.uint32(42)).view(jnp.int32), 16))
    assert (got == want).all()


# ---------------------------------------------------------------------------
# C ABI bridge (native/auron_bridge.cpp): a C host engine drives a
# TaskDefinition end-to-end through the exported symbols — the analog of
# JniBridge.java:49-80 + exec.rs:42-122
# ---------------------------------------------------------------------------


def _build_bridge():
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native = os.path.join(root, "native")
    import shutil

    if shutil.which("make") is None:
        pytest.skip("no make in this environment")
    r = subprocess.run(
        ["make", "-C", native, "libauron_bridge.so", "bridge_harness"],
        capture_output=True, text=True,
    )
    # toolchain exists: a broken build is a FAILURE, not a skip
    assert r.returncode == 0, f"bridge build failed: {r.stderr[-800:]}"
    return os.path.join(native, "bridge_harness")


def _harness_env():
    import sysconfig

    env = dict(os.environ)
    env["PYTHONPATH"] = sysconfig.get_paths()["purelib"]
    env["JAX_PLATFORMS"] = "cpu"
    env["AURON_TPU_ROOT"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return env


def _ipc_bytes(rb):
    import io

    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return sink.getvalue()


def _decode_framed(path):
    import io
    import struct

    data = open(path, "rb").read()
    pos, rows = 0, []
    while pos < len(data):
        (n,) = struct.unpack_from("<Q", data, pos)
        pos += 8
        with pa.ipc.open_stream(io.BytesIO(data[pos : pos + n])) as r:
            for rb in r:
                rows += rb.to_pylist()
        pos += n
    return rows


def test_c_abi_filter_project_roundtrip(tmp_path):
    import json
    import subprocess

    from auron_tpu import types as T
    from auron_tpu.exprs.ir import BinaryOp, col, lit
    from auron_tpu.plan import builders as B

    harness = _build_bridge()
    schema = T.Schema.of(T.Field("k", T.INT64), T.Field("v", T.INT64))
    plan = B.project(
        B.filter_(B.ffi_reader(schema, "input"), [BinaryOp("gt", col(1), lit(10))]),
        [(col(0), "k"), (BinaryOp("mul", col(1), lit(2)), "v2")],
    )
    task_f = tmp_path / "task.bin"
    task_f.write_bytes(B.task(plan).SerializeToString())
    rb = pa.record_batch(
        {"k": np.arange(6, dtype=np.int64),
         "v": np.array([5, 11, 7, 20, 30, 9], dtype=np.int64)}
    )
    in_f = tmp_path / "input.bin"
    in_f.write_bytes(_ipc_bytes(rb))
    out_f = tmp_path / "out.bin"

    r = subprocess.run(
        [harness, str(task_f), str(out_f), "input", str(in_f)],
        env=_harness_env(), capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    rows = _decode_framed(out_f)
    assert rows == [{"k": 1, "v2": 22}, {"k": 3, "v2": 40}, {"k": 4, "v2": 60}]
    metrics = json.loads(r.stdout)
    # whole-stage fusion compiles the filter->project chain into one
    # FusedStageExec whose metric children keep the per-operator split
    # (docs/fusion.md) — the harvested tree must still name both operators
    assert metrics["name"] == "FusedStageExec"
    child_names = {c["name"] for c in metrics["children"]}
    assert {"FilterExec", "ProjectExec"} <= child_names


def test_c_abi_aggregate_through_so(tmp_path):
    import subprocess

    from auron_tpu import types as T
    from auron_tpu.exprs.ir import col
    from auron_tpu.plan import builders as B

    harness = _build_bridge()
    schema = T.Schema.of(T.Field("k", T.INT64), T.Field("v", T.INT64))
    agg_p = B.hash_agg(B.ffi_reader(schema, "rows"),
                       [(col(0), "k")], [("sum", col(1), "s")], "partial")
    agg_f = B.hash_agg(agg_p, [(col(0), "k")], [("sum", col(1), "s")], "final")
    task_f = tmp_path / "task.bin"
    task_f.write_bytes(B.task(agg_f).SerializeToString())

    rng = np.random.default_rng(5)
    k = rng.integers(0, 7, 500).astype(np.int64)
    v = rng.integers(-100, 100, 500).astype(np.int64)
    in_f = tmp_path / "rows.bin"
    in_f.write_bytes(_ipc_bytes(pa.record_batch({"k": k, "v": v})))
    out_f = tmp_path / "out.bin"

    r = subprocess.run(
        [harness, str(task_f), str(out_f), "rows", str(in_f)],
        env=_harness_env(), capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    got = sorted((row["k"], row["s"]) for row in _decode_framed(out_f))
    import pandas as pd

    want = sorted(
        pd.DataFrame({"k": k, "v": v}).groupby("k")["v"].sum().items()
    )
    assert got == want


def test_c_abi_error_relay(tmp_path):
    import subprocess

    harness = _build_bridge()
    task_f = tmp_path / "bad.bin"
    task_f.write_bytes(b"\x00not a protobuf")
    out_f = tmp_path / "out.bin"
    r = subprocess.run(
        [harness, str(task_f), str(out_f)],
        env=_harness_env(), capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert "failed" in r.stderr


def test_live_pid_counts_matches_bincount():
    """The exchange's on-device routing histogram: dead rows and
    out-of-range ids fall out of every bucket."""
    import jax.numpy as jnp

    from auron_tpu.parallel.mesh_driver import _live_pid_counts

    rng = np.random.default_rng(9)
    pids = rng.integers(-1, 8, 5000).astype(np.int32)
    sel = rng.random(5000) < 0.8
    got = np.asarray(_live_pid_counts(jnp.asarray(sel), jnp.asarray(pids), n_parts=7))
    live = pids[sel]
    want = np.bincount(live[(live >= 0) & (live < 7)], minlength=7)
    assert got.dtype == np.int32 and (got == want).all()


@pytest.mark.parametrize("replicated", [False, True])
def test_pallas_pid_path_matches_generic(monkeypatch, replicated):
    """Force the gated pallas pid path (interpret mode) through the real
    HashPartitioning entry and compare with the generic jnp path. A batch
    replicated over several devices (what a mesh exchange hands the next
    stage) must NOT reach the kernel: Mosaic kernels cannot be partitioned
    automatically."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import auron_tpu.exec.shuffle.partitioning as P
    import auron_tpu.ops.pallas_kernels as PK
    from auron_tpu import types as T
    from auron_tpu.columnar import Batch
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.exprs.ir import col

    rng = np.random.default_rng(10)
    b = Batch.from_pydict(
        {"k": rng.integers(-(2**60), 2**60, 2000).tolist()},
        schema=T.Schema.of(T.Field("k", T.INT64)),
    )
    if replicated:
        everywhere = NamedSharding(
            Mesh(np.array(jax.devices()[:4]), ("p",)), PartitionSpec())
        b = Batch(b.schema, jax.device_put(b.device, everywhere), b.dicts)
        assert isinstance(b.device, DeviceBatch)
    hp = P.HashPartitioning([col(0)], 16)
    want = np.asarray(hp.partition_ids(b, None))

    monkeypatch.setattr("auron_tpu.jaxenv.is_tpu", lambda: True)
    orig = PK.partition_ids_pallas
    calls = []

    def kernel(v, n, seed=42):
        calls.append(v.shape)
        return orig(v, n, seed=seed, interpret=True)

    monkeypatch.setattr(PK, "partition_ids_pallas", kernel)
    got = np.asarray(hp.partition_ids(b, None))
    assert (got == want).all()
    assert bool(calls) != replicated
