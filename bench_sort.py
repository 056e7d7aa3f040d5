"""Cluster-sort microbench: lax.sort vs the bitonic network (ops/bitonic.py).

The q3-class agg shape: one group-key word + the null-bits word + the
dead-rows-first key + an int32 payload, at agg batch capacities. This is
the engine's dominant device primitive (VERDICT r3 weak #5); the bitonic
network is the Pallas answer, and its jitted-jnp twin is the measurable
proxy on whatever backend is live (identical algorithm, XLA-scheduled).

Prints one JSON line per (impl, cap): {"impl", "cap", "n_words", "ms",
"backend", "vs_lax"}. A script run on its own, in its own process, on the
backend JAX gives it (each row names it); only a chip run gives the
kernel-vs-lax.sort verdict. The Pallas row is fully unrolled per (planes,
P): its compile takes minutes from P = 131072 up (ROADMAP S8).
"""

import json
import time

import numpy as np


def _time(fn, *args, reps=None):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    if reps is None:
        # one warm rep is enough at multi-million-row caps (CPU proxy)
        reps = 5 if args[0].shape[0] <= (1 << 18) else 1
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1000.0


def main(quiet: bool = False):
    import jax
    import jax.numpy as jnp
    from jax import lax

    import auron_tpu  # noqa: F401  (x64)
    from auron_tpu.ops import bitonic

    backend = jax.default_backend()
    rng = np.random.default_rng(3)
    results = []
    import os

    # default sizes cover agg-batch caps AND a multi-million-row cap
    # (2^21) that forces the tiled multi-block network (VERDICT r4 #4:
    # q95-class reduce sorts run millions of rows); larger caps via
    # BENCH_SORT_CAPS on TPU, where the kernel case actually holds
    caps = tuple(
        int(c) for c in os.environ.get(
            "BENCH_SORT_CAPS", "16384,65536,131072,2097152"
        ).split(",")
    )
    for cap in caps:
        n_groups = max(cap // 64, 1)
        sel = jnp.asarray(rng.random(cap) > 0.2)
        dead = jnp.where(sel, jnp.uint64(0), jnp.uint64(1))
        word = jnp.asarray(rng.integers(0, n_groups, cap).astype(np.uint64))
        nulls = jnp.zeros(cap, jnp.uint64)
        iota = jnp.arange(cap, dtype=jnp.int32)
        ops = (dead, word, nulls, iota)

        lax_fn = jax.jit(lambda *o: lax.sort(o, num_keys=len(o) - 1))
        ms_lax = _time(lax_fn, *ops)
        rows = [("lax", ms_lax)]
        rows.append(("jnp", _time(lambda *o: bitonic.bitonic_sort(o, impl="jnp"), *ops)))
        if backend == "tpu":
            rows.append(
                ("pallas", _time(lambda *o: bitonic.bitonic_sort(o, impl="pallas"), *ops))
            )
        for impl, ms in rows:
            rec = {
                "impl": impl,
                "cap": cap,
                "n_words": 2,
                "ms": round(ms, 3),
                "backend": backend,
                "vs_lax": round(ms_lax / ms, 2) if ms else None,
            }
            results.append(rec)
            if not quiet:
                print(json.dumps(rec), flush=True)
    return results


if __name__ == "__main__":
    main()
