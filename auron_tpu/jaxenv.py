"""Central JAX environment setup for auron-tpu.

SQL engines need exact 64-bit integer semantics (BIGINT columns, 64-bit
hashes, decimal-as-scaled-int64), so x64 mode is enabled globally. On TPU,
s64 ops are lowered by XLA (emulated where needed); hot kernels use 32-bit
lanes where possible.
"""

from __future__ import annotations

import os

_SETUP_DONE = False

# the default compile cache: <checkout>/.jax_cache (git-ignored)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def setup_jax() -> None:
    global _SETUP_DONE
    if _SETUP_DONE:
        return
    import jax

    jax.config.update("jax_enable_x64", True)
    # Persistent compilation cache: the engine compiles one XLA program per
    # (pipeline, capacity-bucket) pair; caching them on disk makes every
    # process after the first start warm (analog of the reference shipping
    # precompiled native code rather than JIT-ing per task). Where
    # JAX_COMPILATION_CACHE_DIR is set JAX already reads it and the
    # directory is the caller's; otherwise the cache lives at ONE fixed
    # path inside the checkout (the path is part of the cache key, so a
    # directory that moves never hits).
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _SETUP_DONE = True


def force_cpu_backend(num_devices: int = 8) -> None:
    """Force the CPU backend with ``num_devices`` virtual devices.

    Used by tests and the CPU gates: must be called before any JAX
    backend is initialized.
    """
    import re

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    want = f"--xla_force_host_platform_device_count={num_devices}"
    os.environ["XLA_FLAGS"] = (flags + " " + want).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    setup_jax()


def is_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"
