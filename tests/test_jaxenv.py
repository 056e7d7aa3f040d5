"""auron_tpu.jaxenv: where the compile cache goes, and that forcing the CPU
backend leaves JAX's backend registry alone.

``setup_jax()`` runs once per process (``import auron_tpu`` already ran it
here), so each case asks a fresh child; the children run on the CPU and
never initialize another backend.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, cwd: str, **env_overrides) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_overrides)
    r = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_REPORT_CACHE_DIR = (
    "import json, jax, auron_tpu\n"
    "print(json.dumps({'dir': jax.config.jax_compilation_cache_dir}))\n"
)


def test_env_cache_dir_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the program sets no
    directory in code."""
    want = str(tmp_path / "cache_from_env")
    got = _child(_REPORT_CACHE_DIR, str(tmp_path), JAX_COMPILATION_CACHE_DIR=want)
    assert got["dir"] == want


def test_default_cache_dir_is_fixed_in_checkout(tmp_path):
    """Unset: one fixed path inside the checkout, whatever the working
    directory (the path is part of the cache key: a directory that moves
    never hits)."""
    other = tmp_path / "elsewhere"
    other.mkdir()
    a = _child(_REPORT_CACHE_DIR, str(tmp_path))
    b = _child(_REPORT_CACHE_DIR, str(other))
    assert a["dir"] == b["dir"] == os.path.join(REPO, ".jax_cache")


def test_force_cpu_backend_leaves_backend_factories():
    """Forcing the CPU must not unregister the other platforms: with
    ``tpu`` gone from the registry Pallas' TPU lowering cannot even be
    imported, and no process could describe a TPU topology."""
    got = _child(
        "import json, jax\n"
        "from jax._src import xla_bridge as xb\n"
        "before = sorted(xb._backend_factories)\n"
        "from auron_tpu.jaxenv import force_cpu_backend\n"
        "force_cpu_backend(2)\n"
        "import jax.experimental.pallas.tpu  # noqa: F401  (needs 'tpu' known)\n"
        "print(json.dumps({'before': before, 'after': sorted(xb._backend_factories),\n"
        "                  'devices': len(jax.devices()),\n"
        "                  'platform': jax.devices()[0].platform}))\n",
        REPO,
    )
    assert got["after"] == got["before"]
    assert "tpu" in got["after"]
    assert (got["devices"], got["platform"]) == (2, "cpu")
