"""Process-level side effects of the launchers: environment variables and
the persistent compile cache. Each case runs in a fresh interpreter so
that what it sets cannot leak into, or hide behind, the test process."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, env: dict) -> dict:
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                         "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env={**base, "PYTHONPATH": str(ROOT / "src"), **env})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_importing_dryrun_leaves_environment_alone():
    code = """if True:
        import json, os
        keys = ("JAX_PLATFORMS", "XLA_FLAGS")
        before = {k: os.environ.get(k) for k in keys}
        import repro.launch.dryrun
        print(json.dumps([before, {k: os.environ.get(k) for k in keys}]))
    """
    before, after = _run(code, {"XLA_FLAGS": "--xla_dump_to=/nonexistent"})
    assert before == after
    assert after == {"JAX_PLATFORMS": None,
                     "XLA_FLAGS": "--xla_dump_to=/nonexistent"}


_CACHE_PROBE = """if True:
    import json, jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
    print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
"""


def test_compile_cache_honours_env_dir(tmp_path):
    path, configured = _run(_CACHE_PROBE, {
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert path == configured == str(tmp_path)
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_ignored_repo_dir():
    code = """if True:
        import json, jax
        from repro.launch.compile_cache import enable_compile_cache
        path = enable_compile_cache()
        print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
    """
    path, configured = _run(code, {"JAX_PLATFORMS": "cpu"})
    assert path == configured == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
