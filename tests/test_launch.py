"""The training CLI and the compile-cache rule of its entry point."""
import json
import math
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_smoke_run_caches_compiles_in_env_dir(tmp_path):
    """``--smoke`` trains the reduced config end to end, and with
    JAX_COMPILATION_CACHE_DIR set every compiled program lands there."""
    cache = tmp_path / "cache"
    log = tmp_path / "log.jsonl"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--env", "bandit", "--steps", "2", "--batch", "2",
         "--max-turns", "1", "--max-turn-tokens", "2", "--max-context", "32",
         "--log", str(log)],
        capture_output=True, text=True, env=env, timeout=600, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = [json.loads(line) for line in log.read_text().splitlines()]
    assert [r["step"] for r in rows] == [0, 1]
    assert all(math.isfinite(r["loss"]) and r["turn_len"] > 0 for r in rows)
    assert any(cache.iterdir()), "no compiled program reached the cache"


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    from repro.utils.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
