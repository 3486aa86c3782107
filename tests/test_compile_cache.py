"""The persistent compile cache lives in one fixed place per checkout."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

PROBE = ("from repro.utils import compile_cache; import jax; "
         "print(compile_cache.enable()); "
         "print(jax.config.jax_persistent_cache_min_compile_time_secs)")


def _enable(env_dir: str | None) -> list[str]:
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, text=True,
                         capture_output=True, timeout=120, check=True)
    return out.stdout.split()


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/xla-cache"])
def test_enable_uses_env_dir_or_the_repo_cache(env_dir):
    cache_dir, min_secs = _enable(env_dir)
    if env_dir is None:
        assert Path(cache_dir) == REPO / ".jax_cache"
    else:
        assert cache_dir == env_dir     # JAX's own setting; nothing else set
    assert float(min_secs) == 0.0
