"""The entry points' persistent compilation cache: the checkout's own
directory takes no size cap, so a second process loads every program a
first one compiled; a directory named from outside keeps its settings."""
import json
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

# two programs, then what this process loaded from the persistent cache
_RUN = """
import json, os, sys
from pathlib import Path
import jax, jax.numpy as jnp, numpy as np
from repro.launch import compile_cache
compile_cache.DEFAULT_DIR = Path(sys.argv[1]).resolve()
loads = []
jax.monitoring.register_event_listener(
    lambda e, **kw: loads.append(e) if e.endswith("cache_hits") else None)
print(compile_cache.enable_compile_cache(), file=sys.stderr)
jax.jit(lambda x: jnp.sin(x) * 2).lower(np.ones(7, np.float32)).compile()
jax.jit(lambda x: jnp.cos(x) + 3).lower(np.ones(9, np.float32)).compile()
print(json.dumps({"loads": len(loads),
                  "max_size": jax.config.jax_compilation_cache_max_size}))
"""


def _run(cache: Path, env_dir: Path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(env_dir),
               JAX_COMPILATION_CACHE_MAX_SIZE="1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", _RUN, str(cache)], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_checkout_cache_takes_no_size_cap(tmp_path):
    """Under a cap smaller than any entry, the checkout's cache still keeps
    both programs and a second process loads both; a directory named
    from outside keeps the cap, and a process there loads none."""
    own = tmp_path / "own"
    first = _run(own, own)
    assert first == {"loads": 0, "max_size": -1}
    assert _run(own, own) == {"loads": 2, "max_size": -1}
    shared = tmp_path / "shared"
    assert _run(own, shared)["max_size"] == 1
    assert _run(own, shared)["loads"] == 0
