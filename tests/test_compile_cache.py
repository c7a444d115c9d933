"""Persistent XLA compilation cache (runtime.distributed.enable_compile_cache).

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, the program uses that
directory and sets none in code; where it is not, every program — from any
working directory — uses one fixed git-ignored directory inside the
checkout. The trainer (``init_runtime``) and the server (``serve``) both
go through the one helper.

Timing assertions are flaky on shared CPU hosts, so the tests assert the
*mechanism*: which directory a fresh process ends up with, that a first
process populates it and a second adds no new entries (every program was a
cache hit) and still computes the right answer.

The in-process test tier runs under the 8-device CPU sim, where XLA:CPU's
executable deserialization is known-bad (conftest.py note) —
``enable_compile_cache`` must refuse there, so the subprocesses below run
single-device.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import sys
import jax, jax.numpy as jnp
sys.path.insert(0, {repo!r})
from ditl_tpu.runtime.distributed import enable_compile_cache

returned = enable_compile_cache()
assert returned == jax.config.jax_compilation_cache_dir, returned
@jax.jit
def f(x):
    return jnp.tanh(x @ x.T).sum()
print("OUT", float(f(jnp.ones((128, 128)))))
print("DIR", jax.config.jax_compilation_cache_dir)
"""


def _run_child(cwd: str, cache_env: str | None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_NUM_CPU_DEVICES",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=REPO)], env=env,
        capture_output=True, text=True, timeout=180, cwd=cwd,
    )
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return dict(ln.split(" ", 1) for ln in out.stdout.strip().splitlines())


def test_env_var_places_the_cache_and_second_process_hits_it(tmp_path):
    cache = str(tmp_path / "xla-cache")
    first = _run_child(REPO, cache)
    assert first["DIR"] == cache  # the environment's, untouched by code
    entries_after_first = set(os.listdir(cache))
    assert entries_after_first, "first run wrote no cache entries"
    second = _run_child(str(tmp_path), cache)
    # Every program the second process compiled was served from the cache.
    assert set(os.listdir(cache)) == entries_after_first
    assert second == first


def test_unset_env_uses_one_in_checkout_dir_from_any_cwd(tmp_path):
    from ditl_tpu.runtime.distributed import DEFAULT_COMPILE_CACHE_DIR

    assert os.path.dirname(DEFAULT_COMPILE_CACHE_DIR) == REPO
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert os.path.basename(DEFAULT_COMPILE_CACHE_DIR) + "/" in f.read().split()
    a = _run_child(REPO, None)
    b = _run_child(str(tmp_path), None)
    assert a["DIR"] == b["DIR"] == DEFAULT_COMPILE_CACHE_DIR
    assert a["OUT"] == b["OUT"]


def test_refuses_multi_device_cpu():
    # In-process: the tier runs under the 8-device host platform, exactly
    # the configuration whose cached-executable deserialization SIGABRTs —
    # the guard must refuse and leave jax config untouched.
    import jax

    from ditl_tpu.runtime.distributed import enable_compile_cache

    assert jax.local_device_count() > 1
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_server_enables_the_cache_before_building_anything(monkeypatch):
    """``serve`` goes through the same helper, and before the model exists
    (a cache enabled after the first compile never sees that program)."""
    from ditl_tpu.infer import server
    from ditl_tpu.models import llama
    from ditl_tpu.runtime import distributed

    order = []
    monkeypatch.setattr(distributed, "enable_compile_cache",
                        lambda: order.append("cache"))

    def stop_here(*a, **k):
        order.append("init_params")
        raise KeyboardInterrupt  # nothing below this matters to the test

    monkeypatch.setattr(llama, "init_params", stop_here)
    with pytest.raises(KeyboardInterrupt):
        server.serve(["--preset", "tiny-llama", "--engine", "continuous",
                      "--cache-mode", "paged"])
    assert order == ["cache", "init_params"]


def test_config_gates_and_defaults():
    from ditl_tpu.config import Config, parse_overrides

    cfg = Config()
    assert cfg.runtime.compile_cache is True  # on by default
    off = parse_overrides(cfg, ["runtime.compile_cache=false"])
    assert off.runtime.compile_cache is False
