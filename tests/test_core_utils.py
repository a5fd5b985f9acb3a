"""Core utilities: errorcheck (CUDA-check analogue), flags, logging."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ScopeError, check_compiles, check_finite,
                        check_shape, sync)
from repro.core.flags import FlagRegistry
from repro.core.logging import Timer, get_logger


def test_check_finite_passes_and_raises():
    check_finite({"a": jnp.ones(3)})
    with pytest.raises(ScopeError, match="non-finite"):
        check_finite({"a": jnp.asarray([1.0, float("nan")])}, where="here")


def test_check_shape():
    check_shape(jnp.ones((2, 3)), (2, 3))
    with pytest.raises(ScopeError, match="shape mismatch"):
        check_shape(jnp.ones((2, 3)), (3, 2))


def test_check_compiles_catches_bad_program():
    def good(x):
        return x + 1
    assert check_compiles(good, jnp.ones(3)) is not None

    def bad(x):
        return x @ jnp.ones((5, 5))       # shape error at lowering
    with pytest.raises(ScopeError, match="compilation failed"):
        check_compiles(bad, jnp.ones((3, 3)))


def test_sync_returns_value():
    x = sync(jnp.ones(4) * 2)
    np.testing.assert_array_equal(np.asarray(x), 2.0)


def test_flag_registry_types_and_duplicates():
    f = FlagRegistry()
    f.declare("a/x", type=int, default=1, owner="a")
    f.declare("a/flag", is_bool=True, default=False, owner="a")
    with pytest.raises(ValueError, match="already declared"):
        f.declare("a/x", owner="b")
    f.parse(["--a.x", "5", "--a.flag"])
    assert f.get("a/x") == 5
    assert f.get("a/flag") is True
    assert f.get("missing", 9) == 9


def test_timer_and_logger():
    log = get_logger("test")
    with Timer() as t:
        sum(range(1000))
    assert t.elapsed >= 0
    log.info("ok")                        # no crash, handler configured


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(env_dir, tmp_path):
    """Without JAX_COMPILATION_CACHE_DIR the persistent compile cache is
    the fixed ``.jax_cache`` at the checkout root; with it, JAX's own
    reading of the variable is left alone."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = subprocess.run(
        [sys.executable, "-c",
         "import repro, jax; print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env, check=True)
    want = str(tmp_path / env_dir) if env_dir \
        else os.path.join(root, ".jax_cache")
    assert r.stdout.strip() == want
