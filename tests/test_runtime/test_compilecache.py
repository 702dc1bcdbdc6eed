"""Persistent compile cache: where it lives, and actual reuse.

The contract under test (runtime/compilecache.py): the cache directory is
placed from outside — ``JAX_COMPILATION_CACHE_DIR`` exported means the
program leaves the variable and ``jax_compilation_cache_dir`` exactly as
set; not exported means one fixed path in the checkout.  A process that
compiled before enabling still reads/writes the cache (the reset_cache()
fix); identical programs hit — in the same process and, the point of the
feature, across processes that share the directory.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from polyaxon_tpu.runtime import compilecache as cc

_JAX_ENV = (
    "JAX_COMPILATION_CACHE_DIR",
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
)
REPO = Path(__file__).resolve().parents[2]


@pytest.fixture()
def cache_env(monkeypatch, tmp_path):
    """Snapshot/restore everything enable_compile_cache mutates: module
    status, jax's env variables, jax config, and the cache singleton — so
    the suite's other tests never see an armed cache.  The fixed default
    is pointed into tmp_path so no test writes into the checkout."""
    import jax
    from jax._src import compilation_cache as jcc

    saved_env = {k: os.environ.get(k) for k in _JAX_ENV}
    for k in _JAX_ENV:
        os.environ.pop(k, None)
    saved_cfg = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
    )
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", tmp_path / "default_cc")
    cc._reset_for_tests()
    yield cc
    cc._reset_for_tests()
    for k, v in saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    jax.config.update("jax_compilation_cache_dir", saved_cfg[0])
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", saved_cfg[1]
    )
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", saved_cfg[2]
    )
    jcc.reset_cache()


def test_default_is_one_fixed_git_ignored_path_in_the_checkout():
    """Not a function of a base dir, a temp name, a pid or the time."""
    assert cc.DEFAULT_CACHE_DIR == REPO / ".compile_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".compile_cache/" in ignored


class TestPlacement:
    def test_set_from_outside_is_left_untouched(self, cache_env, tmp_path):
        import jax

        outside = tmp_path / "outside"
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(outside)
        before = jax.config.jax_compilation_cache_dir
        st = cc.enable_compile_cache()
        assert st.enabled and st.cache_dir == str(outside)
        assert "JAX_COMPILATION_CACHE_DIR" in st.reason
        # Neither the variable nor the config is assigned by the program.
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(outside)
        assert jax.config.jax_compilation_cache_dir == before
        assert outside.is_dir()
        assert not cc.DEFAULT_CACHE_DIR.exists()
        assert cc.cache_dir() == str(outside)

    def test_unset_means_the_fixed_path(self, cache_env):
        import jax

        st = cc.enable_compile_cache()
        default = str(cc.DEFAULT_CACHE_DIR)
        assert st.enabled and st.cache_dir == default
        # Exported so children inherit it, and an already-imported jax is
        # brought in line.
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == default
        assert jax.config.jax_compilation_cache_dir == default
        assert cc.cache_dir() == default
        assert cc.cache_status() is st

    def test_thresholds_default_to_persist_everything(self, cache_env):
        cc.enable_compile_cache()
        # min_entry_size -1: persist regardless of executable size (the
        # CPU smoke configs compile tiny modules).
        assert os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "-1"
        assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"

    def test_outside_thresholds_win(self, cache_env):
        import jax

        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "2.5"
        cc.enable_compile_cache()
        assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2.5"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 2.5

    def test_unusable_dir_is_a_disabled_status(self, cache_env, tmp_path):
        blocked = tmp_path / "file_not_dir"
        blocked.write_text("occupied")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(blocked / "cc")
        st = cc.enable_compile_cache()
        assert not st.enabled
        assert "unusable" in st.reason

    def test_status_placeholder_when_never_enabled(self, cache_env):
        st = cc.cache_status()
        assert not st.enabled
        assert "not enabled" in st.reason

    def test_spawner_injects_no_cache_dir(self):
        """Children inherit the variable; the gang env contract carries
        no cache directory of its own."""
        from polyaxon_tpu.runtime.env import gang_env

        env = gang_env(
            run_id=1, run_uuid="u", run_dir="/r", spec_path="/r/spec.json",
            process_id=0, num_processes=1, coordinator=None,
            devices_per_host=1, accelerator="cpu-1", mesh_axes={"data": 1},
            strategy="ddp", strategy_options={},
        )
        assert not [k for k in env if "CACHE" in k]


class TestReuse:
    def test_in_process_hit_after_reset(self, cache_env):
        """Arm AFTER this process already compiled plenty (the whole
        test session) — reset_cache() must still make writes and reads
        work: first compile of a novel program misses (entry written),
        an identical fresh jit hits."""
        import jax
        import jax.numpy as jnp

        from polyaxon_tpu.tracking.ledger import compile_cache_telemetry

        assert cc.enable_compile_cache().enabled
        d = cc.DEFAULT_CACHE_DIR
        h0, m0 = compile_cache_telemetry()
        jax.jit(lambda x: (x * 3.0 - 1.0).sum())(jnp.arange(11.0))
        h1, m1 = compile_cache_telemetry()
        assert m1 > m0, "cold compile should write a cache entry"
        assert any(d.iterdir()), "cache dir should hold the entry"
        # A DIFFERENT function object, identical program → same XLA
        # module → persistent-cache read, not a recompile.
        jax.jit(lambda x: (x * 3.0 - 1.0).sum())(jnp.arange(11.0))
        h2, _ = compile_cache_telemetry()
        assert h2 > h1, "identical program should hit the cache"

    def test_aot_compile_returns_executable(self, cache_env):
        import jax
        import jax.numpy as jnp
        import numpy as np

        cc.enable_compile_cache()
        jitted = jax.jit(lambda x: x * 2.0 + 0.5)
        x = jnp.arange(5.0)
        fn, secs = cc.aot_compile(jitted, x)
        assert fn is not jitted and secs > 0
        np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x) * 2.0 + 0.5)

    def test_aot_compile_lets_a_compile_error_propagate(self):
        """A failed lower()/compile() is reported from where it happened,
        not swallowed into a second, lazy compile."""
        import jax
        import jax.numpy as jnp

        def bad(x):
            return x @ jnp.ones((3, 3))  # [5] @ [3,3]: shape error at trace

        with pytest.raises(TypeError):
            cc.aot_compile(jax.jit(bad), jnp.arange(5.0))


_CHILD = textwrap.dedent(
    """
    import os, sys
    from polyaxon_tpu.runtime.compilecache import enable_compile_cache
    st = enable_compile_cache()          # before the jax import, as workers do
    assert st.enabled and st.cache_dir == sys.argv[1], st
    import jax, jax.numpy as jnp
    assert jax.config.jax_compilation_cache_dir == sys.argv[1]
    from polyaxon_tpu.tracking.ledger import (
        compile_cache_telemetry, install_compile_hooks,
    )
    install_compile_hooks()
    out = jax.jit(lambda x: (x @ x.T).sum() * 0.25)(
        jnp.arange(64.0).reshape(8, 8)
    )
    jax.block_until_ready(out)
    hits, misses = compile_cache_telemetry()
    print(f"HITS={hits} MISSES={misses}")
    """
)


@pytest.mark.slow
def test_cross_process_reuse(tmp_path):
    """The feature's reason to exist: a SECOND process compiling the
    same program loads it from the directory the outside placed, and
    nothing lands anywhere else."""
    d = str(tmp_path / "cc")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=d)

    def run():
        p = subprocess.run(
            [sys.executable, "-c", _CHILD, d],
            capture_output=True, text=True, timeout=240, env=env,
        )
        assert p.returncode == 0, p.stderr
        line = [l for l in p.stdout.splitlines() if l.startswith("HITS=")][-1]
        hits, misses = (int(part.split("=")[1]) for part in line.split())
        return hits, misses

    hits1, misses1 = run()
    assert misses1 > 0 and hits1 == 0, (hits1, misses1)
    hits2, misses2 = run()
    assert hits2 > 0 and misses2 == 0, (hits2, misses2)
