"""Test-isolation regressions (ISSUE 19 satellite; re-cut in ISSUE 21).

For two PRs the test_sentry rollback-parity suite failed "order-
sensitively": green alone, red after certain sibling files, different
failure sets on identical re-runs.  The leaking state was never a
module registry or an env var — it was the **persistent XLA
compilation cache** (`.xla_cache/`, enabled unconditionally by
tests/conftest.py at the time).  Executables deserialized from that
cache are not bitwise-equivalent to freshly compiled ones on the XLA:CPU
backend: with a warm cache the parity tests failed 6/8 runs (digest
mismatches flipping run-to-run, one `free(): invalid pointer` abort in
the deserialization path), and 8/8 passed with the cache cleared.
Cache warmth depends on what compiled before you — hence the illusion
of test-ORDER sensitivity across files and processes.

The contract pinned here has two halves:

- the CPU suite runs WITHOUT a persistent compilation cache — also when
  whoever runs it exports ``JAX_COMPILATION_CACHE_DIR`` (tests/conftest.py
  drops it) — so every bitwise invariant in tier-1 executes on freshly
  compiled programs only;
- the programs that run on the chip (``chip_smoke.py``, ``bench.py``)
  place their cache through ONE helper,
  ``paddle_tpu.core.chip.place_compile_cache``: the environment's
  directory when it names one, else ``<checkout>/.xla_cache``.
"""
import os
import subprocess
import sys

import jax

from paddle_tpu.core import chip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPersistentCacheIsolation:
    def test_persistent_compilation_cache_is_off(self):
        """Neither the conftest nor anything imported since armed jax's
        persistent compilation cache in this process."""
        assert jax.config.jax_compilation_cache_dir is None
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ

    def test_cache_stays_off_when_the_environment_exports_one(self, tmp_path):
        """A fresh pytest process started with JAX_COMPILATION_CACHE_DIR
        exported (as a chip driver's environment may have it) still runs
        the suite cache-less, and writes nothing there."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q",
             "tests/test_isolation.py::TestPersistentCacheIsolation::"
             "test_persistent_compilation_cache_is_off",
             "tests/test_sentry.py::TestRollbackParity::"
             "test_injected_nan_rollback_is_bitwise_identical",
             "-p", "no:cacheprovider", "-p", "no:randomly"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout[-3000:]
        assert not (tmp_path / "cc").exists()

    def test_cache_directory_stays_untracked(self):
        """The cache directory must never be committable: `.xla_cache/`
        stays in .gitignore (a committed cache re-creates the
        cross-machine flake for everyone)."""
        with open(os.path.join(REPO, ".gitignore")) as f:
            lines = [ln.strip() for ln in f]
        assert ".xla_cache/" in lines


class TestCompileCachePlacement:
    def test_a_set_environment_variable_is_left_alone(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        before = jax.config.jax_compilation_cache_dir
        assert chip.place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_checkouts_xla_cache(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            path = chip.place_compile_cache()
            assert path == os.path.join(REPO, ".xla_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            # this process must stay cache-less for the tests after it
            jax.config.update("jax_compilation_cache_dir", None)
