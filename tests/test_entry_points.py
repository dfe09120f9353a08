"""Entry-point plumbing: where the persistent compilation cache goes, and
the benchmark orchestrator's exit code."""
import pathlib

import jax
import pytest

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture()
def restore_cache_config():
    """The helper updates process-wide jax config: put it back, before
    any compile in this worker can open a cache."""
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_defaults_to_repo_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compilation_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_env_dir_wins(monkeypatch, tmp_path, restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    helper sets no other path."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("fails", [False, True], ids=["ok", "section_raises"])
def test_benchmark_run_exit_code(monkeypatch, capsys, fails):
    """A section that raises prints ``<name>/ERROR`` and fails the run;
    the other sections still run."""
    monkeypatch.syspath_prepend(str(REPO))
    from benchmarks import roofline, run, table6_comparison
    ran = []

    def section(name, boom):
        def main():
            ran.append(name)
            if boom:
                raise RuntimeError("section failed")
        return main

    monkeypatch.setattr(compile_cache, "use_compilation_cache", lambda: "")
    monkeypatch.setattr(roofline, "main", section("roofline", fails))
    monkeypatch.setattr(table6_comparison, "main",
                        section("table6", False))
    rc = run.main(["--only", "roofline,table6"])
    out = capsys.readouterr().out
    assert ran == ["roofline", "table6"]
    assert rc == (1 if fails else 0)
    assert ("roofline/ERROR" in out) == fails
