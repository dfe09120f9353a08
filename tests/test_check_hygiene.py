"""The CI hygiene gate's rules — stdlib-only, loaded by file path like
check_perf (``benchmarks`` is a script directory, not a package).

The rule set must flag generated files (bytecode, ``artifacts/`` JSON,
``*.trace.json`` timelines) while leaving the COMMITTED benchmark
baselines under ``benchmarks/baselines/`` alone — that distinction is
the whole point of the path-anchored ``artifacts/`` rule.
"""
import importlib.util
import pathlib
import subprocess

_PATH = (pathlib.Path(__file__).resolve().parent.parent
         / "benchmarks" / "check_hygiene.py")
_spec = importlib.util.spec_from_file_location("check_hygiene", _PATH)
check_hygiene = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_hygiene)


def test_clean_paths_pass():
    clean = [
        "src/repro/kernels/packing.py",
        "benchmarks/baselines/BENCH_throughput.json",  # committed baseline
        "benchmarks/check_hygiene.py",
        ".github/workflows/ci.yml",
        "artifacts/README",                 # not .json
        "docs/trace.json.md",               # not *.trace.json
        "src/repro/impact/artifacts_helper.py",  # 'artifacts/' only at root
        "src/repro/compile_cache.py",       # the helper, not the cache
        "tests/_hypothesis_compat.py",      # the shim, not the database
    ]
    assert check_hygiene.find_violations(clean) == []


def test_generated_paths_flagged():
    bad = [
        "src/repro/__pycache__/ops.cpython-310.pyc",
        "__pycache__/x.py",
        "src/repro/kernels/ops.pyc",
        "artifacts/BENCH_throughput.json",
        "artifacts/nested/BENCH_serve.json",
        "artifacts/SERVE_continuous.trace.json",
        "somewhere/else/SERVE_flush.trace.json",
        ".jax_cache/jit_predict-0123abcd-cache",
        ".hypothesis/examples/0a1b2c/3d4e5f",
    ]
    got = check_hygiene.find_violations(bad)
    assert [p for p, _ in got] == bad
    labels = dict(got)
    assert "bytecode" in labels["src/repro/kernels/ops.pyc"]
    assert "artifact" in labels["artifacts/BENCH_throughput.json"]
    assert "tracing" in labels["somewhere/else/SERVE_flush.trace.json"]
    assert "compilation cache" in labels[
        ".jax_cache/jit_predict-0123abcd-cache"]
    assert "hypothesis" in labels[".hypothesis/examples/0a1b2c/3d4e5f"]


def test_gitignore_gaps():
    """Every policed artifact class must have its ignore line; comments
    and surrounding noise don't count as coverage."""
    full = list(check_hygiene.REQUIRED_IGNORES)
    assert check_hygiene.gitignore_gaps(full) == []
    assert check_hygiene.gitignore_gaps(
        full + ["# noise", "", "  *.tmp  "]) == []
    missing_traces = [p for p in full if p != "*.trace.json"]
    assert check_hygiene.gitignore_gaps(missing_traces) == ["*.trace.json"]
    for generated in (".jax_cache/", ".hypothesis/"):
        assert check_hygiene.gitignore_gaps(
            [p for p in full if p != generated]) == [generated]
    assert check_hygiene.gitignore_gaps(["# *.trace.json"]) == full


def test_this_repo_gitignore_covers_required():
    """The regression that motivated the check: three SERVE_*.trace.json
    files sat tracked because .gitignore never matched traces.  The real
    .gitignore must cover every policed class."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    lines = (repo / ".gitignore").read_text().splitlines()
    assert check_hygiene.gitignore_gaps(lines) == []


def test_this_repo_tracks_no_serve_traces():
    repo = pathlib.Path(__file__).resolve().parent.parent
    res = subprocess.run(["git", "-C", str(repo), "ls-files",
                          "artifacts/"], capture_output=True, text=True)
    if res.returncode != 0:
        import pytest
        pytest.skip("not a git checkout")
    assert [p for p in res.stdout.splitlines()
            if p.endswith(".trace.json")] == []


def test_main_against_a_real_repo(tmp_path, monkeypatch, capsys):
    """End to end on a throwaway git repo: clean tree exits 0; a tracked
    artifact flips the exit code and prints a ::error annotation; a
    .gitignore coverage gap flips it independently."""
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    (tmp_path / "ok.py").write_text("x = 1\n")
    (tmp_path / ".gitignore").write_text(
        "\n".join(check_hygiene.REQUIRED_IGNORES) + "\n")
    subprocess.run(["git", "-C", str(tmp_path), "add", "ok.py",
                    ".gitignore"], check=True)
    monkeypatch.chdir(tmp_path)
    assert check_hygiene.main() == 0
    assert "passed" in capsys.readouterr().out

    (tmp_path / ".gitignore").write_text("*.pyc\n")   # coverage gap
    assert check_hygiene.main() == 1
    out = capsys.readouterr().out
    assert "::error file=.gitignore::missing ignore pattern" in out
    (tmp_path / ".gitignore").write_text(
        "\n".join(check_hygiene.REQUIRED_IGNORES) + "\n")

    art = tmp_path / "artifacts"
    art.mkdir()
    (art / "BENCH_throughput.json").write_text("{}")
    subprocess.run(["git", "-C", str(tmp_path), "add", "-f",
                    "artifacts/BENCH_throughput.json"], check=True)
    assert check_hygiene.main() == 1
    out = capsys.readouterr().out
    assert "::error file=artifacts/BENCH_throughput.json::" in out
    assert "FAILED" in out


def test_this_repo_is_clean():
    """The gate the hygiene CI job runs, run here too: the actual tree
    must never track a generated file."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    res = subprocess.run(["git", "-C", str(repo), "ls-files"],
                        capture_output=True, text=True)
    if res.returncode != 0:
        import pytest
        pytest.skip("not a git checkout")
    paths = [ln for ln in res.stdout.splitlines() if ln]
    assert check_hygiene.find_violations(paths) == []
