"""What tests/conftest.py does for the run itself: the files that say
they are long come first, a session has one compilation cache, and a
case that hangs fails alone at its limit."""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp

REPO = pathlib.Path(__file__).resolve().parent.parent


def _pytest(tmp_path, *args):
    """A session of its own over the files in ``tmp_path``, under this
    repo's conftest (loaded as a plugin: the files lie outside tests/)."""
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-q", *args,
         str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=300)


def test_a_case_that_hangs_fails_alone_by_name(tmp_path):
    (tmp_path / "test_hang.py").write_text(
        "import time, threading, pytest\n"
        "@pytest.mark.case_limit(1)\n"
        "def test_sleeps_for_ever():\n"
        "    threading.Thread(target=time.sleep, args=(5,), daemon=True,"
        " name='beside').start()\n"
        "    time.sleep(3600)\n"
        "def test_after_it():\n"
        "    pass\n")
    proc = _pytest(tmp_path)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert ("test_hang.py::test_sleeps_for_ever was still running at its "
            "limit of 1 s") in out, out
    # every thread's stack: the sleeping case's and the one beside it
    assert "in test_sleeps_for_ever" in out and "Current thread" in out, out
    assert out.count("Thread 0x") >= 1, out


def test_long_files_are_collected_first_longest_first(tmp_path):
    for name, mark in [("a", ""), ("b", "pytestmark = pytest.mark.long_file"
                                   "(100)\n"),
                       ("c", "pytestmark = pytest.mark.long_file(300)\n"),
                       ("d", "")]:
        (tmp_path / f"test_{name}.py").write_text(
            f"import pytest\n{mark}"
            "def test_one():\n    pass\n"
            "def test_two():\n    pass\n")
    proc = _pytest(tmp_path, "--collect-only")
    files = [ln.split("::")[0].rsplit("/", 1)[-1]
             for ln in proc.stdout.splitlines() if "::" in ln]
    assert files == ["test_c.py"] * 2 + ["test_b.py"] * 2 + [
        "test_a.py"] * 2 + ["test_d.py"] * 2, proc.stdout + proc.stderr


def test_xdist_takes_the_files_in_collection_order(request):
    """--dist loadfile sorts the files by their number of cases unless
    told not to; where xdist is not loaded there is nothing to tell."""
    assert not getattr(request.config.option, "loadscopereorder", False)


def test_the_session_has_one_compile_cache_that_takes_small_programs():
    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == placed
    jax.jit(lambda x: x * 3.25 + os.getpid() % 7)(jnp.ones((3, 7)))
    assert any(os.scandir(placed)), "a compiled program left no entry"


def test_the_budget_script_names_each_breach(tmp_path):
    """scripts/tier1_seconds.py over a hand-made junit file: a file over
    400 s, a long file without the marker, too many seconds added and a
    new case over 60 s are each a breach; the parent against itself is
    none."""
    def junit(name, cases):
        rows = "".join(
            f'<testcase classname="{c}" name="{n}" time="{t}"/>'
            for c, n, t in cases)
        (tmp_path / name).write_text(
            f"<testsuites><testsuite>{rows}</testsuite></testsuites>")
        return str(tmp_path / name)

    marked, bare = "tests.test_spec_decode", "tests.test_metrics"
    parent = junit("parent.xml", [(marked, "a", 200.0), (bare, "b", 5.0)])
    change = junit("change.xml", [(marked, "a", 200.0), (marked, "c", 250.0),
                                  (bare, "b", 95.0)])

    def run(*files):
        return subprocess.run(
            [sys.executable, str(REPO / "scripts" / "tier1_seconds.py"),
             *files], capture_output=True, text=True, timeout=60)

    clean = run(parent, parent)
    assert clean.returncode == 0 and "BREACH" not in clean.stdout, clean
    found = run(change, parent)
    assert found.returncode == 1, found
    for words in ("file over 400 s: tests/test_spec_decode.py 450 s",
                  "file of 95 s without pytest.mark.long_file: "
                  "tests/test_metrics.py",
                  "340 s added, over 150",
                  "new case over 60 s: tests/test_spec_decode.py::c"):
        assert "BREACH " + words in found.stdout, found.stdout
