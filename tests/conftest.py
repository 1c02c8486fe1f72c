"""Test harness: fake an 8-chip mesh on CPU.

The reference tests multi-node behavior by running N raylets as local
processes (ray: python/ray/cluster_utils.py:108); the TPU analogue is a
virtual multi-device CPU backend — 8 XLA host devices let every
sharding/collective path (dp/fsdp/tp/sp/ep) compile and run without
TPU hardware.  Must be set before jax initializes its backends.

The harness also keeps the suite inside its time (PR 49): the files
that say they are long start first, the session compiles a program
once, and a case that hangs fails alone.
"""

import atexit
import faulthandler
import os
import shutil
import signal
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell may preset a TPU platform
# tests/test_mosaic_aot*.py each ask libtpu to describe a v5e, on
# workers of their own: without this the second to load it is refused.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
if "PYTEST_XDIST_WORKER" not in os.environ:
    # One directory for the session, made and taken away by the process
    # that is no xdist worker; the workers, the serving tests' replicas
    # and the smoke's subprocesses inherit it through the environment.
    # In it ONE compilation cache, small programs included, so that the
    # toy program six of them trace is compiled by the first.
    _session = tempfile.mkdtemp(prefix="raytpu-tests-")
    atexit.register(shutil.rmtree, _session, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_session, "xla")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    # For every entry it loads, XLA:CPU logs two paragraphs of machine
    # features at level ERROR ("could lead to SIGILL": here the machine
    # that compiled is the one that runs).  What XLA refuses still
    # reaches a test as an exception.
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
# The cases that killed a worker of this session, one node id a line.
_CRASHED = os.path.join(
    os.path.dirname(os.environ["JAX_COMPILATION_CACHE_DIR"]), "crashed")

import jax  # noqa: E402

# Tests always run on the virtual CPU mesh; pin it at config level too,
# so an environment that lists the TPU first cannot take the chip.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


# ---------------------------------------------------------------------------
# RLlib learning gates: every algorithm's learning test records its
# (algo, env, achieved, gate) here and the suite prints one table at the
# end — the reference's rllib/tuned_examples/ pattern, condensed.
_LEARNING_ROWS = []


@pytest.fixture
def learning_table():
    """Record an algorithm's achieved return against its solved gate."""

    def record(algo: str, env: str, achieved: float, gate: float):
        _LEARNING_ROWS.append((algo, env, float(achieved), float(gate)))

    return record


CASE_LIMIT_S = 600


def pytest_configure(config):
    # --dist loadfile hands out the files with the most cases first by
    # default; this suite's long files hold few.  Collection order
    # instead, which pytest_collection_modifyitems below sets.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers",
        "long_file(seconds): module-level; what the file takes of one "
        "worker in the whole tier-1 run.  Files that carry it start "
        "first, longest first")
    config.addinivalue_line(
        "markers",
        f"case_limit(seconds): the case fails at this many seconds "
        f"instead of {CASE_LIMIT_S}")
    config.addinivalue_line(
        "markers",
        "slow: heavy interpret-mode kernel tests, excluded from the "
        "tier-1 `-m 'not slow'` sweep (run explicitly with -m slow)")
    config.addinivalue_line(
        "markers",
        "doctor_corrupt: test intentionally corrupts engine state "
        "(RAYTPU_FAILPOINTS injectors) — skip the autouse deep-audit "
        "teardown that would fail it")


@pytest.hookimpl(optionalhook=True)      # xdist's: absent under -p no:xdist
def pytest_handlecrashitem(crashitem, report, sched):
    """xdist's hook for a worker that died (here: ``_case_limit``'s
    backstop).  ``--dist loadfile`` books the case as crashed and then
    puts the dead worker's files back in the queue with that case still
    to run; the next worker would die of it too.  It is written down
    here, and ``_case_limit`` fails it at once where it comes again."""
    with open(_CRASHED, "a") as f:
        f.write(crashitem + "\n")


def pytest_collection_modifyitems(items):
    def seconds(item):
        mark = item.get_closest_marker("long_file")
        return mark.args[0] if mark else 0

    items.sort(key=lambda item: -seconds(item))    # stable: files stay whole


@pytest.fixture(autouse=True)
def _case_limit(request):
    """A case that is still running at its limit fails by name with
    every thread's stack, and the run goes on.  Where the main thread
    sits in a call no signal interrupts, the process exits a minute
    later: xdist books the case as crashed and starts another worker."""
    if os.path.exists(_CRASHED):
        with open(_CRASHED) as f:
            if request.node.nodeid in f.read().splitlines():
                pytest.fail(f"{request.node.nodeid} took a worker down with "
                            f"it in this session and is not run again",
                            pytrace=False)
    mark = request.node.get_closest_marker("case_limit")
    limit = mark.args[0] if mark else CASE_LIMIT_S

    def fire(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"{request.node.nodeid} was still running at its "
                    f"limit of {limit} s; every thread's stack:\n{stacks}",
                    pytrace=False)

    before = signal.signal(signal.SIGALRM, fire)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + 60, exit=True)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(autouse=True)
def _doctor_teardown(request):
    """Every LLMEngine a test creates gets a deep invariant audit on
    teardown (util/doctor via serve/audit.live_engines): a test that
    leaks a KV page, a trie ref or an adapter borrow fails HERE, at
    the test that caused it, not three tests later when the pool runs
    dry.  Pre-existing engines (session/module fixtures) are audited
    by the test that created them only; crashed engines are skipped
    (their state is arbitrarily torn); @pytest.mark.doctor_corrupt
    opts intentional-corruption tests out."""
    import sys

    before = set()
    if "ray_tpu.serve.audit" in sys.modules:
        from ray_tpu.serve import audit

        before = {e.engine_id for e in audit.live_engines()}
    yield
    if "ray_tpu.serve.llm_engine" not in sys.modules:
        return
    if request.node.get_closest_marker("doctor_corrupt"):
        return
    from ray_tpu.serve import audit

    problems = []
    for eng in audit.live_engines():
        if eng.engine_id in before or getattr(eng, "_crashed", False):
            continue
        try:
            rep = eng.doctor(deep=True)
        except Exception:
            # Wedged loop / shutdown race — not this test's verdict.
            continue
        for row in rep["checks"]:
            for v in row["violations"]:
                problems.append(
                    f"{eng.engine_id}: {v['check']} [{v['severity']}] "
                    f"{v['subject']}: expected {v['expected']!r}, "
                    f"got {v['actual']!r}")
    if problems:
        pytest.fail(
            "doctor: engine invariants violated after test:\n  "
            + "\n  ".join(problems), pytrace=False)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _LEARNING_ROWS:
        return
    terminalreporter.section("RLlib learning gates")
    terminalreporter.write_line(
        f"{'algorithm':12s} {'env':14s} {'achieved':>10s} {'gate':>10s}")
    for algo, env, ach, gate in sorted(_LEARNING_ROWS):
        mark = "ok" if ach > gate else "FAIL"
        terminalreporter.write_line(
            f"{algo:12s} {env:14s} {ach:10.1f} {gate:10.1f}  {mark}")
