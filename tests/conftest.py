"""Test harness: fake an 8-chip mesh on CPU.

The reference tests multi-node behavior by running N raylets as local
processes (ray: python/ray/cluster_utils.py:108); the TPU analogue is a
virtual multi-device CPU backend — 8 XLA host devices let every
sharding/collective path (dp/fsdp/tp/sp/ep) compile and run without
TPU hardware.  Must be set before jax initializes its backends.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell may preset a TPU platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

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


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy interpret-mode kernel tests, excluded from the "
        "tier-1 `-m 'not slow'` sweep (run explicitly with -m slow)")
    config.addinivalue_line(
        "markers",
        "doctor_corrupt: test intentionally corrupts engine state "
        "(RAYTPU_FAILPOINTS injectors) — skip the autouse deep-audit "
        "teardown that would fail it")


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
