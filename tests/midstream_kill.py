"""A kill that lands mid-stream whatever the machine's load.

A warm replica ends a dozen-token stream in a fraction of a second, so a
test that waits for tokens and then kills a replica by seed may kill one
whose streams have ended: nothing fails over and the test says so, one
run in four on an idle machine.  Here the replicas' steps stand still
while a file exists (they are other processes: a path is what they can
see), the victim is a seeded choice among the replicas that still hold a
request, and the file goes once the kill has landed.
"""

import contextlib
import os
import random
import time

import pytest

from ray_tpu.core import api
from ray_tpu.utils.test_utils import kill_actor_hard


@pytest.fixture
def hold(tmp_path):
    """While this file exists no replica takes a step (imported by the
    test files that kill mid-stream)."""
    return tmp_path / "hold_steps"


def throttle(hold, step_s):
    """The callback a slow adapter's step rides (``jax.debug.callback``,
    ordered): ``step_s`` of sleep, then as long as ``hold`` exists."""
    hold = str(hold)

    def wait():
        time.sleep(step_s)
        deadline = time.monotonic() + 60
        while os.path.exists(hold) and time.monotonic() < deadline:
            time.sleep(0.002)

    return wait


@contextlib.contextmanager
def streams_held(hold):
    """No replica takes a step inside this block."""
    hold.touch()
    try:
        yield
    finally:
        hold.unlink()


def replicas_holding_a_request(app, deployment):
    """``{replica_id: handle}`` of the routable replicas that hold a
    request now, by what each replica says itself."""
    from ray_tpu.serve.handle import _routers

    router = _routers[(app, deployment)]
    with router._lock:
        replicas = {rid: info.handle
                    for rid, info in router._replicas.items()}
    return {rid: h for rid, h in sorted(replicas.items())
            if api.get(h.num_ongoing_requests.remote(), timeout=60) > 0}


def kill_a_replica_mid_stream(app, deployment, hold, arrive_s=0.0):
    """Hold every stream where it is, hard-kill one replica that holds
    one, let go.  Returns the victim's replica id.  ``arrive_s`` is for
    a caller whose requests may still be on their way to a replica."""
    with streams_held(hold):
        deadline = time.monotonic() + arrive_s
        live = replicas_holding_a_request(app, deployment)
        while not live and time.monotonic() < deadline:
            time.sleep(0.01)
            live = replicas_holding_a_request(app, deployment)
        assert live, "every stream ended before the kill"
        victim = random.Random(0).choice(list(live))
        kill_actor_hard(api.runtime(), live[victim]._actor_id)
    return victim
