"""Streaming generators (parity: _raylet.pyx StreamingObjectRefGenerator
:267 + streaming-generator executor :918)."""

import time

import pytest

import ray_tpu
from ray_tpu.core.generator import ObjectRefGenerator


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_task_streaming_basic(rt):
    @ray_tpu.remote(num_returns="streaming")
    def counter(n):
        for i in range(n):
            yield i * 10

    gen = counter.remote(5)
    assert isinstance(gen, ObjectRefGenerator)
    values = [ray_tpu.get(ref) for ref in gen]
    assert values == [0, 10, 20, 30, 40]


def test_streaming_consumes_while_running(rt, tmp_path):
    """The first item reaches the consumer while the producer is still
    running: the producer goes on to its second item only once the
    consumer says that it holds the first (an order of events, where a
    latency under 0.5 s was a race against five other test workers)."""
    got_first = tmp_path / "got_first"    # seen from the worker process

    @ray_tpu.remote(num_returns="streaming")
    def slow():
        yield "first"
        deadline = time.monotonic() + 60
        while not got_first.exists() and time.monotonic() < deadline:
            time.sleep(0.005)
        yield "second" if got_first.exists() else "first never consumed"

    gen = slow.remote()
    assert ray_tpu.get(next(gen)) == "first"
    got_first.touch()
    assert ray_tpu.get(next(gen)) == "second"
    with pytest.raises(StopIteration):
        next(gen)


def test_streaming_error_mid_stream(rt):
    @ray_tpu.remote(num_returns="streaming")
    def flaky():
        yield 1
        yield 2
        raise RuntimeError("stream broke")

    gen = flaky.remote()
    assert ray_tpu.get(next(gen)) == 1
    assert ray_tpu.get(next(gen)) == 2
    bad_ref = next(gen)  # ref to the failing index
    with pytest.raises(Exception, match="stream broke"):
        ray_tpu.get(bad_ref)
    with pytest.raises(StopIteration):
        next(gen)


def test_streaming_empty(rt):
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        return
        yield  # pragma: no cover

    assert list(empty.remote()) == []


def test_streaming_non_iterable_fails(rt):
    @ray_tpu.remote(num_returns="streaming")
    def notgen():
        return 42

    gen = notgen.remote()
    ref = next(gen)
    with pytest.raises(Exception, match="iterable"):
        ray_tpu.get(ref)


def test_actor_streaming_method(rt):
    @ray_tpu.remote
    class Producer:
        @ray_tpu.method(num_returns="streaming")
        def produce(self, n):
            for i in range(n):
                yield {"i": i}

        def ping(self):
            return "ok"

    p = Producer.remote()
    gen = p.produce.remote(3)
    assert isinstance(gen, ObjectRefGenerator)
    assert [ray_tpu.get(r)["i"] for r in gen] == [0, 1, 2]
    # Ordering with normal methods still works.
    assert ray_tpu.get(p.ping.remote()) == "ok"


def test_actor_streaming_to_dead_actor(rt):
    @ray_tpu.remote
    class P:
        @ray_tpu.method(num_returns="streaming")
        def produce(self):
            yield 1

    p = P.remote()
    ray_tpu.get(p.produce.remote().__next__())  # warm: actor alive
    ray_tpu.kill(p)
    time.sleep(0.3)
    gen = p.produce.remote()
    ref = next(gen)
    with pytest.raises(Exception):
        ray_tpu.get(ref)


def test_streaming_timeout(rt):
    from ray_tpu.core.exceptions import GetTimeoutError

    @ray_tpu.remote(num_returns="streaming")
    def slow():
        time.sleep(5)
        yield 1

    gen = slow.remote()
    with pytest.raises(GetTimeoutError):
        gen.next_ready(timeout=0.1)
