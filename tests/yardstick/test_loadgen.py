"""The load generator's promises: the same work for every seed in another
order, arrivals inside the window, and latency from the due instant."""

import collections
import time

import pytest

from benchmarks.harness import loadgen
from benchmarks.harness import request_metrics as rq
from benchmarks.harness import stats
from benchmarks.run import ROOT, load_json

CHAT = load_json(ROOT + "/benchmarks/traffic/chat.json")
# a closed-loop mix (no cell uses one yet): 8 clients, 4 questions on
# each of 16 documents a pass
DOCQA = {"loop": "closed", "clients": 8, "turns": 4, "grid_size": 16,
         "doc_len": {"dist": "loguniform", "min": 2048, "max": 8192},
         "suffix_len": {"dist": "loguniform", "min": 64, "max": 256},
         "output_len": {"dist": "loguniform", "min": 32, "max": 128},
         "schedule_seed": 23}
VOCAB = 32768


def _plan(seed, seconds=45.0):
    return loadgen.open_loop_plan(CHAT, seconds, seed, VOCAB)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_019)])
def test_chat_same_multiset_other_order(seeds):
    a, b = (_plan(s) for s in seeds)
    for measured in (True, False):
        ra = [p for p in a if p.measured == measured]
        rb = [p for p in b if p.measured == measured]
        assert len(ra) == len(rb)
        for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
            assert collections.Counter(map(key, ra)) == \
                collections.Counter(map(key, rb))
    wa = [p for p in a if p.measured]
    wb = [p for p in b if p.measured]
    assert len(wa) == round(CHAT["rate_per_s"] * 45.0)
    # the schedule is replayed: same order, same arrivals; other tokens
    assert [len(p.prompt) for p in wa] == [len(p.prompt) for p in wb]
    assert [p.due for p in wa] == [p.due for p in wb]
    assert [p.prompt for p in wa] != [p.prompt for p in wb]
    # another schedule_seed: the same multiset in another order, and
    # other arrival times
    other = loadgen.open_loop_plan(dict(CHAT, schedule_seed=99), 45.0,
                                   seeds[0], VOCAB)
    wo = [p for p in other if p.measured]
    assert sorted(len(p.prompt) for p in wo) == \
        sorted(len(p.prompt) for p in wa)
    assert [len(p.prompt) for p in wo] != [len(p.prompt) for p in wa]
    assert [p.due for p in wo] != [p.due for p in wa]


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_019)])
def test_mix_without_schedule_seed_draws_the_schedule_from_the_seed(seeds):
    mix = {k: v for k, v in CHAT.items() if k != "schedule_seed"}
    a, b = ([p for p in loadgen.open_loop_plan(mix, 45.0, s, VOCAB)
             if p.measured] for s in seeds)
    assert len(a) == len(b) == round(CHAT["rate_per_s"] * 45.0)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert collections.Counter(map(key, a)) == \
            collections.Counter(map(key, b))
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert [p.due for p in a] != [p.due for p in b]
    assert all(0.0 <= p.due < 45.0 for p in a + b)


def test_chat_same_seed_same_plan():
    a, b = _plan(5), _plan(5)
    assert [(p.due, p.prompt, p.max_new_tokens) for p in a] == \
        [(p.due, p.prompt, p.max_new_tokens) for p in b]


def test_chat_arrivals_inside_the_window_and_lengths_in_range():
    plan = _plan(9, seconds=30.0)
    win = [p for p in plan if p.measured]
    ramp = [p for p in plan if not p.measured]
    assert all(0.0 <= p.due < 30.0 for p in win)
    assert all(-CHAT["ramp_s"] <= p.due < 0.0 for p in ramp)
    assert [p.due for p in plan] == sorted(p.due for p in plan)
    lo, hi = CHAT["prompt_len"]["min"], CHAT["prompt_len"]["max"]
    assert all(lo <= len(p.prompt) <= hi for p in plan)
    assert all(1 <= t < VOCAB for p in plan for t in p.prompt)


def test_length_grid_is_a_quantile_grid():
    grid = loadgen.length_grid(CHAT["prompt_len"], 101)
    assert grid == sorted(grid)
    assert grid[50] == CHAT["prompt_len"]["median"]
    assert loadgen.length_grid({"dist": "loguniform", "min": 2048,
                                "max": 8192}, 16)[0] > 2048
    with pytest.raises(ValueError):
        loadgen.length_grid({"dist": "zipf"}, 4)


@pytest.mark.parametrize("seeds", [(1, 23), (2, 99)])
def test_closed_loop_clients_deal_the_whole_grid_each_pass(seeds):
    g, turns, n = DOCQA["grid_size"], DOCQA["turns"], DOCQA["clients"]
    want = sorted(loadgen.length_grid(DOCQA["doc_len"], g))
    per_pass = (g // n) * turns
    firsts = []
    for seed, sched in zip(seeds, (23, 99)):
        docs = []
        for c in range(n):
            stream = loadgen.closed_loop_stream(
                dict(DOCQA, schedule_seed=sched), seed, VOCAB, c)
            reqs = [next(stream) for _ in range(per_pass)]
            by_doc = collections.defaultdict(list)
            for p in reqs:
                by_doc[p.doc].append(p)
            for ps in by_doc.values():
                assert [p.turn for p in ps] == list(range(turns))
                doc_len = ps[0].doc_len
                shared = ps[0].prompt[:doc_len]
                assert all(p.prompt[:doc_len] == shared for p in ps)
                assert all(64 <= len(p.prompt) - doc_len <= 256 for p in ps)
            docs += [ps[0].doc_len for ps in by_doc.values()]
        firsts.append(docs)
        assert len(docs) == g
    # the same documents under both schedules, dealt in another order
    assert firsts[0] != firsts[1]
    assert sorted(firsts[0]) == sorted(firsts[1]) == want


class _StalledServer:
    """Answers one request at a time, each taking ``service_s``: later
    requests queue behind earlier ones."""

    def __init__(self, service_s):
        import threading

        self.lock = threading.Lock()
        self.service_s = service_s

    def __call__(self, p):
        def gen():
            with self.lock:
                time.sleep(self.service_s)
                yield 1
            for _ in range(p.max_new_tokens - 1):
                yield 1
        return gen()


def test_ttft_counts_from_the_due_instant():
    plan = [loadgen.Planned(idx=i, prompt=[1, 2], max_new_tokens=2,
                            due=0.01 * i) for i in range(6)]
    res = loadgen.run_open_loop(plan, _StalledServer(0.1), VOCAB,
                                max_inflight=1)
    recs = res["records"]
    assert all(r["ok"] for r in recs)
    ttft = rq.ttfts_ms(recs)
    # one worker, 100 ms of service each, all due within 50 ms: request i
    # waits for the i before it, and that wait is in its TTFT
    assert ttft == sorted(ttft)
    assert ttft[-1] > 500.0 and ttft[0] < 200.0
    late = [(r["sent"] - r["due"]) * 1e3 for r in recs]
    assert late[-1] > 300.0
    from_send = [(r["first"] - r["sent"]) * 1e3 for r in recs]
    assert max(from_send) < 200.0


def test_failed_request_is_counted_not_dropped():
    def send(p):
        if p.idx == 1:
            raise RuntimeError("refused")
        return iter([1] * (p.max_new_tokens - (p.idx == 2)))

    plan = [loadgen.Planned(idx=i, prompt=[1], max_new_tokens=3, due=0.0)
            for i in range(3)]
    recs = loadgen.run_open_loop(plan, send, VOCAB)["records"]
    assert [r["ok"] for r in recs] == [True, False, False]
    assert "refused" in recs[1]["error"] and "2 of 3" in recs[2]["error"]


def test_percentile_spread_and_completion():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([], 50) is None
    assert stats.percentile([0, 10], 90) == pytest.approx(9.0)
    assert stats.spread([100, 101, 99, 100, 102, 98]) == \
        pytest.approx(0.025)
    keeps_up = [i / 2.0 for i in range(60)]
    assert stats.completion_share(keeps_up, 2.0) == pytest.approx(1.0)
    saturated = [i / 1.0 for i in range(60)]
    assert stats.completion_share(saturated, 2.0) == pytest.approx(0.5)
