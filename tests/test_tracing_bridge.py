"""The program's spans in the profiler's trace (util/tracing's bridge to
``jax.profiler.TraceAnnotation``), the spans and counts the engine loop
and the trainer open through it, the stable names of the jitted steps
and of the scopes inside them, and the engine's always-on loop clock.

Everything runs on the CPU backend: a capture there holds the host plane
with the program's spans and their stats, and the XLA operations with
their ``hlo_module``; device times are no part of these tests.
"""

import collections
import contextlib
import glob
import queue
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    LLMServer,
    llama_paged_adapter,
)
from ray_tpu.serve.replica import ReplicaActor
from ray_tpu.util import flight_recorder, tracing

pytestmark = pytest.mark.long_file(131)

CFG = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=128, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32,
)
Span = collections.namedtuple("Span", "name line start end stats")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.key(0), CFG)


def _engine(params, adapter=None, **kw):
    cfg = dict(max_slots=4, max_seq_len=128, min_prefill_bucket=16,
               page_size=16, ragged_batching=True, token_budget=36,
               prefill_chunk=16)
    cfg.update(kw)
    return LLMEngine(params, adapter or llama_paged_adapter(CFG),
                     EngineConfig(**cfg))


@contextlib.contextmanager
def _capture(tmp_path):
    """A profiler capture without the Python tracer; yields a function
    that, after the block, returns the trace's events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    loaded = []

    def events():
        return loaded[0]

    try:
        yield events
    finally:
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData

        path = sorted(glob.glob(str(
            tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
        out = []
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    out.append(Span(e.name, (plane.name, i), e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
        loaded.append(out)


def _named(events, name):
    return sorted((e for e in events if e.name == name),
                  key=lambda e: e.start)


def _inside(child, parent):
    return (child.line == parent.line and parent.start <= child.start
            and child.end <= parent.end)


def _seqs(span):
    """The steps a fetch or an emit span covers: entries leave in
    dispatch order, so the span names the first and the last."""
    assert span.stats["seqs"] >= 1
    return list(range(span.stats["seq_first"], span.stats["seq_last"] + 1))


def test_fused_route_reports_the_cells_its_kernel_walks(params, tmp_path):
    """On the fused route ``llm.pack``'s grid_cells is what the layer
    kernel walks for the step's rows: each live row's pooled pages plus
    its self cell, below the page table's capacity."""
    import dataclasses

    adapter = llama_paged_adapter(dataclasses.replace(CFG, fused_decode=True))
    stated, walked = adapter.ragged_grid_cells, []

    def recording(row_start, row_len, maxp, page, lora):
        start, nlen = np.asarray(row_start), np.asarray(row_len)
        assert (maxp, page, lora) == (128 // 16, 16, False)
        walked.append(sum(
            sum(1 for pc in range(maxp) if pc * page < start[r]) + 1
            for r in range(len(nlen)) if nlen[r] > 0))
        return stated(row_start, row_len, maxp, page, lora)

    eng = _engine(params, dataclasses.replace(
        adapter, ragged_grid_cells=recording))
    try:
        eng.generate([1, 2, 3], max_new_tokens=2)
        del walked[:]
        with _capture(tmp_path) as events:
            streams = [eng.submit(list(range(1, n + 1)), max_new_tokens=6,
                                  temperature=0.0) for n in (40, 3, 32)]
            for s in streams:
                s.result(timeout_s=120)
            time.sleep(0.2)
    finally:
        eng.shutdown()
    packs = [p for p in _named(events(), "llm.pack") if "seq" in p.stats]
    assert [p.stats["grid_cells"] for p in packs] == walked
    capacity = 4 * (128 // 16 + 1)
    for p in packs:
        # a self cell a row, and no more pool cells than its pages
        assert (p.stats["rows"] <= p.stats["grid_cells"]
                <= p.stats["live_cells"] + p.stats["rows"] < capacity)
    # decode rows past their first page walk pool cells too
    assert max(p.stats["grid_cells"] - p.stats["rows"] for p in packs) >= 3


# -- the bridge ------------------------------------------------------------

def test_jamba_route_reports_the_cells_its_kernel_walks(tmp_path, monkeypatch):
    """The Jamba adapter states what ``ragged_paged_attention`` walks for
    the step's rows, the bounds of its two calls' grids together, not the
    page table's capacity; and the benchmark's ``page_cells_live_share``
    over such steps reads the pages the rows hold over that walk."""
    import dataclasses

    from benchmarks import run as bench_run
    from benchmarks.harness import program_spans
    from ray_tpu.models import jamba
    from ray_tpu.ops import ragged_paged_attention as rpa
    from ray_tpu.serve.llm_engine import jamba_paged_adapter

    cfg = jamba.JambaConfig(
        vocab_size=97, dim=64, n_layers=4, n_heads=4, n_kv_heads=1,
        head_dim=16, mlp_dim=96, attn_layer_period=3, attn_layer_offset=1,
        dt_rank=8, dtype=jnp.float32, param_dtype=jnp.float32)
    adapter = jamba_paged_adapter(cfg)
    stated, walked = adapter.ragged_grid_cells, []

    def recording(row_start, row_len, maxp, page, lora):
        calls = rpa.live_attention_cells(
            jnp.asarray(row_start), jnp.asarray(row_len),
            jnp.zeros(len(row_len), jnp.int32), 16, maxp, page)
        walked.append(sum(int(n_live[0]) for _ci, n_live, _mine in calls))
        return stated(row_start, row_len, maxp, page, lora)

    eng = LLMEngine(
        jamba.init_params(jax.random.key(0), cfg),
        dataclasses.replace(adapter, ragged_grid_cells=recording),
        EngineConfig(max_slots=4, max_seq_len=64, page_size=8, num_pages=32,
                     ragged_batching=True, prefill_chunk=8, token_budget=16))
    try:
        eng.generate([1, 2, 3], max_new_tokens=2)
        del walked[:]
        with _capture(tmp_path) as events:
            streams = [eng.submit(list(range(1, n + 1)), max_new_tokens=6,
                                  temperature=0.0) for n in (29, 3, 17)]
            for s in streams:
                s.result(timeout_s=300)
            time.sleep(0.2)
    finally:
        eng.shutdown()
    packs = [p for p in _named(events(), "llm.pack") if "seq" in p.stats]
    assert [p.stats["grid_cells"] for p in packs] == walked
    capacity = 4 * (64 // 8 + 1)
    for p in packs:
        assert (p.stats["rows"] <= p.stats["grid_cells"]
                <= p.stats["live_cells"] + p.stats["rows"] < capacity)
    assert max(p.stats["grid_cells"] - p.stats["rows"] for p in packs) >= 3

    # a chat_short-like step through the reader: 20 decode rows of a few
    # hundred tokens (4.4 pages a row, about the ledger's 89 live cells a
    # step) over a 64 x 24 table of 64-token pages.  A row of n pages
    # walks n + 1 cells, so the share is the pages over pages + rows,
    # where the capacity's walk read 100 * live / 1600
    start = np.zeros(64, np.int32)
    start[:20] = np.linspace(30, 460, 20).astype(np.int32)
    nlen = (np.arange(64) < 20).astype(np.int32)
    live = int(np.sum(-(-(start[:20] + 1) // 64)))
    grid = stated(start, nlen, 24, 64, False)
    assert live <= grid <= live + 20
    pack = {"seq": 1, "n_decode": 20, "n_prefill": 0, "n_spec": 0,
            "rows": 20, "budget": 320, "live_cells": live,
            "grid_cells": grid}
    monkeypatch.setattr(program_spans, "lines_of",
                        lambda run: [[("llm.pack", 0, 1, pack)]])
    share = bench_run.reader("page_cells_live_share")(None)
    assert share == pytest.approx(100.0 * live / grid)
    assert 80 < share < 85 and 100.0 * live / (64 * 25) < 6


def test_span_reaches_the_profiler_with_nesting_and_stats(tmp_path):
    assert not tracing.is_enabled()
    with _capture(tmp_path) as events:
        with tracing.span("bridge.outer", attributes={"seq": 7}) as sp:
            with tracing.span("bridge.inner"):
                time.sleep(0.002)
            sp.set(n_decode=3, share=0.5, label="x")
    (outer,), (inner,) = (_named(events(), "bridge.outer"),
                          _named(events(), "bridge.inner"))
    assert _inside(inner, outer)
    assert inner.end - inner.start >= 2_000_000
    assert outer.stats == {"seq": 7, "n_decode": 3, "share": 0.5,
                           "label": "x"}
    # tracing was off: the span kept no record of its own
    assert tracing.finished_spans() == [] or all(
        s["name"] not in ("bridge.outer", "bridge.inner")
        for s in tracing.finished_spans())


def test_span_off_and_uncaptured_touches_nothing(monkeypatch):
    """With tracing off and no capture a span is a flag test: it never
    reaches the span buffer, the export file or the flight recorder."""
    def boom(*_a, **_k):
        raise AssertionError("a disabled span recorded something")

    monkeypatch.setattr(tracing, "_finish", boom)
    monkeypatch.setattr(flight_recorder, "record", boom)
    assert not tracing.is_enabled()
    with tracing.span("bridge.quiet", attributes={"a": 1}) as sp:
        sp.set(b=2)
        assert sp.record is None
    # and a hot-loop span stays out of both even with tracing on
    tracing.enable_tracing()
    try:
        with tracing.span("bridge.hot", record=False) as sp:
            assert sp.record is None
    finally:
        tracing.disable_tracing()


def test_enabled_span_keeps_its_record_and_context():
    tracing.clear()
    tracing.enable_tracing()
    try:
        with tracing.span("bridge.parent", attributes={"k": 1}) as parent:
            with tracing.span("bridge.child") as child:
                child.set(late=True)
    finally:
        tracing.disable_tracing()
    by_name = {s["name"]: s for s in tracing.finished_spans()}
    assert by_name["bridge.child"]["parent_id"] == parent.record["span_id"]
    assert by_name["bridge.child"]["trace_id"] == parent.record["trace_id"]
    assert by_name["bridge.child"]["attributes"] == {"late": True}
    assert by_name["bridge.parent"]["attributes"] == {"k": 1}
    assert (by_name["bridge.parent"]["start"]
            <= by_name["bridge.child"]["start"]
            <= by_name["bridge.child"]["end"]
            <= by_name["bridge.parent"]["end"])
    tracing.clear()


# -- the engine loop's spans ----------------------------------------------

def _step_tokens():
    out = collections.Counter()
    for _n, tags, value, _k in \
            llm_engine._telemetry()["step_tokens"]._samples():
        out[dict(tags).get("phase")] += value
    return out


def test_engine_spans_chain_by_seq_and_count_tokens(params, tmp_path):
    eng = _engine(params)
    try:
        # warm up outside the capture: the first dispatch compiles
        eng.generate([1, 2, 3], max_new_tokens=2)
        before, steps0 = _step_tokens(), eng.stats()["steps"]
        with _capture(tmp_path) as events:
            rng = np.random.default_rng(0)
            streams = [eng.submit(rng.integers(1, 127, size=n).tolist(),
                                  max_new_tokens=6, temperature=0.0)
                       for n in (40, 3, 23)]
            for s in streams:
                s.result(timeout_s=120)
            # let the loop close its last iteration's spans
            time.sleep(0.2)
        after, steps1 = _step_tokens(), eng.stats()["steps"]
    finally:
        eng.shutdown()
    ev = events()
    packs = [p for p in _named(ev, "llm.pack") if "seq" in p.stats]
    seqs = [p.stats["seq"] for p in packs]
    assert seqs == list(range(steps0 + 1, steps1 + 1))
    # the counts at the boundary are the step_tokens counter's growth
    assert sum(p.stats["n_decode"] for p in packs) == \
        after["decode"] - before["decode"]
    assert sum(p.stats["n_prefill"] for p in packs) == \
        after["prefill"] - before["prefill"] == 40 + 3 + 23
    # this adapter's step is the unfused one, whose attention kernel
    # walks what the rows hold too: a self cell a row, and no more pool
    # cells than the rows' pages, below the page table's capacity
    capacity = 4 * (128 // 16 + 1)
    for p in packs:
        assert 0 < p.stats["live_cells"]
        assert (p.stats["rows"] <= p.stats["grid_cells"]
                <= p.stats["live_cells"] + p.stats["rows"] < capacity)
        # the append writes a page a row at least, and none but the
        # pages that hold the rows' tokens
        assert (p.stats["rows"] <= p.stats["append_cells"]
                <= p.stats["live_cells"])
        tokens = (p.stats["n_decode"] + p.stats["n_prefill"]
                  + p.stats["n_spec"])
        assert tokens <= p.stats["budget"] == 36
        # the positions the step's program was compiled for: 4 slots
        # rounded up to 8 where the step's tokens fit, else the budget
        assert p.stats["shape"] == (8 if tokens <= 8 else 36)
        assert p.stats["rows"] >= 1
    # every step has its dispatch and commit under the same seq, inside
    # the loop iteration that names it
    loops = _named(ev, "llm.loop")
    for name in ("llm.dispatch", "llm.commit"):
        assert [d.stats["seq"] for d in _named(ev, name)] == seqs
    by_seq = {lp.stats["seq"]: lp for lp in loops if "seq" in lp.stats}
    assert sorted(by_seq) == seqs
    for span in packs + _named(ev, "llm.dispatch"):
        assert _inside(span, by_seq[span.stats["seq"]])
    # the fetch thread fetched, and the loop emitted, each step once,
    # in order, on their own threads' lines
    fetched = [s for f in _named(ev, "llm.fetch") for s in _seqs(f)]
    emitted = [s for e in _named(ev, "llm.emit") for s in _seqs(e)]
    assert fetched == seqs and emitted == seqs
    assert ({f.line for f in _named(ev, "llm.fetch")}
            .isdisjoint({p.line for p in packs}))
    names = {e.name for e in ev}
    assert {"llm.control", "llm.admit", "llm.idle"} <= names
    # the step's operations run under the registered program's name
    modules = {e.stats["hlo_module"] for e in ev if "hlo_module" in e.stats}
    assert "jit_serve_ragged" in modules


def test_each_shapes_first_dispatch_is_tagged_as_a_compile(params):
    """The step program has an executable a shape, so it compiles twice:
    each first dispatch leaves an ``llm.ragged`` span tagged
    ``compile=true`` (the waterfall and the roofline join skip it) and a
    record of its own in the device plane, with its compile window."""
    from ray_tpu.util import xprof

    tracing.clear()
    tracing.enable_tracing()
    eng = _engine(params)
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)       # 3 tokens, then 1
        first = [s for s in tracing.finished_spans()
                 if s["name"] == "llm.ragged"]
        eng.generate(list(range(1, 21)), max_new_tokens=4)  # a 16-token chunk
        eng.generate([4, 5, 6], max_new_tokens=4)
    finally:
        eng.shutdown()
        tracing.disable_tracing()
    spans = [s for s in tracing.finished_spans() if s["name"] == "llm.ragged"]
    tracing.clear()
    assert [s["attributes"] for s in first] == [
        {"compile": True, "program": "serve.ragged@8"}]
    assert [s["attributes"] for s in spans] == [
        {"compile": True, "program": "serve.ragged@8"},
        {"compile": True, "program": "serve.ragged"}]
    programs = xprof.programs()
    for name, shape in (("serve.ragged@8", 8.0), ("serve.ragged", 36.0)):
        assert programs[name].cost_steps == shape
        assert programs[name].compile_time_s > 0
        assert programs[name].compiled_at is not None


def test_step_shape_fill_share_reads_the_shape_a_step_ran_at(monkeypatch):
    """The benchmark's reader divides a step's tokens by the positions
    its program was compiled for, and by the budget for a program that
    records no ``shape`` (this PR's parent): there it reads what
    ``token_budget_fill_share`` reads, and nothing without spans."""
    from benchmarks import run as bench_run
    from benchmarks.harness import program_spans

    def read(metric, packs):
        lines = [[("llm.pack", i, 1, dict(p, seq=i + 1, n_spec=0, budget=36))
                  for i, p in enumerate(packs)]]
        monkeypatch.setattr(program_spans, "lines_of", lambda run: lines)
        return bench_run.reader(metric)(None)

    packs = [{"n_decode": 2, "n_prefill": 0, "shape": 8},
             {"n_decode": 1, "n_prefill": 17, "shape": 36}]
    assert read("step_shape_fill_share", packs) == pytest.approx(37.5)
    assert read("token_budget_fill_share", packs) == pytest.approx(
        100 * (2 + 18) / 2 / 36)
    parents = [{k: v for k, v in p.items() if k != "shape"} for p in packs]
    assert read("step_shape_fill_share", parents) == \
        read("token_budget_fill_share", parents)
    assert read("step_shape_fill_share", []) is None


def test_trainer_spans_nest_under_the_step(tmp_path):
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        init_params=lambda r: llama.init_params(r, CFG),
        loss_fn=lambda p, b: llama.loss_fn(p, b, CFG),
        params_axes=llama.logical_axes(CFG),
        batch_axes={"tokens": ("batch", None)},
        scaling_config=ScalingConfig(mesh_spec=MeshSpec(dp=1),
                                     devices=jax.devices("cpu")[:1]),
        run_config=RunConfig(report_every=1), seed=0)
    rng = np.random.default_rng(0)

    def batches():
        while True:
            yield {"tokens": rng.integers(0, 128, (2, 16), dtype=np.int32)}

    assert trainer.fit(batches(), num_steps=1).error is None   # compiles
    with _capture(tmp_path) as events:
        result = trainer.fit(batches(), num_steps=3)
    assert result.error is None
    ev = events()
    steps = _named(ev, "train.step")
    assert [s.stats["step"] for s in steps] == [1, 2, 3]
    for name in ("train.data_wait", "train.compute", "train.report"):
        children = _named(ev, name)
        assert len(children) == 3, name
        for child, step in zip(children, steps):
            assert _inside(child, step), name
    modules = {e.stats["hlo_module"] for e in ev if "hlo_module" in e.stats}
    assert "jit_train_step" in modules


# -- stable names inside the programs --------------------------------------

def _lower_with_scopes(make_lowered, monkeypatch, scoped: bool):
    if not scoped:
        monkeypatch.setattr(jax, "named_scope",
                            lambda _name: contextlib.nullcontext())
    try:
        return make_lowered()
    finally:
        monkeypatch.undo()


def _lower_serving_step(params):
    adapter = llama_paged_adapter(CFG)
    cache = adapter.init_cache(8, 16)
    T, R, maxp = 16, 4, 8
    i32 = lambda *s: jnp.zeros(s, jnp.int32)   # noqa: E731

    def serve_ragged(params, cache, toks, pos, rs, rst, rl, ro, bt):
        logits, cache = adapter.ragged_step(params, toks, pos, rs, rst,
                                            rl, ro, bt, cache)
        return llm_engine._sample(logits, jnp.zeros((R,)),
                                  jax.random.key(0)), cache

    return jax.jit(serve_ragged).lower(
        params, cache, i32(T), i32(T), i32(R), i32(R), i32(R), i32(R),
        i32(R, maxp))


def _lower_train_step(params):
    from ray_tpu.train import default_optimizer
    from ray_tpu.train.state import create_train_state
    from ray_tpu.train.step import make_train_step

    import dataclasses

    cfg = dataclasses.replace(CFG, loss_chunk=8)   # the chunked head
    tx = default_optimizer()
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), tx)
    assert step.__name__ == "train_step"
    state = create_train_state(params, tx)
    return jax.jit(step).lower(
        state, {"tokens": jnp.zeros((2, 16), jnp.int32)})


@pytest.mark.parametrize("lower, scopes", [
    (_lower_serving_step, ("embed", "weight_slice", "attention", "mlp",
                           "kv_append", "lm_head", "sample")),
    (_lower_train_step, ("loss", "optimizer", "grad_norm", "embed",
                         "attention", "mlp", "lm_head")),
], ids=["serve_ragged", "train_step"])
def test_scopes_change_metadata_only(params, monkeypatch, lower, scopes):
    scoped = _lower_with_scopes(lambda: lower(params), monkeypatch, True)
    plain = _lower_with_scopes(lambda: lower(params), monkeypatch, False)
    # a scope is one element of an operation's name path, which may
    # start at it inside a loop's body; under a transformation it reads
    # jvp(attention), transpose(jvp(mlp))
    with_locations = scoped.as_text(debug_info=True)
    without = plain.as_text(debug_info=True)
    for scope in scopes:
        element = rf"[/(\"]{scope}[/)\"]"
        assert re.search(element, with_locations), scope
        assert not re.search(element, without), scope
    # without locations the two programs are the same text
    assert scoped.as_text() == plain.as_text()


def test_engine_programs_are_named_after_their_registration(params):
    eng = _engine(params, prefix_cache=True)
    try:
        assert eng._ragged_step_fn.__name__ == "serve_ragged"
        assert eng._prefill_batch_fn.__name__ == "serve_prefill"
        assert eng._decode_fn.__name__ == "serve_decode"
        assert eng._copy_page_fn.__name__ == "serve_copy_page"
    finally:
        eng.shutdown()


# -- the loop clock ---------------------------------------------------------

@pytest.fixture()
def slow_step(monkeypatch):
    """Every step takes ``sleep_s`` longer to come back, as the engine
    sees it: the fetch thread's ``device_get`` sleeps first.  (A sleep
    inside the jitted step will not do here: the CPU backend runs a step
    inside the call that dispatches it.)  The loop then fills its
    pipeline and waits, as it does behind a device."""
    device_get = jax.device_get

    def arm(sleep_s=0.01):
        def slow_get(x):
            if threading.current_thread().name == "llm-fetch":
                time.sleep(sleep_s)
            return device_get(x)

        monkeypatch.setattr(jax, "device_get", slow_get)

    return arm


@pytest.mark.parametrize("slow", [False, True],
                         ids=["plain_step", "slow_step"])
def test_loop_phases_account_for_the_loops_wall(params, slow, slow_step):
    if slow:
        slow_step()
    eng = _engine(params)
    try:
        eng.generate([1, 2, 3], max_new_tokens=2)
        rng = np.random.default_rng(1)
        streams = [eng.submit(rng.integers(1, 127, size=n).tolist(),
                              max_new_tokens=24, temperature=0.0)
                   for n in (40, 9, 23, 5)]
        for s in streams:
            s.result(timeout_s=120)
        loop = eng.stats()["loop"]
    finally:
        eng.shutdown()
    assert set(loop["seconds"]) == {"control", "admit", "pack", "dispatch",
                                    "commit", "emit", "wait", "idle"}
    assert loop["iterations"] > 20
    assert sum(loop["seconds"].values()) == pytest.approx(
        loop["wall_s"], rel=0.05)
    assert all(v >= 0 for v in loop["seconds"].values())
    assert loop["seconds"]["pack"] > 0 and loop["seconds"]["emit"] > 0
    if slow:
        # a step slower than the loop: the pipeline fills and the loop
        # waits for the fetch thread with rows live
        assert loop["seconds"]["wait"] > 0.1
    # the loop thread's own CPU beside its wall: it cannot have used
    # more than passed, and it did not wait on the CPU's clock
    assert 0 < loop["cpu_s"] <= loop["wall_s"]
    assert loop["cpu_s"] <= (loop["wall_s"] - loop["seconds"]["wait"]
                             - loop["seconds"]["idle"]) * 1.05 + 0.05
    longest = loop["longest"]
    assert longest["wall_ms"] > 0 and longest["phase"] in loop["seconds"]
    assert loop["step_interval_median_ms"] > 0
    assert loop["step_interval_max_ms"] >= loop["step_interval_median_ms"]


def test_slowed_phase_yields_one_named_loop_stall(params, monkeypatch):
    flight_recorder.clear()
    eng = _engine(params)
    try:
        eng.generate([1, 2, 3], max_new_tokens=2)
        stream = eng.submit(list(range(1, 30)), max_new_tokens=60,
                            temperature=0.0)
        deadline = time.monotonic() + 60
        while (eng.stats()["loop"]["step_interval_median_ms"] is None
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert eng.stats()["loop"]["step_interval_median_ms"] is not None
        admit, slowed = eng._admit, []

        def slow_admit():
            if not slowed:
                slowed.append(True)
                time.sleep(0.4)
            return admit()

        monkeypatch.setattr(eng, "_admit", slow_admit)
        stream.result(timeout_s=120)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert slowed
    stalls = [e for e in flight_recorder.snapshot()["driver"]
              if e["kind"] == "loop_stall"]
    # exactly one event names the slowed phase (a starved test machine
    # may add a stall of its own under another phase's name)
    named = [s for s in stalls if s["phase"] == "admit"]
    assert len(named) == 1, stalls
    assert named[0]["wall_ms"] >= 400
    assert 1 <= named[0]["step_seq"] <= stats["steps"]
    # the trigger is rate-limited: one bundle for the first stall
    triggers = [e for e in flight_recorder.snapshot()["driver"]
                if e["kind"] == "trigger" and e["reason"] == "loop_stall"]
    assert len(triggers) == 1
    assert triggers[0]["detail"] == stalls[0]["phase"]
    # the longest iteration is this one, or the first dispatch's compile
    assert stats["loop"]["longest"]["phase"] in ("admit", "dispatch")
    assert stats["loop"]["longest"]["wall_ms"] >= 400
    assert stats["loop"]["seconds"]["admit"] >= 0.4
    flight_recorder.clear()


# -- the two waits, the loop's CPU, the replica's way in and out ------------

def _engine_is_empty(eng):
    return (not eng._slot_req and eng._waiting.empty()
            and not eng._backlog and not eng._prefilling
            and eng._unprocessed == 0)


def test_wait_holds_steps_in_flight_and_idle_an_empty_engine(params,
                                                             slow_step):
    """``llm.wait`` opens only with steps in flight, ``llm.idle`` only
    with no request anywhere in the engine: the state is read on the
    loop's own thread, where each phase opens."""
    slow_step()
    eng = _engine(params)
    opened = []
    phase = eng._clock.phase

    def watching(name, attributes=None):
        if name in ("wait", "idle"):
            opened.append((name, dict(attributes or {}), eng._unprocessed,
                           _engine_is_empty(eng)))
        return phase(name, attributes)

    eng._clock.phase = watching
    try:
        eng.generate([1, 2, 3], max_new_tokens=2)
        streams = [eng.submit(list(range(1, n)), max_new_tokens=12,
                              temperature=0.0) for n in (30, 6, 17)]
        for s in streams:
            s.result(timeout_s=120)
        time.sleep(0.15)    # the engine is empty: the loop idles
    finally:
        eng.shutdown()
    waits = [o for o in opened if o[0] == "wait"]
    idles = [o for o in opened if o[0] == "idle"]
    assert len(waits) > 5 and len(idles) >= 2
    for _name, attributes, in_flight, empty in waits:
        assert attributes == {"in_flight": in_flight}
        assert 1 <= in_flight <= eng._PIPELINE_DEPTH and not empty
    # the slow step lets the loop fill the pipeline before it waits
    assert max(w[2] for w in waits) == eng._PIPELINE_DEPTH
    for _name, attributes, in_flight, empty in idles:
        assert attributes == {} and in_flight == 0 and empty


def test_loop_span_carries_cpu_and_wait_the_depth(params, tmp_path,
                                                  slow_step):
    slow_step(0.005)
    eng = _engine(params)
    try:
        eng.generate([1, 2, 3], max_new_tokens=2)
        with _capture(tmp_path) as events:
            eng.generate(list(range(1, 20)), max_new_tokens=12)
            time.sleep(0.2)
    finally:
        eng.shutdown()
    ev = events()
    loops = _named(ev, "llm.loop")
    assert len(loops) > 10
    for lp in loops:
        # the thread's CPU over the iteration: never more than its wall
        # (two clocks: a few microseconds of slack)
        wall_us = (lp.end - lp.start) / 1e3
        assert 0 <= lp.stats["cpu_us"] <= wall_us * 1.05 + 50
    stepping = [lp for lp in loops if "seq" in lp.stats]
    assert stepping and all(lp.stats["cpu_us"] > 0 for lp in stepping)
    # waiting is not working: an iteration that only waited 20 ms for
    # the fetch thread used next to none of it
    waited = [lp for lp in loops if lp.end - lp.start > 15_000_000
              and "seq" not in lp.stats]
    assert waited and all(lp.stats["cpu_us"] < 10_000 for lp in waited)
    # the depth at a dispatch is dispatches less emits in the capture:
    # the span carries its step and no more
    dispatches = _named(ev, "llm.dispatch")
    assert dispatches and all(set(d.stats) == {"seq"} for d in dispatches)
    waits = _named(ev, "llm.wait")
    assert waits and all(1 <= w.stats["in_flight"] <= eng._PIPELINE_DEPTH
                         for w in waits)
    # the engine went empty at the end: that wait is the other name
    assert _named(ev, "llm.idle")


@pytest.fixture()
def replica(params, monkeypatch, slow_step):
    """A replica actor in this process over the real ``LLMServer.stream``
    and a tiny engine; the engine's own record of which step emitted
    each token, by request, is kept beside it."""
    slow_step(0.003)
    eng = _engine(params)
    eng.generate([1, 2, 3], max_new_tokens=2)
    server = LLMServer.__new__(LLMServer)
    server.engine, server._disagg = eng, None
    emitted = collections.defaultdict(list)
    emit = eng._emit

    def recording(req, slot, tok, burst=1):
        emitted[req.request_id].append((tok, eng._emit_seq))
        return emit(req, slot, tok, burst)

    monkeypatch.setattr(eng, "_emit", recording)
    monkeypatch.setattr(ReplicaActor, "_install_sigterm_drain",
                        lambda self: None)
    # (a controller's replica ids hold "#", which ends a span's stats in
    # a capture: there ``request_id`` is lost and containment is the join)
    actor = ReplicaActor("app", "llm", "llm-0", server, (), {}, None)
    try:
        yield actor, eng, emitted
    finally:
        eng.shutdown()


def _stream_through(actor, prompts, n_new=10):
    """One thread a request, as the worker runs them; returns the
    tokens each got, by request id."""
    got = {}

    def one(i, prompt):
        rid = f"streamed-{i}"
        got[rid] = list(actor.handle_request_streaming(
            "stream", ({"tokens": prompt, "max_new_tokens": n_new},), {},
            {"request_id": rid}))

    threads = [threading.Thread(target=one, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    return got


def test_stream_items_name_the_step_that_emitted_them(replica, tmp_path):
    actor, eng, emitted = replica
    prompts = [list(range(1, 28)), [5, 6, 7], list(range(40, 59))]
    with _capture(tmp_path) as events:
        got = _stream_through(actor, prompts)
        time.sleep(0.2)
    ev = events()
    handled = {r.stats["request_id"]: r for r in _named(ev, "serve.replica")}
    assert sorted(handled) == sorted(got)
    items = _named(ev, "serve.stream_item")
    submits = _named(ev, "llm.submit")
    emits = _named(ev, "llm.emit")
    assert len(items) == sum(len(t) for t in got.values())
    for rid, tokens in got.items():
        whole = handled[rid]
        mine = [it for it in items if _inside(it, whole)]
        # every item of the stream, in order, inside its request's span
        assert len(mine) == len(tokens) == 10
        assert [tok for tok, _seq in emitted[rid]] == tokens
        seqs = [it.stats["seq"] for it in mine]
        assert seqs == sorted(seqs) and seqs[0] >= 1
        # and each names the step the engine emitted that token from
        assert seqs == [seq for _tok, seq in emitted[rid]]
        for it in mine:
            # which the loop began to emit before the item left
            emit = [e for e in emits if it.stats["seq"] in _seqs(e)]
            assert len(emit) == 1 and emit[0].start <= it.start
        # the request entered the engine once, on this thread, before
        # its first item
        (submit,) = [s for s in submits if _inside(s, whole)]
        assert submit.end <= mine[0].start
    # an item costs its thread something, and no item holds another
    assert all(it.end > it.start for it in items)


def test_stream_of_another_target_opens_no_item_span(monkeypatch, tmp_path):
    """The span is the engine stream's own: a replica over any other
    generator opens none, and nothing is left on the thread for it."""
    class Plain:
        def stream(self, n):
            yield from range(n)

    monkeypatch.setattr(ReplicaActor, "_install_sigterm_drain",
                        lambda self: None)
    actor = ReplicaActor("app", "plain", "plain-0", Plain(), (), {}, None)
    with _capture(tmp_path) as events:
        assert list(actor.handle_request_streaming(
            "stream", (4,), {}, {"request_id": "plain-0"})) == [0, 1, 2, 3]
    ev = events()
    assert len(_named(ev, "serve.replica")) == 1
    assert _named(ev, "serve.stream_item") == []


def test_items_keep_their_step_after_a_result_that_timed_out(monkeypatch):
    """``result()`` takes tokens off the same queue: what iteration
    yields afterwards still names its own step."""
    req = llm_engine.Request([1], 4, 0.0, queue.Queue(), 0)
    stream = llm_engine.CompletionStream(req)
    seen = []

    class watching(tracing.span):
        def __init__(self, name, ctx=None, attributes=None, *, record=True):
            seen.append((name, dict(attributes or {}), record))
            super().__init__(name, ctx, attributes, record=record)

    monkeypatch.setattr(llm_engine.tracing, "span", watching)
    for tok, seq in ((7, 3), (8, 3)):
        req.tokens.append(tok)
        req.token_seqs.append(seq)
        req.stream.put(tok)
    with pytest.raises(TimeoutError):
        stream.result(timeout_s=0.01)
    for tok, seq in ((9, 4), (10, 6)):
        req.tokens.append(tok)
        req.token_seqs.append(seq)
        req.stream.put(tok)
    req.stream.put(llm_engine._DONE)
    assert list(stream) == [9, 10]
    assert seen == [("serve.stream_item", {"seq": 4}, False),
                    ("serve.stream_item", {"seq": 6}, False)]


@pytest.mark.parametrize("enabled", [False, True],
                         ids=["tracing_off", "tracing_on"])
def test_new_spans_keep_no_record(replica, enabled):
    """With no capture the new spans are a flag test.  Off, a streamed
    request leaves the span buffer empty; on, the hot-loop spans
    (``record=False``) still stay out of it."""
    actor, _eng, _emitted = replica
    tracing.clear()
    if enabled:
        tracing.enable_tracing()
    try:
        got = _stream_through(actor, [list(range(1, 20)), [3, 4]])
        time.sleep(0.1)
        names = {s["name"] for s in tracing.finished_spans()}
    finally:
        tracing.disable_tracing()
        tracing.clear()
    assert all(len(t) == 10 for t in got.values())
    if not enabled:
        assert names == set()
    else:
        assert "serve.replica" in names
        assert not names & {"serve.stream_item", "llm.submit", "llm.wait",
                            "llm.idle", "llm.loop", "llm.dispatch"}
