"""Runner for configurations served through ``serve.run(LLMServer)``.

The process that runs the command is the client: it starts the
deployment, sends the traffic through a streaming ``DeploymentHandle``
and never touches JAX.  The replica is the only owner of the chip; it
makes the weights on the device from the seed, checks itself against
the plain reference before the engine takes the memory, and takes the
profiler trace when asked (only the chip's owner can).
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Any, Dict, Optional

from benchmarks.harness import loadgen
from benchmarks.harness.run_record import Run
from benchmarks.runners.common import (
    CompileCounter,
    TraceWindow,
    memory_peak_bytes,
    model_config,
)

PAGE_DEFAULT = 64
STEP_TOKENS_FAMILY = "raytpu_serve_step_tokens_total"
# Logits of the engine's own step programs (int8 weights, bf16
# activations) against the float32 reference on the same int8 values and
# scales, as a share of the reference's largest logit, on two layers at
# the configuration's widths.  Two variants of the program are checked:
#
# "served": the deployment as configured, int8 KV pages with one scale
# a page.  The prefill row reads none of its own pages: 0.68e-2 to
# 0.94e-2.  The four decode rows read back through the paged cache:
# 1.7e-2 to 2.5e-2, the int8 pages' own error, so on those rows this
# variant catches a dropped term, a wrong position, a page read from the
# wrong slot, int4 in place of int8, and nothing finer.
#
# "bf16_kv": the same weights, kernels and step program with bf16 KV
# pages (``kv_int8`` off), so that the pages' error is out of the way
# and what is left is the program's stated arithmetic: bf16 activations
# and int8 weights dequantized to bf16, float32 accumulation, softmax and
# norms.  Decode rows: 0.77e-2 to 0.98e-2; the prefill row is the served
# variant's, to the last digit.
#
# (My chip runs, PR 23, eight seeds, two layers at Mistral-7B's widths.)
# The bounds are 1.5 to 1.6 times the largest value measured, so a
# matmul, accumulator or softmax moved to a lower precision than stated
# shows where it adds half again to bf16's own rounding of the
# activations.  That rounding is the floor of any comparison with a
# float32 reference: what is small beside it stays unseen (PERF.md
# section 7).
TOLERANCES = {
    "served": {"prefill": 1.5e-2, "decode": 4e-2},
    "bf16_kv": {"prefill": 1.5e-2, "decode": 1.5e-2},
}


def _load_weights(cfg, seed: int):
    """int8 weight-only, fused for decode, made on the device."""
    import jax

    from ray_tpu.models import quant

    return quant.fuse_for_decode(
        quant.init_quantized_llama(jax.random.key(seed % (2**31 - 1)), cfg),
        cfg)


def _program_logits(cfg2, params, toks, n_prompt: int, page: int):
    """Prefill ``n_prompt`` tokens through the engine's ragged step,
    then the rest one token at a time through the paged cache: the
    logits of the last prompt token and of every later one."""
    import jax
    import numpy as np

    from ray_tpu.models import quant
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

    adapter = quant.llama_paged_adapter_quant(cfg2)
    maxp = -(-len(toks) // page)
    cache = adapter.init_cache(maxp, page)
    table = np.arange(maxp, dtype=np.int32)[None]
    step = jax.jit(adapter.ragged_step)
    budget = -(-n_prompt // 8) * 8
    rows = [{"slot": 0, "start": 0, "tokens": toks[:n_prompt]}]
    got = []
    for i in range(n_prompt, len(toks) + 1):
        (ht, _m, _s, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(rows, budget, 1)
        logits, cache = step(params, ht, pos, r_slot, r_start, r_len,
                             r_off, table, cache)
        got.append(np.asarray(logits[0], np.float32))
        if i < len(toks):
            rows = [{"slot": 0, "start": i, "tokens": [toks[i]]}]
    return got


def logits_check(cfg, config: Dict[str, Any], seed: int, *,
                 n_layers: int = 2, n_prompt: int = 83, n_decode: int = 4
                 ) -> Dict[str, Any]:
    """A model of ``n_layers`` layers at the configuration's widths and
    quantisation against the plain reference's full forward pass, as
    served and with bf16 KV pages (see TOLERANCES).  Logits, not
    tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference

    page = config["engine"].get("page_size", PAGE_DEFAULT)
    cfg2 = dataclasses.replace(cfg, n_layers=n_layers)
    params = _load_weights(cfg2, seed)
    rng = np.random.default_rng(seed % (2**32))
    toks = rng.integers(1, cfg.vocab_size, n_prompt + n_decode).tolist()
    hf = dict(config, num_hidden_layers=n_layers)
    with jax.default_matmul_precision("highest"):
        ref_params = reference.from_program_tree(
            reference.dequantize(params), hf)
        want = np.asarray(jax.jit(
            lambda p, t: reference.forward(p, t, hf))(
                ref_params, jnp.asarray(toks, jnp.int32)))
    want = want[n_prompt - 1:]
    scale = float(np.max(np.abs(want)))
    variants = {"served": cfg2}
    if cfg2.kv_int8:
        variants["bf16_kv"] = dataclasses.replace(cfg2, kv_int8=False)
    out: Dict[str, Any] = {"layers": n_layers, "ok": True}
    for name, variant in variants.items():
        got = _program_logits(variant, params, toks, n_prompt, page)
        errs = [float(np.max(np.abs(g - w))) / scale
                for g, w in zip(got, want)]
        tol = TOLERANCES[name]
        ok = bool(all(np.isfinite(g).all() for g in got)
                  and errs[0] <= tol["prefill"]
                  and max(errs[1:]) <= tol["decode"])
        out[name] = {"rel_err_prefill": errs[0],
                     "rel_err_decode": max(errs[1:]),
                     "tol_prefill": tol["prefill"],
                     "tol_decode": tol["decode"], "ok": ok}
        out["ok"] = out["ok"] and ok
    return out


def server_class():
    """Built in a function so that importing this module imports no
    JAX in the client."""
    from ray_tpu.serve.llm_engine import EngineConfig, LLMServer

    class BenchLLMServer(LLMServer):
        def __init__(self, spec: Dict[str, Any]):
            from ray_tpu.models import quant

            self._compiled = CompileCounter()
            config, seed = spec["config"], spec["seed"]
            cfg = model_config(config)
            self._check = logits_check(cfg, config, seed)
            engine_cfg = EngineConfig(**config["engine"])
            super().__init__(cfg, engine_cfg,
                             lambda: _load_weights(cfg, seed),
                             adapter_factory=quant.llama_paged_adapter_quant)
            self._tracer = None
            self._rehearse = bool(spec.get("rehearse"))

        def device_report(self) -> Dict[str, Any]:
            import jax

            devices = jax.devices()
            return {"platform": devices[0].platform,
                    "kind": devices[0].device_kind, "count": len(devices),
                    "memory_peak_bytes": memory_peak_bytes(devices),
                    "check": self._check}

        def counters(self) -> Dict[str, Any]:
            """Engine counters as they stand (cumulative), through the
            program's public readers: ``LLMEngine.stats()`` and the
            metrics registry's sample snapshot."""
            from ray_tpu.util import metrics

            tokens: Dict[str, float] = {}
            for family, _type, _help, samples in metrics.snapshot_samples():
                if family == STEP_TOKENS_FAMILY:
                    for sample in samples:
                        phase = dict(sample[1]).get("phase", "")
                        tokens[phase] = tokens.get(phase, 0.0) + sample[2]
            stats = self.engine.stats()
            return {"steps": stats["steps"], "step_tokens": tokens,
                    "prefix": stats.get("prefix"),
                    "compiles": self._compiled.count,
                    "compile_s": self._compiled.seconds}

        def ring_rows(self) -> Dict[str, Dict[str, Any]]:
            """The engine's request ring by request id, through the
            program's public snapshot of this process's rings."""
            from ray_tpu.serve import request_events

            engine = self.engine.stats()["engine"]
            return {r["request_id"]: {k: r[k] for k in (
                        "state_ts", "prompt_tokens", "generated_tokens",
                        "prefix_hit")}
                    for r in request_events.snapshot_rows(local_only=True)
                    if r["engine"] == engine}

        def trace_start(self, trace_dir: str) -> bool:
            self._tracer = TraceWindow(trace_dir,
                                       allow_empty=self._rehearse)
            self._tracer.start()
            return True

        def trace_stop(self) -> bool:
            self._tracer.stop()
            return True

        def trace_reduce(self) -> Optional[Dict[str, Any]]:
            return self._tracer.reduce()

    return BenchLLMServer


def _payload(p: loadgen.Planned) -> Dict[str, Any]:
    return {"tokens": p.prompt, "max_new_tokens": p.max_new_tokens,
            "temperature": 0.0}


def run(ctx) -> Run:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.utils import accelerator

    config, traffic = ctx.config, ctx.traffic
    vocab = config["vocab_size"]
    ray_tpu.init(ignore_reinit_error=True)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not ctx.rehearse and chips < ctx.chips:
            raise SystemExit(
                f"benchmark: cell {ctx.cell} needs {ctx.chips} TPU chip(s) "
                f"and this host shows {chips}; no result")
        options = {} if ctx.rehearse else {"num_tpus": ctx.chips}
        app = serve.deployment(
            ray_actor_options=options, max_ongoing_requests=512,
        )(server_class()).bind({"config": config, "seed": ctx.seed,
                                "rehearse": ctx.rehearse})
        handle = serve.run(app, name="bench", route_prefix=None,
                           timeout_s=1100.0)
        report = handle.device_report.remote().result(timeout_s=120)
        if not ctx.rehearse and report["platform"] != "tpu":
            raise SystemExit(
                f"benchmark: the replica computes on "
                f"{report['platform']!r}, not a TPU; no result")
        stream = handle.options(stream=True)

        def send(p: loadgen.Planned):
            return stream.remote(_payload(p))

        _warm_up(traffic, send, vocab)
        if ctx.sweep:
            _sweep(ctx, traffic, send, vocab)
            return None

        marks: Dict[str, Any] = {}

        def on_window_start():
            marks["t_start"] = time.perf_counter()
            marks["c0"] = handle.counters.remote().result(timeout_s=60)

        tracer = None
        if ctx.trace:
            tracer = _Tracer(handle, ctx, traffic)
        if traffic["loop"] == "open":
            plan = loadgen.open_loop_plan(traffic, ctx.seconds, ctx.seed,
                                          vocab)
            if tracer:
                tracer.arm()
            res = loadgen.run_open_loop(
                plan, send, vocab, on_window_start=on_window_start,
                max_inflight=traffic.get("max_inflight", 128))
        elif traffic["loop"] == "closed":
            streams = [loadgen.closed_loop_stream(traffic, ctx.seed, vocab, c)
                       for c in range(int(traffic["clients"]))]
            if tracer:
                tracer.arm()
            res = loadgen.run_closed_loop(
                streams, send, vocab, ramp_s=float(traffic.get("ramp_s", 0)),
                seconds=ctx.seconds, on_window_start=on_window_start)
        else:
            raise ValueError(f"unknown loop kind {traffic['loop']!r}")
        trace = tracer.result() if tracer else None
        c1 = handle.counters.remote().result(timeout_s=60)
        ring = handle.ring_rows.remote().result(timeout_s=60)
        report = handle.device_report.remote().result(timeout_s=60)
        if accelerator.backend_initialised() and not ctx.rehearse:
            raise SystemExit("benchmark: the client initialised a JAX "
                             "backend; it would hold the chip")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    records = res["records"]
    meas = [r for r in records if r["measured"]]
    c0 = marks.get("c0", {})
    # counters at the window's end: taken after the drain, so the count
    # of compilations covers the whole window and the drain
    compiles = c1["compiles"] - c0.get("compiles", c1["compiles"])
    check = report["check"]
    failed = sum(1 for r in meas if not r["ok"])
    return Run(
        cell=ctx.cell, config=config, traffic=traffic, chips=ctx.chips,
        seconds=ctx.seconds, setup_s=marks["t_start"] - ctx.t_process_start,
        device={"platform": report["platform"], "kind": report["kind"],
                "count": report["count"],
                "memory_peak_bytes": report["memory_peak_bytes"]},
        attempted=len(meas), failed=failed,
        correct=bool(failed == 0 and len(meas) > 0 and compiles == 0
                     and check["ok"]),
        notes={"compiles_in_window": compiles, "reference_check": check,
               "errors": sorted({r["error"] for r in meas
                                 if r["error"]})[:5]},
        requests=records, ring=ring, counters0=c0, counters1=c1,
        trace=trace)


def _warm_up(traffic, send, vocab) -> None:
    """Warm every program the traffic uses before anything is timed: a
    prompt of several chunks beside live decode rows, then a lone decode
    tail."""
    warm = [loadgen.Planned(idx=-1 - i, prompt=[1 + i] * n, max_new_tokens=m)
            for i, (n, m) in enumerate(traffic.get(
                "warmup", [[300, 4], [40, 12]]))]
    recs = [loadgen.new_record(p) for p in warm]
    threads = [threading.Thread(target=loadgen.drive_one,
                                args=(p, r, send, time.perf_counter(), vocab))
               for p, r in zip(warm, recs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    if not all(r["ok"] for r in recs):
        raise SystemExit(f"benchmark: warm-up failed: "
                         f"{[r['error'] for r in recs]}")


def _sweep(ctx, traffic, send, vocab) -> None:
    """The builder's knee sweep: the same mix at each rate in turn, in
    one process.  Prints one JSON line per rate."""
    from benchmarks.harness import request_metrics as rq
    from benchmarks.harness import stats

    for rate in ctx.sweep:
        mix = dict(traffic, rate_per_s=rate)
        plan = loadgen.open_loop_plan(mix, ctx.seconds, ctx.seed, vocab)
        res = loadgen.run_open_loop(plan, send, vocab,
                                    max_inflight=mix.get("max_inflight", 128))
        meas = [r for r in res["records"] if r["measured"]]
        done = [r["last"] for r in meas if r["ok"]]
        ttft = rq.ttfts_ms(meas)
        print(json.dumps({
            "rate_per_s": rate, "offered": len(meas), "ok": len(done),
            "completion": stats.completion_share(done, rate),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "tpot_mean_ms": stats.mean(rq.tpots_ms(meas)),
            "last_done_s": max(done, default=None)}), flush=True)
        if stats.completion_share(done, rate) < 0.9:
            break   # far past the knee: later rates only drain longer


class _Tracer:
    """Trace ``trace_s`` seconds from a third of the way into the
    window, in the replica, from a thread of the client."""

    def __init__(self, handle, ctx, traffic):
        self.handle, self.ctx = handle, ctx
        self.trace_s = float(traffic.get("trace_s", 4.0))
        self.delay = float(traffic.get("ramp_s", 0)) + ctx.seconds / 3.0
        self.error = None
        self.thread = None

    def arm(self):
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        try:
            time.sleep(self.delay)
            self.handle.trace_start.remote(self.ctx.trace_dir).result(
                timeout_s=120)
            time.sleep(self.trace_s)
            self.handle.trace_stop.remote().result(timeout_s=600)
        except Exception as e:
            self.error = e

    def result(self):
        """After the drain: the replica reduces what it traced."""
        self.thread.join(900)
        if self.error is not None:
            raise self.error
        return self.handle.trace_reduce.remote().result(timeout_s=600)
