"""Headline benchmark: Llama train-step + LLM-serving throughput on chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "extra": {...}}

``value`` is tokens/sec/chip of the full jitted train step (fwd+bwd+
AdamW) on a ~319M-param Llama sized for a single v5e chip, with
TPU-first choices: bf16 compute, head_dim 128 (8 heads — the MXU's
contraction dim wants 128; same param count and 6N flops as the
16-head/64-dim variant, +40% throughput), Pallas flash attention,
dots-saveable remat, bf16 Adam first moment, donated step buffers.

``vs_baseline`` compares against a deliberately un-TPU-optimized
variant — float32 compute, full remat — i.e. what a straight port that
ignores MXU dtype and HBM management would get.  (The reference
publishes no absolute tokens/sec itself; see BASELINE.md.)

``extra`` carries the other north stars (BASELINE.json):
  - llama_1b: a 1.14B-param single-chip config (bf16 master, full
    remat, chunked cross-entropy — never materializes [B,S,V] logits)
  - serving: continuous-batching LLM engine req/s + p50/p95 TTFT on
    the same chip (prompt 128, gen 32, 8 slots).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig, default_optimizer

BATCH = 8
SEQ = 2048

BENCH_CFG = llama.LlamaConfig(
    vocab_size=32_768,
    dim=1024,
    n_layers=16,
    n_heads=8,       # head_dim 128: full MXU contraction (v5e tile 128)
    n_kv_heads=4,
    mlp_dim=4096,
    max_seq_len=SEQ,
)

# 1B-class config for the single-chip headroom point: bf16 master params
# (f32 states would need 14 GB before activations on a 16 GB chip),
# full per-layer remat, sequence-chunked CE.
BENCH_1B_CFG = llama.LlamaConfig(
    vocab_size=32_768,
    dim=2048,
    n_layers=16,
    n_heads=16,
    n_kv_heads=8,
    mlp_dim=8192,
    max_seq_len=SEQ,
    param_dtype=jnp.bfloat16,
    remat_policy="full",
    loss_chunk=512,
)

# Measured multi-billion point (VERDICT r4 item 6: the largest config
# that truly fits 16 GB, not an extrapolation): ~2.24B params with
# bf16 master weights + block-wise INT8 Adam states (train/optim8.py —
# 2 bytes/param of optimizer state), full remat, chunked CE.
BENCH_2B_CFG = llama.LlamaConfig(
    vocab_size=32_768,
    dim=2560,
    n_layers=22,
    n_heads=20,
    n_kv_heads=4,
    mlp_dim=10240,
    max_seq_len=SEQ,
    param_dtype=jnp.bfloat16,
    remat_policy="full",
    loss_chunk=512,
)

# The 8B serving cell: published Llama-3-8B widths at full depth.
# int8 KV pages (per-page scales): the bf16 pool at 24 slots was 3.2 GB;
# int8 at 48 slots × 4 pages is 0.4 GB — double the slots AND less HBM,
# with live-page decode reads halved.  fused_decode: the per-layer
# megakernel (ops/fused_decode.py) collapses each layer's decode op
# graph into one Pallas program — the per-op dispatch latency it
# removes is what held 8B decode at 56% of the weight-read roofline in
# BENCH_r05.
BENCH_8B_CFG = llama.LlamaConfig(
    vocab_size=128_256, dim=4096, n_layers=32, n_heads=32,
    n_kv_heads=8, mlp_dim=14336, max_seq_len=256, kv_int8=True,
    fused_decode=True,
)

# Mixed-length prompt ladders: the serving knee measured on REALISTIC
# traffic instead of the single prompt_len=128 point — with ragged
# batching on, prefill chunks and decode rows share one token-budgeted
# device step, so TTFT at the knee should hold as prompts diversify.
# Weights are per-REQUEST sampling probabilities.
PROMPT_MIXES = {
    # interactive chat: short prompts, tight TTFT expectations
    "short_chat": {"lens": (32, 64, 128), "weights": (0.5, 0.3, 0.2)},
    # retrieval-augmented: mostly long stuffed contexts
    "long_rag": {"lens": (512, 1024, 1536), "weights": (0.3, 0.5, 0.2)},
    # bimodal: chat traffic with occasional huge pastes — the mix that
    # head-of-line-blocks a two-program (prefill|decode) engine
    "bursty": {"lens": (32, 64, 1536), "weights": (0.55, 0.3, 0.15)},
    # Zipfian multi-tenant conversations: popular tenants share a
    # page-aligned system-prompt + history prefix (Zipf(alpha) over
    # tenants picks whose), suffixes are fresh per request, and a
    # private_frac slice belongs to one-off tenants (always-cold
    # baseline).  Serves with EngineConfig.prefix_cache — the mix the
    # radix-tree prefix cache exists for; the record grows a "prefix"
    # block (hit ratio, TTFT-by-hit-depth vs cold).
    "zipf_chat": {"lens": (192, 256, 320), "weights": (0.3, 0.4, 0.3),
                  "zipf": {"tenants": 8, "alpha": 1.1,
                           "shared_frac": 0.6, "private_frac": 0.25}},
}

def _make_trainer(cfg, devices, optimizer=None, trainer_config=None):
    return JaxTrainer(
        init_params=lambda r: llama.init_params(r, cfg),
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        params_axes=llama.logical_axes(cfg),
        batch_axes={"tokens": ("batch", None)},
        optimizer=optimizer or default_optimizer(
            1e-4, warmup_steps=10, mu_dtype=jnp.bfloat16),
        scaling_config=ScalingConfig(
            mesh_spec=MeshSpec(dp=1, fsdp=len(devices)), devices=devices
        ),
        run_config=RunConfig(report_every=1_000_000),
        trainer_config=trainer_config,
    )


def _measure(cfg, devices, *, steps: int, batch: int = None,
             warmup: int = 2, optimizer=None, trainer_config=None,
             extras: dict = None) -> float:
    """Tokens/sec of the jitted train step (post-warmup).

    ``extras`` (when a dict) receives the live trainer, so callers can
    read the trained state's actual shardings afterwards (the 8B ZeRO
    rung reports opt-state bytes/param straight from the arrays)."""
    batch = batch or BATCH
    trainer = _make_trainer(cfg, devices, optimizer, trainer_config)
    if extras is not None:
        extras["trainer"] = trainer
    rng = np.random.default_rng(0)

    def batches():
        while True:
            yield {
                "tokens": rng.integers(
                    0, cfg.vocab_size, (batch, SEQ), dtype=np.int64
                ).astype(np.int32)
            }

    it = batches()
    with trainer.mesh:
        state = trainer.state
        step = trainer._step_fn
        # Pre-stage batches on device: host→device transfers ride a
        # potentially slow transport and real input pipelines overlap them
        # (ray_tpu.data prefetch), so they don't belong in the step timing.
        staged = [trainer.shard_batch(next(it)) for _ in range(min(steps, 4))]
        for _ in range(warmup):
            state, metrics = step(state, staged[0])
        # Fence with a host transfer of a value that depends on the whole
        # step: the loss cannot reach the host before the step has run.
        float(jax.device_get(metrics["loss"]))
        t0 = time.perf_counter()
        for i in range(steps):
            state, metrics = step(state, staged[i % len(staged)])
        float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
    return batch * SEQ * steps / dt


def _measure_serving(cfg, *, n_requests: int = 128, prompt_len: int = 128,
                     gen: int = 32, slots: int = 64,
                     arrival_rate: float = 40.0,
                     params=None, adapter_factory=None,
                     prompt_mix: dict = None, mix_name: str = None,
                     ragged: bool = False,
                     prefill_chunk: int = 0,
                     spec: bool = False) -> dict:
    """Continuous-batching engine (paged KV cache), measured two ways
    (harness shape: the reference's serve microbenchmark,
    python/ray/serve/benchmarks/microbenchmark.py):

    * OPEN-LOOP: requests arrive at ``arrival_rate`` req/s (the
      serving-latency methodology — TTFT at an offered load, not after
      a burst drains a queue);
    * BURST: all requests at once — the max-throughput number.

    ``prompt_mix`` draws per-request prompt lengths from a weighted
    distribution (PROMPT_MIXES) instead of the fixed ``prompt_len``;
    ``ragged`` serves through the unified token-budget step
    (EngineConfig.ragged_batching) with ``prefill_chunk``-token prompt
    slices, so long prompts never head-of-line-block running decodes.
    """
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    if params is None:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    make_adapter = adapter_factory or llama_paged_adapter
    rng = np.random.default_rng(1)
    if prompt_mix is not None:
        lens = rng.choice(np.asarray(prompt_mix["lens"]), n_requests,
                          p=np.asarray(prompt_mix["weights"], np.float64)
                          / np.sum(prompt_mix["weights"]))
    else:
        lens = np.full(n_requests, prompt_len)
    max_seq = min(cfg.max_seq_len,
                  max(512, int(64 * np.ceil((lens.max() + gen + 1) / 64))))
    zipf = (prompt_mix or {}).get("zipf")
    leg_t0 = time.time()  # waterfall-attribution window for this leg
    eng = LLMEngine(
        params, make_adapter(cfg),
        EngineConfig(max_slots=slots, max_seq_len=max_seq,
                     decode_chunk=8,
                     max_new_tokens_default=gen, page_size=64,
                     ragged_batching=ragged,
                     prefill_chunk=prefill_chunk,
                     prefix_cache=bool(zipf) and ragged,
                     # Speculative legs self-draft (draft == target):
                     # acceptance is 1.0 by construction, so the leg
                     # isolates the MECHANICAL overhead/benefit of
                     # k-token verify rows, not draft-model quality.
                     spec_decode=spec and ragged),
    )
    if zipf is not None:
        # Zipfian multi-tenant prompts: rank-k tenant drawn with
        # p(k) ∝ 1/k^alpha shares a fixed page-aligned prefix;
        # private_frac of requests belong to one-off tenants (the
        # honest cold-prefill baseline inside the same run).  Suffixes
        # are always fresh, and make_prompts() is re-invoked per
        # ladder rung so a rung never replays the previous rung's
        # exact prompts as trivial full-prompt hits.
        tenants = int(zipf.get("tenants", 8))
        alpha = float(zipf.get("alpha", 1.1))
        shared_frac = float(zipf.get("shared_frac", 0.6))
        private_frac = float(zipf.get("private_frac", 0.25))
        pz = np.arange(1, tenants + 1, dtype=np.float64) ** -alpha
        pz /= pz.sum()
        tenant_prefix = [
            rng.integers(0, cfg.vocab_size,
                         int(64 * max(1, round(
                             int(max(prompt_mix["lens"])) * shared_frac
                             / 64)))).tolist()
            for _ in range(tenants)]

        def make_prompts():
            out = []
            for n in lens:
                n = int(n)
                if rng.random() < private_frac:
                    out.append(rng.integers(0, cfg.vocab_size,
                                            n).tolist())
                    continue
                pre = tenant_prefix[int(rng.choice(tenants, p=pz))]
                shared = min(len(pre) // 64 * 64, (n - 1) // 64 * 64)
                out.append(pre[:shared]
                           + rng.integers(0, cfg.vocab_size,
                                          n - shared).tolist())
            return out

        prompts = make_prompts()
    else:
        make_prompts = None
        prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
                   for n in lens]
    # TTFT-by-hit-depth accounting (zipf mixes): (hit_tokens,
    # prompt_tokens, ttft_s) per open-loop request, across all rungs.
    prefix_samples = []
    # Warm every compiled variant the run will hit off the clock:
    # prefill batch sizes k ∈ {1, 2, 4, 8} (open-loop trickle admits
    # small groups; burst admits full ones) and every ladder chunk.
    wi = 0
    for kgroup in (1, 2, 4, slots):
        warm = [eng.submit(prompts[(wi + j) % len(prompts)],
                           max_new_tokens=gen) for j in range(kgroup)]
        wi += kgroup
        for s in warm:
            s.result(timeout_s=600)

    def pct(sorted_vals, q):
        return round(
            sorted_vals[min(len(sorted_vals) - 1,
                            int(q * len(sorted_vals)))] * 1e3, 1)

    def open_loop_point(rate: float, n: int) -> dict:
        if make_prompts is not None:
            prompts[:] = make_prompts()  # fresh suffixes per rung
        t0 = time.perf_counter()
        streams = []
        for i in range(n):
            target = t0 + i / rate
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            streams.append(eng.submit(prompts[i % len(prompts)],
                                      max_new_tokens=gen,
                                      temperature=0.0))
        outs = [s.result(timeout_s=600) for s in streams]
        dt = time.perf_counter() - t0
        if zipf is not None:
            prefix_samples.extend(
                (s._req.prefix_hit, len(s._req.prompt), s._req.ttft_s)
                for s in streams)
        ttfts = sorted(s._req.ttft_s for s in streams
                       if s._req.ttft_s is not None)
        assert all(len(o) == gen for o in outs)
        # Steady-state served rate: the OLS slope of completion
        # timestamps vs completion index over the MIDDLE of the run
        # (first fifth = warmup ramp, last twentieth = the drain
        # burst, both trimmed).  Completions arrive in decode-chunk
        # BURSTS, so an endpoint-ratio estimator wobbles by a burst
        # width (enough to flap the knee); the regression slope over
        # the trimmed window averages the bursts out.  A system
        # keeping up completes at the arrival rate → ~1.0; a
        # saturated one at its ceiling μ → μ/rate.
        done = sorted(s._req.finished_at for s in streams)
        ts = np.asarray(done[max(1, n // 5):-max(1, n // 20)])
        idx = np.arange(len(ts))
        slope = float(np.polyfit(idx, ts, 1)[0]) if len(ts) > 2 else 1.0
        served_ss = 1.0 / max(slope, 1e-9)
        completion = min(1.0, served_ss / rate)
        return {
            "offered_req_s": rate,
            "req_per_s": round(n / dt, 2),
            "completion": round(completion, 3),
            # Token throughput from the OLS served rate, not n*gen/dt:
            # below the knee the run-wide ratio just echoes the PACING
            # rate (requests arrive slower than the engine could serve),
            # understating capacity at every sustainable point.
            "decode_tokens_per_s": round(served_ss * gen, 1),
            "ttft_p50_ms": pct(ttfts, 0.50),
            "ttft_p95_ms": pct(ttfts, 0.95),
        }

    # Arrival-rate LADDER: climb offered load until the system stops
    # completing ≥99% of it; the KNEE is the last sustainable point
    # and the headline TTFT is measured there, not past saturation.
    # A rung whose TTFT p95 blows past 10x its p50 hit a bimodal stall
    # (one-off compile, page thrash, preempted host) rather than a
    # smooth queueing regime: flag it ``stalled`` and retry once — the
    # flagged sample stays in the ladder for the record, the retry's
    # numbers stand.  BENCH_r05's 1.14B rung (p95 203x p50, completion
    # 0.116) is the motivating specimen — and it must NEVER be
    # promoted to "knee" just for being the only rung measured: a
    # ladder with no sustaining rung reports knee: null + saturated.
    ladder = []

    def probe(rate: float) -> dict:
        n = max(32, min(int(rate * 12), 192))
        point = open_loop_point(rate, n)
        if (point["ttft_p50_ms"] > 0
                and point["ttft_p95_ms"] > 10.0 * point["ttft_p50_ms"]):
            point["stalled"] = True
            ladder.append(point)
            point = open_loop_point(rate, n)
            point["retry_of_stalled"] = True
            if (point["ttft_p50_ms"] > 0
                    and point["ttft_p95_ms"]
                    > 10.0 * point["ttft_p50_ms"]):
                point["stalled"] = True  # reproduced: a real regime
        ladder.append(point)
        return point

    rate = arrival_rate / 4.0
    knee = None
    first_fail = None
    for _ in range(6):
        point = probe(rate)
        if point["completion"] >= 0.99:
            knee = point
            rate *= 1.5
        else:
            first_fail = point
            break
    # Refine the bracket between the last sustaining and the first
    # failing rung down to <=1.25x spacing (geometric bisection), so
    # the reported knee is within one fine rung of the true one.
    if knee is not None and first_fail is not None:
        lo = knee["offered_req_s"]
        hi = first_fail["offered_req_s"]
        while hi / lo > 1.25 and len(ladder) < 12:
            mid = (lo * hi) ** 0.5
            point = probe(mid)
            if point["completion"] >= 0.99:
                knee, lo = point, mid
            else:
                hi = mid
    saturated = knee is None  # not even the lowest rung sustained

    # Burst: everything at once — the throughput ceiling.
    t0 = time.perf_counter()
    streams_b = [eng.submit(p, max_new_tokens=gen, temperature=0.0)
                 for p in prompts]
    for s in streams_b:
        s.result(timeout_s=600)
    burst_dt = time.perf_counter() - t0
    eng_stats = eng.stats()
    # Migrated-vs-recomputed prefix cost (zipf mixes): ship this run's
    # hot cached prefixes to a cold engine over the kv_transfer int8
    # wire and time it, against the same run's MEASURED cold-prefill
    # cost (cold requests' TTFT per prompt token).  Needs the warm
    # engine alive, so it runs before shutdown.
    mig_probe = None
    if zipf is not None:
        try:
            mig_probe = _probe_prefix_migration(
                eng, cfg, params, make_adapter, max_seq)
        except Exception as e:
            mig_probe = {"error": repr(e)[:120]}
    eng.shutdown()
    # Headline open-loop numbers are AT THE KNEE (highest offered load
    # still completing ≥99%), so TTFT never conflates service with
    # queueing delay past saturation.  A saturated ladder (no rung
    # sustained) has NO honest headline: those fields go null and the
    # per-rung data lives in "ladder" — knee and saturated are
    # mutually exclusive by construction (scripts/bench_schema.py
    # enforces this on every record).
    head = knee if knee is not None else {
        "offered_req_s": None, "req_per_s": None,
        "decode_tokens_per_s": None, "ttft_p50_ms": None,
        "ttft_p95_ms": None}
    out = {
        "arrival_rate_req_s": head["offered_req_s"],
        "req_per_s": head["req_per_s"],
        "decode_tokens_per_s": head["decode_tokens_per_s"],
        "ttft_p50_ms": head["ttft_p50_ms"],
        "ttft_p95_ms": head["ttft_p95_ms"],
        "ladder": ladder,
        "knee_req_s": None if knee is None else knee["offered_req_s"],
        "saturated": saturated,
        "burst_req_per_s": round(n_requests / burst_dt, 2),
        "burst_decode_tokens_per_s": round(n_requests * gen / burst_dt, 1),
        "prompt_len": int(np.median(lens)),
        "gen": gen,
        "slots": slots,
        "batching": "ragged" if ragged else "interleaved",
        "kv": "int8" if getattr(cfg, "kv_int8", False) else "bf16",
        "decode_kernel": ("fused" if getattr(cfg, "fused_decode", False)
                          else "unfused"),
    }
    # Speculative-decoding stats (absent, not zero, when the engine
    # never completed a verify round — scripts/bench_schema.py
    # enforces the shape).  accepted_tokens_per_step counts the bonus
    # token, so a healthy leg sits above 1.0 accepted tokens per
    # target step.
    sp = eng_stats.get("spec")
    if spec and sp and sp.get("rounds"):
        out["spec"] = {
            "rounds": int(sp["rounds"]),
            "drafted_tokens": int(sp["drafted_tokens"]),
            "accepted_tokens": int(sp["accepted_tokens"]),
            "accept_ratio": (
                round(sp["accepted_tokens"] / sp["drafted_tokens"], 3)
                if sp["drafted_tokens"] else None),
            "accepted_tokens_per_step": round(
                (sp["accepted_tokens"] + sp["rounds"]) / sp["rounds"], 2),
            "cooldowns": int(sp.get("cooldowns", 0)),
            "k": int(sp["k"]),
            "draft": "self",
        }
    # Per-request waterfall aggregate over this leg's requests: mean
    # component seconds + control-plane share (absent, not zero, when
    # nothing was attributed — scripts/bench_schema.py validates).
    try:
        from ray_tpu.serve import latency_attribution

        dispatch_overhead = latency_attribution.aggregate(since=leg_t0)
    except Exception:
        dispatch_overhead = None
    if dispatch_overhead is not None:
        out["dispatch_overhead"] = dispatch_overhead
    if prompt_mix is not None:
        # The sampled distribution travels WITH the knee it produced:
        # a mixed-ladder TTFT is meaningless without knowing how long
        # the prompts actually were.
        out["prompt_mix"] = {
            "name": mix_name,
            "lens": [int(x) for x in prompt_mix["lens"]],
            "weights": [round(float(w), 4) for w in prompt_mix["weights"]],
            "sampled_p50": int(np.percentile(lens, 50)),
            "sampled_p95": int(np.percentile(lens, 95)),
            "sampled_max": int(lens.max()),
        }
        if zipf is not None:
            out["prompt_mix"]["zipf"] = dict(zipf)
    if zipf is not None:
        # Prefix-cache effectiveness over every open-loop request:
        # hit ratio, and TTFT split cold (hit = 0) vs deep-hit
        # (≥ 50% of the prompt served from cache) — the
        # TTFT-by-hit-depth comparison the cache is judged on.
        def _ms(vals, f):
            vals = [v for v in vals if v is not None]
            return None if not vals else round(float(f(vals)) * 1e3, 1)

        cold = [t for h, _p, t in prefix_samples if h == 0]
        deep = [t for h, p, t in prefix_samples
                if p > 0 and h >= 0.5 * p]
        tot_prompt = sum(p for _h, p, _t in prefix_samples)
        eng_prefix = eng_stats.get("prefix", {})
        out["prefix"] = {
            "requests": len(prefix_samples),
            "hit_ratio": (round(sum(1 for h, _p, _t in prefix_samples
                                    if h > 0)
                                / max(1, len(prefix_samples)), 3)),
            "hit_token_ratio": round(
                sum(h for h, _p, _t in prefix_samples)
                / max(1, tot_prompt), 3),
            "cold_requests": len(cold),
            "hit50_requests": len(deep),
            "ttft_mean_cold_ms": _ms(cold, np.mean),
            "ttft_mean_hit50_ms": _ms(deep, np.mean),
            "ttft_p50_cold_ms": _ms(cold, np.median),
            "ttft_p50_hit50_ms": _ms(deep, np.median),
            "cached_pages": int(eng_prefix.get("cached_pages", 0)),
            "evicted_pages": int(eng_prefix.get("evicted_pages", 0)),
        }
        if mig_probe is not None and "error" not in mig_probe:
            # Per-page costs: transfer side measured by the probe,
            # recompute side from the run's own cold requests (64 =
            # the engine's page_size above).  Null only when a side
            # measured nothing — no pages moved / no cold requests.
            cold_tok = sum(p for h, p, t in prefix_samples
                           if h == 0 and t is not None)
            cold_s = sum(t for h, p, t in prefix_samples
                         if h == 0 and t is not None)
            pages = mig_probe["migrated_pages"]
            mig_probe["migrate_s_per_page"] = (
                round(mig_probe["seconds"] / pages, 6) if pages
                else None)
            mig_probe["recompute_s_per_page"] = (
                round(cold_s / cold_tok * 64, 6) if cold_tok else None)
            m_pp = mig_probe["migrate_s_per_page"]
            r_pp = mig_probe["recompute_s_per_page"]
            mig_probe["migrate_vs_recompute"] = (
                round(r_pp / m_pp, 2) if m_pp and r_pp else None)
        if mig_probe is not None:
            out["prefix"]["migration"] = mig_probe
    return out


def _probe_prefix_migration(eng, cfg, params, make_adapter, max_seq):
    """Ship the warm engine's hot cached prefixes to a COLD engine over
    the kv_transfer int8 page wire (export_hot_prefixes -> ingest) and
    time it — the transfer half of the migrated-vs-recomputed prefix
    cost the zipf_chat record carries.  The timing includes the cold
    engine's one-time ingest compile, so the reported per-page cost is
    conservative (a steady-state pull is cheaper than this number)."""
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

    cold = LLMEngine(
        params, make_adapter(cfg),
        EngineConfig(max_slots=4, max_seq_len=max_seq, decode_chunk=8,
                     page_size=64, ragged_batching=True,
                     prefix_cache=True))
    try:
        t0 = time.perf_counter()
        transfers = eng.export_hot_prefixes(max_pages=512, mode="int8")
        pages = sum(cold.migration_ingest(t) for t in transfers)
        dt = time.perf_counter() - t0
    finally:
        cold.shutdown()
    return {"migrated_pages": int(pages),
            "wire_bytes": int(sum(int(t.get("wire_bytes", 0))
                                  for t in transfers)),
            "seconds": round(dt, 4)}


def _measure_serving_disagg(cfg, *, n_requests: int = 10, gen: int = 24,
                            lens=(512, 1024, 1536),
                            weights=(0.3, 0.5, 0.2),
                            arrival_rate: float = 2.0,
                            handoff_after_tokens: int = 2,
                            slots: int = 8,
                            params=None, adapter_factory=None) -> dict:
    """long_rag disaggregation on/off ablation, direct two-engine drive.

    OFF (unified): one engine serves the mix — long prefills and
    running decodes share the token-budget step, so a 1536-token
    prefill stretches every concurrent stream's inter-token latency.
    ON (disagg): a prefill engine serves the prompt plus the first
    ``handoff_after_tokens`` tokens, the finished pages migrate to a
    decode engine through the kv_transfer plane (lease -> int8 export
    -> ingest -> release, the same verbs the serve-path handoff uses),
    and decode resumes there against a prefix hit — the decode engine
    never runs a long prefill, which is the ITL separation this
    ablation measures.  TTFT is the prefill engine's (the client holds
    its first token before any page moves); a failed transfer falls
    back to serving the remainder on the prefill engine (the serve
    path's recompute fallback) and is counted in migration.failed.
    The serve-path handoff itself (router, MIGRATING ring state,
    SIGKILL fallback) is tier-1-tested in tests/test_disagg_serving.py.
    """
    import threading

    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    if params is None:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    make_adapter = adapter_factory or llama_paged_adapter
    rng = np.random.default_rng(7)
    req_lens = rng.choice(np.asarray(lens), n_requests,
                          p=np.asarray(weights, np.float64)
                          / np.sum(weights))
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in req_lens]
    max_seq = min(cfg.max_seq_len,
                  max(256, int(64 * np.ceil((int(req_lens.max())
                                             + gen + 1) / 64))))

    def make_engine():
        return LLMEngine(
            params, make_adapter(cfg),
            EngineConfig(max_slots=slots, max_seq_len=max_seq,
                         decode_chunk=4, page_size=64,
                         max_new_tokens_default=gen,
                         ragged_batching=True, prefill_chunk=256,
                         prefix_cache=True))

    def pct_ms(vals, q):
        vals = sorted(v for v in vals if v is not None)
        if not vals:
            return None
        return round(vals[min(len(vals) - 1,
                              int(q * len(vals)))] * 1e3, 2)

    def leg_stats(ttfts, itls, decode_tokens, dt):
        return {"ttft_p50_ms": pct_ms(ttfts, 0.50),
                "ttft_p95_ms": pct_ms(ttfts, 0.95),
                "itl_p50_ms": pct_ms(itls, 0.50),
                "itl_p95_ms": pct_ms(itls, 0.95),
                "decode_tokens_per_s": round(decode_tokens / dt, 1)}

    # Off-the-clock warm prompt: NOT one of the timed prompts, so the
    # prefix cache never hands the unified leg a free hit.
    warm_prompt = rng.integers(0, cfg.vocab_size,
                               int(min(lens))).tolist()

    # --- OFF: unified engine -----------------------------------------
    leg_t0 = time.time()  # waterfall-attribution window for this leg
    uni = make_engine()
    try:
        uni.submit(warm_prompt, max_new_tokens=gen,
                   temperature=0.0).result(timeout_s=600)
        t0 = time.perf_counter()
        streams = []
        for i, p in enumerate(prompts):
            delay = t0 + i / arrival_rate - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            streams.append(uni.submit(p, max_new_tokens=gen,
                                      temperature=0.0))
        outs = [s.result(timeout_s=600) for s in streams]
        dt_u = time.perf_counter() - t0
        ttfts_u = [s._req.ttft_s for s in streams]
        itls_u = [(s._req.finished_at - s._req.first_token_at)
                  / (len(o) - 1)
                  for s, o in zip(streams, outs) if len(o) > 1]
        toks_u = sum(len(o) for o in outs)
    finally:
        uni.shutdown()

    # --- ON: prefill engine -> page migration -> decode engine -------
    pre = make_engine()
    dec = make_engine()
    mig_lock = threading.Lock()
    mig = {"pages": 0, "wire_bytes": 0, "seconds": 0.0, "failed": 0}
    results = [None] * n_requests

    def run_one(prompt):
        s = pre.submit(prompt, max_new_tokens=handoff_after_tokens,
                       temperature=0.0)
        first = s.result(timeout_s=600)
        ttft = s._req.ttft_s
        seq = list(prompt) + list(first)
        lease = None
        transfer = None
        moved = 0
        t1 = time.perf_counter()
        try:
            lease = pre.migration_lease(seq)
            if lease is not None:
                transfer = pre.migration_export(lease["lease_id"],
                                                mode="int8")
                moved = dec.migration_ingest(transfer)
        except Exception:
            moved = 0
        finally:
            if lease is not None:
                pre.migration_release(lease["lease_id"])
        dt_m = time.perf_counter() - t1
        if moved:
            with mig_lock:
                mig["pages"] += moved
                mig["wire_bytes"] += int(transfer.get("wire_bytes", 0))
                mig["seconds"] += dt_m
            eng2 = dec
        else:
            with mig_lock:
                mig["failed"] += 1
            eng2 = pre
        s2 = eng2.submit(seq,
                         max_new_tokens=gen - handoff_after_tokens,
                         temperature=0.0)
        rest = s2.result(timeout_s=600)
        gap = s2._req.first_token_at - s._req.finished_at
        itl = ((s2._req.finished_at - s2._req.first_token_at)
               / (len(rest) - 1)) if len(rest) > 1 else None
        return ttft, itl, len(first) + len(rest), gap

    try:
        run_one(warm_prompt)  # compiles prefill/transfer/resume paths
        mig.update(pages=0, wire_bytes=0, seconds=0.0, failed=0)

        def worker(i, p):
            results[i] = run_one(p)

        t0 = time.perf_counter()
        threads = []
        for i, p in enumerate(prompts):
            delay = t0 + i / arrival_rate - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=worker, args=(i, p),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=600)
        dt_d = time.perf_counter() - t0
    finally:
        pre.shutdown()
        dec.shutdown()

    done = [r for r in results if r is not None]
    unified = leg_stats(ttfts_u, itls_u, toks_u, dt_u)
    disagg = leg_stats([r[0] for r in done],
                       [r[1] for r in done], sum(r[2] for r in done),
                       dt_d)
    disagg["handoff_gap_p50_ms"] = pct_ms([r[3] for r in done], 0.50)
    disagg["migration"] = {"pages": int(mig["pages"]),
                           "wire_bytes": int(mig["wire_bytes"]),
                           "seconds": round(mig["seconds"], 4),
                           "failed": int(mig["failed"])}
    ratio = None
    if unified["itl_p95_ms"] and disagg["itl_p95_ms"]:
        ratio = round(unified["itl_p95_ms"] / disagg["itl_p95_ms"], 2)
    out = {
        "mix": {"name": "long_rag", "lens": [int(x) for x in lens],
                "weights": [round(float(w), 4) for w in weights]},
        "n_requests": n_requests,
        "gen": gen,
        "handoff_after_tokens": handoff_after_tokens,
        "transfer": "int8",
        "unified": unified,
        "disagg": disagg,
        "itl_p95_ratio": ratio,
    }
    try:
        from ray_tpu.serve import latency_attribution

        dispatch_overhead = latency_attribution.aggregate(since=leg_t0)
    except Exception:
        dispatch_overhead = None
    if dispatch_overhead is not None:
        out["dispatch_overhead"] = dispatch_overhead
    return out


def _measure_serving_adapters(cfg, *, n_adapters: int = 6,
                              pool_adapters: int = 4,
                              n_requests: int = 24, gen: int = 16,
                              prompt_len: int = 96,
                              zipf_alpha: float = 1.1,
                              arrival_rate: float = 8.0,
                              slots: int = 8, rank: int = 4,
                              params=None) -> dict:
    """zipf_adapters: multi-tenant LoRA multiplexing vs single-model.

    Requests draw their adapter id from a Zipf popularity curve over
    ``n_adapters`` tenants while the paged pool only holds
    ``pool_adapters`` of them — the head tenants stay resident (pool
    hits) and the tail churns through the refcount-0 LRU (misses +
    evictions), which is the steady state a multiplexed deployment
    runs in.  The single-model leg serves the SAME prompts through the
    same engine shape without LoRA; ``throughput_degradation`` =
    multiplexed tokens/s over single-model tokens/s, the price of the
    segmented gathered-einsum delta plus adapter load churn."""
    import dataclasses as _dc

    from ray_tpu.ops import segmented_lora as _sl
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    if params is None:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    ranks = np.arange(1, n_adapters + 1, dtype=np.float64)
    pz = ranks ** -zipf_alpha
    pz /= pz.sum()
    draws = rng.choice(n_adapters, n_requests, p=pz)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    max_seq = min(cfg.max_seq_len,
                  max(128, int(64 * np.ceil((prompt_len + gen + 1)
                                            / 64))))
    lora = _sl.LoRAConfig(rank=rank, alpha=2.0 * rank)
    page_elems = 8192
    pp = -(-_sl.adapter_elems(cfg, lora) // page_elems)

    def run_leg(model_cfg, ids):
        ecfg = EngineConfig(
            max_slots=slots, max_seq_len=max_seq, page_size=32,
            decode_chunk=4, ragged_batching=True, prefill_chunk=64,
            max_new_tokens_default=gen,
            adapter_pool_pages=(pool_adapters * pp if ids else 0),
            adapter_page_elems=page_elems)
        eng = LLMEngine(params, llama_paged_adapter(model_cfg), ecfg)
        try:
            # Warm compile off the clock (the LoRA program too).
            eng.submit(prompts[0][: prompt_len // 2],
                       max_new_tokens=gen, temperature=0.0,
                       adapter_id=(ids[0] if ids else "")
                       ).result(timeout_s=600)
            t0 = time.perf_counter()
            streams = []
            for i, p in enumerate(prompts):
                delay = t0 + i / arrival_rate - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                streams.append(eng.submit(
                    p, max_new_tokens=gen, temperature=0.0,
                    adapter_id=(ids[i] if ids else "")))
            outs = [s.result(timeout_s=600) for s in streams]
            dt = time.perf_counter() - t0
            ttfts = sorted(s._req.ttft_s for s in streams)
            leg = {
                "tokens_per_s": round(sum(len(o) for o in outs) / dt,
                                      1),
                "ttft_p50_ms": round(
                    ttfts[len(ttfts) // 2] * 1e3, 2),
                "ttft_p95_ms": round(
                    ttfts[min(len(ttfts) - 1,
                              int(0.95 * len(ttfts)))] * 1e3, 2),
            }
            pool = (eng.stats() or {}).get("adapters")
            if pool is not None:
                leg["pool"] = {k: pool[k] for k in
                               ("pool_pages", "resident", "hits",
                                "misses", "evictions", "hit_ratio")}
            return leg
        finally:
            eng.shutdown()

    single = run_leg(cfg, None)
    ids = [f"tenant-{d}" for d in draws]
    multi = run_leg(_dc.replace(cfg, lora=lora), ids)
    degr = None
    if single["tokens_per_s"]:
        degr = round(multi["tokens_per_s"] / single["tokens_per_s"], 3)
    return {
        "mix": {"name": "zipf_adapters", "n_adapters": n_adapters,
                "zipf_alpha": zipf_alpha,
                "pool_adapters": pool_adapters, "rank": rank},
        "n_requests": n_requests,
        "gen": gen,
        "single_model": single,
        "multi": multi,
        "throughput_degradation": degr,
    }


def _measure_serving_chaos(cfg, *, n_waves: int = 4, wave_size: int = 10,
                           gen: int = 10, prefix_len: int = 8,
                           tail_len: int = 4, max_replicas: int = 3,
                           slots: int = 4,
                           decode_sleep_s: float = 0.02) -> dict:
    """SLO-driven autoscaling under chaos: a full serve-plane run
    (controller, autoscaled LLMServer deployment, router) against
    ramped zipf_chat arrival with the replica killer active.

    The goodput leg, not a throughput leg: decode is throttled so
    requests live long enough for the reconciler's pressure signals
    (admission-queue age, ongoing count) to see the ramp.  Asserts by
    schema (bench_schema._check_chaos): the run must show at least one
    scale-up, at least one drain-based scale-down after the ramp ends,
    and at least one replica killed mid-traffic — otherwise the leg
    measured a static fleet on a sunny day.  One wave after the
    replica kill the CONTROLLER itself is hard-killed: the routers
    keep serving on their last broadcast while a replacement rebuilds
    from the checkpoint, and the record carries controller_kills plus
    the measured recovery_seconds (kill -> new controller actor
    answering status).  Sheds (admission-control
    refusals once the queue is over the SLO budget) are counted
    separately from goodput: nothing ran, so nothing failed.

    A chip has one owner and this process may be it, so the replicas
    are ordinary workers (JAX_PLATFORMS=cpu) that build their own
    params: the leg exercises the control plane, and the record says
    so (``replica_platform``)."""
    import re as _re
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import api
    from ray_tpu.core.exceptions import ShedError
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMServer,
        llama_paged_adapter,
    )
    from ray_tpu.serve.controller import CONTROLLER_NAME
    from ray_tpu.util import metrics as _metrics
    from ray_tpu.utils.test_utils import ReplicaKiller, kill_actor_hard

    def slow_adapter_factory(c):
        # Paged + ragged (prefix_cache needs both); the throttle rides
        # the ragged step — a bare sleep would only fire at trace time.
        base = llama_paged_adapter(c)

        def slow_step(*a, **k):
            jax.debug.callback(lambda: time.sleep(decode_sleep_s),
                               ordered=True)
            return base.ragged_step(*a, **k)

        return dataclasses.replace(base, ragged_step=slow_step)

    def metric(family, tag_re=""):
        tot = 0.0
        pat = _re.compile(rf'^{family}{{[^}}]*{tag_re}[^}}]*}} (\S+)$')
        for line in _metrics.export_prometheus().splitlines():
            m = pat.match(line)
            if m:
                tot += float(m.group(1))
        return tot

    # zipf_chat arrival: a few hot shared prefixes (zipf popularity)
    # with unique tails, so prefix-affinity routing and the scale-up
    # warm start both have something to work with.
    rng = np.random.default_rng(11)
    prefixes = [rng.integers(1, cfg.vocab_size,
                             prefix_len).tolist() for _ in range(4)]
    zipf_w = np.array([1.0 / (i + 1) ** 1.1 for i in range(4)])
    zipf_w /= zipf_w.sum()

    def make_prompt():
        pre = prefixes[int(rng.choice(4, p=zipf_w))]
        return pre + rng.integers(1, cfg.vocab_size, tail_len).tolist()

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    serve.start()
    counts = {"completed": 0, "shed": 0, "failed": 0}
    lock = threading.Lock()
    max_groups = 0
    kills = 0
    controller_kills = 0
    recovery_seconds = None
    try:
        ups0 = metric("raytpu_serve_autoscale_decisions_total",
                      'direction="up"')
        downs0 = metric("raytpu_serve_autoscale_decisions_total",
                        'direction="down"')
        drains0 = metric("raytpu_serve_replica_drains_total")
        # Which signal fired each scale-up (decision `reason` tag):
        # predictive arrival_slope vs reactive queue_age/goodput/ongoing.
        reasons = ("arrival_slope", "queue_age", "goodput", "ongoing")
        ups_by_reason0 = {
            r: metric("raytpu_serve_autoscale_decisions_total",
                      f'direction="up"[^}}]*reason="{r}"')
            for r in reasons}
        app = serve.deployment(
            max_ongoing_requests=slots,
            autoscaling_config=dict(
                min_replicas=1, max_replicas=max_replicas,
                target_ongoing_requests=2.0, metrics_interval_s=0.05,
                look_back_period_s=0.5, upscale_delay_s=0.1,
                downscale_delay_s=0.3, target_queue_age_s=0.3,
                target_goodput=0.5,
                # Predictive arm: scale on arrival-rate slope before
                # the queue forms (serve/signals.ArrivalSignal).
                upscale_slope_threshold=1.0,
                arrival_half_life_s=0.5, arrival_slope_window_s=2.0),
        )(LLMServer).bind(
            cfg,
            EngineConfig(max_slots=slots,
                         max_seq_len=max(64, prefix_len + tail_len
                                         + gen + 16),
                         min_prefill_bucket=16, decode_chunk=1,
                         page_size=16, ragged_batching=True,
                         prefix_cache=True, shed_queue_age_s=3.0),
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg),
            adapter_factory=slow_adapter_factory,
        )
        handle = serve.run(app, name="chaos", route_prefix=None)
        shandle = handle.options(stream=True, max_retries=8)

        def run_one():
            try:
                shandle.remote({"tokens": make_prompt(),
                                "max_new_tokens": gen,
                                "temperature": 0.0}).result(timeout_s=300)
                with lock:
                    counts["completed"] += 1
            except ShedError:
                with lock:
                    counts["shed"] += 1
            except Exception:
                with lock:
                    counts["failed"] += 1

        # Warm the compiled paths off the clock.
        handle.remote({"tokens": make_prompt(), "max_new_tokens": 2,
                       "temperature": 0.0}).result(timeout_s=300)

        killer = ReplicaKiller(api.runtime(), seed=0)
        threads = []
        # Ramp: each wave doubles down on the queue before the last
        # one drains, so admission-queue age climbs and the reconciler
        # scales the group count up mid-traffic.
        for wave in range(n_waves):
            for _ in range(wave_size):
                th = threading.Thread(target=run_one, daemon=True)
                th.start()
                threads.append(th)
            time.sleep(0.4)
            max_groups = max(max_groups, int(metric(
                "raytpu_serve_autoscale_actual_groups")))
            # Chaos arm: once capacity scaled beyond one group, kill a
            # replica out from under the live waves (survivors absorb
            # the continuation replays).
            if kills == 0 and len(killer.victims()) >= 2:
                if killer.kill_one() is not None:
                    kills += 1
            # Control-plane chaos arm: one wave after the replica kill,
            # SIGKILL the controller itself mid-ramp.  The data plane
            # must keep serving on the last-known routing table while a
            # replacement controller rebuilds from its checkpoint;
            # recovery_seconds is kill -> a NEW controller actor (fresh
            # actor id, bumped epoch) answering status().
            elif kills >= 1 and controller_kills == 0:
                old = api.get_actor(CONTROLLER_NAME)
                t_kill = time.monotonic()
                kill_actor_hard(api.runtime(), old._actor_id)
                controller_kills += 1
                deadline_ctl = time.monotonic() + 60
                while time.monotonic() < deadline_ctl:
                    try:
                        fresh = serve._get_or_create_controller()
                        if fresh._actor_id != old._actor_id:
                            api.get(fresh.status.remote(), timeout=5.0)
                            recovery_seconds = round(
                                time.monotonic() - t_kill, 4)
                            break
                    except Exception:
                        pass
                    time.sleep(0.05)
        for th in threads:
            th.join(timeout=300)
        # Ramp over: wait for the policy to drain the extra groups
        # back down (downscale_delay + drain settle).
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            max_groups = max(max_groups, int(metric(
                "raytpu_serve_autoscale_actual_groups")))
            if (metric("raytpu_serve_autoscale_decisions_total",
                       'direction="down"') > downs0
                    and metric("raytpu_serve_autoscale_actual_groups")
                    <= 1):
                break
            time.sleep(0.1)
        ups = metric("raytpu_serve_autoscale_decisions_total",
                     'direction="up"') - ups0
        downs = metric("raytpu_serve_autoscale_decisions_total",
                       'direction="down"') - downs0
        drains = metric("raytpu_serve_replica_drains_total") - drains0
        # Absent-not-zero: only reasons that actually fired appear, so
        # the schema can tell "predictive arm never ran" from "ran and
        # scaled zero times" (bench_schema._check_autoscale_signals).
        scale_up_reasons = {}
        for r in reasons:
            n = int(metric("raytpu_serve_autoscale_decisions_total",
                           f'direction="up"[^}}]*reason="{r}"')
                    - ups_by_reason0[r])
            if n >= 1:
                scale_up_reasons[r] = n
        # Post-ramp invariant audit: kills + continuation replays +
        # scale-down drains are exactly the paths that leak KV pages or
        # adapter borrows, and a leg that leaked would still report
        # healthy goodput — the doctor's full partition walk is the
        # difference between "survived" and "survived intact"
        # (bench_schema._check_doctor requires violations == 0).
        from ray_tpu.util import state as _state

        t_doc = time.monotonic()
        doc = _state.doctor_report(deep=True)
        doctor = {
            "checks_run": int(doc.get("checks_run", 0)),
            "violations": int(doc.get("violations", 0)),
            "audit_seconds": round(time.monotonic() - t_doc, 4),
        }
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    offered = n_waves * wave_size
    return {
        "mix": "zipf_chat",
        "replica_platform": "cpu",
        "offered": offered,
        "completed": counts["completed"],
        "shed": counts["shed"],
        "failed": counts["failed"],
        "shed_fraction": round(counts["shed"] / offered, 4),
        "goodput_ratio": round(
            counts["completed"] / max(1, offered - counts["shed"]), 4),
        "scale_ups": int(ups),
        "scale_up_reasons": scale_up_reasons,
        "scale_downs": int(downs),
        "drain_retirements": int(drains),
        "kills": kills,
        "controller_kills": controller_kills,
        "recovery_seconds": recovery_seconds,
        "max_groups": max_groups,
        "max_replicas": max_replicas,
        "gen": gen,
        "doctor": doctor,
    }


def _measure_serving_mixed(cfg, *, n_requests: int = 48,
                           gen: int = 32, slots: int = 32,
                           arrival_rate: float = 8.0,
                           ragged: bool = True,
                           params=None, adapter_factory=None) -> dict:
    """The mixed-length ladder: one full knee ladder per PROMPT_MIX,
    served ragged (token-budget step, 256-token prefill slices) so the
    per-mix knees are comparable — the acceptance bar is that TTFT p95
    at the knee holds as the mix shifts from short_chat to long_rag.
    Ragged mixes serve speculatively (self-draft), report per-mix
    acceptance, and carry a burst-only spec-on/off ablation."""
    if params is None:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    out = {"batching": "ragged" if ragged else "interleaved",
           "mixes": {}}
    from ray_tpu.serve.llm_engine import llama_paged_adapter

    make_adapter = adapter_factory or llama_paged_adapter
    for name, mix in PROMPT_MIXES.items():
        try:
            leg = _measure_serving(
                cfg, n_requests=n_requests, gen=gen, slots=slots,
                arrival_rate=arrival_rate, params=params,
                adapter_factory=adapter_factory, prompt_mix=mix,
                mix_name=name, ragged=ragged,
                prefill_chunk=256 if ragged else 0,
                spec=ragged)
            out["mixes"][name] = leg
        except Exception as e:  # one collapsed mix must not eat the rest
            out["mixes"][name] = {"error": repr(e)[:120]}
            continue
        if "spec" not in leg:
            continue  # leg never speculated → no ablation (absent, not zero)
        try:
            leg["spec_ablation"] = _probe_spec_ablation(
                cfg, params, make_adapter, mix, gen=gen)
        except Exception as e:
            leg["spec_ablation"] = {"error": repr(e)[:120]}
    return out


def _probe_spec_ablation(cfg, params, make_adapter, mix, *,
                         n: int = 24, gen: int = 32,
                         slots: int = 16) -> dict:
    """Burst-only spec-on/off A/B on IDENTICAL prompts: the same mix,
    same seed, same engine shape, toggling only EngineConfig.spec_decode
    — so the delta is the verify-row machinery itself, not workload
    noise.  Burst (not open-loop) because the ablation question is
    decode-ceiling, and a full second knee ladder per mix would double
    the leg's wall clock for no extra signal."""
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

    rng = np.random.default_rng(5)
    lens = rng.choice(np.asarray(mix["lens"]), n,
                      p=np.asarray(mix["weights"], np.float64)
                      / np.sum(mix["weights"]))
    prompts = [rng.integers(0, cfg.vocab_size, int(L)).tolist()
               for L in lens]
    max_seq = min(cfg.max_seq_len,
                  max(512, int(64 * np.ceil((lens.max() + gen + 1) / 64))))
    out = {}
    for label, spec in (("on", True), ("off", False)):
        eng = LLMEngine(
            params, make_adapter(cfg),
            EngineConfig(max_slots=slots, max_seq_len=max_seq,
                         decode_chunk=8, max_new_tokens_default=gen,
                         page_size=64, ragged_batching=True,
                         prefill_chunk=256, spec_decode=spec))
        # Warm the compiled variants off the clock.
        eng.submit(prompts[0], max_new_tokens=gen,
                   temperature=0.0).result(timeout_s=600)
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=gen, temperature=0.0)
                   for p in prompts]
        for s in streams:
            s.result(timeout_s=600)
        dt = time.perf_counter() - t0
        sp = eng.stats().get("spec")
        eng.shutdown()
        leg = {"decode_tokens_per_s": round(n * gen / dt, 1)}
        if spec and sp and sp.get("rounds"):
            leg["accept_ratio"] = (
                round(sp["accepted_tokens"] / sp["drafted_tokens"], 3)
                if sp["drafted_tokens"] else None)
            leg["accepted_tokens_per_step"] = round(
                (sp["accepted_tokens"] + sp["rounds"]) / sp["rounds"], 2)
        out[label] = leg
    off_tps = out["off"]["decode_tokens_per_s"]
    out["speedup"] = (round(out["on"]["decode_tokens_per_s"] / off_tps, 2)
                      if off_tps else None)
    return out


def _measure_8b_train(peak_flops: float) -> dict:
    """The MEASURED full-8B AdamW rung (no extrapolation, ever): all 32
    layers, 128k vocab, bf16 master + int8 Adam states ZeRO-sharded
    over the data axes (train/zero.py), gradient-accumulation
    microbatching so activations fit.  On hardware without enough
    aggregate HBM the rung reports a LOUD structured error with the
    memory math — never a scaled number."""
    from ray_tpu.train import TrainerConfig, adamw8bit
    from ray_tpu.train import zero as zero_mod

    devs = jax.devices()
    n = len(devs)
    cfg8t = llama.LlamaConfig(
        vocab_size=128_256, dim=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, mlp_dim=14336, max_seq_len=SEQ,
        param_dtype=jnp.bfloat16, remat_policy="full", loss_chunk=512,
    )
    n_params = cfg8t.num_params()
    try:
        hbm = (devs[0].memory_stats() or {}).get("bytes_limit")
    except Exception:
        hbm = None
    if not hbm:
        hbm = 16 * 2**30  # v5e-class floor when the backend won't say
    # Per-chip plan, everything 1/n-sharded (params+grads over fsdp,
    # int8 states over the zero axes): 2 B/param params + 2 B/param
    # grad accumulator + ~2.1 B/param int8 states, plus ~2 GiB of
    # transients (gathered layer weights, remat activations, CE chunk).
    overhead = 2 * 2**30
    need = int(6.1 * n_params / n) + overhead
    if need > 0.92 * hbm:
        per_chip_ok = 0.92 * hbm - overhead
        min_chips = int(np.ceil(6.1 * n_params / max(per_chip_ok, 1)))
        return {
            "error": (f"full-8B AdamW needs ~{need / 2**30:.1f} GiB/chip "
                      f"on {n} chip(s) of {hbm / 2**30:.0f} GiB HBM; "
                      f"ZeRO-sharded it fits from {min_chips} chips"),
            "zero_sharding": True,
            "dp_shards": n,
            "est_bytes_per_chip": need,
            "hbm_bytes": int(hbm),
            "min_chips": min_chips,
        }
    grad_accum = 4
    batch = grad_accum * n
    extras: dict = {}
    tps = _measure(
        cfg8t, devs, steps=3, batch=batch,
        optimizer=adamw8bit(1e-4, warmup_steps=10),
        trainer_config=TrainerConfig(zero_sharding=True,
                                     grad_accum=grad_accum),
        extras=extras,
    )
    trainer = extras["trainer"]
    bytes_ = zero_mod.opt_state_bytes(trainer.state.opt_state)
    ds = zero_mod.dp_shards(trainer.mesh)
    tps_chip = tps / n
    hbm_peak = None
    try:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        peaks = [p for p in peaks if p]
        hbm_peak = max(peaks) if peaks else None
    except Exception:
        pass
    return {
        "params_b": round(n_params / 1e9, 2),
        "measured": True,
        "tokens_per_sec_per_chip": round(tps_chip, 1),
        "mfu": round(tps_chip * 6 * n_params / peak_flops, 4),
        "zero_sharding": True,
        "dp_shards": ds,
        "grad_accum": grad_accum,
        "batch": batch,
        "seq": SEQ,
        "optimizer": "adamw8bit (int8 states, ZeRO-sharded)",
        "opt_state_bytes_per_param": round(
            bytes_["per_device"] / n_params, 4),
        "opt_state_bytes_per_device": bytes_["per_device"],
        "hbm_peak_gb": (round(hbm_peak / 2**30, 2)
                        if hbm_peak else None),
    }


def _measure_8b(peak_flops: float) -> dict:
    """North-star #3: the 8B story.

    * SERVING (measured): int8 weight-only quantized 8B (≈8.3 GB)
      fits 16 GB HBM next to a paged bf16 KV cache; decode tok/s and
      TTFT measured through the real engine.
    * TRAIN (measured): full 32-layer AdamW with int8 Adam states
      ZeRO-sharded over the data axes (_measure_8b_train) — the rung
      that replaced the retired depth-truncated extrapolation; when
      the hardware can't hold it, the record says so in an error block
      with the memory math instead of scaling a smaller measurement.
    """
    from ray_tpu.models import quant

    cfg8 = BENCH_8B_CFG
    out: dict = {"params_b": round(cfg8.num_params() / 1e9, 2)}

    qparams = quant.init_quantized_llama(jax.random.PRNGKey(0), cfg8)
    # Fused qkv + gate/up: 5 projection matmuls → 2 per layer (decode
    # is per-op latency-bound on top of the weight reads).
    qparams = quant.fuse_for_decode(qparams, cfg8)
    jax.block_until_ready(qparams)
    out["int8_weight_gb"] = round(quant.quantized_bytes(qparams) / 2**30, 2)
    serving = _measure_serving(
        cfg8, n_requests=96, prompt_len=128, gen=32, slots=48,
        arrival_rate=4.0, params=qparams,
        adapter_factory=quant.llama_paged_adapter_quant,
    )
    out["serving_int8"] = serving
    del qparams, serving

    # Full-8B measured train rung (ZeRO-sharded int8 Adam states).
    _leg(out, "train", _measure_8b_train, peak_flops)
    out["train"].setdefault("zero_sharding", True)
    return out


def _measure_serving_multihost(cfg, *, shard_counts=(1, 2, 4),
                               n_requests: int = 16, gen: int = 16,
                               prompt_len: int = 32,
                               params=None) -> dict:
    """Multi-host tensor-parallel serving ladder: one engine per rung,
    weights sharded over a ``dcn_tp x tp`` serving mesh (shard count =
    hosts in the shard group; on CPU, contiguous virtual-device groups
    stand in for the host boundary).  Every multi-shard rung runs the
    DCN ablation — exact bf16-fallback collectives vs the int8
    quantized allreduce (EQuARX-style per-chunk scales) — recording
    greedy burst throughput plus the same per-decode-step
    bytes-on-wire accounting the serve telemetry counters use, so the
    record shows the >= 3x DCN reduction directly."""
    from ray_tpu.parallel.collectives import allreduce_wire_bytes
    from ray_tpu.parallel.mesh import create_serving_mesh
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    devs = jax.devices()
    if params is None:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    chunk = 32  # divides dim -> no pad waste in the quantized wire
    ladder = []
    for shards in shard_counts:
        # KV pools shard along heads over the COMBINED (dcn_tp, tp)
        # axis, so the whole group size must divide n_kv_heads.
        tp = max(1, min(len(devs) // shards, cfg.n_kv_heads // shards))
        if shards * tp > len(devs) or cfg.n_kv_heads % (shards * tp):
            continue
        for mode in (("bf16",) if shards == 1 else ("bf16", "int8")):
            cfg2 = dataclasses.replace(
                cfg, tensor_parallel=True,
                dcn_quantized_allreduce=(mode == "int8"),
                dcn_allreduce_chunk=chunk)
            eng = LLMEngine(
                params, llama_paged_adapter(cfg2),
                EngineConfig(max_slots=n_requests,
                             max_seq_len=max(128, prompt_len + gen + 16),
                             decode_chunk=8, page_size=16,
                             max_new_tokens_default=gen),
                mesh=create_serving_mesh(shards, tp),
            )
            try:
                # Warm the compiled variants off the clock.
                eng.submit(prompts[0],
                           max_new_tokens=gen).result(timeout_s=600)
                t0 = time.perf_counter()
                streams = [eng.submit(p, max_new_tokens=gen,
                                      temperature=0.0)
                           for p in prompts]
                n_tokens = sum(
                    len(s.result(timeout_s=600)) for s in streams)
                dt = time.perf_counter() - t0
                coll = (eng._coll_bytes_fn(1) if eng._coll_bytes_fn
                        else {"ici": 0, "dcn": 0})
            finally:
                eng.shutdown()
            fp32_dcn = 2 * cfg.n_layers * allreduce_wire_bytes(
                cfg.dim, axis_size=shards, quantized=False)
            ladder.append({
                "shards": shards,
                "tp": tp,
                "dcn_collective": mode,
                "toks_per_s": round(n_tokens / dt, 1),
                "ici_bytes_per_step": int(coll["ici"]),
                "dcn_bytes_per_step": int(coll["dcn"]),
                "dcn_bytes_ratio_vs_fp32": (
                    round(fp32_dcn / coll["dcn"], 2)
                    if coll["dcn"] else None),
            })
    return {"ladder": ladder}


def _require_tpu():
    """The devices this benchmark measures.  It measures a TPU; on any
    other platform it fails instead of timing something nobody
    deploys."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench.py measures a TPU; JAX found platform="
                 f"{devices[0].platform!r} ({devices[0].device_kind!r}) "
                 f"— run it through the chip tool")
    return devices


def _leg(extra: dict, name: str, fn, *args, **kwargs) -> None:
    """Run one leg into ``extra[name]``.  A leg that raises is recorded
    as ``{"error": ...}`` so the other legs still report, and main()
    exits non-zero when any did."""
    try:
        extra[name] = fn(*args, **kwargs)
    except Exception as e:
        # No ", "/": " — the final stdout line must stay compact.
        extra[name] = {"error": repr(e).replace(": ", ":")
                       .replace(", ", ",")[:200]}


def main():
    from ray_tpu.utils.accelerator import (
        enable_compile_cache,
        local_chip_spec,
    )

    devices = _require_tpu()
    enable_compile_cache()
    cfg = BENCH_CFG
    spec = local_chip_spec()
    peak = spec["peak_flops"]

    tps = _measure(cfg, devices, steps=10)
    # Baseline: same step in float32 — the throughput of a port that
    # ignores the MXU's bf16 preference.  (f32 *without* remat, the truly
    # naive variant, OOMs outright at this size: 34 GB of attention probs.)
    baseline_cfg = dataclasses.replace(cfg, dtype=jax.numpy.float32,
                                       remat_policy="full")
    baseline_tps = _measure(baseline_cfg, devices, steps=3)

    n_chips = len(devices)
    tps_chip = tps / n_chips
    mfu = tps_chip * 6 * cfg.num_params() / peak

    extra = {
        "chips": n_chips,
        "platform": spec["chip"].removeprefix("TPU-"),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n_chips},
        "mfu": round(mfu, 4),
        "batch": BATCH,
        "seq": SEQ,
        "params_m": round(cfg.num_params() / 1e6, 1),
    }

    def train_leg(cfg_x, **kw):
        tps_x = _measure(cfg_x, devices, **kw) / n_chips
        return {
            "tokens_per_sec_per_chip": round(tps_x, 1),
            "mfu": round(tps_x * 6 * cfg_x.num_params() / peak, 4),
        }

    # North star #1: the largest single-chip config (≥1B params).
    _leg(extra, "llama_1b", lambda: {
        "params_m": round(BENCH_1B_CFG.num_params() / 1e6, 1),
        **train_leg(BENCH_1B_CFG, steps=4)})

    # The MEASURED multi-billion point: 2.24B end-to-end on one chip
    # via int8 Adam states (no extrapolation).
    def leg_2b():
        from ray_tpu.train import adamw8bit

        return {
            "params_b": round(BENCH_2B_CFG.num_params() / 1e9, 2),
            **train_leg(BENCH_2B_CFG, steps=3, batch=4,
                        optimizer=adamw8bit(1e-4, warmup_steps=10)),
            "optimizer": "adamw8bit (int8 block-quantized m,v)",
        }

    _leg(extra, "llama_2b", leg_2b)
    # North star #2: serving req/s + TTFT (continuous batching),
    # open-loop at an offered load + burst ceiling — for BOTH the 319M
    # and the 1.14B configs.
    _leg(extra, "serving", _measure_serving,
         dataclasses.replace(cfg, max_seq_len=512))
    _leg(extra, "serving_1b", _measure_serving,
         dataclasses.replace(BENCH_1B_CFG, max_seq_len=512),
         n_requests=64, slots=32, arrival_rate=12.0)
    # The MIXED-length ladders (short-chat / long-RAG / bursty), served
    # through the ragged token-budget step: the knee under realistic
    # traffic, where the old two-program engine's TTFT p95 exploded as
    # soon as long prompts entered the mix.
    _leg(extra, "serving_mixed", _measure_serving_mixed,
         dataclasses.replace(cfg, max_seq_len=2048),
         n_requests=64, slots=48, arrival_rate=16.0)
    _leg(extra, "serving_1b_mixed", _measure_serving_mixed,
         dataclasses.replace(BENCH_1B_CFG, max_seq_len=2048),
         n_requests=48, slots=32, arrival_rate=6.0)
    # North star #3: the 8B artifact — int8 serving (measured) + the
    # full-8B train rung (measured where the hardware holds it).
    _leg(extra, "llama_8b", _measure_8b, peak)
    # Multi-host serving ladder: shard-group replicas on a hybrid
    # dcn_tp x tp mesh, quantized-vs-exact DCN ablation with
    # bytes-on-wire in the record.
    _leg(extra, "serving_multihost", _measure_serving_multihost,
         dataclasses.replace(cfg, max_seq_len=512))
    # Disaggregated prefill/decode ablation on the long-RAG mix:
    # unified vs prefill -> kv_transfer -> decode, direct two-engine
    # drive (the serve-path handoff is tier-1-tested).
    _leg(extra, "serving_disagg", _measure_serving_disagg,
         dataclasses.replace(cfg, max_seq_len=2048))
    # Multi-tenant LoRA multiplexing: Zipf adapter popularity through
    # the paged adapter pool vs the same traffic single-model — pool hit
    # ratio and the segmented-matmul throughput price.
    _leg(extra, "serving_adapters", _measure_serving_adapters,
         dataclasses.replace(cfg, max_seq_len=512))
    # SLO-driven autoscaling chaos: full serve-plane run (controller +
    # autoscaled deployment + replica killer) under ramped zipf_chat
    # arrival — goodput ratio, shed fraction, scale events, kills
    # survived.  Control-plane behaviour on CPU replicas (see the leg),
    # so a tiny model: nothing here is a device metric.
    _leg(extra, "serving_chaos", _measure_serving_chaos,
         llama.LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                           n_kv_heads=2, mlp_dim=64, max_seq_len=128,
                           remat=False))

    result = {
        "metric": f"llama_{cfg.num_params()/1e6:.0f}M_train_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tps / baseline_tps, 3),
        "extra": extra,
    }
    # The record survives two independent ways: BENCH_OUT.json on disk
    # AND the final stdout line.  The driver wrapper parses that LAST
    # line into BENCH_r0N.json's ``parsed`` — BENCH_r05 shipped
    # parsed:null because its bounded stdout tail cut the line
    # mid-object.  So the line is COMPACT (no separator padding; ~25%
    # smaller, and the mixed ladders grow the record further), printed
    # last, and flushed; scripts/gen_perf_tables.py can still recover
    # the last complete JSON line from a wrapper, and the file copy
    # makes even that unnecessary when the filesystem comes home.
    blob = json.dumps(result, separators=(",", ":"))
    with open("BENCH_OUT.json", "w") as f:
        f.write(blob + "\n")
    def errored(block):
        """An {"error": ...} anywhere under a leg (mixes and ablations
        nest their own).  "min_chips" marks the 8B train rung's
        structured "does not fit this many chips", which is a result."""
        if not isinstance(block, dict):
            return False
        if "error" in block and "min_chips" not in block:
            return True
        return any(errored(v) for v in block.values())

    failed = sorted(k for k, v in extra.items() if errored(v))
    if failed:
        print(f"bench.py: legs failed: {failed}", file=sys.stderr)
    print(blob, flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
