"""Runner for Jamba configurations served through ``serve.run(LLMServer)``
with ``jamba_paged_adapter``: Mamba-1 layers whose recurrent state lives
per slot beside the paged KV of the attention layers.

The client side is ``serve_llm``'s, whatever of it is free of the model
(warm-up, sweep, tracer, payload, the replica's reporting methods); what
differs is in the replica: the weights, the adapter, the two checks
against the plain reference (``harness/reference_jamba.py``: three
layers before the engine takes the memory, and the served tokens at
full depth after the window) and a heap frozen after the warm-up.  ``run``
repeats ``serve_llm.run``'s open-loop path because that function builds
its own server class; folding the two is a ``benchmark`` PR's (PERF.md
section 7).
"""

from __future__ import annotations

import gc
import importlib.util
import time
from typing import Any, Dict, List

from benchmarks.harness import loadgen
from benchmarks.harness.run_record import Run
from benchmarks.runners import serve_llm
from benchmarks.runners.common import CompileCounter

# Logits of the engine's own ragged step (bf16 weights and activations;
# float32 state, exponent, softplus, norms, softmax) against the float32
# reference on the same weights, as a share of the reference's largest
# logit, on three layers (Mamba, attention, Mamba) at the configuration's
# widths.  Three rows, each with its own bound:
#
# "chunked": slot 0, a prompt of 300 tokens prefilled in chunks of 128
# beside the other row's decode steps, then 96 decoded tokens: the state
# handed from chunk to chunk and from the last chunk to the decode rows,
# and the attention layer reading its own pages between the two Mamba
# layers.
# "beside": slot 5, a prompt of 40 tokens whole, then 96 decoded tokens
# while slot 0 prefills and decodes: two rows live in one step, each
# with its own state.
# "reused_slot": slot 5 again, taken by a second request once the first
# has finished: 50 prompt tokens whole and 64 decoded.  Its first row
# has row_start 0, which is what resets the slot; a program that kept
# the first request's state fails here and nowhere else.
#
# Logits (my chip runs, PR 27; PERF.md section 4), at the prompt's end over
# 89 seeds: chunked 0.72e-2 to 1.17e-2, beside 0.86e-2 to 1.25e-2,
# reused_slot 0.83e-2 to 1.31e-2; over the decoded tokens up to 1.44e-2,
# 1.54e-2 and 1.46e-2 (50 seeds on this schedule, 39 with 8 decoded tokens
# a row).  That is bf16's rounding of the activations over three layers,
# the floor of any comparison with a float32 reference; the bounds are 1.4
# to 1.5 times the largest.  A program that does not reset a reused slot
# reads 22e-2 to 37e-2 there.  What is small beside that floor the logits
# do not show: an SSM state kept in bf16 reads 0.9e-2 to 1.6e-2.
TOLERANCES = {"chunked": 2.0e-2, "beside": 2.3e-2, "reused_slot": 2.0e-2}
# So the state itself is held to the reference's: the first Mamba layer's
# SSM state after a sequence's last token (slot 0 after ``chunked``, slot
# 5 after ``reused_slot``) against the reference's float32 state, as the
# mean over the 5120 channels of |difference| / |reference| (2-norms over
# the channel's 16 states).  The first layer, because its input is the
# embedding and nothing upstream blurs it (the third layer reads 0.9e-2 to
# 1.3e-2 whatever the state's precision).  Each sequence decodes for about
# the cell's median answer (N_DECODE), so that a cache which kept the state
# in less than float32 would have rounded it after every one of those
# tokens.  Float32 state: 0.215e-2 to 0.407e-2 (50 seeds, my chip runs, PR
# 27).  Rounded to bf16 between the check's steps (``state_control``):
# 0.683e-2 to 0.861e-2 (9 seeds).  The bound lies between the two, 1.3
# times the first's largest and 1.3 times under the second's smallest.
# Not reset: over 25e-2.
STATE_TOLERANCE = 5.2e-3
# ``served_check``: how far under the reference's largest logit the logit
# of a served token may lie, as a share of the sequence's largest.  The
# engine's 28-layer step in bf16 picks the reference's argmax for 92% to
# 95% of tokens and otherwise one at most 0.99e-2 to 2.27e-2 under it (33
# runs of the cell, 665 to 901 tokens each; my chip runs, PR 27); another
# request's token at the same place reads 29e-2 to 51e-2 in a run's median
# (random weights have favourite tokens, so a run's smallest is 0).
SERVED_SAMPLES, SERVED_LEN, SERVED_MARGIN = 6, 512, 5.0e-2
# the check's three layers, as published keys: Mamba, attention, Mamba
CHECK_HF = {"num_hidden_layers": 3, "attn_layer_period": 3,
            "attn_layer_offset": 1}
CHUNK = 128
SLOTS = {"chunked": 0, "beside": 5, "reused_slot": 5}
N_PROMPT = {"chunked": 300, "beside": 40, "reused_slot": 50}
N_DECODE = {"chunked": 96, "beside": 96, "reused_slot": 64}


def model_config(config: Dict[str, Any]):
    """``JambaConfig`` from the published keys."""
    import jax.numpy as jnp

    from ray_tpu.models.jamba import JambaConfig

    c = config
    dtype = getattr(jnp, c.get("torch_dtype", "bfloat16"))
    return JambaConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        mlp_dim=c["intermediate_size"],
        attn_layer_period=c["attn_layer_period"],
        attn_layer_offset=c["attn_layer_offset"],
        d_state=c["mamba_d_state"], d_conv=c["mamba_d_conv"],
        dt_rank=c["mamba_dt_rank"], expand=c["mamba_expand"],
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=dtype, param_dtype=dtype)


def _load_weights(cfg, seed: int):
    import jax

    from ray_tpu.models import jamba

    return jamba.init_params(jax.random.key(seed % (2**31 - 1)), cfg)


def _pieces(n_prompt: int, n_total: int, chunk: int) -> List[tuple]:
    """(start, length) of a sequence's rows: its prompt in chunks, then
    one token at a time."""
    cuts = list(range(0, n_prompt, chunk)) + list(range(n_prompt, n_total))
    return [(a, b - a) for a, b in zip(cuts, cuts[1:] + [n_total])]


def _schedule(seqs: Dict[str, List[int]], n_prompt: Dict[str, int]):
    """The check's steps: per step the rows (name, slot, start, length).
    ``beside`` begins at step 0 and ``chunked`` at step 1, so the
    prompt's chunks ride beside decode rows; ``reused_slot`` takes
    ``beside``'s slot the step after that sequence's last row."""
    pieces = {k: _pieces(n_prompt[k], len(seqs[k]),
                         CHUNK if k == "chunked" else len(seqs[k]))
              for k in seqs}
    begins = {"beside": 0, "chunked": 1,
              "reused_slot": len(pieces["beside"])}
    n_steps = max(begins[k] + len(pieces[k]) for k in seqs)
    return [[(k, SLOTS[k]) + pieces[k][s - begins[k]] for k in seqs
             if 0 <= s - begins[k] < len(pieces[k])]
            for s in range(n_steps)]


def _program_logits(cfg3, params, seqs, n_prompt, page: int,
                    ssm_dtype=None):
    """Run the check's schedule through the adapter's ragged step.
    Returns {name: [(position, logits)]} for every row that ended at or
    after its prompt's last token.  ``ssm_dtype`` rounds the SSM state
    to that precision between steps: how a cache that kept it in less
    than float32 would read (``state_control``: the demonstration that
    STATE_TOLERANCE sees it)."""
    import jax
    import numpy as np

    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
    from ray_tpu.serve.llm_engine import jamba_paged_adapter

    adapter = jamba_paged_adapter(cfg3)
    schedule = _schedule(seqs, n_prompt)
    n_slots = 8
    budget = -(-max(sum(r[3] for r in rows) for rows in schedule) // 8) * 8
    maxp = -(-max(len(s) for s in seqs.values()) // page)
    cache = adapter.init_cache(n_slots * maxp, page, n_slots)
    table = np.arange(n_slots * maxp,
                      dtype=np.int32).reshape(n_slots, maxp)
    step = jax.jit(adapter.ragged_step, donate_argnums=(8,))
    got: Dict[str, list] = {k: [] for k in seqs}
    for rows in schedule:
        packed = [{"slot": slot, "start": start,
                   "tokens": seqs[name][start:start + n]}
                  for name, slot, start, n in rows]
        (ht, _m, _s, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(packed, budget, n_slots)
        logits, cache = step(params, ht, pos, r_slot, r_start, r_len,
                             r_off, table, cache)
        if ssm_dtype is not None:
            cache = dict(cache, ssm=cache["ssm"].astype(ssm_dtype)
                         .astype(cache["ssm"].dtype))
        for i, (name, _slot, start, n) in enumerate(rows):
            if start + n >= n_prompt[name]:
                got[name].append((start + n - 1,
                                  np.asarray(logits[i], np.float32)))
    # what each slot's last sequence left: [layer, d_state, d_inner]
    states = {name: np.asarray(cache["ssm"][:, SLOTS[name]])
              for name in ("chunked", "reused_slot")}
    return got, states


def _state_error(have, ref) -> float:
    """``have`` [d_state, d_inner] of the program against ``ref``
    [d_inner, d_state] of the reference (see STATE_TOLERANCE)."""
    import numpy as np

    have, ref = np.asarray(have, np.float64).T, np.asarray(ref, np.float64)
    return float(np.mean(np.linalg.norm(have - ref, axis=1)
                         / np.linalg.norm(ref, axis=1)))


def logits_check(cfg, config: Dict[str, Any], seed: int, *,
                 ssm_dtype=None) -> Dict[str, Any]:
    """Three layers (Mamba, attention, Mamba) at the configuration's
    widths through the engine's ragged step against the plain
    reference's full forward pass of each sequence: logits, not tokens
    (TOLERANCES), and the first Mamba layer's final SSM state
    (STATE_TOLERANCE)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_jamba

    page = config["engine"].get("page_size", serve_llm.PAGE_DEFAULT)
    hf = dict(config, **CHECK_HF)
    cfg3 = model_config(hf)
    params = _load_weights(cfg3, seed)
    rng = np.random.default_rng(seed % (2**32))
    seqs = {k: rng.integers(1, cfg.vocab_size,
                            N_PROMPT[k] + N_DECODE[k]).tolist()
            for k in N_PROMPT}
    got, states = _program_logits(cfg3, params, seqs, N_PROMPT, page,
                                  ssm_dtype)
    out: Dict[str, Any] = {"layers": 3, "ok": True}
    with jax.default_matmul_precision("highest"):
        ref_params = reference_jamba.from_program_tree(params, hf)
        forward = jax.jit(
            lambda p, t: reference_jamba.forward_with_states(p, t, hf))
        state_errs: Dict[str, float] = {}
        for name, rows in got.items():
            want, want_states = forward(
                ref_params, jnp.asarray(seqs[name], jnp.int32))
            want = np.asarray(want)
            if name in states:
                state_errs[name] = _state_error(states[name][0],
                                                want_states[0])
            scale = float(np.max(np.abs(want)))
            errs = [float(np.max(np.abs(g - want[i]))) / scale
                    for i, g in rows]
            ok = bool(len(rows) == N_DECODE[name] + 1
                      and all(np.isfinite(g).all() for _i, g in rows)
                      and max(errs) <= TOLERANCES[name])
            out[name] = {"rel_err_prefill": errs[0],
                         "rel_err_decode": max(errs[1:]),
                         "tol": TOLERANCES[name], "ok": ok}
            out["ok"] = out["ok"] and ok
        ok = bool(len(state_errs) == 2
                  and max(state_errs.values()) <= STATE_TOLERANCE)
        out["ssm_state"] = {"rel_err": state_errs,
                            "tol": STATE_TOLERANCE, "ok": ok}
        out["ok"] = out["ok"] and ok
    return out


def served_check(config: Dict[str, Any], weights, served) -> Dict[str, Any]:
    """What the engine served in the run against the plain reference at
    the configuration's full depth: SERVED_SAMPLES finished requests,
    spread evenly over the run's order of finishing, each prompt plus
    answer through ``reference_jamba`` one layer at a time (the float32
    copy of one layer's weights beside the engine's).  Every served
    token has to be the reference's argmax after the tokens before it,
    or within SERVED_MARGIN of that logit as a share of the sequence's
    largest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_jamba as ref

    t0 = time.perf_counter()
    fits = [(p, a) for p, a in served if a and len(p) + len(a) <= SERVED_LEN]
    out: Dict[str, Any] = {"layers": config["num_hidden_layers"],
                           "finished": len(served), "requests": 0,
                           "tokens": 0, "margin": SERVED_MARGIN, "ok": False}
    if not fits:
        return out
    picks = [fits[i] for i in sorted({int(i) for i in np.linspace(
        0, len(fits) - 1, SERVED_SAMPLES)})]
    toks = np.zeros((len(picks), SERVED_LEN), np.int32)
    # the control: at each served token's place, the token the next
    # sampled request was given at the same point of its answer
    other = np.zeros_like(toks)
    for r, (p, a) in enumerate(picks):
        toks[r, :len(p) + len(a)] = list(p) + list(a)
        a2 = picks[(r + 1) % len(picks)][1]
        other[r, len(p):len(p) + len(a)] = [a2[k % len(a2)]
                                            for k in range(len(a))]
    kinds = ref.layer_kinds(config)
    with jax.default_matmul_precision("highest"):
        head = ref.head_from_program_tree(weights)
        block = {k: jax.jit(jax.vmap(
            lambda x, lp, k=k: ref.layer(x, lp, k, config)[0],
            in_axes=(0, None))) for k in set(kinds)}
        x = head["tok_embed"][jnp.asarray(toks)]
        for i, kind in enumerate(kinds):
            x = block[kind](x, ref.layer_from_program_tree(weights, config, i))

        @jax.jit
        def readings(x, head, toks, other):
            # the logits after token j - 1 chose token j
            logits = ref.logits_of(x, head, config)          # [B, L, V]
            at = lambda t: jnp.take_along_axis(  # noqa: E731
                logits, jnp.roll(t, -1, 1)[..., None], -1)[..., 0]
            return (logits.max(-1), at(toks), at(other),
                    jnp.abs(logits).max(-1))

        top, got, swapped, absmax = (
            np.asarray(a, np.float64) for a in
            readings(x, head, jnp.asarray(toks), jnp.asarray(other)))
    short, control, exact, distinct = [], [], 0, set()
    for r, (p, a) in enumerate(picks):
        at = slice(len(p) - 1, len(p) + len(a) - 1)
        scale = absmax[r, :len(p) + len(a)].max()
        short += list((top[r, at] - got[r, at]) / scale)
        control += list((top[r, at] - swapped[r, at]) / scale)
        exact += int(np.sum(top[r, at] == got[r, at]))
        distinct |= set(a)
    out.update(requests=len(picks), tokens=len(short),
               distinct_tokens=len(distinct),
               exact_share=exact / len(short),
               rel_short_max=float(max(short)),
               # a program that handed a request another's tokens
               rel_short_swapped_median=float(np.median(control)),
               seconds=time.perf_counter() - t0,
               ok=bool(np.isfinite(short).all()
                       and max(short) <= SERVED_MARGIN))
    return out


def server_class():
    """Built in a function so that importing this module imports no
    JAX in the client."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMServer,
        jamba_paged_adapter,
    )

    class BenchJambaServer(serve_llm.server_class()):
        def __init__(self, spec: Dict[str, Any]):
            self._compiled = CompileCounter()
            config, seed = spec["config"], spec["seed"]
            cfg = model_config(config)
            self._check = logits_check(cfg, config, seed)
            self._config, self._cfg, self._seed = config, cfg, seed
            self._served: List[tuple] = []      # (prompt, answer), finished

            def load():
                self._weights = _load_weights(cfg, seed)
                return self._weights

            LLMServer.__init__(
                self, cfg, EngineConfig(**config["engine"]), load,
                adapter_factory=jamba_paged_adapter)
            self._tracer = None
            self._rehearse = bool(spec.get("rehearse"))

        def __call__(self, payload: Dict[str, Any]) -> Dict[str, Any]:
            out = super().__call__(payload)
            self._served.append((payload["tokens"], out["tokens"]))
            return out

        def stream(self, payload: Dict[str, Any]):
            answer: List[int] = []
            for tok in super().stream(payload):
                answer.append(tok)
                yield tok
            self._served.append((payload["tokens"], answer))

        def settle(self) -> int:
            """After the warm-up: one full collection, then everything
            start-up and compilation built is taken out of the
            collector's sight (``gc.freeze``, as serving deployments
            do).  Left in it, each full collection in the window stops
            the engine loop for 0.07 to 0.11 s, one to three times a
            window, and the tokens of every live request with it (my
            chip runs, PR 27; PERF.md Findings).  ``LLMServer`` has no
            such step yet.  Returns how many objects were frozen."""
            gc.collect()
            gc.freeze()
            return gc.get_freeze_count()

        def state_control(self) -> Dict[str, Any]:
            """The check again with the SSM state rounded to bfloat16
            between its steps, as a cache that kept it in less than the
            configuration's float32 would: its ``ssm_state`` has to come
            out not ok.  ``chip_smoke.py``'s Jamba case asks for it; a
            run of the cell does not."""
            import jax.numpy as jnp

            return logits_check(self._cfg, self._config, self._seed,
                                ssm_dtype=jnp.bfloat16)

        def served_check(self) -> Dict[str, Any]:
            """After the window, the engine idle: see ``served_check``."""
            return served_check(self._config, self._weights, self._served)

        def counters(self) -> Dict[str, Any]:
            out = super().counters()
            stats = self.engine.stats()
            out["state_cache"] = stats.get("state_cache")
            out["loop"] = stats["loop"]
            return out

    return BenchJambaServer


def run(ctx) -> Run:
    if importlib.util.find_spec("ray_tpu.models.jamba") is None:
        raise SystemExit(
            f"benchmark: cell {ctx.cell} needs ray_tpu.models.jamba, "
            f"which this program does not have; no result")
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.utils import accelerator

    config, traffic = ctx.config, ctx.traffic
    vocab = config["vocab_size"]
    if traffic["loop"] != "open":
        raise ValueError(f"serve_jamba drives open loops only, not "
                         f"{traffic['loop']!r}")
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
            return stream.remote(serve_llm._payload(p))

        serve_llm._warm_up(traffic, send, vocab)
        handle.settle.remote().result(timeout_s=60)
        gc.collect()        # and the load generator's own process
        gc.freeze()
        if ctx.sweep:
            serve_llm._sweep(ctx, traffic, send, vocab)
            return None

        marks: Dict[str, Any] = {}

        def on_window_start():
            marks["t_start"] = time.perf_counter()
            marks["c0"] = handle.counters.remote().result(timeout_s=60)

        tracer = serve_llm._Tracer(handle, ctx, traffic) if ctx.trace \
            else None
        plan = loadgen.open_loop_plan(traffic, ctx.seconds, ctx.seed, vocab)
        if tracer:
            tracer.arm()
        res = loadgen.run_open_loop(
            plan, send, vocab, on_window_start=on_window_start,
            max_inflight=traffic.get("max_inflight", 128))
        trace = tracer.result() if tracer else None
        c1 = handle.counters.remote().result(timeout_s=60)
        ring = handle.ring_rows.remote().result(timeout_s=60)
        report = handle.device_report.remote().result(timeout_s=60)
        served = handle.served_check.remote().result(timeout_s=600)
        if accelerator.backend_initialised() and not ctx.rehearse:
            raise SystemExit("benchmark: the client initialised a JAX "
                             "backend; it would hold the chip")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    records = res["records"]
    meas = [r for r in records if r["measured"]]
    c0 = marks.get("c0", {})
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
                     and check["ok"] and served["ok"]),
        notes={"compiles_in_window": compiles, "reference_check": check,
               "served_check": served,
               "state_cache": c1.get("state_cache"),
               "errors": sorted({r["error"] for r in meas
                                 if r["error"]})[:5]},
        requests=records, ring=ring, counters0=c0, counters1=c1,
        trace=trace)
