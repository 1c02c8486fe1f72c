"""Runner for Brumby configurations served through ``serve.run(LLMServer)``
with ``brumby_paged_adapter``: power-retention layers whose state is a
matrix and a vector per KV head and slot, and no page of KV at all.

The drive is ``serve_jamba.run`` itself (chip check, deployment, warm-up,
``settle``, sweep, tracer, open loop, counters, the served check, the
record): that function builds its server from its module's
``server_class``, which is set to this file's for the length of the
call.  The replica's free parts (``settle``, the served-token log, the
counters) are inherited from ``serve_jamba``'s server class.  What is
this file's own is what differs in the model: the weights, the adapter,
and the two checks against the plain reference
(``harness/reference_brumby.py``).  Folding the three serving runners
into one is a ``benchmark`` PR's (ROADMAP.md Queue 3 item 10).
"""

from __future__ import annotations

import importlib.util
import time
from typing import Any, Dict, List

from benchmarks.runners import serve_jamba
from benchmarks.runners.common import CompileCounter, model_config
from benchmarks.runners.serve_jamba import _pieces

# taken before ``run`` sets the module's name to this file's own
_jamba_server_class = serve_jamba.server_class

# ``logits_check``: three layers at the configuration's widths through
# the adapter's ragged step against the float32 reference's quadratic
# form, logits as a share of the reference's largest, three rows:
#
# "chunked": slot 0, a prompt of four and a half of the engine's chunks
# (2,300 tokens in chunks of 512) beside the other row's decode steps,
# then 96 decoded tokens: the state handed from chunk to chunk (five of
# them, the last one short) and from the last chunk to the decode rows,
# at a depth of thousands of tokens.
# "beside": slot 5, 40 tokens whole, then 96 decoded while slot 0
# prefills and decodes: two rows live in one step, each with its state.
# "reused_slot": slot 5 again once the first request has finished: 50
# tokens whole and 64 decoded.  Its first row has row_start 0, which is
# what resets the slot.
#
# The limits and the readings behind them are in PERF.md section 4.
# Logits differ from the reference by bfloat16's rounding of weights'
# products and activations (and of the kernels' matmul operands) over
# three layers; a state kept in bfloat16 adds little to that, so the
# state itself is held to the reference's (STATE_TOLERANCE).  Over 101
# seeds the long row reads at most 1.4e-2 and the two short rows
# 1.9e-2 but for four seeds at 2.1e-2 to 2.3e-2 (few terms under the
# normaliser: a small denominator carries the rounding further), so
# the limit leaves the largest reading two fifths of room.
TOLERANCES = {"chunked": 3.2e-2, "beside": 3.2e-2, "reused_slot": 3.2e-2}
# The first layer's final (S, z) of a slot against the reference's
# direct sum, as the mean over the KV heads of |difference| / |reference|
# (Frobenius norms, the state folded to the deduplicated 8256 features):
# slot 0 after ``chunked`` and slot 5 after ``reused_slot``.  The first
# layer, because its input is the embedding and nothing upstream blurs
# it.  ``state_control`` rounds the state to bfloat16 between the check's
# steps, as a cache that kept it in less than float32 would; it has to
# come out NOT ok by this limit.
STATE_TOLERANCE = 8.0e-3
# ``served_check``: how far under the reference's largest logit the logit
# of a served token may lie, as a share of the sequence's largest.
# SERVED_SAMPLES finished requests of at most SERVED_LEN tokens, and the
# LONGEST finished one of at most SERVED_LONG_LEN: seventeen chunks of
# the engine's and a slot held over as many steps, positions past 8,000
# under rotary and the gate's running sums.  The long one runs the same
# reference in smaller blocks of queries and of MLP columns, because the
# engine leaves 2.07 GiB of the chip free: compiled for a v5e ahead of
# time the mixer alone takes 0.90 GiB so at 8,704 tokens (1.29 in blocks
# of 256) and 2.57 GiB at the traffic's longest, 16,768 (PERF.md
# section 4).
SERVED_SAMPLES, SERVED_LEN, SERVED_MARGIN = 4, 2048, 2.5e-2
SERVED_LONG_LEN, LONG_QUERY_BLOCK, LONG_MLP_BLOCK = 8704, 128, 2176
MLP_BLOCK = 4352
CHECK_LAYERS = 3
SLOTS = {"chunked": 0, "beside": 5, "reused_slot": 5}
N_DECODE = {"chunked": 96, "beside": 96, "reused_slot": 64}


def _prompts(chunk: int) -> Dict[str, int]:
    """The rows' prompt lengths for an engine whose chunk is ``chunk``."""
    return {"chunked": 4 * chunk + chunk * 63 // 128, "beside": 40,
            "reused_slot": 50}


def _load_weights(cfg, seed: int):
    import jax

    from ray_tpu.models import brumby

    return brumby.init_params(jax.random.key(seed % (2**31 - 1)), cfg)


def _schedule(seqs: Dict[str, List[int]], n_prompt: Dict[str, int],
              chunk: int):
    """The check's steps: per step the rows (name, slot, start, length).
    ``beside`` begins at step 0 and ``chunked`` at step 1, so the
    prompt's chunks ride beside decode rows; ``reused_slot`` takes
    ``beside``'s slot the step after that sequence's last row."""
    pieces = {k: _pieces(n_prompt[k], len(seqs[k]),
                         chunk if k == "chunked" else len(seqs[k]))
              for k in seqs}
    begins = {"beside": 0, "chunked": 1,
              "reused_slot": len(pieces["beside"])}
    n_steps = max(begins[k] + len(pieces[k]) for k in seqs)
    return [[(k, SLOTS[k]) + pieces[k][s - begins[k]] for k in seqs
             if 0 <= s - begins[k] < len(pieces[k])]
            for s in range(n_steps)]


def _program_logits(cfg3, params, seqs, n_prompt, chunk: int,
                    state_dtype=None):
    """Run the check's schedule through the adapter's ragged step.
    Returns ({name: [(position, logits)]} for every row that ended at or
    after its prompt's last token, the first layer's state each slot's
    last sequence left).  ``state_dtype`` rounds the state to that
    precision between steps (``state_control``)."""
    import jax
    import numpy as np

    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
    from ray_tpu.serve.llm_engine import brumby_paged_adapter

    adapter = brumby_paged_adapter(cfg3)
    schedule = _schedule(seqs, n_prompt, chunk)
    n_slots = 8
    budget = -(-max(sum(r[3] for r in rows) for rows in schedule) // 8) * 8
    cache = adapter.init_cache(0, 1, n_slots)
    table = np.zeros((n_slots, 0), np.int32)
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
        if state_dtype is not None:
            cache = {k: v.astype(state_dtype).astype(v.dtype)
                     for k, v in cache.items()}
        for i, (name, _slot, start, n) in enumerate(rows):
            if start + n >= n_prompt[name]:
                got[name].append((start + n - 1,
                                  np.asarray(logits[i], np.float32)))
    states = {name: (np.asarray(cache["ret_s"][0, SLOTS[name]]),
                     np.asarray(cache["ret_z"][0, SLOTS[name]]))
              for name in ("chunked", "reused_slot")}
    return got, states


def _state_error(have, want, d: int) -> Dict[str, float]:
    """The program's (S [KVH, D', d], z [KVH, D']) of one slot against
    the reference's (S [KVH, D, d], z [KVH, D]); see STATE_TOLERANCE."""
    import numpy as np

    from ray_tpu.ops.power_retention import to_canonical

    out = {}
    for key, h, w in zip(("s", "z"), have, want):
        w = np.asarray(w, np.float64)
        errs = [np.linalg.norm(to_canonical(h[j], d) - w[j])
                / np.linalg.norm(w[j]) for j in range(w.shape[0])]
        out[key] = float(np.mean(errs))
    return out


def logits_check(cfg, config: Dict[str, Any], seed: int, *,
                 state_dtype=None) -> Dict[str, Any]:
    """Three layers at the configuration's widths through the engine's
    ragged step against the plain reference's full forward pass of each
    sequence: logits, not tokens (TOLERANCES), and the first layer's
    final state (STATE_TOLERANCE)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_brumby as ref

    hf = dict(config, num_hidden_layers=CHECK_LAYERS)
    cfg3 = dataclasses.replace(cfg, n_layers=CHECK_LAYERS)
    params = _load_weights(cfg3, seed)
    rng = np.random.default_rng(seed % (2**32))
    chunk = config["engine"]["prefill_chunk"]
    n_prompt = _prompts(chunk)
    seqs = {k: rng.integers(1, cfg.vocab_size,
                            n_prompt[k] + N_DECODE[k]).tolist()
            for k in n_prompt}
    got, states = _program_logits(cfg3, params, seqs, n_prompt, chunk,
                                  state_dtype)
    out: Dict[str, Any] = {"layers": CHECK_LAYERS, "ok": True}
    state_errs: Dict[str, Dict[str, float]] = {}
    with jax.default_matmul_precision("highest"):
        ref_params = ref.from_program_tree(params, hf)
        forward = jax.jit(lambda p, t: ref.forward_hidden(p, t, hf))
        for name, rows in got.items():
            hidden, want_state = forward(
                ref_params, jnp.asarray(seqs[name], jnp.int32))
            at = np.asarray([i for i, _g in rows])
            want = np.asarray(ref.logits_of(hidden[at], ref_params, hf))
            if name in states:
                state_errs[name] = _state_error(states[name], want_state,
                                                cfg.head_dim)
            scale = float(np.max(np.abs(want)))
            errs = [float(np.max(np.abs(g - want[r]))) / scale
                    for r, (_i, g) in enumerate(rows)]
            ok = bool(len(rows) == N_DECODE[name] + 1
                      and all(np.isfinite(g).all() for _i, g in rows)
                      and max(errs) <= TOLERANCES[name])
            out[name] = {"rel_err_prefill": errs[0],
                         "rel_err_decode": max(errs[1:]),
                         "tol": TOLERANCES[name], "ok": ok}
            out["ok"] = out["ok"] and ok
    worst = max((v for e in state_errs.values() for v in e.values()),
                default=float("inf"))
    ok = bool(len(state_errs) == 2 and worst <= STATE_TOLERANCE)
    out["ret_state"] = {"rel_err": state_errs, "tol": STATE_TOLERANCE,
                        "ok": ok}
    out["ok"] = out["ok"] and ok
    return out


def _reference_steps(config, query_block: int):
    """The reference's jitted pieces, one matrix group at a time: beside
    the engine there is no room for a float32 copy of a whole layer."""
    import jax

    from benchmarks.harness import reference_brumby as ref

    eps = float(config["rms_norm_eps"])
    return (jax.jit(lambda x, lp: x + ref.mixer(
                x, lp, config, query_block=query_block)[0]),
            jax.jit(lambda x, w: ref.rms_norm(x, w, eps)),
            jax.jit(lambda x, u, g, up, dn: x + ref.mlp_part(u, g, up, dn)))


def _reference_logits(steps, config, weights, head, toks, at,
                      mlp_block: int):
    """The reference's logits at positions ``at`` of ``toks`` at the
    configuration's full depth."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_brumby as ref

    mix, norm, part = steps
    cols = config["intermediate_size"]
    x = ref.embed(head, jnp.asarray(toks))
    for i in range(config["num_hidden_layers"]):
        x = mix(x, ref.mixer_from_program_tree(weights, config, i))
        u = norm(x, weights["ln_ff"][i])
        for c0 in range(0, cols, mlp_block):
            x = part(x, u, *ref.mlp_columns_from_program_tree(
                weights, i, c0, min(cols, c0 + mlp_block)))
    return np.asarray(ref.logits_of(x[at], head, config), np.float64)


def served_check(config: Dict[str, Any], weights, served) -> Dict[str, Any]:
    """What the engine served in the run against the plain reference at
    the configuration's full depth: SERVED_SAMPLES finished requests of
    at most SERVED_LEN tokens, spread evenly over the run's order of
    finishing, and the longest finished request of at most
    SERVED_LONG_LEN, each prompt plus answer through the reference one
    layer at a time.  Every served token has to be the reference's
    argmax after the tokens before it, or within SERVED_MARGIN of that
    logit as a share of the sequence's largest."""
    import jax
    import numpy as np

    from benchmarks.harness import reference_brumby as ref

    t0 = time.perf_counter()
    done = [(p, a) for p, a in served if a]
    fits = [pa for pa in done if len(pa[0]) + len(pa[1]) <= SERVED_LEN]
    longer = [pa for pa in done
              if SERVED_LEN < len(pa[0]) + len(pa[1]) <= SERVED_LONG_LEN]
    out: Dict[str, Any] = {"layers": config["num_hidden_layers"],
                           "finished": len(served), "requests": 0,
                           "tokens": 0, "longest": 0,
                           "margin": SERVED_MARGIN, "ok": False}
    if not fits or (len(fits) < len(done) and not longer):
        return out      # traffic with long requests has one compared
    picks = [fits[i] + (SERVED_LEN, ref.QUERY_BLOCK, MLP_BLOCK)
             for i in sorted({int(i) for i in np.linspace(
                 0, len(fits) - 1, SERVED_SAMPLES)})]
    if longer:
        picks.append(max(longer, key=lambda pa: len(pa[0]) + len(pa[1]))
                     + (SERVED_LONG_LEN, LONG_QUERY_BLOCK, LONG_MLP_BLOCK))
    short, exact, distinct = [], 0, set()
    with jax.default_matmul_precision("highest"):
        head = ref.head_from_program_tree(weights)
        steps = {block: _reference_steps(config, block)
                 for block in {pick[3] for pick in picks}}
        for p, a, length, query_block, mlp_block in picks:
            toks = np.zeros((length,), np.int32)    # one compiled length
            toks[:len(p) + len(a)] = list(p) + list(a)
            # the logits after token j - 1 chose token j
            logits = _reference_logits(
                steps[query_block], config, weights, head, toks,
                np.arange(len(p) - 1, len(p) + len(a) - 1), mlp_block)
            got = logits[np.arange(len(a)), np.asarray(a)]
            top = logits.max(-1)
            short += list((top - got) / np.abs(logits).max())
            exact += int(np.sum(top == got))
            distinct |= set(a)
    out.update(requests=len(picks), tokens=len(short),
               longest=max(len(p) + len(a) for p, a, *_ in picks),
               distinct_tokens=len(distinct),
               exact_share=exact / len(short),
               rel_short_max=float(max(short)),
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
        brumby_paged_adapter,
    )

    class BenchBrumbyServer(_jamba_server_class()):
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
                adapter_factory=brumby_paged_adapter)
            self._tracer = None
            self._rehearse = bool(spec.get("rehearse"))

        def state_control(self) -> Dict[str, Any]:
            """The check again with the retention state rounded to
            bfloat16 between its steps: its ``ret_state`` has to come
            out not ok.  ``chip_smoke.py``'s Brumby case asks for it; a
            run of the cell does not."""
            import jax.numpy as jnp

            return logits_check(self._cfg, self._config, self._seed,
                                state_dtype=jnp.bfloat16)

        def served_check(self) -> Dict[str, Any]:
            """After the window, the engine idle: see ``served_check``."""
            return served_check(self._config, self._weights, self._served)

    return BenchBrumbyServer


def run(ctx):
    if importlib.util.find_spec("ray_tpu.models.brumby") is None:
        raise SystemExit(
            f"benchmark: cell {ctx.cell} needs ray_tpu.models.brumby, "
            f"which this program does not have; no result")
    serve_jamba.server_class = server_class
    try:
        return serve_jamba.run(ctx)
    finally:
        serve_jamba.server_class = _jamba_server_class
