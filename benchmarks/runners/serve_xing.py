"""Runner for Xing4.0 configurations served through ``serve.run(LLMServer)``
with ``xing_paged_adapter``: latent attention over one page pool, routed
experts with no token dropped, a four-stream hyper-connection residual.

The drive is ``serve_jamba.run`` itself (chip check, deployment, warm-up,
``settle``, sweep, tracer, open loop, counters, the served check, the
record), which builds its server from its module's ``server_class``, set
to this file's for the length of the call, as ``serve_brumby`` does.
What is this file's own is what differs in the model: the weights, the
adapter, the two checks against the plain reference
(``harness/reference_xing.py``) with their controls (a planted fault
each limit has to refuse), and the experts' counters at the traced
window's two ends.
"""

from __future__ import annotations

import importlib.util
import time
from typing import Any, Dict, List

from benchmarks.runners import serve_jamba
from benchmarks.runners.common import CompileCounter
from benchmarks.runners.serve_jamba import _pieces

# taken before ``run`` sets the module's name to this file's own
_jamba_server_class = serve_jamba.server_class

# ``logits_check``: three layers (one dense, two routed) at the
# configuration's widths through the model's ragged step against the
# float32 reference's full forward pass of each sequence, logits as a
# share of the reference's largest, three rows:
#
# "chunked": slot 0, 700 tokens in chunks of 256 beside the other row's
# decode steps, then 96 decoded: the latent pages handed from chunk to
# chunk and to the decode rows, eleven pages deep.
# "beside": slot 5, 40 tokens whole, then 96 decoded while slot 0
# prefills and decodes: two rows live in one step, each with its pages.
# "reused_slot": slot 5 again, 50 whole and 64 decoded, under a block
# table that hands it the first request's pages in another order: stale
# pages under a new table.
#
# Routing: a top-4 is discontinuous, and the program's inputs to a router
# carry bfloat16's rounding of everything upstream (the logits' own
# readings: 2 to 3.6e-2 of the largest), which moves a score by up to
# 3e-2: the program routes 5 to 9% of (token, layer) pairs differently
# from the reference.  A differing pair is excused only where it is a
# near-tie by the reference's own scores: every expert the two choices
# do not share has its score within ROUTE_EPS of the reference's fourth
# largest (``reference_xing.swap_gap``), and only there does the
# reference run with the program's choice.  Anywhere else it keeps its
# own, the logits then differ by an expert's whole output (a reading of
# 0.24), and ``route`` is not ok: ``step_gap_max`` over ROUTE_EPS.  The
# share of pairs so excused is bounded too (STEP_MISMATCH_SHARE): a
# program whose choices were near-ties of the reference's everywhere and
# still its own on a quarter of the pairs is not this program.
# ``wrong_expert_control`` swaps one chosen expert of every 50th token
# for its neighbour and has to come out not ok by ROUTE_EPS.  Limits and
# readings: PERF.md section 4.
TOLERANCES = {"chunked": 5.0e-2, "beside": 5.0e-2, "reused_slot": 5.0e-2}
# one pair of limits for both checks, set from the deeper one's readings
# (``served_check``, seven layers: the rounding a router's inputs carry
# grows with the layers before it)
ROUTE_EPS = 6.0e-2
STEP_MISMATCH_SHARE = 0.25
# The router's own precision is held apart from that: the program's
# ``route`` and the reference's router on the SAME inputs (the
# reference's, rounded to the program's activations), as the root mean
# square of the scores' difference over tokens and experts, and the
# share of tokens whose choices differ.  ``route_control`` computes the
# scores in bfloat16 and has to come out NOT ok by both.
ROUTER_SCORE_RMS = 3.0e-5
ROUTER_MISMATCH_SHARE = 1.5e-3
# The first layer's latent rows of slot 0 after ``chunked`` against the
# reference's c | kr, relative 2-norm over the sequence.  The first
# layer, because its input is the embedding and nothing upstream blurs
# it.  ``cache_control`` rounds the pool to float8_e4m3fn between the
# check's steps, as a cache that kept it in less than bfloat16 would; it
# has to come out NOT ok by this limit.
LATENT_TOLERANCE = 1.0e-2
# ``served_check``: what the engine served in the window against the
# reference at the configuration's full depth, EVERY token: the served
# token's logit lies at most SERVED_MARGIN (a share of the sequence's
# largest logit) under the reference's largest.  The reference runs with
# the choices the ENGINE's steps made, read back from the sequence's own
# pages (``xing.token_log``: the step writes each token's experts beside
# its latent row) and excused by the same ROUTE_EPS rule, every token
# and routed layer: two compiled shapes of one step round differently at
# bfloat16's level, a top-4 amplifies that into another expert, and the
# reference's own routing then describes another function (30% of pairs
# by the fifth routed layer).  Sampled are finished requests whose pages
# still hold their log when the run ends (found by the ids and positions
# logged with them), the longest of at most SERVED_LONG_LEN among them.
# ``served_control`` plants two faults, each has to come out not ok:
# another request's answer under this request's prompt, and ONE token of
# one answer replaced (a wrong token out of one slot).
SERVED_SAMPLES, SERVED_LONG_LEN = 6, 4096
SERVED_MARGIN = 6.0e-2
SERVED_PAD = 2048       # padded lengths are multiples: two compiled shapes
CHECK_HF = {"num_hidden_layers": 3, "first_k_dense_replace": 1}
SLOTS = {"chunked": 0, "beside": 5, "reused_slot": 5}


def _lengths(chunk: int):
    """The rows' (prompt, decoded) lengths for an engine whose chunk is
    ``chunk``: 700 in chunks of 256 and 96, 96, 64 decoded at the cell's."""
    return ({"chunked": 2 * chunk + chunk * 47 // 64, "beside": 40,
             "reused_slot": 50},
            {"chunked": 3 * chunk // 8, "beside": 3 * chunk // 8,
             "reused_slot": chunk // 4})


def model_config(config: Dict[str, Any]):
    """``XingConfig`` from the published keys."""
    import jax.numpy as jnp

    from ray_tpu.models.xing import XingConfig

    dtype = getattr(jnp, config.get("torch_dtype", "bfloat16"))
    return XingConfig.from_published(config, dtype=dtype, param_dtype=dtype)


def _load_weights(cfg, seed: int):
    import jax

    from ray_tpu.models import xing

    return xing.init_params(jax.random.key(seed % (2**31 - 1)), cfg)


def _schedule(seqs: Dict[str, List[int]], chunk: int):
    """The check's steps: per step the rows (name, slot, start, length),
    as ``serve_jamba._schedule`` with this file's lengths."""
    n_prompt = _lengths(chunk)[0]
    pieces = {k: _pieces(n_prompt[k], len(seqs[k]),
                         chunk if k == "chunked" else len(seqs[k]))
              for k in seqs}
    begins = {"beside": 0, "chunked": 1,
              "reused_slot": len(pieces["beside"])}
    n_steps = max(begins[k] + len(pieces[k]) for k in seqs)
    return [[(k, SLOTS[k]) + pieces[k][s - begins[k]] for k in seqs
             if 0 <= s - begins[k] < len(pieces[k])]
            for s in range(n_steps)]


def _program_run(cfg3, params, seqs, chunk: int, page: int,
                 route_dtype=None, cache_dtype=None):
    """Run the check's schedule through the model's ragged step.
    Returns ({name: [(position, logits)]} for every row that ended at or
    after its prompt's last token, {name: choices [Lm, n, k]} of every
    token, slot 0's first-layer latent rows).  ``route_dtype`` computes
    the router in that precision (``route_control``); ``cache_dtype``
    rounds the pool to it between steps (``cache_control``)."""
    import jax
    import numpy as np

    from ray_tpu.models import xing
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

    schedule = _schedule(seqs, chunk)
    n_prompt = _lengths(chunk)[0]
    n_slots = 8
    budget = -(-max(sum(r[3] for r in rows) for rows in schedule) // 8) * 8
    maxp = -(-max(len(s) for s in seqs.values()) // page)
    cache = xing.init_cache(cfg3, n_slots * maxp, page)
    table = np.arange(n_slots * maxp, dtype=np.int32).reshape(n_slots, maxp)
    step = jax.jit(
        lambda p, *a: xing.ragged_step(p, *a[:-1], cfg3, a[-1],
                                       with_routes=True,
                                       route_dtype=route_dtype),
        donate_argnums=(8,))
    got: Dict[str, list] = {k: [] for k in seqs}
    chose = {k: np.zeros((cfg3.n_moe, len(seqs[k]), cfg3.top_k), np.int32)
             for k in seqs}
    for rows in schedule:
        if any(name == "reused_slot" and start == 0
               for name, _s, start, _n in rows):
            # the second request of the slot reads the first one's pages
            # one place on: its second page is the first one's third, stale
            table[SLOTS["reused_slot"]] = np.roll(
                table[SLOTS["reused_slot"]], -1)
        packed = [{"slot": slot, "start": start,
                   "tokens": seqs[name][start:start + n]}
                  for name, slot, start, n in rows]
        (ht, _m, _s, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(packed, budget, n_slots)
        logits, cache, routes = step(params, ht, pos, r_slot, r_start,
                                     r_len, r_off, table, cache)
        if cache_dtype is not None:
            cache = dict(cache, kv_c=cache["kv_c"].astype(cache_dtype)
                         .astype(cache["kv_c"].dtype))
        routes = np.asarray(routes)
        for i, (name, _slot, start, n) in enumerate(rows):
            off = int(r_off[i])
            chose[name][:, start:start + n] = routes[:, off:off + n]
            if start + n >= n_prompt[name]:
                got[name].append((start + n - 1,
                                  np.asarray(logits[i], np.float32)))
    n0 = len(seqs["chunked"])
    latent = np.asarray(cache["kv_c"][0, 0, table[SLOTS["chunked"]]]
                        .astype(np.float32)).reshape(maxp * page, -1)
    return got, chose, latent[:n0, :cfg3.latent_dim]


def _swap_one(choice, every: int):
    """``choice`` [Lm, n, k] with one expert of every ``every``-th token
    replaced by the smallest expert the token did not choose: a program
    that picks a wrong expert there (``wrong_expert_control``)."""
    import numpy as np

    out = choice.copy()
    k = choice.shape[-1]
    for j in range(choice.shape[0]):
        for t in range(0, choice.shape[1], every):
            spare = min(set(range(k + 1)) - set(choice[j, t].tolist()))
            out[j, t] = np.sort(np.append(choice[j, t, 1:], spare))
    return out


def logits_check(cfg, config: Dict[str, Any], seed: int, *,
                 route_dtype=None, cache_dtype=None,
                 swap_every: int = 0) -> Dict[str, Any]:
    """Three layers at the configuration's widths through the model's
    ragged step against the plain reference: logits (TOLERANCES), the
    routing (ROUTE_EPS, STEP_MISMATCH_SHARE; ROUTER_SCORE_RMS,
    ROUTER_MISMATCH_SHARE) and the first layer's latent pages
    (LATENT_TOLERANCE)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_xing as ref
    from ray_tpu.models import xing

    hf = dict(config, **CHECK_HF)
    cfg3 = dataclasses.replace(
        cfg, n_layers=hf["num_hidden_layers"],
        first_dense=hf["first_k_dense_replace"])
    params = _load_weights(cfg3, seed)
    rng = np.random.default_rng(seed % (2**32))
    eng = config["engine"]
    n_prompt, n_decode = _lengths(eng["prefill_chunk"])
    seqs = {k: rng.integers(1, cfg.vocab_size,
                            n_prompt[k] + n_decode[k]).tolist()
            for k in n_prompt}
    got, chose, latent = _program_run(
        cfg3, params, seqs, eng["prefill_chunk"], eng["page_size"],
        route_dtype, cache_dtype)
    if swap_every:
        chose = {k: _swap_one(v, swap_every) for k, v in chose.items()}
    out: Dict[str, Any] = {"layers": cfg3.n_layers, "ok": True}
    first = cfg3.first_dense
    mismatched, alone, pairs, gap_max, sq = 0, 0, 0, 0.0, 0.0
    head = ref.head_from_program_tree(params)

    # the two routers on the same inputs, rounded as the program's are
    def rounded(u):
        return u.astype(cfg3.dtype)

    router = jax.jit(lambda u, w, b: xing.route(
        rounded(u), w, b, cfg3, route_dtype)[:2])
    ref_router = jax.jit(lambda u, w, b: ref.route(
        rounded(u).astype(jnp.float32),
        {"router": w, "router_bias": b}, hf)[::2])
    with jax.default_matmul_precision("highest"):
        # one compiled length for the three: a causal model's positions
        # do not see what is padded on behind them
        n_pad = -(-max(len(t) for t in seqs.values()) // 128) * 128
        for name, rows in got.items():
            n = len(seqs[name])
            toks = np.zeros((n_pad,), np.int32)
            toks[:n] = seqs[name]
            theirs = np.tile(np.arange(cfg3.top_k, dtype=np.int32),
                             (cfg3.n_moe, n_pad, 1))
            theirs[:, :n] = chose[name]
            X, infos = ref.forward(
                params, toks, hf, route_eps=ROUTE_EPS,
                choices={first + j: theirs[j] for j in range(cfg3.n_moe)})
            for j, info in enumerate(infos[first:]):
                own = np.asarray(info["choice"])[:n]
                mismatched += int(np.any(own != chose[name][j],
                                         axis=-1).sum())
                pairs += n
                gap_max = max(gap_max,
                              float(np.max(np.asarray(info["gap"])[:n])))
                w, b = params["moe"]["router"][j], params["moe"]["bias"][j]
                scores, choice = router(info["router_in"], w, b)
                ref_scores, ref_choice = ref_router(info["router_in"], w, b)
                alone += int(np.any(np.asarray(choice)[:n]
                                    != np.asarray(ref_choice)[:n],
                                    axis=-1).sum())
                sq += float(np.sum((np.asarray(scores, np.float64)[:n]
                                    - np.asarray(ref_scores)[:n]) ** 2))
            if name == "chunked":
                want_lat = np.asarray(infos[0]["latent"], np.float64)[:n]
                lat_err = float(np.linalg.norm(latent - want_lat)
                                / np.linalg.norm(want_lat))
            at = np.asarray([i for i, _g in rows])
            want = np.asarray(ref.logits_of(X[at], head, hf))
            scale = float(np.max(np.abs(want)))
            errs = [float(np.max(np.abs(g - want[r]))) / scale
                    for r, (_i, g) in enumerate(rows)]
            ok = bool(len(rows) == n_decode[name] + 1
                      and all(np.isfinite(g).all() for _i, g in rows)
                      and max(errs) <= TOLERANCES[name])
            out[name] = {"rel_err_prefill": errs[0],
                         "rel_err_decode": max(errs[1:]),
                         "tol": TOLERANCES[name], "ok": ok}
            out["ok"] = out["ok"] and ok
    rms = (sq / pairs / cfg3.n_experts) ** 0.5
    ok = bool(gap_max <= ROUTE_EPS
              and mismatched / pairs <= STEP_MISMATCH_SHARE
              and rms <= ROUTER_SCORE_RMS
              and alone / pairs <= ROUTER_MISMATCH_SHARE)
    out["route"] = {"pairs": pairs,
                    "step_gap_max": gap_max, "eps": ROUTE_EPS,
                    "step_mismatch_share": mismatched / pairs,
                    "step_mismatch_tol": STEP_MISMATCH_SHARE,
                    "router_score_rms": rms, "tol": ROUTER_SCORE_RMS,
                    "router_mismatch_share": alone / pairs,
                    "router_mismatch_tol": ROUTER_MISMATCH_SHARE,
                    "ok": ok}
    out["ok"] = out["ok"] and ok
    ok = bool(lat_err <= LATENT_TOLERANCE)
    out["latent_pages"] = {"rel_err": lat_err, "tol": LATENT_TOLERANCE,
                           "ok": ok}
    out["ok"] = out["ok"] and ok
    return out


def logged_routes(log: Dict[str, Any], fed: List[int]):
    """The choices the engine's steps made for the tokens ``fed`` of one
    sequence (from position 0), ``[Lm, len(fed), k]``, read from the
    pages' log (``xing.token_log``); None where a page of the sequence
    no longer holds what it wrote (another sequence owns it since) or
    cannot be told from another."""
    import numpy as np

    ids, pos, routes = log["tokens"], log["pos"], log["routes"]
    page = ids.shape[1]
    first = {}
    for pg in range(ids.shape[0]):
        first.setdefault((int(pos[pg, 0]), int(ids[pg, 0])), []).append(pg)
    fed = np.asarray(fed, np.int64)
    out = []
    for p0 in range(0, len(fed), page):
        want = fed[p0:p0 + page]
        m = len(want)
        found = [pg for pg in first.get((p0, int(want[0])), ())
                 if np.array_equal(ids[pg, :m], want)
                 and np.array_equal(pos[pg, :m], np.arange(p0, p0 + m))]
        if len(found) != 1:
            return None
        out.append(routes[:, found[0], :, :m])
    return np.concatenate(out, axis=2).transpose(0, 2, 1).astype(np.int32)


def compare_served(config: Dict[str, Any], weights,
                   samples) -> Dict[str, Any]:
    """``samples`` [(prompt, answer, the engine's choices for all but the
    answer's last token)] through the reference at the configuration's
    full depth, one layer (one expert) at a time, with those choices
    under the ROUTE_EPS rule: every token's shortfall under the
    reference's largest logit, every (token, layer)'s gap."""
    import jax
    import numpy as np

    from benchmarks.harness import reference_xing as ref

    head = ref.head_from_program_tree(weights)
    first, k = config["first_k_dense_replace"], config["num_experts_per_tok"]
    short, exact, distinct = [], 0, set()
    gap_max, mismatched, pairs = 0.0, 0, 0
    with jax.default_matmul_precision("highest"):
        for p, a, routes in samples:
            n = len(p) + len(a)
            toks = np.zeros((-(-n // SERVED_PAD) * SERVED_PAD,), np.int32)
            toks[:n] = list(p) + list(a)
            # the last token was never fed: no choice of the engine's,
            # and no logits of the reference's are read there
            theirs = np.tile(np.arange(k, dtype=np.int32),
                             (routes.shape[0], len(toks), 1))
            theirs[:, :n - 1] = routes
            X, infos = ref.forward(
                weights, toks, config, route_eps=ROUTE_EPS,
                query_block=256,
                choices={first + j: theirs[j] for j in range(len(theirs))})
            for j, info in enumerate(infos[first:]):
                own = np.asarray(info["choice"])[:n - 1]
                mismatched += int(np.any(own != routes[j], axis=-1).sum())
                pairs += n - 1
                gap_max = max(gap_max, float(
                    np.max(np.asarray(info["gap"])[:n - 1])))
            # the logits after token j - 1 chose token j
            at = np.arange(len(p) - 1, n - 1)
            logits = np.asarray(ref.logits_of(X[at], head, config),
                                np.float64)
            got = logits[np.arange(len(a)), np.asarray(a)]
            top = logits.max(-1)
            short += list((top - got) / np.abs(logits).max())
            exact += int(np.sum(top == got))
            distinct |= set(a)
    worst = float(max(short))
    return {"requests": len(samples), "tokens": len(short),
            "longest": max(len(p) + len(a) for p, a, _r in samples),
            "distinct_tokens": len(distinct),
            "exact_share": exact / len(short),
            "rel_short_p90": float(np.percentile(short, 90)),
            "rel_short_max": worst, "margin": SERVED_MARGIN,
            "step_gap_max": gap_max, "eps": ROUTE_EPS,
            "step_mismatch_share": mismatched / max(pairs, 1),
            "step_mismatch_tol": STEP_MISMATCH_SHARE,
            "ok": bool(np.isfinite(short).all() and worst <= SERVED_MARGIN
                       and gap_max <= ROUTE_EPS
                       and mismatched <= STEP_MISMATCH_SHARE * pairs)}


def held_requests(served, log) -> List[tuple]:
    """The finished requests of at most SERVED_LONG_LEN tokens whose
    pages still hold their log: [(prompt, answer, choices)]."""
    held = []
    for p, a in served:
        if a and len(p) + len(a) <= SERVED_LONG_LEN:
            routes = logged_routes(log, list(p) + list(a)[:-1])
            if routes is not None:
                held.append((p, a, routes))
    return held


def served_check(config: Dict[str, Any], weights, served,
                 log) -> Dict[str, Any]:
    """What the engine served in the run against the plain reference:
    SERVED_SAMPLES of ``held_requests``, spread evenly over the run's
    order of finishing with the longest among them.  See SERVED_MARGIN."""
    import numpy as np

    t0 = time.perf_counter()
    held = held_requests(served, log)
    out: Dict[str, Any] = {"layers": config["num_hidden_layers"],
                           "finished": len(served), "held": len(held),
                           "requests": 0, "tokens": 0, "longest": 0,
                           "margin": SERVED_MARGIN, "ok": False}
    if not held:
        return out
    size = [len(p) + len(a) for p, a, _r in held]
    picks = sorted({int(i) for i in np.linspace(
        0, len(held) - 1, SERVED_SAMPLES - 1)} | {int(np.argmax(size))})
    out.update(compare_served(config, weights, [held[i] for i in picks]))
    out["seconds"] = time.perf_counter() - t0
    return out


def served_control(config: Dict[str, Any], weights, served,
                   log) -> Dict[str, Any]:
    """Two planted faults of the served path on the shortest held
    request, each through ``compare_served`` with the engine's own
    choices: ``other_answer`` (another request's answer under its
    prompt) and ``one_token`` (one token of its answer replaced).  Both
    have to come out not ok."""
    held = sorted(held_requests(served, log),
                  key=lambda s: len(s[0]) + len(s[1]))
    if len(held) < 2:
        return {"held": len(held)}
    (p, a, routes), other = held[0], list(held[1][1])
    swapped = (other * (len(a) // len(other) + 1))[:len(a)]
    one = list(a)
    one[len(a) // 2] = (one[len(a) // 2] + 1) % config["vocab_size"] or 1
    return {"held": len(held),
            "other_answer": compare_served(config, weights,
                                           [(p, swapped, routes)]),
            "one_token": compare_served(config, weights,
                                        [(p, one, routes)])}


def server_class():
    """Built in a function so that importing this module imports no
    JAX in the client."""
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMServer,
        xing_paged_adapter,
    )

    class BenchXingServer(_jamba_server_class()):
        def __init__(self, spec: Dict[str, Any]):
            self._compiled = CompileCounter()
            config, seed = spec["config"], spec["seed"]
            cfg = model_config(config)
            self._check = logits_check(cfg, config, seed)
            self._config, self._cfg, self._seed = config, cfg, seed
            self._served: List[tuple] = []      # (prompt, answer), finished
            self._moe_ends: List[Dict[str, Any]] = []

            def load():
                self._weights = _load_weights(cfg, seed)
                return self._weights

            LLMServer.__init__(
                self, cfg, EngineConfig(**config["engine"]), load,
                adapter_factory=xing_paged_adapter)
            self._tracer = None
            self._rehearse = bool(spec.get("rehearse"))

        def route_control(self) -> Dict[str, Any]:
            """The check again with the router computed in bfloat16: its
            ``route`` has to come out not ok.  ``chip_smoke.py``'s case
            asks for it; a run of the cell does not."""
            import jax.numpy as jnp

            return logits_check(self._cfg, self._config, self._seed,
                                route_dtype=jnp.bfloat16)

        def cache_control(self) -> Dict[str, Any]:
            """The check again with the latent pool rounded to
            float8_e4m3fn between its steps: its ``latent_pages`` has to
            come out not ok."""
            import jax.numpy as jnp

            return logits_check(self._cfg, self._config, self._seed,
                                cache_dtype=jnp.float8_e4m3fn)

        def wrong_expert_control(self) -> Dict[str, Any]:
            """The check again with one expert of every 50th token
            swapped for one the token did not choose: its ``route`` has
            to come out not ok (``step_gap_max`` over ROUTE_EPS)."""
            return logits_check(self._cfg, self._config, self._seed,
                                swap_every=50)

        def _token_log(self) -> Dict[str, Any]:
            from ray_tpu.models import xing

            return self.engine.read_cache(
                lambda cache: xing.token_log(cache, self._cfg),
                timeout_s=120.0)[1]

        def served_check(self) -> Dict[str, Any]:
            """After the window, the engine idle: see ``served_check``.
            ``peak_bytes``: the device's peak before and after it (the
            run's ``memory_peak_bytes`` is read before)."""
            import jax

            def peak():
                stats = jax.local_devices()[0].memory_stats() or {}
                return stats.get("peak_bytes_in_use")

            before = peak()
            out = served_check(self._config, self._weights, self._served,
                               self._token_log())
            return dict(out, peak_bytes=[before, peak()])

        def served_control(self) -> Dict[str, Any]:
            return served_control(self._config, self._weights,
                                  self._served, self._token_log())

        def _moe_end(self) -> Dict[str, Any]:
            counters = dict(self.engine.stats()["model_counters"])
            return {"steps": counters.pop("step"), **counters}

        def trace_start(self, trace_dir: str) -> bool:
            self._moe_ends = [self._moe_end()]
            return super().trace_start(trace_dir)

        def trace_stop(self) -> bool:
            # before the profiler stops: writing the trace out takes tens
            # of seconds here, and the engine serves on through them
            self._moe_ends.append(self._moe_end())
            return super().trace_stop()

        def trace_reduce(self):
            """The reduced trace, with the experts' counters at the traced
            window's two ends beside it (``xing_spans`` reads them)."""
            trace = super().trace_reduce()
            if trace is not None and len(self._moe_ends) == 2:
                trace["model_counters"] = self._moe_ends
            return trace

        def counters(self) -> Dict[str, Any]:
            out = super().counters()
            out.pop("state_cache", None)
            out["model_counters"] = self.engine.stats().get("model_counters")
            return out

    return BenchXingServer


def run(ctx):
    if importlib.util.find_spec("ray_tpu.models.xing") is None:
        raise SystemExit(
            f"benchmark: cell {ctx.cell} needs ray_tpu.models.xing, "
            f"which this program does not have; no result")
    serve_jamba.server_class = server_class
    try:
        return serve_jamba.run(ctx)
    finally:
        serve_jamba.server_class = _jamba_server_class
