"""Runner for MiniCPM-SALA configurations served through
``serve.run(LLMServer)`` with ``sala_paged_adapter``: lightning (linear
attention) layers whose matrix state lives by slot beside the paged KV
and the paged compressed keys of the block-sparse layers.

The drive is ``serve_jamba.run`` itself, which builds its server from
its module's ``server_class``, set to this file's for the length of the
call (as ``serve_brumby`` does).  What is this file's own is what
differs in the model: the weights, the adapter, and the two comparisons
with the plain reference (``harness/reference_sala.py``) that decide
``correct``: three layers through the model's step before the engine
takes the memory, logits, a state and the selections with the three
controls (``logits_check``), and after the window what the ENGINE
served at the configuration's sixteen layers (``served_check``).
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import sys
import time
from typing import Any, Dict, List, Optional

from benchmarks.runners import serve_jamba, serve_llm
from benchmarks.runners.common import CompileCounter
from benchmarks.runners.serve_jamba import _pieces

_jamba_server_class = serve_jamba.server_class

# ``logits_check``: three layers (lightning, minicpm4, lightning: the
# published positions 21 to 23) at the configuration's widths through
# the model's ragged step, the engine's two shapes (a step with a chunk
# at the token budget, a step of decode rows alone at round8(slots)),
# against the float32 reference's full forward pass of each sequence:
#
# "beside": slot 5, 8,250 tokens in chunks of 512, then 48 decoded: its
# last chunk crosses ``dense_len`` inside the chunk, and its decode rows
# ride beside the other row's chunks.
# "long": slot 0, 16,448 tokens in chunks of 512 beside those decode
# rows, then 32 decoded: past 16,384 positions, every chunk past the
# sixteenth and every decoded token selects 64 of up to 258 blocks.
# "reused_slot": slot 5 again once "beside" has ended, 600 tokens and 16
# decoded: its first row has ``row_start == 0``, which is what zeroes
# the slot's states, and its pages are the first request's, whose
# compressed keys it must not read.
#
# Held beside the logits (a logit check alone cannot see a small
# substitution: PERF.md section 7): the first lightning layer's state
# after each sequence's last token, and the sparse layer's selected
# pages of every token.  A top-64 is as discontinuous as a top-8: the
# reference attends as the program did only for a query whose differing
# blocks all have the reference's own score within SEL_EPS (relative) of
# its own 64th (``reference_sala.minicpm4``); any other query keeps the
# reference's own selection, counts under ``kept`` and fails
# ``selection`` with the swap, its two scores and their gap named.
# Limits and readings (my chip runs, PR 41; PERF.md section 4):
#
# TOLERANCES, logits as a share of the reference's largest: the program
# (bfloat16 weights and activations; float32 state, scores, norms and
# softmax) reads 0.34e-2 to 0.57e-2 over the three rows of eleven seeds,
# prompt's end and decoded tokens alike: bfloat16's rounding of the
# activations over three layers.  The controls on the long row: every
# position attended 1.83e-2 to 2.31e-2, the forced blocks alone 1.82e-2
# to 2.36e-2, one decay for all heads 0.21 to 0.25; the reference
# computed in bfloat16 (state and selection scores too) 4.4e-2 to 22e-2.
# The limit is 1.75 times the program's largest and 1.8 times under the
# smallest control.
TOLERANCES = {"long": 1.0e-2, "beside": 1.0e-2, "reused_slot": 1.0e-2}
# STATE_TOLERANCE, the first lightning layer's state after a sequence's
# last token, mean over the heads of the relative Frobenius distance:
# the program 0.405e-2 to 0.420e-2 (33 readings; the keys and values
# it sums are bfloat16 where the reference's are float32), the reference
# with its state in bfloat16 11e-2 to 73e-2 (it rounds after every one
# of up to 16,480 tokens).  2.4 times the first, 11 times under the
# second.
STATE_TOLERANCE = 1.0e-2
# SEL_EPS, how far (relative) a differing block's own score may lie from
# the reference's 64th for the program's choice to stand: the largest
# over a sequence 0.11e-2 to 0.36e-2 (22 readings past ``dense_len``;
# no query kept the reference's own selection).  SEL_MISMATCH_SHARE, the
# selected blocks that differ over those selected: 0.34e-2 to 0.59e-2.
SEL_EPS = 1.0e-2
SEL_MISMATCH_SHARE = 2.0e-2
CHECK_HF = {"num_hidden_layers": 3, "first_layer": 21,
            "mixer_types": ["lightning-attn", "minicpm4", "lightning-attn"]}
CHECK_PLAN = {"chunk": 512, "slots": 8,
              "rows": {"beside": (5, 8250, 48), "long": (0, 16448, 32),
                       "reused_slot": (5, 600, 16)}}

# ``served_check``: requests the engine finished in the run, prompt plus
# answer through the reference at the configuration's FULL depth on the
# engine's own weights: one that passed ``past`` positions (the shortest
# such, so that the reference fits beside the engine: ``length`` is 16,384
# + the longest answer) and the last other one to finish.  Every served
# token has to be the reference's argmax after the tokens before it, or
# within SERVED_MARGIN of that logit as a share of the largest at the
# answer's positions.  The engine's sixteen-layer step in bfloat16, its
# own selections and all, picks the reference's argmax for 94.9% to 97.8%
# of tokens and otherwise one at most 0.62e-2 to 1.42e-2 under it (21
# runs of the cell, 929 tokens each: the 16,607- and the 8,898-position
# request of the replayed schedule; my chip runs, PR 41, PERF.md section
# 4); another request's token at the same place reads 0.74 to 0.90 in a
# run's median.  The limit is 3.5 times the first's largest and 15 times
# under the second's smallest.  It is a limit on TOKENS: what a precision
# below does to logits, state and selections ``logits_check`` holds.
SERVED_PLAN = {"past": 16384, "length": 17408, "answer": 1024}
SERVED_MARGIN = 5.0e-2
# queries a sparse layer of the served check's reference scores at once:
# its float32 scores are [64, 32, 17408], 0.14 GB a copy
SERVED_QUERY_BLOCK = 64


def model_config(config: Dict[str, Any]):
    """``SalaConfig`` from the published keys."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import SalaConfig
    from ray_tpu.ops.block_sparse_attention import BlockSparse

    from benchmarks.harness import reference_sala

    c = config
    sc = reference_sala.sparse_config(c)
    dtype = getattr(jnp, c.get("torch_dtype", "bfloat16"))
    kw = dict(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mlp_dim=c["intermediate_size"],
        mixer_types=tuple(c["mixer_types"]),
        first_layer=int(c.get("first_layer", 0)),
        published_layers=reference_sala.published_depth(c),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        scale_emb=float(c["scale_emb"]), scale_depth=float(c["scale_depth"]),
        dim_model_base=int(c["dim_model_base"]),
        sparse=BlockSparse(
            block=sc["block_size"], kernel=sc["kernel_size"],
            stride=sc["kernel_stride"], topk=sc["topk"],
            window=sc["window_size"], init_blocks=sc["init_blocks"],
            dense_len=sc["dense_len"]),
        dtype=dtype, param_dtype=dtype)
    assert len(kw["mixer_types"]) == c["num_hidden_layers"]
    assert c["lightning_nh"] == c["lightning_nkv"] == kw["n_heads"]
    return SalaConfig(**kw)


def _load_weights(cfg, seed: int):
    import jax

    from ray_tpu.models import minicpm_sala

    return minicpm_sala.init_params(jax.random.key(seed % (2**31 - 1)), cfg)


def _schedule(plan: Dict[str, Any]):
    """The check's steps: per step the rows (name, slot, start, length).
    ``beside`` begins at step 0; ``long`` once ``beside``'s prompt is in,
    so its chunks ride beside decode rows; ``reused_slot`` takes
    ``beside``'s slot once that sequence has ended and ``long``'s prompt
    is in (a step holds one chunk), beside ``long``'s decode rows."""
    rows = plan["rows"]
    pieces = {k: _pieces(n, n + d, plan["chunk"])
              for k, (_slot, n, d) in rows.items()}
    chunks = {k: len(pieces[k]) - rows[k][2] for k in rows}
    begins = {"beside": 0, "long": chunks["beside"]}
    begins["reused_slot"] = max(len(pieces["beside"]),
                                begins["long"] + chunks["long"])
    n_steps = max(begins[k] + len(pieces[k]) for k in rows)
    return [[(k, rows[k][0]) + pieces[k][s - begins[k]] for k in rows
             if 0 <= s - begins[k] < len(pieces[k])]
            for s in range(n_steps)]


def program_run(cfg3, params, seqs, plan: Dict[str, Any], page: int):
    """Run the check's schedule through the adapter's ragged step (with
    the model's ``probe``, which adds the selections to what it
    returns).  Returns ({name: [(position, logits)]} for every row that
    ended at or after its prompt's last token, {name: first lightning
    layer's state of the sequence's slot after its last row}, {name:
    the sparse layers' selections bool[La, n, KVH, maxp]}, the counter
    leaf)."""
    import jax
    import numpy as np

    from ray_tpu.models import minicpm_sala
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
    from ray_tpu.serve.llm_engine import ragged_step_shapes

    rows_of = plan["rows"]
    schedule = _schedule(plan)
    n_slots = plan["slots"]
    small, big = ragged_step_shapes(plan["chunk"] + n_slots, n_slots)
    maxp = -(-max(len(s) for s in seqs.values()) // page)
    cache = minicpm_sala.init_cache(cfg3, n_slots * maxp, page, n_slots)
    # slots take their pages in descending order: a table no identity
    table = np.arange(n_slots * maxp, dtype=np.int32)[::-1].reshape(
        n_slots, maxp).copy()
    step = jax.jit(
        lambda p, t, pos, a, b, c, d, bt, cache: minicpm_sala.ragged_step(
            p, t, pos, a, b, c, d, bt, cfg3, cache, probe=True),
        donate_argnums=(8,))
    La = cfg3.layer_kinds().count(minicpm_sala.SPARSE)
    got: Dict[str, list] = {k: [] for k in seqs}
    states: Dict[str, Any] = {}
    sels = {k: np.zeros((La, len(s), cfg3.n_kv_heads, maxp), bool)
            for k, s in seqs.items()}
    for rows in schedule:
        packed = [{"slot": slot, "start": start,
                   "tokens": seqs[name][start:start + n]}
                  for name, slot, start, n in rows]
        budget = small if all(n == 1 for *_x, n in rows) else big
        (ht, _m, _s, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(packed, budget, n_slots)
        logits, cache, picked = step(params, ht, pos, r_slot, r_start,
                                     r_len, r_off, table, cache)
        picked = np.asarray(picked)
        for i, (name, slot, start, n) in enumerate(rows):
            off = int(r_off[i])
            sels[name][:, start:start + n] = picked[:, off:off + n]
            if start + n >= rows_of[name][1]:
                got[name].append((start + n - 1,
                                  np.asarray(logits[i], np.float32)))
            if start + n == len(seqs[name]):
                states[name] = np.asarray(cache["lin_s"][0, slot])
    return got, states, sels, np.asarray(cache["sel_pages"])


def _state_error(have, ref) -> float:
    """Mean over the heads of the relative Frobenius distance."""
    import numpy as np

    have, ref = (np.asarray(a, np.float64) for a in (have, ref))
    return float(np.mean(np.linalg.norm(have - ref, axis=(1, 2))
                         / np.linalg.norm(ref, axis=(1, 2))))


def compare(hf: Dict[str, Any], params, seqs, plan, got, states, sels,
            *, controls=(), dtype=None) -> Dict[str, Any]:
    """The program's run of the check against the reference's full
    forward pass of each sequence (see the limits above).  ``controls``
    names the reference's controls to run on the longest sequence, each
    of which has to come out refused.  ``dtype`` computes the reference
    in that precision instead and compares IT with the float32
    reference: the reading from the precision below (PERF.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_sala as ref

    out: Dict[str, Any] = {"layers": hf["num_hidden_layers"], "ok": True}
    rows_of = plan["rows"]
    nb = lambda n: -(-n // ref.sparse_config(hf)["block_size"])  # noqa: E731

    def run(name, control=None, selections=None, dt=jnp.float32):
        n_prompt = rows_of[name][1]
        fwd = jax.jit(lambda p, t, s: ref.forward(
            p, t, hf, selections=s, sel_eps=SEL_EPS, control=control,
            logits_from=n_prompt - 1, dtype=dt))
        toks = jnp.asarray(seqs[name], jnp.int32)
        return jax.device_get(fwd(params, toks, selections))

    def errs_of(want, rows):
        scale = float(np.max(np.abs(want)))
        first = rows[0][0]
        return [float(np.max(np.abs(g - want[i - first]))) / scale
                for i, g in rows]

    with jax.default_matmul_precision("highest"):
        state_errs: Dict[str, float] = {}
        selection: Dict[str, Any] = {}
        for name, rows in got.items():
            n = len(seqs[name])
            sel = jnp.asarray(sels[name][:, :, :, :nb(n)])
            want = run(name, selections=sel)
            state = states[name]
            if dtype is not None:       # the reference's own lower reading
                low = run(name, dt=dtype)
                rows = [(rows_of[name][1] - 1 + i, row)
                        for i, row in enumerate(low["logits"])]
                state = low["states"][0]
            errs = errs_of(want["logits"], rows)
            ok = bool(len(rows) == rows_of[name][2] + 1
                      and all(np.isfinite(g).all() for _i, g in rows)
                      and max(errs) <= TOLERANCES[name])
            out[name] = {"rel_err_prefill": errs[0],
                         "rel_err_decode": max(errs[1:]),
                         "tol": TOLERANCES[name], "ok": ok}
            out["ok"] = out["ok"] and ok
            state_errs[name] = _state_error(state, want["states"][0])
            info = want["sparse"][0]
            selection[name] = {
                "mismatch_share": float(info["mismatch_share"]),
                "gap_max": float(info["gap_max"]),
                "kept": int(info["kept"]),
                # block, scores and their gap, where a block differed
                "swap": ({k: float(v) for k, v in info["swap"].items()}
                         if float(info["gap_max"]) > 0 else None)}
        ok = bool(max(state_errs.values()) <= STATE_TOLERANCE)
        out["lin_state"] = {"rel_err": state_errs, "tol": STATE_TOLERANCE,
                            "ok": ok}
        out["ok"] = out["ok"] and ok
        ok = bool(dtype is not None or all(
            s["kept"] == 0 and s["mismatch_share"] <= SEL_MISMATCH_SHARE
            for s in selection.values()))
        out["selection"] = dict(selection, eps=SEL_EPS,
                                share_tol=SEL_MISMATCH_SHARE, ok=ok)
        out["ok"] = out["ok"] and ok
        longest = max(seqs, key=lambda k: len(seqs[k]))
        for control in controls:
            want = run(longest, control=control)
            err = max(errs_of(want["logits"], got[longest]))
            out[control] = {"rel_err": err, "tol": TOLERANCES[longest],
                            "refused": bool(err > TOLERANCES[longest])}
            out["ok"] = out["ok"] and out[control]["refused"]
    return out


def program_side(cfg, config: Dict[str, Any], seed: int, *,
                 plan: Optional[Dict[str, Any]] = None,
                 check_hf: Optional[Dict[str, Any]] = None):
    """The check's program side: the configuration cut to the check's
    layers, its weights and sequences from the seed, and what the
    model's ragged step made of them (``program_run``)."""
    import numpy as np

    plan = plan or CHECK_PLAN
    page = config["engine"].get("page_size", serve_llm.PAGE_DEFAULT)
    hf = dict(config, **(CHECK_HF if check_hf is None else check_hf))
    cfg3 = model_config(hf)
    params = _load_weights(cfg3, seed)
    rng = np.random.default_rng(seed % (2**32))
    seqs = {k: rng.integers(1, cfg.vocab_size, n + d).tolist()
            for k, (_slot, n, d) in plan["rows"].items()}
    return (hf, params, seqs, plan) + program_run(cfg3, params, seqs, plan,
                                                  page)


def logits_check(cfg, config: Dict[str, Any], seed: int, *,
                 plan: Optional[Dict[str, Any]] = None,
                 check_hf: Optional[Dict[str, Any]] = None,
                 controls=None, dtype=None) -> Dict[str, Any]:
    """Three layers at the configuration's widths through the model's
    ragged step against the plain reference, with the three controls."""
    from benchmarks.harness import reference_sala

    t0 = time.perf_counter()
    *side, pages = program_side(cfg, config, seed, plan=plan,
                                check_hf=check_hf)
    out = compare(*side, controls=(reference_sala.CONTROLS
                                   if controls is None else controls),
                  dtype=dtype)
    out["sel_pages"] = [int(x) for x in pages]
    out["seconds"] = time.perf_counter() - t0
    return out


def _served_picks(served, plan: Dict[str, Any]) -> List[tuple]:
    """Which finished requests the served check replays: the shortest
    that passed ``past`` positions, then the last other to finish."""
    fits = [(p, a) for p, a in served
            if a and len(p) + len(a) <= plan["length"]
            and len(a) <= plan["answer"]]
    long = sorted((pa for pa in fits
                   if len(pa[0]) + len(pa[1]) > plan["past"]),
                  key=lambda pa: len(pa[0]) + len(pa[1]))[:1]
    return long + [pa for pa in fits if pa not in long][-1:]


def served_programs(config: Dict[str, Any], plan: Dict[str, Any]):
    """The served check's reference as compiled pieces, each taking the
    engine's own weight tree: ``embed(tree, tokens)``, ``layer[kind](x,
    tree, i, j, l_pub)`` (layer ``i``, the ``j``-th of its kind, at
    published position ``l_pub``: all traced, so one program a kind; its
    float32 copy of the layer's weights lives inside the call, ``x`` is
    donated) and ``readings(x, tree, start, nxt, other)`` over the
    ``answer`` positions from ``start``: the largest logit, the logits of
    the tokens ``nxt`` and ``other``, the largest magnitude."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import reference_sala as ref

    A, f32 = plan["answer"], jnp.float32

    @jax.jit
    def embed(tree, toks):
        return ref.embed(toks, tree, config, f32)

    def layer(kind):
        return jax.jit(lambda x, tree, i, j, l_pub: ref.layer(
            x, ref.layer_slice(tree, kind, i, j, f32), kind, config, l_pub,
            query_block=SERVED_QUERY_BLOCK)[0], donate_argnums=(0,))

    @jax.jit
    def readings(x, tree, start, nxt, other):
        # the logits after token j - 1 chose token j
        xs = jax.lax.dynamic_slice(jnp.pad(x, ((0, A), (0, 0))),
                                   (start, 0), (A, x.shape[1]))
        logits = ref.logits_of(xs, tree, config)               # [A, V]
        at = lambda t: jnp.take_along_axis(  # noqa: E731
            logits, t[:, None], -1)[:, 0]
        return (logits.max(-1), at(nxt), at(other), jnp.abs(logits).max(-1))

    return embed, {k: layer(k) for k in set(ref.layer_kinds(config))}, readings


def served_check(config: Dict[str, Any], weights, served,
                 plan: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """What the engine served in the run against the plain reference at
    the configuration's full depth (see SERVED_PLAN): each picked
    request's prompt plus answer through ``reference_sala``, float32, one
    layer at a time on the engine's own weights, the sparse layers'
    queries in blocks, the reference's OWN selections."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import reference_sala as ref

    t0 = time.perf_counter()
    plan = dict(SERVED_PLAN, **(plan or {}))
    picks = _served_picks(served, plan)
    totals = [len(p) + len(a) for p, a in picks]
    out: Dict[str, Any] = {"layers": config["num_hidden_layers"],
                           "finished": len(served), "requests": len(picks),
                           "positions": totals, "past": plan["past"],
                           "margin": SERVED_MARGIN, "tokens": 0, "ok": False}
    if len(picks) < 2 or max(totals) <= plan["past"]:
        return out
    A = plan["answer"]
    n_pad = -(-max(totals) // 1024) * 1024
    kinds = ref.layer_kinds(config)
    embed, layer, readings = served_programs(config, plan)
    short, control, exact = [], [], 0
    with jax.default_matmul_precision("highest"):
        for r, (p, a) in enumerate(picks):
            toks = np.zeros((n_pad,), np.int32)
            toks[:len(p) + len(a)] = list(p) + list(a)
            # the control: at each served token's place, the token the
            # next picked request was given at the same point of its answer
            a2 = picks[(r + 1) % len(picks)][1]
            nxt, other = np.zeros((2, A), np.int32)
            nxt[:len(a)] = a
            other[:len(a)] = [a2[k % len(a2)] for k in range(len(a))]
            x = embed(weights, jnp.asarray(toks))
            for i, kind in enumerate(kinds):
                x = layer[kind](x, weights, i, kinds[:i].count(kind),
                                ref.published_position(config, i))
            top, got, swapped, absmax = (
                np.asarray(v, np.float64)[:len(a)] for v in readings(
                    x, weights, len(p) - 1, jnp.asarray(nxt),
                    jnp.asarray(other)))
            del x
            scale = absmax.max()
            short += list((top - got) / scale)
            control += list((top - swapped) / scale)
            exact += int(np.sum(top == got))
    out.update(tokens=len(short), exact_share=exact / len(short),
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
        sala_paged_adapter,
    )

    class BenchSalaServer(_jamba_server_class()):
        def __init__(self, spec: Dict[str, Any]):
            self._compiled = CompileCounter()
            config, seed = spec["config"], spec["seed"]
            cfg = model_config(config)
            # a smoke at toy sizes brings a plan of its own
            self._check = logits_check(cfg, config, seed,
                                       plan=config.get("check_plan"),
                                       check_hf=config.get("check_hf"))
            self._config, self._cfg, self._seed = config, cfg, seed
            self._served: List[tuple] = []      # (prompt, answer), finished
            # pool pages the rows of ONE token read, by the host's count
            # of every step packed (``walk_page_count``, which
            # ``llm.pack.grid_cells`` and so ``sparse_walk_roofline_share``
            # go by): ``served_check`` holds it to the device's own
            self._host_pages = 0

            def load():
                self._weights = _load_weights(cfg, seed)
                return self._weights

            def adapter(cfg):
                from ray_tpu.ops.block_sparse_attention import walk_page_count

                plain = sala_paged_adapter(cfg)
                layers = cfg.layer_kinds().count("minicpm4")

                def grid_cells(row_start, row_len, maxp, page, lora):
                    ones = [s for s, n in zip(row_start, row_len) if n == 1]
                    self._host_pages += layers * walk_page_count(
                        ones, [1] * len(ones), cfg.n_kv_heads, cfg.sparse,
                        page)
                    return plain.ragged_grid_cells(row_start, row_len, maxp,
                                                   page, lora)

                return dataclasses.replace(plain,
                                           ragged_grid_cells=grid_cells)

            LLMServer.__init__(
                self, cfg, EngineConfig(**config["engine"]), load,
                adapter_factory=adapter)
            self._tracer = None
            self._rehearse = bool(spec.get("rehearse"))

        def served_check(self) -> Dict[str, Any]:
            """After the window, the engine idle: ``served_check`` on
            what it served, and the device's count of the pages its rows
            of one token read (``sel_pages[0]``) against the host's count
            of the same steps."""
            out = served_check(self._config, self._weights, self._served,
                               self._config.get("served_plan"))
            counters = self.engine.stats().get("model_counters") or {}
            device = [int(n) for n in counters.get("sel_pages", ())]
            out["walk_pages"] = {"device": device, "host": self._host_pages,
                                 "ok": bool(device
                                            and device[0] == self._host_pages)}
            out["ok"] = bool(out["ok"] and out["walk_pages"]["ok"])
            return out

        def counters(self) -> Dict[str, Any]:
            out = super().counters()
            out["model_counters"] = self.engine.stats().get("model_counters")
            return out

    return BenchSalaServer


def main(argv=None) -> int:
    """The builder's runs on the chip that are no cell's: ``--check``
    (the three-layer comparison with its controls, alone) and
    ``--readings`` (the reference in bfloat16 against itself in float32:
    the reading from the precision below)."""
    ap = argparse.ArgumentParser(prog="python3 -m benchmarks.runners.serve_sala")
    ap.add_argument("--config",
                    default="benchmarks/configs/minicpm_sala_pp2.json")
    ap.add_argument("--seed", type=int, default=2100004101)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--readings", action="store_true")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    cfg = model_config(config)
    if args.check:
        print(json.dumps({"check": logits_check(cfg, config, args.seed)}),
              flush=True)
    if args.readings:
        import jax.numpy as jnp

        print(json.dumps({"readings_bfloat16_reference": logits_check(
            cfg, config, args.seed, controls=(), dtype=jnp.bfloat16)}),
            flush=True)
    return 0


def run(ctx):
    if importlib.util.find_spec("ray_tpu.models.minicpm_sala") is None:
        raise SystemExit(
            f"benchmark: cell {ctx.cell} needs ray_tpu.models.minicpm_sala, "
            f"which this program does not have; no config of it can run; "
            f"no result")
    serve_jamba.server_class = server_class
    try:
        return serve_jamba.run(ctx)
    finally:
        serve_jamba.server_class = _jamba_server_class


if __name__ == "__main__":
    sys.exit(main())
