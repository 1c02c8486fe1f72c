#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python3 chip_smoke.py            # all phases, one after another

Drives both hot paths once through the entry points a user calls, at the
full width of the models the benchmark uses (depth cut where stated,
weights random from a seed), and checks what comes out against the
float32 ``jax.numpy`` references the repository already holds:

    kernels        every Pallas kernel, compiled by Mosaic (never the
                   interpreter), against its reference
    train          ``JaxTrainer.fit`` on bench.BENCH_CFG (319M), B=8 S=2048,
                   5 steps on one fixed batch: loss near ln(V), falling
    serve          Llama-3-8B widths at full depth, int8 weights + int8 KV,
                   ragged step, through ``serve.run`` and a handle: 8 of 8
                   requests answered, the replica on the chip and the
                   caller off it, prefill logits against ``llama.forward``
    engine_legacy  ``LLMEngine`` in-process on the two-program path (319M)
    multichip      only with four or more devices: train at fsdp=4, serve
                   at tp=4 with a 512-token prompt, one-chip workers

A chip belongs to one process at a time, so this parent never imports
JAX and runs each phase as a child in turn; the chip is free between
phases.  Each child pins ``jax_platforms`` to ``tpu`` before first use,
so a missing or busy chip raises instead of landing on the CPU.  Any
child's non-zero exit is this script's non-zero exit and no result is
printed.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Numbers printed on the way are smoke timings, not measurements: they go
in no table.  tests/test_chip_smoke.py runs the same phase functions at
toy widths on the CPU, with the platform stated explicitly.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

PHASES = ("kernels", "train", "serve", "engine_legacy", "multichip")
REPORT_TAG = "CHIP_SMOKE_REPORT "
BUDGET_S = 1150.0          # the contract allows 1200, compilation included
PAGE = 64


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# shared by the phases (children only: everything below imports JAX lazily)
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds this process spent in the XLA backend compiler (or reading
    the persistent cache in its place), and how often the cache hit."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> dict:
        return {"compile_s": round(self.seconds, 1),
                "cache_hits": self.hits, "cache_misses": self.misses}


def require_platform(platform: str) -> list:
    """The devices a phase runs on; a phase never reports success from
    a platform other than the one its caller stated."""
    import jax

    from ray_tpu.ops import platform as ops_platform

    devices = jax.devices()
    if devices[0].platform != platform:
        raise RuntimeError(
            f"phase was told platform={platform!r} but JAX computes on "
            f"{devices[0].platform!r}")
    if platform == "tpu" and ops_platform.interpret_mode():
        raise RuntimeError("on a TPU the Pallas kernels must be compiled "
                           "by Mosaic, but interpret_mode() is True")
    return devices


def check_close(name: str, got, want, tol: float, *, what="kernel") -> float:
    """Print ``got`` against the float32 reference ``want`` and fail
    past ``tol``, which is relative to the reference's largest value."""
    import numpy as np

    from ray_tpu.ops import platform as ops_platform

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite values")
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want)))
    how = "interpreted" if ops_platform.interpret_mode() else "compiled"
    log(f"  {what}={name} {how} max_abs_err={err:.3e} ref_max={scale:.3e} "
        f"rel={err / scale:.2e} tol={tol:.0e}")
    if err > tol * scale:
        raise AssertionError(
            f"{name}: error {err:.3e} exceeds {tol:.0e} of {scale:.3e}")
    return err / scale


def peak_hbm(devices) -> list:
    """peak_bytes_in_use per device (None where the backend has no
    memory_stats, i.e. the CPU)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def llama3_8b_int8():
    """bench.py's 8B serving cell (published Llama-3-8B widths, full
    depth, int8 KV pages; the weights are load_int8_params') on the
    unfused path: the fused megakernel has its own check in
    ``kernels``."""
    import bench

    return dataclasses.replace(bench.BENCH_8B_CFG, fused_decode=False)


def load_int8_params(cfg, seed: int):
    """Random int8 weights from a seed, built where they are used (in
    the replica): no checkpoint, no network."""
    import jax

    from ray_tpu.models import quant

    return quant.fuse_for_decode(
        quant.init_quantized_llama(jax.random.key(seed), cfg), cfg)


def load_params(cfg, seed: int):
    import jax

    from ray_tpu.models import llama

    return llama.init_params(jax.random.key(seed), cfg)


def prefill_logits_check(params, cfg, prompt, *, path: str,
                         n_layers=None, tol: float = 5e-2) -> float:
    """Last-prompt-token logits from the program the engine jits for
    prefill (``path`` "ragged": adapter.ragged_step; "batch":
    adapter.prefill_batch) against ``llama.forward`` in float32 on the
    same weights (int8 leaves dequantized).  ``n_layers`` cuts both to
    the first layers, for a model whose float32 copy would not fit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, quant
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
    from ray_tpu.serve.llm_engine import llama_paged_adapter

    if n_layers is not None and n_layers < cfg.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
        params = dict(params, layers=jax.tree.map(
            lambda a: a[:n_layers], params["layers"]))
    adapter = llama_paged_adapter(cfg)
    n = len(prompt)
    maxp = -(-n // PAGE)
    cache = adapter.init_cache(maxp, PAGE)
    if path == "ragged":
        T = -(-n // 8) * 8
        (toks, _mask, _slot, pos, r_slot, r_start, r_len,
         r_off) = pack_ragged_batch(
            [{"slot": 0, "start": 0, "tokens": list(prompt)}], T, 1)
        logits, _ = jax.jit(adapter.ragged_step)(
            params, toks, pos, r_slot, r_start, r_len, r_off,
            np.arange(maxp, dtype=np.int32)[None], cache)
    else:
        toks = np.zeros((1, maxp * PAGE), np.int32)
        toks[0, :n] = prompt
        logits, _ = jax.jit(adapter.prefill_batch)(
            params, toks, np.asarray([n], np.int32),
            np.arange(maxp, dtype=np.int32)[None], cache)
    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32, kv_int8=False,
                                  remat=False)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: llama.forward(
            quant.dequantize_params(p, jnp.float32), t, ref_cfg)[0, -1])(
                params, jnp.asarray(prompt, jnp.int32)[None])
    return check_close(f"prefill_logits[{path},L={cfg.n_layers}]",
                       logits[0], want, tol, what="logits")


def fixed_prompts(cfg, n: int, length: int, seed: int = 1) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, length).tolist()
            for _ in range(n)]


def check_answers(outs, new_tokens: int, vocab: int) -> None:
    for i, toks in enumerate(outs):
        if len(toks) != new_tokens:
            raise AssertionError(
                f"request {i}: {len(toks)} tokens, wanted {new_tokens}")
        if not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"request {i}: token outside the vocab")


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelDims:
    """Kernel widths: the defaults are the 8B serving cell's (32 query
    heads over 8 KV heads of 128, 64-token pages) and the train cell's
    flash shape; tests shrink them."""

    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 128
    page: int = PAGE
    slots: int = 16
    maxp: int = 4
    layers: int = 2
    flash_batch: int = 2
    flash_seq: int = 1024
    flash_heads: int = 8
    flash_kv_heads: int = 4
    fused_cfg: object = None     # None: llama3_8b_int8 cut to 2 layers


def _rand(key, shape, dtype):
    import jax

    return jax.random.normal(key, shape, "float32").astype(dtype)


def _kernels_flash(d: KernelDims, platform: str) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention
    from ray_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(0), 4)
    B, S, D = d.flash_batch, d.flash_seq, d.head_dim
    q = _rand(ks[0], (B, S, d.flash_heads, D), jnp.bfloat16)
    k = _rand(ks[1], (B, S, d.flash_kv_heads, D), jnp.bfloat16)
    v = _rand(ks[2], (B, S, d.flash_kv_heads, D), jnp.bfloat16)
    w = _rand(ks[3], q.shape, jnp.float32)
    # One all-zero segment id masks nothing, and routes
    # dot_product_attention to its einsum path: the reference.
    seg = jnp.zeros((B, S), jnp.int32)

    def ref(q, k, v):
        return attention.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True, segment_ids=seg)

    def loss(f):
        return lambda q, k, v: (f(q, k, v).astype(jnp.float32) * w).sum()

    if platform == "tpu" and not attention._flash_eligible(
            q, k, True, None, None):
        raise AssertionError("the train shape must take the flash kernel")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(q, k, v)
        want_g = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    check_close("flash_attention.forward",
                jax.jit(flash_attention)(q, k, v), want, 2e-2)
    got_g = jax.jit(jax.grad(loss(flash_attention),
                             argnums=(0, 1, 2)))(q, k, v)
    for name, g, wg in zip(("dq", "dk", "dv"), got_g, want_g):
        check_close(f"flash_attention.backward.{name}", g, wg, 3e-2)


def _page_setup(d: KernelDims, seed: int):
    """Random pools [L, KVH, P+1, page, D] (last page = scratch), a
    shuffled block table and per-slot lengths that leave room for one
    more token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.key(seed), 2)
    P = d.slots * d.maxp
    shape = (d.layers, d.kv_heads, P + 1, d.page, d.head_dim)
    k_pools = _rand(ks[0], shape, jnp.bfloat16)
    v_pools = _rand(ks[1], shape, jnp.bfloat16)
    bt = rng.permutation(P).astype(np.int32).reshape(d.slots, d.maxp)
    lengths = rng.integers(1, d.maxp * d.page - 1, d.slots).astype(np.int32)
    lengths[0] = d.page            # next append opens a fresh page
    return k_pools, v_pools, bt, lengths


def _quantize_pools(pools):
    """int8 pools + page-major scales [L, P, KVH, 1], and the float32
    values they stand for."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    q8, scale = llama._quant_pages(pools)          # scale [L, KVH, P]
    deq = q8.astype(jnp.float32) * scale[..., None, None]
    return q8, scale.transpose(0, 2, 1)[..., None], deq


def _kernels_paged(d: KernelDims) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa

    k_pools, v_pools, bt, lengths = _page_setup(d, 1)
    ks = jax.random.split(jax.random.key(11), 3)
    q = _rand(ks[0], (d.slots, d.heads, d.head_dim), jnp.bfloat16)
    layer = d.layers - 1
    f32 = jnp.float32

    def reference(kp, vp):
        with jax.default_matmul_precision("highest"):
            return jax.jit(pa.paged_decode_attention_reference)(
                q.astype(f32), kp[layer].astype(f32),
                vp[layer].astype(f32), bt, lengths)

    want = reference(k_pools, v_pools)
    check_close("paged_decode_attention", jax.jit(pa.paged_decode_attention)(
        q, k_pools[layer], v_pools[layer], bt, lengths), want, 2e-2)
    acc, _m, l = jax.jit(pa.paged_decode_attention_partial)(
        q, k_pools, v_pools, jnp.int32(layer), bt, lengths)
    check_close("paged_decode_attention_partial", acc / l, want, 2e-2)

    k8, k_sc, k_deq = _quantize_pools(k_pools)
    v8, v_sc, v_deq = _quantize_pools(v_pools)
    acc, _m, l = jax.jit(
        lambda q, k, v, ks, vs, ly, bt, ln: pa.paged_decode_attention_partial(
            q, k, v, ly, bt, ln, k_scales=ks, v_scales=vs))(
        q, k8, v8, k_sc, v_sc, jnp.int32(layer), bt, lengths)
    check_close("paged_decode_attention_partial[int8]", acc / l,
                reference(k_deq, v_deq), 2e-2)

    # append: one new row per slot at (page of lengths, lengths % page)
    k_new = _rand(ks[1], (d.layers, d.slots, d.kv_heads, d.head_dim),
                  jnp.bfloat16)
    v_new = _rand(ks[2], k_new.shape, jnp.bfloat16)
    pids = bt[np.arange(d.slots), lengths // d.page]
    offs = lengths % d.page
    got_k, got_v = jax.jit(pa.paged_append)(
        k_pools, v_pools, k_new, v_new, pids, offs)
    for name, got, pool, new in (("k", got_k, k_pools, k_new),
                                 ("v", got_v, v_pools, v_new)):
        want_pool = np.array(pool.astype(f32))
        want_pool[:, :, pids, offs] = np.asarray(
            new.astype(f32)).transpose(0, 2, 1, 3)
        check_close(f"paged_append.{name}", got, want_pool, 0.0)
    got = jax.jit(pa.paged_append_quantized)(
        k8, v8, k_sc, v_sc, k_new, v_new, pids, offs)
    _check_int8_rows("paged_append_quantized", got, (k_new, v_new),
                     [(np.arange(d.slots), pids, offs)])


def _check_int8_rows(name, pools_and_scales, news, places) -> None:
    """Appended rows of int8 pools, dequantized with their page's scale,
    against the float32 rows that were appended: within one quantization
    step.  ``places``: (index into the new rows, page id, offset)."""
    import numpy as np

    from ray_tpu.ops import platform as ops_platform

    how = "interpreted" if ops_platform.interpret_mode() else "compiled"
    k8, v8, k_sc, v_sc = (np.asarray(a) for a in pools_and_scales)
    for which, q8, sc, new in (("k", k8, k_sc, news[0]),
                               ("v", v8, v_sc, news[1])):
        new = np.asarray(new.astype("float32"))       # [L, T|B, KVH, D]
        worst = 0.0
        for idx, pid, off in places:
            rows = q8[:, :, pid, off].astype(np.float32)   # [L,KVH,n,D]
            scale = sc[:, pid][..., 0].transpose(0, 2, 1)[..., None]
            err = np.abs(rows * scale - new[:, idx].transpose(0, 2, 1, 3))
            worst = max(worst, float(np.max(err / scale)))
        log(f"  kernel={name}.{which} {how} max_err={worst:.3f} "
            f"quantization steps (tol 1.0)")
        if not worst <= 1.0:
            raise AssertionError(f"{name}.{which}: {worst} steps off")


def _kernels_ragged(d: KernelDims) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import ragged_paged_attention as rpa

    k_pools, v_pools, bt, _ = _page_setup(d, 2)
    page = d.page
    # A mixed batch: decode rows, a prompt's first chunk, a later chunk
    # that crosses a page boundary, and padding rows.
    rows = [{"slot": 1, "start": page + 3, "tokens": None},
            {"slot": 2, "start": 0, "tokens": [1] * (page // 2 + 3)},
            {"slot": 3, "start": page - 5, "tokens": [1] * 11},
            {"slot": 0, "start": 2 * page, "tokens": None}]
    T = -(-(sum(len(r["tokens"] or [0]) for r in rows) + 5) // 8) * 8
    R = d.slots
    (_toks, _mask, _slot, _pos, r_slot, r_start, r_len,
     r_off) = rpa.pack_ragged_batch(rows, T, R)
    ks = jax.random.split(jax.random.key(12), 5)
    q = _rand(ks[0], (T, d.heads, d.head_dim), jnp.bfloat16)
    k_new = _rand(ks[1], (d.layers, T, d.kv_heads, d.head_dim),
                  jnp.bfloat16)
    v_new = _rand(ks[2], k_new.shape, jnp.bfloat16)
    layer = d.layers - 1
    f32 = jnp.float32

    def reference(kp, vp, meta, **scales):
        with jax.default_matmul_precision("highest"):
            return jax.jit(functools.partial(
                rpa.ragged_attention_reference, **scales))(
                    q.astype(f32), k_new[layer].astype(f32),
                    v_new[layer].astype(f32), kp[layer], vp[layer], *meta)

    k8, k_sc, _ = _quantize_pools(k_pools)
    v8, v_sc, _ = _quantize_pools(v_pools)
    attend = jax.jit(rpa.ragged_paged_attention)
    attend8 = jax.jit(
        lambda q, k, v, kp, vp, ks, vs, ly, *m: rpa.ragged_paged_attention(
            q, k, v, kp, vp, ly, *m, k_scales=ks, v_scales=vs))
    # The step as packed (decode rows beside chunks), then with only its
    # rows of one token and only its chunks: each leaves one of the
    # kernel's two calls no cell to walk, and what that call does not
    # write must not reach the result.
    for case, lens in (("", r_len),
                       ("[decode rows only]", np.where(r_len == 1, 1, 0)),
                       ("[chunks only]", np.where(r_len > 1, r_len, 0))):
        meta = (r_slot, r_start, lens.astype(np.int32), r_off, bt)
        check_close(
            "ragged_paged_attention" + case,
            attend(q, k_new[layer], v_new[layer], k_pools, v_pools,
                   jnp.int32(layer), *meta),
            reference(k_pools.astype(f32), v_pools.astype(f32), meta), 2e-2)
        check_close(
            "ragged_paged_attention[int8]" + case,
            attend8(q, k_new[layer], v_new[layer], k8, v8, k_sc, v_sc,
                    jnp.int32(layer), *meta),
            reference(k8, v8, meta, k_scales=k_sc[layer],
                      v_scales=v_sc[layer]), 2e-2)

    # The append as packed, then with a padding row between live ones,
    # then with no live row at all (its grid has no cell to walk): pages
    # no row writes keep their bits, and their scales.
    gap = np.where(np.arange(R) == 1, 0, r_len)
    append = jax.jit(rpa.ragged_paged_append)
    scatter = jax.jit(rpa.ragged_append_reference)
    append_q = jax.jit(rpa.ragged_paged_append_quantized)
    state8 = (k8, v8, k_sc, v_sc)
    for case, lens in (("", r_len), ("[padding between]", gap),
                       ("[no live row]", np.zeros_like(r_len))):
        m = (r_slot, r_start, lens, r_off, bt)
        got_k, got_v = append(k_pools, v_pools, k_new, v_new, *m)
        for li in range(d.layers):
            want_k, want_v = scatter(
                k_pools[li], v_pools[li], k_new[li], v_new[li], *m)
            # the last physical page is scratch: padding writes land there
            check_close(f"ragged_paged_append{case}.k[layer {li}]",
                        got_k[li][:, :-1], want_k[:, :-1], 0.0)
            check_close(f"ragged_paged_append{case}.v[layer {li}]",
                        got_v[li][:, :-1], want_v[:, :-1], 0.0)
        got = append_q(*state8, k_new, v_new, *m)
        places = []
        for i in np.flatnonzero(lens):
            pos = r_start[i] + np.arange(lens[i])
            places.append((r_off[i] + np.arange(lens[i]),
                           bt[r_slot[i], pos // page], pos % page))
        _check_int8_rows(f"ragged_paged_append_quantized{case}", got,
                         (k_new, v_new), places)
        kept = np.setdiff1d(np.arange(k8.shape[2] - 1),
                            [p for _, pid, _ in places for p in pid])
        for which, g, before in zip(("k", "v", "k_scale", "v_scale"), got,
                                    state8):
            axis = 2 if g.ndim == 5 else 1        # pools, scales by page
            check_close(
                f"ragged_paged_append_quantized{case}.{which}[unwritten]",
                jnp.take(g, kept, axis=axis),
                jnp.take(before, kept, axis=axis), 0.0)


def _kernels_fused(d: KernelDims) -> None:
    """The fused per-layer megakernels against the unfused path: one
    decode step and one ragged step over the same random int8 cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

    cfg = d.fused_cfg or dataclasses.replace(
        llama3_8b_int8(), n_layers=2, max_seq_len=d.maxp * d.page)
    fused = dataclasses.replace(cfg, fused_decode=True)
    params = load_int8_params(cfg, 0)
    dims = dataclasses.replace(d, heads=cfg.n_heads,
                               kv_heads=cfg.n_kv_heads,
                               head_dim=cfg.head_dim, layers=cfg.n_layers)
    k_pools, v_pools, bt, lengths = _page_setup(dims, 4)
    k8, k_sc, _ = _quantize_pools(k_pools * 0.3)
    v8, v_sc, _ = _quantize_pools(v_pools * 0.3)
    cache = {"k": k8, "v": v8, "k_scale": k_sc, "v_scale": v_sc}
    toks = np.random.default_rng(5).integers(
        1, cfg.vocab_size, d.slots).astype(np.int32)
    active = np.ones((d.slots,), bool)

    def decode(c):
        return jax.jit(lambda p, t, a, b, ln, ch: llama.decode_slots_paged(
            p, t, a, b, ln, c, ch)[0])(params, toks, active, bt, lengths,
                                       cache)

    check_close("fused_decode_layer (decode_slots_paged fused vs unfused)",
                decode(fused), decode(cfg), 5e-2)

    rows = [{"slot": 1, "start": int(lengths[1]), "tokens": None},
            {"slot": 2, "start": 0, "tokens": toks[:9].tolist()},
            {"slot": 3, "start": int(lengths[3]), "tokens": None}]
    (t_host, _mask, _slot, pos, r_slot, r_start, r_len,
     r_off) = pack_ragged_batch(rows, 16, d.slots)
    t_host[r_off[0]] = toks[1]
    t_host[r_off[2]] = toks[3]

    def ragged(c):
        return jax.jit(lambda p, ch: llama.ragged_step_paged(
            p, t_host, pos, r_slot, r_start, r_len, r_off, bt, c, ch)[0])(
                params, cache)[:len(rows)]

    check_close("fused_ragged_layer (ragged_step_paged fused vs unfused)",
                ragged(fused), ragged(cfg), 5e-2)


def _mixed_rows(d: KernelDims):
    """Packed rows of both kinds over ``d.slots`` slots: two rows of one
    token, a chunk of several pages deep in its sequence and one that
    starts its sequence, with a padding row between them."""
    import jax.numpy as jnp

    long = (d.maxp - 2) * d.page - 20
    ctx = (d.maxp - 1) * d.page - long
    rows = [(0, ctx + 5, 1, 0), (2, ctx, long, 3), (0, 0, 0, 0),
            (1, 9, 1, long + 4), (3, 0, 40, long + 8)]
    return (tuple(jnp.asarray(x, jnp.int32) for x in zip(*rows)),
            long + 48)


def _kernels_lightning(d: KernelDims) -> None:
    """``lightning_decode`` and ``lightning_chunk`` against their plain
    forms: float32 throughout, so the limit is rounding of sums."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import lightning_attention as la

    rows, T = _mixed_rows(d)
    H, hd = d.heads, d.head_dim
    ks = jax.random.split(jax.random.key(6), 4)
    q = _rand(ks[0], (T, H, hd), jnp.bfloat16).astype(jnp.float32) \
        * hd ** -0.5
    k, v = (_rand(ks[i], (T, H, hd), jnp.bfloat16) for i in (1, 2))
    lam = jnp.exp(-0.5 * jnp.exp2(-8.0 * (jnp.arange(H) + 1.0) / H))
    s0 = _rand(ks[3], (d.layers, d.slots + 1, H, hd, hd), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for kind in ("decode", "chunk"):
            o, s = jax.jit(getattr(la, f"lightning_{kind}"))(
                q, k, v, lam, s0, 1, *rows)
            o2, s2 = jax.jit(getattr(la, f"lightning_{kind}_reference"))(
                q, k, v, lam, s0, 1, *rows)
            check_close(f"lightning_{kind} (output)", o, o2, 1e-4)
            check_close(f"lightning_{kind} (state)", s, s2, 1e-4)


def _kernels_block_sparse(d: KernelDims) -> None:
    """``block_sparse_walk`` under a selection that differs by KV head
    against the dense gather, over block tables of three pool cells: a
    row of one token whose list ends inside a cell, one whose list is
    shorter than a cell, a chunk whose tokens' picks leave gaps in their
    union, a chunk that starts its sequence; and the counts it returns
    (pages listed, cells of ``cell_pages`` entries) against the host's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import block_sparse_attention as bsa

    H, KVH, hd, page, maxp = d.heads, 2, d.head_dim, d.page, 24
    G = bsa.cell_pages(maxp)
    spec = [(0, 19 * page + 5, 1, 0), (2, 17 * page, 2 * page, 3),
            (0, 0, 0, 0), (1, 9, 1, 2 * page + 4),
            (3, 0, 40, 2 * page + 8)]
    rows = tuple(jnp.asarray(x, jnp.int32) for x in zip(*spec))
    T = 2 * page + 48
    ks = jax.random.split(jax.random.key(7), 6)
    q = _rand(ks[0], (T, H, hd), jnp.bfloat16)
    kn, vn = (_rand(ks[i], (T, KVH, hd), jnp.bfloat16) for i in (1, 2))
    P = d.slots * maxp
    kp, vp = (_rand(ks[i], (d.layers, KVH, P + 1, page, hd), jnp.bfloat16)
              for i in (3, 4))
    bt = jnp.asarray(np.random.default_rng(0).permutation(P).reshape(
        d.slots, maxp), jnp.int32)
    few = np.zeros(T, bool)
    for _slot, _start, n, off in spec:
        few[off:off + n] = n > 1
    mask = (jax.random.uniform(ks[5], (T, KVH, maxp))
            < jnp.where(few, 0.12, 0.5)[:, None, None]).at[:, :, 0].set(True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(bsa.block_sparse_attention_reference)(
            q, kn, vn, kp[1], vp[1], *rows, bt, mask)
    got, pages, cells = jax.jit(bsa.block_sparse_attention)(
        q, kn, vn, kp, vp, 1, *rows, bt, mask)
    check_close("block_sparse_walk", got, want, 2e-2)
    m = np.asarray(mask)
    host = np.zeros((2, 2), int)        # pages, cells x one token, more
    for _slot, start, n, off in spec:
        if n:
            lists = (m[off:off + n].any(axis=0)
                     & (np.arange(maxp) * page < start)).sum(axis=-1)
            host[:, int(n > 1)] += [lists.sum(), (-(-lists // G)).sum()]
    if [pages.tolist(), cells.tolist()] != host.tolist():
        raise AssertionError(
            f"block_sparse_walk counted {pages.tolist()} pages in "
            f"{cells.tolist()} cells of {G}; the host {host.tolist()}")


def _kernels_adam8(d: KernelDims) -> None:
    """``adam8_update`` (the whole fused chain, applied) against its
    plain form over two steps, on a view of each kind: rows of whole
    blocks, blocks of two rows, rows that end in a short block with a
    ragged last tile.  Mosaic and XLA divide and take roots their own
    way, so the limits leave a code one step and a parameter one
    rounding of room; at PR 48 nothing differed, to the bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.ops import adam8bit

    hp = adam8bit.Adam8(fused=True, clip=1.0, weight_decay=0.1, apply=True)
    kernel = jax.jit(adam8bit.adam8_update, static_argnames="hp")
    plain = jax.jit(adam8bit.adam8_update_reference, static_argnames="hp")
    for rows, cols in ((512, 2048), (1024, 128), (256, 2432)):
        assert adam8bit.tile_shape(rows, cols) is not None
        ks = jax.random.split(jax.random.key(rows), 3)
        p = _rand(ks[0], (rows, cols), jnp.bfloat16) * 0.02
        zero = (jnp.zeros((rows, cols), jnp.int8), jnp.full(
            adam8bit.scale_shape(rows, cols), 1e-12, jnp.float32))
        got = want = (p,) + zero + zero
        for i in (1, 2):
            g = _rand(ks[i], (rows, cols), jnp.bfloat16) * 0.01 * i
            scal = adam8bit.scalars(hp, jnp.int32(i), jnp.bfloat16,
                                    gnorm=optax.global_norm(g),
                                    step_size=jnp.float32(-1e-3))
            got = kernel(scal, g, got[0], *got[1:], hp=hp)
            want = plain(scal, g, want[0], *want[1:], hp=hp)
        name = f"adam8_update [{rows}, {cols}]"
        check_close(f"{name} (parameter)", got[0], want[0], 2.0 ** -7)
        for what, i in (("mu", 1), ("nu", 3)):
            check_close(f"{name} ({what} scales)", got[i + 1], want[i + 1],
                        1e-5)
            off = np.abs(np.asarray(got[i], np.int32)
                         - np.asarray(want[i], np.int32))
            log(f"  kernel={name} ({what} codes) differ in "
                f"{float((off > 0).mean()):.2e} by at most {int(off.max())}")
            if off.max() > 1 or (off > 0).mean() > 1e-2:
                raise AssertionError(f"{name}: {what} codes differ")


def phase_kernels(platform: str, *, dims: KernelDims = KernelDims()) -> dict:
    require_platform(platform)
    _kernels_flash(dims, platform)
    _kernels_paged(dims)
    _kernels_ragged(dims)
    _kernels_fused(dims)
    _kernels_lightning(dims)
    _kernels_block_sparse(dims)
    _kernels_adam8(dims)
    return {}


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------


def phase_train(platform: str, *, cfg=None, batch: int = 8,
                seq: int = 2048, steps: int = 5, mesh_spec=None,
                devices=None, expect_loss0=None) -> dict:
    """``JaxTrainer.fit`` for ``steps`` steps on ONE fixed batch: the
    loss starts near ln(vocab) (random weights) and falls."""
    import itertools

    import numpy as np

    import bench
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import (
        JaxTrainer,
        RunConfig,
        ScalingConfig,
        default_optimizer,
    )

    all_devices = require_platform(platform)
    cfg = cfg or bench.BENCH_CFG
    devices = devices or all_devices[:1]
    trainer = JaxTrainer(
        init_params=lambda r: llama.init_params(r, cfg),
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        params_axes=llama.logical_axes(cfg),
        batch_axes={"tokens": ("batch", None)},
        # lr 0 at step 0, full from step 2: five steps on one batch move
        optimizer=default_optimizer(1e-3, warmup_steps=2),
        scaling_config=ScalingConfig(
            mesh_spec=mesh_spec or MeshSpec(dp=1), devices=devices),
        run_config=RunConfig(report_every=1),
    )
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int64).astype(np.int32)
    t0 = time.perf_counter()
    stamps = []
    result = trainer.fit(itertools.repeat({"tokens": tokens}),
                         num_steps=steps,
                         report=lambda m: stamps.append(time.perf_counter()))
    if result.error is not None:
        raise result.error
    losses = [m["loss"] for m in result.metrics_history]
    log(f"  train {cfg.num_params() / 1e6:.0f}M B={batch} S={seq} on "
        f"{len(devices)} device(s) mesh={dict(trainer.mesh.shape)}: "
        f"losses={[round(x, 4) for x in losses]}")
    ln_v = math.log(cfg.vocab_size)
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"want {steps} finite losses, got {losses}")
    if abs(losses[0] - ln_v) > 1.0:
        raise AssertionError(
            f"step-0 loss {losses[0]:.3f} is not near ln(V)={ln_v:.3f}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if expect_loss0 is not None and abs(losses[0] - expect_loss0) > 2e-2:
        raise AssertionError(
            f"step-0 loss {losses[0]:.4f} differs from the one-device "
            f"value {expect_loss0:.4f} by more than 0.02")
    peaks = peak_hbm(devices)
    steady = (stamps[-1] - stamps[1]) / (steps - 2) if steps > 2 else None
    log(f"  train first step (compile included) "
        f"{stamps[0] - t0:.1f}s, later steps "
        f"{'n/a' if steady is None else f'{steady:.3f}s'} each "
        f"(smoke timing); peak HBM per device: "
        f"{[None if p is None else round(p / 2**30, 2) for p in peaks]} GiB")
    return {"loss0": losses[0], "loss_last": losses[-1],
            "peak_hbm_bytes": peaks}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def smoke_server_class():
    """LLMServer plus what the smoke asks of a replica: where it runs,
    what it compiled, and whether its prefill agrees with the reference.
    Built in a function so that importing this file imports no JAX."""
    from ray_tpu.serve.llm_engine import LLMServer

    class SmokeLLMServer(LLMServer):
        def __init__(self, model_cfg, engine_cfg, param_loader, **kw):
            self._clock = CompileClock()
            self._model_cfg = model_cfg
            super().__init__(model_cfg, engine_cfg, param_loader, **kw)

        def device_report(self) -> dict:
            import jax

            devices = jax.devices()
            return {"pid": os.getpid(),
                    "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                    "peak_hbm_bytes": peak_hbm(devices),
                    **self._clock.report()}

        def prefill_check(self, prompt, n_layers) -> float:
            return prefill_logits_check(
                self.engine._params, self._model_cfg, prompt,
                path="ragged", n_layers=n_layers)

    return SmokeLLMServer


def phase_serve(platform: str, *, cfg=None, slots: int = 48,
                n_requests: int = 8, prompt_len: int = 128,
                new_tokens: int = 16, ref_layers: int = 2,
                ready_timeout_s: float = 900.0) -> dict:
    """The serving entry points, end to end: ``serve.run`` of an
    ``LLMServer`` deployment, requests through the handle.  This process
    is the caller and stays off the chip; the replica asks for the
    host's chips (``num_tpus``) and must report ``platform``."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import quant
    from ray_tpu.serve.llm_engine import EngineConfig
    from ray_tpu.utils import accelerator

    cfg = cfg or llama3_8b_int8()
    ray_tpu.init(ignore_reinit_error=True)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if platform == "tpu" and chips < 1:
            raise RuntimeError(
                "ray_tpu.init() found no TPU chip on this host "
                "(no /dev/accel* or /dev/vfio/<N>)")
        log(f"  caller pid={os.getpid()}: ray_tpu.init() sees TPU={chips} "
            f"without JAX; backend initialised: "
            f"{accelerator.backend_initialised()}")
        app = serve.deployment(
            ray_actor_options=({"num_tpus": chips} if platform == "tpu"
                               else {}),
            max_ongoing_requests=2 * n_requests,
        )(smoke_server_class()).bind(
            cfg,
            EngineConfig(max_slots=slots, max_seq_len=cfg.max_seq_len,
                         page_size=PAGE, ragged_batching=True,
                         max_new_tokens_default=new_tokens),
            functools.partial(load_int8_params, cfg, 0),
            adapter_factory=quant.llama_paged_adapter_quant,
        )
        t0 = time.perf_counter()
        handle = serve.run(app, name="chip_smoke", route_prefix=None,
                           timeout_s=ready_timeout_s)
        log(f"  replica ready after {time.perf_counter() - t0:.1f}s "
            f"(weights built in the replica)")
        prompts = fixed_prompts(cfg, n_requests, prompt_len)
        t0 = time.perf_counter()
        pending = [handle.remote({"tokens": p, "max_new_tokens": new_tokens,
                                  "temperature": 0.0}) for p in prompts]
        outs = [r.result(timeout_s=ready_timeout_s)["tokens"]
                for r in pending]
        check_answers(outs, new_tokens, cfg.vocab_size)
        log(f"  {len(outs)} of {n_requests} requests answered "
            f"({prompt_len} prompt + {new_tokens} new tokens each) in "
            f"{time.perf_counter() - t0:.1f}s, first compile included "
            f"(smoke timing)")
        rel = handle.prefill_check.remote(
            prompts[0], ref_layers).result(timeout_s=ready_timeout_s)
        rep = handle.device_report.remote().result(timeout_s=60)
        log(f"  replica pid={rep['pid']} JAX_PLATFORMS="
            f"{rep['JAX_PLATFORMS']} platform={rep['platform']} "
            f"device_kind={rep['kind']!r} count={rep['count']} "
            f"compile_s={rep['compile_s']} cache_hits={rep['cache_hits']} "
            f"cache_misses={rep['cache_misses']}")
        if rep["platform"] != platform:
            raise AssertionError(
                f"replica computes on {rep['platform']!r}, not "
                f"{platform!r}")
        if rep["pid"] == os.getpid():
            raise AssertionError("the replica must be its own process")
        if accelerator.backend_initialised():
            raise AssertionError(
                "the process that called serve.run initialised a JAX "
                "backend: it would hold the chip")
        log("  caller never initialised a JAX backend")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"device": {k: rep[k] for k in ("platform", "kind", "count")},
            "prefill_rel_err": rel, "compile_s": rep["compile_s"],
            "cache_hits": rep["cache_hits"],
            "cache_misses": rep["cache_misses"]}


def _serve_recurrent_case(platform: str, runner, config: dict, name: str,
                          state_key: str, *, n_requests: int,
                          prompt_len: int, new_tokens: int,
                          ready_timeout_s: float) -> dict:
    """One model with per-slot recurrent state through the benchmark's
    own replica class (``runner.server_class()``): the reference check
    before the engine takes the memory, ``n_requests`` prompts of
    several chunks through ``serve.run``, the served tokens held to the
    reference, and the control: the check has to refuse a state kept in
    bfloat16 (``state_key`` of its report not ok)."""
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(ignore_reinit_error=True)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        app = serve.deployment(
            ray_actor_options=({"num_tpus": chips} if platform == "tpu"
                               else {}),
            max_ongoing_requests=2 * n_requests,
        )(runner.server_class()).bind({"config": config, "seed": 0})
        t0 = time.perf_counter()
        handle = serve.run(app, name=f"chip_smoke_{name}", route_prefix=None,
                           timeout_s=ready_timeout_s)
        rep = handle.device_report.remote().result(timeout_s=60)
        check = rep["check"]
        log(f"  {name} replica ready after {time.perf_counter() - t0:.1f}s "
            f"on {rep['platform']}; reference check: "
            + " ".join(f"{k}={check[k]['rel_err_prefill']:.2e}/"
                       f"{check[k]['rel_err_decode']:.2e}"
                       for k in runner.TOLERANCES))
        if rep["platform"] != platform:
            raise AssertionError(
                f"replica computes on {rep['platform']!r}, not "
                f"{platform!r}")
        if not check["ok"]:
            raise AssertionError(f"{name} reference check failed: {check}")
        vocab = config["vocab_size"]
        prompts = [[(7 * i + 3 * j) % (vocab - 1) + 1
                    for j in range(prompt_len)] for i in range(n_requests)]
        pending = [handle.remote({"tokens": p, "max_new_tokens": new_tokens,
                                  "temperature": 0.0}) for p in prompts]
        outs = [r.result(timeout_s=ready_timeout_s)["tokens"]
                for r in pending]
        check_answers(outs, new_tokens, vocab)
        state = handle.counters.remote().result(timeout_s=60)["state_cache"]
        log(f"  {len(outs)} {name} requests answered ({prompt_len} prompt "
            f"+ {new_tokens} new tokens each); state cache {state}")
        if state["resets"] < n_requests or state["live"] != 0:
            raise AssertionError(f"state cache accounting: {state}")
        served = handle.served_check.remote().result(
            timeout_s=ready_timeout_s)
        log(f"  served tokens against the reference: {served}")
        if not served["ok"] or served["tokens"] < new_tokens:
            raise AssertionError(f"{name} served-token check: {served}")
        control = handle.state_control.remote().result(
            timeout_s=ready_timeout_s)[state_key]
        log(f"  control, state rounded to bfloat16: {control} against "
            f"{check[state_key]['rel_err']}")
        if control["ok"]:
            raise AssertionError(
                f"the check accepts a bfloat16 state: {control}")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"platform": rep["platform"], "reference_check": check,
            "state_cache": state, "served_check": served,
            "state_control": control}


def _benchmark_config(name: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def phase_serve_jamba(platform: str, *, config=None, n_requests: int = 8,
                      prompt_len: int = 300, new_tokens: int = 16,
                      ready_timeout_s: float = 900.0) -> dict:
    """The serving phase's second case: a model with per-slot recurrent
    state beside paged KV.  Jamba at three layers (Mamba, attention,
    Mamba) of the published widths; ``config`` defaults to the
    benchmark's file."""
    from benchmarks.runners import serve_jamba

    config = config or _benchmark_config("jamba2_3b")
    config = dict(config, **serve_jamba.CHECK_HF,
                  engine=dict(config["engine"], max_slots=8,
                              max_seq_len=512, num_pages=64,
                              prefill_chunk=128))
    return _serve_recurrent_case(
        platform, serve_jamba, config, "jamba", "ssm_state",
        n_requests=n_requests, prompt_len=prompt_len,
        new_tokens=new_tokens, ready_timeout_s=ready_timeout_s)


def phase_serve_brumby(platform: str, *, config=None, n_requests: int = 6,
                       prompt_len: int = 1100, new_tokens: int = 16,
                       ready_timeout_s: float = 900.0) -> dict:
    """The serving phase's third case: a model whose cache is state by
    slot and NOTHING by page.  Brumby at three power-retention layers of
    the published widths (matrix state per KV head, 0.11 GB a slot at
    this depth); ``config`` defaults to the benchmark's file."""
    from benchmarks.runners import serve_brumby

    config = config or _benchmark_config("brumby14b_pp4")
    config = dict(config, num_hidden_layers=serve_brumby.CHECK_LAYERS,
                  engine=dict(config["engine"], max_slots=8,
                              max_seq_len=2048))
    out = _serve_recurrent_case(
        platform, serve_brumby, config, "brumby", "ret_state",
        n_requests=n_requests, prompt_len=prompt_len,
        new_tokens=new_tokens, ready_timeout_s=ready_timeout_s)
    if out["state_cache"]["bytes"] <= 0:
        raise AssertionError(f"no retention state held: {out}")
    return out


def phase_serve_xing(platform: str, *, config=None, n_requests: int = 6,
                     prompt_len: int = 600, new_tokens: int = 16,
                     ready_timeout_s: float = 1200.0) -> dict:
    """The serving phase's fourth case: latent attention over one page
    pool, routed experts with no token dropped, a four-stream residual.
    Xing4.0 at three layers (one dense, two routed) of the published
    widths through the benchmark's own replica class: the reference
    check before the engine takes the memory, prompts of several chunks
    through ``serve.run``, the experts' counters, the served tokens held
    to the reference with the choices the engine's steps logged in
    their pages, and the controls, each of which a check has to refuse:
    a router computed in bfloat16 and a wrong expert on every 50th token
    (``route``), a pool kept in float8_e4m3fn (``latent_pages``),
    another request's answer and one replaced token (the served check).
    ``config`` defaults to the benchmark's file."""
    import ray_tpu
    from benchmarks.runners import serve_xing
    from ray_tpu import serve

    config = config or _benchmark_config("xing4_29b_pp8")
    config = dict(config, **serve_xing.CHECK_HF,
                  engine=dict(config["engine"], max_slots=8,
                              max_seq_len=1024))
    ray_tpu.init(ignore_reinit_error=True)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        app = serve.deployment(
            ray_actor_options=({"num_tpus": chips} if platform == "tpu"
                               else {}),
            max_ongoing_requests=2 * n_requests,
        )(serve_xing.server_class()).bind({"config": config, "seed": 0})
        t0 = time.perf_counter()
        handle = serve.run(app, name="chip_smoke_xing", route_prefix=None,
                           timeout_s=ready_timeout_s)
        rep = handle.device_report.remote().result(timeout_s=60)
        check = rep["check"]
        log(f"  xing replica ready after {time.perf_counter() - t0:.1f}s "
            f"on {rep['platform']}; reference check: "
            + " ".join(f"{k}={check[k]['rel_err_prefill']:.2e}/"
                       f"{check[k]['rel_err_decode']:.2e}"
                       for k in serve_xing.TOLERANCES)
            + f" route={check['route']} latent={check['latent_pages']}")
        if rep["platform"] != platform:
            raise AssertionError(
                f"replica computes on {rep['platform']!r}, not "
                f"{platform!r}")
        if not check["ok"]:
            raise AssertionError(f"xing reference check failed: {check}")
        vocab = config["vocab_size"]
        prompts = [[(7 * i + 3 * j) % (vocab - 1) + 1
                    for j in range(prompt_len)] for i in range(n_requests)]
        pending = [handle.remote({"tokens": p, "max_new_tokens": new_tokens,
                                  "temperature": 0.0}) for p in prompts]
        outs = [r.result(timeout_s=ready_timeout_s)["tokens"]
                for r in pending]
        check_answers(outs, new_tokens, vocab)
        counters = handle.counters.remote().result(
            timeout_s=60)["model_counters"]
        pairs = [sum(layer) for layer in counters["moe_tokens"]]
        log(f"  {len(outs)} xing requests answered ({prompt_len} prompt + "
            f"{new_tokens} new tokens each); pairs served by layer {pairs}")
        # every token of every request met top-k experts in each layer
        least = n_requests * (prompt_len + new_tokens - 1) \
            * config["num_experts_per_tok"]
        if min(pairs) < least or len(set(pairs)) != 1:
            raise AssertionError(f"experts' counters: {pairs} < {least}")
        served = handle.served_check.remote().result(
            timeout_s=ready_timeout_s)
        log(f"  served tokens against the reference: {served}")
        if (not served["ok"] or served["held"] < n_requests
                or served["tokens"] < new_tokens * min(
                    n_requests, serve_xing.SERVED_SAMPLES - 1)):
            raise AssertionError(f"xing served-token check: {served}")
        controls = {}
        for name, key in (("route_control", "route"),
                          ("wrong_expert_control", "route"),
                          ("cache_control", "latent_pages")):
            controls[name] = getattr(handle, name).remote().result(
                timeout_s=ready_timeout_s)[key]
            log(f"  {name}: {controls[name]} against {check[key]}")
            if controls[name]["ok"]:
                raise AssertionError(
                    f"the check accepts {name}: {controls[name]}")
        planted = handle.served_control.remote().result(
            timeout_s=ready_timeout_s)
        for name in ("other_answer", "one_token"):
            controls[name] = planted.get(name)
            log(f"  served check, {name}: {controls[name]}")
            if controls[name] is None or controls[name]["ok"]:
                raise AssertionError(
                    f"the served check accepts {name}: {planted}")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return dict(controls, platform=rep["platform"], reference_check=check,
                model_counters=counters, served_check=served)


def phase_serve_glm5(platform: str, *, config=None, n_requests: int = 4,
                     prompt_len: int = 2600, new_tokens: int = 16,
                     controls=None, ready_timeout_s: float = 1500.0) -> dict:
    """The serving phase's fifth case: latent attention kept to the
    positions a learned indexer selects, over a latent and an index-key
    pool, and routed experts of which the chip holds 16 of 256.  GLM-5 at
    three layers (one dense, two routed) of the published widths through
    the benchmark's own replica class: the reference check before the
    engine takes the memory (logits, routing, the selection, the first
    layer's attention output and both pools' pages), prompts past
    ``index_topk`` through ``serve.run`` so that decode rows select, the
    held experts' counters, the served tokens held to the reference with
    the logged choices, and the controls (``serve_glm5.CONTROLS``: a
    planted fault each, which its part of the check has to refuse).
    ``config`` defaults to the benchmark's file; ``controls`` to all."""
    import ray_tpu
    from benchmarks.runners import serve_glm5
    from ray_tpu import serve

    config = config or _benchmark_config("glm5_ep16")
    config = dict(config, **serve_glm5.CHECK_HF,
                  engine=dict(config["engine"], max_slots=8,
                              max_seq_len=prompt_len + 8 * new_tokens))
    names = list(serve_glm5.CONTROLS if controls is None else controls)
    ray_tpu.init(ignore_reinit_error=True)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        app = serve.deployment(
            ray_actor_options=({"num_tpus": chips} if platform == "tpu"
                               else {}),
            max_ongoing_requests=2 * n_requests,
        )(serve_glm5.server_class()).bind({"config": config, "seed": 0})
        t0 = time.perf_counter()
        handle = serve.run(app, name="chip_smoke_glm5", route_prefix=None,
                           timeout_s=ready_timeout_s)
        rep = handle.device_report.remote().result(timeout_s=60)
        check = rep["check"]
        log(f"  glm5 replica ready after {time.perf_counter() - t0:.1f}s "
            f"on {rep['platform']}; reference check: "
            + " ".join(f"{k}={check[k]['rel_err_prefill']:.2e}/"
                       f"{check[k]['rel_err_decode']:.2e}"
                       for k in serve_glm5.TOLERANCES)
            + f" route={check['route']} selection={check['selection']}"
              f" pools={check['pool_pages']}")
        if rep["platform"] != platform:
            raise AssertionError(
                f"replica computes on {rep['platform']!r}, not "
                f"{platform!r}")
        if not check["ok"]:
            raise AssertionError(f"glm5 reference check failed: {check}")
        vocab = config["vocab_size"]
        prompts = [[(7 * i + 3 * j) % (vocab - 1) + 1
                    for j in range(prompt_len)] for i in range(n_requests)]
        pending = [handle.remote({"tokens": p, "max_new_tokens": new_tokens,
                                  "temperature": 0.0}) for p in prompts]
        outs = [r.result(timeout_s=ready_timeout_s)["tokens"]
                for r in pending]
        check_answers(outs, new_tokens, vocab)
        counters = handle.counters.remote().result(
            timeout_s=60)["model_counters"]
        pairs = [sum(layer) for layer in counters["moe_tokens"]]
        log(f"  {len(outs)} glm5 requests answered ({prompt_len} prompt + "
            f"{new_tokens} new tokens each); pairs served by the experts "
            f"held, by layer {pairs}")
        # the experts held serve their share of the pairs and no more
        fed = n_requests * (prompt_len + new_tokens - 1) \
            * config["num_experts_per_tok"]
        if min(pairs) <= 0 or max(pairs) >= fed:
            raise AssertionError(f"experts' counters: {pairs} of {fed}")
        served = handle.served_check.remote().result(
            timeout_s=ready_timeout_s)
        log(f"  served tokens against the reference: {served}")
        if not served["ok"] or served["held"] < n_requests:
            raise AssertionError(f"glm5 served-token check: {served}")
        found = {}
        for name in names:
            part = serve_glm5.CONTROLS[name][1]
            found[name] = handle.control.remote(name).result(
                timeout_s=ready_timeout_s)[part]
            log(f"  {name}: {found[name]} against {check[part]}")
            if found[name]["ok"]:
                raise AssertionError(
                    f"the check accepts {name}: {found[name]}")
        planted = handle.served_control.remote().result(
            timeout_s=ready_timeout_s)
        for name in ("other_answer", "one_token"):
            found[name] = planted.get(name)
            log(f"  served check, {name}: {found[name]}")
            if found[name] is None or found[name]["ok"]:
                raise AssertionError(
                    f"the served check accepts {name}: {planted}")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return dict(found, platform=rep["platform"], reference_check=check,
                model_counters=counters, served_check=served)


def phase_serve_sala(platform: str, *, config=None, n_requests: int = 3,
                     prompt_len: int = 8300, new_tokens: int = 12,
                     ready_timeout_s: float = 1500.0) -> dict:
    """The serving phase's sixth case: a matrix state by slot beside
    pages and paged compressed keys.  MiniCPM-SALA at three layers
    (lightning, minicpm4, lightning) of the published widths through the
    benchmark's own replica class: the reference check before the engine
    takes the memory (logits, the lightning state, the selected pages,
    and the three controls, each of which it has to refuse), prompts past
    ``dense_len`` through ``serve.run`` so that decode rows select, the
    state cache's accounting, and the replica's served check: two of the
    answers against the reference at the layers held, and the device's
    count of the pages read against the host's.  ``config`` defaults to
    the benchmark's file."""
    import ray_tpu
    from benchmarks.harness import reference_sala
    from benchmarks.runners import serve_sala
    from ray_tpu import serve

    config = config or _benchmark_config("minicpm_sala_pp2")
    hf = config.get("check_hf") or serve_sala.CHECK_HF
    config = dict(config, **hf, engine=dict(
        config["engine"], max_seq_len=-(-(prompt_len + new_tokens) // 64)
        * 64))
    # every request here is as long as the others: each one "passed"
    config.setdefault("served_plan", {"past": prompt_len})
    ray_tpu.init(ignore_reinit_error=True)
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        app = serve.deployment(
            ray_actor_options=({"num_tpus": chips} if platform == "tpu"
                               else {}),
            max_ongoing_requests=2 * n_requests,
        )(serve_sala.server_class()).bind({"config": config, "seed": 0})
        t0 = time.perf_counter()
        handle = serve.run(app, name="chip_smoke_sala", route_prefix=None,
                           timeout_s=ready_timeout_s)
        rep = handle.device_report.remote().result(timeout_s=60)
        check = rep["check"]
        log(f"  sala replica ready after {time.perf_counter() - t0:.1f}s "
            f"on {rep['platform']}; reference check: "
            + " ".join(f"{k}={check[k]['rel_err_prefill']:.2e}/"
                       f"{check[k]['rel_err_decode']:.2e}"
                       for k in serve_sala.TOLERANCES)
            + f" state={check['lin_state']} selection={check['selection']}"
            + "".join(f" {c}={check[c]}" for c in reference_sala.CONTROLS))
        if rep["platform"] != platform:
            raise AssertionError(
                f"replica computes on {rep['platform']!r}, not "
                f"{platform!r}")
        if not check["ok"]:
            raise AssertionError(f"sala reference check failed: {check}")
        vocab = config["vocab_size"]
        prompts = [[(7 * i + 3 * j) % (vocab - 1) + 1
                    for j in range(prompt_len)] for i in range(n_requests)]
        pending = [handle.remote({"tokens": p, "max_new_tokens": new_tokens,
                                  "temperature": 0.0}) for p in prompts]
        outs = [r.result(timeout_s=ready_timeout_s)["tokens"]
                for r in pending]
        check_answers(outs, new_tokens, vocab)
        counters = handle.counters.remote().result(timeout_s=60)
        state, pages = counters["state_cache"], counters["model_counters"]
        log(f"  {len(outs)} sala requests answered ({prompt_len} prompt + "
            f"{new_tokens} new tokens each); state cache {state}; pages "
            f"read {pages}")
        if state["resets"] < n_requests or state["live"] != 0:
            raise AssertionError(f"state cache accounting: {state}")
        if min(pages["sel_pages"]) <= 0:
            raise AssertionError(f"no page counted as read: {pages}")
        served = handle.served_check.remote().result(
            timeout_s=ready_timeout_s)
        log(f"  sala served check: {served}")
        if not served["ok"]:
            raise AssertionError(f"sala served check failed: {served}")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"platform": rep["platform"], "reference_check": check,
            "state_cache": state, "model_counters": pages,
            "served_check": served}


# ---------------------------------------------------------------------------
# phase: engine_legacy
# ---------------------------------------------------------------------------


def phase_engine_legacy(platform: str, *, cfg=None, slots: int = 64,
                        n_requests: int = 8, prompt_len: int = 128,
                        new_tokens: int = 16, mesh=None,
                        long_prompt: int = 0) -> dict:
    """``LLMEngine`` in-process on the two-program path (batched prefill
    + decode chunks, ``ragged_batching=False``), which is kept because
    it is the only path that runs under a mesh: with ``mesh`` this is
    the tensor-parallel engine (the ``multichip`` phase), and
    ``long_prompt`` adds a prompt long enough to enter the flash kernel
    under shard_map.  No benchmark cell measures this path."""
    import bench
    from ray_tpu.serve.llm_engine import (
        EngineConfig,
        LLMEngine,
        llama_paged_adapter,
    )

    require_platform(platform)
    cfg = cfg or dataclasses.replace(bench.BENCH_CFG, max_seq_len=512)
    params = load_params(cfg, 0)
    eng = LLMEngine(
        params, llama_paged_adapter(cfg),
        EngineConfig(max_slots=slots, max_seq_len=cfg.max_seq_len,
                     decode_chunk=8, page_size=PAGE,
                     max_new_tokens_default=new_tokens,
                     ragged_batching=False),
        mesh=mesh)
    try:
        prompts = fixed_prompts(cfg, n_requests, prompt_len)
        if long_prompt:
            prompts[0] = fixed_prompts(cfg, 1, long_prompt, seed=2)[0]
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=new_tokens, temperature=0.0)
                   for p in prompts]
        outs = [s.result(timeout_s=900) for s in streams]
        check_answers(outs, new_tokens, cfg.vocab_size)
        log(f"  two-program engine"
            f"{'' if mesh is None else ' on mesh ' + str(dict(mesh.shape))}"
            f": {len(outs)} of {n_requests} requests answered (prompts "
            f"{sorted({len(p) for p in prompts})}, {new_tokens} new tokens)"
            f" in {time.perf_counter() - t0:.1f}s, compile included "
            f"(smoke timing)")
    finally:
        eng.shutdown()
        eng._thread.join(timeout=60)
    rel = None
    if mesh is None:
        rel = prefill_logits_check(params, cfg, prompts[0], path="batch")
    return {"prefill_rel_err": rel}


# ---------------------------------------------------------------------------
# phase: multichip
# ---------------------------------------------------------------------------


def phase_multichip(platform: str, *, expect_loss0=None, cfg=None,
                    batch: int = 8, seq: int = 2048, serve_cfg=None,
                    prompt_len: int = 128, long_prompt: int = 512,
                    n: int = 4) -> dict:
    """Four chips in one process: the train phase at fsdp=n (step-0 loss
    equal to the one-device value, memory in use on every device), then
    the tensor-parallel engine answering a prompt long enough for the
    flash kernel."""
    import bench
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.parallel.mesh import create_serving_mesh

    devices = require_platform(platform)[:n]
    if len(devices) < n:
        raise RuntimeError(f"multichip wants {n} devices, found "
                           f"{len(devices)}")
    out = phase_train(platform, cfg=cfg, batch=batch, seq=seq,
                      mesh_spec=MeshSpec(dp=1, fsdp=n), devices=devices,
                      expect_loss0=expect_loss0)
    peaks = out["peak_hbm_bytes"]
    if platform == "tpu" and not all(p and p > 2**28 for p in peaks):
        raise AssertionError(f"fsdp={n} left a device idle: {peaks}")
    serve_cfg = serve_cfg or dataclasses.replace(
        bench.BENCH_CFG, max_seq_len=1024)
    phase_engine_legacy(
        platform, cfg=dataclasses.replace(serve_cfg, tensor_parallel=True),
        slots=16, mesh=create_serving_mesh(1, n, devices=devices),
        prompt_len=prompt_len, long_prompt=long_prompt)
    return out


def _chip_owner_report():
    import jax

    return {"pid": os.getpid(), "platform": jax.devices()[0].platform,
            "count": len(jax.devices()),
            "ids": [d.id for d in jax.devices()],
            "visible": os.environ.get("TPU_VISIBLE_CHIPS")}


def phase_owners(platform: str, *, n: int = 2) -> dict:
    """One owner per chip on a host with several: ``n`` tasks that each
    ask for one chip run at once, each in its own process, each seeing
    exactly one device.  The caller stays off the chip."""
    import ray_tpu
    from ray_tpu.utils import accelerator

    ray_tpu.init(ignore_reinit_error=True)
    try:
        probe = ray_tpu.remote(num_tpus=1, num_cpus=0)(_chip_owner_report)
        reps = ray_tpu.get([probe.remote() for _ in range(n)], timeout=300)
        for rep in reps:
            log(f"  chip owner {rep}")
        if any(r["platform"] != platform or r["count"] != 1 for r in reps):
            raise AssertionError(f"want one {platform} device each: {reps}")
        if len({r["pid"] for r in reps} | {os.getpid()}) != n + 1:
            raise AssertionError(f"owners must be separate processes: "
                                 f"{reps}")
        if accelerator.backend_initialised():
            raise AssertionError("the caller initialised a JAX backend")
    finally:
        ray_tpu.shutdown()
    return {}


# ---------------------------------------------------------------------------
# child: one phase in its own process
# ---------------------------------------------------------------------------


def run_child(phase: str, expect_loss0) -> int:
    t0 = time.perf_counter()
    import jax

    from ray_tpu.utils import accelerator

    report: dict = {"phase": phase}
    if phase in ("serve", "owners"):
        # The caller of serve.run / ray_tpu.init must stay off the chip.
        # Pinned all the same: if it ever touches JAX it takes the chip,
        # the replica cannot get it, and the phase fails.
        jax.config.update("jax_platforms", "tpu")
        fn = phase_serve if phase == "serve" else phase_owners
        report.update(fn("tpu"))
        if phase == "serve":
            report["jamba"] = phase_serve_jamba("tpu")
            report["brumby"] = phase_serve_brumby("tpu")
            report["xing"] = phase_serve_xing("tpu")
            report["glm5"] = phase_serve_glm5("tpu")
            report["sala"] = phase_serve_sala("tpu")
    else:
        clock = CompileClock()
        try:
            held = accelerator.claim_tpu()
        except RuntimeError as e:
            jax.config.update("jax_platforms", "cpu")
            found = jax.devices()[0]
            log(f"chip_smoke[{phase}]: no TPU for this process; JAX finds "
                f"platform={found.platform!r} device_kind="
                f"{found.device_kind!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS')!r}): {e}")
            return 3
        log(f"  {phase} pid={os.getpid()} "
            + " ".join(f"{k}={v}" for k, v in held.items()))
        report["device"] = {"platform": held["platform"],
                            "kind": held["device_kind"],
                            "count": held["count"]}
        fn = {"kernels": phase_kernels, "train": phase_train,
              "engine_legacy": phase_engine_legacy,
              "multichip": functools.partial(
                  phase_multichip, expect_loss0=expect_loss0)}[phase]
        report.update(fn("tpu"))
        report.update(clock.report())
    report["wall_s"] = round(time.perf_counter() - t0, 1)
    log(REPORT_TAG + json.dumps(report))
    return 0


# ---------------------------------------------------------------------------
# parent: no JAX, one child at a time
# ---------------------------------------------------------------------------


def run_phase(phase: str, deadline: float, extra=()) -> dict:
    """Run one phase as a child in its own process group, pass its
    output through, and leave no process behind.  Returns the child's
    report; raises SystemExit(child's code) when it fails."""
    log(f"== phase {phase}")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         *extra],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    report = None
    timer = None
    try:
        timer = threading.Timer(max(1.0, deadline - time.monotonic()),
                                lambda: os.killpg(proc.pid, signal.SIGKILL))
        timer.start()
        for line in proc.stdout:
            if line.startswith(REPORT_TAG):
                report = json.loads(line[len(REPORT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        if timer is not None:
            timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)   # workers it left behind
        except ProcessLookupError:
            pass
    if rc != 0 or report is None:
        log(f"chip_smoke: phase {phase} FAILED (exit code {rc})")
        raise SystemExit(rc or 1)
    log(f"== phase {phase} ok: {timing(report)}")
    return report


def timing(report: dict) -> str:
    """One phase's smoke timing; compile seconds where the phase has a
    process that compiles (``owners`` does not report any)."""
    if "compile_s" not in report:
        return f"wall {report['wall_s']}s"
    return (f"wall {report['wall_s']}s, compile {report['compile_s']}s, "
            f"persistent-cache hits {report['cache_hits']} misses "
            f"{report['cache_misses']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=PHASES + ("owners",),
                    help="run one phase in THIS process (what the parent "
                         "starts; also useful alone)")
    ap.add_argument("--expect-loss0", type=float, default=None,
                    help="multichip: the one-device step-0 loss")
    args = ap.parse_args(argv)
    if args.phase:
        return run_child(args.phase, args.expect_loss0)

    if "jax" in sys.modules:
        raise RuntimeError("the chip_smoke parent must not import JAX: it "
                           "would hold the chip its children need")
    t0 = time.monotonic()
    deadline = t0 + BUDGET_S
    reports = {}
    for phase in PHASES:
        if phase == "multichip":
            count = reports["kernels"]["device"]["count"]
            if count < 4:
                log(f"== multichip: not run ({count} devices)")
                continue
            reports["owners"] = run_phase("owners", deadline)
            reports[phase] = run_phase(
                phase, deadline,
                ("--expect-loss0", repr(reports["train"]["loss0"])))
        else:
            reports[phase] = run_phase(phase, deadline)
    log("== smoke timings (not measurements): "
        + "; ".join(f"{p}: {timing(r)}" for p, r in reports.items())
        + f"; total {time.monotonic() - t0:.0f}s")
    devices = {json.dumps(r["device"], sort_keys=True)
               for r in reports.values() if "device" in r}
    if len(devices) != 1:
        log(f"chip_smoke: phases disagree about the device: {devices}")
        return 1
    print(json.dumps({"ok": True, "device": reports["kernels"]["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    # Re-enter as the importable module ``chip_smoke``: what the serve
    # phase ships to its replica is then pickled by reference to a module
    # the worker can import, not by value out of ``__main__``.
    import chip_smoke

    sys.exit(chip_smoke.main())
