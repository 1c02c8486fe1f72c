"""8B int8 decode-step roofline profiler (VERDICT r4 item 2).

Builds the Llama-3-8B config with random int8 weights on the real
chip, jits the paged decode step, and decomposes time per decode step:

  - in-jit scan of K steps  → device time per step (dispatch amortized)
  - single-step dispatches  → host+dispatch overhead per step
  - compiled memory analysis → does the dequant materialize bf16?

Run: python release/profile_8b_decode.py [--slots 8] [--layers 32]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--pages", type=int, default=384)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--kv-int8", action="store_true", default=False)
    ap.add_argument("--fuse", action="store_true", default=False)
    args = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.models.quant import quantize_params

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")

    cfg = dataclasses.replace(
        llama.LLAMA3_8B, n_layers=args.layers,
        max_seq_len=args.pages * args.page_size // max(1, args.slots),
        kv_int8=args.kv_int8,
    )
    print(f"config: L={cfg.n_layers} dim={cfg.dim} heads={cfg.n_heads} "
          f"kv={cfg.n_kv_heads} mlp={cfg.mlp_dim} vocab={cfg.vocab_size}")

    # Random int8 params built ON HOST (1 layer, broadcast to L —
    # identical layers are fine for bandwidth measurement), streamed to
    # the chip once; building on device leaves fp32 temps that eat HBM.
    t0 = time.time()
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params = llama.init_params(jax.random.key(0), dataclasses.replace(
            cfg, n_layers=1))
        qparams = quantize_params(params, cast_rest=jnp.bfloat16)
        del params
        qparams = jax.tree.map(np.asarray, qparams)
    qparams["layers"] = jax.tree.map(
        lambda x: np.broadcast_to(x, (cfg.n_layers,) + x.shape[1:]),
        qparams["layers"])
    qparams = jax.device_put(qparams, dev)
    jax.block_until_ready(jax.tree.leaves(qparams)[0])
    if args.fuse:
        from ray_tpu.models.quant import fuse_for_decode

        qparams = fuse_for_decode(qparams, cfg)
        jax.block_until_ready(jax.tree.leaves(qparams)[0])
    int8_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(qparams))
    print(f"weights resident: {int8_bytes / 1e9:.2f} GB "
          f"({time.time() - t0:.1f}s to build)")

    cache = llama.init_paged_cache(cfg, args.pages, args.page_size)
    kv_bytes = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(cache))
    print(f"kv pool: {kv_bytes / 1e9:.2f} GB "
          f"({args.pages} pages x {args.page_size})")

    slots = args.slots
    maxp = args.pages // slots
    bt = jnp.asarray(
        np.arange(args.pages, dtype=np.int32).reshape(slots, maxp)
        % args.pages)
    lengths = jnp.full((slots,), 128, jnp.int32)
    tokens = jnp.ones((slots,), jnp.int32)
    active = jnp.ones((slots,), bool)

    def one_step(params, cache, tokens, lengths):
        logits, cache, new_len = llama.decode_slots_paged(
            params, tokens, active, bt, lengths, cfg, cache)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, new_len

    def k_steps(k, params, cache, tokens, lengths):
        def body(carry, _):
            toks, cache, lens = carry
            toks, cache, lens = one_step(params, cache, toks, lens)
            return (toks, cache, lens), ()

        (toks, cache, lens), _ = jax.lax.scan(
            body, (tokens, cache, lengths), None, length=k)
        return toks, cache, lens

    jit_k = jax.jit(k_steps, static_argnums=(0,), donate_argnums=(2,))
    jit_1 = jax.jit(one_step, donate_argnums=(1,))

    # Compile + memory analysis.
    t0 = time.time()
    lowered = jit_k.lower(args.steps, qparams, cache, tokens, lengths)
    compiled = lowered.compile()
    print(f"compile: {time.time() - t0:.1f}s")
    try:
        ma = compiled.memory_analysis()
        print(f"memory: args={ma.argument_size_in_bytes / 1e9:.2f} GB "
              f"out={ma.output_size_in_bytes / 1e9:.2f} GB "
              f"temp={ma.temp_size_in_bytes / 1e9:.3f} GB")
        if ma.temp_size_in_bytes > 2e9:
            print("WARNING: temp > 2 GB — dequant is materializing "
                  "bf16 weights instead of fusing into the matmuls")
    except Exception as e:
        print(f"(memory analysis unavailable: {e})")

    # Warm.
    toks, cache2, lens = compiled(qparams, cache, tokens, lengths)
    float(jax.device_get(toks[0]))  # fence: a host read of a value the step produced

    # K steps inside one dispatch → device time per step.
    t0 = time.perf_counter()
    toks, cache2, lens = compiled(qparams, cache2, toks, lens)
    float(jax.device_get(toks[0]))
    per_step_scan = (time.perf_counter() - t0) / args.steps
    print(f"in-scan decode step: {per_step_scan * 1000:.2f} ms "
          f"→ {slots / per_step_scan:.0f} tok/s at {slots} slots")

    # Single-step dispatches → host/dispatch overhead.
    toks1, cache3, lens1 = jit_1(qparams, cache2, toks, lens)
    float(jax.device_get(toks1[0]))
    n1 = 8
    t0 = time.perf_counter()
    for _ in range(n1):
        toks1, cache3, lens1 = jit_1(qparams, cache3, toks1, lens1)
    float(jax.device_get(toks1[0]))
    per_step_single = (time.perf_counter() - t0) / n1
    print(f"single-dispatch step: {per_step_single * 1000:.2f} ms "
          f"(dispatch overhead {1000 * (per_step_single - per_step_scan):.2f} ms)")

    # Roofline: weight bytes per step / HBM bandwidth (v5e ~819 GB/s).
    bw = 819e9
    bound = int8_bytes / bw
    print(f"weight-read bound: {bound * 1000:.2f} ms/step "
          f"→ roofline {slots / bound:.0f} tok/s; achieved "
          f"{100 * bound / per_step_scan:.0f}% of roofline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
