"""Compile the ``bench.py``-shaped serving and training steps for a TPU
v5e WITHOUT a chip, on one device and on the four-device meshes (how:
tests/mosaic_aot.py)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from ray_tpu.models import llama, quant
from ray_tpu.parallel.mesh import MeshSpec, create_mesh, create_serving_mesh
from tests.mosaic_aot import (  # noqa: F401 (fixtures)
    PAGE, _assert_fused_layer_grid_follows_the_rows, _compile, _on, _one,
    _sds, bench, mosaic_not_interpreter, v5e,
)

pytestmark = pytest.mark.long_file(289)


# -- the serving shapes bench.py measures -----------------------------------

def _serving_shapes(bench):
    cfg8 = dataclasses.replace(bench.BENCH_8B_CFG, fused_decode=False)
    return {
        "319m": (dataclasses.replace(bench.BENCH_CFG, max_seq_len=512), 64),
        "1b": (dataclasses.replace(bench.BENCH_1B_CFG, max_seq_len=512), 32),
        "8b_int8": (cfg8, 48),
    }


def _abstract_params(cfg, int8_weights: bool):
    def make():
        p = llama.init_params(jax.random.key(0), cfg)
        if int8_weights:
            p = quant.fuse_for_decode(
                quant.quantize_params(p, cast_rest=cfg.dtype), cfg)
        return p

    return jax.eval_shape(make)


def _abstract_cache(cfg, slots):
    maxp = cfg.max_seq_len // PAGE
    return jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, slots * maxp, PAGE)), maxp


SERVE_CASES = [("319m", False), ("319m", True), ("1b", False),
               ("8b_int8", True)]


def _serve_setup(bench, v5e, name, kv_int8):
    cfg, slots = _serving_shapes(bench)[name]
    cfg = dataclasses.replace(cfg, kv_int8=kv_int8)
    mesh = _one(v5e)
    params = _on(mesh, _abstract_params(cfg, name == "8b_int8"))
    cache, maxp = _abstract_cache(cfg, slots)
    return cfg, slots, maxp, mesh, params, _on(mesh, cache)


# -- the model steps the engine and the trainer jit -------------------------

@pytest.mark.parametrize("name,kv_int8", SERVE_CASES)
def test_decode_step(bench, v5e, name, kv_int8):
    cfg, slots, maxp, mesh, params, cache = _serve_setup(
        bench, v5e, name, kv_int8)
    ints, bt, active = _on(mesh, (
        _sds(slots, dtype=jnp.int32), _sds(slots, maxp, dtype=jnp.int32),
        _sds(slots, dtype=jnp.bool_)))
    _compile(lambda p, t, a, b, l, c: llama.decode_slots_paged(
        p, t, a, b, l, cfg, c), params, ints, active, bt, ints, cache,
        donate_argnums=(5,))


@pytest.mark.parametrize("name,kv_int8", SERVE_CASES)
def test_ragged_step(bench, v5e, name, kv_int8):
    cfg, slots, maxp, mesh, params, cache = _serve_setup(
        bench, v5e, name, kv_int8)
    T = slots + PAGE                      # EngineConfig.token_budget=0
    toks, rows, bt, idx = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, maxp, dtype=jnp.int32), _sds(40, dtype=jnp.int32)))
    _compile(lambda p, t, pos, rs, r0, rl, ro, b, c:
             llama.ragged_step_paged(p, t, pos, rs, r0, rl, ro, b, cfg, c),
             params, toks, toks, rows, rows, rows, rows, bt, cache,
             donate_argnums=(8,))
    # speculative verify rows: extra logits at logit_idx
    _compile(lambda p, t, pos, rs, r0, rl, ro, b, c, li:
             llama.ragged_step_paged(p, t, pos, rs, r0, rl, ro, b, cfg, c,
                                     logit_idx=li),
             params, toks, toks, rows, rows, rows, rows, bt, cache, idx,
             donate_argnums=(8,))


@pytest.mark.parametrize("name,kv_int8,prompt", [
    ("319m", False, 128), ("319m", False, 512), ("1b", False, 512),
    ("8b_int8", True, 128)])
def test_prefill_batch(bench, v5e, name, kv_int8, prompt):
    cfg, slots, maxp, mesh, params, cache = _serve_setup(
        bench, v5e, name, kv_int8)
    K = 4
    toks, lens, pages = _on(mesh, (
        _sds(K, prompt, dtype=jnp.int32), _sds(K, dtype=jnp.int32),
        _sds(K, maxp, dtype=jnp.int32)))
    _compile(lambda p, t, n, pg, c: llama.prefill_batch_paged(
        p, t, n, pg, cfg, c), params, toks, lens, pages, cache,
        donate_argnums=(4,))


def test_prefill_long_prompt(bench, v5e):
    """The long_rag / bursty mixes' 1536-token prompts (max_seq 2048)."""
    cfg = dataclasses.replace(bench.BENCH_CFG, max_seq_len=2048)
    mesh = _one(v5e)
    params = _on(mesh, _abstract_params(cfg, False))
    cache, maxp = _abstract_cache(cfg, 8)
    toks, lens, pages = _on(mesh, (
        _sds(2, 1536, dtype=jnp.int32), _sds(2, dtype=jnp.int32),
        _sds(2, maxp, dtype=jnp.int32)))
    _compile(lambda p, t, n, pg, c: llama.prefill_batch_paged(
        p, t, n, pg, cfg, c), params, toks, lens, pages, _on(mesh, cache),
        donate_argnums=(4,))


def _train_step(bench, cfg, mesh, batch, optimizer=None):
    """The jitted step JaxTrainer builds, and an abstract (state, batch)."""
    from ray_tpu.train.state import create_train_state
    from ray_tpu.train.step import compile_train_step

    tx = optimizer or bench.default_optimizer(
        1e-4, warmup_steps=10, mu_dtype=jnp.bfloat16)
    with mesh:
        state = jax.eval_shape(lambda: create_train_state(
            llama.init_params(jax.random.key(0), cfg), tx))
        step, _state_sh, _batch_sh = compile_train_step(
            mesh, lambda p, b: llama.loss_fn(p, b, cfg), tx, state,
            llama.logical_axes(cfg), {"tokens": ("batch", None)})
    # The jit carries in_shardings over the topology's devices, so the
    # abstract arguments need none of their own.
    tokens = {"tokens": _sds(batch, bench.SEQ, dtype=jnp.int32)}
    return step.__wrapped__, state, tokens


@pytest.mark.parametrize("which,batch", [
    ("BENCH_CFG", 8), ("BENCH_1B_CFG", 8), ("BENCH_2B_CFG", 4)])
def test_train_step_one_device(bench, v5e, which, batch):
    from ray_tpu.train import adamw8bit

    mesh = create_mesh(MeshSpec(dp=1), devices=v5e[:1])
    opt = (adamw8bit(1e-4, warmup_steps=10)
           if which == "BENCH_2B_CFG" else None)
    step, state, tokens = _train_step(
        bench, getattr(bench, which), mesh, batch, opt)
    with mesh:
        compiled = (step.trace(state, tokens)
                    .lower(lowering_platforms=("tpu",)).compile())
    # Printed, not asserted: this sum says 15.6 GiB for the 319M step,
    # whose peak_bytes_in_use on the chip is 3.6 GiB (chip_smoke, PR 21),
    # so it is no predictor of what fits; memory_stats() is.
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"{which} B={batch}: compiler memory analysis "
          f"{used / 2**30:.2f} GiB")


# -- four devices: the flash kernel under dp/fsdp/tp -------------------------

def test_train_step_fsdp4(bench, v5e):
    """JaxTrainer with no ScalingConfig takes every device with fsdp."""
    mesh = create_mesh(MeshSpec(dp=1, fsdp=4), devices=v5e)
    step, state, tokens = _train_step(bench, bench.BENCH_CFG, mesh, 8)
    with mesh:
        step.trace(state, tokens).lower(
            lowering_platforms=("tpu",)).compile()


def test_train_step_tp_and_dp(bench, v5e):
    mesh = create_mesh(MeshSpec(dp=2, tp=2), devices=v5e)
    step, state, tokens = _train_step(bench, bench.BENCH_CFG, mesh, 8)
    with mesh:
        step.trace(state, tokens).lower(
            lowering_platforms=("tpu",)).compile()


def _tp4_setup(bench, v5e, kv_int8=False):
    cfg = dataclasses.replace(bench.BENCH_CFG, max_seq_len=1024,
                              tensor_parallel=True, kv_int8=kv_int8)
    mesh = create_serving_mesh(1, 4, devices=v5e)
    place = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    params = _abstract_params(cfg, False)
    # parameter shardings as shard_params_for_serving would place them
    from ray_tpu.parallel.sharding import spec_for

    rules = llama._SERVING_RULES
    axes = frozenset(mesh.axis_names)
    params = jax.tree.map(
        lambda ax, leaf: place(leaf, NamedSharding(
            mesh, spec_for(ax, rules, mesh_axes=axes))),
        llama.logical_axes(cfg), params,
        is_leaf=lambda x: isinstance(x, tuple))
    slots = 16
    cache, maxp = _abstract_cache(cfg, slots)
    cache = jax.tree.map(place, cache, llama.paged_cache_shardings(
        mesh, kv_int8=kv_int8))
    return cfg, mesh, params, cache, slots, maxp


@pytest.mark.parametrize("kv_int8", [False, True])
def test_decode_step_tp4(bench, v5e, kv_int8):
    cfg, mesh, params, cache, slots, maxp = _tp4_setup(bench, v5e, kv_int8)
    ints, bt, active = _on(mesh, (
        _sds(slots, dtype=jnp.int32), _sds(slots, maxp, dtype=jnp.int32),
        _sds(slots, dtype=jnp.bool_)))
    _compile(lambda p, t, a, b, l, c: llama.decode_slots_paged(
        p, t, a, b, l, cfg, c), params, ints, active, bt, ints, cache,
        mesh=mesh, donate_argnums=(5,))


def test_prefill_512_tp4(bench, v5e):
    """128 compiles even unsharded (_flash_eligible needs S >= 256);
    512 is the length that enters the flash kernel."""
    cfg, mesh, params, cache, slots, maxp = _tp4_setup(bench, v5e)
    toks, lens, pages = _on(mesh, (
        _sds(2, 512, dtype=jnp.int32), _sds(2, dtype=jnp.int32),
        _sds(2, maxp, dtype=jnp.int32)))
    _compile(lambda p, t, n, pg, c: llama.prefill_batch_paged(
        p, t, n, pg, cfg, c), params, toks, lens, pages, cache,
        mesh=mesh, donate_argnums=(4,))


# -- the fused megakernel ---------------------------------------------------

@pytest.mark.parametrize("name,kv_int8", [("319m", False),
                                          ("8b_int8", True)])
def test_fused_decode_step(bench, v5e, name, kv_int8):
    cfg, slots, maxp, mesh, params, cache = _serve_setup(
        bench, v5e, name, kv_int8)
    cfg = dataclasses.replace(cfg, fused_decode=True)
    ints, bt, active = _on(mesh, (
        _sds(slots, dtype=jnp.int32), _sds(slots, maxp, dtype=jnp.int32),
        _sds(slots, dtype=jnp.bool_)))
    _compile(lambda p, t, a, b, l, c: llama.decode_slots_paged(
        p, t, a, b, l, cfg, c), params, ints, active, bt, ints, cache,
        donate_argnums=(5,))


@pytest.mark.parametrize("name,kv_int8", [("319m", False),
                                          ("8b_int8", True)])
def test_fused_ragged_step(bench, v5e, name, kv_int8):
    cfg, slots, maxp, mesh, params, cache = _serve_setup(
        bench, v5e, name, kv_int8)
    cfg = dataclasses.replace(cfg, fused_decode=True)
    T = slots + PAGE
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, maxp, dtype=jnp.int32)))

    def step(p, t, pos, rs, r0, rl, ro, b, c):
        return llama.ragged_step_paged(p, t, pos, rs, r0, rl, ro, b, cfg, c)

    args = (params, toks, toks, rows, rows, rows, rows, bt, cache)
    _compile(step, *args, donate_argnums=(8,))
    _assert_fused_layer_grid_follows_the_rows(step, *args)
