"""Compile every Pallas kernel, and the model steps that carry them,
for a TPU v5e WITHOUT a chip.

libtpu can describe a topology it does not have
(``jax.experimental.topologies``), and ``jit(...).trace(...).lower(
lowering_platforms=("tpu",)).compile()`` then runs the real Mosaic and
XLA:TPU compilers against abstract arguments placed on that topology's
devices.  Every other test runs the kernels through the Pallas
interpreter, which accepts programs Mosaic refuses; this file is the
off-chip guard that the programs ``chip_smoke.py`` runs still compile,
at the widths the benchmark uses, on one device and on four.  It costs
no chip time.  What it cannot see is a wrong answer: that is the smoke's
``kernels`` phase.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama, quant
from ray_tpu.ops import platform
from ray_tpu.parallel.mesh import MeshSpec, create_mesh, create_serving_mesh

REPO = pathlib.Path(__file__).resolve().parent.parent
PAGE = 64


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def v5e():
    return topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices


@pytest.fixture(autouse=True)
def mosaic_not_interpreter(monkeypatch):
    monkeypatch.setattr(platform, "interpret_mode", lambda: False)


def _on(mesh, tree, spec=P()):
    """Abstract arguments placed on ``mesh`` (replicated unless told)."""
    sh = NamedSharding(mesh, spec)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)


def _compile(fn, *args, mesh=None, **jit_kw):
    with mesh if mesh is not None else contextlib.nullcontext():
        return (jax.jit(fn, **jit_kw).trace(*args)
                .lower(lowering_platforms=("tpu",)).compile())


def _one(v5e):
    return Mesh(np.array(v5e[:1]), ("x",))


def _sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


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


# -- kernels, one by one ----------------------------------------------------

@pytest.mark.parametrize("B,S,H,KVH", [
    (8, 2048, 8, 4),
    (4, 4096, 16, 8),    # internlm2_1b8-pretrain_4k: 4 MB of dq in VMEM
])
def test_flash_forward_and_backward(v5e, B, S, H, KVH):
    from ray_tpu.ops.flash_attention import flash_attention

    mesh = _one(v5e)
    q = _on(mesh, _sds(B, S, H, 128))
    kv = _on(mesh, _sds(B, S, KVH, 128))
    _compile(flash_attention, q, kv, kv)
    _compile(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("kv_int8", [False, True])
def test_paged_decode_kernels(v5e, kv_int8):
    from ray_tpu.ops import paged_attention as pa

    mesh = _one(v5e)
    L, KVH, H, D, slots, maxp = 4, 8, 32, 128, 48, 4
    Pn = slots * maxp + 1
    pool = _sds(L, KVH, Pn, PAGE, D,
                dtype=jnp.int8 if kv_int8 else jnp.bfloat16)
    scales = _sds(L, Pn, KVH, 1, dtype=jnp.float32)
    q = _sds(slots, H, D)
    new = _sds(L, slots, KVH, D)
    bt = _sds(slots, maxp, dtype=jnp.int32)
    ints = _sds(slots, dtype=jnp.int32)
    ly = _sds(dtype=jnp.int32)
    a = lambda *xs: _on(mesh, xs)
    if kv_int8:
        _compile(lambda q, k, v, ks, vs, ly, bt, ln:
                 pa.paged_decode_attention_partial(
                     q, k, v, ly, bt, ln, k_scales=ks, v_scales=vs),
                 *a(q, pool, pool, scales, scales, ly, bt, ints))
        _compile(pa.paged_append_quantized,
                 *a(pool, pool, scales, scales, new, new, ints, ints))
    else:
        _compile(pa.paged_decode_attention_partial,
                 *a(q, pool, pool, ly, bt, ints))
        _compile(pa.paged_append, *a(pool, pool, new, new, ints, ints))
        page_pool = _sds(KVH, Pn, PAGE, D)
        _compile(pa.paged_decode_attention,
                 *a(q, page_pool, page_pool, bt, ints))


@pytest.mark.parametrize("kv_int8", [False, True])
def test_ragged_kernels(v5e, kv_int8):
    from ray_tpu.ops import ragged_paged_attention as rpa

    mesh = _one(v5e)
    L, KVH, H, D, R, maxp, T = 4, 8, 32, 128, 48, 4, 112
    Pn = R * maxp + 1
    pool = _sds(L, KVH, Pn, PAGE, D,
                dtype=jnp.int8 if kv_int8 else jnp.bfloat16)
    scales = _sds(L, Pn, KVH, 1, dtype=jnp.float32)
    q = _sds(T, H, D)
    kv = _sds(T, KVH, D)
    new = _sds(L, T, KVH, D)
    rows = _sds(R, dtype=jnp.int32)
    bt = _sds(R, maxp, dtype=jnp.int32)
    ly = _sds(dtype=jnp.int32)
    a = lambda *xs: _on(mesh, xs)
    if kv_int8:
        _compile(lambda q, k, v, kp, vp, ks, vs, ly, rs, r0, rl, ro, bt:
                 rpa.ragged_paged_attention(
                     q, k, v, kp, vp, ly, rs, r0, rl, ro, bt,
                     k_scales=ks, v_scales=vs),
                 *a(q, kv, kv, pool, pool, scales, scales, ly,
                    rows, rows, rows, rows, bt))
        _compile(rpa.ragged_paged_append_quantized,
                 *a(pool, pool, scales, scales, new, new,
                    rows, rows, rows, rows, bt))
    else:
        _compile(rpa.ragged_paged_attention,
                 *a(q, kv, kv, pool, pool, ly, rows, rows, rows, rows, bt))
        _compile(rpa.ragged_paged_append,
                 *a(pool, pool, new, new, rows, rows, rows, rows, bt))


@pytest.mark.parametrize("T, H, KVH, R, maxp, pages, kv_int8", [
    (64, 20, 1, 64, 24, 1537, False),       # Jamba, the decode shape
    (320, 20, 1, 64, 24, 1537, False),      # Jamba, the budget
    (272, 32, 8, 16, 40, 897, True),        # a llama-8B class, unfused
], ids=["jamba-64", "jamba-320", "llama-int8"])
def test_ragged_attention_both_calls(v5e, T, H, KVH, R, maxp, pages, kv_int8):
    """Both calls of ``ragged_paged_attention`` at the widths the cells
    run: the one-token call's stacked heads and the chunk call's whole
    window fit VMEM, and the live list fits SMEM beside the block table
    (and, for int8 pools, the two tables of page scales)."""
    from ray_tpu.ops import ragged_paged_attention as rpa

    mesh = _one(v5e)
    D = 128
    pool = _sds(2, KVH, pages, PAGE, D,
                dtype=jnp.int8 if kv_int8 else jnp.bfloat16)
    scales = _sds(2, pages, KVH, 1, dtype=jnp.float32)
    rows = _sds(R, dtype=jnp.int32)
    args = _on(mesh, (_sds(T, H, D), _sds(T, KVH, D), _sds(T, KVH, D), pool,
                      pool, _sds(dtype=jnp.int32), rows, rows, rows, rows,
                      _sds(R, maxp, dtype=jnp.int32), scales, scales))

    def attend(q, k, v, kp, vp, ly, rs, r0, rl, ro, bt, ks, vs):
        return rpa.ragged_paged_attention(
            q, k, v, kp, vp, ly, rs, r0, rl, ro, bt,
            k_scales=ks if kv_int8 else None,
            v_scales=vs if kv_int8 else None)

    text = _compile(attend, *args).as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 2


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


def test_adamw8bit_update_moves_no_leaf(v5e):
    """The optimizer alone on three of internlm2_1b8-pretrain_4k's leaf
    shapes: one kernel a leaf in the leaf's own layout.  Round it no
    loop over segments, no copy and no reshape that is not a bitcast of
    anything the size of a leaf (the flat ``[nb, 256]`` form cost seven
    passes over the parameters, PERF.md PR 48), and next to no
    temporaries (that form: 2.26 GiB for 1.3 GiB of leaves)."""
    from ray_tpu.train import adamw8bit
    from ray_tpu.train.step import apply_gradients

    mesh = _one(v5e)
    params = _on(mesh, {"w_gate": _sds(24, 2048, 8192),
                        "wq": _sds(24, 2048, 16, 128),
                        "lm_head": _sds(2048, 92544)})
    tx = adamw8bit(1e-4, warmup_steps=10)
    state = _on(mesh, jax.eval_shape(tx.init, params))

    def update(params, grads, state):
        with jax.named_scope("optimizer"):
            return apply_gradients(tx, grads, state, params)

    compiled = _compile(update, params, params, state, mesh=mesh,
                        donate_argnums=(0, 2))
    text = compiled.as_text()
    assert len(re.findall(r"%optimizer[.\d]* = .* custom-call\(", text)) == 3
    assert " while(" not in text
    width = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2}
    moved = []
    for line in text.splitlines():
        op = re.search(r" (copy|copy-start|reshape|transpose)\(", line)
        shape = re.search(r"= \(?(\w+)\[([\d,]*)\]", line)
        if op and shape and (
                np.prod([int(d) for d in shape.group(2).split(",") if d])
                * width.get(shape.group(1), 4) >= 16 * 2**20):
            moved.append(line.strip()[:160])
    assert not moved, moved
    leaves = sum(np.prod(p.shape) * 2 for p in params.values())
    assert compiled.memory_analysis().temp_size_in_bytes < leaves / 10


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


def _pallas_grids(jaxpr, name):
    """The grid of every ``pallas_call`` called ``name`` anywhere in
    ``jaxpr``; a bound the call takes as an operand reads None."""
    found = []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_grids(sub, name)
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            found.append(tuple(
                b if isinstance(b, int) else None
                for b in eqn.params["grid_mapping"].grid))
    return found


def _assert_fused_layer_grid_follows_the_rows(step, *args):
    """The fused layer kernel's one grid bound is an operand of the call
    (the step's live cells plus the weight tiles), not the page table's
    capacity: a fall-back to the static bound on the chip fails here."""
    grids = _pallas_grids(jax.make_jaxpr(step)(*args).jaxpr,
                          "fused_ragged_layer")
    assert grids == [(None,)], grids


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


# -- the benchmark's chat cell: weights read where they are stored ----------

_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = \(?(\w+)\[([\d,]*)\][^ ]* "
    r"(dynamic-slice|copy|concatenate)\(", re.M)


def weight_sized_int8_copies(hlo_text: str, min_bytes: int = 4 * 2**20):
    """(opcode, shape) of every dynamic-slice, copy and concatenate in a
    compiled module's text, in any computation, whose result is int8 and
    at least ``min_bytes`` large: a layer's weight made a second time."""
    return [(op, dims) for dt, dims, op in _HLO_RESULT.findall(hlo_text)
            if dt == "s8"
            and np.prod([int(d) for d in dims.split(",") if d]) >= min_bytes]


def _step_shapes(token_budget, max_slots):
    """The two shapes ``LLMEngine`` compiles of the ragged step."""
    from ray_tpu.serve.llm_engine import ragged_step_shapes

    small, budget = ragged_step_shapes(token_budget, max_slots)
    return {"budget": budget, "small": small}


def _chat_cell(v5e):
    """``mistral7b_w8-chat`` as the benchmark runs it, abstract on one
    v5e device: the model config, the engine's settings, the mesh, the
    fused int8 artifact and the int8 page cache."""
    from benchmarks.runners.common import model_config

    config = json.loads(
        (REPO / "benchmarks/configs/mistral7b_w8.json").read_text())
    cfg, eng = model_config(config), config["engine"]
    assert cfg.fused_decode and cfg.kv_int8
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: quant.fuse_for_decode(
            quant.init_quantized_llama(jax.random.key(0), cfg), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, eng["num_pages"],
                                       eng["page_size"])))
    return cfg, eng, mesh, params, cache


@pytest.mark.parametrize("shape", ["budget", "small"])
def test_chat_cell_step_copies_no_weight(v5e, shape):
    """``mistral7b_w8-chat`` as the benchmark runs it: Mistral-7B widths,
    the fused int8 artifact, 16 slots of 40 pages, a token budget of 80
    and the engine's small shape of 16 positions for steps that carry no
    prompt chunk, int8 KV pages.  The fused layer kernel takes the
    stacked weights whole, so the compiled step holds no operation that
    writes a layer's int8 weight again, inside the layer loop or hoisted out of it (a
    reshape of a stacked leaf that stopped being a bitcast would be).
    Before PR 25 it held four a layer, 16.6 ms of a 46 ms step."""
    cfg, eng, mesh, params, cache = _chat_cell(v5e)
    slots, page = eng["max_slots"], eng["page_size"]
    maxp = eng["max_seq_len"] // page
    shapes = _step_shapes(slots + page, slots)
    assert (slots, maxp, shapes) == (16, 40, {"budget": 80, "small": 16})
    T = shapes[shape]
    assert llama.ragged_weight_routes(params, cfg)["sliced"] == [
        "ln_attn", "ln_mlp"]
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, maxp, dtype=jnp.int32)))

    def step(p, t, pos, rs, r0, rl, ro, b, c):
        return llama.ragged_step_paged(p, t, pos, rs, r0, rl, ro, b, cfg, c)

    args = (params, toks, toks, rows, rows, rows, rows, bt, cache)
    compiled = _compile(step, *args, donate_argnums=(8,))
    _assert_fused_layer_grid_follows_the_rows(step, *args)
    text = compiled.as_text()
    assert "fused_ragged_layer" in text
    assert weight_sized_int8_copies(text) == []
    # the reader does find what it looks for: a slice of the stack in
    # front of the kernel, as the step had it before
    before = ("  %dynamic_slice.123 = s8[1,4096,28672]{2,1,0:T(8,128)(4,1)}"
              " dynamic-slice(%param_0.1, %p, %c, %c)\n"
              "  ROOT %copy.3 = s8[32,4096,4096]{2,1,0} copy(%bitcast.30)\n"
              "  %copy.52 = bf16[80,4096]{1,0} copy(%get-tuple-element.7)\n")
    assert weight_sized_int8_copies(before) == [
        ("dynamic-slice", "1,4096,28672"), ("copy", "32,4096,4096")]


@pytest.mark.parametrize("T", [16, 80])
def test_fused_layer_kernel_at_chat_widths(v5e, T):
    """``fused_ragged_layer`` alone at the chat cell's widths (32 query
    heads over 8 KV heads of 128, int8 weights, int8 pools of 64-token
    pages, 16 slots of 40 pages) at the two step shapes the cell runs.
    Its attention phase stacks a KV head's four query heads into the
    rows of one product: 8 rows for a row of one token, 4 x the step's
    window for a chunk row (the engine names no longest row, so the
    window is the buffer: 320 rows at 80 positions), whose flash state
    and unrolled KV heads have to fit the 48 MiB the call states.  One
    Mosaic call, its grid bound an operand."""
    from ray_tpu.ops import ragged_paged_attention as rpa

    cfg, eng, mesh, params, cache = _chat_cell(v5e)
    slots = eng["max_slots"]
    maxp = eng["max_seq_len"] // eng["page_size"]
    x, rope, rows, bt, ly = _on(mesh, (
        _sds(T, cfg.dim), _sds(T, cfg.head_dim // 2, dtype=jnp.float32),
        _sds(slots, dtype=jnp.int32), _sds(slots, maxp, dtype=jnp.int32),
        _sds(dtype=jnp.int32)))

    def layer(x, layers, c, ly, rs, r0, rl, ro, bt, sin, cos):
        return rpa.fused_ragged_layer(
            x, layers, c["k"], c["v"], ly, rs, r0, rl, ro, bt, sin, cos,
            eps=cfg.norm_eps, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, k_scales=c["k_scale"],
            v_scales=c["v_scale"])

    args = (x, params["layers"], cache, ly, rows, rows, rows, rows, bt,
            rope, rope)
    text = _compile(layer, *args).as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert _pallas_grids(jax.make_jaxpr(layer)(*args).jaxpr,
                         "fused_ragged_layer") == [(None,)]


# -- Jamba: the selective-scan kernel and the cell's whole step ---------------

def _jamba_cell():
    from benchmarks.runners import serve_jamba

    config = json.loads(
        (REPO / "benchmarks/configs/jamba2_3b.json").read_text())
    eng = config["engine"]
    T = eng["max_slots"] + max(eng["prefill_chunk"], eng["page_size"])
    return serve_jamba.model_config(config), eng, T


def test_ssm_scan_kernel(v5e):
    """The kernel at the cell's sizes: 320 packed tokens, 64 rows, the
    states of 26 layers and 64 slots updated in place."""
    from ray_tpu.ops.ssm_scan import ssm_scan

    cfg, eng, T = _jamba_cell()
    mesh = _one(v5e)
    C, N, R = cfg.d_inner, cfg.d_state, eng["max_slots"]
    f32 = jnp.float32
    rows = _sds(R, dtype=jnp.int32)
    compiled = _compile(
        ssm_scan, *_on(mesh, (
            _sds(T, C, dtype=f32), _sds(T, C, dtype=f32),
            _sds(T, N, dtype=f32), _sds(T, N, dtype=f32),
            _sds(N, C, dtype=f32), _sds(26, R + 1, N, C, dtype=f32),
            _sds(dtype=jnp.int32), rows, rows, rows, rows)),
        donate_argnums=(5,))
    assert "ssm_scan" in compiled.as_text()


@pytest.mark.parametrize("shape", ["budget", "small"])
def test_jamba_cell_step_updates_the_state_in_place(v5e, shape):
    """The step program of ``jamba2_3b-chat_short`` at full depth and
    published widths, in both shapes the engine compiles (320 positions,
    and 64 for steps without a prompt chunk), fits the chip with its
    cache, and no copy of the SSM states (0.55 GB) is made in it: the
    scan kernel's alias holds through the layer scans."""
    from ray_tpu.models import jamba

    cfg, eng, T = _jamba_cell()
    slots, page = eng["max_slots"], eng["page_size"]
    shapes = _step_shapes(T, slots)
    assert (cfg.n_layers, cfg.d_inner, shapes) == (
        28, 5120, {"budget": 320, "small": 64})
    T = shapes[shape]
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: jamba.init_params(jax.random.key(0), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: jamba.init_cache(cfg, eng["num_pages"], page, slots)))
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, eng["max_seq_len"] // page, dtype=jnp.int32)))
    compiled = _compile(
        lambda p, t, pos, rs, r0, rl, ro, b, c:
        jamba.ragged_step(p, t, pos, rs, r0, rl, ro, b, cfg, c),
        params, toks, toks, rows, rows, rows, rows, bt, cache,
        donate_argnums=(8,))
    text = compiled.as_text()
    for kernel in ("ssm_scan", "ragged_paged_attention", "ragged_kv_append"):
        assert kernel in text
    state = f"f32[26,{slots + 1},16,5120]"
    assert [ln for ln in text.splitlines()
            if re.search(r"= \S*" + re.escape(state) + r"\S* copy\(", ln)
            ] == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30


# -- the Brumby cell: power retention, state by slot and no page -------------

def _brumby_cell():
    from benchmarks.runners.common import model_config

    config = json.loads(
        (REPO / "benchmarks" / "configs" / "brumby14b_pp4.json").read_text())
    eng = config["engine"]
    return model_config(config), eng, eng["max_slots"] + eng["prefill_chunk"]


@pytest.mark.parametrize("kernel", ["retention_decode", "retention_chunk"])
def test_retention_kernels(v5e, kernel):
    """Each kernel at the cell's sizes: 524 packed tokens, 12 rows, the
    states of 10 layers and 12 slots (4.9 GB) updated in place."""
    from ray_tpu.ops import power_retention as pr

    cfg, eng, T = _brumby_cell()
    mesh = _one(v5e)
    H, KVH, d, R = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, eng["max_slots"]
    Dp = cfg.feature_dim
    assert (T, H, KVH, d, Dp) == (524, 40, 8, 128, 9216)
    f32 = jnp.float32
    rows = _sds(R, dtype=jnp.int32)
    compiled = _compile(
        getattr(pr, kernel), *_on(mesh, (
            _sds(T, H, d), _sds(T, KVH, d), _sds(T, KVH, d),
            _sds(T, KVH, dtype=f32),
            _sds(cfg.n_layers, R + 1, KVH, Dp, d, dtype=f32),
            _sds(cfg.n_layers, R + 1, KVH, Dp, dtype=f32),
            _sds(dtype=jnp.int32), rows, rows, rows, rows)),
        donate_argnums=(4, 5))
    assert kernel in compiled.as_text()


@pytest.mark.parametrize("shape", ["budget", "small"])
def test_brumby_cell_step_updates_the_state_in_place(v5e, shape):
    """The step program of ``brumby14b_pp4-doc_long`` at its ten layers
    and published widths, in both shapes the engine compiles (524
    positions, and 16 = 12 slots rounded up to 8 for steps without a
    prompt chunk), fits the chip with its weights (9.05 GiB) and state
    (4.61 GiB), and no copy of either state array is made in it: both
    kernels' aliases hold through the layer scan."""
    from ray_tpu.models import brumby

    cfg, eng, T = _brumby_cell()
    slots = eng["max_slots"]
    shapes = _step_shapes(T, slots)
    assert (cfg.n_layers, cfg.dim, cfg.mlp_dim, shapes) == (
        10, 5120, 17408, {"budget": 524, "small": 16})
    T = shapes[shape]
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: brumby.init_params(jax.random.key(0), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: brumby.init_cache(cfg, 0, 64, slots)))
    assert set(cache) == {"ret_s", "ret_z"}
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, 0, dtype=jnp.int32)))
    compiled = _compile(
        lambda p, t, pos, rs, r0, rl, ro, b, c:
        brumby.ragged_step(p, t, pos, rs, r0, rl, ro, b, cfg, c),
        params, toks, toks, rows, rows, rows, rows, bt, cache,
        donate_argnums=(8,))
    text = compiled.as_text()
    for kernel in ("retention_decode", "retention_chunk"):
        assert kernel in text
    for state in (f"f32[10,{slots + 1},8,9216,128]",
                  f"f32[10,{slots + 1},8,9216]"):
        assert [ln for ln in text.splitlines()
                if re.search(r"= \S*" + re.escape(state) + r"\S* copy\(", ln)
                ] == []
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


# -- the Xing cell: latent page pool, routed experts, four-stream residual ---

def _xing_cell():
    from benchmarks.runners.serve_xing import model_config

    config = json.loads(
        (REPO / "benchmarks" / "configs" / "xing4_29b_pp8.json").read_text())
    eng = config["engine"]
    return model_config(config), eng, eng["max_slots"] + eng["prefill_chunk"]


@pytest.mark.parametrize("shape", ["budget", "small"])
def test_latent_attention_kernel(v5e, shape):
    """``ragged_latent_attention`` and the pool's append at the cell's
    sizes: 32 heads of 640 lanes over a pool of 2817 pages of 64 tokens
    and 7 layers (1.6 GB), in the two calls of a step that carries a
    chunk (288 positions) and the one call of a decode step (32); a pool
    cell spans four pages of the table's 88 columns (22 cells a row: the
    pool is four operands, 256 keys a score tile)."""
    from ray_tpu.ops import latent_attention as la

    cfg, eng, T = _xing_cell()
    slots, page = eng["max_slots"], eng["page_size"]
    maxp = eng["max_seq_len"] // page
    T = _step_shapes(T, slots)[shape]
    assert (cfg.n_heads, cfg.pool_width, cfg.kv_rank, maxp) == (
        32, 640, 512, 88)
    assert (la.cell_pages(page, maxp), la.CELL_KEYS) == (4, 256)
    mesh = _one(v5e)
    rows = _sds(slots, dtype=jnp.int32)
    pool = _sds(cfg.n_layers, 1, slots * maxp + 1, page, cfg.pool_width)

    def both(q, new, fresh, pool, layer, rs, r0, rl, ro, bt):
        out = la.ragged_latent_attention(
            q, new, pool, layer, rs, r0, rl, ro, bt,
            scale=cfg.softmax_scale, rank=cfg.kv_rank)
        return out, la.ragged_latent_append(pool, fresh, rs, r0, rl, ro, bt)

    compiled = _compile(both, *_on(mesh, (
        _sds(T, cfg.n_heads, cfg.pool_width), _sds(T, cfg.pool_width),
        _sds(cfg.n_layers, T, cfg.pool_width), pool,
        _sds(dtype=jnp.int32), rows, rows, rows, rows,
        _sds(slots, maxp, dtype=jnp.int32))), donate_argnums=(3,))
    text = compiled.as_text()
    assert text.count('"ragged_latent_attention"') or \
        "ragged_latent_attention" in text
    assert "ragged_latent_append" in text
    # the pool is appended to where it lies
    assert [ln for ln in text.splitlines() if re.search(
        r"= \S*bf16\[7,1,2817,64,640\]\S* copy\(", ln)] == []


def test_moe_grouped_ffn_kernel(v5e):
    """``moe_grouped_ffn`` at the cell's sizes: 288 tokens' 1152 pairs over
    64 experts of 3 x 3584 x 1024, one routed layer's leaves whole."""
    from ray_tpu.ops import moe_experts as moe

    cfg, _eng, T = _xing_cell()
    mesh = _one(v5e)
    E, D, F, k = cfg.n_experts, cfg.dim, cfg.moe_dim, cfg.top_k
    assert (T, E, D, F, k) == (288, 64, 3584, 1024, 4)
    experts = {"w_gate": _sds(E, D, F), "w_up": _sds(E, D, F),
               "w_down": _sds(E, F, D)}
    compiled = _compile(
        moe.routed_experts,
        *_on(mesh, (_sds(T, D), _sds(T, k, dtype=jnp.int32),
                    _sds(T, k, dtype=jnp.float32), experts,
                    _sds(T, dtype=jnp.bool_))))
    text = compiled.as_text()
    assert "moe_grouped_ffn" in text
    assert [ln for ln in text.splitlines() if re.search(
        r"= \S*bf16\[64,(3584,1024|1024,3584)\]\S* copy\(", ln)] == []


@pytest.mark.parametrize("shape", ["budget", "small"])
def test_xing_cell_step_copies_neither_pool_nor_experts(v5e, shape):
    """The step program of ``xing4_29b_pp8-reason`` at its seven layers
    and published widths, in both shapes the engine compiles (288
    positions and 32), fits the chip with its weights (9.17 GiB) and pool
    (1.5 GiB), and copies neither: the pool's alias holds through the
    layers, and no routed layer's experts (1.4 GB) are sliced out in
    front of the grouped products."""
    from ray_tpu.models import xing

    cfg, eng, T = _xing_cell()
    slots, page = eng["max_slots"], eng["page_size"]
    maxp = eng["max_seq_len"] // page
    shapes = _step_shapes(T, slots)
    assert (cfg.n_layers, cfg.first_dense, cfg.dim, shapes) == (
        7, 2, 3584, {"budget": 288, "small": 32})
    T = shapes[shape]
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: xing.init_params(jax.random.key(0), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: xing.init_cache(cfg, slots * maxp, page)))
    assert set(cache) == {"kv_c", "moe_tokens", "moe_distinct"}
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, maxp, dtype=jnp.int32)))
    compiled = _compile(
        lambda p, t, pos, rs, r0, rl, ro, b, c:
        xing.ragged_step(p, t, pos, rs, r0, rl, ro, b, cfg, c),
        params, toks, toks, rows, rows, rows, rows, bt, cache,
        donate_argnums=(8,))
    text = compiled.as_text()
    for kernel in ("ragged_latent_attention", "ragged_latent_append"):
        assert kernel in text
    for big in ("bf16[7,1,2817,64,640]", "bf16[64,3584,1024]",
                "bf16[64,1024,3584]"):
        assert [ln for ln in text.splitlines()
                if re.search(r"= \S*" + re.escape(big) + r"\S* copy\(", ln)
                ] == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


# -- the GLM-5 cell: sparse latent attention, two pools, a share of experts --

@pytest.mark.parametrize("shape", ["budget", "small"])
def test_glm5_cell_step_copies_neither_pool_nor_experts(v5e, shape):
    """The step program of ``glm5_ep16-doc_32k`` at its five layers and
    published widths, in both shapes the engine compiles (520 positions
    and 8): the masked walk of ``ragged_latent_attention`` (whole-step
    window, heads in groups of 8: 4160 stacked rows; a pool cell four
    pages of the table's 524 columns, 131 cells a row, with a
    ``[520, 256]`` block of the selection a cell), the indexer
    and the bisection, the gathered list of the one-token rows and the
    append of both pools compile for a v5e, fit the chip with the weights
    (7.28 GiB) and pools (1.92 GiB), and copy neither pool nor a routed
    layer's sixteen experts."""
    from benchmarks.runners.serve_glm5 import model_config
    from ray_tpu.models import glm5
    from ray_tpu.ops import latent_attention as la

    config = json.loads(
        (REPO / "benchmarks" / "configs" / "glm5_ep16.json").read_text())
    cfg, eng = model_config(config), config["engine"]
    slots, page = eng["max_slots"], eng["page_size"]
    maxp = eng["max_seq_len"] // page
    shapes = _step_shapes(slots + eng["prefill_chunk"], slots)
    G = la.cell_pages(page, maxp)
    assert (G, -(-maxp // G), shapes["budget"] * la.SPARSE_CHUNK_HEADS) == (
        4, 131, 4160)
    assert (cfg.n_layers, cfg.first_dense, cfg.dim, cfg.n_experts,
            cfg.n_routed, maxp, shapes) == (
        5, 1, 6144, 16, 256, 524, {"budget": 520, "small": 8})
    T = shapes[shape]
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: glm5.init_params(jax.random.key(0), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: glm5.init_cache(cfg, slots * maxp, page)))
    assert set(cache) == {"kv_c", "kv_i", "moe_tokens", "moe_distinct"}
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, maxp, dtype=jnp.int32)))
    compiled = _compile(
        lambda p, t, pos, rs, r0, rl, ro, b, c:
        glm5.ragged_step(p, t, pos, rs, r0, rl, ro, b, cfg, c),
        params, toks, toks, rows, rows, rows, rows, bt, cache,
        donate_argnums=(8,))
    text = compiled.as_text()
    for kernel in ("ragged_latent_attention", "ragged_latent_append",
                   "moe_grouped_ffn"):
        assert kernel in text
    for big in ("bf16[5,1,4193,64,640]", "bf16[5,1,4193,64,128]",
                "bf16[16,6144,2048]", "bf16[16,2048,6144]"):
        assert [ln for ln in text.splitlines()
                if re.search(r"= \S*" + re.escape(big) + r"\S* copy\(", ln)
                ] == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5 * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


# -- the MiniCPM-SALA cell: a matrix state beside pages and compressed keys --

@pytest.mark.parametrize("shape", ["budget", "small"])
def test_sala_cell_step_copies_no_pool_state_or_weight_stack(v5e, shape):
    """The step program of ``minicpm_sala_pp2-doc_64k`` at its sixteen
    layers and published widths, in both shapes the engine compiles (520
    positions and 8): ``lightning_decode``, ``lightning_chunk``,
    ``block_sparse_walk`` (the chunk's whole-window form) and the append
    compile for a v5e, fit the chip with the weights (9.39 GiB), pools
    (2.16 GiB) and state (0.21 GiB), and copy neither a pool, the state,
    nor a stack of projection weights (XLA re-laid ``lin.wq/wk/wv`` out
    every step until the head split stood behind a barrier)."""
    from benchmarks.runners.serve_sala import model_config
    from ray_tpu.models import minicpm_sala as sala

    config = json.loads((REPO / "benchmarks" / "configs"
                         / "minicpm_sala_pp2.json").read_text())
    cfg, eng = model_config(config), config["engine"]
    slots, page = eng["max_slots"], eng["page_size"]
    maxp = eng["max_seq_len"] // page
    shapes = _step_shapes(slots + eng["prefill_chunk"], slots)
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, maxp,
            shapes) == (16, 4096, 32, 2, 1040, {"budget": 520, "small": 8})
    T = shapes[shape]
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: sala.init_params(jax.random.key(0), cfg)))
    cache = _on(mesh, jax.eval_shape(
        lambda: sala.init_cache(cfg, slots * maxp, page, slots)))
    assert set(cache) == {"k", "v", "kh", "lin_s", "sel_pages",
                          "walk_cells"}
    toks, rows, bt = _on(mesh, (
        _sds(T, dtype=jnp.int32), _sds(slots, dtype=jnp.int32),
        _sds(slots, maxp, dtype=jnp.int32)))
    compiled = _compile(
        lambda p, t, pos, rs, r0, rl, ro, b, c:
        sala.ragged_step(p, t, pos, rs, r0, rl, ro, b, cfg, c),
        params, toks, toks, rows, rows, rows, rows, bt, cache,
        donate_argnums=(8,))
    text = compiled.as_text()
    for kernel in ("lightning_decode", "lightning_chunk",
                   "block_sparse_walk", "ragged_kv_append"):
        assert kernel in text
    for big in ("bf16[4,2,8321,64,128]", "f32[4,33284,256]",
                "f32[12,9,32,128,128]", "bf16[12,4096,4096]",
                "bf16[16,4096,16384]", "bf16[16,16384,4096]"):
        assert [ln for ln in text.splitlines()
                if re.search(r"= \S*" + re.escape(big) + r"\S* copy\(", ln)
                ] == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.25 * 2**30
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30


@pytest.mark.parametrize("piece", ["embed", "lightning-attn", "minicpm4",
                                   "readings"])
def test_sala_served_check_fits_beside_the_engine(v5e, piece):
    """The served check's reference (``serve_sala.served_programs``) runs
    after the window with the engine idle on the same chip: weights, pools
    and state hold 11.76 of 15.75 GiB.  Each compiled piece, at the
    configuration's widths and the check's 17,408 positions, has to fit
    in half of what is left beside the activations it is handed."""
    from benchmarks.runners import serve_sala
    from ray_tpu.models import minicpm_sala as sala

    config = json.loads((REPO / "benchmarks" / "configs"
                         / "minicpm_sala_pp2.json").read_text())
    cfg, plan = serve_sala.model_config(config), serve_sala.SERVED_PLAN
    assert plan["length"] == plan["past"] + plan["answer"] == 17408
    mesh = _one(v5e)
    params = _on(mesh, jax.eval_shape(
        lambda: sala.init_params(jax.random.key(0), cfg)))
    x, toks, i32, ans = _on(mesh, (
        _sds(plan["length"], cfg.dim, dtype=jnp.float32),
        _sds(plan["length"], dtype=jnp.int32), _sds(dtype=jnp.int32),
        _sds(plan["answer"], dtype=jnp.int32)))
    embed, layer, readings = serve_sala.served_programs(config, plan)
    fn, args = {"embed": (embed, (params, toks)),
                "readings": (readings, (x, params, i32, ans, ans)),
                }.get(piece) or (layer[piece], (x, params, i32, i32, i32))
    with jax.default_matmul_precision("highest"):
        compiled = (fn.trace(*args).lower(lowering_platforms=("tpu",))
                    .compile())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 2.0 * 2**30


def test_block_sparse_walk_compiles(v5e):
    """The walk alone at the cell's shapes: rows of one token through
    their own pages, a chunk's rows through the context once under the
    selection as a mask; a pool cell eight entries of a (row, KV head)'s
    list, so both pools eight times and, for the chunk, the mask's
    blocks of the eight (the chunk call's ``[8320, 512]`` float32 tiles
    beside its state, queries and output have to fit the chip's VMEM)."""
    from ray_tpu.ops import block_sparse_attention as bsa

    T, H, KVH, hd, maxp, slots = 520, 32, 2, 128, 1040, 8
    assert bsa.cell_pages(maxp) == bsa.CELL_PAGES == 8
    mesh = _one(v5e)
    pool = _sds(4, KVH, slots * maxp + 1, PAGE, hd)
    args = _on(mesh, (
        _sds(T, H, hd), _sds(T, KVH, hd), _sds(T, KVH, hd), pool, pool,
        _sds(dtype=jnp.int32), *[_sds(slots, dtype=jnp.int32)] * 4,
        _sds(slots, maxp, dtype=jnp.int32),
        _sds(T, KVH, maxp, dtype=jnp.bool_)))
    text = _compile(bsa.block_sparse_attention, *args).as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "block_sparse_walk" in ln]
    # two calls; neither copies a pool to pass it sixteen times
    assert len(calls) == 2
    assert not re.search(r"bf16\[4,2,8321,64,128\]\S* copy\(", text)
