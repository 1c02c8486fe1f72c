"""Compile each benchmark cell's step program for a TPU v5e WITHOUT a
chip, at published widths and in both shapes the engine compiles, and
read the compiled module for what the cell's PR promised: no copy of a
weight, a pool or a state (how: tests/mosaic_aot.py; the two cells with
routed experts, the longest compiles: test_mosaic_aot_cells_moe.py)."""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from tests.mosaic_aot import (  # noqa: F401 (fixtures)
    REPO, _assert_fused_layer_grid_follows_the_rows, _brumby_cell,
    _chat_cell, _compile, _jamba_cell, _on, _one, _sds, _step_shapes,
    mosaic_not_interpreter, v5e,
)

pytestmark = pytest.mark.long_file(141)


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
    ret_z = f"f32[10,{slots + 1},8,9216]"
    for state in (f"f32[10,{slots + 1},8,9216,128]", ret_z):
        assert [ln for ln in text.splitlines()
                if re.search(r"= \S*" + re.escape(state) + r"\S* copy\(", ln)
                ] == []
    # nor does ``ret_z`` make a round trip through VMEM every layer (38 MB
    # in and out: XLA prefetches it for a scatter as soon as VMEM has the
    # room), nor is the chunk kernel's ``[rows, 8, 8, D']`` normaliser
    # re-laid out whole to read one row of eight (31 MB a layer, 0.38 ms
    # of every step until PR 50 wrote the decode rows' ``z`` in the kernel)
    assert re.search(re.escape(ret_z) + r"\{[^}]*S\(1\)\}", text) is None
    assert [ln for ln in text.splitlines()
            if re.search(r"= f32\[%d,8,8,9216\]\S* copy\(" % (slots + 1), ln)
            ] == []
    mem = compiled.memory_analysis()
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
