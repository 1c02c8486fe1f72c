"""Compile the step programs of the two benchmark cells with routed
experts (Xing, GLM-5) for a TPU v5e WITHOUT a chip, at published widths
and in both shapes the engine compiles: they fit the chip with their
weights and pools and copy neither a pool nor a layer's experts (how:
tests/mosaic_aot.py; the other cells: test_mosaic_aot_cells.py).  A
file of their own: the four compiles are the longest cases a kernel
change has to wait for."""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import pytest

from tests.mosaic_aot import (  # noqa: F401 (fixtures)
    REPO, _compile, _on, _one, _sds, _step_shapes, _xing_cell,
    mosaic_not_interpreter, v5e,
)

pytestmark = pytest.mark.long_file(269)


# -- the Xing cell: latent page pool, routed experts, four-stream residual ---
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
