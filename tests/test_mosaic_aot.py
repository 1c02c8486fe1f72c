"""Compile every Pallas kernel alone for a TPU v5e WITHOUT a chip, at
the widths the benchmark's cells use (how: tests/mosaic_aot.py; the
cells' whole steps: test_mosaic_aot_cells*.py; the ``bench.py``-shaped
steps and the meshes: test_mosaic_aot_steps.py)."""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.mosaic_aot import (  # noqa: F401 (fixtures)
    PAGE, REPO, _brumby_cell, _chat_cell, _compile, _jamba_cell, _on, _one,
    _pallas_grids, _sds, _step_shapes, _xing_cell, mosaic_not_interpreter,
    v5e,
)

pytestmark = pytest.mark.long_file(210)


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


def test_attention_scope_moves_nothing_but_products(v5e):
    """One layer's attention of ``internlm2_1b8-pretrain_4k`` (norm,
    projections, RoPE, the flash kernels, the output projection; forward
    under ``jax.checkpoint`` and its gradient): every instruction of the
    compiled program whose result holds 16 MB or more is a product's
    output fusion (RoPE rides one: ``llama.rope_in_one_pass``), a kernel
    or a norm's reduction.  A ``transpose``, ``copy`` or ``convert`` of
    that size, or a loop fusion (RoPE's halves, its ``concatenate``, a
    float32 copy of dq), is a pass over q, k or their cotangents that
    PR 52 took out: 75 ms of the cell's 1606 ms step."""
    from ray_tpu.models import llama

    B, S, H, KVH, D = 4, 4096, 16, 8, 128
    cfg = llama.LlamaConfig(vocab_size=128, dim=H * D, n_layers=1,
                            n_heads=H, n_kv_heads=KVH, mlp_dim=128,
                            max_seq_len=S, dtype=jnp.bfloat16)
    mesh = _one(v5e)
    x, sin = _on(mesh, (_sds(B, S, H * D),
                        _sds(B, S, D // 2, dtype=jnp.float32)))
    layer = _on(mesh, {
        "ln_attn": _sds(H * D),
        "attn": {"wq": _sds(H * D, H, D), "wk": _sds(H * D, KVH, D),
                 "wv": _sds(H * D, KVH, D), "wo": _sds(H, D, H * D)}})

    def attention(x, layer, sin, cos):
        normed = llama.rms_norm(x, layer["ln_attn"], cfg.norm_eps)
        return x + llama._attn_block(normed, layer, cfg, sin, cos, None)[0]

    def both_ways(x, layer, sin, cos, ct):
        y, vjp = jax.vjp(
            lambda x, layer: jax.checkpoint(attention)(x, layer, sin, cos),
            x, layer)
        return y, vjp(ct)

    text = _compile(both_ways, x, layer, sin, sin, x).as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count("flash_fwd") >= 2 and "flash_bwd_dkv" in entry
    moved = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) "
                     r"(fusion|copy|convert|transpose|concatenate)\(", line)
        if (m is None or "kind=kOutput" in line
                or re.search(r'op_name="[^"]*/reduce_sum"', line)):
            continue
        name, result, op = m.groups()
        sizes = [np.prod([int(d) for d in dims.split(",")])
                 * (4 if dtype == "f32" else 2)
                 for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", result)]
        if max(sizes, default=0) >= 16 * 2**20:
            moved.append((name, op, result.split("{")[0]))
    assert moved == [], moved


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
    args = _on(mesh, (
        _sds(T, H, d), _sds(T, KVH, d), _sds(T, KVH, d),
        _sds(T, KVH, dtype=f32),
        _sds(cfg.n_layers, R + 1, KVH, Dp, d, dtype=f32),
        _sds(cfg.n_layers, R + 1, KVH, Dp, dtype=f32),
        _sds(dtype=jnp.int32), rows, rows, rows, rows))
    fn = getattr(pr, kernel)
    assert kernel in _compile(fn, *args, donate_argnums=(4, 5)).as_text()
    # the live rows bound the grid (an operand of the call); a state
    # block is 1024 features, nine a row and KV head.  PR 50 swept the
    # decode kernel's block up to the whole D' on the chip: its row costs
    # what a plain copy of its bytes costs at every size, so the block
    # stays the one both kernels share
    assert pr.state_block(Dp) == 1024
    grid = {"retention_decode": (None, 9, KVH),
            "retention_chunk": (KVH, None, 9)}[kernel]
    assert _pallas_grids(jax.make_jaxpr(fn)(*args).jaxpr, kernel) == [grid]


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
