"""Fused per-layer decode megakernel — one Pallas program per layer.

BENCH_r05 put 8B int8 decode at 56 % of the weight-read roofline and
release/ablate_8b_decode.py attributed the gap to per-op dispatch
latency: at decode batch sizes every layer pays pipeline setup for a
dozen tiny XLA ops (norms, rope, attention glue, residual adds)
between the matmuls that actually move weight bytes.  This kernel
replaces the WHOLE per-layer decode op graph —

    RMSNorm -> int8 qkv projection -> RoPE -> paged attention over
    int8 KV pages -> o-proj -> RMSNorm -> gate/up/down MLP

— with ONE ``pl.pallas_call`` whose 1-D grid is a hand-scheduled
sequence of PHASES (TPU grids execute sequentially, which is the whole
trick):

    [qkv tiles | attention cells (b-major, page-minor) | o-proj tiles
     | fused gate/up/down MLP tiles]

Weight matrices stream through VMEM in column/row tiles via BlockSpec
index maps; each map CLAMPS outside its own phase, so consecutive grid
cells see an identical block index and Mosaic elides the dead DMAs
(the same last-live-page trick ops/paged_attention.py uses for KV
pages).  Activations, flash-attention state (m, l, acc) and the
residual stream never leave VMEM scratch between phases.  HBM traffic
per layer is the int8 weight bytes plus the live KV pages — the
roofline's numerator and nothing else.

Contracts kept from the unfused path (models/llama.py
decode_slots_paged):

  * the KV pools are STRICTLY read-only here — the new token's k/v
    rows ride out as outputs and the caller appends all layers at once
    post-scan (ops/paged_attention.paged_append*), preserving the
    aliased in-place pool update;
  * the page-table layout, OOB sentinel (== num_pages -> scratch
    page) and per-page-per-kv-head int8 scales are exactly
    ops/paged_attention.py's;
  * int8 weights stay ``{"q", "scale"}`` per-output-channel; scales
    apply to matmul RESULTS inside the kernel, so HBM moves int8.

Numerics are tolerance-gated against the unfused path in interpret
mode on CPU (tests/test_fused_decode.py), the kernel is compiled by
Mosaic for a v5e at the 319M and 8B-int8 shapes in
tests/test_mosaic_aot.py, and chip_smoke.py's ``kernels`` phase checks
it against the unfused path on the chip.  Its scratch (activations,
flash state, residual stream for every slot) plus double-buffered
weight tiles pass the 16 MiB scoped-VMEM default at 48 slots of 8B
width, hence ``vmem_limit_bytes`` below.  Whether it is FASTER than the
unfused path has not been measured on this tree (ROADMAP Queue 1
item 5); tile sizes are keyword-tunable for that work.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import platform
from ray_tpu.ops.paged_attention import _MIN_QPG, NEG_INF


def _qdict(node) -> bool:
    return isinstance(node, dict) and set(node.keys()) == {"q", "scale"}


def _pick_tile(total: int, target: int, multiple: int = 1) -> int:
    """Largest divisor of ``total`` that is <= target and a multiple of
    ``multiple`` (falls back to ``total`` when nothing smaller fits)."""
    best = total
    d = multiple
    while d <= min(total, target):
        if total % d == 0:
            best = d
        d += multiple
    return best if total % best == 0 else total


def _fused_kernel(*refs, B: int, D: int, H: int, KVH: int, qpg: int,
                  qpg_p: int, hd: int, page: int, P: int, maxp: int,
                  M: int, tq: int, to: int, tm: int, eps: float,
                  scale: float, soft_cap: Optional[float],
                  quantized: bool, dot_dt):
    n_pre = 5 if quantized else 3
    if quantized:
        bt_ref, len_ref, _ly_ref, ks_ref, vs_ref = refs[:5]
    else:
        bt_ref, len_ref, _ly_ref = refs[:3]
        ks_ref = vs_ref = None
    (x_ref, xt_ref, ln_a_ref, ln_m_ref, sin_ref, cos_ref,
     wqkv_ref, sqkv_ref, kp_ref, vp_ref, wo_ref, so_ref,
     wg_g_ref, wg_u_ref, sg_g_ref, sg_u_ref, wd_ref, sd_ref,
     xo_ref, kn_ref, vn_ref,
     xn_s, qkv_s, qs, m_s, l_s, acc_s, ao_s, h_s, y_s) = refs[n_pre:]

    half = hd // 2
    Tq = ((H + 2 * KVH) * hd) // tq
    To = D // to
    Tm = M // tm
    S1 = Tq                      # first attention cell
    S2 = S1 + B * maxp           # first o-proj tile
    S3 = S2 + To                 # first MLP tile
    S4 = S3 + Tm                 # grid end
    t = pl.program_id(0)

    def head_slice(hq: int):
        """Row-block of qkv_s holding head ``hq`` (static), [B, hd]."""
        base = hq * hd
        j, off = divmod(base, tq)
        return qkv_s[j][:, off:off + hd]

    def rope(xh):
        x1, x2 = xh[:, :half], xh[:, half:]
        sn = sin_ref[...].astype(jnp.float32)
        cs = cos_ref[...].astype(jnp.float32)
        return jnp.concatenate([x1 * cs - x2 * sn, x2 * cs + x1 * sn],
                               axis=-1)

    def capped(s):
        if soft_cap is not None:
            return soft_cap * jnp.tanh(s / soft_cap)
        return s

    # ---- phase 0 start: RMSNorm of the residual stream ----------------
    @pl.when(t == 0)
    def _norm_in():
        x32 = x_ref[...].astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        xn_s[...] = (x32 * lax.rsqrt(var + eps)
                     * ln_a_ref[...].astype(jnp.float32))

    # ---- phase 0: qkv projection, one output-column tile per cell -----
    @pl.when(t < S1)
    def _qkv_tile():
        w = wqkv_ref[...].astype(dot_dt)
        res = lax.dot_general(
            xn_s[...].astype(dot_dt), w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        qkv_s[t] = res * sqkv_ref[...].astype(jnp.float32)

    # ---- phase 1 start: RoPE + q regroup + new k/v rows ---------------
    @pl.when(t == S1)
    def _attn_setup():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        for h in range(KVH):
            for g in range(qpg):
                qs[:, h, g, :] = rope(head_slice(h * qpg + g))
            for g in range(qpg, qpg_p):  # sublane padding rows
                qs[:, h, g, :] = jnp.zeros((B, hd), jnp.float32)
            lo, hi = h * hd, (h + 1) * hd
            kn_ref[:, lo:hi] = rope(head_slice(H + h)).astype(kn_ref.dtype)
            vn_ref[:, lo:hi] = head_slice(H + KVH + h).astype(vn_ref.dtype)

    # ---- phase 1: paged flash attention, one (slot, page) per cell ----
    @pl.when((t >= S1) & (t < S2))
    def _attn_cell():
        ci = t - S1
        b = ci // maxp
        p = ci % maxp
        length = len_ref[b]

        @pl.when(p * page < length)
        def _():
            if quantized:
                last = jnp.maximum(length - 1, 0) // page
                pid = bt_ref[b, jnp.minimum(p, last)]
            for h in range(KVH):
                q = qs[b, h]                       # [qpg_p, hd]
                k = kp_ref[0, h, 0]                # [page, hd]
                s = lax.dot_general(
                    q.astype(dot_dt), k.astype(dot_dt),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quantized:
                    s = s * ks_ref[pid, h]
                s = capped(s)
                pos = p * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(pos < length, s, NEG_INF)
                m_prev = m_s[b, h]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                probs = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_s[b, h] = (corr * l_s[b, h]
                             + jnp.sum(probs, axis=-1, keepdims=True))
                v = vp_ref[0, h, 0]
                pv = lax.dot_general(
                    probs.astype(dot_dt), v.astype(dot_dt),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if quantized:
                    pv = pv * vs_ref[pid, h]
                acc_s[b, h] = acc_s[b, h] * corr + pv
                m_s[b, h] = m_new

    # ---- phase 1 end: fold the current token's self term, normalize ---
    @pl.when(t == S2 - 1)
    def _attn_final():
        for h in range(KVH):
            lo, hi = h * hd, (h + 1) * hd
            kh = kn_ref[:, lo:hi].astype(jnp.float32)
            vh = vn_ref[:, lo:hi].astype(jnp.float32)
            for g in range(qpg):
                q = qs[:, h, g, :]                 # [B, hd]
                s = capped(jnp.sum(q * kh, axis=-1, keepdims=True)
                           * scale)
                m_prev = m_s[:, h, g, :]
                l_prev = l_s[:, h, g, :]
                a_prev = acc_s[:, h, g, :]
                m_new = jnp.maximum(m_prev, s)
                corr = jnp.exp(m_prev - m_new)
                p_self = jnp.exp(s - m_new)
                o = (a_prev * corr + p_self * vh) / (l_prev * corr + p_self)
                hq = h * qpg + g
                ao_s[:, hq * hd:(hq + 1) * hd] = o

    # ---- phase 2: o-proj tiles + residual add -------------------------
    @pl.when((t >= S2) & (t < S3))
    def _oproj_tile():
        w = wo_ref[...].astype(dot_dt)
        o = lax.dot_general(
            ao_s[...].astype(dot_dt), w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o = o * so_ref[...].astype(jnp.float32)
        h_s[t - S2] = xt_ref[...].astype(jnp.float32) + o

    # ---- phase 3 start: second RMSNorm (over the h_s tiles) -----------
    @pl.when(t == S3)
    def _mlp_norm():
        ss = jnp.zeros((B, 1), jnp.float32)
        for j in range(To):
            hj = h_s[j]
            ss = ss + jnp.sum(hj * hj, axis=-1, keepdims=True)
        r = lax.rsqrt(ss / D + eps)
        for j in range(To):
            sl = slice(j * to, (j + 1) * to)
            xn_s[:, sl] = h_s[j] * r * ln_m_ref[:, sl].astype(jnp.float32)
        y_s[...] = jnp.zeros_like(y_s)

    # ---- phase 3: fused gate/up/down, one mlp-row tile per cell -------
    @pl.when(t >= S3)
    def _mlp_tile():
        hn = xn_s[...].astype(dot_dt)
        g = lax.dot_general(
            hn, wg_g_ref[...].astype(dot_dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        g = g * sg_g_ref[...].astype(jnp.float32)
        u = lax.dot_general(
            hn, wg_u_ref[...].astype(dot_dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        u = u * sg_u_ref[...].astype(jnp.float32)
        act = (g * jax.nn.sigmoid(g)) * u
        y_s[...] += lax.dot_general(
            act.astype(dot_dt), wd_ref[...].astype(dot_dt),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # ---- grid end: down-proj scale + second residual ------------------
    @pl.when(t == S4 - 1)
    def _final():
        for j in range(To):
            sl = slice(j * to, (j + 1) * to)
            xo_ref[:, sl] = (
                h_s[j] + y_s[:, sl] * sd_ref[:, sl].astype(jnp.float32)
            ).astype(xo_ref.dtype)


def _weight_pair(leaf, cols_of_hd: Optional[int] = None):
    """(operand, per-output-channel scale [1, N]) from a param leaf.

    Quantized ``{"q", "scale"}`` leaves pass int8 straight through (the
    kernel applies the scale to matmul RESULTS); plain leaves get a
    ones scale.  ``cols_of_hd`` tiles a per-head-dim scale ([1,..,hd]
    from unfused per-weight quantization) across that many heads."""
    if _qdict(leaf):
        q = leaf["q"]
        s = leaf["scale"].reshape(1, -1).astype(jnp.float32)
        q = q.reshape(q.shape[0], -1)
        if cols_of_hd is not None and s.shape[1] != q.shape[1]:
            s = jnp.tile(s, (1, cols_of_hd))
        return q, s
    w = leaf.reshape(leaf.shape[0], -1)
    return w, jnp.ones((1, w.shape[1]), jnp.float32)


def _assemble_qkv(attn, H: int, KVH: int, hd: int, dt):
    """One [D, (H+2KVH)*hd] operand + [1, ...] scale from either the
    fused ``wqkv`` artifact or separate wq/wk/wv leaves."""
    if "wqkv" in attn:
        return _weight_pair(attn["wqkv"])
    parts = [(attn["wq"], H), (attn["wk"], KVH), (attn["wv"], KVH)]
    if all(_qdict(w) for w, _ in parts):
        ws, ss = zip(*(_weight_pair(w, n) for w, n in parts))
        return jnp.concatenate(ws, axis=1), jnp.concatenate(ss, axis=1)
    # Mixed / unquantized: dequantize to the compute dtype and fold the
    # scale away (test-path convenience; serving artifacts are fused).
    deq = []
    for w, _n in parts:
        if _qdict(w):
            w = w["q"].astype(dt) * w["scale"].astype(dt)
        deq.append(w.reshape(w.shape[0], -1).astype(dt))
    w = jnp.concatenate(deq, axis=1)
    return w, jnp.ones((1, w.shape[1]), jnp.float32)


def _assemble_gateup(mlp, dt):
    if "w_gateup" in mlp:
        return _weight_pair(mlp["w_gateup"])
    parts = [mlp["w_gate"], mlp["w_up"]]
    if all(_qdict(w) for w in parts):
        ws, ss = zip(*(_weight_pair(w) for w in parts))
        return jnp.concatenate(ws, axis=1), jnp.concatenate(ss, axis=1)
    deq = []
    for w in parts:
        if _qdict(w):
            w = w["q"].astype(dt) * w["scale"].astype(dt)
        deq.append(w.astype(dt))
    w = jnp.concatenate(deq, axis=1)
    return w, jnp.ones((1, w.shape[1]), jnp.float32)


def fused_decode_layer(
    x: jax.Array,
    layer,
    k_pools: jax.Array,
    v_pools: jax.Array,
    layer_idx: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    sin: jax.Array,
    cos: jax.Array,
    *,
    eps: float,
    n_heads: int,
    n_kv_heads: int,
    soft_cap: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    tile_qkv: int = 256,
    tile_out: int = 256,
    tile_mlp: int = 128,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused decode layer: x [B, D] residual stream in, pools
    read-only, -> (x_out [B, D], k_new [B, KVH, hd], v_new [B, KVH,
    hd]).  ``layer`` is one layer's param subtree (scan-sliced), int8
    ``{"q", "scale"}`` leaves or plain weights, fused (wqkv/w_gateup)
    or separate projections.  sin/cos [B, hd//2] from rope_table."""
    B, D = x.shape
    H, KVH = n_heads, n_kv_heads
    hd = D // H
    L, KVH_p, P, page, _ = k_pools.shape
    assert KVH_p == KVH, (KVH_p, KVH)
    maxp = block_tables.shape[1]
    M = (layer["mlp"]["w_down"]["q"].shape[0] if _qdict(
        layer["mlp"]["w_down"]) else layer["mlp"]["w_down"].shape[0])
    qpg = H // KVH
    qpg_p = max(qpg, _MIN_QPG)
    quantized = k_scales is not None
    dt = x.dtype
    Cq = (H + 2 * KVH) * hd

    wqkv, sqkv = _assemble_qkv(layer["attn"], H, KVH, hd, dt)
    wg, sg = _assemble_gateup(layer["mlp"], dt)
    # wo contracts over (heads, head_dim): fold both into rows.
    wo_leaf = layer["attn"]["wo"]
    if _qdict(wo_leaf):
        wo = wo_leaf["q"].reshape(H * hd, D)
        so = wo_leaf["scale"].reshape(1, D).astype(jnp.float32)
    else:
        wo = wo_leaf.reshape(H * hd, D)
        so = jnp.ones((1, D), jnp.float32)
    wd, sd = _weight_pair(layer["mlp"]["w_down"])
    ln_a = layer["ln_attn"].reshape(1, D).astype(jnp.float32)
    ln_m = layer["ln_mlp"].reshape(1, D).astype(jnp.float32)

    # Sublane-pad the slot dim; padded rows carry length 0 (fully
    # masked) and zero activations (no NaNs: the self term's
    # denominator is >= its own exp(0) = 1).
    B_p = max(8, -(-B // 8) * 8)
    if B_p != B:
        pad = B_p - B
        x = jnp.pad(x, ((0, pad), (0, 0)))
        sin = jnp.pad(sin, ((0, pad), (0, 0)))
        cos = jnp.pad(cos, ((0, pad), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, pad), (0, 0)))
        lengths = jnp.pad(lengths, ((0, pad),))

    tq = _pick_tile(Cq, tile_qkv, multiple=hd)
    to = _pick_tile(D, tile_out, multiple=128 if D % 128 == 0 else 1)
    tm = _pick_tile(M, tile_mlp, multiple=128 if M % 128 == 0 else 1)
    Tq, To, Tm = Cq // tq, D // to, M // tm
    S1 = Tq
    S2 = S1 + B_p * maxp
    S3 = S2 + To
    S4 = S3 + Tm

    def clip(v, n):
        return jnp.clip(v, 0, n - 1)

    def const2(t, *pf):
        return (0, 0)

    def pool_map(t, bt, ln, ly, *sc):
        ci = clip(t - S1, B_p * maxp)
        b = ci // maxp
        # Dead cells (past the slot's last live page) repeat that page:
        # identical consecutive indices make Mosaic skip the DMA.
        last = jnp.maximum(ln[b] - 1, 0) // page
        pe = jnp.minimum(ci % maxp, last)
        return (ly[0], 0, jnp.minimum(bt[b, pe], P - 1), 0, 0)

    in_specs = [
        pl.BlockSpec((B_p, D), const2),                        # x (norm)
        pl.BlockSpec((B_p, to),
                     lambda t, *pf: (0, clip(t - S2, To))),    # x (resid)
        pl.BlockSpec((1, D), const2),                          # ln_attn
        pl.BlockSpec((1, D), const2),                          # ln_mlp
        pl.BlockSpec((B_p, hd // 2), const2),                  # sin
        pl.BlockSpec((B_p, hd // 2), const2),                  # cos
        pl.BlockSpec((D, tq), lambda t, *pf: (0, clip(t, Tq))),
        pl.BlockSpec((1, tq), lambda t, *pf: (0, clip(t, Tq))),
        pl.BlockSpec((1, KVH, 1, page, hd), pool_map),         # k pages
        pl.BlockSpec((1, KVH, 1, page, hd), pool_map),         # v pages
        pl.BlockSpec((H * hd, to),
                     lambda t, *pf: (0, clip(t - S2, To))),    # wo
        pl.BlockSpec((1, to),
                     lambda t, *pf: (0, clip(t - S2, To))),    # so
        pl.BlockSpec((D, tm),
                     lambda t, *pf: (0, clip(t - S3, Tm))),    # w gate
        pl.BlockSpec((D, tm),
                     lambda t, *pf: (0, M // tm + clip(t - S3, Tm))),
        pl.BlockSpec((1, tm),
                     lambda t, *pf: (0, clip(t - S3, Tm))),    # s gate
        pl.BlockSpec((1, tm),
                     lambda t, *pf: (0, M // tm + clip(t - S3, Tm))),
        pl.BlockSpec((tm, D),
                     lambda t, *pf: (clip(t - S3, Tm), 0)),    # w_down
        pl.BlockSpec((1, D), const2),                          # sd
    ]
    out_specs = [
        pl.BlockSpec((B_p, D), const2),
        pl.BlockSpec((B_p, KVH * hd), const2),
        pl.BlockSpec((B_p, KVH * hd), const2),
    ]
    scratch = [
        pltpu.VMEM((B_p, D), jnp.float32),                 # xn_s
        pltpu.VMEM((Tq, B_p, tq), jnp.float32),            # qkv_s
        pltpu.VMEM((B_p, KVH, qpg_p, hd), jnp.float32),    # qs
        pltpu.VMEM((B_p, KVH, qpg_p, 1), jnp.float32),     # m_s
        pltpu.VMEM((B_p, KVH, qpg_p, 1), jnp.float32),     # l_s
        pltpu.VMEM((B_p, KVH, qpg_p, hd), jnp.float32),    # acc_s
        pltpu.VMEM((B_p, H * hd), jnp.float32),            # ao_s
        pltpu.VMEM((To, B_p, to), jnp.float32),            # h_s
        pltpu.VMEM((B_p, D), jnp.float32),                 # y_s
    ]
    ly = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    prefetch = [block_tables.astype(jnp.int32),
                lengths.astype(jnp.int32), ly]
    if quantized:
        ly_s = jnp.asarray(layer_idx, jnp.int32)
        prefetch += [k_scales[ly_s, :, :, 0], v_scales[ly_s, :, :, 0]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S4,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kern = functools.partial(
        _fused_kernel, B=B_p, D=D, H=H, KVH=KVH, qpg=qpg, qpg_p=qpg_p,
        hd=hd, page=page, P=P, maxp=maxp, M=M, tq=tq, to=to, tm=tm,
        eps=eps, scale=hd ** -0.5, soft_cap=soft_cap,
        quantized=quantized, dot_dt=dt)
    x_out, k_new, v_new = pl.pallas_call(
        kern,
        name="fused_decode_layer",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B_p, D), dt),
            jax.ShapeDtypeStruct((B_p, KVH * hd), dt),
            jax.ShapeDtypeStruct((B_p, KVH * hd), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 2**20),
        interpret=platform.interpret_mode(),
    )(*prefetch, x, x, ln_a, ln_m, sin.astype(jnp.float32),
      cos.astype(jnp.float32), wqkv, sqkv, k_pools, v_pools, wo, so,
      wg, wg, sg, sg, wd, sd)
    return (x_out[:B], k_new[:B].reshape(B, KVH, hd),
            v_new[:B].reshape(B, KVH, hd))
