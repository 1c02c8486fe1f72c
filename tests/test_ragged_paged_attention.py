"""Ragged paged attention: one kernel, one batch for mixed
prefill+decode (PAPERS.md "Ragged Paged Attention"; ROADMAP item #1).

Three layers of parity, all interpret-mode on CPU:

  * kernel vs dense-gather reference (fp32 and int8 pools, with and
    without the max_row_tokens VMEM cap);
  * the in-place append kernels vs their scatter references;
  * llama.ragged_step_paged end-to-end against the existing
    prefill_slot_paged + decode_slots_paged pipeline — same pages,
    same tokens, greedy-argmax-identical — across fp32, int8-KV,
    fused-megakernel, and int8-weight (w8a16) configs.

Everything here is fp32/argmax-exact by construction; bf16 configs are
exercised through the engine suite, where greedy equality is NOT a
contract (XLA keeps excess precision under jit, so bf16 logit ties may
round differently between fused programs — both roundings are valid).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import ragged_paged_attention as rpa

pytestmark = pytest.mark.long_file(274)


# Every kernel call goes through one of these: under the Pallas
# interpreter an EAGER call compiles its kernel anew, so the cases of one
# shape share a ``jax.jit`` and differ in operands only (the row arrays,
# the layer index and the cell lists are arrays, never constants).
_attend = jax.jit(rpa.ragged_paged_attention,
                  static_argnames=("soft_cap", "max_row_tokens"))
_fused_layer = jax.jit(
    rpa.fused_ragged_layer,
    static_argnames=("eps", "n_heads", "n_kv_heads", "max_row_tokens"))
_ragged_step = jax.jit(llama.ragged_step_paged,
                       static_argnames=("cfg", "max_row_tokens"))
_prefill_slot = jax.jit(llama.prefill_slot_paged, static_argnames=("cfg",))
_decode_slots = jax.jit(llama.decode_slots_paged, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("every_cell",))
def _append(state, k_new, v_new, slot, start, nlen, off, bt, *,
            every_cell=False):
    """``ragged_paged_append`` (two pools) or ``..._quantized`` (two pools
    and their scales) over the cells that write, or, traced with
    ``every_cell``, over a list of ALL ``R x NPR`` cells: the walk the
    append was before it had a list."""
    fn = (rpa.ragged_paged_append if len(state) == 2
          else rpa.ragged_paged_append_quantized)
    with pytest.MonkeyPatch.context() as patch:
        if every_cell:
            cells = slot.shape[0] * _NPR
            patch.setattr(rpa, "live_append_cells", lambda *a: (
                jnp.arange(cells, dtype=jnp.int32),
                jnp.full((1,), cells, jnp.int32)))
        return fn(*state, k_new, v_new, slot, start, nlen, off, bt)


def _mixed_rows(T=48, R=4):
    """One decode row, one mid-prompt prefill chunk, one fresh prefill,
    one padding row — the shapes a real engine step packs."""
    return (np.asarray([2, 0, 3, 0], np.int32),    # slot
            np.asarray([19, 0, 7, 0], np.int32),   # start
            np.asarray([1, 11, 13, 0], np.int32),  # len (0 = padding)
            np.asarray([0, 1, 12, 0], np.int32))   # off


def _pools(rng, L, KVH, Pt, page, D, int8=False):
    k = rng.standard_normal((L, KVH, Pt, page, D)).astype(np.float32)
    v = rng.standard_normal((L, KVH, Pt, page, D)).astype(np.float32)
    if not int8:
        return jnp.asarray(k), jnp.asarray(v), None, None
    ks = np.abs(k).max(axis=(1, 3, 4), initial=1e-6) / 127.0
    vs = np.abs(v).max(axis=(1, 3, 4), initial=1e-6) / 127.0
    kq = np.round(k / ks[:, None, :, None, None]).astype(np.int8)
    vq = np.round(v / vs[:, None, :, None, None]).astype(np.int8)
    return (jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(np.repeat(ks[:, :, None, None], KVH, axis=2)),
            jnp.asarray(np.repeat(vs[:, :, None, None], KVH, axis=2)))


@pytest.mark.parametrize("mrt", [None, 16])
@pytest.mark.parametrize("int8", [False, True])
def test_kernel_matches_reference(mrt, int8):
    rng = np.random.default_rng(0)
    L, KVH, Pt, page, D, H = 2, 2, 17, 16, 8, 4
    T, _R = 48, 4
    kp, vp, ks, vs = _pools(rng, L, KVH, Pt, page, D, int8=int8)
    # Shuffled physical pages: the block-table indirection must be
    # honored (page Pt-1 is the scratch page and stays out of tables).
    bt = rng.permutation(Pt - 1)[:16].reshape(4, 4).astype(np.int32)
    rs, rst, rl, ro = _mixed_rows(T)
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    kn = rng.standard_normal((T, KVH, D)).astype(np.float32)
    vn = rng.standard_normal((T, KVH, D)).astype(np.float32)
    for layer in (0, 1):
        kl = (kp[layer].astype(jnp.float32) if not int8 else kp[layer])
        vl = (vp[layer].astype(jnp.float32) if not int8 else vp[layer])
        ref = rpa.ragged_attention_reference(
            q, kn, vn, kl, vl, rs, rst, rl, ro, bt,
            k_scales=None if ks is None else ks[layer],
            v_scales=None if vs is None else vs[layer])
        got = _attend(
            jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), kp, vp,
            jnp.int32(layer), jnp.asarray(rs), jnp.asarray(rst),
            jnp.asarray(rl), jnp.asarray(ro), jnp.asarray(bt), k_scales=ks,
            v_scales=vs, max_row_tokens=mrt)
        mask = np.zeros(T, bool)
        for r in range(4):
            mask[ro[r]:ro[r] + rl[r]] = rl[r] > 0
        np.testing.assert_allclose(np.asarray(got)[mask],
                                   np.asarray(ref)[mask],
                                   atol=2e-5, rtol=2e-5)
        # Buffer rows no row covers are zero, never garbage.
        assert not np.any(np.asarray(got)[~mask])


def test_kernel_soft_cap():
    rng = np.random.default_rng(1)
    L, KVH, Pt, page, D, H, T = 1, 1, 9, 16, 8, 2, 16
    kp, vp, _, _ = _pools(rng, L, KVH, Pt, page, D)
    bt = np.arange(8, dtype=np.int32).reshape(2, 4)
    rs = np.asarray([1, 0], np.int32)
    rst = np.asarray([33, 0], np.int32)
    rl = np.asarray([1, 0], np.int32)
    ro = np.asarray([0, 0], np.int32)
    q = rng.standard_normal((T, H, D)).astype(np.float32) * 4
    kn = rng.standard_normal((T, KVH, D)).astype(np.float32)
    vn = rng.standard_normal((T, KVH, D)).astype(np.float32)
    ref = rpa.ragged_attention_reference(
        q, kn, vn, kp[0], vp[0], rs, rst, rl, ro, bt, soft_cap=20.0)
    got = _attend(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), kp, vp,
        jnp.int32(0), jnp.asarray(rs), jnp.asarray(rst), jnp.asarray(rl),
        jnp.asarray(ro), jnp.asarray(bt), soft_cap=20.0)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref)[0],
                               atol=2e-5, rtol=2e-5)


def test_append_matches_reference():
    rng = np.random.default_rng(2)
    L, KVH, Pt, page, D, T = 2, 2, 17, 16, 8, 48
    kp, vp, _, _ = _pools(rng, L, KVH, Pt, page, D)
    bt = rng.permutation(Pt - 1)[:16].reshape(4, 4).astype(np.int32)
    rs, rst, rl, ro = _mixed_rows(T)
    kn = rng.standard_normal((L, T, KVH, D)).astype(np.float32)
    vn = rng.standard_normal((L, T, KVH, D)).astype(np.float32)
    want_k, want_v = kp, vp
    for layer in range(L):
        wk, wv = rpa.ragged_append_reference(
            want_k[layer], want_v[layer], kn[layer], vn[layer],
            rs, rst, rl, ro, bt)
        want_k = want_k.at[layer].set(wk)
        want_v = want_v.at[layer].set(wv)
    got_k, got_v = _append(
        (kp, vp), jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(rs),
        jnp.asarray(rst), jnp.asarray(rl), jnp.asarray(ro),
        jnp.asarray(bt))
    # The scratch page (Pt-1) is garbage-tolerant; everything else must
    # match the scatter reference exactly.
    np.testing.assert_array_equal(np.asarray(got_k)[:, :, :-1],
                                  np.asarray(want_k)[:, :, :-1])
    np.testing.assert_array_equal(np.asarray(got_v)[:, :, :-1],
                                  np.asarray(want_v)[:, :, :-1])


def test_append_quantized_grow_only_scales():
    """Fresh tokens land dequant-close; a page extended by a small-
    magnitude row keeps its scale (existing int8 stays bit-stable)."""
    rng = np.random.default_rng(3)
    L, KVH, Pt, page, D, T = 1, 1, 5, 16, 8, 16
    kq = np.zeros((L, KVH, Pt, page, D), np.int8)
    vq = np.zeros((L, KVH, Pt, page, D), np.int8)
    ks = np.full((L, Pt, KVH, 1), 0.05, np.float32)
    vs = np.full((L, Pt, KVH, 1), 0.05, np.float32)
    # page 0 holds 8 tokens of slot 0 already, quantized at scale 0.05
    kq[0, :, 0, :8] = rng.integers(-100, 100, (KVH, 8, D))
    bt = np.full((1, 2), Pt, np.int32)
    bt[0, :2] = [0, 1]
    rs = np.asarray([0], np.int32)
    rst = np.asarray([8], np.int32)
    rl = np.asarray([4], np.int32)
    ro = np.asarray([0], np.int32)
    kn = (rng.standard_normal((L, T, KVH, D)) * 0.01).astype(np.float32)
    vn = (rng.standard_normal((L, T, KVH, D)) * 0.01).astype(np.float32)
    gk, gv, gks, gvs = _append(
        (jnp.asarray(kq), jnp.asarray(vq), jnp.asarray(ks),
         jnp.asarray(vs)), jnp.asarray(kn), jnp.asarray(vn),
        jnp.asarray(rs), jnp.asarray(rst), jnp.asarray(rl),
        jnp.asarray(ro), jnp.asarray(bt))
    # grow-only: the small appended row must not shrink page 0's scale
    assert float(gks[0, 0, 0, 0]) == pytest.approx(0.05)
    # pre-existing int8 values are untouched
    np.testing.assert_array_equal(np.asarray(gk)[0, :, 0, :8], kq[0, :, 0, :8])
    # the fresh tokens dequantize back within one quant step
    deq = np.asarray(gk, np.float32)[0, :, 0, 8:12] \
        * float(gks[0, 0, 0, 0])
    np.testing.assert_allclose(deq, kn[0, :4].transpose(1, 0, 2),
                               atol=float(gks[0, 0, 0, 0]))
    del gv, gvs


def test_pack_ragged_batch_contract():
    rows = [
        dict(slot=2, start=19, tokens=None),          # decode
        dict(slot=0, start=0, tokens=[5, 6, 7]),      # prefill chunk
        dict(slot=3, start=16, tokens=[9, 9]),        # later chunk
    ]
    (htoks, dmask, tslot, tpos, rslot, rstart, rlen, roff
     ) = rpa.pack_ragged_batch(rows, token_budget=8, max_slots=4)
    assert list(rlen) == [1, 3, 2, 0]
    assert list(roff) == [0, 1, 4, 0]
    assert list(rslot) == [2, 0, 3, 0]
    assert list(rstart) == [19, 0, 16, 0]
    # decode rows read from the device cur; prefill rows from the host
    assert list(dmask[:6]) == [True, False, False, False, False, False]
    assert list(tslot[:1]) == [2]
    assert list(htoks[1:6]) == [5, 6, 7, 9, 9]
    # absolute positions: decode at start, chunks start+i
    assert list(tpos[:6]) == [19, 0, 1, 2, 16, 17]
    # over-budget / over-slots packing is a scheduler bug, not a clamp
    with pytest.raises(AssertionError):
        rpa.pack_ragged_batch(
            [dict(slot=0, start=0, tokens=list(range(9)))],
            token_budget=8, max_slots=4)
    with pytest.raises(AssertionError):
        rpa.pack_ragged_batch(
            [dict(slot=s, start=0, tokens=None) for s in range(5)],
            token_budget=8, max_slots=4)


def test_window_size_caps_vmem_window():
    # uncapped: the whole (padded) buffer
    assert rpa.window_size(48, None) == 48
    # capped: rounded row bound + the 8-row alignment slack
    assert rpa.window_size(256, 16) == 24
    # cap can never exceed the buffer itself
    assert rpa.window_size(16, 64) == 16


# ---------------------------------------------------------------------------
# end-to-end: ragged_step_paged vs the prefill+decode pipeline
# ---------------------------------------------------------------------------


def _pipeline_oracle(params, cfg, prompts, bt, num_pages, page,
                     decode_steps):
    """The existing two-program pipeline: per-slot prefill, then lockstep
    decode — the numbers the ragged step must reproduce."""
    cache = llama.init_paged_cache(cfg, num_pages, page)
    firsts = []
    for s, p in enumerate(prompts):
        S = ((len(p) + page - 1) // page) * page
        toks = np.zeros(S, np.int32)
        toks[:len(p)] = p
        lg, cache = _prefill_slot(
            params, jnp.asarray(toks), jnp.asarray(len(p)),
            jnp.asarray(bt[s, :S // page]), cfg, cache)
        firsts.append(int(jnp.argmax(lg)))
    lens = np.asarray([len(p) for p in prompts], np.int32)
    cur = np.asarray(firsts, np.int32)
    outs = [[c] for c in cur]
    for _ in range(decode_steps):
        lg, cache, lens = _decode_slots(
            params, jnp.asarray(cur), jnp.ones(len(prompts), bool),
            jnp.asarray(bt), jnp.asarray(lens), cfg, cache)
        cur = np.asarray(jnp.argmax(lg, -1)).astype(np.int32)
        for s in range(len(prompts)):
            outs[s].append(int(cur[s]))
    return outs


def _ragged_run(params, cfg, prompts, bt, num_pages, page, decode_steps):
    """Same tokens through ragged steps: step 1 packs slot 0's whole
    prompt next to slot 1's first chunk; step 2 MIXES slot 0's first
    decode with slot 1's closing chunk; then both decode."""
    cache = llama.init_paged_cache(cfg, num_pages, page)
    T, R = 48, 4
    outs = [[], []]

    def step(rows):
        nonlocal cache
        (htoks, _dm, _ts, tpos, rslot, rstart, rlen, roff
         ) = rpa.pack_ragged_batch(rows, T, R)
        lg, cache2 = _ragged_step(
            params, jnp.asarray(htoks), jnp.asarray(tpos),
            jnp.asarray(rslot), jnp.asarray(rstart), jnp.asarray(rlen),
            jnp.asarray(roff), jnp.asarray(bt), cfg, cache,
            max_row_tokens=32)
        cache = cache2
        return np.asarray(jnp.argmax(lg, -1))

    p0, p1 = prompts
    arg = step([dict(slot=0, start=0, tokens=list(p0)),
                dict(slot=1, start=0, tokens=list(p1[:16]))])
    outs[0].append(int(arg[0]))
    arg = step([dict(slot=0, start=len(p0), tokens=[outs[0][-1]]),
                dict(slot=1, start=16, tokens=list(p1[16:]))])
    outs[0].append(int(arg[0]))
    outs[1].append(int(arg[1]))
    lens = np.asarray([len(p0) + 1, len(p1)])
    for _ in range(decode_steps - 1):
        arg = step([
            dict(slot=0, start=int(lens[0]), tokens=[outs[0][-1]]),
            dict(slot=1, start=int(lens[1]), tokens=[outs[1][-1]])])
        lens += 1
        outs[0].append(int(arg[0]))
        outs[1].append(int(arg[1]))
    return outs


@pytest.mark.parametrize("kv_int8,fused", [
    (False, False),
    # The single-axis variants add ~30s of compile for paths the
    # corners already cross — keep them for `-m slow` sweeps only.
    pytest.param(True, False, marks=pytest.mark.slow),
    pytest.param(False, True, marks=pytest.mark.slow),
    (True, True)])
def test_ragged_step_matches_pipeline(kv_int8, fused):
    cfg = llama.LlamaConfig(
        vocab_size=211, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
        mlp_dim=256, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, kv_int8=kv_int8, fused_decode=fused)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 211, 13), rng.integers(1, 211, 29)]
    page, num_pages, maxp = 16, 16, 4
    bt = np.full((2, maxp), num_pages, np.int32)   # OOB sentinel
    bt[0, :2] = [0, 1]
    bt[1, :3] = [2, 3, 4]
    want = _pipeline_oracle(params, cfg, prompts, bt, num_pages, page,
                            decode_steps=3)
    got = _ragged_run(params, cfg, prompts, bt, num_pages, page,
                      decode_steps=3)
    # slot 1's first token arrives one ragged step later by packing
    assert got[0] == want[0][:len(got[0])]
    assert got[1] == want[1][:len(got[1])]


def test_ragged_step_matches_pipeline_int8_weights():
    """w8a16: both paths dequantize per layer inside their scans
    (llama._deq_layer), so greedy tokens must agree exactly."""
    from ray_tpu.models import quant

    cfg = llama.LlamaConfig(
        vocab_size=211, dim=128, n_layers=2, n_heads=2, n_kv_heads=1,
        mlp_dim=256, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = quant.init_quantized_llama(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 211, 13), rng.integers(1, 211, 29)]
    page, num_pages, maxp = 16, 16, 4
    bt = np.full((2, maxp), num_pages, np.int32)
    bt[0, :2] = [0, 1]
    bt[1, :3] = [2, 3, 4]
    want = _pipeline_oracle(params, cfg, prompts, bt, num_pages, page,
                            decode_steps=2)
    got = _ragged_run(params, cfg, prompts, bt, num_pages, page,
                      decode_steps=2)
    assert got[0] == want[0][:len(got[0])]
    assert got[1] == want[1][:len(got[1])]


# ---------------------------------------------------------------------------
# the fused layer reads the stacked weights in place
# ---------------------------------------------------------------------------

_TOY = dict(vocab_size=211, dim=128, n_layers=3, n_heads=2, n_kv_heads=1,
            mlp_dim=256, max_seq_len=256, dtype=jnp.float32,
            param_dtype=jnp.float32)
IN_PLACE = {"in_place": ["w_down", "w_gateup", "wo", "wqkv"],
            "sliced": ["ln_attn", "ln_mlp"]}
ASSEMBLED = {"in_place": ["w_down", "wo"],
             "sliced": ["ln_attn", "ln_mlp", "w_gateup", "wqkv"]}


@functools.cache
def _fused_artifact(cfg, key=0):
    """The serving artifact: int8 weights, q/k/v and gate/up fused.
    Random norm vectors, so that a layer read from the wrong place shows
    in every operand.  Built once a config: the cases only read it."""
    from ray_tpu.models import quant

    params = quant.fuse_for_decode(
        quant.init_quantized_llama(jax.random.PRNGKey(key), cfg), cfg)
    for i, name in enumerate(("ln_attn", "ln_mlp")):
        params["layers"][name] = 1.0 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(10 + i), params["layers"][name].shape)
    return params


def _fused_layer_call(layers, kp, vp, ks, vs, li, x, sin, cos):
    slot, start, nlen, off = _mixed_rows()
    bt = jnp.asarray(np.arange(16, dtype=np.int32).reshape(4, 4))
    return _fused_layer(
        x, layers, kp, vp, jnp.int32(li), jnp.asarray(slot),
        jnp.asarray(start), jnp.asarray(nlen), jnp.asarray(off), bt,
        sin, cos, eps=1e-5, n_heads=2, n_kv_heads=1, k_scales=ks,
        v_scales=vs, max_row_tokens=16)


@pytest.mark.parametrize("li", [0, 1, 2])
@pytest.mark.parametrize("kv_int8", [False, True])
def test_fused_layer_reads_the_stack_in_place(kv_int8, li):
    """Fed the whole stack and a layer index, the kernel gives the bits
    it gives for a one-layer stack of that layer at index 0: its index
    maps picked that layer's weights, scales and pages, and nothing was
    rounded on the way."""
    cfg = llama.LlamaConfig(**_TOY, kv_int8=kv_int8)
    layers = _fused_artifact(cfg)["layers"]
    assert rpa.weight_routes(layers) == IN_PLACE
    rng = np.random.default_rng(5)
    L, T, hd = cfg.n_layers, 48, cfg.head_dim
    kp, vp, ks, vs = _pools(rng, L, 1, 17, 16, hd, int8=kv_int8)
    x = jnp.asarray(rng.standard_normal((T, cfg.dim)), jnp.float32)
    sin, cos = llama.rope_table(cfg, jnp.arange(T)[None])
    sin, cos = sin[0], cos[0]

    def one_layer(i):
        # None (no page scales) is an empty subtree and stays None
        return jax.tree.map(lambda a: a[i:i + 1], (layers, kp, vp, ks, vs))

    got = _fused_layer_call(layers, kp, vp, ks, vs, li, x, sin, cos)
    want = _fused_layer_call(*one_layer(li), 0, x, sin, cos)
    other = _fused_layer_call(*one_layer((li + 1) % L), 0, x, sin, cos)
    for g, w, o in zip(got, want, other):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert not np.array_equal(np.asarray(g), np.asarray(o))


def _big_int8_slices(jaxpr, min_bytes):
    """dynamic_slice equations, anywhere in ``jaxpr``, whose result is
    int8 and at least ``min_bytes`` large."""
    found = []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _big_int8_slices(sub, min_bytes)
        if eqn.primitive.name == "dynamic_slice":
            aval = eqn.outvars[0].aval
            if aval.dtype == jnp.int8 and aval.size >= min_bytes:
                found.append(aval)
    return found


@pytest.mark.parametrize("tree,routes,sliced_int8", [
    ("fused_int8", IN_PLACE, 0),
    ("separate_int8", ASSEMBLED, 5),     # wq wk wv, w_gate w_up
    ("separate_plain", ASSEMBLED, 0)])
def test_weight_routes_follow_the_tree(tree, routes, sliced_int8):
    """In place or sliced is read off the parameter tree: the fused
    artifact's step takes no slice of an int8 weight at all; a tree with
    separate projections slices exactly those and assembles them."""
    from ray_tpu.models import quant

    cfg = llama.LlamaConfig(**_TOY, kv_int8=True, fused_decode=True)
    if tree == "fused_int8":
        params = _fused_artifact(cfg)
    elif tree == "separate_int8":
        params = quant.init_quantized_llama(jax.random.PRNGKey(0), cfg)
    else:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert rpa.weight_routes(params["layers"]) == routes
    assert llama.ragged_weight_routes(params, cfg) == routes
    assert llama.ragged_weight_routes(
        params, dataclasses.replace(cfg, fused_decode=False)) is None
    slot, start, nlen, off = (jnp.asarray(a) for a in _mixed_rows())
    T = 48
    jaxpr = jax.make_jaxpr(
        lambda p, c: llama.ragged_step_paged(
            p, jnp.ones(T, jnp.int32), jnp.arange(T), slot, start, nlen,
            off, jnp.zeros((4, 4), jnp.int32), cfg, c,
            max_row_tokens=16))(
                params, llama.init_paged_cache(cfg, 16, 16))
    smallest_weight = cfg.dim * cfg.n_kv_heads * cfg.head_dim
    assert len(_big_int8_slices(jaxpr.jaxpr, smallest_weight)) == sliced_int8


@pytest.mark.parametrize("kv_int8", [False, True])
def test_fused_artifact_step_matches_unfused(kv_int8):
    """The serving artifact through the fused step (weights read in
    place) against the same artifact through the unfused step, which
    dequantizes each layer's slice: same logits to float32 rounding of
    a different order of sums, and pages written alike."""
    cfg = llama.LlamaConfig(**_TOY, kv_int8=kv_int8)
    params = _fused_artifact(cfg)
    rng = np.random.default_rng(2)
    rows = [dict(slot=0, start=0, tokens=list(rng.integers(1, 211, 13))),
            dict(slot=1, start=0, tokens=list(rng.integers(1, 211, 21)))]
    bt = np.full((4, 4), 16, np.int32)
    bt[0, :2], bt[1, :3] = [0, 1], [2, 3, 4]

    def run(fused):
        c = dataclasses.replace(cfg, fused_decode=fused)
        cache = llama.init_paged_cache(c, 16, 16)
        out = []
        step_rows = rows
        for _ in range(2):
            (ht, _dm, _ts, pos, rs, r0, rl, ro) = rpa.pack_ragged_batch(
                step_rows, 48, 4)
            lg, cache = _ragged_step(
                params, jnp.asarray(ht), jnp.asarray(pos), jnp.asarray(rs),
                jnp.asarray(r0), jnp.asarray(rl), jnp.asarray(ro),
                jnp.asarray(bt), c, cache, max_row_tokens=32)
            out.append(np.asarray(lg[:2]))
            step_rows = [dict(slot=s, start=len(r["tokens"]), tokens=[7 + s])
                         for s, r in enumerate(rows)]
        return out

    for got, want in zip(run(True), run(False)):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(
            want).max())


# ---------------------------------------------------------------------------
# the fused layer walks only the page cells its rows hold
# ---------------------------------------------------------------------------

_PAGE, _MAXP, _SLOTS = 16, 4, 6
# (slot, start, len, off) of six packed rows over a 6 x 4 page table of
# 16-token pages: where a walk over the live cells only differs from a
# walk over all 6 x 5 of them.
LIVE_WALK_ROWS = {
    # padding rows in front of, between and behind the live ones
    "mostly_padding": ([0, 2, 0, 5, 1, 0], [0, 19, 0, 16, 40, 0],
                       [0, 1, 0, 11, 1, 0], [0, 0, 0, 1, 12, 0]),
    # rows with nothing pooled yet: a self cell and no pool cell
    "start_zero": ([3, 1, 4, 0, 0, 0], [0, 0, 33, 0, 0, 0],
                   [13, 1, 1, 0, 0, 0], [0, 13, 14, 0, 0, 0]),
    # pooled tokens end exactly at a page's end
    "start_on_page_boundary": ([0, 1, 2, 0, 0, 0], [16, 32, 48, 0, 0, 0],
                               [1, 5, 1, 0, 0, 0], [0, 1, 6, 0, 0, 0]),
    "prompts_beside_decode": ([4, 0, 2, 5, 0, 0], [21, 0, 50, 32, 0, 0],
                              [1, 20, 1, 9, 0, 0], [0, 1, 21, 22, 0, 0]),
    # every row live at the longest context: every cell of the table
    "full_table": (list(range(6)), [63] * 6, [1] * 6, list(range(6))),
}


def _rows(case):
    return tuple(np.asarray(a, np.int32) for a in LIVE_WALK_ROWS[case])


def _old_walks_live_cells(start, nlen, maxp, page):
    """The cells, in the order of the kernel's old walk over all of
    them, at which its ``pl.when`` conditions fired."""
    return [r * (maxp + 1) + pc
            for r in range(len(nlen)) for pc in range(maxp + 1)
            if nlen[r] > 0 and (pc == maxp or pc * page < start[r])]


@pytest.mark.parametrize("case", list(LIVE_WALK_ROWS) + ["all_padding"])
def test_live_page_cells_are_the_old_walks_live_cells(case):
    if case == "all_padding":
        start, nlen = np.zeros(6, np.int32), np.zeros(6, np.int32)
    else:
        _slot, start, nlen, _off = _rows(case)
    want = _old_walks_live_cells(start, nlen, _MAXP, _PAGE)
    live_ci, n_live = rpa.live_page_cells(
        jnp.asarray(start), jnp.asarray(nlen), _MAXP, _PAGE)
    assert live_ci.shape == (_SLOTS * (_MAXP + 1),) and n_live.shape == (1,)
    assert live_ci.dtype == n_live.dtype == jnp.int32
    n = int(n_live[0])
    assert n == len(want) == rpa.live_cell_count(start, nlen, _PAGE)
    assert np.asarray(live_ci)[:n].tolist() == want
    assert (n == _SLOTS * (_MAXP + 1)) == (case == "full_table")
    # a row's self cell, which finalises it, comes after its pool cells
    assert want == sorted(want)


def _live_walk_setup(kv_int8, seed=6):
    cfg = llama.LlamaConfig(**dict(_TOY, n_layers=2), kv_int8=kv_int8)
    params = _fused_artifact(cfg)
    rng = np.random.default_rng(seed)
    Pt = _SLOTS * _MAXP + 1
    kp, vp, ks, vs = _pools(rng, cfg.n_layers, 1, Pt, _PAGE, cfg.head_dim,
                            int8=kv_int8)
    bt = rng.permutation(Pt - 1).reshape(_SLOTS, _MAXP).astype(np.int32)
    return cfg, params, rng, (kp, vp, ks, vs), jnp.asarray(bt)


@pytest.mark.parametrize("case", list(LIVE_WALK_ROWS))
@pytest.mark.parametrize("kv_int8", [False, True])
def test_fused_live_walk_matches_unfused_step(kv_int8, case):
    """The fused step, whose attention phase walks the live cells only,
    against the unfused step (``ragged_paged_attention``, held to the
    dense reference above) on the same rows, pages and weights."""
    cfg, params, rng, (kp, vp, ks, vs), bt = _live_walk_setup(kv_int8)
    slot, start, nlen, off = _rows(case)
    T = 48
    toks = rng.integers(1, cfg.vocab_size, T).astype(np.int32)
    pos = np.zeros(T, np.int32)
    for r in range(_SLOTS):
        pos[off[r]:off[r] + nlen[r]] = start[r] + np.arange(nlen[r])
    cache = {"k": kp, "v": vp}
    if kv_int8:
        cache.update(k_scale=ks, v_scale=vs)

    def run(fused):
        logits, _ = _ragged_step(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(slot),
            jnp.asarray(start), jnp.asarray(nlen), jnp.asarray(off), bt,
            dataclasses.replace(cfg, fused_decode=fused), cache,
            max_row_tokens=32)
        return np.asarray(logits)[nlen > 0]

    got, want = run(True), run(False)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("case", list(LIVE_WALK_ROWS))
@pytest.mark.parametrize("kv_int8", [False, True])
def test_fused_live_walk_gives_the_old_walks_bits(kv_int8, case):
    """Handed a list of ALL the table's cells the kernel is the walk it
    was before (every cell a grid step, ``pl.when`` skipping the dead
    ones).  The list of the live cells gives the same bits: the same
    cells ran the same arithmetic in the same order."""
    cfg, params, rng, (kp, vp, ks, vs), bt = _live_walk_setup(kv_int8)
    slot, start, nlen, off = (jnp.asarray(a) for a in _rows(case))
    T, cells = 48, _SLOTS * (_MAXP + 1)
    x = jnp.asarray(rng.standard_normal((T, cfg.dim)), jnp.float32)
    sin, cos = llama.rope_table(cfg, jnp.arange(T)[None])

    def layer(live_cells):
        return _fused_layer(
            x, params["layers"], kp, vp, jnp.int32(1), slot, start, nlen,
            off, bt, sin[0], cos[0], eps=1e-5, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, k_scales=ks, v_scales=vs,
            max_row_tokens=32, live_cells=live_cells)

    every_cell = (jnp.arange(cells, dtype=jnp.int32),
                  jnp.full((1,), cells, jnp.int32))
    got = layer(rpa.live_page_cells(start, nlen, _MAXP, _PAGE))
    for g, old, built_here in zip(got, layer(every_cell), layer(None)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(old))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(built_here))


# ---------------------------------------------------------------------------
# the fused layer's attention phase: a KV head's group stacked into one
# product, a one-token row through a window of that token
# ---------------------------------------------------------------------------

# (slot, start, len, off) of six packed rows over the same 6 x 4 table
# of 16-token pages, in a 40-token buffer: what decides which of the
# phase's two row paths a cell takes, and where each can go wrong.
STACKED_ROWS = {
    # rows of one token at offsets that are no multiple of 8
    "decode_unaligned": ([2, 0, 5, 1, 3, 0], [19, 33, 63, 5, 40, 0],
                         [1, 1, 1, 1, 1, 0], [1, 3, 6, 11, 13, 0]),
    # a chunk whose window holds its neighbours' tokens on both sides
    "chunk_beside_decode": ([4, 0, 2, 5, 0, 0], [21, 9, 50, 32, 0, 0],
                            [1, 11, 1, 1, 0, 0], [0, 1, 12, 13, 0, 0]),
    # one-token prompts and a fresh chunk: a self cell and no pool
    # cell, the state reset there
    "one_token_start_zero": ([3, 1, 4, 2, 0, 0], [0, 27, 0, 0, 0, 0],
                             [1, 1, 1, 6, 0, 0], [2, 5, 9, 12, 0, 0]),
    # a prompt's LAST chunk of one token, behind chunks of two prompts
    "last_chunk_one_token": ([5, 1, 2, 0, 0, 0], [52, 20, 37, 0, 0, 0],
                             [1, 9, 1, 0, 0, 0], [0, 1, 10, 0, 0, 0]),
    # pooled tokens end exactly at a page's end, on both paths
    "start_on_page_boundary": ([0, 1, 2, 3, 0, 0], [16, 32, 48, 16, 0, 0],
                               [1, 5, 1, 7, 0, 0], [3, 4, 9, 10, 0, 0]),
    # padding rows in front of, between and behind the live ones
    "padding_between": ([0, 2, 0, 5, 1, 0], [0, 19, 0, 16, 40, 0],
                        [0, 1, 0, 11, 1, 0], [0, 0, 0, 1, 12, 0]),
    # no live row: an empty list, the attention phase no grid step
    "empty_list": ([0] * 6, [0] * 6, [0] * 6, [0] * 6),
}
# (H, KVH): MHA is the group of one, GQA's four pad to a sublane tile,
# MQA is the group of H
STACKED_HEADS = {"qpg1": (4, 4), "qpg4": (8, 2), "qpgH": (8, 1)}
_HD, _MLP, _T = 32, 256, 40


def _plain_layers(rng, L, H, KVH, dt):
    """A stacked layer tree of plain (unquantized) fused leaves."""
    D = H * _HD

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * shape[1] ** -0.5, dt)

    return {"attn": {"wqkv": w(L, D, (H + 2 * KVH) * _HD), "wo": w(L, D, D)},
            "mlp": {"w_gateup": w(L, D, 2 * _MLP), "w_down": w(L, _MLP, D)},
            "ln_attn": jnp.asarray(1 + 0.1 * rng.standard_normal((L, D)), dt),
            "ln_mlp": jnp.asarray(1 + 0.1 * rng.standard_normal((L, D)), dt)}


def _layer_reference(x, layers, li, kp, vp, ks, vs, meta, sin, cos, H, KVH,
                     eps):
    """The layer ``fused_ragged_layer`` runs, in plain float32 jnp round
    ``ragged_attention_reference``; what the kernel rounds to the
    operands' dtype (fresh k/v, the attention's output) is rounded."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    dt, T = x.dtype, x.shape[0]
    thru = lambda a: a.astype(dt).astype(jnp.float32)

    def norm(v, g):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g

    def rope(a):
        a1, a2 = a[..., :_HD // 2], a[..., _HD // 2:]
        sn, cs = sin[:, None], cos[:, None]
        return jnp.concatenate([a1 * cs - a2 * sn, a2 * cs + a1 * sn], -1)

    x32 = f32(x)
    heads = (thru(norm(x32, f32(layers["ln_attn"][li])))
             @ f32(layers["attn"]["wqkv"][li])).reshape(T, H + 2 * KVH, _HD)
    q, k = rope(heads[:, :H]), thru(rope(heads[:, H:H + KVH]))
    v = thru(heads[:, H + KVH:])
    attn = rpa.ragged_attention_reference(
        q, k, v, kp[li] if ks is not None else f32(kp[li]),
        vp[li] if ks is not None else f32(vp[li]), *meta,
        k_scales=None if ks is None else ks[li],
        v_scales=None if vs is None else vs[li])
    h = x32 + thru(attn.reshape(T, H * _HD)) @ f32(layers["attn"]["wo"][li])
    gu = thru(norm(h, f32(layers["ln_mlp"][li]))) @ f32(
        layers["mlp"]["w_gateup"][li])
    act = jax.nn.silu(gu[:, :_MLP]) * gu[:, _MLP:]
    return h + thru(act) @ f32(layers["mlp"]["w_down"][li]), k, v


@pytest.mark.parametrize("case", list(STACKED_ROWS))
@pytest.mark.parametrize("heads", list(STACKED_HEADS))
@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
def test_fused_layer_stacked_heads_match_reference(kind, heads, case):
    """``fused_ragged_layer`` against the dense reference layer: its
    attention phase makes one product a KV head and cell (the group's
    query heads stacked, head-major), takes a row of one token through
    a window of that token and a longer row through the step's window,
    and resets the flash state at a row's first cell."""
    rng = np.random.default_rng(38)
    (H, KVH), L, li = STACKED_HEADS[heads], 2, 1
    D, Pt = H * _HD, _SLOTS * _MAXP + 1
    dt = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
    kp, vp, ks, vs = _pools(rng, L, KVH, Pt, _PAGE, _HD, int8=kind == "int8")
    if kind != "int8":
        kp, vp = kp.astype(dt), vp.astype(dt)
    bt = rng.permutation(Pt - 1).reshape(_SLOTS, _MAXP).astype(np.int32)
    slot, start, nlen, off = (np.asarray(a, np.int32)
                              for a in STACKED_ROWS[case])
    meta = tuple(jnp.asarray(a) for a in (slot, start, nlen, off, bt))
    layers = _plain_layers(rng, L, H, KVH, dt)
    x = jnp.asarray(rng.standard_normal((_T, D)), dt)
    pos = np.zeros(_T, np.int32)
    for r in range(_SLOTS):
        pos[off[r]:off[r] + nlen[r]] = start[r] + np.arange(nlen[r])
    ang = pos[:, None] * (1e4 ** (-np.arange(_HD // 2) / (_HD // 2)))[None]
    sin, cos = jnp.asarray(np.sin(ang), jnp.float32), jnp.asarray(
        np.cos(ang), jnp.float32)
    pools_before = [np.asarray(a).copy() for a in (kp, vp)]

    got = _fused_layer(
        x, layers, kp, vp, jnp.int32(li), *meta, sin, cos, eps=1e-5,
        n_heads=H, n_kv_heads=KVH, k_scales=ks, v_scales=vs)
    want = _layer_reference(x, layers, li, kp, vp, ks, vs, meta, sin, cos,
                            H, KVH, 1e-5)
    n_live = int(rpa.live_page_cells(meta[1], meta[2], _MAXP, _PAGE)[1][0])
    assert (n_live == 0) == (case == "empty_list")
    tol = 3e-2 if kind == "bfloat16" else 2e-4
    for g, w_, shape in zip(got, want, ((_T, D), (_T, KVH, _HD),
                                        (_T, KVH, _HD))):
        g, w_ = np.asarray(g, np.float32), np.asarray(w_, np.float32)
        assert g.shape == shape and np.isfinite(g).all()
        np.testing.assert_allclose(g, w_, rtol=0,
                                   atol=tol * np.abs(w_).max())
    # the pools are operands the layer only reads
    for before, after in zip(pools_before, (kp, vp)):
        np.testing.assert_array_equal(before, np.asarray(after))


# ---------------------------------------------------------------------------
# ragged_paged_attention walks its rows' cells, in two calls
# ---------------------------------------------------------------------------

# (slot, start, len, off) over the same 6 x 4 table, in a 48-token
# buffer.  "mixed": decode rows at unaligned offsets between two chunk
# rows, a padding row in the middle, rows with nothing pooled (a chunk
# and a one-token prompt), pasts that end exactly on a page's edge; the
# other two leave one of the kernel's two calls no cell to walk.
TWO_CALL_ROWS = {
    "mixed": ([3, 1, 0, 4, 0, 2], [0, 32, 0, 21, 0, 16],
              [11, 1, 0, 1, 1, 13], [0, 11, 0, 12, 13, 14]),
    "one_token_rows_only": ([2, 0, 5, 1, 0, 3], [19, 33, 16, 63, 0, 0],
                            [1, 1, 1, 1, 0, 1], [0, 1, 2, 3, 0, 4]),
    "chunk_rows_only": ([1, 3, 0, 0, 5, 0], [10, 32, 0, 0, 0, 0],
                        [12, 20, 0, 0, 9, 0], [0, 12, 0, 0, 32, 0]),
}


def _two_call_rows(case):
    return tuple(np.asarray(a, np.int32) for a in TWO_CALL_ROWS[case])


@pytest.mark.parametrize("case", list(TWO_CALL_ROWS) + list(LIVE_WALK_ROWS))
def test_two_calls_walk_each_live_cell_once(case):
    """The lists of the two calls are the live cells split by the row's
    length: together what ``live_cell_count`` says on the host, each in
    ascending order, and each call's positions its own rows' tokens."""
    _slot, start, nlen, off = (
        _two_call_rows(case) if case in TWO_CALL_ROWS else _rows(case))
    one, more = rpa.live_attention_cells(
        jnp.asarray(start), jnp.asarray(nlen), jnp.asarray(off), 45,
        _MAXP, _PAGE)
    walked = []
    for (live_ci, n_live, mine), takes in ((one, nlen == 1), (more, nlen > 1)):
        cells = np.asarray(live_ci)[:int(n_live[0])].tolist()
        assert cells == sorted(cells)
        assert {c // (_MAXP + 1) for c in cells} == set(np.flatnonzero(takes))
        want = np.zeros(48, bool)       # round8(45) positions
        for r in np.flatnonzero(takes):
            want[off[r]:off[r] + nlen[r]] = True
        np.testing.assert_array_equal(np.asarray(mine), want)
        walked += cells
    assert len(walked) == rpa.live_cell_count(start, nlen, _PAGE)
    assert sorted(walked) == _old_walks_live_cells(start, nlen, _MAXP, _PAGE)
    if case == "one_token_rows_only":
        assert int(more[1][0]) == 0 and not np.asarray(more[2]).any()
    if case == "chunk_rows_only":
        assert int(one[1][0]) == 0 and not np.asarray(one[2]).any()


@pytest.mark.parametrize("case", list(TWO_CALL_ROWS))
@pytest.mark.parametrize("heads", [(20, 1), (8, 2)], ids=["mqa", "gqa"])
@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
def test_two_call_kernel_matches_reference(kind, heads, case):
    """Both calls against the dense reference: Jamba's MQA (20 query
    heads padded to 24 stacked rows a token) and a GQA group of four
    (padded to 8), plain and int8 pools, float32 and bfloat16 operands
    (the kernel computes from float32 casts of either)."""
    rng = np.random.default_rng(21)
    (H, KVH), L, D, T = heads, 2, 8, 48
    Pt = _SLOTS * _MAXP + 1
    dt = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
    kp, vp, ks, vs = _pools(rng, L, KVH, Pt, _PAGE, D, int8=kind == "int8")
    if kind != "int8":
        kp, vp = kp.astype(dt), vp.astype(dt)
    bt = rng.permutation(Pt - 1).reshape(_SLOTS, _MAXP).astype(np.int32)
    slot, start, nlen, off = _two_call_rows(case)
    q, kn, vn = (jnp.asarray(rng.standard_normal((T, n, D)), dt)
                 for n in (H, KVH, KVH))
    meta = tuple(jnp.asarray(a) for a in (slot, start, nlen, off, bt))
    layer = 1
    ref = rpa.ragged_attention_reference(
        q.astype(jnp.float32), kn.astype(jnp.float32),
        vn.astype(jnp.float32),
        kp[layer] if kind == "int8" else kp[layer].astype(jnp.float32),
        vp[layer] if kind == "int8" else vp[layer].astype(jnp.float32),
        *meta, k_scales=None if ks is None else ks[layer],
        v_scales=None if vs is None else vs[layer])
    kw = dict(k_scales=ks, v_scales=vs, max_row_tokens=24)
    got = _attend(q, kn, vn, kp, vp, jnp.int32(layer), *meta, **kw)
    assert got.shape == (T, H, D) and got.dtype == jnp.float32
    mask = np.zeros(T, bool)
    for r in range(_SLOTS):
        mask[off[r]:off[r] + nlen[r]] = True
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(ref)[mask],
                               atol=2e-5, rtol=2e-5)
    # positions no row covers are zero, whatever a call left unwritten
    assert not np.any(np.asarray(got)[~mask])
    # lists built once in front of a layer loop give the same bits
    handed = _attend(
        q, kn, vn, kp, vp, jnp.int32(layer), *meta, **kw,
        live_cells=rpa.live_attention_cells(*meta[1:4], T, _MAXP, _PAGE))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(handed))


# ---------------------------------------------------------------------------
# the append walks only the pages its rows write
# ---------------------------------------------------------------------------

_BUDGET = 48
_NPR = rpa._pages_per_row(rpa.window_size(_BUDGET, None), _PAGE)
# (slot, start, len, off) of six packed rows over the same 6 x 4 table,
# in a 48-token buffer: where the pages a step writes differ from the
# 6 x _NPR cells the append used to walk in every layer.
APPEND_ROWS = {
    "decode_only": ([2, 0, 5, 1, 0, 0], [19, 33, 16, 63, 0, 0],
                    [1, 1, 1, 1, 0, 0], [0, 1, 2, 3, 0, 0]),
    "chunk_crosses_pages": ([1, 3, 0, 0, 0, 0], [10, 30, 0, 0, 0, 0],
                            [12, 20, 0, 0, 0, 0], [0, 12, 0, 0, 0, 0]),
    # a row that starts a page, one that fills a page to its end
    "start_on_page_boundary": ([0, 1, 2, 0, 0, 0], [16, 32, 48, 0, 0, 0],
                               [1, 16, 5, 0, 0, 0], [0, 1, 17, 0, 0, 0]),
    # one row of the whole budget, off a page's start: every cell of it
    "full_budget_chunk": ([4, 0, 0, 0, 0, 0], [5, 0, 0, 0, 0, 0],
                          [_BUDGET, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]),
    "padding_between": ([0, 2, 0, 5, 1, 0], [0, 19, 0, 16, 40, 0],
                        [0, 1, 0, 11, 1, 0], [0, 0, 0, 1, 12, 0]),
    "all_slots_live": (list(range(6)), [63, 0, 15, 16, 31, 47],
                       [1, 9, 2, 1, 1, 1], [0, 1, 10, 12, 13, 14]),
    "no_live_row": ([0] * 6, [0] * 6, [0] * 6, [0] * 6),
}


def _append_rows(case):
    return tuple(np.asarray(a, np.int32) for a in APPEND_ROWS[case])


def _old_appends_live_cells(start, nlen, npr, page):
    """The cells of the append's old walk over ``(R, NPR)`` at which its
    kernel wrote: the condition its body had (``live``)."""
    return [r * npr + j for r in range(len(nlen)) for j in range(npr)
            if nlen[r] > 0
            and (start[r] // page + j) * page < start[r] + nlen[r]]


@pytest.mark.parametrize("case", list(APPEND_ROWS))
def test_live_append_cells_are_the_old_walks_live_cells(case):
    _slot, start, nlen, _off = _append_rows(case)
    want = _old_appends_live_cells(start, nlen, _NPR, _PAGE)
    live_ci, n_live = rpa.live_append_cells(
        jnp.asarray(start), jnp.asarray(nlen), _NPR, _PAGE)
    assert live_ci.shape == (_SLOTS * _NPR,) and n_live.shape == (1,)
    assert live_ci.dtype == n_live.dtype == jnp.int32
    n = int(n_live[0])
    assert n == len(want) == rpa.append_cell_count(start, nlen, _PAGE)
    assert np.asarray(live_ci)[:n].tolist() == want == sorted(want)
    assert (n == 0) == (case == "no_live_row")
    if case == "full_budget_chunk":
        # the budget's longest row touches every cell a row can have
        assert want == list(range(_NPR))


@pytest.mark.parametrize("case", list(APPEND_ROWS))
@pytest.mark.parametrize("kv_int8", [False, True])
def test_append_live_walk_gives_the_old_walks_bits(kv_int8, case):
    """Both appends over the list of the cells that write, against the
    walk they were before (a list of ALL ``R x NPR`` cells: the ones the
    rows do not reach go to the scratch page and copy it through) and,
    for plain pools, against the scatter reference; a page no row wrote
    keeps its input's bits."""
    rng = np.random.default_rng(11)
    L, KVH, D, Pt = 2, 2, 8, _SLOTS * _MAXP + 1
    kp, vp, ks, vs = _pools(rng, L, KVH, Pt, _PAGE, D, int8=kv_int8)
    bt = rng.permutation(Pt - 1).reshape(_SLOTS, _MAXP).astype(np.int32)
    slot, start, nlen, off = _append_rows(case)
    kn = rng.standard_normal((L, _BUDGET, KVH, D)).astype(np.float32)
    vn = rng.standard_normal((L, _BUDGET, KVH, D)).astype(np.float32)
    state = (kp, vp, ks, vs) if kv_int8 else (kp, vp)

    operands = (state, jnp.asarray(kn), jnp.asarray(vn),
                *(jnp.asarray(a) for a in (slot, start, nlen, off, bt)))
    got = _append(*operands)
    old = _append(*operands, every_cell=True)
    written = sorted({int(bt[slot[r], p // _PAGE]) for r in range(_SLOTS)
                      for p in range(start[r], start[r] + nlen[r])})
    assert len(written) == rpa.append_cell_count(start, nlen, _PAGE)
    kept = np.setdiff1d(np.arange(Pt - 1), written)
    for g, o, before in zip(got, old, state):
        # pools [L, KVH, P, page, D] and scales [L, P, KVH, 1] by page;
        # the scratch page Pt - 1 is garbage-tolerant
        g, o, before = (np.moveaxis(np.asarray(a), 2 if a.ndim == 5 else 1,
                                    0) for a in (g, o, before))
        np.testing.assert_array_equal(g[:-1], o[:-1])
        np.testing.assert_array_equal(g[kept], before[kept])
        if written:
            assert not np.array_equal(g[written], before[written])
    if not kv_int8:
        for layer in range(L):
            wk, wv = rpa.ragged_append_reference(
                kp[layer], vp[layer], kn[layer], vn[layer], slot, start,
                nlen, off, bt)
            np.testing.assert_array_equal(
                np.asarray(got[0])[layer, :, :-1], np.asarray(wk)[:, :-1])
            np.testing.assert_array_equal(
                np.asarray(got[1])[layer, :, :-1], np.asarray(wv)[:, :-1])
