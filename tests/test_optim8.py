"""8-bit Adam states (train/optim8.py, ops/adam8bit.py) vs full-precision
AdamW, vs the flat ``[nb, 256]`` form the state once had, and the kernel
vs its plain reference."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.ops import adam8bit
from ray_tpu.train.optim8 import BLOCK, adamw8bit, scale_by_adam8bit
from ray_tpu.train.step import apply_gradients

pytestmark = pytest.mark.long_file(112)


def _fit(opt, steps=500):
    """Train a small least-squares problem; return final loss."""
    key = jax.random.key(0)
    kw, kx = jax.random.split(key)
    w_true = jax.random.normal(kw, (37, 5))  # 37: exercises block padding
    X = jax.random.normal(kx, (256, 37))
    y = X @ w_true
    params = {"w": jnp.zeros((37, 5))}
    state = opt.init(params)

    def loss_fn(p):
        return jnp.mean((X @ p["w"] - y) ** 2)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    for _ in range(steps):
        params, state, loss = step(params, state)
    return float(loss)


def test_tracks_full_precision_adam():
    lr = 0.05
    full = _fit(optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.scale_by_adam(b1=0.9, b2=0.95),
        optax.scale_by_learning_rate(lr)))
    eight = _fit(optax.chain(
        optax.clip_by_global_norm(1.0),
        scale_by_adam8bit(b1=0.9, b2=0.95),
        optax.scale_by_learning_rate(lr)))
    # Both must converge; int8 states cost at most a modest factor.
    assert full < 1e-2
    assert eight < 5e-2
    assert eight < 10 * max(full, 1e-4)


@pytest.mark.parametrize("shape", [
    (300, 7),           # ragged: flattened and padded
    (3, 64, 512),       # rows of whole blocks
    (2, 32, 4, 128),    # a block is two rows of the [256, 128] view
    (8, 384),           # rows that end in a short block
    (512,),
])
def test_state_is_int8(shape):
    """int8 codes laid out as the leaf's 2-D view, one float32 scale per
    at most 256 elements, under 1.2 bytes a parameter."""
    n = math.prod(shape)
    state = scale_by_adam8bit().init({"w": jnp.zeros(shape)})
    for q, scale in (state.mu["w"], state.nu["w"]):
        assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
        rows, cols = q.shape
        assert (rows, cols) == adam8bit.view_shape(shape)
        assert n <= rows * cols < n + BLOCK
        # a scale serves one row's run of at most BLOCK columns (two
        # rows of 128, each holding it, where a block is two rows)
        assert scale.shape == (-(-cols // BLOCK), rows)
        assert scale.size * BLOCK >= n
        assert q.size + scale.size * 4 < n * 1.2 + BLOCK


def test_adamw8bit_trains_llama_tiny():
    from ray_tpu.models import llama

    cfg = llama.LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    opt = adamw8bit(1e-3, warmup_steps=1)
    state = opt.init(params)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0,
                                cfg.vocab_size)

    @jax.jit
    def step(params, state):
        (loss, _), grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True)(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    losses = []
    for _ in range(8):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # actually learning


# -- against the flat form ---------------------------------------------------

def _flat_init(shape):
    nb = -(-math.prod(shape) // BLOCK)
    return (jnp.zeros((nb, BLOCK), jnp.int8),
            jnp.full((nb, 1), 1e-12, jnp.float32))


@jax.jit
def _flat_update(g, mq, nq, count, b1=0.9, b2=0.95, eps=1e-8):
    """``scale_by_adam8bit``'s update as it was while every leaf was
    flattened to ``[nb, 256]`` (PR 47's ``upd`` without its ``lax.map``
    over segments): the reference the new layout is held to."""
    shape, dt = g.shape, g.dtype
    cf = count.astype(jnp.float32)
    nb = mq[0].shape[0]
    pad = nb * BLOCK - math.prod(shape)
    g32 = jnp.pad(g.reshape(-1), (0, pad)).reshape(nb, BLOCK).astype(
        jnp.float32)
    m = mq[0].astype(jnp.float32) * mq[1]
    u = nq[0].astype(jnp.float32) * nq[1]
    n = b2 * (u * u) + (1 - b2) * (g32 * g32)
    m = b1 * m + (1 - b1) * g32
    mhat = m / (1 - b1 ** cf)
    nhat = n / (1 - b2 ** cf)
    out = mhat / (jnp.sqrt(nhat) + eps)
    out = jnp.clip(out, -10.0, 10.0).astype(dt)
    ms2 = jnp.maximum(
        jnp.max(jnp.abs(m), axis=1, keepdims=True) / 127.0, 1e-12)
    mq2 = jnp.clip(jnp.round(m / ms2), -127, 127).astype(jnp.int8)
    un = jnp.sqrt(n)
    ns2 = jnp.maximum(jnp.max(un, axis=1, keepdims=True) / 127.0, 1e-12)
    nq2 = jnp.clip(jnp.round(un / ns2), -127, 127).astype(jnp.int8)
    out = out.reshape(-1)[: math.prod(shape)].reshape(shape)
    return out, (mq2, ms2), (nq2, ns2)


def _grad(i, shape, dtype):
    """Gradients whose leading slices differ by decades, so that blocks
    differ in scale."""
    decades = jax.random.randint(
        jax.random.key(100 + i), shape[:1] + (1,) * (len(shape) - 1), -4, 1)
    g = jax.random.normal(jax.random.key(i), shape) * 10.0 ** decades
    return g.astype(dtype)


def _flat_scales(scale, q_shape, n):
    """A state's scales in the flat form's order, one a block."""
    rows, cols = q_shape
    if cols == adam8bit.HALF:       # a block is two rows, both hold it
        np.testing.assert_array_equal(scale[0, 0::2], scale[0, 1::2])
        scale = scale[:, 0::2]
    return np.asarray(scale).T.reshape(-1, 1)[: -(-n // BLOCK)]


WHOLE = [((3, 64, 512), jnp.bfloat16), ((2, 32, 4, 128), jnp.bfloat16),
         ((512,), jnp.float32), ((37, 5), jnp.float32),
         # through the kernel: whole units of 128 rows
         ((256, 512), jnp.bfloat16), ((2, 64, 2, 128), jnp.bfloat16),
         ((128, 2304), jnp.bfloat16)]


@pytest.mark.parametrize("shape,dtype", WHOLE)
def test_whole_blocks_equal_the_flat_form(shape, dtype):
    """Where the flat blocks are runs along the leaf's trailing axes the
    new layout moves nothing: updates, codes and scales are bit-equal to
    the flat form's over three steps."""
    n = math.prod(shape)
    opt = scale_by_adam8bit()
    state = opt.init({"w": jnp.zeros(shape, dtype)})
    update = jax.jit(opt.update)
    fm, fn = _flat_init(shape), _flat_init(shape)
    for i in range(3):
        g = _grad(i, shape, dtype)
        out, state = update({"w": g}, state)
        want, fm, fn = _flat_update(g, fm, fn, jnp.int32(i + 1))
        np.testing.assert_array_equal(
            np.asarray(out["w"].astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
        for (q, s), (fq, fs) in ((state.mu["w"], fm), (state.nu["w"], fn)):
            np.testing.assert_array_equal(
                np.asarray(q).reshape(-1)[:n],
                np.asarray(fq).reshape(-1)[:n])
            np.testing.assert_array_equal(
                _flat_scales(s, q.shape, n), np.asarray(fs))


@pytest.mark.parametrize("shape", [(8, 384), (128, 640)])
def test_short_blocks_never_straddle_rows(shape):
    """A last axis of 256 k + 128 ends every row in a block of 128: no
    block is longer than 256, none holds two rows' elements, and the
    moments are within half a quantisation step of the exact ones, as
    the flat form's are."""
    b1, b2 = 0.9, 0.95
    opt = scale_by_adam8bit(b1=b1, b2=b2)
    state = opt.init({"w": jnp.zeros(shape)})
    g = _grad(0, shape, jnp.float32)
    _, state = jax.jit(opt.update)({"w": g}, state)
    rows, cols = shape
    for (q, s), exact in ((state.mu["w"], (1 - b1) * g),
                          (state.nu["w"], jnp.sqrt((1 - b2) * g * g))):
        assert q.shape == shape and s.shape == (-(-cols // BLOCK), rows)
        spread = np.repeat(np.asarray(s).T, BLOCK, axis=1)[:, :cols]
        err = np.abs(np.asarray(q, np.float32) * spread - np.asarray(exact))
        assert (err <= 0.5 * spread * (1 + 1e-6)).all()
        # each block's scale is ITS OWN maximum: the short one too
        short = np.abs(np.asarray(exact))[:, cols - adam8bit.HALF:]
        np.testing.assert_allclose(np.asarray(s)[-1], np.maximum(
            short.max(axis=1) / 127.0, 1e-12), rtol=1e-6)


# -- the kernel against its reference ---------------------------------------

FUSED = adam8bit.Adam8(fused=True, clip=1.0, weight_decay=0.1)
MODES = {"plain": adam8bit.Adam8(), "fused": FUSED,
         "applied": FUSED._replace(apply=True),
         "no_clip": FUSED._replace(clip=0.0, weight_decay=0.0)}


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("rows,cols,dtype", [
    (256, 512, jnp.bfloat16),     # whole blocks, two units of rows
    (256, 128, jnp.bfloat16),     # a block is two rows
    (128, 640, jnp.bfloat16),     # a short block ends the row
    (128, 384, jnp.float32),
    (128, 2432, jnp.bfloat16),    # two tiles of columns, the second ragged
])
def test_kernel_equals_reference(mode, rows, cols, dtype):
    """Every layout and every chain through the Pallas interpreter,
    bit for bit against plain jax.numpy, over two steps.  (In float32
    to a unit in the last place and one code: XLA's CPU compiler
    contracts ``b1 * m + (1 - b1) * g`` into a fused multiply-add around
    one product or the other, program by program; the chip has none.
    bfloat16 gradients leave the products room, and nothing differs.)"""
    hp = MODES[mode]
    assert adam8bit.tile_shape(rows, cols) is not None
    kernel = jax.jit(adam8bit.adam8_update, static_argnames="hp")
    plain = jax.jit(adam8bit.adam8_update_reference, static_argnames="hp")
    p = (_grad(7, (rows, cols), dtype) * 0.1) if hp.fused else None
    zero = lambda: (jnp.zeros((rows, cols), jnp.int8), jnp.full(
        adam8bit.scale_shape(rows, cols), 1e-12, jnp.float32))
    got = want = (None,) + zero() + zero()
    for i in range(2):
        g = _grad(i, (rows, cols), dtype) * (30.0 if i else 0.01)
        scal = adam8bit.scalars(hp, jnp.int32(i + 1), dtype,
                                gnorm=optax.global_norm(g),
                                step_size=jnp.float32(-0.01))
        got = kernel(scal, g, p, *got[1:], hp=hp)
        want = plain(scal, g, p, *want[1:], hp=hp)
        for name, a, b in zip(("out", "mq", "ms", "nq", "ns"), got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
            if dtype == jnp.bfloat16:
                np.testing.assert_array_equal(a, b, err_msg=name)
            elif name in ("mq", "nq"):
                assert np.abs(a - b).max() <= 1 and (a != b).mean() < 1e-3
            else:
                np.testing.assert_allclose(
                    a, b, rtol=1e-6, atol=1e-6 * np.abs(b).max(),
                    err_msg=name)


# -- the fused chain ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adamw8bit_is_the_chain_of_its_parts(dtype):
    """clip -> 8-bit Adam -> weight decay -> step size as one pass gives
    what ``optax.chain`` of the four gives, and its ``apply`` is
    ``update`` then ``optax.apply_updates`` less one rounding.
    (In float32, where nothing rounds between the parts, to the last
    place, which XLA's CPU compiler decides by what it contracts.
    In bfloat16 that compiler keeps float32 between two parts where it
    fuses them, so a clipped gradient differs by a bfloat16 rounding, a
    code by one, and the direction of an element whose second moment's
    code is 0 or 1 by its whole clipped range: the parameters agree in
    the mean to a hundredth of a step.)"""
    lr, wd, warm = 1e-2, 0.1, 3
    shapes = {"a": (256, 512), "b": (4, 64, 2, 128), "c": (37, 5),
              "d": (24, 512)}
    params = {k: _grad(9, s, dtype) * 0.1 for k, s in shapes.items()}
    one = adamw8bit(lr, weight_decay=wd, warmup_steps=warm)
    chain = optax.chain(
        optax.clip_by_global_norm(1.0), scale_by_adam8bit(),
        optax.add_decayed_weights(wd),
        optax.scale_by_learning_rate(
            optax.linear_schedule(0.0, lr, warm)))
    s_one = one.init(params)
    s_chain = chain.init(params)
    p_one = p_chain = params

    @jax.jit
    def by_update(g, s, p):
        u, s = one.update(g, s, p)
        return optax.apply_updates(p, u), s

    for i in range(4):      # the norm clips in some steps and not in others
        g = {k: _grad(10 + i, s, dtype) * (0.002 if i % 2 else 1.0)
             for k, s in shapes.items()}
        p_app, s_app = jax.jit(      # from where ``update`` stands
            lambda g, s, p: apply_gradients(one, g, s, p))(g, s_one, p_one)
        p_one, s_one = by_update(g, s_one, p_one)
        u, s_chain = jax.jit(chain.update)(g, s_chain, p_chain)
        p_chain = optax.apply_updates(p_chain, u)
        for k in shapes:
            a, b, c = (np.asarray(x[k].astype(jnp.float32))
                       for x in (p_one, p_app, p_chain))
            if dtype == jnp.float32:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
                np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-7)
            else:
                # ``apply`` rounds once where ``update`` hands on a
                # rounded update that ``apply_updates`` rounds again
                assert (np.abs(a - b) <= (np.abs(b) + lr) * 2.0 ** -7).all()
                assert np.abs(a - c).mean() <= 0.005 * lr * (i + 1)
    if dtype == jnp.bfloat16:   # the moments are the same either way
        for a, b in zip(jax.tree.leaves(s_one), jax.tree.leaves(s_app)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
