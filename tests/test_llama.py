import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LLAMA_TINY, LlamaConfig


def test_param_count_matches_formula():
    cfg = LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_logical_axes_mirror_params():
    cfg = LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    axes = llama.logical_axes(cfg)
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
    )
    assert len(flat_p) == len(flat_a)
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a), (p.shape, a)


def test_forward_shapes_and_finite():
    cfg = LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    t1 = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab_size)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % cfg.vocab_size)
    l1 = llama.forward(params, t1, cfg)
    l2 = llama.forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                               atol=1e-5)


def test_loss_and_grads():
    cfg = LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    (loss, aux), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(
        params, {"tokens": tokens}, cfg
    )
    assert bool(jnp.isfinite(loss))
    # a uniform-random model should sit near ln(vocab)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5
    gnorm = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


# (heads, positions, dtype, taken by the product)
ROPE_CASES = {
    "q_heads": (4, 32, jnp.bfloat16, True),
    "one_kv_head": (1, 24, jnp.bfloat16, True),
    "float32_rounds_on_an_mxu": (2, 16, jnp.float32, False),
    "a_decode_row": (4, 1, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", ROPE_CASES)
def test_rope_in_one_pass_is_apply_rope(case):
    """The rotation as a product's epilogue is apply_rope's arithmetic,
    forward and backward (the same products and one sum each; a compiler
    that contracts one product of a sum into a fused multiply-add may
    choose the other one, which shows in the last bit of a few elements
    in ten thousand); dtype and length decide which runs."""
    H, S, dtype, by_product = ROPE_CASES[case]
    from ray_tpu.util import metrics

    def traced():              # the registry's count of each form's traces
        return {dict(tags)["form"]: n
                for _, tags, n, _ in llama._rope_traces()._samples()}

    before = traced()
    kx, kg = jax.random.split(jax.random.key(3))
    x = jax.random.normal(kx, (2, S, H, 128), dtype)
    g = jax.random.normal(kg, x.shape, dtype)
    pos = jnp.broadcast_to(jnp.arange(S) + 1000, (2, S))
    sin, cos = llama.rope_table(LlamaConfig(dim=128 * H, n_heads=H), pos)

    def both(fn):
        y, vjp = jax.vjp(lambda x: fn(x, sin, cos), x)
        return y, vjp(g)[0]

    want = jax.jit(lambda: both(llama.apply_rope))()
    assert traced() == before
    got = jax.jit(lambda: both(llama.rope_in_one_pass))()
    form = "product" if by_product else "concat"
    assert traced() == {**before, form: before.get(form, 0) + 1}
    assert metrics.registry().get("raytpu_rope_traces_total") is not None
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.mean(a != b) <= (1e-3 if by_product else 0)
        np.testing.assert_allclose(a, b, rtol=2 ** -7, atol=2 ** -9)


def test_ragged_step_matches_forward():
    """The serving step over a paged cache against the cache-free
    oracle (tests/oracle.py): the logits after each prompt's last token,
    and after one more token read back through the pages, equal
    ``llama.forward``'s over the whole sequence."""
    from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
    from tests import oracle

    cfg = LLAMA_TINY
    params = llama.init_params(jax.random.key(0), cfg)
    B, S, page = 2, 8, 8
    seqs = np.asarray(jax.random.randint(
        jax.random.key(2), (B, S), 0, cfg.vocab_size)).tolist()
    cache = llama.init_paged_cache(cfg, num_pages=2 * B, page_size=page)
    table = np.arange(2 * B, dtype=np.int32).reshape(B, 2)

    def step(rows, cache):
        (toks, _mask, _slot, pos, r_slot, r_start, r_len, r_off) = \
            pack_ragged_batch(rows, B * S, B)
        return llama.ragged_step_paged(params, toks, pos, r_slot, r_start,
                                       r_len, r_off, table, cfg, cache)

    def check(logits):
        for b in range(B):
            np.testing.assert_allclose(
                np.asarray(logits[b]),
                oracle.next_token_logits(params, cfg, seqs[b]),
                rtol=2e-2, atol=2e-2)

    logits, cache = step([{"slot": b, "start": 0, "tokens": seqs[b]}
                          for b in range(B)], cache)
    check(logits)
    # one decode row each == forward over the extended sequence
    for b in range(B):
        seqs[b].append(int(np.argmax(np.asarray(logits[b]))))
    logits, cache = step([{"slot": b, "start": S, "tokens": seqs[b][S:]}
                          for b in range(B)], cache)
    check(logits)


def test_sharded_forward_on_mesh(cpu_devices):
    import dataclasses

    from ray_tpu.parallel import MeshSpec, create_mesh, shard_tree, sharding_for

    # float32 so sharded-vs-unsharded is exact (bf16 accumulates in a
    # different order per sharding, which is noise, not a bug)
    cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32)
    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    params = llama.init_params(jax.random.key(0), cfg)
    sharded = shard_tree(mesh, params, llama.logical_axes(cfg))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    tokens = jax.device_put(tokens, sharding_for(mesh, ("batch", None)))

    logits = jax.jit(lambda p, t: llama.forward(p, t, cfg))(sharded, tokens)
    ref = llama.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)
