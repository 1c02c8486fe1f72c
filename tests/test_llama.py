import jax
import jax.numpy as jnp
import numpy as np

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
