"""Flash attention kernel vs the einsum reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.ops.flash_attention import flash_attention


def _rand_qkv(key, B=1, S=256, H=4, KVH=2, D=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, KVH, D), dtype)
    v = jax.random.normal(kv, (B, S, KVH, D), dtype)
    return q, k, v


def _ref(q, k, v, causal=True):
    return dot_product_attention(q, k, v, causal=causal)


@pytest.mark.parametrize("kvh", [4, 2])  # MHA and GQA
def test_forward_matches_reference(kvh):
    q, k, v = _rand_qkv(jax.random.key(0), KVH=kvh)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_forward_noncausal():
    q, k, v = _rand_qkv(jax.random.key(1), S=256)
    out = flash_attention(q, k, v, causal=False)
    ref = _ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


# (heads, kv heads, S, block_q, block_kv, causal, bytes of dq a call holds)
GRAD_CASES = {
    "gqa_one_block": (4, 2, 256, 512, 512, True, None),   # nq = nk = 1
    "mha": (2, 2, 256, 128, 128, True, None),
    "gqa": (4, 2, 256, 128, 128, True, None),
    "gqa_wide_q": (4, 2, 256, 128, 64, True, None),
    "gqa_wide_kv": (4, 2, 256, 64, 128, True, None),
    "gqa_noncausal": (4, 2, 256, 128, 128, False, None),
    # equal blocks of two lane tiles and more: a crossed pair in halves
    "gqa_halved_diagonal": (4, 2, 512, 256, 256, True, None),
    # dq of all 512 rows is not held: two spans of two q blocks a call
    "gqa_spans": (4, 2, 512, 128, 128, True, 2 * 128 * 2 * 64 * 12),
    "mha_spans_noncausal": (2, 2, 256, 64, 128, False, 3 * 64 * 64 * 12),
}


@pytest.mark.parametrize("case", GRAD_CASES)
def test_gradients_match_reference(case, monkeypatch):
    from ray_tpu.ops import flash_attention as fa

    H, KVH, S, bq, bk, causal, held = GRAD_CASES[case]
    if held is not None:
        monkeypatch.setattr(fa, "DQ_RESIDENT_BYTES", held)
    q, k, v = _rand_qkv(jax.random.key(2), S=S, H=H, KVH=KVH)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=bq,
                                       block_kv=bk) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, causal=causal) ** 2)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_backward_calls_by_what_vmem_holds(monkeypatch):
    """One call where dq of the whole sequence is held, a call a span of
    q blocks where it is not: the shape decides, nothing else."""
    from ray_tpu.ops import flash_attention as fa

    q, k, v = _rand_qkv(jax.random.key(5), S=512)

    def calls():                    # a fresh function: nothing cached
        grad = jax.grad(lambda q: flash_attention(
            q, k, v, block_q=128, block_kv=128).sum())
        return str(jax.make_jaxpr(grad)(q)).count("flash_bwd_dkv")

    assert calls() == 1
    monkeypatch.setattr(fa, "DQ_RESIDENT_BYTES", 128 * 2 * 64 * 12)
    assert calls() == 4


@pytest.mark.parametrize("shape,counts", [
    ((4096, 512, 512, True), (36, 8, 64)),     # the training cell
    ((4096, 512, 512, False), (64, 0, 64)),
    ((4096, 1024, 1024, True), (10, 4, 16)),   # ... in default_blocks(4096)
    ((512, 256, 128, True), (6, 4, 8)),
    ((512, 128, 256, True), (6, 4, 8)),
    ((256, 256, 256, True), (1, 1, 1)),
])
def test_schedule_counts(shape, counts):
    """(walked, masked, rectangle): every pair that holds a visible
    position is walked once and no other; only a pair that also holds
    a hidden one is masked."""
    from ray_tpu.ops.flash_attention import block_pairs, pair_counts

    assert pair_counts(*shape) == counts
    S, bq, bk, causal = shape
    visible = np.tril(np.ones((S, S), bool)) if causal else np.ones(
        (S, S), bool)
    tiles = visible.reshape(S // bq, bq, S // bk, bk)
    pairs = block_pairs(*shape)
    assert [(qi, ki) for qi, ki, _ in pairs] == [
        (qi, ki) for qi in range(S // bq) for ki in range(S // bk)
        if tiles[qi, :, ki].any()]
    assert all(m == (not tiles[qi, :, ki].all()) for qi, ki, m in pairs)


def test_default_blocks_and_crossed_parts():
    """Wide blocks where they divide the sequence, else what
    _flash_eligible asks to divide it; a crossed pair between equal
    blocks forms three quarters, each part whole lane tiles."""
    from ray_tpu.ops.flash_attention import _crossed_parts, default_blocks

    assert default_blocks(4096) == (1024, 1024)
    assert default_blocks(1536) == (512, 512)
    assert default_blocks(256) == (256, 256)
    assert _crossed_parts(1024, 1024) == ((0, 1024, 0, 512),
                                          (512, 512, 512, 512))
    assert _crossed_parts(128, 128) == ((0, 128, 0, 128),)     # half a tile
    assert _crossed_parts(512, 256) == ((0, 512, 0, 256),)


def test_rejects_bad_shapes():
    q, k, v = _rand_qkv(jax.random.key(3), S=200)  # not block-divisible
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, block_q=128, block_kv=128)


def test_unequal_blocks_causal():
    """block_q != block_kv must still produce correct causal output."""
    q, k, v = _rand_qkv(jax.random.key(4), S=512)
    for bq, bk in [(256, 128), (128, 256), (512, 128)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bk)
        ref = _ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"bq={bq} bk={bk}",
        )


def test_eligibility_matches_kernel(monkeypatch):
    from ray_tpu.ops import attention, platform

    # pretend we're on TPU so the shape logic is actually exercised
    monkeypatch.setattr(platform, "interpret_mode", lambda: False)

    mk = lambda s, kl=None: (
        jax.ShapeDtypeStruct((1, s, 4, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, kl or s, 2, 64), jnp.bfloat16),
    )
    q, k = mk(1024)
    assert attention._flash_eligible(q, k, True, None, None)
    # S=640 not divisible by the clamped 512 block: must NOT be eligible
    q, k = mk(640)
    assert not attention._flash_eligible(q, k, True, None, None)
    # decode-offset (k longer than q) must fall back to einsum
    q, k = mk(256, kl=512)
    assert not attention._flash_eligible(q, k, True, None, None)
    # packed sequences fall back
    q, k = mk(1024)
    assert not attention._flash_eligible(q, k, True, "segs", None)


def test_flash_partition_specs():
    """Batch over the data axes, heads over the tensor axes, by the
    rule table; a dimension its axes do not divide stays whole."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import flash_partition_specs

    train = {"pp": 1, "dp": 2, "fsdp": 2, "ep": 1, "sp": 1, "tp": 2}
    q, kv = flash_partition_specs(train, 8, 8, 4)
    assert q == kv == P(("dp", "fsdp"), None, "tp", None)
    assert flash_partition_specs(train, 2, 8, 4)[0] == P(
        None, None, "tp", None)           # 2 rows over 4 data shards
    assert flash_partition_specs(train, 8, 8, 1)[0] == P(
        ("dp", "fsdp"), None, None, None)  # one KV head over tp=2
    serving = {"dcn_tp": 2, "dp": 1, "fsdp": 1, "tp": 2}
    assert flash_partition_specs(serving, 3, 8, 4)[0][2] == ("dcn_tp", "tp")


def test_flash_runs_per_shard_under_a_mesh(cpu_devices):
    """Mosaic kernels cannot be partitioned by GSPMD: under a mesh the
    kernel is entered through shard_map (the AOT test proves that for a
    TPU; this proves the sharded result is the same attention)."""
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshSpec, create_mesh

    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices=cpu_devices)
    q, k, v = _rand_qkv(jax.random.key(7), B=4, S=256, H=4, KVH=2)
    with mesh:
        out = jax.jit(attention._flash_over_mesh)(q, k, v)
        g = jax.jit(jax.grad(
            lambda q: attention._flash_over_mesh(q, k, v).sum()))(q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g_ref = jax.grad(lambda q: _ref(q, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-4, rtol=2e-4)
