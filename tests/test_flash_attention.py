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


def test_gradients_match_reference():
    q, k, v = _rand_qkv(jax.random.key(2), S=256, H=4, KVH=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch",
        )


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
