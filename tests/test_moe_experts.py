"""``ops/moe_experts``: the kernel against the loop over experts, and
that no pair is dropped whatever the routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe_experts as moe

T, D, F, E, K = 24, 32, 64, 8, 2


def _experts(seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return {"w_gate": jax.random.normal(k[0], (E, D, F)) * D ** -0.5,
            "w_up": jax.random.normal(k[1], (E, D, F)) * D ** -0.5,
            "w_down": jax.random.normal(k[2], (E, F, D)) * F ** -0.5}


def _routing(kind: str):
    rng = np.random.default_rng(0)
    if kind == "spread":
        choice = np.stack([rng.choice(E, K, replace=False) for _ in range(T)])
    elif kind == "one_expert":      # every token to experts 3 and 5
        choice = np.tile(np.asarray([3, 5]), (T, 1))
    else:                           # half the experts never chosen
        choice = np.stack([rng.choice(E // 2, K, replace=False)
                           for _ in range(T)])
    weight = rng.uniform(0.2, 1.0, (T, K)).astype(np.float32)
    return jnp.asarray(np.sort(choice, -1), jnp.int32), jnp.asarray(weight)


@pytest.mark.parametrize("padding", [5, 0])   # trailing padding tokens
@pytest.mark.parametrize("kind", ["spread", "one_expert", "half_idle"])
def test_the_kernel_equals_the_loop_over_experts(padding, kind):
    u = jax.random.normal(jax.random.key(7), (T, D))
    choice, weight = _routing(kind)
    live = T - padding
    valid = jnp.arange(T) < live
    experts = _experts()
    y, sizes = moe.routed_experts(u, choice, weight, experts, valid)
    want = moe.routed_experts_reference(u, choice, weight, experts, valid)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # every pair of a live token is in a group: nothing has a capacity
    assert int(jnp.sum(sizes)) == live * K
    np.testing.assert_array_equal(
        np.asarray(sizes),
        np.bincount(np.asarray(choice[:live]).reshape(-1), minlength=E))
    np.testing.assert_array_equal(np.asarray(y[live:]), 0.0)


@pytest.mark.parametrize("kind", ["spread", "one_expert"])
def test_no_live_token_is_no_work(kind):
    u = jax.random.normal(jax.random.key(7), (T, D))
    choice, weight = _routing(kind)
    y, sizes = moe.routed_experts(u, choice, weight, _experts(),
                                  jnp.zeros((T,), bool))
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    np.testing.assert_array_equal(np.asarray(sizes), 0)


def test_tile_plan_pads_each_group_to_whole_tiles():
    sizes = jnp.asarray([3, 0, 17, 0, 16, 1, 0, 0], jnp.int32)
    src, tile_expert, n_tiles = moe.tile_plan(sizes, 40, tile=16)
    assert int(n_tiles[0]) == 1 + 2 + 1 + 1
    np.testing.assert_array_equal(np.asarray(tile_expert[:5]),
                                  [0, 2, 2, 4, 5])
    src = np.asarray(src).reshape(-1, 16)
    np.testing.assert_array_equal(src[0, :4], [0, 1, 2, 40])
    np.testing.assert_array_equal(src[1], np.arange(3, 19))
    np.testing.assert_array_equal(src[2, :2], [19, 40])
    np.testing.assert_array_equal(src[3], np.arange(20, 36))
    np.testing.assert_array_equal(src[4, :2], [36, 40])
    # every sorted pair has exactly one padded row
    live = src[src < 40]
    np.testing.assert_array_equal(np.sort(live), np.arange(37))


# ------------------------------------------------------- a share of a layer

RANKS = 4       # the layer's 8 experts over four chips, two each


def _whole_layer_reference(u, choice, weight, experts, shared):
    """The uncut layer: every expert of the layer on every token it
    chose, plus the shared expert."""
    return (moe.routed_experts_reference(u, choice, weight, experts)
            + _shared(u, shared))


def _shared(u, shared):
    g, up, down = shared
    return (jax.nn.silu(u @ g) * (u @ up)) @ down


@pytest.mark.parametrize("padding", [5, 0])
@pytest.mark.parametrize("kind", ["spread", "one_expert", "half_idle"])
def test_the_shares_of_all_ranks_add_up_to_the_whole_layer(padding, kind):
    """The share test: each rank holds ``E / RANKS`` experts, routes over
    all ``E`` and computes its own experts' part; the parts of all ranks
    plus the shared expert counted ONCE equal the uncut reference's whole
    layer, and every pair is served by exactly one rank."""
    u = jax.random.normal(jax.random.key(7), (T, D))
    choice, weight = _routing(kind)
    live = T - padding
    valid = jnp.arange(T) < live
    experts = _experts()
    ks = jax.random.split(jax.random.key(11), 3)
    shared = (jax.random.normal(ks[0], (D, F)) * D ** -0.5,
              jax.random.normal(ks[1], (D, F)) * D ** -0.5,
              jax.random.normal(ks[2], (F, D)) * F ** -0.5)
    held = E // RANKS
    total, served = jnp.zeros((T, D)), 0
    for rank in range(RANKS):
        mine = {k: v[rank * held:(rank + 1) * held]
                for k, v in experts.items()}
        y, sizes = moe.routed_experts(u, choice, weight, mine, valid,
                                      first=rank * held)
        want = moe.routed_experts_reference(u, choice, weight, mine, valid,
                                            first=rank * held)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert sizes.shape == (held,)
        np.testing.assert_array_equal(
            np.asarray(sizes),
            np.bincount(np.asarray(choice[:live]).reshape(-1),
                        minlength=E)[rank * held:(rank + 1) * held])
        total, served = total + y, served + int(jnp.sum(sizes))
    assert served == live * K
    whole = _whole_layer_reference(u, choice, weight, experts, shared)
    got = total + jnp.where(valid[:, None], _shared(u, shared), 0.0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.where(valid[:, None], whole, 0.0)),
        rtol=5e-5, atol=5e-5)


def test_a_rank_whose_experts_nobody_chose_does_no_work():
    u = jax.random.normal(jax.random.key(7), (T, D))
    choice, weight = _routing("half_idle")      # experts 0 to 3 only
    experts = {k: v[6:] for k, v in _experts().items()}
    y, sizes = moe.routed_experts(u, choice, weight, experts,
                                  jnp.ones((T,), bool), first=6)
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    np.testing.assert_array_equal(np.asarray(sizes), 0)
