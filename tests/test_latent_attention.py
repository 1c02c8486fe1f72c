"""``ops/latent_attention``: the kernels under the Pallas interpreter
against their plain twins, and the cells a step walks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch

RANK, ROPE, H, PAGE, SLOTS, MAXP, L = 16, 8, 4, 8, 4, 6, 2
W = RANK + ROPE
TABLE = np.random.default_rng(1).permutation(SLOTS * MAXP).astype(
    np.int32).reshape(SLOTS, MAXP)


def _pool(seed=0):
    return jax.random.normal(jax.random.key(seed),
                             (L, 1, SLOTS * MAXP + 1, PAGE, W), jnp.float32)


def _rows(rows, T):
    out = pack_ragged_batch(rows, T, SLOTS)
    return tuple(jnp.asarray(a) for a in out[4:])


# decode rows beside a chunk; a chunk alone from token 0; every row one
# token (the small shape); a row whose past ends inside a page
CASES = {
    "mixed": ([{"slot": 2, "start": 13, "tokens": [1]},
               {"slot": 0, "start": 9, "tokens": list(range(11))},
               {"slot": 3, "start": 40, "tokens": [1]}], 72),
    "from_zero": ([{"slot": 1, "start": 0, "tokens": list(range(20))}], 72),
    "decode_only": ([{"slot": 2, "start": 13, "tokens": [1]},
                     {"slot": 1, "start": 1, "tokens": [1]},
                     {"slot": 0, "start": 24, "tokens": [1]}], 8),
    "short_tail": ([{"slot": 3, "start": 17, "tokens": [1, 2, 3]},
                    {"slot": 1, "start": 8, "tokens": [4]}], 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_attention_kernel_equals_twin(case):
    rows, T = CASES[case]
    r = _rows(rows, T)
    q = jax.random.normal(jax.random.key(1), (T, H, W), jnp.float32)
    new = jax.random.normal(jax.random.key(2), (T, W), jnp.float32)
    pool = _pool()
    for layer in range(L):
        got = la.ragged_latent_attention(
            q, new, pool, layer, *r, TABLE, scale=0.3, rank=RANK)
        want = la.ragged_latent_attention_reference(
            q, new, pool[layer, 0], *r, TABLE, scale=0.3, rank=RANK)
        assert got.shape == (T, H, RANK) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_heads_in_groups_give_the_same(monkeypatch):
    """The chunk call with the heads in two groups (the cell's 32 go in
    two of 16): the list walks the rows once a group."""
    monkeypatch.setattr(la, "CHUNK_HEADS", 2)
    rows, T = CASES["mixed"]
    r = _rows(rows, T)
    assert [(c[1], c[2]) for c in la._calls(T, H, None)] == [
        (H, "one"), (2, "more")]
    q = jax.random.normal(jax.random.key(1), (T, H, W), jnp.float32)
    new = jax.random.normal(jax.random.key(2), (T, W), jnp.float32)
    pool = _pool()
    got = la.ragged_latent_attention(q, new, pool, 1, *r, TABLE, scale=0.3,
                                     rank=RANK)
    want = la.ragged_latent_attention_reference(
        q, new, pool[1, 0], *r, TABLE, scale=0.3, rank=RANK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_append_kernel_equals_twin(case):
    rows, T = CASES[case]
    r = _rows(rows, T)
    new = jax.random.normal(jax.random.key(3), (L, T, W), jnp.float32)
    pool = _pool()
    got = la.ragged_latent_append(pool, new, *r, TABLE)
    want = la.ragged_latent_append_reference(pool, new, *r, TABLE)
    scratch = SLOTS * MAXP      # the last page takes what belongs nowhere
    np.testing.assert_array_equal(np.asarray(got[:, :, :scratch]),
                                  np.asarray(want[:, :, :scratch]))
    assert not np.array_equal(np.asarray(got), np.asarray(pool))


def test_no_row_leaves_output_zero_and_pool_untouched():
    """A step of padding rows only: the list of cells is empty, the grid
    has no step that does anything."""
    T = 8
    r = _rows([], T)
    q = jax.random.normal(jax.random.key(1), (T, H, W), jnp.float32)
    new = jax.random.normal(jax.random.key(2), (T, W), jnp.float32)
    pool = _pool()
    out = la.ragged_latent_attention(q, new, pool, 0, *r, TABLE,
                                     scale=0.3, rank=RANK)
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    got = la.ragged_latent_append(pool, jnp.stack([new] * L), *r, TABLE)
    np.testing.assert_array_equal(np.asarray(got[:, :, :SLOTS * MAXP]),
                                  np.asarray(pool[:, :, :SLOTS * MAXP]))


@pytest.mark.parametrize("case", sorted(CASES))
def test_cell_count_on_the_host_is_the_lists_length(case):
    rows, T = CASES[case]
    _slot, start, nlen, _off = _rows(rows, T)
    n = 0
    for _cq, hg, which in la._calls(T, H, None):
        _ci, n_live = la.live_latent_cells(
            start, nlen, la._takes(nlen, which), H // hg, MAXP, PAGE)
        n += int(n_live[0])
    assert n == la.latent_cell_count(np.asarray(start), np.asarray(nlen),
                                     PAGE, H)
    assert n > 0


def test_rows_of_one_token_go_through_a_window_of_one():
    assert la._calls(32, 32, None) == [(1, 32, "one"), (32, 16, "more")]
    assert la._calls(288, 32, None) == [
        (1, 32, "one"), (288, la.CHUNK_HEADS, "more")]
