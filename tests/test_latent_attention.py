"""``ops/latent_attention``: the kernels under the Pallas interpreter
against their plain twins, and the cells a step walks."""

import functools

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
# the tables one slot on: a slot a new sequence took over
ROTATED = np.roll(TABLE, 1, axis=0)
# keys a pool cell spans: one page, two, four (the table's six columns
# are no multiple of it) and the module's own (the whole table one cell)
CELL_KEYS = [PAGE, 2 * PAGE, 4 * PAGE, la.CELL_KEYS]


def _pool(seed=0):
    return jax.random.normal(jax.random.key(seed),
                             (L, 1, SLOTS * MAXP + 1, PAGE, W), jnp.float32)


def _rows(rows, T):
    out = pack_ragged_batch(rows, T, SLOTS)
    return tuple(jnp.asarray(a) for a in out[4:])


# decode rows beside a chunk; a chunk alone from token 0; every row one
# token (the small shape); a row whose past ends inside a page; then, for
# cells of several pages: pasts that end inside a cell of four pages (in
# its second page, and one page into the table's last, short cell), on
# its edge, under one page; two chunk rows in a step; a slot under the
# table a new sequence brought
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
    "ends_inside": ([{"slot": 1, "start": 11, "tokens": list(range(5))},
                     {"slot": 3, "start": 41, "tokens": [1]},
                     {"slot": 0, "start": 39, "tokens": [2, 3]}], 8),
    "on_the_edge": ([{"slot": 0, "start": 32, "tokens": list(range(5))},
                     {"slot": 2, "start": 16, "tokens": [1]},
                     {"slot": 1, "start": 32, "tokens": [7]}], 8),
    "under_a_page": ([{"slot": 3, "start": 5, "tokens": [1, 2, 3, 4]},
                      {"slot": 0, "start": 3, "tokens": [9]}], 8),
    "two_chunks": ([{"slot": 1, "start": 24, "tokens": list(range(10))},
                    {"slot": 3, "start": 35, "tokens": list(range(12))},
                    {"slot": 0, "start": 47, "tokens": [1]}], 72),
    "reused_slot": ([{"slot": 2, "start": 13, "tokens": [1]},
                     {"slot": 0, "start": 9, "tokens": list(range(11))},
                     {"slot": 3, "start": 40, "tokens": [1]}], 72, ROTATED),
}


def _case(case):
    rows, T, *table = CASES[case]
    return rows, T, table[0] if table else TABLE


# every case at cells of two and of four pages; cells of one page and of
# the whole table where a step holds every kind of row
TWIN_CASES = [(case, keys) for case in sorted(CASES)
              for keys in CELL_KEYS[1:3]] + [
    (case, keys) for case in ("mixed", "two_chunks")
    for keys in (CELL_KEYS[0], CELL_KEYS[3])]


@functools.partial(jax.jit, static_argnames=("cell_keys",))
def _attend(q, new, pool, layer, slot, start, nlen, off, table, *,
            cell_keys):
    """``ragged_latent_attention``, a pool cell ``cell_keys`` keys while
    it is traced: traced as the engine traces its step, so that the steps
    of one shape share a compile (an eager call compiles the interpreted
    kernel anew, a second each)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(la, "CELL_KEYS", cell_keys)
        return la.ragged_latent_attention(
            q, new, pool, layer, slot, start, nlen, off, table, scale=0.3,
            rank=RANK)


@pytest.mark.parametrize("case,cell_keys", TWIN_CASES)
def test_attention_kernel_equals_twin(case, cell_keys):
    rows, T, table = _case(case)
    r = _rows(rows, T)
    q = jax.random.normal(jax.random.key(1), (T, H, W), jnp.float32)
    new = jax.random.normal(jax.random.key(2), (T, W), jnp.float32)
    pool = _pool()
    for layer in range(L):
        got = _attend(q, new, pool, layer, *r, table, cell_keys=cell_keys)
        want = la.ragged_latent_attention_reference(
            q, new, pool[layer, 0], *r, table, scale=0.3, rank=RANK)
        assert got.shape == (T, H, RANK) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_heads_in_groups_give_the_same(monkeypatch):
    """The chunk call with the heads in two groups (the cell's 32 go in
    two of 16): the list walks the rows once a group."""
    monkeypatch.setattr(la, "CHUNK_HEADS", 2)
    monkeypatch.setattr(la, "CELL_KEYS", 4 * PAGE)
    rows, T, _ = _case("mixed")
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
    rows, T, table = _case(case)
    r = _rows(rows, T)
    new = jax.random.normal(jax.random.key(3), (L, T, W), jnp.float32)
    pool = _pool()
    got = la.ragged_latent_append(pool, new, *r, table)
    want = la.ragged_latent_append_reference(pool, new, *r, table)
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


@pytest.mark.parametrize("cell_keys", CELL_KEYS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_cell_count_on_the_host_is_the_lists_length(case, cell_keys,
                                                    monkeypatch):
    """The list is as long as the grid steps the host counts (a row's
    ``ceil(pages / G)`` pool cells and its self cell, once a head group),
    and ``grid_cells`` is the PAGES those span: ``G`` a pool cell, one
    the self cell."""
    monkeypatch.setattr(la, "CELL_KEYS", cell_keys)
    monkeypatch.setattr(la, "CHUNK_HEADS", 2)
    rows, T, _ = _case(case)
    _slot, start, nlen, _off = _rows(rows, T)
    G = la.cell_pages(PAGE, MAXP)
    assert G == min(cell_keys // PAGE, MAXP)
    nc = -(-MAXP // G)
    steps = pages = 0
    for _cq, hg, which in la._calls(T, H, None):
        live_ci, n_live = la.live_latent_cells(
            start, nlen, la._takes(nlen, which), H // hg, MAXP, PAGE)
        pc = np.asarray(live_ci)[:int(n_live[0])] % (nc + 1)
        steps += len(pc)
        pages += int(np.sum(np.where(pc < nc, G, 1)))
    start, nlen = np.asarray(start), np.asarray(nlen)
    groups = np.where(nlen > 1, H // 2, 1)
    assert steps == int(np.sum(
        (nlen > 0) * groups * (-(-(-(-start // PAGE)) // G) + 1))) > 0
    assert pages == la.latent_cell_count(start, nlen, PAGE, H, MAXP)
    # the masked walk takes the rows of several tokens, its own groups
    monkeypatch.setattr(la, "SPARSE_CHUNK_HEADS", 1)
    assert la.sparse_cell_count(start, nlen, PAGE, H, MAXP) == int(np.sum(
        (nlen > 1) * H * (-(-start // (G * PAGE)) * G + 1)))
    if G == 1:      # cells of one page: the pooled pages and a self cell
        assert pages == int(np.sum(
            (nlen > 0) * groups * (-(-start // PAGE) + 1)))


def test_rows_of_one_token_go_through_a_window_of_one():
    assert la._calls(32, 32, None) == [(1, 32, "one"), (32, 16, "more")]
    assert la._calls(288, 32, None) == [
        (1, 32, "one"), (288, la.CHUNK_HEADS, "more")]
