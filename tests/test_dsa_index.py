"""``ops/dsa_index`` and the sparse side of ``ops/latent_attention``: index
scores, causality, the top-k selection and the attention over what was
selected, against plain ``jnp``; ragged rows, rotated block tables, and
padding rows that leave both pools bit-equal."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.runners.serve_glm5 import TRACED, planted
from ray_tpu.ops import dsa_index as dsa
from ray_tpu.ops import latent_attention as la

T, R, J, D, PAGE, MAXP, SLOTS = 24, 4, 4, 16, 8, 6, 4
H, RANK, ROPE, W = 4, 16, 8, 32       # the latent side: W lanes, 24 used
C = MAXP * PAGE
P = SLOTS * MAXP


def _batch(seed, rows, roll=0):
    """rows: [(slot, start, len)] -> packed arrays and random operands;
    ``roll`` hands every slot the next one's pages (a slot that a new
    sequence took over)."""
    rng = np.random.default_rng(seed)
    row = np.zeros((4, R), np.int32)
    off = 0
    for i, (slot, start, n) in enumerate(rows):
        row[:, i] = slot, start, n, off
        off += n
    table = np.roll(rng.permutation(P).astype(np.int32).reshape(
        SLOTS, MAXP), roll, axis=0)
    k = jax.random.split(jax.random.key(seed), 8)
    ops = {
        "qI": jax.random.normal(k[0], (T, J, D)),
        "wI": jax.random.normal(k[1], (T, J)),
        "newI": jax.random.normal(k[2], (T, D)),
        "pool_i": jax.random.normal(k[3], (2, 1, P + 1, PAGE, D)),
        "q": jax.random.normal(k[4], (T, H, W)).at[..., RANK + ROPE:].set(0),
        "new": jax.random.normal(k[5], (T, W)),
        "pool": jax.random.normal(k[6], (2, 1, P + 1, PAGE, W)),
    }
    return tuple(jnp.asarray(r) for r in row), jnp.asarray(table), ops


def _position_space(rows, sel, more):
    """[T, C + T] bool: what each token of each live row selected, by
    position of its sequence (``index_select_reference``'s layout)."""
    out = np.zeros((T, C + T), bool)
    off = 0
    for i, (_slot, start, n) in enumerate(rows):
        if n and more[i]:
            out[off:off + n, :start] = np.asarray(sel.pool)[off:off + n,
                                                            :start]
            out[off:off + n, start:start + n] = np.asarray(
                sel.self)[off:off + n, off:off + n]
        elif n:
            out[off, :start + 1] = np.asarray(
                dsa.one_mask(sel, C))[i, :start + 1]
        off += n
    return out


# the last three for the masked walk's cells of several pages: pasts that
# end inside a cell of four pages (in its second page, and one page into
# the table's last, short cell), on its edge, and under one page
ROWS = {
    "chunk_and_decodes": [(2, 19, 9), (0, 33, 1), (3, 7, 1), (1, 0, 5)],
    "decodes_only": [(0, 40, 1), (1, 3, 1), (2, 17, 1), (3, 29, 1)],
    "two_chunks": [(1, 24, 10), (3, 16, 12)],
    "first_chunk": [(0, 0, 20)],
    "ends_inside": [(1, 11, 6), (3, 41, 7), (0, 39, 1)],
    "on_the_edge": [(0, 32, 8), (2, 16, 5), (1, 32, 1)],
    "under_a_page": [(3, 5, 4), (0, 3, 9), (2, 7, 1)],
}
# keys a pool cell of the walk spans: one page, two, four (the table's
# six columns are no multiple of it), the module's own (the whole table)
CELL_KEYS = [PAGE, 2 * PAGE, 4 * PAGE, la.CELL_KEYS]


# The steps below are traced as the engine traces its step, so that the
# cases of one shape share a compile (an eager call compiles the
# interpreted kernel anew, a second or more each).

@functools.partial(jax.jit, static_argnames=("topk",))
def _select(ops, layer, rs, r0, rl, ro, table, *, topk):
    scores = dsa.index_scores(ops["qI"], ops["wI"], ops["newI"],
                              ops["pool_i"], layer, rs, r0, rl, ro, table)
    return scores, dsa.select(scores, topk)


@functools.partial(jax.jit, static_argnames=("cell_keys", "dense"))
def _attend(ops, layer, rs, r0, rl, ro, table, sel=None, *, cell_keys,
            dense=False):
    """The sparse attention under ``sel`` (the dense walk where ``dense``
    says so), a pool cell ``cell_keys`` keys while it is traced."""
    args = (ops["q"], ops["new"], ops["pool"], layer, rs, r0, rl, ro, table)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(la, "CELL_KEYS", cell_keys)
        if dense:
            return la.ragged_latent_attention(*args, scale=0.3, rank=RANK)
        return la.ragged_sparse_latent_attention(*args, sel, scale=0.3,
                                                 rank=RANK)


@pytest.mark.parametrize("topk", [6, 64])
@pytest.mark.parametrize("kind", sorted(ROWS))
def test_scores_and_selection_equal_the_dense_twin(kind, topk):
    rows = ROWS[kind]
    (rs, r0, rl, ro), table, ops = _batch(1, rows)
    scores, sel = _select(ops, 1, rs, r0, rl, ro, table, topk=topk)
    want_s, want_m = dsa.index_select_reference(
        ops["qI"], ops["wI"], ops["newI"], ops["pool_i"][1, 0], rs, r0, rl,
        ro, table, topk)
    more = np.asarray(scores.more)
    np.testing.assert_array_equal(_position_space(rows, sel, more),
                                  np.asarray(want_m))
    # the scores themselves, where a query may look
    off = 0
    for i, (_slot, start, n) in enumerate(rows):
        if n > 1:
            np.testing.assert_allclose(
                np.asarray(scores.pool)[off:off + n, :start],
                np.asarray(want_s)[off:off + n, :start], rtol=1e-5,
                atol=1e-5)
        elif n == 1:
            np.testing.assert_allclose(
                np.asarray(scores.one)[i, :start + 1],
                np.asarray(want_s)[off, :start + 1], rtol=1e-5, atol=1e-5)
        off += n


def test_no_query_selects_a_later_position_or_another_rows():
    rows = ROWS["chunk_and_decodes"]
    (rs, r0, rl, ro), table, ops = _batch(2, rows)
    scores = dsa.index_scores(ops["qI"], ops["wI"], ops["newI"],
                              ops["pool_i"], 0, rs, r0, rl, ro, table)
    sel = dsa.select(scores, 1000)
    pool, own = np.asarray(sel.pool), np.asarray(sel.self)
    # the chunk (tokens 0..8 of the buffer, start 19): its pool part ends
    # at 19, its own part is lower-triangular, nothing past its tokens
    assert pool[:9, :19].all() and not pool[:9, 19:].any()
    np.testing.assert_array_equal(own[:9, :9], np.tri(9, dtype=bool))
    assert not own[:9, 9:].any() and not own[9:11].any()
    # row 3 (5 tokens from position 0) sees its own tokens and no pool
    assert not pool[11:16].any()
    np.testing.assert_array_equal(own[11:16, 11:16], np.tri(5, dtype=bool))
    # decode rows: positions up to and with their own
    one = np.asarray(dsa.one_mask(sel, C))
    assert one[1, :34].all() and not one[1, 34:].any()
    assert one[2, :8].all() and not one[2, 8:].any()
    assert not one[0].any() and not one[3].any()
    # padding tokens select nothing
    assert not pool[16:].any() and not own[16:].any()


@pytest.mark.parametrize("k", [1, 5, 40])
def test_bisection_equals_a_sort(k):
    rng = np.random.default_rng(k)
    a = jnp.asarray(rng.normal(size=(7, 33)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(7, 9)), jnp.float32)
    ok_a = jnp.asarray(rng.random((7, 33)) < 0.7)
    ok_b = jnp.asarray(rng.random((7, 9)) < 0.5).at[3].set(False)
    ok_a = ok_a.at[3].set(False)            # a query with no candidate
    got = dsa.topk_masks([(a, ok_a), (b, ok_b)], k)
    want = dsa.topk_masks_reference([(a, ok_a), (b, ok_b)], k)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    n = np.asarray(got[0]).sum(1) + np.asarray(got[1]).sum(1)
    np.testing.assert_array_equal(
        n, np.minimum(np.asarray(ok_a).sum(1) + np.asarray(ok_b).sum(1), k))


def test_ties_at_the_threshold_and_the_list():
    """Bit-equal scores at the k-th: a mask keeps them all; a list holds
    exactly k, the larger scores first."""
    x = jnp.asarray([[3.0, 1.0, 1.0, 2.0, 1.0, 0.0]])
    ok = jnp.ones((1, 6), bool)
    (every,) = dsa.topk_masks([(x, ok)], 3)
    np.testing.assert_array_equal(np.asarray(every)[0],
                                  [1, 1, 1, 1, 1, 0])
    idx, good = dsa.top_list(x, ok, 3)
    assert sorted(np.asarray(idx)[0, :2].tolist()) == [0, 3]
    assert int(np.asarray(idx)[0, 2]) in (1, 2, 4)
    assert np.asarray(good).all()
    # fewer candidates than k: the list ends early
    idx, good = dsa.top_list(x, jnp.asarray([[1, 0, 0, 1, 0, 0]], bool), 4)
    assert sorted(np.asarray(idx)[0, :2].tolist()) == [0, 3]
    np.testing.assert_array_equal(np.asarray(good)[0], [1, 1, 0, 0])
    np.testing.assert_array_equal(np.asarray(idx)[0, 2:], [0, 0])


def test_sel_token_count():
    assert dsa.sel_token_count([0, 10, 5000], [4, 1, 2], 2048) == (
        1 + 2 + 3 + 4 + 11 + 2048 + 2048)
    assert dsa.sel_token_count([2040], [16], 2048) == sum(
        min(p + 1, 2048) for p in range(2040, 2056))
    assert dsa.sel_token_count([0, 0], [0, 0], 2048) == 0


def _sparse_reference(ops, rows, table, mask, layer, scale):
    """Each token's softmax over the positions ``mask`` [T, C + T] names
    (position space), plain jnp: float32 [T, H, RANK]."""
    out = np.zeros((T, H, RANK), np.float32)
    pool = np.asarray(ops["pool"])[layer, 0]
    off = 0
    for (slot, start, n) in rows:
        past = pool[np.asarray(table)[slot]].reshape(C, W)[:start]
        keys = np.concatenate([past, np.asarray(ops["new"])[off:off + n]])
        for t in range(n):
            m = mask[off + t, :start + n]
            s = np.einsum("hw,kw->hk", np.asarray(ops["q"])[off + t],
                          keys) * scale
            s = np.where(m[None], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[off + t] = p @ keys[:, :RANK]
        off += n
    return out


@pytest.mark.parametrize("topk,cell_keys", [
    (6, 4 * PAGE), (64, 4 * PAGE), (6, 2 * PAGE)])
@pytest.mark.parametrize("kind", sorted(ROWS))
def test_sparse_attention_equals_plain_jnp(kind, topk, cell_keys):
    """The gathered list (rows of one token) and the masked walk (rows
    of more, cells of two and of four pages) over the positions the
    selection names, under a permuted block table."""
    rows = ROWS[kind]
    (rs, r0, rl, ro), table, ops = _batch(3, rows)
    scores, sel = _select(ops, 1, rs, r0, rl, ro, table, topk=topk)
    got = _attend(ops, 1, rs, r0, rl, ro, table, sel, cell_keys=cell_keys)
    mask = _position_space(rows, sel, np.asarray(scores.more))
    want = _sparse_reference(ops, rows, table, mask, 1, 0.3)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    if topk >= C + T:       # nothing cut: the dense kernel's function
        dense = _attend(ops, 1, rs, r0, rl, ro, table, cell_keys=cell_keys,
                        dense=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("cell_keys,roll", [
    (keys, 0) for keys in CELL_KEYS] + [(4 * PAGE, 1), (la.CELL_KEYS, 1)])
def test_everything_selected_is_the_dense_kernel(cell_keys, roll):
    """Whatever the cells span, and under the table a slot's next
    sequence brings (``roll``)."""
    rows = ROWS["chunk_and_decodes"]
    (rs, r0, rl, ro), table, ops = _batch(4, rows, roll)
    _scores, sel = _select(ops, 0, rs, r0, rl, ro, table, topk=C + T)
    got = _attend(ops, 0, rs, r0, rl, ro, table, sel, cell_keys=cell_keys)
    dense = _attend(ops, 0, rs, r0, rl, ro, table, cell_keys=cell_keys,
                    dense=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", ["chunk_and_decodes", "decodes_only"])
def test_the_runners_recent_fault_selects_the_newest(kind):
    """``serve_glm5``'s ``recent`` plant takes ``select``'s place and
    keeps every query to the newest ``topk`` positions of its row."""
    rows = ROWS[kind]
    (rs, r0, rl, ro), table, ops = _batch(5, rows)
    with planted("recent"):
        scores = dsa.index_scores(ops["qI"], ops["wI"], ops["newI"],
                                  ops["pool_i"], 0, rs, r0, rl, ro, table)
        sel = dsa.select(scores, 6)
    assert TRACED["recent"] > 0
    mask = _position_space(rows, sel, np.asarray(scores.more))
    off = 0
    for _slot, start, n in rows:
        for t in range(n):
            p = start + t
            want = np.zeros((C + T,), bool)
            want[max(0, p - 5):p + 1] = True
            np.testing.assert_array_equal(mask[off + t], want)
        off += n


def test_the_runners_dense_fault_attends_to_everything():
    """``serve_glm5``'s ``dense`` plant takes the sparse attention's
    place: whatever the selection, the result is the dense walk's."""
    (rs, r0, rl, ro), table, ops = _batch(5, ROWS["chunk_and_decodes"])
    scores = dsa.index_scores(ops["qI"], ops["wI"], ops["newI"],
                              ops["pool_i"], 0, rs, r0, rl, ro, table)
    sel = dsa.select(scores, 3)
    args = (ops["q"], ops["new"], ops["pool"], 0, rs, r0, rl, ro, table)
    sparse = la.ragged_sparse_latent_attention(*args, sel, scale=0.3,
                                               rank=RANK)
    with planted("dense"):
        got = la.ragged_sparse_latent_attention(*args, sel, scale=0.3,
                                                rank=RANK)
    dense = la.ragged_latent_attention(*args, scale=0.3, rank=RANK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)
    assert np.abs(np.asarray(sparse) - np.asarray(dense)).max() > 1e-2
    # and the plant is gone with its block
    again = la.ragged_sparse_latent_attention(*args, sel, scale=0.3,
                                              rank=RANK)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(sparse))


def test_padding_rows_leave_both_pools_bit_equal():
    (rs, r0, rl, ro), table, ops = _batch(6, [])
    for leaf, new in (("pool", jnp.stack([ops["new"]] * 2)),
                      ("pool_i", jnp.stack([ops["newI"]] * 2))):
        after = la.ragged_latent_append(ops[leaf], new, rs, r0, rl, ro, table)
        np.testing.assert_array_equal(np.asarray(after)[:, :, :P],
                                      np.asarray(ops[leaf])[:, :, :P])
    # and a live row writes its own pages of both and no other
    (rs, r0, rl, ro), table, ops = _batch(6, [(1, 5, 9)])
    after = la.ragged_latent_append(ops["pool_i"], jnp.stack(
        [ops["newI"]] * 2), rs, r0, rl, ro, table)
    got = np.asarray(after)[0, 0, np.asarray(table)[1]].reshape(C, D)
    np.testing.assert_array_equal(got[5:14], np.asarray(ops["newI"])[:9])
    untouched = np.ones((P,), bool)
    untouched[np.asarray(table)[1, :2]] = False
    np.testing.assert_array_equal(np.asarray(after)[:, :, :P][:, :, untouched],
                                  np.asarray(ops["pool_i"])[:, :, :P][
                                      :, :, untouched])
