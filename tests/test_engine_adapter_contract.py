"""What ``LLMEngine`` may assume of a model: the seam is
``PagedEngineAdapter`` with ONE step plug.  The same contract is held
to every adapter in the tree (llama, llama with LoRA, Jamba, Brumby, Xing, GLM-5,
MiniCPM-SALA), so a new
model family knows what it has to provide."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import brumby, glm5, jamba, llama, minicpm_sala, xing
from ray_tpu.ops.block_sparse_attention import BlockSparse
from ray_tpu.ops import segmented_lora
from ray_tpu.ops.ragged_paged_attention import pack_ragged_batch
from ray_tpu.serve.llm_engine import (
    EngineConfig,
    LLMEngine,
    brumby_paged_adapter,
    glm5_paged_adapter,
    jamba_paged_adapter,
    llama_paged_adapter,
    ragged_step_shapes,
    sala_paged_adapter,
    xing_paged_adapter,
)

pytestmark = pytest.mark.long_file(123)

LLAMA = llama.LlamaConfig(
    vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=64, remat=False, dtype=jnp.float32,
    param_dtype=jnp.float32)
LLAMA_LORA = dataclasses.replace(
    LLAMA, lora=segmented_lora.LoRAConfig(rank=4, alpha=8.0))
JAMBA = jamba.JambaConfig(
    vocab_size=97, dim=64, n_layers=4, n_heads=4, n_kv_heads=1, head_dim=16,
    mlp_dim=96, attn_layer_period=3, attn_layer_offset=1, dt_rank=8,
    dtype=jnp.float32, param_dtype=jnp.float32)
BRUMBY = brumby.BrumbyConfig(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
    mlp_dim=64, dtype=jnp.float32, param_dtype=jnp.float32)
XING = xing.XingConfig(
    vocab_size=97, dim=64, n_layers=4, n_heads=4, n_kv_heads=4, mlp_dim=96,
    first_dense=2, q_rank=16, kv_rank=8, nope_dim=16, rope_dim=8, v_dim=16,
    n_experts=8, top_k=2, moe_dim=32, dtype=jnp.float32,
    param_dtype=jnp.float32)
GLM5 = glm5.Glm5Config(
    vocab_size=97, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, mlp_dim=96,
    first_dense=1, q_rank=16, kv_rank=8, nope_dim=16, rope_dim=8, v_dim=16,
    n_routed=8, n_experts=4, expert_first=4, top_k=2, moe_dim=32,
    index_heads=4, index_dim=16, index_topk=4, dtype=jnp.float32,
    param_dtype=jnp.float32)
SALA = minicpm_sala.SalaConfig(
    vocab_size=97, dim=64, n_heads=4, n_kv_heads=2, head_dim=16, mlp_dim=96,
    mixer_types=("lightning-attn", "minicpm4", "lightning-attn"),
    first_layer=21, dim_model_base=32,
    sparse=BlockSparse(block=8, kernel=4, stride=2, topk=4, window=16,
                       dense_len=32),
    dtype=jnp.float32, param_dtype=jnp.float32)
PAGE, SLOTS, MAXP, BUDGET = 8, 4, 4, 24
TABLE = np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)
ROWS = [{"slot": 2, "start": 0, "tokens": [5, 9, 2, 7, 1, 3]},
        {"slot": 0, "start": 0, "tokens": [4, 8]}]

# name: (adapter factory, model config, init_params, keywords its step takes)
CASES = {
    "llama": (llama_paged_adapter, LLAMA, llama.init_params,
              {"logit_idx"}),
    "llama_lora": (llama_paged_adapter, LLAMA_LORA, llama.init_params,
                   {"lora", "logit_idx"}),
    "jamba": (jamba_paged_adapter, JAMBA, jamba.init_params, set()),
    "brumby": (brumby_paged_adapter, BRUMBY, brumby.init_params, set()),
    "xing": (xing_paged_adapter, XING, xing.init_params, set()),
    "glm5": (glm5_paged_adapter, GLM5, glm5.init_params, set()),
    "sala": (sala_paged_adapter, SALA, minicpm_sala.init_params, set()),
}


@functools.cache
def _family(case):
    """A family's adapter, parameters and step, built once for both
    tests: one jitted function and an executable a shape, as in the
    engine, so that the shapes the tests share are compiled once."""
    make, cfg, init_params, takes = CASES[case]
    adapter = make(cfg)
    return (adapter, cfg, init_params(jax.random.key(0), cfg), takes,
            jax.jit(adapter.ragged_step))


def _init_cache(adapter):
    """The adapter's cache as the engine asks for it: a cache that holds
    state by slot is told the slots, and no page where it holds none."""
    if adapter.state_bytes_per_slot:
        return adapter.init_cache(SLOTS * MAXP if adapter.paged_kv else 0,
                                  PAGE, SLOTS)
    return adapter.init_cache(SLOTS * MAXP, PAGE)


@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_step_is_the_one_step_plug(case):
    adapter, cfg, params, takes, step = _family(case)
    # one step field; what an adapter does not serve is absent, not a stub
    step_fields = {f.name for f in dataclasses.fields(adapter)
                   if f.name.startswith("ragged_step")}
    assert step_fields == {"ragged_step"}
    # a cache of state by slot only says how much state that is
    assert adapter.paged_kv or adapter.state_bytes_per_slot
    cache = _init_cache(adapter)
    if adapter.state_bytes_per_slot:
        assert adapter.prefill_slot is None and adapter.decode_slots is None
        # the adapter names its by-slot leaves; they are the whole tree
        # exactly where it says that it holds no page
        assert adapter.state_leaves and set(adapter.state_leaves) <= set(cache)
        assert adapter.paged_kv == (set(adapter.state_leaves) < set(cache))
    else:
        assert not adapter.state_leaves
    # counters the step keeps are leaves of the tree, and not state by slot
    assert set(adapter.counter_leaves) <= set(cache)
    assert not set(adapter.counter_leaves) & set(adapter.state_leaves)
    # a step that bounds its rows says so; the rows here are inside it
    assert (adapter.max_row_tokens is None
            or max(len(r["tokens"]) for r in ROWS) <= adapter.max_row_tokens)
    (toks, _mask, _slot, pos, r_slot, r_start, r_len, r_off) = \
        pack_ragged_batch(ROWS, BUDGET, SLOTS)
    nine = (params, toks, pos, r_slot, r_start, r_len, r_off, TABLE, cache)

    logits, new_cache = step(*nine)
    assert logits.shape == (SLOTS, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)
    assert np.isfinite(np.asarray(logits[:len(ROWS)])).all()

    idx = np.asarray([0, 1, 2, 6, 7], np.int32)
    if "lora" in takes:
        pool = adapter.make_adapter_pool(EngineConfig(max_slots=SLOTS))
        # every token on the null adapter: the pool's zero scratch page
        lora = (pool.device_pool, pool.page_table([]),
                np.zeros((BUDGET,), np.int32))
        with_lora, _ = step(*nine, lora=lora)
        np.testing.assert_array_equal(np.asarray(with_lora),
                                      np.asarray(logits))
    else:
        assert adapter.make_adapter_pool is None
    if "logit_idx" in takes:
        row, verify, _ = step(*nine, logit_idx=idx)
        np.testing.assert_array_equal(np.asarray(row), np.asarray(logits))
        assert verify.shape == (len(idx), cfg.vocab_size)
        # the last token of row 0 sits at flat position 5, of row 1 at 7
        np.testing.assert_array_equal(np.asarray(verify[4]),
                                      np.asarray(logits[1]))
    for name in sorted({"lora", "logit_idx"} - takes):
        value = idx if name == "logit_idx" else (None, None, None)
        with pytest.raises((TypeError, ValueError), match=name):
            step(*nine, **{name: value})


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_decode_step_reads_the_same_at_either_compiled_shape(case):
    """The engine runs a step at the smallest of ``ragged_step_shapes``
    that holds its tokens.  Only the [T] arrays change length, and a
    row's result does not depend on the padding beside it: the same
    decode rows through the small shape and through the budget's give
    the same tokens, logits and cache."""
    adapter, _cfg, params, takes, step = _family(case)
    cache = _init_cache(adapter)
    small, budget = ragged_step_shapes(BUDGET, SLOTS)
    assert (small, budget) == (8, BUDGET)
    pool = None
    if "lora" in takes:
        pool = adapter.make_adapter_pool(EngineConfig(max_slots=SLOTS))
        pool.acquire("tenant-a")
    def run(rows, T, cache):
        (toks, _mask, _slot, pos, r_slot, r_start, r_len, r_off, tok_ad) = \
            pack_ragged_batch(rows, T, SLOTS, with_adapters=True)
        kw = ({"lora": (pool.device_pool, pool.page_table(["tenant-a"]),
                        tok_ad)} if pool is not None else {})
        return step(params, toks, pos, r_slot, r_start, r_len, r_off,
                    TABLE, cache, **kw)

    prompts = [dict(ROWS[0], adapter=1), dict(ROWS[1], adapter=0)]
    _, cache = run(prompts, budget, cache)
    decode = [{"slot": 2, "start": 6, "tokens": [11], "adapter": 1},
              {"slot": 0, "start": 2, "tokens": [13], "adapter": 0}]
    (l_small, c_small), (l_budget, c_budget) = (
        run(decode, T, cache) for T in (small, budget))
    l_small, l_budget = (np.asarray(x[:len(decode)])
                         for x in (l_small, l_budget))
    np.testing.assert_array_equal(l_small.argmax(-1), l_budget.argmax(-1))
    np.testing.assert_allclose(l_small, l_budget, rtol=1e-5, atol=1e-5)
    for name in sorted(cache):
        np.testing.assert_allclose(
            np.asarray(c_small[name]), np.asarray(c_budget[name]),
            rtol=1e-5, atol=1e-6, err_msg=name)
    # and the step did write: the cache is not what it was
    assert any(not np.array_equal(np.asarray(c_small[name]),
                                  np.asarray(cache[name]))
               for name in cache)


@pytest.mark.parametrize("field", ["prefill_slot", "decode_slots"])
def test_two_program_path_names_the_field_it_misses(field):
    params = llama.init_params(jax.random.key(0), LLAMA)
    adapter = dataclasses.replace(llama_paged_adapter(LLAMA),
                                  **{field: None})
    with pytest.raises(ValueError, match=f"PagedEngineAdapter.{field}"):
        LLMEngine(params, adapter, EngineConfig(
            max_slots=2, max_seq_len=64, page_size=PAGE))
    # the ragged step needs neither
    eng = LLMEngine(params, adapter, EngineConfig(
        max_slots=2, max_seq_len=64, page_size=PAGE, ragged_batching=True))
    try:
        assert len(eng.generate([1, 2, 3], max_new_tokens=3)) == 3
    finally:
        eng.shutdown()
