"""The serving tests' oracle: greedy decoding with no cache at all.

Every token comes from ``llama.forward`` over the whole sequence so
far, so the oracle shares no cache layout, page table, kernel or
sampling code with the engine it judges.  Use it in fp32: greedy
equality between different jitted programs is not a contract in bf16
(tiny-model logit ties round differently under fusion).
"""

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama

# One compiled forward per padded length, not one per length: attention
# is causal, so right padding cannot reach the positions before it.
_PAD = 16
_forward = jax.jit(llama.forward, static_argnums=2)


def next_token_logits(params, cfg, tokens: Sequence[int]) -> np.ndarray:
    """float32 logits [V] for the token after ``tokens``."""
    n = len(tokens)
    buf = np.zeros((1, -(-n // _PAD) * _PAD), np.int32)
    buf[0, :n] = tokens
    return np.asarray(_forward(params, jnp.asarray(buf), cfg)[0, n - 1],
                      np.float32)


def greedy_tokens(params, cfg, prompt: Sequence[int], n: int) -> List[int]:
    """The ``n`` tokens greedy decoding yields after ``prompt``."""
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(np.argmax(next_token_logits(params, cfg, toks))))
    return toks[len(prompt):]


def decisive_prompts(params, cfg, n_prompts: int, prompt_len: int,
                     n_new: int, margin: float, seed: int = 0):
    """``(prompts, tokens)``: the first ``n_prompts`` prompts of a seeded
    search whose greedy continuation of ``n_new`` tokens is decided at
    EVERY step by at least ``margin`` between the two largest logits, and
    in which no token repeats its neighbour (a model this small likes to
    settle on one token, and a stream of one token reads the same with a
    token lost or sent twice).

    What an engine in bf16 is held to token for token.  Two programs
    that round differently (a prefill and a decode step, a kernel
    before and after a change to its order of sums) may break a near
    tie either way, and both answers are valid; a token decided by
    ``margin`` is the same under every valid rounding, so a difference
    there is a defect of the engine and not of the oracle."""
    rng = np.random.default_rng(seed)
    width = -(-(prompt_len + n_new) // _PAD) * _PAD
    found = []
    for _ in range(32):
        toks = np.zeros((256, width), np.int32)
        toks[:, :prompt_len] = rng.integers(1, cfg.vocab_size,
                                            (256, prompt_len))
        least = np.full(256, np.inf, np.float32)
        for at in range(prompt_len, prompt_len + n_new):
            logits = np.asarray(
                _forward(params, jnp.asarray(toks), cfg)[:, at - 1],
                np.float32)
            top = np.sort(logits, axis=-1)[:, -2:]
            least = np.minimum(least, top[:, 1] - top[:, 0])
            toks[:, at] = logits.argmax(-1)
        new = toks[:, prompt_len:prompt_len + n_new]
        keep = (least >= margin) & (new[:, 1:] != new[:, :-1]).all(axis=1)
        found += [(row[:prompt_len].tolist(), row[prompt_len:].tolist())
                  for row in toks[keep, :prompt_len + n_new]]
        if len(found) >= n_prompts:
            prompts, tokens = zip(*found[:n_prompts])
            return list(prompts), list(tokens)
    raise AssertionError(
        f"{len(found)} of {n_prompts} prompts found whose every greedy "
        f"token is decided by a logit margin of {margin} and differs "
        f"from the one before: the model's continuations are near ties "
        f"or one token over and over, and argmaxes cannot judge them")
