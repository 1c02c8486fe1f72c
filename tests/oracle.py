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
