"""The plain reference's own promises, at toy sizes on the CPU: it reads
an int8 weight-only tree by the published convention, and its forward
pass is causal."""

import numpy as np
import pytest

from benchmarks.harness import reference

TOY = {"hidden_size": 16, "intermediate_size": 32, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 50,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-5}


@pytest.mark.parametrize("q_shape,scale_shape", [
    ((4, 6), (1, 6)),           # one matrix, a scale per output channel
    ((3, 4, 6), (3, 1, 6)),     # stacked by layer: the layer axis is kept
    ((3, 4, 2, 6), (3, 1, 1, 6)),
])
def test_dequantize_is_q_times_the_channel_scale(q_shape, scale_shape):
    rng = np.random.default_rng(0)
    q = rng.integers(-127, 128, q_shape).astype(np.int8)
    scale = rng.uniform(0.01, 0.1, scale_shape).astype(np.float32)
    tree = {"layers": {"w": {"q": q, "scale": scale}}, "norm": scale}
    out = reference.dequantize(tree)
    assert out["norm"] is scale
    got = np.asarray(out["layers"]["w"])
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, q.astype(np.float32) * scale)
    np.testing.assert_allclose(got[..., 2], q[..., 2] * scale[..., 2])


def test_dequantize_refuses_a_scale_that_is_not_per_output_channel():
    q = np.zeros((4, 6), np.int8)
    with pytest.raises(ValueError, match="output channel"):
        reference.dequantize({"q": q, "scale": np.ones((4, 1), np.float32)})


def _toy_params(rng):
    z = reference.dims(TOY)
    mat = lambda *s: rng.normal(0, 0.2, s).astype(np.float32)  # noqa: E731
    layer = lambda: {  # noqa: E731
        "wq": mat(z["d"], z["h"] * z["hd"]),
        "wk": mat(z["d"], z["kvh"] * z["hd"]),
        "wv": mat(z["d"], z["kvh"] * z["hd"]),
        "wo": mat(z["h"] * z["hd"], z["d"]),
        "w_gate": mat(z["d"], z["m"]), "w_up": mat(z["d"], z["m"]),
        "w_down": mat(z["m"], z["d"]),
        "ln_attn": np.ones(z["d"], np.float32),
        "ln_mlp": np.ones(z["d"], np.float32)}
    return {"tok_embed": mat(z["v"], z["d"]),
            "layers": [layer() for _ in range(TOY["num_hidden_layers"])],
            "final_norm": np.ones(z["d"], np.float32),
            "lm_head": mat(z["d"], z["v"])}


def test_forward_is_causal_and_sees_positions():
    rng = np.random.default_rng(1)
    params = _toy_params(rng)
    toks = np.array([3, 7, 7, 11, 2, 9], np.int32)
    base = np.asarray(reference.forward(params, toks, TOY))
    assert base.shape == (6, TOY["vocab_size"])
    later = toks.copy()
    later[4] = 40
    moved = np.asarray(reference.forward(params, later, TOY))
    np.testing.assert_allclose(moved[:4], base[:4], atol=1e-6)
    assert np.abs(moved[4:] - base[4:]).max() > 1e-3
    # the same token at another position gives another logit row (rope)
    assert np.abs(base[1] - base[2]).max() > 1e-4
