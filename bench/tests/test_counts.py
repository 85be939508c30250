"""``bench/counts.py`` against XLA's own count of an unrolled one-layer
program at the configuration's published widths, with its SwiGLU MLP and
with a two-matrix GELU one (compiled on the CPU, nothing allocated).  XLA counts the full (S, S) attention the reference
computes and the elementwise work, which ``counts`` leaves out; at S = 256
the elementwise work is under 1% of the total."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import counts
from bench.reference import decoder, weights
from bench.tests.conftest import ROOT

S = 256


MLPS = {"swiglu": {}, "gelu": {"mlp": "gelu_tanh", "norm_type": "layernorm",
                                "attention_bias": True}}


def _config(mlp="swiglu"):
    c = json.loads((ROOT / "bench" / "configs" /
                    "h2o-danube-3-4b.json").read_text())
    return dict(c, **MLPS[mlp])


def _xla_flops(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("mlp", list(MLPS))
def test_one_layer_forward(mlp):
    c = dict(_config(mlp), num_hidden_layers=1)
    f32 = jnp.float32
    layer = jax.eval_shape(lambda: jax.tree.map(
        lambda x: x[0].astype(f32), weights.init_base(c, 0)["groups"]["0"]))
    ad = jax.eval_shape(lambda: jax.tree.map(
        lambda x: x[0, 0], weights.init_bank(c, 1, 0, 0.1)["groups"]["0"]))
    x = jax.ShapeDtypeStruct((1, S, c["hidden_size"]), f32)
    pos = jnp.arange(S)[None]

    def block(lp, la, x):
        return decoder._block(c, decoder.f32_matmul, x, pos, lp, la)

    got = _xla_flops(block, layer, ad, x)
    full_attn = counts.attn_fwd_flops(c, S) * S          # no causal half
    want = S * (2 * counts.layer_matmul_params(c) + counts.adapter_fwd_flops(c))
    assert got == pytest.approx(want + full_attn, rel=0.01)
    # the count keeps only what causal attention needs: half, plus the
    # diagonal
    causal = counts.attn_fwd_flops(c, S * (S + 1) / 2)
    assert causal == pytest.approx(full_attn * (S + 1) / (2 * S))


def test_unembedding():
    c = _config()
    x = jax.ShapeDtypeStruct((S, c["hidden_size"]), jnp.float32)
    t = jax.ShapeDtypeStruct((c["vocab_size"], c["hidden_size"]), jnp.float32)
    got = _xla_flops(lambda x, t: decoder.f32_matmul(x, t.T), x, t)
    assert got == pytest.approx(S * counts.unembed_fwd_flops(c), rel=1e-3)


def test_serving_total():
    d = _config()
    per_layer = counts.layer_matmul_params(d)
    assert per_layer == 3840 * (2 * 3840 + 2 * 960) + 3 * 3840 * 10240
    one = counts.serve_request_flops(d, 1, 1)      # one token, one logit
    assert one == pytest.approx(d["num_hidden_layers"] * (
        2 * per_layer + counts.adapter_fwd_flops(d)
        + counts.attn_fwd_flops(d, 1)) + counts.unembed_fwd_flops(d))
