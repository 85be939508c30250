"""The float32 reference and the seeded weights against the program, at
small widths on the CPU: the benchmark's configuration (RMSNorm, SwiGLU,
sliding window, 4:2 GQA) and the same with the reference's other branch
(LayerNorm, tanh-GELU, q/k/v bias)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import decoder, weights
from bench.tests import tiny
from bench.tests.conftest import ROOT

VARIANTS = {
    "rmsnorm-swiglu": ({}, {}),
    "layernorm-gelu-bias": (
        {"norm_type": "layernorm", "norm_eps": 1e-5, "mlp": "gelu_tanh",
         "attention_bias": True},
        {"norm_type": "layernorm", "mlp_type": "gelu", "attn_bias": True}),
}


def config(variant="rmsnorm-swiglu"):
    mine, theirs = VARIANTS[variant]
    c = json.loads((ROOT / "bench" / "configs" /
                    "h2o-danube-3-4b.json").read_text())
    c = tiny.tiny_config(c, **mine)
    c["program"]["overrides"].update(theirs)
    return c


def program(c):
    from repro.models.config import get_config
    p = c["program"]
    return get_config(p["arch"]).with_overrides(**p["overrides"])


@pytest.fixture(params=list(VARIANTS))
def cfgs(request):
    c = config(request.param)
    return c, program(c)


def test_weights_match_the_program_bit_for_bit(cfgs):
    from repro.models import model
    c, cfg = cfgs
    mine = weights.init_base(c, 2 ** 31 + 5)
    theirs = model.init_base(cfg, jax.random.key(2 ** 31 + 5))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)))


def _bank_adapter(c, seed):
    bank = weights.init_bank(c, 3, seed, 0.5)
    return weights.user_adapter(bank, 1)


def test_forward_agrees(cfgs):
    """The program's own forward in float32 agrees with the reference to
    float32 rounding."""
    from repro.models import model
    c, cfg = cfgs
    cfg = cfg.with_overrides(param_dtype="float32")
    base = jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights.init_base(c, 11))
    ad = _bank_adapter(c, 3)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, c["vocab_size"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(cfg, base, ad, {"tokens": toks})
    got = decoder.logits(c, base, ad, toks)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_decode_through_the_engine_matches_the_reference():
    """The bank engine's greedy tokens are the reference's argmax at every
    served position when both run in float32, with slots reused."""
    from repro.core.adapter_bank import AdapterBank
    from repro.launch.serve import Request, ServeEngine
    c = config()
    cfg = program(c).with_overrides(param_dtype="float32")
    base = jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights.init_base(c, 4))
    tree = jax.tree.map(np.asarray, weights.init_bank(c, 3, 5, 0.5))
    bank = AdapterBank(tree=tree, n_clients=3, rank=c["lora_rank"],
                       users={f"u{i}": i for i in range(3)})
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, user_id=f"u{i % 3}",
                    prompt=rng.integers(0, c["vocab_size"], 5 + i).astype(np.int32),
                    gen=6) for i in range(4)]
    with jax.default_matmul_precision("highest"):
        done = ServeEngine(cfg, base, bank, slots=2, max_len=16).run(reqs)
    full = weights.init_bank(c, 3, 5, 0.5)
    for r in reqs:
        toks = done[r.rid]
        lg = decoder.logits(c, base, weights.user_adapter(full, r.rid % 3),
                            jnp.asarray(toks[None, :-1]))[0]
        p = len(r.prompt)
        np.testing.assert_array_equal(np.asarray(lg[p - 1:]).argmax(-1),
                                      toks[p:])
