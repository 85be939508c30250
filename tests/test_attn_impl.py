"""Attention-backend registry (attention.select_impl) + attn_impl threading
(DESIGN.md §14): dispatch precedence, the auto/cross thresholds, federated
resolution, and checkpoint-fingerprint semantics.

Flash-path EXECUTION lives in tests/test_kernels.py (subprocess harness —
kernel-suite isolation); nothing here compiles a Pallas program.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.core import fed_engine
from repro.core.fed_model import FedTask
from repro.core.federated import FedConfig, run_federated
from repro.models import attention, model
from repro.models.attention import (AUTO_REF_MAX_SEQ, CROSS_TILE_THRESHOLD,
                                    IMPLS, select_impl)

from conftest import make_batch


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------

def test_auto_threshold_crossover():
    assert select_impl(None, 128) == "ref"
    assert select_impl(None, AUTO_REF_MAX_SEQ) == "ref"
    assert select_impl(None, AUTO_REF_MAX_SEQ + 1) == "blockwise"


def test_explicit_impl_overrides_config(tiny_cfg):
    cfg = tiny_cfg.with_overrides(attn_impl="blockwise")
    assert select_impl(cfg, 64) == "blockwise"
    assert select_impl(cfg, 64, impl="ref") == "ref"
    assert select_impl(cfg, 64, impl="flash") == "flash"
    # config-level flash is honored at any length
    assert select_impl(tiny_cfg.with_overrides(attn_impl="flash"), 8) \
        == "flash"


def test_cv_hp_downgrade_at_short_seq(tiny_cfg):
    for name in ("blockwise_cv", "blockwise_hp"):
        assert select_impl(tiny_cfg, 64, impl=name) == "ref"
        assert select_impl(tiny_cfg, AUTO_REF_MAX_SEQ + 1, impl=name) == name
        cfg = tiny_cfg.with_overrides(attn_impl=name)
        assert select_impl(cfg, 64) == "ref"


def test_unknown_impl_raises(tiny_cfg):
    with pytest.raises(ValueError, match="unknown attn_impl"):
        select_impl(tiny_cfg, 64, impl="fast")
    with pytest.raises(ValueError, match="unknown attn_impl"):
        select_impl(tiny_cfg.with_overrides(attn_impl="bogus"), 64)


def test_cross_attention_crossover_pin():
    """Pins the tiling crossover at CROSS_TILE_THRESHOLD (the old inline
    4_194_304 literal in cross_attention)."""
    assert CROSS_TILE_THRESHOLD == 4_194_304
    assert select_impl(None, 2048, kv_len=2048) == "ref"        # == threshold
    assert select_impl(None, 2048, kv_len=2049) == "blockwise"  # just above
    # explicit ref/blockwise are honored on the cross path ...
    assert select_impl(None, 8192, kv_len=8192, impl="ref") == "ref"
    assert select_impl(None, 64, kv_len=64, impl="blockwise") == "blockwise"
    # ... every other backend (flash is causal-only) falls to the threshold
    assert select_impl(None, 64, kv_len=64, impl="flash") == "ref"
    assert select_impl(None, 4096, kv_len=4096, impl="flash") == "blockwise"


def test_kv_valid_pins_ref(tiny_cfg):
    """Decode attends through the exact masked reference whatever backend
    the config names: under 'flash' and 'blockwise' a decode run gives the
    logits, tokens and KV rings of 'ref' bit for bit, on the scalar-idx
    path and on the ragged path (rows at -1 masked), with an SWA ring that
    wraps and an unscanned tail block."""
    from repro.core import adapter_bank
    from repro.launch import serve
    cfg0 = tiny_cfg.with_overrides(n_layers=3, layer_pattern=("attn", "swa"),
                                   window=2)
    params = model.init_params(cfg0, jax.random.key(0))
    bank = adapter_bank.random_bank(cfg0, 2, jax.random.key(1)).decode_tree()
    toks = np.random.default_rng(0).integers(
        0, cfg0.vocab_size, (4, 3, 1)).astype(np.int32)
    step = jax.jit(model.decode_step, static_argnums=0)

    def decode(impl):
        cfg = cfg0.with_overrides(attn_impl=impl)
        cache, logits = model.init_decode_cache(cfg, 3, 4), []
        for t, tok in enumerate(toks):
            lg, cache = step(cfg, params["base"], params["adapter"], cache,
                             {"token": tok,
                              "positions": np.full((3, 1), t, np.int32)})
            logits.append(lg)
        ragged, nxt = model.init_decode_cache(cfg, 3, 4), []
        for t, tok in enumerate(toks):
            pos = np.asarray([t, -1, t - 1], np.int32)   # row 2 starts late
            rows = np.where(pos >= 0, [0, 0, 1], -1).astype(np.int32)
            n, ragged = serve._serve_step(cfg, params["base"], bank, ragged,
                                          tok, pos, rows)
            nxt.append(n)
        return logits, cache, nxt, ragged

    want = decode("ref")
    for impl in ("flash", "blockwise"):
        jax.tree.map(np.testing.assert_array_equal, decode(impl), want)


def test_impls_registry_is_exhaustive():
    assert IMPLS == ("auto", "ref", "blockwise", "blockwise_hp",
                     "blockwise_cv", "flash")


# ---------------------------------------------------------------------------
# config-driven dispatch through the model stack
# ---------------------------------------------------------------------------

def test_forward_hidden_defers_to_cfg(tiny_cfg):
    """cfg.attn_impl="blockwise" and an explicit attn_impl="blockwise" are
    the same program; both match the default ref numerics at short seq."""
    batch = make_batch(tiny_cfg, b=2, s=16)
    params = model.init_params(tiny_cfg, jax.random.key(0))

    def hid(cfg, **kw):
        h, _, _ = model.forward_hidden(cfg, params["base"],
                                       params["adapter"], batch, **kw)
        return np.asarray(h)

    ref = hid(tiny_cfg)                                   # auto -> ref
    via_cfg = hid(tiny_cfg.with_overrides(attn_impl="blockwise"))
    via_kwarg = hid(tiny_cfg, attn_impl="blockwise")
    np.testing.assert_array_equal(via_cfg, via_kwarg)
    np.testing.assert_allclose(via_cfg, ref, rtol=2e-5, atol=2e-5)


def test_self_attention_explicit_kwarg_beats_cfg(tiny_cfg, monkeypatch):
    seen = []
    orig = attention.select_impl

    def spy(cfg, seq_len, **kw):
        out = orig(cfg, seq_len, **kw)
        seen.append(out)
        return out

    monkeypatch.setattr(attention, "select_impl", spy)
    cfg = tiny_cfg.with_overrides(attn_impl="blockwise")
    p = attention.init_attn(jax.random.key(0), cfg)
    x = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
    pos = jnp.arange(8)[None]
    attention.self_attention(cfg, p, x, pos, impl="ref")
    assert seen[-1] == "ref"
    attention.self_attention(cfg, p, x, pos)
    assert seen[-1] == "blockwise"


# ---------------------------------------------------------------------------
# federated resolution + fingerprint semantics
# ---------------------------------------------------------------------------

def test_run_federated_rejects_unknown_backend(tiny_cfg):
    task = FedTask(tiny_cfg, base={}, n_classes=2)   # validation-only stub
    fed = FedConfig(n_clients=2, attn_impl="fastpath")
    with pytest.raises(ValueError, match="attn_impl"):
        run_federated(task, fed, [{}, {}], [{}, {}])


def test_fingerprint_includes_attn_impl():
    fed = FedConfig()
    assert fed.attn_impl is None                 # inherit task.cfg
    fp = fed_engine._fingerprint(fed)
    assert fp["attn_impl"] == "auto"             # None normalized
    fed2 = dataclasses.replace(fed, attn_impl="flash")
    assert fed_engine._fingerprint(fed2)["attn_impl"] == "flash"


def test_checkpoint_backfills_attn_impl(tmp_path):
    """Pre-§14 checkpoints carry no attn_impl — backfilled to "auto" like
    uplink_codec/client_store; a genuine mismatch still rejects."""
    want = {"arch": "tiny", "attn_impl": "auto"}
    old_meta = {"arch": "tiny"}                  # older checkpoint
    ckpt.check_fingerprint("x.npz", dict(old_meta), want,
                           defaults={"attn_impl": "auto"})
    with pytest.raises(ValueError, match="attn_impl"):
        ckpt.check_fingerprint(
            "x.npz", dict(old_meta), {"arch": "tiny", "attn_impl": "flash"},
            defaults={"attn_impl": "auto"})
