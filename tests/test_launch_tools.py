"""Launch-tool smoke coverage: the multi-pod dry-run compiler and the
batched serving driver's CLI entry points.

dryrun MUST run as its own process (it sets XLA_FLAGS to request 512
placeholder devices before jax initializes — see its module docstring and
conftest.py), so the test shells out; serve.main is safe in-process.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

_REPO = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_dryrun_single_combo(tmp_path):
    """One (arch × shape) combo lowers + compiles against the emulated
    256-device production mesh and drops its JSON artifact where told
    (--out-dir keeps test artifacts out of the repo tree)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(_REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "fed-100m",
         "--shape", "train_4k", "--no-hlo", "--out-dir", str(tmp_path)],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1/1 combos lowered+compiled" in proc.stdout
    art = tmp_path / "16x16" / "fed-100m__train_4k.json"
    assert art.exists(), list(tmp_path.rglob("*"))
    rec = json.loads(art.read_text())
    assert rec["arch"] == "fed-100m" and rec["shape"] == "train_4k"
    assert rec["n_devices"] == 256
    assert rec["compile_s"] > 0
    assert "hlo_path" not in rec                      # --no-hlo honored


def test_serve_main_cli(monkeypatch, capsys):
    """The serving driver's argparse entry generates end to end."""
    from repro.launch import serve
    # keep this worker's later compiles out of the persistent cache
    monkeypatch.setattr(serve, "place_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv",
                        ["serve", "--arch", "fed-100m", "--reduced",
                         "--batch", "1", "--prompt-len", "4", "--gen", "2"])
    serve.main()
    out = capsys.readouterr().out
    assert "generated (1, 6)" in out
    assert "sample:" in out


def test_serve_generate_sampled_path():
    """The non-greedy decode branch (categorical sampling) stays in-vocab
    and deterministic under a fixed seed."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import generate
    from repro.models import model
    from repro.models.config import get_config

    cfg = get_config("fed-100m").reduced()
    params = model.init_params(cfg, jax.random.key(0))
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 4)),
        jnp.int32)
    out1 = generate(cfg, params, prompts, gen=3, greedy=False, seed=7)
    out2 = generate(cfg, params, prompts, gen=3, greedy=False, seed=7)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 7)
    assert np.all(np.asarray(out1) >= 0)
    assert np.all(np.asarray(out1) < cfg.vocab_size)


def test_steps_default_attn_impl_from_config(monkeypatch):
    """steps.make_train_step/make_prefill_step pass attn_impl=None down the
    stack, so the attention layer resolves the backend from
    ModelConfig.attn_impl (DESIGN.md §14) — not a hardcoded "auto"."""
    import jax
    from repro.launch import steps as st
    from repro.models import attention, model
    from repro.models.config import get_config

    from conftest import make_batch

    seen = []
    orig = attention.select_impl

    def spy(cfg, seq_len, **kw):
        out = orig(cfg, seq_len, **kw)
        seen.append((kw.get("impl"), out))
        return out

    monkeypatch.setattr(attention, "select_impl", spy)
    cfg = get_config("fed-100m").reduced().with_overrides(
        attn_impl="blockwise")
    params = model.init_params(cfg, jax.random.key(0))
    batch = make_batch(cfg, b=2, s=16)

    step = st.make_train_step(cfg, lr=1e-3)
    jax.eval_shape(step, params, step.optimizer.init(params["adapter"]),
                   batch)
    assert seen and all(received is None for received, _ in seen)
    assert all(resolved == "blockwise" for _, resolved in seen)

    seen.clear()
    pf = st.make_prefill_step(cfg)
    jax.eval_shape(pf, params, {k: v for k, v in batch.items()
                                if k != "labels"})
    assert seen and all(s == (None, "blockwise") for s in seen)
