"""The DeepSeek-V2 reference, its seeded weights and its driver against the
program, at small widths on the CPU: weights bit for bit, the program's
forward and the reference's agreeing to float32 rounding, a sound run of
the cell correct, and the float8 control beyond the limit."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, counts_mla, run
from bench.reference import deepseek_v2, deepseek_v2_weights as weights
from bench.tests import tiny
from bench.tests.conftest import ROOT

CELL = "dsv2lite-serve-longgen"
# 4 of 8 routed experts held, top 3, the published structure otherwise
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 16, "v_head_dim": 16, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "vocab_size": 512, "n_routed_experts": 4, "num_experts_per_tok": 3}
PROGRAM = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_dim",
           "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
           "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
           "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
           "n_routed_experts": "experts_held",
           "num_experts_per_tok": "top_k"}
# at these sizes the sound program read at most 0.0030 over four seeds on
# the CPU, and the float8 control at least 0.077
TINY_LIMITS = {"served_logit_gap": 0.01}


def tiny_config(**kw) -> dict:
    c = json.loads((ROOT / "bench" / "configs" /
                    "deepseek-v2-lite.json").read_text())
    c = {**c, **TINY, **kw,
         "published": dict(c["published"], n_routed_experts=8)}
    over = {PROGRAM[k]: c[k] for k in PROGRAM}
    over.update(n_experts=8, shared_d_ff=2 * c["moe_intermediate_size"])
    c["program"] = {"arch": c["program"]["arch"], "overrides": over}
    return c


def program(c):
    from repro.models.config import get_config
    p = c["program"]
    return get_config(p["arch"]).with_overrides(**p["overrides"])


def test_weights_match_the_program_bit_for_bit():
    from repro.models import model
    c = tiny_config()
    mine = weights.init_base(c, 2 ** 31 + 5)
    theirs = model.init_base(program(c), jax.random.key(2 ** 31 + 5))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)))


def test_forward_agrees():
    """The program's own forward in float32, at a capacity factor where the
    Switch dispatch keeps every token, agrees with the reference to float32
    rounding."""
    from repro.models import model
    c = tiny_config(torch_dtype="float32")
    cfg = program(c).with_overrides(param_dtype="float32",
                                    capacity_factor=8 / 3)
    base = weights.init_base(c, 11)
    ad = jax.tree.map(lambda x: x[1], weights.init_bank(c, 3, 3, 0.5))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, c["vocab_size"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(cfg, base, ad, {"tokens": toks})
    got = deepseek_v2.logits(dict(weights.freeze(c)), base, ad, toks)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def shrink(cell):
    cell.config = tiny_config()
    cell.mix = dict(cell.mix, users=6, slots=4, max_len=48, requests=8,
                    prompt=dict(cell.mix["prompt"], min=2, max=24),
                    output=dict(cell.mix["output"], min=2, max=24, median=8),
                    check_tokens=40)
    cell.limits = TINY_LIMITS


def test_a_sound_run_is_correct_and_the_control_is_not():
    res = run.run_cell(tiny.args(CELL), find_chips=tiny.cpu_chips,
                       adjust=shrink)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}

    ns = tiny.args(CELL)
    spec = run.read_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in spec["workloads"] if w["name"] == CELL)
    cell = run.Cell(spec, wl, ns, run.CACHE / "trace" / "control-test")
    shrink(cell)
    cell.devices, _ = tiny.cpu_chips(1, {})
    driver = run.load_module(run.BENCH / "drivers" / "serve_bank_mla.py",
                             "bench_driver_serve_bank_mla")
    out = control.readings(run, driver, cell, driver.run(cell), True)
    assert out["correct"] and not out["control_correct"], out


def test_counts_at_published_sizes():
    """This chip's share: 4.70 B weights a token could touch, of which each
    token multiplies through 1.23 B."""
    c = json.loads((ROOT / "bench" / "configs" /
                    "deepseek-v2-lite.json").read_text())
    mla = counts_mla.mla_matmul_params(c)
    assert mla == 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    per_token = counts_mla.token_matmul_flops(c) / 2
    assert per_token == pytest.approx(1.232e9, rel=1e-3)
    one = counts_mla.serve_request_flops(c, 1, 1)
    assert one == pytest.approx(counts_mla.token_matmul_flops(c)
                                + 27 * counts_mla.attn_fwd_flops(c, 1)
                                + 2 * 102400 * 2048)


def _ev(line, name, a, b, op_name=None, plane="/device:TPU:0"):
    e = {"plane": plane, "line": line, "name": name, "start": float(a),
         "end": float(b)}
    if op_name is not None:
        e["op_name"] = op_name
    return e


def test_outer_scopes_count_each_instant_once():
    """Hand-made intervals: an ``mla`` dot inside a scope-less ``while``,
    an ``moe`` dot after it, and a second execution; each instant goes to
    the innermost operation, and only the most-run program's executions
    count."""
    from bench import layer_scopes
    from bench import trace_reduce as tr
    step = "jit(_serve_step)/while/body"
    evs = [_ev("host", "bench.window", 0, 100, plane="/host:CPU"),
           _ev(tr.MODULES_LINE, "jit__serve_step", 10, 40),
           _ev(tr.MODULES_LINE, "jit__serve_step", 50, 80),
           _ev(tr.MODULES_LINE, "jit_other", 85, 95),
           _ev(tr.OPS_LINE, "%while.1 = (", 10, 40, "jit(_serve_step)/while"),
           _ev(tr.OPS_LINE, "%fusion.1 = f32[2]", 10, 30,
               f"{step}/mla/attention/dot_general"),
           _ev(tr.OPS_LINE, "%fusion.2 = f32[2]", 30, 40,
               f"{step}/moe/dot_general"),
           _ev(tr.OPS_LINE, "%scatter.1 = f32[2]", 50, 60,
               "jit(_serve_step)/mla/kv_ring/scatter"),
           _ev(tr.OPS_LINE, "%fusion.3 = f32[2]", 60, 80,
               "jit(_serve_step)/logits/dot_general"),
           _ev(tr.OPS_LINE, "%fusion.4 = f32[2]", 85, 95,
               "jit(other)/mla/dot_general")]
    rec = {"driver": "serve_bank", "events": evs}
    assert layer_scopes.per_step_ms(rec, "mla") == pytest.approx(
        1e3 * 30e-9 / 2)
    assert layer_scopes.per_step_ms(rec, "moe") == pytest.approx(
        1e3 * 10e-9 / 2)
    no_moe = [e for e in evs if "moe" not in e.get("op_name", "")]
    assert layer_scopes.per_step_ms(dict(rec, events=no_moe), "moe") is None
    assert layer_scopes.per_step_ms({"driver": "other"}, "mla") is None
