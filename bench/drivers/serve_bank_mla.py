"""Driver ``serve_bank_mla``: ``serve_bank``'s personalised serving through
``ServeEngine`` for a DeepSeek-V2 configuration (latent attention, a held
share of the experts), checked against that architecture's own float32
reference (``bench.reference.deepseek_v2``).

The window, the backlog, the warm-up and the ``served_logit_gap`` check
are ``serve_bank``'s: set-up makes the bf16 base on the device in one
compiled call and the f32 bank likewise, keeps the bank on the host, warms
the engine's one step shape, then times one ``run()`` of the mix's backlog;
afterwards the reference runs once over each sampled request's prompt and
served tokens with that user's adapter, and the compared number is the
widest gap by which a served token's logit lies below the reference's best
at its position.  FLOPs come from ``bench.counts_mla``.  The record has
``serve_bank``'s keys and names ``serve_bank`` as its driver, as it times
the same engine the same way, so the serving readers read it unchanged.
"""
from __future__ import annotations

import functools
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts_mla, traffic
from bench.reference import deepseek_v2, deepseek_v2_weights as weights


@functools.partial(jax.jit, static_argnums=(0, 4))
def _ref_logits(c_items, base, adapter, tokens, quant):
    return deepseek_v2.logits(dict(c_items), base, adapter, tokens,
                              quant=quant)


def check_program(cfg, c: dict) -> None:
    """The sizes ``Cell.model_config`` cannot state, program against file."""
    want = {"kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_dim": c["qk_nope_head_dim"],
            "qk_rope_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "n_experts": c["published"]["n_routed_experts"],
            "n_held": c["n_routed_experts"],
            "top_k": c["num_experts_per_tok"],
            "expert_d_ff": c["moe_intermediate_size"],
            "shared_d_ff": c["n_shared_experts"] * c["moe_intermediate_size"],
            "norm_topk": c["norm_topk_prob"],
            "n_dense_layers": c["first_k_dense_replace"],
            "tie_embeddings": c["tie_word_embeddings"],
            "yarn_factor": c["rope_scaling"]["factor"],
            "yarn_original_max":
                c["rope_scaling"]["original_max_position_embeddings"],
            "yarn_beta_fast": c["rope_scaling"]["beta_fast"],
            "yarn_beta_slow": c["rope_scaling"]["beta_slow"],
            "yarn_mscale": c["rope_scaling"]["mscale"],
            "yarn_mscale_all_dim": c["rope_scaling"]["mscale_all_dim"]}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad or c["routed_scaling_factor"] != 1:
        raise ValueError(f"program config {cfg.name} disagrees with "
                         f"{c['name']}.json: {bad}")


def reference_gaps(c: dict, mix: dict, seed: int, reqs: list, done: dict,
                   pick: list, quant: bool = False) -> dict:
    """Per sampled request, the reference's gap at each served token; with
    ``quant`` also the gap of the token the float8 control puts first."""
    base = weights.init_base(c, seed)
    bank = weights.init_bank(c, mix["users"], seed + 1, mix["delta_ratio"])
    max_len = mix["max_len"]
    frozen = weights.freeze(c)

    def logits(ad, toks, q):
        return _ref_logits(frozen, base, ad, toks, q)[0]

    gaps, ctrl = {}, {}
    for i in pick:
        user, prompt, gen = reqs[i]
        toks = done[i]
        x = np.zeros((1, max_len), np.int32)
        x[0, :len(toks) - 1] = toks[:-1]
        ad = jax.tree.map(lambda a: a[user], bank)
        at = slice(len(prompt) - 1, len(toks) - 1)
        ref = np.asarray(logits(ad, jnp.asarray(x), False))[at]
        best = ref.max(-1)
        gaps[i] = best - ref[np.arange(gen), toks[len(prompt):]]
        if quant:
            low = np.asarray(logits(ad, jnp.asarray(x), True))[at]
            ctrl[i] = best - ref[np.arange(gen), low.argmax(-1)]
    del base, bank
    return {"gaps": gaps, "control": ctrl}


def run(cell) -> dict:
    from repro.core.adapter_bank import AdapterBank
    from repro.launch.serve import Request, ServeEngine
    mix, c = cell.mix, cell.config
    cfg = cell.model_config()
    check_program(cfg, c)
    base = weights.init_base(c, cell.seed)
    bank = jax.tree.map(np.asarray,
                        weights.init_bank(c, mix["users"], cell.seed + 1,
                                          mix["delta_ratio"]))
    bank = AdapterBank(tree=bank, n_clients=mix["users"],
                       rank=c["lora_rank"],
                       users={f"user-{u}": u for u in range(mix["users"])})
    eng = ServeEngine(cfg, base, bank, slots=mix["slots"],
                      max_len=mix["max_len"])
    reqs = traffic.requests(mix, cell.seed, c["vocab_size"])
    todo = [Request(rid=i, user_id=f"user-{u}", prompt=p, gen=g)
            for i, (u, p, g) in enumerate(reqs)]
    warm = [Request(rid=i, user_id=f"user-{i % mix['users']}",
                    prompt=np.arange(1, 3, dtype=np.int32), gen=2)
            for i in range(mix["slots"])]
    eng.run(warm)
    cell.begin_window()
    t0 = time.perf_counter()
    done = eng.run(todo)
    t1 = time.perf_counter()
    cell.end_window()
    memory = cell.memory_peak()
    del eng, base, bank
    gc.collect()

    failed = [r.rid for r in todo
              if r.rid not in done
              or len(done[r.rid]) != len(r.prompt) + r.gen
              or not np.array_equal(done[r.rid][:len(r.prompt)], r.prompt)]
    generated = sum(r.gen for r in todo if r.rid not in failed)
    pick = [i for i in traffic.check_sample(reqs, cell.seed,
                                            mix["check_tokens"])
            if i not in failed]
    t_ref = time.perf_counter()
    ref = reference_gaps(c, mix, cell.seed, reqs, done, pick)
    print(f"window {t1 - t0:.2f} s for {len(todo)} requests; reference "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr, flush=True)
    cell.compared = {"reqs": reqs, "done": done, "pick": pick,
                     "gaps": ref["gaps"]}
    widest = max(float(g.max()) for g in ref["gaps"].values())
    print(f"checked {len(pick)} requests, "
          f"{sum(reqs[i][2] for i in pick)} served tokens; widest gap "
          f"{widest}", file=sys.stderr, flush=True)
    flops = sum(counts_mla.serve_request_flops(c, len(p), g)
                for _, p, g in reqs)
    return {
        "attempted": len(todo),
        "failed": len(failed),
        "metrics": {"serve_tokens_per_s": generated / (t1 - t0),
                    "setup_s": t0 - cell.t_start},
        "record": {"driver": "serve_bank", "window_s": t1 - t0,
                   "flops": flops, "tokens": generated},
        "checks": [{"name": "served_logit_gap", "value": widest,
                    "limit": cell.limits.get("served_logit_gap", 0.0)}],
        "memory_peak_bytes": memory,
    }
