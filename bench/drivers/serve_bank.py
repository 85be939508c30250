"""Driver ``serve_bank``: personalised serving through ``ServeEngine``, one
batched greedy decode over a bank of per-user tri-LoRA adapters.

Set-up makes the bf16 base on the device in one compiled call and the f32
bank likewise, then keeps the bank on the host, as a bank exported from a
checkpoint arrives; the engine puts its own decode copy on the device.  A
warm-up ``run()`` compiles the engine's step (one shape: slots x one
token).  The window is one ``run()`` over the mix's backlog, a fixed
number of requests (``bench/traffic.py``), more than there are slots, so
freed slots take the next request as they would in service; the engine
admits nothing from outside a call, so the window still ends in one
drain.  The backlog, not ``--seconds``, sets the window's length.  Tokens
are the generated tokens of every request.

Correctness: once the window has closed and the engine is freed, the
float32 reference (``bench.reference.decoder``) runs once over each
sampled request's prompt and served tokens with that user's adapter; the
compared number is the widest gap by which a served token's logit lies
below the reference's best at its position.  A request that is missing,
of the wrong length, or whose prompt came back changed counts as failed.
"""
from __future__ import annotations

import functools
import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, traffic
from bench.reference import decoder, weights


@functools.partial(jax.jit, static_argnums=(0, 4))
def _ref_logits(c_items, base, adapter, tokens, quant):
    return decoder.logits(dict(c_items), base, adapter, tokens, quant=quant)


def reference_gaps(c: dict, mix: dict, seed: int, reqs: list, done: dict,
                   pick: list, quant: bool = False) -> dict:
    """Per sampled request, the reference's gap at each served token; with
    ``quant`` also the gap of the token the float8 control puts first."""
    base = weights.init_base(c, seed)
    bank = weights.init_bank(c, mix["users"], seed + 1,
                             mix["delta_ratio"])
    max_len = mix["max_len"]

    frozen = weights._freeze(c)

    def logits(ad, toks, q):
        return _ref_logits(frozen, base, ad, toks, q)[0]

    gaps, ctrl = {}, {}
    for i in pick:
        user, prompt, gen = reqs[i]
        toks = done[i]
        x = np.zeros((1, max_len), np.int32)
        x[0, :len(toks) - 1] = toks[:-1]
        ad = weights.user_adapter(bank, user)
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
    flops = sum(counts.serve_request_flops(c, len(p), g)
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
