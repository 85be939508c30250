#!/usr/bin/env python3
"""Chip smoke test: the federated tri-LoRA trainer and the adapter-bank
server, run once on a TPU through their normal entry points, at the
published widths of the paper's LLaMA-7B backbone (``celora-llama-7b``:
d_model 4096, 32 heads of 128, d_ff 11008, vocab 32000) with depth cut to
24 of its 32 layers so that a local fit fits one 16 GB v5e.  Weights are
random, drawn from a seed.

  python chip_smoke.py               # one chip: train, flash, serve phases
  python chip_smoke.py --four-chips  # four chips: sharded client store
                                     # against the device store

Every phase runs in this one process and prints what it did and checked;
the last line of standard output is one JSON object naming the device.
The script fails before any phase when JAX's first device is not a TPU.
The timings and memory figures it prints are smoke figures, not metrics.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LAYERS = 24              # of LLaMA-7B's 32: what one chip holds for a fit
# lr is a LoRA rate for a 7B model.  AdamW's first update moves every entry
# of B by ±lr, so at the trainer's default 3e-3 one step makes ΔW = 2·A·C·B
# about 40% of W's scale and two programs that round differently part
# ways: on four v5e chips the sharded store's round-1 loss was 15.23 where
# the one-chip store's was 13.26.  At 1e-4 ΔW stays near 1% of W.
TRAIN = dict(engine="scan", method="celora", clients=4, rounds=2,
             chunk_rounds=2, local_steps=2, batch=1, seq=256, seed=0,
             lr=1e-4)
# Flash and the reference attention take the same bf16 q/k/v to f32 but
# round at different points: the reference feeds its f32 probabilities to
# the MXU at default precision, and both round their outputs to bf16, whose
# relative step is 2**-8 ≈ 3.9e-3.  Through 24 layers (same weights,
# zero-delta adapter) one forward pass on a v5e gave logits 1.647e-2 apart
# in norm and losses 7.974e-5 apart; the bounds leave about 3x and 12x.
FWD_LOGITS_RTOL = 5e-2
FWD_LOSS_RTOL = 1e-3
# A round's loss follows an AdamW step, whose first update is the sign of
# each gradient entry times lr: entries within rounding noise of zero take
# different signs under two programs that round differently, which moves
# the round loss further apart than one forward pass.  The bound is one
# bf16 step of the loss: more than that is not rounding.  On a v5e at lr
# 1e-4 flash and the reference attention were 1.334e-4 apart.
ROUND_RTOL = 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def smoke_config():
    from repro.models.config import get_config
    return get_config("celora-llama-7b").with_overrides(
        n_layers=LAYERS, name=f"celora-llama-7b-{LAYERS}l")


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def c_payload_bytes(cfg) -> tuple[int, int]:
    """(bytes comm prices for one client's uplink, bytes of 24 layers × the
    adapted targets × one f32 r×r C)."""
    import jax
    from repro.core import comm, tri_lora
    from repro.models import model
    ad = jax.eval_shape(lambda: model.init_adapter(cfg, jax.random.key(0)))
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), ad)
    per_b, _ = comm.per_client_comm(
        jax.eval_shape(tri_lora.tree_payload, stacked))
    return per_b, LAYERS * len(cfg.lora_targets) * cfg.lora_rank ** 2 * 4


def check_uplink(cfg, history, clients: int) -> None:
    per_b, expect = c_payload_bytes(cfg)
    check(per_b == expect,
          f"comm prices {per_b} B per client = {LAYERS} layers x "
          f"{len(cfg.lora_targets)} targets x r^2={cfg.lora_rank ** 2} f32")
    got = [r["uplink_bytes"] for r in history]
    check(all(b == clients * per_b for b in got),
          f"uplink bytes per round {got} = {clients} clients x {per_b} B")


def train_phase(cfg, device) -> list:
    from repro.launch import train
    print(f"[train] run(arch={cfg.name}, {TRAIN})", flush=True)
    t0 = time.perf_counter()
    out = train.run(cfg, **TRAIN)
    cold_s = time.perf_counter() - t0
    losses = [r["loss"] for r in out["history"]]
    print(f"  round losses {losses}; call took {cold_s:.1f} s "
          f"(init, compile and {TRAIN['rounds']} rounds)", flush=True)
    check(len(losses) == TRAIN["rounds"]
          and all(math.isfinite(v) for v in losses),
          "every round loss is finite")
    check_uplink(cfg, out["history"], TRAIN["clients"])
    del out
    # the same call again finds its programs in the compile cache, so its
    # round walls hold no compilation (loading the cached program aside)
    out = train.run(cfg, **TRAIN, verbose=False)
    again = [r["loss"] for r in out["history"]]
    check(again == losses, "a second identical run repeats the losses")
    walls = [r["wall_s"] for r in out["history"]]
    print(f"  smoke figures: round wall on a compile-cache hit {walls} s, "
          f"device memory {memory(device)}", flush=True)
    return losses


def flash_phase(cfg, device, ref_losses: list):
    from repro.launch import train
    kw = dict(TRAIN, rounds=1)
    print(f"[flash] run(arch={cfg.name}, attn_impl='flash', {kw})",
          flush=True)
    out = train.run(cfg, attn_impl="flash", **kw)
    loss = out["history"][0]["loss"]
    rel = abs(loss - ref_losses[0]) / abs(ref_losses[0])
    print(f"  round-0 loss {loss} vs reference attention {ref_losses[0]} "
          f"(relative gap {rel:.3e})", flush=True)
    check(math.isfinite(loss) and rel <= ROUND_RTOL,
          f"flash round loss agrees with the reference within {ROUND_RTOL} "
          f"relative")
    check_forward_agrees(cfg, out["base"])
    check_kernel_compiled(out)
    print(f"  smoke figures: device memory {memory(device)}", flush=True)
    return out["base"]


def check_forward_agrees(cfg, base) -> None:
    """One forward pass over every client's first batch, same weights and
    a zero-delta adapter, with reference and flash attention."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import synthetic
    from repro.models import model

    @functools.partial(jax.jit, static_argnums=0)
    def logits_and_loss(c, base, adapter, toks, labs):
        logits, _ = model.forward(c, base, adapter, {"tokens": toks})
        logp = jax.nn.log_softmax(logits, axis=-1)
        return logits, -jnp.mean(jnp.take_along_axis(
            logp, labs[..., None], axis=-1))

    batches = [next(synthetic.lm_batches(
        synthetic.make_lm_data(TRAIN["seed"] + 17 * i, 200_000,
                               cfg.vocab_size),
        TRAIN["batch"], TRAIN["seq"], seed=TRAIN["seed"] + i))
        for i in range(TRAIN["clients"])]
    toks, labs = (jnp.asarray(np.concatenate([b[k] for b in batches]))
                  for k in ("tokens", "labels"))
    adapter = model.init_adapter(cfg, jax.random.key(TRAIN["seed"]))
    ref, ref_loss = logits_and_loss(cfg, base, adapter, toks, labs)
    fl, fl_loss = logits_and_loss(cfg.with_overrides(attn_impl="flash"),
                                  base, adapter, toks, labs)
    err = float(jnp.linalg.norm(fl - ref) / jnp.linalg.norm(ref))
    gap = abs(float(fl_loss) - float(ref_loss)) / abs(float(ref_loss))
    print(f"  forward: loss {float(fl_loss)} flash vs {float(ref_loss)} "
          f"reference (relative gap {gap:.3e}); logits differ by {err:.3e} "
          f"of their norm", flush=True)
    check(err <= FWD_LOGITS_RTOL and gap <= FWD_LOSS_RTOL,
          f"one forward pass agrees: logits within {FWD_LOGITS_RTOL}, loss "
          f"within {FWD_LOSS_RTOL} relative")


def check_kernel_compiled(out) -> None:
    """Compile the trainer's vmapped local fit for the flash run's config
    and find the Mosaic kernel in it: flash ran compiled, not interpreted."""
    import jax
    import jax.numpy as jnp
    from repro.core import client_batch
    from repro.launch import train
    from repro.optim import adamw
    fit = jax.vmap(train.make_local_fit(out["cfg"], adamw(lr=TRAIN["lr"])),
                   in_axes=(None, 0, 0, 0))
    toks = jax.ShapeDtypeStruct(
        (TRAIN["clients"], TRAIN["local_steps"], TRAIN["batch"],
         TRAIN["seq"]), jnp.int32)
    text = jax.jit(fit).lower(
        out["base"], client_batch.stack_states(out["adapters"]), toks,
        toks).compile().as_text()
    check("tpu_custom_call" in text,
          "the compiled local fit holds the flash kernel (tpu_custom_call)")


def serve_phase(cfg, base, device) -> None:
    import jax
    import numpy as np
    from repro.core.adapter_bank import random_bank
    from repro.launch.serve import ServeEngine, make_requests
    prompt_len, gen, n = 32, 16, 8
    print(f"[serve] ServeEngine(slots=4, max_len=48) over random_bank(4), "
          f"{n} requests of {prompt_len}+{gen} tokens", flush=True)
    bank = random_bank(cfg, 4, jax.random.key(1))
    eng = ServeEngine(cfg, base, bank, slots=4, max_len=prompt_len + gen)
    reqs = make_requests(bank, n, prompt_len=prompt_len, gen=gen,
                         vocab=cfg.vocab_size, seed=0)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    dt = time.perf_counter() - t0
    check(sorted(done) == [r.rid for r in reqs], f"all {n} requests return")
    check(all(len(v) == prompt_len + gen for v in done.values()),
          f"every request returns {prompt_len + gen} tokens")
    toks = np.concatenate(list(done.values()))
    check(bool((toks >= 0).all() and (toks < cfg.vocab_size).all()),
          f"every token is in [0, {cfg.vocab_size})")
    print(f"  smoke figures: {dt:.2f} s for {n * gen} new tokens "
          f"(compile included), device memory {memory(device)}", flush=True)


def four_chip_phase(cfg, devices) -> None:
    import jax
    import numpy as np
    from repro.launch import train
    check(len(devices) == 4, f"{len(devices)} devices visible, 4 needed")
    runs = {}
    for store in ("sharded", "device"):
        print(f"[four-chips] run(arch={cfg.name}, client_store={store!r}, "
              f"{TRAIN})", flush=True)
        out = train.run(cfg, client_store=store, **TRAIN)
        runs[store] = [(r["loss"], r["uplink_bytes"]) for r in out["history"]]
        if store == "sharded":
            per_dev = adapter_bytes_per_device(out["stacked"])
            total = sum(l.nbytes for l in jax.tree.leaves(out["stacked"]))
            print(f"  stacked adapter bytes per device {per_dev} of "
                  f"{total} in all", flush=True)
            check(sorted(per_dev) == [0, 1, 2, 3]
                  and all(b * 4 == total for b in per_dev.values()),
                  "each device holds a quarter of the stacked adapters")
        mem = [memory(d) for d in devices]
        print(f"  smoke figures: per-device memory {mem}", flush=True)
        del out
    sh = np.asarray([v[0] for v in runs["sharded"]])
    dv = np.asarray([v[0] for v in runs["device"]])
    check(bool(np.all(np.isfinite(sh))), "sharded round losses are finite")
    print(f"  relative loss gaps per round "
          f"{(np.abs(sh - dv) / np.abs(dv)).tolist()}", flush=True)
    check(bool(np.allclose(sh, dv, rtol=ROUND_RTOL, atol=0.0)),
          f"sharded losses {sh.tolist()} match one-chip {dv.tolist()} "
          f"within {ROUND_RTOL} relative")
    check([v[1] for v in runs["sharded"]] == [v[1] for v in runs["device"]],
          "uplink bytes are identical")
    check_uplink(cfg, [{"uplink_bytes": v[1]} for v in runs["sharded"]],
                 TRAIN["clients"])


def adapter_bytes_per_device(stacked) -> dict:
    import jax
    per: dict = {}
    for leaf in jax.tree.leaves(stacked):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return per


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded client-store phase on four "
                         "chips, against the one-chip device store")
    args = ap.parse_args()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is on platform "
              f"{dev.platform!r}; this smoke test needs a TPU",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import place_compile_cache
    print(f"device {dev.device_kind} x{len(devices)}; compile cache "
          f"{place_compile_cache()}", flush=True)
    cfg = smoke_config()
    if args.four_chips:
        four_chip_phase(cfg, devices)
    else:
        losses = train_phase(cfg, dev)
        base = flash_phase(cfg, dev, losses)
        serve_phase(cfg, base, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
