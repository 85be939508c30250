"""Federated training driver (the end-to-end launcher).

Runs CE-LoRA federated fine-tuning of a causal-LM backbone on synthetic
Zipf-Markov data split across simulated clients:

  PYTHONPATH=src python -m repro.launch.train --arch fed-100m \\
      --clients 4 --rounds 10 --local-steps 20 --batch 8 --seq 256

On the CPU container this trains the ~100M `fed-100m` config for a few
hundred total steps (examples/federated_finetune.py wraps exactly this).
For TPU, the same step functions lower against the production mesh
(see launch/dryrun.py).

Like the classification runtime (`repro.core.federated`, DESIGN.md §6),
client dispatch is selectable: ``client_parallelism="vmap"`` (default)
stacks all clients' adapters on a leading client axis and runs ONE batched
local fit per round; ``"loop"`` is the one-dispatch-per-client reference.

Partial participation (DESIGN.md §8): ``--participation``, ``--sampler``
and ``--straggler-frac`` plug the deterministic sampling plan of
:mod:`repro.core.sampling` into the LM driver — unsampled clients keep
their adapters frozen for the round, aggregation renormalizes over the
post-straggler participants, and the reported communication is the exact
per-round uplink/downlink BYTES of the participants' payloads
(:mod:`repro.core.comm`).

Uplink compression (DESIGN.md §10): ``--uplink-codec {bf16,int8,int4}``
quantizes the payload before it crosses the wire (per-tile scales,
stochastic rounding, client-side error feedback —
:mod:`repro.core.compress`); bytes are reported for the ENCODED pytree
and the server aggregates the dequantized payloads.  Works under both
engines; the EF residual is checkpointed and a resume across a codec
change is refused.

Compiled rounds (DESIGN.md §9): ``--engine scan`` fuses local fit, select,
similarity, aggregation, and install into one jitted round step and scans
it over ``--chunk-rounds`` rounds per dispatch, checkpointing the full
stacked adapter state to ``--ckpt`` at every chunk boundary; ``--resume``
restores it, fast-forwards the data streams, and reproduces the
uninterrupted run exactly.

Asynchronous buffered rounds (DESIGN.md §13): ``--engine async`` replaces
the per-round barrier with the FedBuff-style buffered server of
:mod:`repro.core.async_engine` — clients dispatch in plan order, arrive
under the seeded virtual-latency model (``--latency`` /
``--latency-scale`` / ``--latency-sigma``), and every ``--buffer-size``
arrivals the server aggregates with the ``--staleness-decay`` discount.
In the zero-staleness limit (uniform latency, buffer = cohort) it is the
eager driver's history.
"""
from __future__ import annotations

import argparse
import functools
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import check_fingerprint
from repro.checkpoint import metadata as ckpt_metadata
from repro.checkpoint import restore, save
from repro.core import (aggregation, client_batch, comm, compress, sampling,
                        tri_lora)
from repro.core import client_store as client_store_lib
from repro.core.similarity import cka
from repro.data import synthetic
from repro.launch.compile_cache import place_compile_cache
from repro.models import model
from repro.models.config import ModelConfig, get_config
from repro.optim import adamw, apply_updates


def make_local_fit(cfg: ModelConfig, opt):
    """One client's local fit: a ``lax.scan`` of optimizer steps over its
    (steps, B, S) token and label stacks.  The frozen ``base`` is an
    argument and never a closed-over constant, so no compiled program
    carries the weights; vmap it with ``in_axes=(None, 0, 0, 0)`` to fit
    stacked clients against one shared base."""
    def local_fit(base, adapter, toks, labs):
        state = opt.init(adapter)

        def step(carry, b):
            ad, st = carry
            (loss, _), g = jax.value_and_grad(
                lambda a: model.loss_fn(cfg, a, base,
                                        {"tokens": b[0], "labels": b[1]}),
                has_aux=True)(ad)
            upd, st = opt.update(g, st, ad)
            return (apply_updates(ad, upd), st), loss

        (adapter, _), losses = jax.lax.scan(step, (adapter, state),
                                            (toks, labs))
        return adapter, losses

    return local_fit


def run(arch: str | ModelConfig = "fed-100m", clients: int = 4,
        rounds: int = 10, local_steps: int = 20, batch: int = 8, seq: int = 256,
        lr: float = 3e-3, seed: int = 0, method: str = "celora",
        ckpt: str | None = None, verbose: bool = True,
        reduced: bool = False, client_parallelism: str = "vmap",
        participation: float = 1.0, sampler: str = "uniform",
        straggler_frac: float = 0.0, engine: str = "eager",
        chunk_rounds: int = 8, resume: bool = False,
        uplink_codec: str = "none", scan_donate: bool = True,
        scan_prefetch: bool = True, client_store: str = "device",
        buffer_size: int = 0, async_concurrency: int = 0,
        staleness_decay: float = 1.0, latency: str = "uniform",
        latency_scale: float = 1.0, latency_sigma: float = 0.5,
        attn_impl: str | None = None) -> dict:
    """``arch`` names a registered config or is a :class:`ModelConfig`."""
    if client_parallelism not in ("loop", "vmap"):
        raise ValueError(f"client_parallelism={client_parallelism!r}; "
                         f"expected 'loop' or 'vmap'")
    if engine not in ("eager", "scan", "async"):
        raise ValueError(f"engine={engine!r}; "
                         f"expected 'eager', 'scan', or 'async'")
    vectorized = client_parallelism == "vmap"
    if engine in ("scan", "async") and not vectorized:
        raise ValueError(f"engine={engine!r} runs on the stacked client "
                         f"axis; use client_parallelism='vmap'")
    if engine == "async":
        if resume:
            raise ValueError("--resume is not supported by the LM driver's "
                             "async engine (use the classification runtime "
                             "for resumable async runs)")
        if straggler_frac > 0.0:
            raise ValueError("engine='async' replaces the straggler drop "
                             "mask with the latency model; set "
                             "straggler_frac=0")
        if client_store != "device":
            raise ValueError("engine='async' requires client_store='device'")
        sampling.LatencyModel(latency, latency_scale, latency_sigma)
    if client_store not in client_store_lib.STORE_BACKENDS:
        raise ValueError(f"client_store={client_store!r}; expected one of "
                         f"{client_store_lib.STORE_BACKENDS}")
    if client_store != "device" and not vectorized:
        raise ValueError(f"client_store={client_store!r} requires "
                         f"client_parallelism='vmap'")
    if client_store == "host" and engine != "eager":
        raise ValueError("the LM driver's host-backed store runs eager "
                         "rounds only (cohort gather/write-back per round); "
                         "use --engine eager or client_store="
                         "'device'/'sharded'")
    if resume and engine != "scan":
        raise ValueError("--resume requires --engine scan (the eager "
                         "driver does not write resumable state)")
    partial = participation < 1.0 or straggler_frac > 0.0
    sampling.n_sampled(clients, participation)    # validates participation
    if not 0.0 <= straggler_frac < 1.0:
        raise ValueError(f"straggler_frac must be in [0, 1); "
                         f"got {straggler_frac}")
    cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
    arch = cfg.name                     # the checkpoint metadata's name
    if reduced:
        cfg = cfg.reduced()
    if attn_impl is not None:
        # backend rides on cfg (DESIGN.md §14): every downstream loss_fn /
        # forward_hidden call resolves it via attention.select_impl
        from repro.models.attention import IMPLS
        if attn_impl not in IMPLS:
            raise ValueError(f"attn_impl={attn_impl!r}; "
                             f"expected one of {IMPLS}")
        cfg = cfg.with_overrides(attn_impl=attn_impl)
    base = model.init_base(cfg, jax.random.key(seed))

    # per-client Zipf-Markov LM streams with client-specific transition
    # structure (the non-IID-ness federated personalization feeds on)
    streams = [synthetic.make_lm_data(seed + 17 * i, 200_000,
                                      cfg.vocab_size) for i in range(clients)]
    iters = [synthetic.lm_batches(s, batch, seq, seed=seed + i)
             for i, s in enumerate(streams)]

    adapters = [model.init_adapter(cfg, jax.random.key(seed + i))
                for i in range(clients)]
    opt = adamw(lr=lr)

    # uplink compression (repro.core.compress, DESIGN.md §10): encode the
    # payload before pricing bytes, dequantize before aggregation, carry the
    # error-feedback residual per client; inactive for the identity codec
    # and for non-communicating methods
    codec = compress.get_codec(uplink_codec)
    compressed = not codec.is_identity and method in ("celora", "fedavg")
    payload_of = tri_lora.tree_payload if method == "celora" else (lambda t: t)

    _local_fit = make_local_fit(cfg, opt)
    fit = jax.jit(jax.vmap(_local_fit, in_axes=(None, 0, 0, 0))
                  if vectorized else _local_fit)
    stacked = None
    if vectorized and client_store != "host":
        stacked = client_batch.stack_states(adapters)
        if client_store == "sharded":
            # client axis over the device mesh (DESIGN.md §12): the same
            # stacked programs run under GSPMD with each device owning an
            # m/d row block and a replica of the frozen base
            from repro.launch import mesh as mesh_lib
            cmesh = mesh_lib.make_client_mesh(clients)
            stacked = mesh_lib.shard_clients(cmesh, stacked)
            base = jax.device_put(base, jax.sharding.NamedSharding(
                cmesh, jax.sharding.PartitionSpec()))
    local_fit = functools.partial(fit, base)

    def _draw(i):
        bs = [next(iters[i]) for _ in range(local_steps)]
        return (np.stack([b["tokens"] for b in bs]),
                np.stack([b["labels"] for b in bs]))

    # weighted sampling sees the true per-client stream sizes (the
    # synthetic LM streams are equal-sized, so it coincides with uniform
    # here — heterogeneous shards would differentiate it)
    stream_sizes = [len(s) for s in streams]

    # per-round participation plans, deterministic in the seed: both engines
    # (and a killed-then-resumed scan run) see the identical subsets
    plans = [(sampling.build_plan(sampler, clients, participation,
                                  straggler_frac, rnd, seed,
                                  sample_counts=stream_sizes)
              if partial else sampling.full_plan(clients, rnd))
             for rnd in range(rounds)]

    if client_store == "host":
        history, adapters = _run_host_lm(
            local_fit=local_fit, draw=_draw, adapters=adapters, plans=plans,
            method=method, clients=clients, seed=seed, codec=codec,
            compressed=compressed, payload_of=payload_of, verbose=verbose)
        if ckpt:
            save(ckpt, {"adapter_client0": adapters[0]},
                 metadata={"arch": arch, "rounds": rounds, "method": method})
            if verbose:
                print(f"saved adapter checkpoint -> {ckpt}")
        return {"history": history, "adapters": adapters, "cfg": cfg,
                "base": base}

    if engine == "async":
        history, adapters = _run_async_lm(
            local_fit_raw=_local_fit, base=base, draw=_draw, stacked=stacked,
            plans=plans, method=method, clients=clients, rounds=rounds,
            seed=seed, verbose=verbose, codec=codec, compressed=compressed,
            payload_of=payload_of, buffer_size=buffer_size,
            concurrency=async_concurrency, staleness_decay=staleness_decay,
            latency_model=sampling.LatencyModel(latency, latency_scale,
                                                latency_sigma))
        if ckpt:
            save(ckpt, {"adapter_client0": adapters[0]},
                 metadata={"arch": arch, "rounds": rounds, "method": method})
            if verbose:
                print(f"saved adapter checkpoint -> {ckpt}")
        return {"history": history, "adapters": adapters, "cfg": cfg,
                "base": base}

    if engine == "scan":
        history, stacked = _run_scan_lm(
            cfg=cfg, local_fit_raw=_local_fit, base=base, draw=_draw,
            stacked=stacked, plans=plans, method=method, clients=clients,
            rounds=rounds, chunk_rounds=chunk_rounds, seed=seed,
            ckpt=ckpt, resume=resume, verbose=verbose,
            codec=codec, compressed=compressed, payload_of=payload_of,
            donate=scan_donate, prefetch=scan_prefetch,
            client_store=client_store)
        # "stacked" keeps the client axis in its device layout
        return {"history": history,
                "adapters": client_batch.unstack_states(stacked),
                "stacked": stacked, "cfg": cfg, "base": base}

    if compressed:
        ef = (compress.init_ef(payload_of(stacked)) if vectorized
              else [compress.init_ef(payload_of(a)) for a in adapters])
    history = []
    for rnd in range(rounds):
        t0 = time.perf_counter()
        plan = plans[rnd]
        smask = plan.mask(clients, which="sampled")
        cmask = jnp.asarray(plan.mask(clients)) if partial else None
        if vectorized:
            drawn = [_draw(i) for i in range(clients)]  # all: rng parity
            toks = jnp.asarray(np.stack([d[0] for d in drawn]))
            labs = jnp.asarray(np.stack([d[1] for d in drawn]))
            new_stacked, ls = local_fit(stacked, toks, labs)  # ls (m, steps)
            stacked = (client_batch.select_clients(jnp.asarray(smask),
                                                   new_stacked, stacked)
                       if partial else new_stacked)
            losses = [float(l) for l in np.asarray(ls[:, -1])[plan.sampled]]
        else:
            losses = []
            for i in range(clients):
                toks, labs = (jnp.asarray(a) for a in _draw(i))
                if not smask[i]:
                    continue                # unsampled: frozen this round
                adapters[i], ls = local_fit(adapters[i], toks, labs)
                losses.append(float(ls[-1]))

        rc = comm.RoundComm.zero()
        if compressed and vectorized:
            # encode once per round: bytes priced on the ENCODED pytree,
            # the server consumes the dequantized payload, EF advances for
            # delivered uploads only
            payload = payload_of(stacked)
            enc, served, ef_new = compress.encode_stacked(
                codec, payload, ef, compress.client_keys(seed, rnd, clients))
            rc = comm.round_comm_compressed_stacked(enc, payload,
                                                    plan.n_participants)
            ef = (client_batch.select_clients(cmask, ef_new, ef)
                  if partial else ef_new)
        elif compressed:
            payloads = [payload_of(a) for a in adapters]
            encoded = [compress.encode_client(
                codec, payloads[i], ef[i],
                compress.client_key(seed, rnd, i)) for i in range(clients)]
            rc = comm.round_comm_compressed_payloads(
                [encoded[i][0] for i in plan.participants],
                [payloads[i] for i in plan.participants])
            served_list = [e[1] for e in encoded]
            for i in plan.participants:
                ef[i] = encoded[i][2]
        if method == "celora":
            if vectorized:
                if not compressed:
                    served = tri_lora.tree_payload(stacked)
                    rc = comm.round_comm_stacked(served,
                                                 plan.n_participants)
                s_model = cka.pairwise_model_similarity_stacked(
                    served, jax.random.key(seed + 99), 32)
                w = aggregation.personalized_weights(s_model,
                                                     participants=cmask)
                mixed = aggregation.aggregate_stacked(served, w)
                installed = tri_lora.tree_load_payload(stacked, mixed)
                stacked = (client_batch.select_clients(cmask, installed,
                                                       stacked)
                           if partial else installed)
            else:
                if not compressed:
                    served_list = [tri_lora.tree_payload(a) for a in adapters]
                    rc = comm.round_comm_payloads(
                        [served_list[i] for i in plan.participants])
                s_model = cka.pairwise_model_similarity(
                    served_list, jax.random.key(seed + 99), 32)
                w = aggregation.personalized_weights(s_model,
                                                     participants=cmask)
                downs = aggregation.aggregate_payloads(served_list, w)
                for i in plan.participants:
                    adapters[i] = tri_lora.tree_load_payload(adapters[i],
                                                             downs[i])
        elif method == "fedavg":
            if vectorized:
                if not compressed:
                    served = stacked
                    rc = comm.round_comm_stacked(served,
                                                 plan.n_participants)
                g = aggregation.fedavg_stacked(served, [1] * clients, cmask)
                bc = client_batch.broadcast_to_clients(g, clients)
                stacked = (client_batch.select_clients(cmask, bc, stacked)
                           if partial else bc)
            else:
                if not compressed:
                    served_list = [jax.tree.map(lambda x: x, a)
                                   for a in adapters]
                    rc = comm.round_comm_payloads(
                        [served_list[i] for i in plan.participants])
                g = aggregation.fedavg(served_list, [1] * clients, cmask)
                for i in plan.participants:
                    adapters[i] = jax.tree.map(lambda x: x, g)

        rec = {"round": rnd, "loss": float(np.mean(losses)),
               "uplink_floats": rc.uplink_elems,
               "uplink_bytes": rc.uplink_bytes,
               "downlink_bytes": rc.downlink_bytes,
               "participants": plan.participants.tolist(),
               "wall_s": time.perf_counter() - t0}
        history.append(rec)
        if verbose:
            print(f"round {rnd:3d}  loss {rec['loss']:.4f}  "
                  f"uplink {rc.uplink_bytes}B "
                  f"({plan.n_participants}/{clients} clients)  "
                  f"{rec['wall_s']:.1f}s", flush=True)

    if vectorized:
        adapters = client_batch.unstack_states(stacked)
    if ckpt:
        save(ckpt, {"adapter_client0": adapters[0]},
             metadata={"arch": arch, "rounds": rounds, "method": method})
        if verbose:
            print(f"saved adapter checkpoint -> {ckpt}")
    return {"history": history, "adapters": adapters, "cfg": cfg,
            "base": base}


def _run_host_lm(*, local_fit, draw, adapters, plans, method: str,
                 clients: int, seed: int, codec, compressed: bool,
                 payload_of, verbose: bool):
    """Host-backed LM rounds (``--client-store host``): the m adapters live
    in host numpy (:class:`repro.core.client_store.HostClientStore`); each
    round gathers only the sampled cohort to the device, fits, aggregates
    over the cohort, and writes back.  For CE-LoRA a device-resident all-m
    bank of the r×r C payloads (plus its EF residual when compressed)
    backs the full pairwise CKA — the full adapters never stack on device.
    Produces the identical history as the stacked eager driver (equality
    asserted in tests/test_client_store.py)."""
    store = client_store_lib.HostClientStore(adapters)
    bank = ef_bank = None            # celora: all-m C payload (+ EF) bank
    ef_pop = None                    # fedavg compressed: host EF residuals
    if method == "celora":
        bank = jax.tree.map(jnp.asarray, payload_of(store.population))
        if compressed:
            ef_bank = compress.init_ef(bank)
    elif method == "fedavg" and compressed:
        ef_pop = jax.tree.map(lambda l: np.zeros(l.shape, np.float32),
                              payload_of(store.population))

    history = []
    for rnd, plan in enumerate(plans):
        t0 = time.perf_counter()
        drawn = [draw(i) for i in range(clients)]   # all: rng parity
        cids = plan.sampled
        toks = jnp.asarray(np.stack([drawn[i][0] for i in cids]))
        labs = jnp.asarray(np.stack([drawn[i][1] for i in cids]))
        cohort = store.gather(cids)
        cohort, ls = local_fit(cohort, toks, labs)
        losses = [float(l) for l in np.asarray(ls[:, -1])]
        pml = jnp.asarray(plan.cohort_mask())
        pmf = jnp.asarray(plan.mask(clients))
        cdev = jnp.asarray(cids.astype(np.int32))
        payload = payload_of(cohort)
        rc = comm.RoundComm.zero()
        if method == "celora":
            # fresh cohort Cs join the all-m bank before encode/CKA; the
            # bank is re-scattered after install so its rows stay "each
            # client's current C"
            bank = client_batch.scatter_clients(bank, cdev, payload)
            if compressed:
                enc, served_all, ef_all = compress.encode_stacked(
                    codec, bank, ef_bank,
                    compress.client_keys(seed, rnd, clients))
                ef_bank = client_batch.select_clients(pmf, ef_all, ef_bank)
                rc = comm.round_comm_compressed_stacked(
                    enc, bank, plan.n_participants)
            else:
                served_all = bank
                rc = comm.round_comm_stacked(bank, plan.n_participants)
            s_model = cka.pairwise_model_similarity_stacked(
                served_all, jax.random.key(seed + 99), 32)
            w = aggregation.personalized_weights(s_model, participants=pmf)
            # participants ⊆ cohort ⇒ nonzero columns all index cohort rows
            mixed = aggregation.aggregate_stacked(
                client_batch.gather_clients(served_all, cdev),
                w[cdev[:, None], cdev[None, :]])
            cohort = client_batch.select_clients(
                pml, tri_lora.tree_load_payload(cohort, mixed), cohort)
            bank = client_batch.scatter_clients(bank, cdev,
                                                payload_of(cohort))
        elif method == "fedavg":
            if compressed:
                keys = jax.vmap(
                    lambda i: compress.client_key(seed, rnd, i))(cdev)
                ef_c = client_batch.gather_clients(
                    jax.tree.map(jnp.asarray, ef_pop), cdev)
                enc, served, ef_new = compress.encode_stacked(
                    codec, payload, ef_c, keys)
                rc = comm.round_comm_compressed_stacked(
                    enc, payload, plan.n_participants)
                ef_c = client_batch.select_clients(pml, ef_new, ef_c)
                jax.tree.map(
                    lambda l, v: l.__setitem__(cids, np.asarray(v)),
                    ef_pop, ef_c)
            else:
                served = payload
                rc = comm.round_comm_stacked(payload, plan.n_participants)
            g = aggregation.fedavg_stacked(served, jnp.ones(len(cids)), pml)
            cohort = client_batch.select_clients(
                pml, client_batch.broadcast_to_clients(g, len(cids)), cohort)
        store.scatter(cids, cohort)
        rec = {"round": rnd, "loss": float(np.mean(losses)),
               "uplink_floats": rc.uplink_elems,
               "uplink_bytes": rc.uplink_bytes,
               "downlink_bytes": rc.downlink_bytes,
               "participants": plan.participants.tolist(),
               "wall_s": time.perf_counter() - t0}
        history.append(rec)
        if verbose:
            print(f"round {rnd:3d}  loss {rec['loss']:.4f}  "
                  f"uplink {rc.uplink_bytes}B "
                  f"({plan.n_participants}/{clients} clients)  "
                  f"{rec['wall_s']:.1f}s", flush=True)
    return history, store.unstack()


def _run_async_lm(*, local_fit_raw, base, draw, stacked, plans,
                  method: str, clients: int, rounds: int, seed: int,
                  verbose: bool,
                  codec, compressed: bool, payload_of,
                  buffer_size: int, concurrency: int,
                  staleness_decay: float,
                  latency_model: sampling.LatencyModel):
    """Asynchronous buffered LM rounds (``--engine async``, DESIGN.md §13):
    the :class:`repro.core.async_engine.AsyncScheduler` replays seeded
    virtual-time arrivals; dispatched cohorts fit via a gathered vmapped
    program, uploads buffer at the server, and every ``buffer_size``
    arrivals the aggregate is rebuilt with the ``staleness_decay**s``
    column discount.  Zero-staleness limit ≡ the eager driver's history."""
    from repro.core.async_engine import AsyncScheduler

    k = int(plans[0].sampled.size)
    K = int(buffer_size) if buffer_size else k
    if not 1 <= K <= k:
        raise ValueError(f"buffer_size must be in [1, cohort size {k}]; "
                         f"got {K}")
    Mc = int(concurrency) if concurrency else k
    decay = float(staleness_decay)
    if not 0.0 < decay <= 1.0:
        raise ValueError(f"staleness_decay must be in (0, 1]; got {decay}")
    vfit = jax.vmap(local_fit_raw, in_axes=(None, 0, 0, 0))
    has_payload = method in ("celora", "fedavg")
    if has_payload:
        payload_struct = jax.eval_shape(payload_of, stacked)
        per_down_b, _ = comm.per_client_comm(payload_struct)
        per_b, per_e = comm.per_client_comm(
            compress.wire_struct(codec, payload_struct, clients)
            if compressed else payload_struct)
        if not compressed:
            per_down_b = per_b
    else:
        per_b = per_e = per_down_b = 0
    state = {"stacked": stacked,
             "ef": compress.init_ef(payload_of(stacked))
             if compressed else None}

    def _fit(base, stk, ef, ids, waves, toks, labs):
        rows = client_batch.gather_clients(stk, ids)
        new, ls = vfit(base, rows, toks, labs)
        if compressed:
            keys = jax.vmap(lambda w, i: compress.client_key(seed, w, i))(
                waves, ids)
            ef_rows = client_batch.gather_clients(ef, ids)
            _, served, ef_new = compress.encode_stacked(
                codec, payload_of(new), ef_rows, keys)
            ef = client_batch.scatter_clients(ef, ids, ef_new)
        else:
            served = payload_of(new) if has_payload else None
        return client_batch.scatter_clients(stk, ids, new), ef, ls, served

    fit_jit = jax.jit(_fit)

    def _flush(stk, served_K, ids, stale):
        pmask = jnp.zeros((clients,), bool).at[ids].set(True)
        col = None
        if decay != 1.0:
            col = jnp.ones((clients,), jnp.float32).at[ids].set(
                jnp.power(decay, stale.astype(jnp.float32)))
        served_m = client_batch.scatter_clients(payload_of(stk), ids,
                                                served_K)
        if method == "celora":
            s_model = cka.pairwise_model_similarity_stacked(
                served_m, jax.random.key(seed + 99), 32)
            w = aggregation.personalized_weights(s_model, participants=pmask,
                                                 col_scale=col)
            mixed = aggregation.aggregate_stacked(served_m, w)
            stk = client_batch.select_clients(
                pmask, tri_lora.tree_load_payload(stk, mixed), stk)
        else:
            g = aggregation.fedavg_stacked(served_m, jnp.ones(clients),
                                           pmask, col_scale=col)
            stk = client_batch.select_clients(
                pmask, client_batch.broadcast_to_clients(g, clients), stk)
        return stk

    flush_jit = jax.jit(_flush) if has_payload else None

    consumed = np.zeros(clients, np.int64)
    history: list = []
    t_last = [time.perf_counter()]

    def fit_group(records):
        ids, wv, toks, labs = [], [], [], []
        for r in records:
            # lazy draw-and-discard keeps each client's stream position at
            # one session per wave — the eager driver's rng parity
            while consumed[r.client] < r.wave:
                draw(r.client)
                consumed[r.client] += 1
            tk, lb = draw(r.client)
            consumed[r.client] += 1
            ids.append(r.client)
            wv.append(r.wave)
            toks.append(tk)
            labs.append(lb)
        new_stk, new_ef, ls, served = fit_jit(
            base, state["stacked"], state["ef"], jnp.asarray(ids, jnp.int32),
            jnp.asarray(wv, jnp.int32), jnp.asarray(np.stack(toks)),
            jnp.asarray(np.stack(labs)))
        state["stacked"], state["ef"] = new_stk, new_ef
        ls = np.asarray(ls)
        for j, r in enumerate(records):
            r.loss = float(ls[j, -1])
            if served is not None:
                r.upload = jax.tree.map(lambda l, j=j: l[j], served)

    def on_flush(records, f, sim_now):
        ids = np.asarray(sorted(r.client for r in records), np.int32)
        stale = np.asarray([f - r.version for r in records], np.float64)
        if has_payload:
            served_K = jax.tree.map(
                lambda *xs: jnp.stack(xs), *[r.upload for r in records])
            state["stacked"] = flush_jit(
                state["stacked"], served_K,
                jnp.asarray([r.client for r in records], jnp.int32),
                jnp.asarray(stale))
        now = time.perf_counter()
        rec = {"round": f,
               "loss": float(np.mean([r.loss for r in records])),
               "uplink_floats": per_e * K, "uplink_bytes": per_b * K,
               "downlink_bytes": per_down_b * K,
               "participants": [int(i) for i in ids],
               "wall_s": now - t_last[0], "sim_t": float(sim_now),
               "staleness": float(np.mean(stale))}
        t_last[0] = now
        history.append(rec)
        if verbose:
            print(f"flush {f:3d}  t={sim_now:8.2f}  loss {rec['loss']:.4f}"
                  f"  uplink {rec['uplink_bytes']}B  stale "
                  f"{rec['staleness']:.2f}", flush=True)

    sched = AsyncScheduler(
        waves=[np.asarray(p.sampled) for p in plans], m=clients,
        latency=latency_model, seed=seed, buffer_size=K, concurrency=Mc,
        rounds=rounds, fit_group=fit_group, flush_cb=on_flush)
    sched.run()
    return history, client_batch.unstack_states(state["stacked"])


def _run_scan_lm(*, cfg, local_fit_raw, base, draw, stacked, plans,
                 method: str, clients: int, rounds: int, chunk_rounds: int,
                 seed: int,
                 ckpt: str | None, resume: bool, verbose: bool,
                 codec=None, compressed: bool = False, payload_of=None,
                 donate: bool = True, prefetch: bool = True,
                 client_store: str = "device"):
    """Compiled LM rounds: one jitted ``lax.scan`` dispatch per chunk of
    rounds (mirrors :mod:`repro.core.fed_engine` for the classification
    runtime; DESIGN.md §9).  Checkpoints the full stacked adapter state at
    chunk boundaries; ``resume`` restores it, fast-forwards the data
    streams, and continues bit-for-bit.  With an active ``codec`` the
    error-feedback residual joins the scanned carry and the checkpoint, and
    bytes are priced on the encoded pytree (DESIGN.md §10).  ``donate`` and
    ``prefetch`` are the §11 pipeline knobs: the stacked adapter carry is
    donated to the chunk program (old handles deleted — any re-read
    raises), and a background thread draws/stacks the next chunk's batches
    while the current chunk computes."""
    chunk = max(1, int(chunk_rounds))
    vfit = jax.vmap(local_fit_raw, in_axes=(None, 0, 0, 0))
    pstack = sampling.stack_plans(plans, clients)
    codec = codec or compress.get_codec("none")
    payload_of = payload_of or (lambda t: t)
    if method == "celora":
        payload_struct = jax.eval_shape(tri_lora.tree_payload, stacked)
    elif method == "fedavg":
        payload_struct = jax.eval_shape(lambda t: t, stacked)
    else:
        payload_struct = None
    if payload_struct is None:
        per_b, per_e, per_down_b = 0, 0, 0
    elif compressed:
        # uplink priced on the encoded pytree; downlink stays the raw
        # payload (the server broadcasts full-precision aggregates)
        per_b, per_e = comm.per_client_comm(
            compress.wire_struct(codec, payload_struct, clients))
        per_down_b, _ = comm.per_client_comm(payload_struct)
    else:
        per_b, per_e = comm.per_client_comm(payload_struct)
        per_down_b = per_b
    ef = compress.init_ef(payload_of(stacked)) if compressed else {}

    def round_step(base, carry, xs):
        stk, ef = carry
        toks, labs, smask, pmask, rnd = xs
        new, ls = vfit(base, stk, toks, labs)
        stk = client_batch.select_clients(smask, new, stk)
        if compressed:
            _, served, ef_new = compress.encode_stacked(
                codec, payload_of(stk), ef,
                compress.client_keys(seed, rnd, clients))
            ef = client_batch.select_clients(pmask, ef_new, ef)
        else:
            served = payload_of(stk)
        if method == "celora":
            s_model = cka.pairwise_model_similarity_stacked(
                served, jax.random.key(seed + 99), 32)
            w = aggregation.personalized_weights(s_model, participants=pmask)
            mixed = aggregation.aggregate_stacked(served, w)
            stk = client_batch.select_clients(
                pmask, tri_lora.tree_load_payload(stk, mixed), stk)
        elif method == "fedavg":
            g = aggregation.fedavg_stacked(served, jnp.ones(clients), pmask)
            stk = client_batch.select_clients(
                pmask, client_batch.broadcast_to_clients(g, clients), stk)
        sm = smask.astype(ls.dtype)
        loss = jnp.sum(ls[:, -1] * sm) / jnp.maximum(jnp.sum(sm), 1.0)
        return (stk, ef), loss

    def scan_fn(c, xs, base):
        return jax.lax.scan(functools.partial(round_step, base), c, xs)

    # the carry is donated, the frozen base never is
    run_chunk = (jax.jit(scan_fn, donate_argnums=(0,)) if donate
                 else jax.jit(scan_fn))

    hist_loss: list = []
    hist_wall: list = []
    hist_host: list = []
    hist_dev: list = []
    start = 0
    if resume and ckpt and not os.path.exists(ckpt):
        warnings.warn(f"--resume: no checkpoint at {ckpt!r} — starting "
                      f"from round 0 (checkpoints will be written there)")
    if resume and ckpt and os.path.exists(ckpt):
        meta = ckpt_metadata(ckpt)
        if "rounds_done" not in meta:
            raise ValueError(f"{ckpt!r} is not a scan-engine checkpoint "
                             f"(no rounds_done in metadata)")
        # uplink_codec is part of the fingerprint (the stored EF residual
        # is meaningful only under the codec that produced it); so is the
        # store backend, backfilled to "device" for pre-§12 checkpoints
        check_fingerprint(
            ckpt, meta,
            {"arch": cfg.name, "method": method, "clients": clients,
             "seed": seed, "uplink_codec": codec.name,
             "client_store": client_store, "attn_impl": cfg.attn_impl},
            defaults={"uplink_codec": "none", "client_store": "device",
                      "attn_impl": "auto"})  # pre-§14 checkpoints
        start = int(meta["rounds_done"])
        if start > rounds:
            raise ValueError(f"checkpoint has {start} completed rounds but "
                             f"the run asks for only {rounds}")
        tree = restore(ckpt, {"state": stacked, "ef": ef,
                              "loss": np.zeros(start, np.float32),
                              "wall": np.zeros(start, np.float32)})
        stacked, ef = tree["state"], tree["ef"]
        hist_loss = [float(v) for v in tree["loss"]]
        hist_wall = [float(v) for v in tree["wall"]]
        hist_host = [0.0] * start
        hist_dev = [0.0] * start
        for _ in range(start):          # fast-forward the data streams
            for i in range(clients):
                draw(i)
        if verbose:
            print(f"resumed {start} rounds from {ckpt}", flush=True)

    def produce(n_rounds: int):
        drawn = [[draw(i) for i in range(clients)] for _ in range(n_rounds)]
        toks = jnp.asarray(np.stack([np.stack([d[0] for d in rr])
                                     for rr in drawn]))
        labs = jnp.asarray(np.stack([np.stack([d[1] for d in rr])
                                     for rr in drawn]))
        return toks, labs

    def dispatch(carry, batches, c0, c1):
        toks, labs = batches
        xs = (toks, labs,
              jnp.asarray(pstack.sampled_mask[c0:c1]),
              jnp.asarray(pstack.participant_mask[c0:c1]),
              jnp.arange(c0, c1, dtype=jnp.int32))
        carry, losses = run_chunk(carry, xs, base)
        return carry, np.asarray(losses)         # one host sync per chunk

    def on_chunk(carry, c0, c1, losses, host_s, device_s, wall_s):
        hist_loss.extend(float(v) for v in losses)
        hist_wall.extend([wall_s] * (c1 - c0))
        hist_host.extend([host_s] * (c1 - c0))
        hist_dev.extend([device_s] * (c1 - c0))
        if ckpt:
            save(ckpt, {"state": carry[0], "ef": carry[1],
                        "loss": np.asarray(hist_loss, np.float32),
                        "wall": np.asarray(hist_wall, np.float32)},
                 metadata={"rounds_done": c1, "arch": cfg.name,
                           "method": method, "engine": "scan",
                           "clients": clients, "seed": seed,
                           "uplink_codec": codec.name,
                           "client_store": client_store,
                           "attn_impl": cfg.attn_impl})
        if verbose:
            print(f"rounds {c0:3d}–{c1 - 1:3d}  loss "
                  f"{hist_loss[-1]:.4f}  ({wall_s:.1f}s/round)", flush=True)

    carry = client_batch.drive_chunks(
        (stacked, ef),
        [(c0, min(c0 + chunk, rounds))
         for c0 in range(start, rounds, chunk)],
        produce, dispatch, on_chunk, donate=donate, prefetch=prefetch)
    stacked = carry[0]

    history = [{"round": rnd, "loss": hist_loss[rnd],
                "uplink_floats": per_e * plans[rnd].n_participants,
                "uplink_bytes": per_b * plans[rnd].n_participants,
                "downlink_bytes": per_down_b * plans[rnd].n_participants,
                "participants": plans[rnd].participants.tolist(),
                "wall_s": hist_wall[rnd],
                "host_s": hist_host[rnd], "device_s": hist_dev[rnd]}
               for rnd in range(rounds)]
    return history, stacked


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fed-100m")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--method", default="celora",
                    choices=["celora", "fedavg", "local"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--client-parallelism", default="vmap",
                    choices=["loop", "vmap"])
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per round (0, 1]")
    ap.add_argument("--sampler", default="uniform",
                    choices=["uniform", "weighted", "round_robin"])
    ap.add_argument("--straggler-frac", type=float, default=0.0,
                    help="fraction of sampled clients dropped after local fit")
    ap.add_argument("--engine", default="eager",
                    choices=["eager", "scan", "async"],
                    help="scan = compiled multi-round engine (DESIGN.md "
                         "§9); async = buffered staleness-weighted server "
                         "(DESIGN.md §13)")
    ap.add_argument("--chunk-rounds", type=int, default=8,
                    help="scan engine: rounds fused per dispatch")
    ap.add_argument("--resume", action="store_true",
                    help="scan engine: restore --ckpt and continue")
    ap.add_argument("--uplink-codec", default="none",
                    choices=["none", "bf16", "int8", "int4"],
                    help="quantized uplink compression with error feedback "
                         "(repro.core.compress, DESIGN.md §10)")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "ref", "blockwise", "blockwise_cv",
                             "blockwise_hp", "flash"],
                    help="attention backend for client training (DESIGN.md "
                         "§14); default: the arch config's "
                         "ModelConfig.attn_impl")
    ap.add_argument("--no-donate", action="store_true",
                    help="scan engine: disable carry buffer donation "
                         "(DESIGN.md §11)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="scan engine: disable overlapped chunk prefetch "
                         "(DESIGN.md §11)")
    ap.add_argument("--buffer-size", type=int, default=0,
                    help="async engine: aggregate every K arrivals "
                         "(0 = cohort size, the zero-staleness limit)")
    ap.add_argument("--async-concurrency", type=int, default=0,
                    help="async engine: max clients in flight "
                         "(0 = cohort size)")
    ap.add_argument("--staleness-decay", type=float, default=1.0,
                    help="async engine: contribution discount "
                         "decay**staleness (1.0 = none)")
    ap.add_argument("--latency", default="uniform",
                    choices=["uniform", "lognormal", "exp"],
                    help="async engine: virtual client latency model")
    ap.add_argument("--latency-scale", type=float, default=1.0)
    ap.add_argument("--latency-sigma", type=float, default=0.5,
                    help="async engine: lognormal latency sigma")
    ap.add_argument("--client-store", default="device",
                    choices=["device", "sharded", "host"],
                    help="population residency (DESIGN.md §12): device-"
                         "resident stack, client axis sharded over the "
                         "device mesh, or host-resident with per-round "
                         "cohort gather/write-back")
    args = ap.parse_args()
    place_compile_cache()
    out = run(arch=args.arch, clients=args.clients, rounds=args.rounds,
              local_steps=args.local_steps, batch=args.batch, seq=args.seq,
              lr=args.lr, method=args.method, ckpt=args.ckpt,
              reduced=args.reduced,
              client_parallelism=args.client_parallelism,
              participation=args.participation, sampler=args.sampler,
              straggler_frac=args.straggler_frac, engine=args.engine,
              chunk_rounds=args.chunk_rounds, resume=args.resume,
              uplink_codec=args.uplink_codec,
              scan_donate=not args.no_donate,
              scan_prefetch=not args.no_prefetch,
              client_store=args.client_store,
              buffer_size=args.buffer_size,
              async_concurrency=args.async_concurrency,
              staleness_decay=args.staleness_decay, latency=args.latency,
              latency_scale=args.latency_scale, attn_impl=args.attn_impl,
              latency_sigma=args.latency_sigma)
    first, last = out["history"][0]["loss"], out["history"][-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} over {args.rounds} rounds")


if __name__ == "__main__":
    main()
