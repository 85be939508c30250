"""Multi-tenant personalized serving driver (DESIGN.md §15).

  PYTHONPATH=src python -m repro.launch.serve --arch fed-100m --reduced \\
      --batch 4 --prompt-len 32 --gen 16            # single-adapter path
  PYTHONPATH=src python -m repro.launch.serve --arch fed-100m --reduced \\
      --users 8 --requests 16 --slots 4             # request-stream path

Two inference modes for paper eqn (10)'s per-client adapters:

* :func:`generate` — the original single-adapter batched decode (adapters
  stay factored; every row shares one adapter tree).
* :class:`ServeEngine` — the multi-tenant path: a seeded stream of requests
  from DISTINCT users is decoded in one continuously-batched loop, each
  batch slot applying its own tri-LoRA row from an
  :class:`~repro.core.adapter_bank.AdapterBank` (grouped heterogeneous
  decode).  An admitted request's prompt goes into its slot's ring in
  chunks of :data:`PREFILL_CHUNK` tokens by the prefill program, and its
  decode starts at the prompt's last token.  Finished requests free their
  slot for the next arrival; slot reuse is safe because a reused slot
  restarts at position 0 and the ring validity mask (``slot <= idx``)
  hides every stale KV entry.
* :func:`serve_naive` — the baseline the benchmark beats: per user, merge
  that user's adapter into the base weights (eqn. 10) and decode batch-1,
  sequentially.

``ServeEngine.run`` writes host spans into the profiler's trace whenever
one is being recorded (``jax.profiler.trace(dir)`` around the call):
``serve.run``, ``serve.ring_init``, and per loop iteration a
``serve.step`` holding ``serve.admit``, ``serve.prefill``,
``serve.dispatch``, ``serve.sync`` and ``serve.bookkeep``.  ``serve.run``
carries ``ServeEngine.stats``, the slot count and the per-layer KV ring
shape as metadata.  The decode step's operations carry the named scopes
``kv_ring``, ``tri_lora``, ``attention`` and ``logits`` in their
``op_name`` metadata; an MLA model's attention sub-blocks run under
``mla`` and its expert layers under ``moe``.  The prefill program runs
under ``prefill``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adapter_bank import AdapterBank
from repro.launch.compile_cache import place_compile_cache
from repro.models import model, transformer
from repro.models.config import get_config


# the weights are arguments of every compiled step, never constants in it;
# the cache is donated, so a step updates its rings in place
_decode_step = jax.jit(model.decode_step, static_argnums=0, donate_argnums=3)


def generate(cfg, params, prompts: jnp.ndarray, gen: int,
             greedy: bool = True, seed: int = 0):
    """prompts: (B, P) int32.  Returns (B, P+gen) tokens."""
    b, p = prompts.shape
    cache = model.init_decode_cache(cfg, b, p + gen)

    toks = [prompts[:, i:i + 1] for i in range(p)]
    out = list(toks)
    key = jax.random.key(seed)
    logits = None
    for t in range(p + gen - 1):
        cur = out[t]
        pos = (jnp.full((b, 1, 3), t, jnp.int32) if cfg.pos_type == "mrope"
               else jnp.full((b, 1), t, jnp.int32))
        logits, cache = _decode_step(cfg, params["base"], params["adapter"],
                                     cache, {"token": cur, "positions": pos})
        if t >= p - 1:
            if greedy:
                nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, -1])[:, None]
            if t + 1 >= len(out):
                out.append(nxt.astype(jnp.int32))
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# request stream
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    user_id: str
    prompt: np.ndarray           # (P,) int32
    gen: int


def make_requests(bank: AdapterBank, n: int, *, prompt_len: int, gen: int,
                  vocab: int, seed: int = 0) -> List[Request]:
    """Seeded arrival order: each request draws a user from the bank and a
    random prompt — the stream every driver/benchmark/test replays."""
    rng = np.random.default_rng(seed)
    users = sorted(bank.users)
    return [Request(rid=i, user_id=users[int(rng.integers(len(users)))],
                    prompt=rng.integers(0, vocab, (prompt_len,)).astype(
                        np.int32),
                    gen=gen)
            for i in range(n)]


# ---------------------------------------------------------------------------
# batched heterogeneous engine
# ---------------------------------------------------------------------------

def _with_positions(cache: dict, pos: jnp.ndarray) -> dict:
    """Install host-managed per-slot positions into every cache ``idx`` leaf
    — (q, B) for scanned layer groups, (B,) for tail blocks."""
    flat, treedef = jax.tree.flatten_with_path(cache)
    leaves = []
    for path, leaf in flat:
        last = str(getattr(path[-1], "key", getattr(path[-1], "idx",
                                                    path[-1])))
        if last == "idx":
            top = str(getattr(path[0], "key", getattr(path[0], "idx",
                                                      path[0])))
            if top == "groups":
                leaf = jnp.broadcast_to(pos, (np.shape(leaf)[0],)
                                        + pos.shape)
            else:
                leaf = pos
        leaves.append(leaf)
    return jax.tree.unflatten(treedef, leaves)


#: Prompt tokens a prefill call takes.  A call reads every weight once, as
#: a decode step does, and 128 tokens is about the largest chunk whose
#: FLOPs stay under that read on a v5e: at Danube's 3.96 B parameters
#: 2 x 3.96e9 x 128 is about 1.0 TFLOP, 5 ms at 197 TFLOP/s, against 9.4 ms
#: for its 7.7 GB of weights at 819 GB/s.  So a call costs about one weight
#: read, where feeding the same tokens one a decode step cost 128 slot-steps.
PREFILL_CHUNK = 128


@functools.partial(jax.jit, static_argnums=0, donate_argnums=3)
def _prefill(cfg, base, bank_dec, cache, tok, start, n, slot, row):
    """One slot's prompt chunk into the ring, in place: ``tok`` (1, C)
    holds ``n`` real tokens for positions ``start + [0, n)`` of ring row
    ``slot``, each through bank row ``row``; padding writes nothing.  The
    cache is donated; nothing is read back (no logits)."""
    with jax.named_scope("prefill"):
        i = jnp.arange(tok.shape[1], dtype=jnp.int32)
        pos = jnp.where(i < n, start + i, -1)[None]
        positions = (jnp.broadcast_to(pos[..., None], pos.shape + (3,))
                     if cfg.pos_type == "mrope" else pos)
        # the call's one bank row, cut out before the layer scan: handed the
        # whole bank, XLA re-lays all of it every call
        one = jax.tree_util.tree_map_with_path(
            lambda path, a: jax.lax.dynamic_slice_in_dim(
                a, row, 1, axis=1 if path[0].key == "groups" else 0),
            bank_dec)
        _, cache = model.decode_step(
            cfg, base, one, cache, {"token": tok, "positions": positions},
            adapter_rows=jnp.zeros((1,), jnp.int32), ring_row=slot)
    return cache


@functools.partial(jax.jit, static_argnums=0, donate_argnums=3)
def _serve_step(cfg, base, bank_dec, cache, tok, pos, rows):
    """One continuous-batching decode step; greedy next token per slot.
    The cache is donated: its rings are updated in place."""
    cache = _with_positions(cache, pos)
    positions = (jnp.broadcast_to(pos[:, None, None], (pos.shape[0], 1, 3))
                 if cfg.pos_type == "mrope" else pos[:, None])
    logits, cache = model.decode_step(
        cfg, base, bank_dec, cache, {"token": tok, "positions": positions},
        adapter_rows=rows)
    with jax.named_scope("logits"):
        nxt = jnp.argmax(logits[:, -1], axis=-1)
    return nxt, cache


@dataclasses.dataclass
class ServeStats:
    """What one ``ServeEngine.run`` did, counted in its slot loop.  Each
    slot of each step is exactly one of prefill (fed a prompt token whose
    output is discarded), emit (its output token was emitted) or empty;
    prompts go through prefill calls, so no slot-step is prefill.
    ``prefill_calls`` counts the prefill program's calls and
    ``prefill_tokens`` the prompt tokens they wrote, padding excluded."""
    steps: int = 0
    slot_steps_prefill: int = 0
    slot_steps_emit: int = 0
    slot_steps_empty: int = 0
    admitted: int = 0
    finished: int = 0
    prefill_calls: int = 0
    prefill_tokens: int = 0


def _ring_shapes(cache: dict) -> list:
    """The per-layer ring shapes of a decode cache, ``(B, K, W, hd)`` for
    K/V rings and ``(B, W, lanes)`` for MLA's latent ring: scanned groups
    drop their leading layer axis."""
    out = set()
    for path, leaf in jax.tree.flatten_with_path(cache)[0]:
        if getattr(path[-1], "key", None) in transformer.RING_NAMES:
            out.add(leaf.shape[1:] if path[0].key == "groups"
                    else leaf.shape)
    return sorted(out)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init_cache(cfg, slots: int, max_len: int) -> dict:
    """The engine's decode cache, every ``idx`` already per slot (all -1,
    no slot live): the shapes every step and prefill call is handed."""
    return _with_positions(model.init_decode_cache(cfg, slots, max_len),
                           jnp.full((slots,), -1, jnp.int32))


class ServeEngine:
    """Continuous-batching decode over a stacked adapter bank.

    ``slots`` concurrent sequences share one jitted decode program; every
    step each slot applies its own bank row (grouped tri-LoRA) and advances
    its own ring position (ragged ``idx``).  Idle slots carry row/pos -1 —
    the masked-slot sentinel of the grouped kernels.  Greedy decode only:
    the point is bit-replayable equivalence to the per-user oracle.

    ``stats`` holds the counters of the last ``run()`` (reset at each).

    The step's named scopes are metadata, which JAX's persistent cache
    leaves out of its key unless told otherwise; a step loaded from the
    cache would then carry the scopes of whichever build compiled it first
    into every profile.  So constructing an engine makes the process's
    cache key on metadata.
    """

    def __init__(self, cfg, base: dict, bank: AdapterBank, *, slots: int = 8,
                 max_len: int = 128):
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        self.cfg, self.base, self.bank = cfg, base, bank
        self.slots, self.max_len = slots, max_len
        self._bank_dec = bank.decode_tree()
        rings = _ring_shapes(jax.eval_shape(
            lambda: model.init_decode_cache(cfg, slots, max_len)))
        self._ring = ";".join("x".join(map(str, r)) for r in rings)
        self._ring_len = min(r[-2] for r in rings)
        self.stats = ServeStats()

    def run(self, requests: Sequence[Request],
            progress: bool = False) -> Dict[int, np.ndarray]:
        """Drain the request stream; returns {rid: (P+gen,) tokens}."""
        for r in requests:
            need = len(r.prompt) + r.gen
            if need > self.max_len:
                raise ValueError(f"request {r.rid} needs {need} positions "
                                 f"> max_len={self.max_len}")
            if len(r.prompt) - 1 > self._ring_len:   # prefill never wraps
                raise ValueError(f"request {r.rid}'s prompt would wrap a "
                                 f"ring of {self._ring_len} positions")
        st = self.stats = ServeStats()
        with jax.profiler.TraceAnnotation("serve.run") as span:
            done = self._drain(list(requests), st, progress)
            span.set_metadata(slots=self.slots, kv_ring=self._ring,
                              **dataclasses.asdict(st))
        return done

    def _drain(self, queue: List[Request], st: ServeStats,
               progress: bool) -> Dict[int, np.ndarray]:
        n_req = len(queue)
        with jax.profiler.TraceAnnotation("serve.ring_init"):
            cache = _init_cache(self.cfg, self.slots, self.max_len)
        active: List[Optional[Request]] = [None] * self.slots
        emitted: Dict[int, List[int]] = {}
        pos = np.full((self.slots,), -1, np.int32)
        rows = np.full((self.slots,), -1, np.int32)
        tok = np.zeros((self.slots,), np.int32)
        done: Dict[int, np.ndarray] = {}

        while queue or any(a is not None for a in active):
            with jax.profiler.StepTraceAnnotation("serve.step",
                                                  step_num=st.steps):
                with jax.profiler.TraceAnnotation("serve.admit"):
                    new = []
                    for s in range(self.slots):   # arrivals into free slots
                        if active[s] is None and queue:
                            r = queue.pop(0)
                            active[s] = r
                            emitted[r.rid] = list(r.prompt)
                            rows[s] = self.bank.lookup(r.user_id)
                            # slot REUSE: the ring restarts at 0; the mask
                            # (slot <= position) hides stale KV
                            pos[s] = len(r.prompt) - 1
                            tok[s] = int(r.prompt[-1])
                            st.admitted += 1
                            new.append(s)
                with jax.profiler.TraceAnnotation("serve.prefill"):
                    for s in new:
                        cache = self._write_prompt(
                            cache, active[s].prompt[:-1], s, rows[s], st)
                with jax.profiler.TraceAnnotation("serve.dispatch"):
                    nxt, cache = _serve_step(
                        self.cfg, self.base, self._bank_dec, cache,
                        jnp.asarray(tok[:, None]), jnp.asarray(pos),
                        jnp.asarray(rows))
                with jax.profiler.TraceAnnotation("serve.sync"):
                    nxt = np.asarray(nxt)
                with jax.profiler.TraceAnnotation("serve.bookkeep"):
                    self._bookkeep(nxt, active, emitted, pos, rows, tok,
                                   done, st, progress, n_req)
            st.steps += 1
        return done

    def _write_prompt(self, cache, prompt: np.ndarray, slot: int, row: int,
                      st: ServeStats):
        """Prefill calls that write ``prompt`` into ring row ``slot`` from
        position 0, :data:`PREFILL_CHUNK` tokens a call; dispatched, never
        waited for."""
        c = PREFILL_CHUNK
        for start in range(0, len(prompt), c):
            part = prompt[start:start + c]
            tok = np.zeros((1, c), np.int32)
            tok[0, :len(part)] = part
            cache = _prefill(self.cfg, self.base, self._bank_dec, cache, tok,
                             np.int32(start), np.int32(len(part)),
                             np.int32(slot), np.int32(row))
            st.prefill_calls += 1
            st.prefill_tokens += len(part)
        return cache

    def _bookkeep(self, nxt, active, emitted, pos, rows, tok, done,
                  st: ServeStats, progress: bool, n_req: int) -> None:
        for s in range(self.slots):
            r = active[s]
            if r is None:
                st.slot_steps_empty += 1
                continue
            emitted[r.rid].append(int(nxt[s]))     # greedy continuation
            tok[s] = int(nxt[s])
            st.slot_steps_emit += 1
            pos[s] += 1
            if len(emitted[r.rid]) >= len(r.prompt) + r.gen:
                done[r.rid] = np.asarray(emitted.pop(r.rid), np.int32)
                st.finished += 1
                if progress:
                    print(f"#   finished rid={r.rid} user={r.user_id} "
                          f"({len(done)}/{n_req})")
                active[s] = None          # freed: next arrival reuses it
                pos[s], rows[s], tok[s] = -1, -1, 0


def serve_naive(cfg, base: dict, bank: AdapterBank,
                requests: Sequence[Request]) -> Dict[int, np.ndarray]:
    """The merged-adapter baseline: per request, fold that user's adapter
    into W (paper eqn. 10) and decode batch-1 — no cross-user batching."""
    sc = cfg.lora_alpha / cfg.lora_rank
    ng, nt = model._none_adapters_like(cfg, base.get("groups") is not None)
    none_ad = {"groups": ng, "tail": nt}
    merged_cache: Dict[int, dict] = {}
    out: Dict[int, np.ndarray] = {}
    for r in requests:
        row = bank.lookup(r.user_id)
        if row not in merged_cache:
            merged_cache[row] = bank.merged_base(base, row, sc)
        params = {"base": merged_cache[row], "adapter": none_ad}
        toks = generate(cfg, params, jnp.asarray(r.prompt[None]), r.gen)
        out[r.rid] = np.asarray(toks[0], np.int32)
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fed-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--users", type=int, default=0,
                    help="multi-tenant mode: serve a seeded request stream "
                         "from this many distinct users")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    place_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = model.init_params(cfg, jax.random.key(0))

    if args.users:                      # multi-tenant request-stream path
        from repro.core.adapter_bank import random_bank
        bank = random_bank(cfg, args.users, jax.random.key(args.seed))
        reqs = make_requests(bank, args.requests,
                             prompt_len=args.prompt_len, gen=args.gen,
                             vocab=cfg.vocab_size, seed=args.seed)
        eng = ServeEngine(cfg, params["base"], bank, slots=args.slots,
                          max_len=args.prompt_len + args.gen)
        t0 = time.perf_counter()
        done = eng.run(reqs, progress=True)
        dt = time.perf_counter() - t0
        n_new = sum(r.gen for r in reqs)
        print(f"served {len(done)} requests from {args.users} users in "
              f"{dt:.1f}s ({n_new / max(dt, 1e-9):.1f} tok/s, "
              f"{args.slots} slots)")
        print("sample:", done[reqs[0].rid][-args.gen:])
        return

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    t0 = time.perf_counter()
    out = generate(cfg, params, prompts, args.gen)
    dt = time.perf_counter() - t0
    n_new = args.batch * args.gen
    print(f"generated {out.shape} in {dt:.1f}s "
          f"({1e3 * dt / max(n_new, 1):.1f} ms/token, batched)")
    print("sample:", np.asarray(out[0, -args.gen:]))


if __name__ == "__main__":
    main()
