"""Multi-tenant serving engine (repro.launch.serve, DESIGN.md §15).

The contract under test: batched heterogeneous decode — every batch slot
applying its OWN tri-LoRA bank row — emits token-for-token the SAME greedy
continuations as the per-user sequential oracle (merge that user's adapter
into W, decode batch-1).  Covered: batch sizes 1 / 2 / odd / full, more
requests than slots (continuous-batching slot reuse), duplicate users
inside one batch, and a Hypothesis property that permuting the request
stream permutes nothing (outputs are keyed by request, not by slot).
Prompts go into the ring by the chunked prefill: prompts of one token, of
exactly one chunk and of several chunks serve the oracle's tokens, and a
chunk through the decode step gives the logits the token-by-token decode
gives.  Also what a profile of the engine reads: ``ServeEngine.stats``
counts every slot-step and prefill call exactly, the ``serve.*`` spans
reach the profiler's trace, and the compiled step carries its named
scopes.

Hypothesis is an optional dev dependency (repo convention,
tests/test_properties.py) — the property test skips on a bare environment.
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.core import adapter_bank
from repro.launch.serve import (PREFILL_CHUNK, Request, ServeEngine,
                                make_requests, serve_naive)
from repro.models import model
from repro.models.config import get_config

N_USERS = 4


@pytest.fixture(scope="module")
def setup(tiny_cfg):
    params = model.init_params(tiny_cfg, jax.random.key(0))
    bank = adapter_bank.random_bank(tiny_cfg, N_USERS, jax.random.key(1))
    return tiny_cfg, params["base"], bank


def _assert_same(reqs, got, ref):
    assert set(got) == {r.rid for r in reqs} == set(ref)
    for r in reqs:
        np.testing.assert_array_equal(
            got[r.rid], ref[r.rid],
            err_msg=f"engine diverged from the per-user oracle on "
                    f"rid={r.rid} user={r.user_id}")


@pytest.mark.parametrize("n", [1, 2, 3, 8])   # 1 / 2 / odd / full stream
def test_engine_matches_per_user_oracle(setup, n):
    cfg, base, bank = setup
    reqs = make_requests(bank, n, prompt_len=3, gen=4,
                         vocab=cfg.vocab_size, seed=n)
    # slots < n for the full stream: finished requests free their slot and
    # the next arrival reuses it (ring restarts at position 0)
    eng = ServeEngine(cfg, base, bank, slots=min(n, 4), max_len=7)
    got = eng.run(reqs)
    ref = serve_naive(cfg, base, bank, reqs)
    _assert_same(reqs, got, ref)


def test_duplicate_users_share_a_batch(setup):
    """Two slots serving the SAME bank row alongside two other users —
    the grouped gather must broadcast, not alias."""
    cfg, base, bank = setup
    rng = np.random.default_rng(7)
    users = sorted(bank.users)
    picks = [users[0], users[2], users[0], users[1]]
    reqs = [Request(rid=i, user_id=u,
                    prompt=rng.integers(0, cfg.vocab_size, (3,)).astype(
                        np.int32), gen=4)
            for i, u in enumerate(picks)]
    eng = ServeEngine(cfg, base, bank, slots=4, max_len=7)
    got = eng.run(reqs)
    ref = serve_naive(cfg, base, bank, reqs)
    _assert_same(reqs, got, ref)


@pytest.mark.parametrize("lens", [
    # a one-token prompt (no prefill call), exactly one chunk, a chunk and
    # one token, several chunks; 2 slots, so finished slots are reused
    [(1, 3), (PREFILL_CHUNK, 2), (PREFILL_CHUNK + 1, 2),
     (2 * PREFILL_CHUNK + 5, 3), (7, 4)],
])
def test_prefill_serves_the_per_user_oracle(setup, lens):
    """Prompts written into the ring by the prefill program, a chunk a call
    through each user's own bank row, then decoded: the same greedy tokens
    as the per-user oracle, which feeds every prompt token through the
    decode step."""
    cfg, base, bank = setup
    reqs = _ragged_requests(cfg, bank, lens, seed=4)
    assert len({r.user_id for r in reqs}) == N_USERS
    eng = ServeEngine(cfg, base, bank, slots=2,
                      max_len=max(p + g for p, g in lens))
    _assert_same(reqs, eng.run(reqs), serve_naive(cfg, base, bank, reqs))
    assert eng.stats.prefill_tokens == sum(p - 1 for p, _ in lens)


def test_a_chunk_gives_the_token_by_token_logits(setup):
    """The decode step handed 16 tokens a row (S = 16) into ring row 1 of
    three, three chunks for 37 prompt tokens (the last padded), gives at
    every position the logits that 37 one-token steps give that row."""
    from repro.launch import serve
    cfg, base, bank = setup
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, 37)
    dec = bank.decode_tree()
    user = np.int32(2)
    step = jax.jit(model.decode_step, static_argnums=0)
    with jax.default_matmul_precision("highest"):
        cache = serve._init_cache(cfg, 3, 40)
        got = []
        for start in range(0, 37, 16):
            part = toks[start:start + 16]
            n = len(part)
            lg, cache = step(
                cfg, base, dec, cache,
                {"token": np.pad(part, (0, 16 - n))[None].astype(np.int32),
                 "positions": np.where(np.arange(16) < n,
                                       start + np.arange(16), -1)[None]},
                adapter_rows=np.asarray([user]), ring_row=np.int32(1))
            got.append(np.asarray(lg[0, :n]))
        cache = serve._init_cache(cfg, 3, 40)
        want = []
        for t in range(37):
            pos = np.asarray([-1, t, -1], np.int32)
            lg, cache = step(
                cfg, base, dec, serve._with_positions(cache, pos),
                {"token": np.asarray([[0], [toks[t]], [0]], np.int32),
                 "positions": pos[:, None]},
                adapter_rows=np.asarray([-1, user, -1], np.int32))
            want.append(np.asarray(lg[1, 0]))
    np.testing.assert_allclose(np.concatenate(got), np.stack(want),
                               atol=1e-5, rtol=1e-5)


def test_engine_rejects_overlong_request(setup):
    cfg, base, bank = setup
    reqs = make_requests(bank, 1, prompt_len=6, gen=4,
                         vocab=cfg.vocab_size, seed=0)
    eng = ServeEngine(cfg, base, bank, slots=2, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.run(reqs)


@pytest.mark.parametrize("arch", ["tiny", "h2o-danube-3-4b", "qwen2.5-14b",
                                  "deepseek-v2-lite"])
def test_step_donates_its_cache(setup, arch):
    """Each step consumes the cache it is handed (its rings are written in
    place) and the cache it returns drives the next step: fed a 3-token
    prompt step by step it emits what a fresh engine emits.  Danube's ring
    is cut to 2 slots, so the third token wraps it; DeepSeek's latent rings
    (the leading dense layer's and the scanned stack's) are donated too."""
    from repro.launch import serve
    cfg, base, bank = setup
    if arch != "tiny":
        cfg = get_config(arch).reduced().with_overrides(window=2)
        base = model.init_params(cfg, jax.random.key(0))["base"]
        bank = adapter_bank.random_bank(cfg, N_USERS, jax.random.key(1))
    reqs = make_requests(bank, 2, prompt_len=3, gen=1,
                         vocab=cfg.vocab_size, seed=5)
    want = ServeEngine(cfg, base, bank, slots=2, max_len=4).run(reqs)

    eng = ServeEngine(cfg, base, bank, slots=2, max_len=4)
    cache = model.init_decode_cache(cfg, 2, 4)
    rows = np.asarray([bank.lookup(r.user_id) for r in reqs], np.int32)
    for t in range(3):
        # the rings; the step installs ``pos`` in place of every ``idx``
        old = [a for a in jax.tree.leaves(cache) if a.ndim >= 3]
        tok = np.asarray([[r.prompt[t]] for r in reqs], np.int32)
        nxt, cache = serve._serve_step(cfg, base, eng._bank_dec, cache, tok,
                                       np.full((2,), t, np.int32), rows)
        assert all(a.is_deleted() for a in old)
    np.testing.assert_array_equal(np.asarray(nxt),
                                  [want[r.rid][-1] for r in reqs])


def test_request_permutation_property(setup):
    """Permuting the arrival order (and hence which slot / which adapter
    row each request lands on) permutes NOTHING observable: outputs are a
    function of (user, prompt), not of slot assignment."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, base, bank = setup
    reqs = make_requests(bank, N_USERS, prompt_len=3, gen=4,
                         vocab=cfg.vocab_size, seed=11)
    assert len({r.user_id for r in reqs}) > 1     # heterogeneous batch
    eng = ServeEngine(cfg, base, bank, slots=N_USERS, max_len=7)
    baseline = eng.run(reqs)

    @given(perm=st.permutations(list(range(N_USERS))))
    @settings(max_examples=10, deadline=None)
    def prop(perm):
        got = eng.run([reqs[i] for i in perm])
        for r in reqs:
            np.testing.assert_array_equal(got[r.rid], baseline[r.rid])

    prop()


# ---------------------------------------------------------------------------
# counters, spans and scopes (what a profile of the engine reads)
# ---------------------------------------------------------------------------

def _ragged_requests(cfg, bank, lens, seed=3):
    """One request per (prompt length, reply length), users in turn."""
    rng = np.random.default_rng(seed)
    users = sorted(bank.users)
    return [Request(rid=i, user_id=users[i % len(users)],
                    prompt=rng.integers(0, cfg.vocab_size, p).astype(
                        np.int32), gen=g)
            for i, (p, g) in enumerate(lens)]


C = PREFILL_CHUNK


@pytest.mark.parametrize("lens,slots,steps", [
    # all admitted at once: the longest reply sets the steps
    pytest.param([(3, 4), (1, 2), (C + 2, 1), (2, 3)], 4, 4, id="lens0-4"),
    # slot reuse, a drain: A(4,2) and B(2,5) at step 0, C(1,1) at 2,
    # D(2C+5,3) at 3 (steps 3-5), E(6,2) at 5 (steps 5-6)
    pytest.param([(4, 2), (2, 5), (1, 1), (2 * C + 5, 3), (6, 2)], 2, 7,
                 id="lens1-2"),
])
def test_stats_count_every_slot_step(setup, lens, slots, steps):
    cfg, base, bank = setup
    reqs = _ragged_requests(cfg, bank, lens)
    eng = ServeEngine(cfg, base, bank, slots=slots, max_len=2 * C + 8)
    got = eng.run(reqs)
    _assert_same(reqs, got, serve_naive(cfg, base, bank, reqs))
    st = eng.stats
    assert st.steps == steps
    assert st.slot_steps_emit == sum(g for _, g in lens)
    assert st.slot_steps_prefill == 0
    assert st.slot_steps_emit + st.slot_steps_empty == slots * st.steps
    assert st.prefill_calls == sum(-(-(p - 1) // C) for p, _ in lens)
    assert st.prefill_tokens == sum(p - 1 for p, _ in lens)
    assert st.admitted == st.finished == len(lens)


def test_stats_reset_at_each_run(setup):
    cfg, base, bank = setup
    eng = ServeEngine(cfg, base, bank, slots=2, max_len=8)
    eng.run(_ragged_requests(cfg, bank, [(3, 3), (2, 2), (4, 1)]))
    first = eng.stats
    eng.run(_ragged_requests(cfg, bank, [(2, 1)]))
    assert eng.stats is not first
    assert (eng.stats.steps, eng.stats.slot_steps_emit,
            eng.stats.slot_steps_prefill, eng.stats.slot_steps_empty,
            eng.stats.admitted, eng.stats.finished, eng.stats.prefill_calls,
            eng.stats.prefill_tokens) == (1, 1, 0, 1, 1, 1, 1, 1)


def test_decode_step_carries_named_scopes(setup):
    """The compiled step's op_name metadata holds the scopes a trace
    reduction buckets device time by; a refactor that drops one fails."""
    from repro.launch import serve
    cfg, base, bank = setup
    eng = ServeEngine(cfg, base, bank, slots=2, max_len=8)
    cache = model.init_decode_cache(cfg, 2, 8)
    ints = np.zeros((2,), np.int32)
    hlo = serve._serve_step.lower(
        cfg, base, eng._bank_dec, cache, ints[:, None], ints,
        ints).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("kv_ring", "tri_lora", "attention", "logits"):
        assert any(f"/{scope}/" in n for n in names), scope
    # an executable loaded from the persistent cache keeps its metadata
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_prefill_carries_its_scope(setup):
    """Every operation of the compiled prefill program runs under the
    ``prefill`` scope that ``serve_prefill_ms`` reads, and the ring's
    writes under ``kv_ring`` inside it."""
    from repro.launch import serve
    cfg, base, bank = setup
    cache = serve._init_cache(cfg, 2, 8)
    one = np.int32(0)
    hlo = serve._prefill.lower(
        cfg, base, bank.decode_tree(), cache,
        np.zeros((1, PREFILL_CHUNK), np.int32), one, one, one,
        one).compile().as_text()
    names = set(re.findall(r'op_name="(jit[^"]*)"', hlo))
    assert names and all(n.startswith("jit(_prefill)/prefill/")
                         for n in names), names
    assert any("/kv_ring/" in n for n in names)


def test_run_writes_its_spans_into_a_profile(setup, tmp_path):
    from jax.profiler import ProfileData
    cfg, base, bank = setup
    eng = ServeEngine(cfg, base, bank, slots=2, max_len=8)
    reqs = _ragged_requests(cfg, bank, [(3, 2), (2, 2), (1, 3)])
    eng.run(reqs)                                       # compile outside
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    seen = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    st = eng.stats
    assert len(seen["serve.run"]) == len(seen["serve.ring_init"]) == 1
    for name in ("serve.step", "serve.admit", "serve.prefill",
                 "serve.dispatch", "serve.sync", "serve.bookkeep"):
        assert len(seen[name]) == st.steps, name
    assert [s["step_num"] for s in seen["serve.step"]] == list(
        range(st.steps))
    meta = seen["serve.run"][0]
    assert meta["slots"] == 2 and meta["kv_ring"] == "2x2x8x128"
    assert {k: meta[k] for k in dataclasses.asdict(st)} == \
        dataclasses.asdict(st)
