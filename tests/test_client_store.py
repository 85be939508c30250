"""ClientStore backends (repro.core.client_store, DESIGN.md §12).

The store-parametrized equivalence harness: ``device`` / ``sharded`` /
``host`` population backends must produce ENGINE-IDENTICAL training
histories — same participation, byte accounting, allclose loss / accuracy /
final states — across eager⇄scan, full and partial participation,
stragglers, every uplink codec, and kill-then-resume.  Plus the store
contract itself (gather∘scatter round-trips the population exactly for any
id subset) and fault injection on the host-backed cohort streamer.

The Hypothesis property tests at the bottom follow the repo convention
(tests/test_properties.py): ``hypothesis`` is an optional dev dependency,
so they skip individually on a bare environment while the deterministic
harness above always runs.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import client_batch, client_store, sampling
from repro.core.fed_model import FedTask
from repro.core.federated import FedConfig, run_federated
from repro.data import partition, synthetic

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

STORES = client_store.STORE_BACKENDS


# ---------------------------------------------------------------------------
# store contract: gather ∘ scatter round-trips the population exactly
# ---------------------------------------------------------------------------

_M = 8                  # divisible by every emulated device count CI uses


def _toy_states(m=_M, seed=0):
    """m tiny per-client pytrees with mixed shapes, ranks, and dtypes."""
    rng = np.random.default_rng(seed)
    return [{"A": rng.standard_normal((3, 2)).astype(np.float32),
             "C": rng.standard_normal((2, 2)).astype(np.float32),
             "ef": {"C": rng.standard_normal((2, 2)).astype(np.float32)},
             "h": jnp.asarray(rng.standard_normal(4), jnp.bfloat16),
             "step": np.int32(i)}
            for i, _ in zip(range(m), range(m))]


def _snapshot(store):
    if isinstance(store, client_store.HostClientStore):
        return jax.tree.map(np.array, store.population)
    return jax.tree.map(np.asarray, store.resident())


_ID_CASES = {
    "empty": [],
    "single": [3],
    "pair": [0, _M - 1],          # both block boundaries
    "subset": [1, 2, 4],
    "full": list(range(_M)),
}


@pytest.mark.parametrize("case", sorted(_ID_CASES))
@pytest.mark.parametrize("backend", STORES)
def test_gather_scatter_roundtrip(backend, case):
    """scatter(ids, gather(ids)) is the identity on the population — for
    empty, singleton, boundary, arbitrary, and full cohorts alike."""
    store = client_store.make_store(backend, _toy_states())
    ids = np.asarray(_ID_CASES[case], np.int32)
    before = _snapshot(store)
    rows = store.gather(ids)
    for leaf in jax.tree.leaves(rows):        # cohort-shaped, cohort-sized
        assert leaf.shape[0] == len(ids)
    store.scatter(ids, rows)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), before, _snapshot(store))


@pytest.mark.parametrize("backend", STORES)
def test_scatter_touches_only_cohort_rows(backend):
    """Writing modified cohort rows changes exactly those population rows;
    a later gather observes the previous scatter (write-back ordering)."""
    store = client_store.make_store(backend, _toy_states())
    ids = np.asarray([1, 4], np.int32)
    before = _snapshot(store)
    rows = store.gather(ids)
    store.scatter(ids, jax.tree.map(lambda l: l + 1, rows))
    after = _snapshot(store)
    sel = np.zeros(_M, bool)
    sel[ids] = True

    def check(b, a):
        np.testing.assert_array_equal(a[~sel], b[~sel])
        np.testing.assert_allclose(
            np.asarray(a[sel], np.float32), np.asarray(b[sel], np.float32)
            + 1, rtol=1e-2)
    jax.tree.map(check, before, after)
    # the next gather sees the written rows, not the originals
    jax.tree.map(lambda g, a: np.testing.assert_array_equal(
        np.asarray(g), a[ids]), store.gather(ids), after)


@pytest.mark.parametrize("backend", STORES)
def test_unstack_matches_states(backend):
    states = _toy_states()
    out = client_store.make_store(backend, states).unstack()
    assert len(out) == _M
    for s, o in zip(states, out):
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s, o)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="client_store"):
        client_store.make_store("disk", _toy_states())


def test_plan_cohort_is_sampled():
    """The cohort a store materializes is the SAMPLED set (stragglers
    train), and cohort_mask is the cohort-local view of mask(m)."""
    plan = sampling.build_plan("uniform", m=10, participation=0.6,
                               straggler_frac=0.4, rnd=3, seed=7)
    np.testing.assert_array_equal(plan.cohort, plan.sampled)
    assert plan.dropped.size > 0            # stragglers actually exercised
    np.testing.assert_array_equal(plan.cohort_mask(),
                                  plan.mask(10)[plan.sampled])
    assert set(plan.sampled[plan.cohort_mask()]) == set(plan.participants)


# ---------------------------------------------------------------------------
# engine-identical histories: device ≡ sharded ≡ host
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_setup(tiny_cfg):
    n_classes, seq = 4, 16
    tr = synthetic.make_classification_data(0, 600, seq, tiny_cfg.vocab_size,
                                            n_classes, class_sep=1.5)
    te = synthetic.make_classification_data(1, 300, seq, tiny_cfg.vocab_size,
                                            n_classes, class_sep=1.5)
    m = 4
    trs = partition.dirichlet_partition(0, tr.labels, m, 0.5)
    tes = partition.dirichlet_partition(0, te.labels, m, 0.5)
    ctrain = [{"tokens": tr.tokens[s], "labels": tr.labels[s]} for s in trs]
    ctest = [{"tokens": te.tokens[s], "labels": te.labels[s]} for s in tes]
    task = FedTask.create(jax.random.key(0), tiny_cfg, n_classes)
    return task, ctrain, ctest, m


_MEMO: dict = {}


def _run(fed_setup, store, engine, rounds=2, memo=False, **kw):
    task, ctrain, ctest, m = fed_setup
    kw.setdefault("chunk_rounds", 2)
    kw.setdefault("use_data_sim", False)    # CKA-only: no GMM fit per run
    kw.setdefault("cka_probes", 8)
    key = (store, engine, rounds, tuple(sorted(kw.items())))
    if memo and key in _MEMO:
        return _MEMO[key]
    fed = FedConfig(method="celora", n_clients=m, rounds=rounds,
                    local_steps=2, batch_size=8, lr=1e-2, engine=engine,
                    client_store=store, **kw)
    out = run_federated(task, fed, ctrain, ctest)
    if memo:
        _MEMO[key] = out
    return out


def _assert_history_close(ref, out, states_atol=5e-4):
    """Backend choice must be invisible to the history: identical
    participation and byte accounting, allclose loss/accuracy/states (the
    same contract and tolerances as the eager⇄scan equivalence)."""
    assert len(ref["history"]) == len(out["history"])
    for r_ref, r_out in zip(ref["history"], out["history"]):
        assert r_ref.sampled == r_out.sampled
        assert r_ref.participants == r_out.participants
        assert r_ref.dropped == r_out.dropped
        assert r_ref.uplink_bytes == r_out.uplink_bytes
        assert r_ref.downlink_bytes == r_out.downlink_bytes
        assert r_ref.uplink_elems == r_out.uplink_elems
        assert abs(r_ref.train_loss - r_out.train_loss) < 1e-4
        np.testing.assert_allclose(r_ref.accs, r_out.accs, atol=1e-3)
    for s_ref, s_out in zip(ref["states"], out["states"]):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=states_atol), s_ref, s_out)


@pytest.mark.parametrize("participation", [1.0, 0.4])
@pytest.mark.parametrize("engine", ["eager", "scan"])
@pytest.mark.parametrize("store", ["sharded", "host"])
def test_store_matches_device(fed_setup, store, engine, participation):
    kw = dict(participation=participation, seed=3)
    ref = _run(fed_setup, "device", engine, memo=True, **kw)
    out = _run(fed_setup, store, engine, **kw)
    _assert_history_close(ref, out)


@pytest.mark.parametrize("store", ["sharded", "host"])
def test_store_matches_device_stragglers(fed_setup, store):
    """Trained-but-not-uploaded state is the subtlest cohort case: the
    straggler's row must advance in the population without entering the
    aggregate."""
    kw = dict(participation=1.0, straggler_frac=0.3, seed=1)
    ref = _run(fed_setup, "device", "scan", memo=True, **kw)
    out = _run(fed_setup, store, "scan", **kw)
    _assert_history_close(ref, out)


@pytest.mark.parametrize("codec", ["none", "bf16", "int8", "int4"])
def test_host_matches_device_codecs(fed_setup, codec):
    """Under compression the device engines encode ALL m clients per round
    (per-(round, client) key folds) and carry per-client EF residuals; the
    host backend's bank re-encode must reproduce that stream exactly."""
    kw = dict(participation=0.4, seed=3, uplink_codec=codec)
    ref = _run(fed_setup, "device", "scan", memo=True, **kw)
    out = _run(fed_setup, "host", "scan", **kw)
    _assert_history_close(ref, out)


def test_sharded_matches_device_codec(fed_setup):
    kw = dict(participation=0.4, seed=3, uplink_codec="int8")
    ref = _run(fed_setup, "device", "scan", memo=True, **kw)
    out = _run(fed_setup, "sharded", "scan", **kw)
    _assert_history_close(ref, out)


def test_host_matches_device_data_similarity(fed_setup):
    """With S^data on, the GMM similarity is a pre-dispatch constant — the
    host cohort program must mix it identically."""
    kw = dict(participation=0.5, seed=2, use_data_sim=True,
              feature_samples=64, gmm_components=2)
    ref = _run(fed_setup, "device", "scan", **kw)
    out = _run(fed_setup, "host", "scan", **kw)
    # The host store fits the 2 sampled clients, the device store all 4,
    # so XLA tiles the two vmapped fits differently and their gradients
    # differ in the last bit; Adam's g / (sqrt(v) + eps) turns that into an
    # O(lr) = 1e-2 update gap for entries near zero.  Measured on XLA:CPU
    # (JAX 0.9.0): 1.1e-4 on one host and up to 1.07e-3 on another.
    _assert_history_close(ref, out, states_atol=5e-3)


def test_host_fedavg_matches_device(fed_setup):
    """Non-personalized aggregation: cohort-restricted FedAvg with the
    population sample counts equals the full-m masked mean."""
    task, ctrain, ctest, m = fed_setup
    outs = {}
    for store in ("device", "host"):
        fed = FedConfig(method="fedpetuning", n_clients=m, rounds=2,
                        local_steps=2, batch_size=8, lr=1e-2,
                        participation=0.5, seed=4, engine="scan",
                        chunk_rounds=2, client_store=store)
        outs[store] = run_federated(task, fed, ctrain, ctest)
    _assert_history_close(outs["device"], outs["host"])


def test_host_rejects_loop_parallelism(fed_setup):
    with pytest.raises(ValueError, match="client_store"):
        _run(fed_setup, "host", "eager", client_parallelism="loop")
    with pytest.raises(ValueError, match="client_store"):
        _run(fed_setup, "nvme", "eager")


# ---------------------------------------------------------------------------
# kill-then-resume
# ---------------------------------------------------------------------------

def test_host_resume_reproduces_history(fed_setup, tmp_path):
    """Host-backed run checkpointed at a chunk boundary and resumed later
    reproduces the uninterrupted history EXACTLY — with a codec, so the EF
    residual bank crosses the checkpoint too."""
    path = str(tmp_path / "fed.npz")
    kw = dict(participation=0.5, seed=3, uplink_codec="int8")
    full = _run(fed_setup, "host", "scan", rounds=6, **kw)
    _run(fed_setup, "host", "scan", rounds=4, checkpoint_path=path, **kw)
    res = _run(fed_setup, "host", "scan", rounds=6, checkpoint_path=path,
               resume=True, **kw)
    for r_full, r_res in zip(full["history"], res["history"]):
        assert r_full.train_loss == r_res.train_loss
        assert r_full.accs == r_res.accs
        assert r_full.participants == r_res.participants
        assert r_full.uplink_bytes == r_res.uplink_bytes
    for s_full, s_res in zip(full["states"], res["states"]):
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_full, s_res)
    assert os.listdir(tmp_path) == ["fed.npz"]


def test_resume_rejects_other_store(fed_setup, tmp_path):
    """The checkpoint fingerprint includes the store backend: a population
    written by one backend must not silently resume under another."""
    path = str(tmp_path / "fed.npz")
    kw = dict(participation=0.5, seed=3)
    _run(fed_setup, "device", "scan", rounds=2, checkpoint_path=path, **kw)
    with pytest.raises(ValueError, match="different run configuration"):
        _run(fed_setup, "host", "scan", rounds=4, checkpoint_path=path,
             resume=True, **kw)
    _run(fed_setup, "host", "scan", rounds=2, checkpoint_path=path, **kw)
    with pytest.raises(ValueError, match="different run configuration"):
        _run(fed_setup, "device", "scan", rounds=4, checkpoint_path=path,
             resume=True, **kw)


# ---------------------------------------------------------------------------
# fault injection on the host-backed cohort streamer
# ---------------------------------------------------------------------------

class _Boom(Exception):
    pass


def test_host_producer_exception_reraises(fed_setup, monkeypatch):
    """A failure on the prefetch producer thread (loader dies mid-draw)
    must surface in the consumer as the original exception, not a hang or
    a silent truncation."""
    real = client_batch.stack_cohort_batches
    calls = {"n": 0}

    def dying(loaders, ids, n_batches):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise _Boom("loader died on the producer thread")
        return real(loaders, ids, n_batches)

    monkeypatch.setattr(client_batch, "stack_cohort_batches", dying)
    with pytest.raises(_Boom, match="producer thread"):
        _run(fed_setup, "host", "scan", rounds=4, participation=0.5, seed=3)


def test_host_kill_between_fit_and_writeback(fed_setup, tmp_path,
                                             monkeypatch):
    """Killed AFTER the cohort fit but BEFORE the write-back: the round is
    not in the checkpoint, so resume replays it from the last completed
    round — the population (and the EF residual bank, rebuilt from it)
    must be neither missing the round nor have it applied twice."""
    path = str(tmp_path / "fed.npz")
    kw = dict(participation=0.5, seed=3, uplink_codec="int8")
    full = _run(fed_setup, "host", "scan", rounds=6, **kw)

    real = client_store.HostClientStore.scatter
    calls = {"n": 0}

    def dying(self, ids, values):
        calls["n"] += 1
        if calls["n"] == 5:       # round 4, right after the chunk-2 save
            raise _Boom("killed between cohort fit and write-back")
        return real(self, ids, values)

    monkeypatch.setattr(client_store.HostClientStore, "scatter", dying)
    with pytest.raises(_Boom):
        _run(fed_setup, "host", "scan", rounds=6, checkpoint_path=path, **kw)
    assert calls["n"] == 5        # died in round 4 (post-checkpoint-at-4)
    monkeypatch.setattr(client_store.HostClientStore, "scatter", real)

    res = _run(fed_setup, "host", "scan", rounds=6, checkpoint_path=path,
               resume=True, **kw)
    for r_full, r_res in zip(full["history"], res["history"]):
        assert r_full.train_loss == r_res.train_loss
        assert r_full.accs == r_res.accs
    for s_full, s_res in zip(full["states"], res["states"]):
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_full, s_res)


# ---------------------------------------------------------------------------
# LM driver (repro.launch.train) host backend
# ---------------------------------------------------------------------------

def test_lm_driver_host_matches_device():
    """The language-model driver's host-backed round loop reproduces the
    device history (referenced from train._run_host_lm)."""
    from repro.launch.train import run as train_run
    kw = dict(arch="fed-100m", clients=3, rounds=2, local_steps=2, batch=2,
              seq=16, method="celora", verbose=False, reduced=True,
              participation=0.67, uplink_codec="int8")
    ref = train_run(engine="eager", **kw)
    out = train_run(engine="eager", client_store="host", **kw)
    for h_ref, h_out in zip(ref["history"], out["history"]):
        assert h_ref["participants"] == h_out["participants"]
        assert h_ref["uplink_bytes"] == h_out["uplink_bytes"]
        assert abs(h_ref["loss"] - h_out["loss"]) < 1e-4
    for a_ref, a_out in zip(ref["adapters"], out["adapters"]):
        jax.tree.map(lambda p, q: np.testing.assert_allclose(
            np.asarray(p), np.asarray(q), atol=5e-5), a_ref, a_out)
    with pytest.raises(ValueError, match="host"):
        train_run(engine="scan", client_store="host", **kw)


# ---------------------------------------------------------------------------
# Hypothesis property tests (skipped on a bare environment)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, _M - 1), unique=True, max_size=_M),
           st.sampled_from(STORES))
    def test_roundtrip_arbitrary_masks(ids, backend):
        """For ANY participation id set — empty through full — the cohort
        gather followed by its scatter leaves the population bit-identical,
        and perturbed scatters land on exactly the cohort rows."""
        store = client_store.make_store(backend, _toy_states())
        ids = np.sort(np.asarray(ids, np.int32))
        before = _snapshot(store)
        store.scatter(ids, store.gather(ids))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), before, _snapshot(store))
        store.scatter(ids, jax.tree.map(lambda l: l + 1, store.gather(ids)))
        after = _snapshot(store)
        sel = np.zeros(_M, bool)
        sel[ids] = True
        jax.tree.map(lambda b, a: np.testing.assert_array_equal(
            np.asarray(a[~sel]), np.asarray(b[~sel])), before, after)

    @settings(max_examples=3, deadline=None)
    @given(st.integers(0, 50))
    def test_history_backend_invariant(fed_setup, seed):
        """Backend choice is invisible to the RoundRecord history for
        arbitrary seeds (arbitrary participation draws)."""
        kw = dict(participation=0.5, seed=seed)
        ref = _run(fed_setup, "device", "eager", rounds=1, **kw)
        out = _run(fed_setup, "host", "eager", rounds=1, **kw)
        _assert_history_close(ref, out)
