"""Device-resident scan pipeline (DESIGN.md §11): carry donation safety,
ChunkPrefetcher determinism, Loader.skip RNG-stream equality, eval_every
history semantics, and the host/device wall-clock split."""
import time

import jax
import numpy as np
import pytest

from repro.core import client_batch
from repro.core.fed_model import FedTask
from repro.core.federated import FedConfig, run_federated
from repro.data import partition, synthetic
from repro.data.pipeline import Loader


# ---------------------------------------------------------------------------
# Loader.skip — no-materialization resume fast-forward
# ---------------------------------------------------------------------------

def _loader_pair(n, batch_size, seed=7, drop_last=False):
    rng = np.random.default_rng(0)
    arrays = {"tokens": rng.integers(0, 50, (n, 4)).astype(np.int32),
              "labels": rng.integers(0, 3, n).astype(np.int32)}
    return (Loader(arrays, batch_size, seed=seed, drop_last=drop_last),
            Loader(arrays, batch_size, seed=seed, drop_last=drop_last))


@pytest.mark.parametrize("n,bs,drop_last", [
    (40, 8, False),       # exact epochs
    (37, 8, False),       # short final batch (resample padding consumed)
    (37, 8, True),        # short batch dropped
    (5, 8, False),        # n < batch_size: every batch is padded
])
@pytest.mark.parametrize("sessions", [1, 3, 7])
def test_loader_skip_matches_replay(n, bs, drop_last, sessions):
    """skip(k) must leave the RNG stream exactly where drawing (and
    discarding) k batches would — mixed skip/draw histories coincide."""
    drawn, skipped = _loader_pair(n, bs, drop_last=drop_last)
    steps = 4
    for _ in range(sessions):
        for _b in drawn.batches(steps):
            pass
        skipped.skip(steps)
    for bd, bs_ in zip(drawn.batches(steps), skipped.batches(steps)):
        np.testing.assert_array_equal(bd["tokens"], bs_["tokens"])
        np.testing.assert_array_equal(bd["labels"], bs_["labels"])


def test_loader_skip_spans_epochs():
    """A skip longer than one epoch consumes the per-epoch permutation and
    short-batch resample draws of every crossed epoch."""
    drawn, skipped = _loader_pair(21, 4)   # 6 batches/epoch, last short
    for _b in drawn.batches(17):           # ~3 epochs
        pass
    skipped.skip(17)
    for bd, bs_ in zip(drawn.batches(3), skipped.batches(3)):
        np.testing.assert_array_equal(bd["tokens"], bs_["tokens"])


# ---------------------------------------------------------------------------
# ChunkPrefetcher — background draw/stack, deterministic order
# ---------------------------------------------------------------------------

def _make_loaders(m=3, n=30, bs=4, seed=11):
    rng = np.random.default_rng(1)
    return [Loader({"tokens": rng.integers(0, 50, (n, 6)).astype(np.int32),
                    "labels": rng.integers(0, 3, n).astype(np.int32)},
                   bs, seed=seed + i) for i in range(m)]


@pytest.mark.parametrize("schedule", [[1, 1, 1], [3, 3], [3, 3, 1]])
def test_chunk_prefetcher_matches_serial(schedule):
    """The prefetched stream is bit-for-bit the serial stack_chunk_batches
    loop — chunk sizes 1, 3, and an odd tail."""
    steps = 2
    serial = _make_loaders()
    ref = [client_batch.stack_chunk_batches(serial, n, steps)
           for n in schedule]
    pre = _make_loaders()
    pf = client_batch.ChunkPrefetcher(
        lambda n: client_batch.stack_chunk_batches(pre, n, steps), schedule)
    try:
        for rt, rl in [r for r in ref]:
            (toks, labs), produce_s = pf.get()
            assert produce_s >= 0.0
            np.testing.assert_array_equal(np.asarray(toks), np.asarray(rt))
            np.testing.assert_array_equal(np.asarray(labs), np.asarray(rl))
        with pytest.raises(StopIteration):
            pf.get()
    finally:
        pf.close()


def test_chunk_prefetcher_bounded_queue():
    """The producer stays at most `depth` chunks ahead (bounded host
    memory), and close() stops a mid-schedule producer."""
    produced = []

    def produce(n):
        produced.append(n)
        return n
    pf = client_batch.ChunkPrefetcher(produce, [1] * 10, depth=2)
    time.sleep(0.5)
    assert len(produced) <= 3          # depth in queue + one in flight
    assert pf.get()[0] == 1
    pf.close()
    n_after_close = len(produced)
    time.sleep(0.3)
    assert len(produced) == n_after_close   # producer actually stopped


def test_chunk_prefetcher_propagates_errors():
    def produce(n):
        raise RuntimeError("loader exploded")
    pf = client_batch.ChunkPrefetcher(produce, [2])
    try:
        with pytest.raises(RuntimeError, match="loader exploded"):
            pf.get()
    finally:
        pf.close()


def test_chunk_prefetcher_get_after_close_raises():
    """get() after close() must raise immediately — the producer is
    stopped and the queue will never be fed again, so the old behavior
    (blocking on an empty queue forever) was a deadlock."""
    pf = client_batch.ChunkPrefetcher(lambda n: n, [1] * 4, depth=1)
    pf.get()
    pf.close()
    with pytest.raises(RuntimeError, match="after close"):
        pf.get()


def test_chunk_prefetcher_close_while_producer_blocked():
    """close() must terminate a producer that is blocked in _put on a full
    queue — and keep draining until the thread actually exits (a single
    drain races the producer's in-flight put)."""
    import threading
    started = threading.Event()

    def produce(n):
        started.set()
        return np.zeros(1 << 16)       # bulky: forces queue-full blocking
    pf = client_batch.ChunkPrefetcher(produce, [1] * 50, depth=1)
    started.wait(timeout=5.0)
    time.sleep(0.2)                    # let the producer block in _put
    pf.close()
    assert not pf._thread.is_alive()
    with pytest.raises(RuntimeError, match="after close"):
        pf.get()


# ---------------------------------------------------------------------------
# engine-level: donation safety, eval_every, wall split
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_setup(tiny_cfg):
    n_classes, seq = 4, 16
    tr = synthetic.make_classification_data(0, 400, seq, tiny_cfg.vocab_size,
                                            n_classes, class_sep=1.5)
    te = synthetic.make_classification_data(1, 200, seq, tiny_cfg.vocab_size,
                                            n_classes, class_sep=1.5)
    m = 3
    trs = partition.dirichlet_partition(0, tr.labels, m, 0.5)
    tes = partition.dirichlet_partition(0, te.labels, m, 0.5)
    ctrain = [{"tokens": tr.tokens[s], "labels": tr.labels[s]} for s in trs]
    ctest = [{"tokens": te.tokens[s], "labels": te.labels[s]} for s in tes]
    task = FedTask.create(jax.random.key(0), tiny_cfg, n_classes)
    return task, ctrain, ctest, m


def _run(fed_setup, engine="scan", rounds=3, **kw):
    task, ctrain, ctest, m = fed_setup
    kw.setdefault("chunk_rounds", 2)           # odd tail at rounds=3
    kw.setdefault("use_data_sim", False)       # skip the one-shot GMM
    fed = FedConfig(method="celora", n_clients=m, rounds=rounds,
                    local_steps=2, batch_size=8, lr=1e-2, seed=3,
                    cka_probes=8, engine=engine, **kw)
    return run_federated(task, fed, ctrain, ctest)


def _assert_identical(a, b):
    for r_a, r_b in zip(a["history"], b["history"]):
        assert r_a.train_loss == r_b.train_loss
        assert r_a.accs == r_b.accs
        assert r_a.uplink_bytes == r_b.uplink_bytes
    for s_a, s_b in zip(a["states"], b["states"]):
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), s_a, s_b)


def test_donation_and_prefetch_do_not_change_results(fed_setup):
    """donate/prefetch are execution details: any on/off combination gives
    the identical history and final states (multi-chunk run, so a donated
    buffer re-read or a mis-ordered prefetch would diverge or raise)."""
    ref = _run(fed_setup, scan_donate=False, scan_prefetch=False)
    for kw in (dict(scan_donate=True, scan_prefetch=False),
               dict(scan_donate=False, scan_prefetch=True),
               dict(scan_donate=True, scan_prefetch=True)):
        _assert_identical(ref, _run(fed_setup, **kw))


def test_donated_run_is_repeatable(fed_setup):
    """Use-after-donate guard: run the donating engine twice from the same
    initial state — if any chunk re-read a donated buffer the second run
    would raise (the engine deletes old carries) or corrupt the history."""
    a = _run(fed_setup, scan_donate=True, scan_prefetch=True)
    b = _run(fed_setup, scan_donate=True, scan_prefetch=True)
    _assert_identical(a, b)


def test_donated_carry_buffers_are_deleted():
    """The donation contract itself: after a donating dispatch the old
    carry handles are dead — accessing one raises instead of silently
    reading stale memory."""
    import jax.numpy as jnp
    f = jax.jit(lambda c, x: (jax.tree.map(lambda l: l + x, c), x),
                donate_argnums=(0,))
    carry = {"a": jnp.ones((8,)), "b": jnp.zeros((4,))}
    out, _ = f(carry, 2.0)
    jax.tree.map(lambda l: l.delete(), carry)
    with pytest.raises(RuntimeError, match="deleted"):
        _ = carry["a"] + 1
    assert float(out["a"][0]) == 3.0


def test_eval_every_semantics(fed_setup):
    """eval_every > 1: losses/bytes are unchanged, eval rounds match the
    every-round run bit-for-bit, off-cadence rounds carry the LAST
    evaluated accuracies, the final round always evaluates, and the
    `evaluated` flag marks the cadence."""
    every = _run(fed_setup, rounds=5, eval_every=1)
    sparse = _run(fed_setup, rounds=5, eval_every=3)
    last = None
    for r_e, r_s in zip(every["history"], sparse["history"]):
        assert r_e.train_loss == r_s.train_loss      # training unaffected
        assert r_e.uplink_bytes == r_s.uplink_bytes
        expect_eval = r_s.round % 3 == 0 or r_s.round == 4
        assert r_s.evaluated == expect_eval
        assert r_e.evaluated                          # eval_every=1: all
        if expect_eval:
            np.testing.assert_allclose(r_s.accs, r_e.accs, atol=1e-6)
            last = r_s.accs
        else:
            assert r_s.accs == last                   # carried forward
    # final_accs always reflect the final states, which eval cadence must
    # not perturb
    np.testing.assert_allclose(sparse["final_accs"], every["final_accs"],
                               atol=1e-6)
    for x, y in zip(jax.tree.leaves(every["states"]),
                    jax.tree.leaves(sparse["states"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# Eager and scan compile the same round into different XLA programs, whose
# float32 reductions differ in the last bit.  Adam divides each gradient
# entry by its own running magnitude (g / (sqrt(v) + eps)), so an entry
# near zero turns that last-bit difference into an O(lr) difference in the
# update, and the loss gap grows about a hundredfold a round once visible.
# Measured on XLA:CPU (JAX 0.9.0), rounds 0-3: 1.2e-7, 2.4e-7, 5.4e-6 and
# 8.7e-4 to 9.9e-4 (by host); with Adam's eps raised to 1e-2 round 3 stays
# at 1.2e-7.  The bound is 1e-4 through round 2 and five times the worst
# gap at round 3.
_EAGER_SCAN_LOSS_BOUND = (1e-4, 1e-4, 1e-4, 5e-3)


def test_eval_every_eager_matches_scan(fed_setup):
    """The eager engine honors the same cadence semantics."""
    eager = _run(fed_setup, engine="eager", rounds=4, eval_every=2)
    scan = _run(fed_setup, engine="scan", rounds=4, eval_every=2)
    for r_e, r_s, bound in zip(eager["history"], scan["history"],
                               _EAGER_SCAN_LOSS_BOUND, strict=True):
        assert r_e.evaluated == r_s.evaluated
        assert abs(r_e.train_loss - r_s.train_loss) < bound
        np.testing.assert_allclose(r_e.accs, r_s.accs, atol=1e-3)


def test_eval_every_validation(fed_setup):
    with pytest.raises(ValueError, match="eval_every"):
        _run(fed_setup, eval_every=0)


def test_wall_split_recorded(fed_setup):
    """The scan engine splits wall_s into host staging vs device compute;
    both are positive and bounded by the total."""
    out = _run(fed_setup, scan_prefetch=False)
    for rec in out["history"]:
        assert rec.host_s >= 0.0 and rec.device_s > 0.0
        assert rec.host_s + rec.device_s <= rec.wall_s + 1e-6
    # prefetch on: host stall shrinks to the residual wait, never negative
    out_pf = _run(fed_setup, scan_prefetch=True)
    for rec in out_pf["history"]:
        assert rec.host_s >= 0.0
        assert rec.host_s + rec.device_s <= rec.wall_s + 1e-6
