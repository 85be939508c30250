"""ClientStore: where the m-client federated population lives (DESIGN.md §12).

The vectorized runtimes of :mod:`repro.core.federated` /
:mod:`repro.core.fed_engine` keep ALL m clients' state — tri-LoRA adapters,
EF residuals, pFedMe anchors — as one device-resident stacked pytree with a
leading (m, …) client axis.  That caps the population at device memory,
while the paper's cross-device setting (and the CELLM / pFedLoRA framings
in PAPERS.md) assumes populations far larger than any single accelerator:
resident memory must scale with per-round PARTICIPATION, not population.

This module makes the population's residency a first-class backend choice
(``FedConfig.client_store``):

* ``"device"`` — the legacy runtime, bit for bit: one stacked pytree on the
  default device, whole-population round programs.
* ``"sharded"`` — the stacked client axis laid over a 1-D ``("clients",)``
  device mesh (:func:`repro.launch.mesh.make_client_mesh`); cohort
  gather/scatter run as ``shard_map`` collectives (masked local take +
  ``psum`` combine / masked ``.at[].set`` drop-scatter), so no device ever
  materializes more than its m/d shard plus the k-row cohort.  CPU-emulated
  in CI with ``--xla_force_host_platform_device_count=N``.
* ``"host"`` — the population lives in host numpy; only the ACTIVE COHORT
  (the round's sampled clients — stragglers included, since they train) is
  gathered host→device, fitted by a fused per-round program, and written
  back post-round.  Device residency is O(k) client rows plus, for
  personalized aggregation, an O(m) bank of the tiny r×r C payloads (the
  CKA row refresh compares a refreshed row against ALL m columns, and the
  compressed runtime must re-encode every client's frozen C under the
  round's key stream) — never the O(m) full adapter/optimizer state.

Store contract (uniform across backends, proven by the store-parametrized
harness in tests/test_client_store.py):

* ``gather(ids)`` returns the cohort rows as a device pytree; ``scatter``
  writes updated cohort rows back.  ``scatter(ids, gather(ids))`` is the
  identity on the population for ANY id subset (empty, full, arbitrary).
* gather is ordered strictly AFTER the previous round's write-back — the
  cohort always sees the population as of the last completed round.
* backend choice is invisible to the training history: device ≡ sharded ≡
  host ``RoundRecord`` streams for the same ``FedConfig`` (same contract
  and tolerances as the eager⇄scan equivalence).

The host backend's round loop (:func:`run_cohort`) reuses the scan
pipeline's overlap machinery: cohort batches for round r+1 are drawn and
stacked by a :class:`repro.core.client_batch.ChunkPrefetcher` producer
thread while round r computes, and every non-cohort loader is
RNG-fast-forwarded (:meth:`repro.data.pipeline.Loader.skip`) so the data
streams stay draw-equivalent with the all-m engines.  Chunk-cadence
checkpoints store the full host population with the shared run fingerprint
(including ``client_store``), so kill-then-resume reproduces the
uninterrupted history exactly — EF residuals are written back only at
round end, so a kill between fit and write-back simply replays the round.
"""
from __future__ import annotations

import functools
import os
import time
import warnings
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint import ckpt
from repro.core import (admission, aggregation, client_batch, comm, compress,
                        faults, sampling)
from repro.core.jit_cache import JitCache
from repro.core.similarity import cka

STORE_BACKENDS = ("device", "sharded", "host")

_COHORT_CACHE = JitCache(maxsize=8)
_COHORT_EVAL_CACHE = JitCache(maxsize=8)


def make_store(backend: str, states: Sequence[Any], *,
               parallelism: str = "vmap"):
    """Build the population store for ``backend`` from m per-client states.

    ``parallelism`` is the legacy ``FedConfig.client_parallelism`` mode:
    the ``device`` store honors its ``"shard"`` placement (NamedSharding
    over the client mesh) so pre-§12 configs behave bit-for-bit.
    """
    if backend not in STORE_BACKENDS:
        raise ValueError(f"client_store={backend!r}; "
                         f"expected one of {STORE_BACKENDS}")
    if backend == "sharded":
        return ShardedClientStore(states)
    if backend == "host":
        return HostClientStore(states)
    return DeviceClientStore(states, shard=(parallelism == "shard"))


class DeviceClientStore:
    """The legacy backend: the whole population as one device-resident
    stacked pytree.  ``gather``/``scatter`` are plain row indexing — they
    exist so the store contract (and its property tests) is uniform."""

    backend = "device"

    def __init__(self, states: Sequence[Any], *, shard: bool = False):
        self.m = len(states)
        self._stacked = client_batch.stack_states(states)
        self._place = lambda t: t
        if shard:
            from repro.launch import mesh as mesh_lib
            cmesh = mesh_lib.make_client_mesh(self.m)
            self._place = functools.partial(mesh_lib.shard_clients, cmesh)
            self._stacked = self._place(self._stacked)

    def resident(self) -> Any:
        """The device-resident stacked population the round programs own.
        Engines that update it wholesale (scan carry, eager stacked loop)
        must hand it back via :meth:`adopt`."""
        return self._stacked

    def adopt(self, stacked: Any) -> None:
        """Install an engine-updated stacked population as current."""
        self._stacked = stacked

    def place(self, tree: Any) -> Any:
        """Lay a client-axis tree out the way the population is laid out."""
        return self._place(tree)

    def gather(self, ids) -> Any:
        return client_batch.gather_clients(self._stacked, ids)

    def scatter(self, ids, values: Any) -> None:
        self._stacked = client_batch.scatter_clients(self._stacked, ids,
                                                     values)

    def unstack(self) -> list:
        return client_batch.unstack_states(self._stacked)


class ShardedClientStore:
    """Client axis sharded over the 1-D ``("clients",)`` device mesh.

    The stacked population is placed with
    :func:`repro.launch.mesh.shard_clients`, so each of the d mesh devices
    owns an m/d row block.  Cohort gather/scatter are ``shard_map``
    programs over that layout:

    * gather — every device takes its LOCAL rows of the (replicated) id
      vector via a masked block index, zeros the rows it does not own, and
      a ``psum`` over ``"clients"`` combines the blocks into the
      replicated (k, …) cohort (each global row has exactly one owner, so
      the sum is exact, not an average).
    * scatter — each device maps the ids it owns to block-local positions
      and drop-scatters everyone else's rows out of range
      (``.at[pos].set(..., mode="drop")``), leaving its block's other rows
      untouched.

    Ids must be unique (participation plans are sorted unique by
    construction); duplicate ids would race in the scatter.
    """

    backend = "sharded"

    def __init__(self, states: Sequence[Any]):
        from repro.launch import mesh as mesh_lib
        self.m = len(states)
        self.mesh = mesh_lib.make_client_mesh(self.m)
        self._place = functools.partial(mesh_lib.shard_clients, self.mesh)
        self._stacked = self._place(client_batch.stack_states(states))

        @jax.jit
        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P("clients"), P()), out_specs=P())
        def _gather(block_tree, ids):
            lo = jax.lax.axis_index("clients") * (self.m
                                                  // self.mesh.devices.size)

            def one(block):
                per = block.shape[0]
                local = (ids >= lo) & (ids < lo + per)
                rows = block[jnp.where(local, ids - lo, 0)]
                mask = local.reshape((-1,) + (1,) * (rows.ndim - 1))
                return jax.lax.psum(jnp.where(mask, rows,
                                              jnp.zeros_like(rows)),
                                    "clients")

            return jax.tree.map(one, block_tree)

        @jax.jit
        @functools.partial(jax.shard_map, mesh=self.mesh,
                           in_specs=(P("clients"), P(), P()),
                           out_specs=P("clients"))
        def _scatter(block_tree, ids, vals_tree):
            lo = jax.lax.axis_index("clients") * (self.m
                                                  // self.mesh.devices.size)

            def one(block, vals):
                per = block.shape[0]
                local = (ids >= lo) & (ids < lo + per)
                pos = jnp.where(local, ids - lo, per)   # per = out of range
                return block.at[pos].set(vals.astype(block.dtype),
                                         mode="drop")

            return jax.tree.map(one, block_tree, vals_tree)

        self._gather_fn = _gather
        self._scatter_fn = _scatter

    def resident(self) -> Any:
        return self._stacked

    def adopt(self, stacked: Any) -> None:
        self._stacked = stacked

    def place(self, tree: Any) -> Any:
        return self._place(tree)

    def gather(self, ids) -> Any:
        return self._gather_fn(self._stacked, jnp.asarray(ids, jnp.int32))

    def scatter(self, ids, values: Any) -> None:
        self._stacked = self._scatter_fn(self._stacked,
                                         jnp.asarray(ids, jnp.int32), values)

    def unstack(self) -> list:
        return client_batch.unstack_states(self._stacked)


class HostClientStore:
    """Population in host numpy; gather materializes cohort rows on device,
    scatter writes device rows back into the host arrays in place.  The
    device round program never sees a leaf wider than the cohort."""

    backend = "host"

    def __init__(self, states: Sequence[Any]):
        self.m = len(states)
        self.population = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *states)

    def load(self, population: Any) -> None:
        """Replace the population wholesale (checkpoint restore)."""
        self.population = population

    def gather(self, ids) -> Any:
        ids = np.asarray(ids)
        return jax.tree.map(lambda l: jnp.asarray(l[ids]), self.population)

    def scatter(self, ids, values: Any) -> None:
        ids = np.asarray(ids)

        def write(l, v):
            l[ids] = np.asarray(v).astype(l.dtype, copy=False)
        jax.tree.map(write, self.population, values)

    def unstack(self) -> list:
        return [jax.tree.map(lambda l: l[i], self.population)
                for i in range(self.m)]


# ---------------------------------------------------------------------------
# host-backed cohort engine
# ---------------------------------------------------------------------------

def _build_cohort_fn(strategy, fed, local_fit: Callable,
                     use_data: bool, use_model: bool):
    """One jitted program per round: fit the k-row cohort, maintain the
    all-m payload/EF banks, refresh S^model rows, aggregate over the
    cohort, install — the cohort-resident analogue of the scan engine's
    ``round_step`` (which it must match allclose; tests/test_client_store).

    The aggregation restriction is exact, not approximate: participants ⊆
    sampled = cohort, so every nonzero column of the personalized weight
    matrix (and every nonzero FedAvg weight) indexes a cohort row —
    ``W[cohort, cohort] @ served_cohort`` equals the all-m mix.
    """
    vfit = jax.vmap(local_fit)
    eta = fed.pfedme_eta
    self_weight = fed.self_weight
    codec = compress.get_codec(fed.uplink_codec)
    communicates = strategy.aggregate != "none"
    compressed = not codec.is_identity and communicates
    personalized = strategy.aggregate == "personalized"
    seed = fed.seed
    m = fed.n_clients
    # §16 robustness — every new op below is gated on these static flags,
    # so the fault-free config traces the legacy cohort program unchanged
    fm = faults.fault_model_of(fed)
    adm = admission.control_of(fed)
    robust = fm.active or adm.enabled

    def cohort_step(cohort, bank, ef_bank, s_model, adm_state, xs, consts):
        if fm.active:
            # fault masks arrive cohort-local: (k,) rows over SORTED sampled
            toks, labs, pml, pmf, cids, rnd, fc_l, fl_l, fcor_l, fv_l = xs
        else:
            toks, labs, pml, pmf, cids, rnd = xs
        prev_c = dict(cohort)
        tr = strategy.trainable(cohort)
        w_ref = cohort.get("w", {})
        # the whole cohort trains (stragglers too); pml masks the install
        tr, losses = vfit(tr, w_ref, toks, labs)
        new = dict(cohort)
        new.update(tr)
        cohort = strategy.after_local(new, eta)
        if fm.active:
            # crash: the round's local work is lost; divergent: the client's
            # divergence detection resets to the round start
            cohort = client_batch.select_clients(~(fc_l | fv_l), cohort,
                                                 prev_c)

        payload = strategy.uplink(cohort)
        if fm.active and fm.divergent > 0:
            # the divergent upload is the blowup the norm gate must catch
            payload = faults.scale_rows(payload, fv_l, fm.divergent_scale)
        if fm.active:
            sent_l = pml & ~fc_l             # left the device at all
            delivered_l = sent_l & ~fl_l     # reached the server
        else:
            delivered_l = pml
        if use_model:
            # post-fit Cs join the all-m bank BEFORE encode/refresh: the
            # CKA columns (and the compressed re-encode) must see sampled
            # clients' fresh Cs and everyone else's frozen ones
            bank = client_batch.scatter_clients(bank, cids, payload)
        enc_c = None
        ef_all = ef_new = None
        if compressed:
            if use_model:
                # the device engines encode ALL m every round (key stream
                # folded per (round, client)), and unsampled clients'
                # decoded Cs vary per round through it — so equivalence
                # requires the full-bank encode, not a cohort-only one
                enc_all, dec_all, ef_all = compress.encode_stacked(
                    codec, bank, ef_bank, compress.client_keys(seed, rnd, m))
                if not robust:
                    ef_bank = client_batch.select_clients(pmf, ef_all,
                                                          ef_bank)
                    cohort = dict(cohort, ef=client_batch.gather_clients(
                        ef_bank, cids))
                if fm.active and fm.corrupt > 0:
                    enc_c = client_batch.gather_clients(enc_all, cids)
                served_all = dec_all
                served = client_batch.gather_clients(dec_all, cids)
            else:
                # no CKA ⇒ only cohort payloads are ever consumed; the
                # per-(round, client) keys are independent folds, so the
                # cohort-only encode equals the all-m one row for row
                keys = jax.vmap(
                    lambda i: compress.client_key(seed, rnd, i))(cids)
                enc_c, served, ef_new = compress.encode_stacked(
                    codec, payload, cohort["ef"], keys)
                if not robust:
                    cohort = dict(cohort, ef=client_batch.select_clients(
                        pml, ef_new, cohort["ef"]))
                served_all = None
        else:
            served = payload
            served_all = bank
        if fm.active and fm.corrupt > 0 and communicates:
            served = faults.corrupt_served(codec if compressed else None,
                                           enc_c, served,
                                           delivered_l & fcor_l,
                                           fm.corrupt_mode)
            if served_all is not None:
                # the server's m-wide CKA view must see the mangled rows too
                served_all = client_batch.scatter_clients(served_all, cids,
                                                          served)
        accept_l = delivered_l
        if robust and communicates:
            if adm.enabled:
                # participants ⊆ cohort, so the k-row gate computes the
                # same masked medians as the device engines' m-row one
                norms, finite = admission.payload_stats(served)
                accept_l, adm_state = admission.admit(
                    norms, finite, delivered_l, adm_state, adm)
            if compressed:
                # EF advances only for ACCEPTED uploads — rejection rolls
                # the residual back by never installing the new one
                if use_model:
                    accept_f = jnp.zeros(m, bool).at[cids].set(accept_l)
                    ef_bank = client_batch.select_clients(accept_f, ef_all,
                                                          ef_bank)
                    cohort = dict(cohort, ef=client_batch.gather_clients(
                        ef_bank, cids))
                else:
                    cohort = dict(cohort, ef=client_batch.select_clients(
                        accept_l, ef_new, cohort["ef"]))
        agg_l = accept_l if robust and communicates else pml
        agg_f = (jnp.zeros(m, bool).at[cids].set(accept_l)
                 if robust and communicates else pmf)
        weights = None
        if personalized:
            sims = []
            if use_data:
                sims.append(consts["s_data"])
            if use_model:
                cs = cka.stacked_cs(served_all)
                refreshed = cka.refresh_rows_inline(s_model, cs, cids,
                                                    consts["probes"])
                if robust:
                    # refresh only ACCEPTED rows; pairs touching a sampled-
                    # but-unaccepted client keep their previous entry
                    smask_f = jnp.zeros(m, bool).at[cids].set(True)
                    clean = jnp.logical_not(smask_f) | agg_f
                    valid = ((agg_f[:, None] & clean[None, :])
                             | (agg_f[None, :] & clean[:, None]))
                    s_model = jnp.where(valid, refreshed, s_model)
                else:
                    s_model = refreshed
                sims.append(s_model)
            if not sims:
                raise ValueError(
                    f"celora needs at least one similarity term; got "
                    f"use_data_sim={use_data}, use_model_sim={use_model}")
            w_full = aggregation.personalized_weights(sum(sims), self_weight,
                                                      agg_f)
            # nonzero columns all live in the cohort (see docstring), so
            # the k×k restriction reproduces the all-m mix exactly
            weights = w_full[cids[:, None], cids[None, :]]
        if robust and communicates:
            # rejected/undelivered rows may hold NaN/Inf; their weight is 0
            # but 0 x NaN still poisons the aggregation einsum
            served = faults.zero_rows(served, accept_l)
        down = strategy.server_stacked(
            served, sample_counts=consts["counts"][cids],
            weights=weights, participants=agg_l)
        if down is not None:
            cohort = client_batch.select_clients(
                agg_l, strategy.install(cohort, down), cohort)
        if use_model:
            # re-scatter AFTER install: participants' resident Cs changed;
            # the bank row contract is "each client's CURRENT C"
            bank = client_batch.scatter_clients(bank, cids,
                                                strategy.uplink(cohort))
        return (cohort, bank, ef_bank, s_model, adm_state,
                jnp.mean(losses), accept_l)

    return jax.jit(cohort_step)


def run_cohort(*, task, fed, strategy, states: list, loaders: Sequence,
               sample_counts: Sequence[int],
               plans: Sequence[sampling.ParticipationPlan],
               local_fit: Callable, eval_one: Callable,
               s_data: Optional[np.ndarray],
               test_toks: np.ndarray, test_labs: np.ndarray,
               verbose: bool = False) -> dict:
    """The ``client_store="host"`` body of ``run_federated`` (both
    engines): host-resident population, device-resident cohorts.  Returns
    the identical result dict as the other engine bodies.

    ``test_toks``/``test_labs`` are HOST arrays (m, pad, T)/(m, pad): eval
    streams them through device slabs so the device never holds the full
    m-client test stack either.
    """
    from repro.core import fed_engine
    from repro.core.federated import RoundRecord, _do_eval, _print_round

    m = fed.n_clients
    k = len(plans[0].sampled)
    if any(len(p.sampled) != k for p in plans):
        raise ValueError("run_cohort needs a round-invariant sampled count "
                         "(one compiled cohort program)")
    chunk = max(1, int(fed.chunk_rounds))
    scan_engine = fed.engine == "scan"
    store = HostClientStore(states)
    del states

    codec = compress.get_codec(fed.uplink_codec)
    communicates = strategy.aggregate != "none"
    compressed = not codec.is_identity and communicates
    personalized = strategy.aggregate == "personalized"
    use_data = personalized and fed.use_data_sim and s_data is not None
    use_model = personalized and fed.use_model_sim

    # ---- §16 robustness: seeded fault draws + admission state (host side)
    fm = faults.fault_model_of(fed)
    adm = admission.control_of(fed)
    robust = fm.active or adm.enabled
    adm_state = admission.init_state(adm.window) if adm.enabled else None
    fdraws = ([fm.draw(m, rnd, fed.seed) for rnd in range(fed.rounds)]
              if fm.active else None)

    # ---- byte pricing: identical to the device engines, from eval_shape
    pop_struct = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), store.population)
    payload_struct = jax.eval_shape(strategy.uplink, pop_struct)
    per_down_b, _ = comm.per_client_comm(payload_struct)
    per_b, per_e = comm.per_client_comm(
        compress.wire_struct(codec, payload_struct, m)
        if compressed and payload_struct is not None else payload_struct)
    if not compressed:
        per_down_b = per_b

    def _build_banks():
        bank = ef_bank = None
        if use_model:
            bank = jax.tree.map(jnp.asarray, strategy.uplink(store.population))
            if compressed:
                ef_bank = jax.tree.map(jnp.asarray, store.population["ef"])
        return bank, ef_bank

    bank, ef_bank = _build_banks()
    s_model = None
    probes = None
    if use_model:
        r = cka.stacked_cs(bank).shape[-1]
        probes = jax.random.normal(jax.random.key(fed.seed + 97),
                                   (fed.cka_probes, r), jnp.float32)
        s_model = cka.pairwise_model_similarity_stacked(
            bank, jax.random.key(fed.seed + 97), fed.cka_probes)

    consts = {"counts": jnp.asarray(np.asarray(sample_counts, np.int64)),
              "s_data": jnp.asarray(s_data) if use_data else None,
              "probes": probes}

    step = _COHORT_CACHE.get_or_build(
        (task.base, task.cfg),
        ("cohort", strategy.name, fed.lr, fed.local_steps, fed.batch_size,
         fed.pfedme_eta, fed.self_weight, use_data, use_model,
         fed.uplink_codec, fed.seed if compressed else None,
         fed.fault_crash, fed.fault_loss, fed.fault_corrupt,
         fed.fault_corrupt_mode, fed.fault_divergent,
         fed.fault_divergent_scale, fed.admission, fed.admission_norm_mult,
         fed.admission_window),
        lambda: _build_cohort_fn(strategy, fed, local_fit,
                                 use_data, use_model))
    veval = _COHORT_EVAL_CACHE.get_or_build(
        (task.base, task.cfg), ("cohort-eval", strategy.name),
        lambda: jax.jit(jax.vmap(eval_one)))

    def eval_population() -> list:
        # slabbed eval: device residency stays O(slab), not O(m)
        slab = max(k, min(m, 64))
        out = np.zeros(m, np.float32)
        for lo in range(0, m, slab):
            ids = np.arange(lo, min(lo + slab, m))
            st = store.gather(ids)
            out[ids] = np.asarray(
                veval(strategy.trainable(st), jnp.asarray(test_toks[ids]),
                      jnp.asarray(test_labs[ids])))
        return [float(v) for v in out]

    # ---- resume from a chunk-boundary checkpoint (scan engine contract)
    hist_loss: list = []
    hist_accs: list = []
    hist_wall: list = []
    hist_acc_rows: list = []       # per-round (m,) accepted-upload masks
    start = 0
    if scan_engine and fed.checkpoint_path and fed.resume:
        if not os.path.exists(fed.checkpoint_path):
            warnings.warn(f"resume: no checkpoint at "
                          f"{fed.checkpoint_path!r} — starting from round 0 "
                          f"(checkpoints will be written there)")
        else:
            meta = ckpt.metadata(fed.checkpoint_path)
            if "rounds_done" not in meta:
                raise ValueError(f"{fed.checkpoint_path!r} is not a "
                                 f"scan-engine checkpoint (no rounds_done "
                                 f"in metadata)")
            ckpt.check_fingerprint(
                fed.checkpoint_path, meta, fed_engine._fingerprint(fed),
                defaults=dict({"uplink_codec": "none", "eval_every": 1,
                               "client_store": "device",
                               "attn_impl": "auto"},
                              **fed_engine.ROBUSTNESS_DEFAULTS),
                ignore=("rounds",))
            start = int(meta["rounds_done"])
            if start > fed.rounds:
                raise ValueError(f"checkpoint has {start} completed rounds "
                                 f"but the run asks for only {fed.rounds}")
            like = {"state": store.population,
                    "loss": np.zeros((start,), np.float32),
                    "accs": np.zeros((start, m), np.float32),
                    "wall": np.zeros((start,), np.float32)}
            if s_model is not None:
                like["s_model"] = np.zeros(s_model.shape, np.float32)
            if adm_state is not None:
                like["admission"] = {"meds": np.zeros((adm.window,),
                                                      np.float32),
                                     "count": np.zeros((), np.int32)}
            if robust:
                like["accept"] = np.zeros((start, m), bool)
            tree = ckpt.restore(fed.checkpoint_path, like, as_numpy=True)
            store.load(tree["state"])
            bank, ef_bank = _build_banks()   # bank rows = current Cs
            if s_model is not None:
                s_model = jnp.asarray(tree["s_model"])
            if adm_state is not None:
                adm_state = jax.tree.map(jnp.asarray, tree["admission"])
            if robust:
                hist_acc_rows = [np.asarray(row, bool)
                                 for row in tree["accept"]]
            hist_loss = [float(v) for v in tree["loss"]]
            hist_accs = [list(map(float, row)) for row in tree["accs"]]
            hist_wall = [float(v) for v in tree["wall"]]
            # fast-forward every per-client stream over the done rounds
            for _ in range(start):
                for ld in loaders:
                    ld.skip(fed.local_steps)
            if verbose:
                print(f"[{strategy.name}] resumed {start} rounds "
                      f"from {fed.checkpoint_path}")

    def _save(rounds_done: int) -> None:
        tree = {"state": store.population,
                "loss": np.asarray(hist_loss, np.float32),
                "accs": np.asarray(hist_accs, np.float32),
                "wall": np.asarray(hist_wall, np.float32)}
        if s_model is not None:
            tree["s_model"] = np.asarray(s_model)
        if adm_state is not None:
            tree["admission"] = jax.tree.map(np.asarray, adm_state)
        if robust:
            tree["accept"] = np.asarray(hist_acc_rows, bool)
        ckpt.save(fed.checkpoint_path, tree,
                  metadata=dict(fed_engine._fingerprint(fed), engine="scan",
                                strategy=strategy.name,
                                rounds_done=rounds_done))

    def _round_stats(rnd: int, plan, accept_row) -> tuple:
        """(n_up, n_down, rejected_ids, failed_ids) — the robust history
        fields; the fault-free values when ``robust`` is off."""
        if not robust:
            return (plan.n_participants, plan.n_participants, [], [])
        pm = plan.mask(m)
        if fm.active:
            fd = fdraws[rnd]
            sent = pm & ~fd.crash
            delivered = sent & ~fd.loss
            failed = np.nonzero(pm & (fd.crash | fd.loss))[0].tolist()
        else:
            sent = delivered = pm
            failed = []
        acc = np.asarray(accept_row, bool)
        n_down = int(acc.sum()) if communicates else plan.n_participants
        return (int(sent.sum()), n_down,
                np.nonzero(delivered & ~acc)[0].tolist(), failed)

    history: list = []
    for rnd in range(start):
        plan = plans[rnd]
        n_up, n_down, rejected, failed = _round_stats(
            rnd, plan, hist_acc_rows[rnd] if robust else None)
        history.append(RoundRecord(
            rnd, hist_loss[rnd], hist_accs[rnd],
            uplink_bytes=per_b * n_up,
            downlink_bytes=per_down_b * n_down,
            wall_s=hist_wall[rnd],
            participants=plan.participants.tolist(),
            sampled=plan.sampled.tolist(), dropped=plan.dropped.tolist(),
            uplink_elems=per_e * n_up,
            evaluated=_do_eval(rnd, fed),
            rejected=rejected, failed=failed))

    accs = hist_accs[-1][:] if start else [0.0] * m
    rounds_left = list(range(start, fed.rounds))
    prefetcher = None
    if scan_engine and fed.scan_prefetch and rounds_left:
        plan_iter = iter([plans[r] for r in rounds_left])

        def produce(_n):
            return client_batch.stack_cohort_batches(
                loaders, next(plan_iter).sampled, fed.local_steps)

        prefetcher = client_batch.ChunkPrefetcher(produce,
                                                  [1] * len(rounds_left))
    try:
        for rnd in rounds_left:
            plan = plans[rnd]
            t0 = time.perf_counter()
            if prefetcher is not None:
                (toks, labs), _produce_s = prefetcher.get()
            else:
                toks, labs = client_batch.stack_cohort_batches(
                    loaders, plan.sampled, fed.local_steps)
            t_fetch = time.perf_counter()
            # gather strictly AFTER the previous round's write-back: the
            # cohort sees the population as of the last completed round
            cohort = store.gather(plan.cohort)
            xs = (toks, labs,
                  jnp.asarray(plan.cohort_mask()),
                  jnp.asarray(plan.mask(m)),
                  jnp.asarray(plan.sampled.astype(np.int32)),
                  jnp.asarray(rnd, jnp.int32))
            if fm.active:
                fd = fdraws[rnd]
                xs = xs + tuple(jnp.asarray(f[plan.sampled]) for f in
                                (fd.crash, fd.loss, fd.corrupt, fd.divergent))
            cohort, bank, ef_bank, s_model, adm_state, loss, accept_l = step(
                cohort, bank, ef_bank, s_model, adm_state, xs, consts)
            loss = float(loss)                 # host sync before write-back
            store.scatter(plan.cohort, cohort)
            accept_row = None
            if robust:
                accept_row = np.zeros(m, bool)
                accept_row[plan.sampled] = np.asarray(accept_l)
                hist_acc_rows.append(accept_row)
            evaluated = _do_eval(rnd, fed)
            if evaluated:
                accs = eval_population()
            t_done = time.perf_counter()
            hist_loss.append(loss)
            hist_accs.append(list(accs))
            hist_wall.append(t_done - t0)
            n_up, n_down, rejected, failed = _round_stats(rnd, plan,
                                                          accept_row)
            history.append(RoundRecord(
                rnd, loss, list(accs),
                uplink_bytes=per_b * n_up,
                downlink_bytes=per_down_b * n_down,
                wall_s=t_done - t0,
                participants=plan.participants.tolist(),
                sampled=plan.sampled.tolist(), dropped=plan.dropped.tolist(),
                uplink_elems=per_e * n_up,
                host_s=t_fetch - t0, device_s=t_done - t_fetch,
                evaluated=evaluated,
                rejected=rejected, failed=failed))
            if verbose:
                _print_round(strategy, history[-1])
            if scan_engine and fed.checkpoint_path and \
                    ((rnd + 1 - start) % chunk == 0 or rnd == fed.rounds - 1):
                _save(rnd + 1)
    finally:
        if prefetcher is not None:
            prefetcher.close()

    return {
        "method": strategy.name,
        "history": history,
        "final_accs": history[-1].accs,
        "mean_acc": history[-1].mean_acc,
        "min_acc": history[-1].min_acc,
        "max_acc": history[-1].max_acc,
        "uplink_floats_per_round": history[-1].uplink_elems,
        "uplink_bytes_per_round": history[-1].uplink_bytes,
        "downlink_bytes_per_round": history[-1].downlink_bytes,
        "states": store.unstack(),
    }
