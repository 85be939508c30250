"""Stacked tri-LoRA adapter bank for multi-tenant personalized serving
(DESIGN.md §15).

CE-LoRA's personalized aggregation leaves ONE tri-factorized (A, C, B)
adapter per client (paper eqn. 3/10); after training those live stacked on
a leading (m, …) client axis inside every federated checkpoint — the same
layout all three ``client_store`` backends (device / sharded / host) and
both engines (scan / async) write under ``state/adapter``.  This module
turns that training artifact into a serving artifact:

* :func:`export_bank` — load the stacked adapter tree from a checkpoint
  (template-free, validated against the run metadata), ignoring everything
  serving must not depend on: the error-feedback carry (``state/ef``), the
  uplink codec, optimizer state.
* :class:`AdapterBank` — per-request ``user_id → bank row`` lookup plus the
  three views serving needs: ``row(i)`` (one client's adapter tree, for the
  per-user oracle and weight merging), ``decode_tree()`` (group-axis-leading
  bank the batched decode scan consumes), and ``merged_base()`` (eqn. 10
  inference-time merge for the naive baseline).
* :func:`random_bank` — a synthetic bank with non-trivial, per-client
  distinct deltas (freshly initialized adapters have B = 0, i.e. ΔW = 0,
  which would make every heterogeneous-serving test vacuous).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import tri_lora


def _normalize_tail(tree: dict) -> dict:
    """``ckpt.load_subtree`` rebuilds tuple indices as string dict keys;
    decode consumes the tail (and a leading section) as tuples again."""
    out = dict(tree)
    for name in ("tail", "lead"):
        if isinstance(tree.get(name), dict):
            out[name] = tuple(tree[name][k] for k in sorted(tree[name],
                                                            key=int))
    out.setdefault("tail", ())
    if "groups" not in out:
        out["groups"] = None
    return out


def _adapter_leaves(tree: Any) -> list:
    return [a for a in jax.tree.leaves(tree, is_leaf=tri_lora.is_adapter)
            if tri_lora.is_adapter(a)]


@dataclasses.dataclass
class AdapterBank:
    """A stacked (m, …) tri-LoRA adapter tree plus the user → row map.

    ``tree`` mirrors the model's adapter structure ({'groups', 'tail'},
    and 'lead' for a model with leading dense layers) with every {A, C, B}
    leaf carrying a leading client axis: groups leaves are (m, q, …), tail
    and lead leaves (m, …).
    """

    tree: dict
    n_clients: int
    rank: int
    users: Dict[str, int]

    def lookup(self, user_id: str) -> int:
        """Bank row serving this user; unknown users fail loudly."""
        try:
            return self.users[user_id]
        except KeyError:
            raise KeyError(
                f"user {user_id!r} has no adapter bank row (known: "
                f"{sorted(self.users)[:8]}…)") from None

    def rows(self, user_ids: Sequence[Optional[str]]) -> jnp.ndarray:
        """(B,) int32 row indices; ``None`` entries (empty batch slots)
        become -1, the masked-row sentinel of the grouped kernels."""
        return jnp.asarray([-1 if u is None else self.lookup(u)
                            for u in user_ids], jnp.int32)

    def row(self, i: int) -> dict:
        """One client's adapter tree — exactly what ``model.decode_step``
        takes as ``adapter`` (groups leaves (q, …), tail a tuple)."""
        if not 0 <= i < self.n_clients:
            raise IndexError(f"bank row {i} out of range "
                             f"[0, {self.n_clients})")
        return jax.tree.map(lambda x: jnp.asarray(x)[i], self.tree)

    def decode_tree(self) -> dict:
        """Bank view for the batched decode scan: the layer-group axis must
        LEAD the scanned xs, so groups leaves become (q, m, …); tail and
        lead leaves stay (m, …)."""
        out = {k: jax.tree.map(jnp.asarray, v)
               for k, v in self.tree.items() if k != "groups"}
        out["groups"] = None
        if self.tree.get("groups") is not None:
            out["groups"] = jax.tree.map(
                lambda x: jnp.swapaxes(jnp.asarray(x), 0, 1),
                self.tree["groups"])
        return out

    def merged_base(self, base: dict, i: int, scaling: float) -> dict:
        """Paper eqn. 10: W_i = W + s·A_i·C_i·B_i folded into the base
        params — the naive per-user serving baseline."""
        row = self.row(i)

        def _merge(b, a):
            if a is None:
                return b
            if tri_lora.is_adapter(a):
                return tri_lora.merge(b, a, scaling)
            if isinstance(a, dict):
                return {k: (_merge(b[k], a[k]) if k in a else b[k])
                        for k in b}
            return tuple(_merge(bb, aa) for bb, aa in zip(b, a))

        out = dict(base)
        if base.get("groups") is not None and row.get("groups") is not None:
            out["groups"] = _merge(base["groups"], row["groups"])
        out["tail"] = _merge(base["tail"], row["tail"])
        if "lead" in row:
            out["lead"] = _merge(base["lead"], row["lead"])
        return out


def _validate(tree: dict, n_clients: int, path: str) -> int:
    leaves = _adapter_leaves(tree)
    if not leaves:
        raise ValueError(
            f"checkpoint {path!r} stores no tri-LoRA {{A,B,C}} nodes under "
            f"state/adapter — not a federated fine-tuning checkpoint")
    ranks = set()
    for ad in leaves:
        for k in ("A", "B", "C"):
            if ad[k].shape[0] != n_clients:
                raise ValueError(
                    f"checkpoint {path!r}: adapter leaf {k} has leading dim "
                    f"{ad[k].shape[0]} but metadata says n_clients="
                    f"{n_clients} — stacked client axis mismatch")
        ranks.add(int(ad["C"].shape[-1]))
    if len(ranks) != 1:
        raise ValueError(f"checkpoint {path!r}: inconsistent tri-LoRA ranks "
                         f"{sorted(ranks)} across adapter leaves")
    return ranks.pop()


def export_bank(path: str,
                user_ids: Optional[Sequence[str]] = None) -> AdapterBank:
    """Export the stacked adapter bank from a federated checkpoint.

    Works on checkpoints from every engine/store combination because they
    all persist the same ``state/adapter`` stacked subtree; the EF carry
    (``state/ef``), optimizer moments, and the uplink codec are wire/train
    artifacts and are deliberately NOT read.  Validation is fail-loud: a
    checkpoint without federated metadata, without adapter leaves, or whose
    stacked client axis contradicts ``n_clients`` raises ``ValueError``.

    ``user_ids`` maps request identities to bank rows positionally
    (defaults to ``client-0 … client-{m-1}``).
    """
    meta = ckpt.metadata(path)
    if "n_clients" not in meta:
        raise ValueError(
            f"checkpoint {path!r} has no 'n_clients' in its metadata — not "
            f"a federated checkpoint (or written before the adapter-bank "
            f"layout, DESIGN.md §15); cannot export an adapter bank")
    m = int(meta["n_clients"])
    sub = ckpt.load_subtree(path, "state/adapter")
    if not sub:
        raise ValueError(
            f"checkpoint {path!r} stores nothing under state/adapter — "
            f"cannot export an adapter bank")
    tree = _normalize_tail(sub)
    rank = _validate(tree, m, path)
    if user_ids is None:
        user_ids = [f"client-{i}" for i in range(m)]
    if len(user_ids) != m:
        raise ValueError(f"{len(user_ids)} user_ids for {m} bank rows")
    return AdapterBank(tree=tree, n_clients=m, rank=rank,
                       users={u: i for i, u in enumerate(user_ids)})


def random_bank(cfg, m: int, key: jax.Array,
                user_ids: Optional[Sequence[str]] = None) -> AdapterBank:
    """Synthetic m-row bank with DISTINCT non-zero deltas per client.

    Freshly initialized tri-LoRA adapters are exact no-ops (B = 0), so a
    bank of them cannot distinguish correct heterogeneous routing from
    ignoring the adapters entirely; here B is drawn random and C is a
    perturbed identity, keeping deltas small but row-distinct.
    """
    from repro.models import transformer

    ag, at = transformer.init_stack_adapters(key, cfg, cross=cfg.enc_dec)
    proto = {"groups": ag, "tail": at}
    if cfg.n_dense_layers:
        proto["lead"] = transformer.init_lead_adapters(key, cfg)
    leaves, treedef = jax.tree.flatten(proto, is_leaf=tri_lora.is_adapter)
    out = []
    for j, ad in enumerate(leaves):
        if not tri_lora.is_adapter(ad):
            out.append(ad)
            continue
        r = ad["C"].shape[-1]
        ka, kb, kc = jax.random.split(jax.random.fold_in(key, j), 3)
        out.append({
            "A": (jax.random.normal(ka, (m,) + ad["A"].shape, jnp.float32)
                  / np.sqrt(r)),
            "C": (jnp.eye(r, dtype=jnp.float32)
                  + 0.1 * jax.random.normal(kc, (m,) + ad["C"].shape,
                                            jnp.float32)),
            "B": 0.02 * jax.random.normal(kb, (m,) + ad["B"].shape,
                                          jnp.float32),
        })
    tree = jax.tree.unflatten(treedef, out)
    if user_ids is None:
        user_ids = [f"client-{i}" for i in range(m)]
    return AdapterBank(tree=tree, n_clients=m, rank=int(cfg.lora_rank),
                       users={u: i for i, u in enumerate(user_ids)})
