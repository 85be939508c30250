"""Federated fine-tuning task: frozen backbone + tri-LoRA + local head.

This is the vehicle for reproducing the paper's accuracy experiments at
CPU scale: a small "pre-trained" transformer backbone (optionally warmed up
on IID data, then frozen) with per-client trainable (adapter, classifier
head).  LoRA adapts the attention projections exactly as in the full-size
archs; the head is always local (never transmitted) for every method,
matching the paper's setup where the task head follows the local data.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model
from repro.models.config import ModelConfig
from repro.optim import adamw, apply_updates


class FedTask(NamedTuple):
    cfg: ModelConfig
    base: dict             # frozen backbone params
    n_classes: int

    # ------------------------------------------------------------------ init
    @staticmethod
    def create(key: jax.Array, cfg: ModelConfig, n_classes: int,
               pretrain_batches=None, pretrain_lr: float = 1e-3) -> "FedTask":
        params = model.init_params(cfg, key)
        base = params["base"]
        if pretrain_batches is not None:
            base = _pretrain(cfg, params, pretrain_batches, pretrain_lr,
                             n_classes)
        return FedTask(cfg, base, n_classes)

    def init_client(self, key: jax.Array) -> dict:
        k1, k2 = jax.random.split(key)
        adapter = model.init_adapter(self.cfg, k1)
        head = (jax.random.normal(k2, (self.cfg.d_model, self.n_classes))
                * 0.02).astype(jnp.float32)
        return {"adapter": adapter, "head": head}

    # --------------------------------------------------------------- forward
    def logits(self, adapter: dict, head: jnp.ndarray,
               tokens: jnp.ndarray) -> jnp.ndarray:
        # attn_impl rides on cfg (forward_hidden defers to cfg.attn_impl via
        # attention.select_impl), so every client trains through the
        # configured backend — flash included
        hidden, _, _ = model.forward_hidden(self.cfg, self.base, adapter,
                                            {"tokens": tokens},
                                            attn_impl=self.cfg.attn_impl)
        pooled = jnp.mean(hidden.astype(jnp.float32), axis=1)
        return pooled @ head

    def loss(self, trainable: dict, tokens: jnp.ndarray,
             labels: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        logits = self.logits(trainable["adapter"], trainable["head"], tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return nll, acc

    def features(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Frozen-backbone features for the GMM data-similarity (B=0 adapter
        ⇒ ΔW = 0, so features are adapter-independent)."""
        adapter = model.init_adapter(self.cfg, jax.random.key(0))
        hidden, _, _ = model.forward_hidden(self.cfg, self.base, adapter,
                                            {"tokens": tokens},
                                            attn_impl=self.cfg.attn_impl)
        return jnp.mean(hidden.astype(jnp.float32), axis=1)


def _pretrain(cfg, params, batches, lr, n_classes) -> dict:
    """Brief full-parameter warm-up on IID data; the result is the frozen
    'pre-trained foundation model' the federated phase adapts."""
    head = jnp.zeros((cfg.d_model, n_classes), jnp.float32)
    train = {"base": params["base"], "head": head}
    adapter = params["adapter"]
    opt = adamw(lr=lr)
    state = opt.init(train)

    @jax.jit
    def step(train, state, tokens, labels):
        def lf(tr):
            hidden, _, _ = model.forward_hidden(cfg, tr["base"], adapter,
                                                {"tokens": tokens})
            pooled = jnp.mean(hidden.astype(jnp.float32), axis=1)
            logits = pooled @ tr["head"]
            logp = jax.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        loss, grads = jax.value_and_grad(lf)(train)
        upd, state = opt.update(grads, state, train)
        return apply_updates(train, upd), state, loss

    for b in batches:
        train, state, loss = step(train, state,
                                  jnp.asarray(b["tokens"]),
                                  jnp.asarray(b["labels"]))
    return train["base"]
