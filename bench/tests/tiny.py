"""Small sizes of the benchmark's cells, for runs on the CPU."""
from __future__ import annotations

import argparse

import jax

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512}
PROGRAM = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
           "head_dim": "head_dim", "num_hidden_layers": "n_layers",
           "vocab_size": "vocab_size"}


def tiny_config(config: dict, **kw) -> dict:
    """The configuration at small widths, keeping its kind of norm, MLP,
    bias, GQA grouping (two KV heads) and adapters."""
    c = {**config, **TINY, "num_key_value_heads": 2, **kw}
    c["program"] = {"arch": config["program"]["arch"],
                    "overrides": {PROGRAM[k]: c[k] for k in PROGRAM}}
    return c


def cpu_chips(chips, peaks):
    return jax.devices("cpu")[:1], {"bf16_flops_per_s": 1e12,
                                    "hbm_bytes_per_s": 1e11}


def args(workload, seed=3, seconds=1.0, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)


def shrink(limits=None):
    """An ``adjust`` for ``run_cell``: tiny widths, small traffic."""
    def adjust(cell):
        cell.config = tiny_config(cell.config)
        cell.mix = dict(cell.mix, users=6, slots=4, max_len=48, requests=8,
                        prompt=dict(cell.mix["prompt"], min=2, max=24,
                                    median=8),
                        output=dict(cell.mix["output"], min=2, max=24,
                                    median=8),
                        check_tokens=40)
        if limits is not None:
            cell.limits = limits
    return adjust
