"""Paper Table III — per-round communication cost (uplink floats/client).

Exact analytic accounting per method on the paper's own model shapes
(RoBERTa-base, LLaMA-7B) AND on every assigned architecture's tri-LoRA
layout.  Validated against the paper's stated ratios (LLaMA: CE-LoRA =
0.10% of FedPETuning, a 1024× reduction).

The paper's RoBERTa CE-LoRA entry (7.68e2) is internally inconsistent with
its LLaMA accounting (one vs two adapted modules/layer) — we report the
two-module (q,v) accounting and flag the discrepancy (DESIGN.md §7).
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402

from repro.configs import ASSIGNED  # noqa: E402
from repro.models import model  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.core import tri_lora  # noqa: E402
from repro.core.baselines import STRATEGIES  # noqa: E402


def adapter_payloads(arch: str) -> dict:
    """Uplink floats/round/client for every method, from the REAL adapter
    tree of the architecture (counts measured on the pytree, not derived)."""
    cfg = get_config(arch)
    adapter = jax.eval_shape(
        lambda: model.init_adapter(cfg, jax.random.key(0)))
    leaves = jax.tree.flatten(adapter, is_leaf=tri_lora.is_adapter)[0]
    a = sum(int(x["A"].size) for x in leaves)
    b = sum(int(x["B"].size) for x in leaves)
    c = sum(int(x["C"].size) for x in leaves)
    full = a + b
    return {
        "arch": arch, "n_modules": len(leaves),
        "fedpetuning": full, "pfedme_lora": full, "fdlora": full,
        "ffa_lora": b, "pfedme_ffa": b,
        "celora": c, "lora_loc": 0,
        "celora_pct": 100.0 * c / full,
        "reduction_x": full / max(c, 1),
    }


def main(quick: bool = False) -> list[dict]:
    rows = []
    print("# Table III — uplink floats per round per client")
    print("arch,n_modules,fedpetuning(A+B),ffa_lora(B),celora(C),"
          "celora_pct,reduction_x")
    archs = ["celora-roberta-base", "celora-llama-7b"] + list(ASSIGNED)
    for arch in archs:
        r = adapter_payloads(arch)
        rows.append(r)
        print(f"{arch},{r['n_modules']},{r['fedpetuning']},{r['ffa_lora']},"
              f"{r['celora']},{r['celora_pct']:.3f}%,{r['reduction_x']:.0f}x")
    # paper-claim checks (LLaMA-7B, q+v, r=8)
    llama = next(r for r in rows if r["arch"] == "celora-llama-7b")
    assert llama["fedpetuning"] == 4_194_304, llama        # 4.19e6 ✓ paper
    assert llama["celora"] == 4_096, llama                 # 4.10e3 ✓ paper
    assert llama["reduction_x"] == 1024, llama             # 1024× ✓ paper
    rob = next(r for r in rows if r["arch"] == "celora-roberta-base")
    assert rob["fedpetuning"] == 294_912, rob              # 2.95e5 ✓ paper
    print("# paper-claim asserts passed (LLaMA 1024x, RoBERTa A+B=2.95e5)")
    return rows


if __name__ == "__main__":
    main()
