#!/usr/bin/env python3
"""Readings that the cells' limits are set from (not part of a benchmark
run).

  python3 bench/control.py --workload <cell> --seeds <n> ... [--control <k>]

For each seed it makes a whole benchmark run of the cell and prints the
numbers its correctness check compared, the lower readings, with the
run's ``correct``.  For the first ``k`` seeds it also reads the control:
the reference computed with float8 matmuls put in the program's place, at
each position of the same prompts and served tokens, with the gap of the
token the control puts first; its numbers go through the same judgement
against the cell's limits.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_checks(serve_bank, cell) -> list:
    """The control's compared numbers, each beside the cell's limit."""
    cmp = cell.compared
    ref = serve_bank.reference_gaps(cell.config, cell.mix, cell.seed,
                                    cmp["reqs"], cmp["done"], cmp["pick"],
                                    quant=True)
    widest = max(float(g.max()) for g in ref["control"].values())
    return [{"name": "served_logit_gap", "value": widest,
             "limit": cell.limits.get("served_logit_gap", 0.0)}]


def readings(run, driver, cell, res: dict, control: bool) -> dict:
    def named(checks):
        return {ch["name"]: ch["value"] for ch in checks}
    out = {"program": named(res["checks"]), "correct": run.judge(res)}
    if control:
        ctrl = dict(res, failed=0, checks=control_checks(driver, cell))
        out["control_fp8"] = named(ctrl["checks"])
        out["control_correct"] = run.judge(ctrl)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import run
    spec = run.read_json(ROOT / "BENCHMARK.json")
    workload = next(w for w in spec["workloads"] if w["name"] == args.workload)
    devices = driver = None
    for k, seed in enumerate(args.seeds):
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=spec["run_seconds"], trace=0)
        cell = run.Cell(spec, workload, ns, run.CACHE / "trace" / "control")
        if devices is None:
            driver = run.load_module(
                run.BENCH / "drivers" / f"{cell.mix['driver']}.py",
                f"bench_driver_{cell.mix['driver']}")
            devices, _ = run.setup_jax(
                cell.chips, run.read_json(run.BENCH / "peaks.json"))
            sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        cell.devices = devices
        res = driver.run(cell)
        line = {"seed": seed, "failed": res["failed"],
                "tokens_per_s": res["metrics"]["serve_tokens_per_s"],
                **readings(run, driver, cell, res, k < args.control)}
        print(json.dumps(line), flush=True)
        del cell, res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
