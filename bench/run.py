#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell's entry in ``BENCHMARK.json`` names
its configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/mixes/<traffic>.json``); the mix names its driver
(``bench/drivers/<driver>.py``), the cell's limits are in
``bench/limits/<cell>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Peaks come from ``bench/peaks.json`` by the
device's kind.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries its
per-layer metrics, the device's busy and window seconds and a breakdown.
The last line of standard output is the result; the numbers the
correctness check compared, each beside its limit, are the last lines of
standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown device, a
    configuration that disagrees with the program)."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise BenchError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.exists():
        raise BenchError(f"no file {path}")
    return json.loads(path.read_text())


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Cell:
    """What a driver is given: the cell's files, the run's arguments, the
    devices, and the window's start and end."""

    def __init__(self, spec: dict, workload: dict, args, trace_dir: Path):
        self.spec, self.workload = spec, workload
        self.name = workload["name"]
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.chips = int(workload["chips"])
        conf = next(c for c in spec["configs"]
                    if c["name"] == workload["config"])
        self.config = read_json(ROOT / conf["file"])
        self.mix = read_json(BENCH / "mixes" / f"{workload['traffic']}.json")
        lim = BENCH / "limits" / f"{self.name}.json"
        self.limits = read_json(lim) if lim.exists() else {}
        self.t_start = T_START
        self.trace_dir = trace_dir
        self.devices: list = []
        self._ann = None
        self.window_open = False

    def model_config(self):
        """The program's ModelConfig for this cell, checked against the
        configuration file: the file is the configuration as it is run."""
        from repro.models.config import get_config
        c, prog = self.config, self.config["program"]
        cfg = get_config(prog["arch"]).with_overrides(**prog["overrides"])
        want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
                "n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"], "hd": c["head_dim"],
                "n_layers": c["num_hidden_layers"],
                "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
                "norm_type": c["norm_type"],
                "mlp_type": "gelu" if c["mlp"] == "gelu_tanh" else "swiglu",
                "attn_bias": c["attention_bias"],
                "lora_rank": c["lora_rank"], "lora_alpha": c["lora_alpha"],
                "lora_targets": tuple(c["lora_targets"]),
                "param_dtype": c["torch_dtype"]}
        bad = {k: (getattr(cfg, k), v) for k, v in want.items()
               if getattr(cfg, k) != v}
        if cfg.padded_vocab != cfg.vocab_size:
            bad["padded_vocab"] = (cfg.padded_vocab, cfg.vocab_size)
        if "swa" in cfg.layer_pattern and cfg.window != c["sliding_window"]:
            bad["window"] = (cfg.window, c["sliding_window"])
        if bad:
            raise BenchError(f"program config {cfg.name} disagrees with "
                             f"{c['name']}.json: {bad}")
        return cfg

    def begin_window(self) -> None:
        """Start of the measured window: the profiler starts here in a
        traced run."""
        if self.trace:
            import jax
            jax.profiler.start_trace(str(self.trace_dir))
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.window_open = True

    def end_window(self) -> None:
        if self.window_open and self.trace:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.window_open = False

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def wanted(entry: dict, cell: str, e2e_here: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in e2e_here


def setup_jax(chips: int, peaks: dict):
    """Place the compile cache, then find the chips: a TPU of a kind the
    peaks table knows, as many as the cell asks for."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"JAX's first device is on platform "
                         f"{dev.platform!r}; this benchmark runs on a TPU")
    if dev.device_kind not in peaks:
        raise BenchError(f"device kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} chips visible, the cell asks for "
                         f"{chips}")
    return devices[:chips], peaks[dev.device_kind]


def judge(out: dict) -> bool:
    """A run is correct when it attempted work, nothing failed, and every
    compared number lies within its limit."""
    checks = out["checks"]
    return (out["attempted"] > 0 and out["failed"] == 0 and bool(checks)
            and all(ch["value"] <= ch["limit"] for ch in checks))


def run_cell(args, *, find_chips=setup_jax, adjust=None) -> dict:
    """One run of one cell; returns the result line as a dict.  Tests pass
    ``find_chips`` to run on the CPU and ``adjust(cell)`` to shrink the
    cell's sizes; a benchmark run passes neither."""
    spec = read_json(ROOT / "BENCHMARK.json")
    try:
        workload = next(w for w in spec["workloads"]
                        if w["name"] == args.workload)
    except StopIteration:
        raise BenchError(f"no workload {args.workload!r} in BENCHMARK.json")
    trace_dir = CACHE / "trace" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    cell = Cell(spec, workload, args, trace_dir)
    if adjust is not None:
        adjust(cell)
    driver = load_module(BENCH / "drivers" / f"{cell.mix['driver']}.py",
                         f"bench_driver_{cell.mix['driver']}")
    cell.devices, peak = find_chips(cell.chips,
                                    read_json(BENCH / "peaks.json"))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    out = driver.run(cell)
    cell.end_window()
    gc.collect()

    checks = out["checks"]
    correct = judge(out)
    e2e_here = {m["name"] for m in spec["end_to_end"]
                if "workloads" not in m or cell.name in m["workloads"]}
    dev0 = cell.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(cell.devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if cell.trace:
        from bench import trace_reduce
        tr = trace_reduce.reduce(trace_reduce.events(trace_dir))
        rec = dict(out["record"], trace=tr, peak=peak, chips=cell.chips)
        metrics = {}
        for m in spec["per_layer"]:
            if not wanted(m, cell.name, e2e_here):
                continue
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            v = reader.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": out["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] in e2e_here}
    result.update(metrics=metrics, device=device)
    result["checks"] = {ch["name"]: {"value": ch["value"],
                                     "limit": ch["limit"]} for ch in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args)
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    for name, ch in result["checks"].items():
        log(f"check {name} = {ch['value']!r} limit {ch['limit']!r} "
            f"{'ok' if ch['value'] <= ch['limit'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
