import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination against the production mesh using ShapeDtypeStruct
stand-ins — no allocation, but full GSPMD partitioning.

MUST be run as its own process (the two lines above must execute before any
jax device initialization — do not import this module from tests/benches).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fed]

Artifacts (one JSON + gzipped compiled HLO per combo) land in
artifacts/dryrun/<mesh>/ and feed benchmarks/roofline.py.
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED
from repro.launch import sharding as shd
from repro.launch import steps as st
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import model
from repro.models.config import get_config

ART = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "artifacts", "dryrun")


def _ns(mesh, spec_tree):
    return shd.to_named(spec_tree, mesh)


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                save_hlo: bool = True, fed: bool = False,
                serve_layout: str = "auto",
                train_layout: str = "mixed",
                fed_bf16: bool = False,
                microbatches: int = 1,
                attn_impl: str | None = None,
                art_dir: str = ART) -> dict:
    t0 = time.time()
    cfg = st.shape_variant(get_config(arch), shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    sh = st.SHAPES[shape_name]
    baxes = batch_axes(mesh)

    params_abs = model.abstract_params(cfg)
    pspec = shd.param_specs(params_abs, mesh, cfg)
    batch_abs = st.input_specs(cfg, shape_name)
    bspec = shd.batch_specs(batch_abs, mesh, baxes)

    with jax.set_mesh(mesh):
        if fed:
            assert multi_pod, "federated round step needs the pod axis"
            step = st.make_fed_round_step(
                cfg, mesh,
                payload_dtype=jnp.bfloat16 if fed_bf16 else None)
            n_pods = mesh.shape["pod"]
            ad_abs = st.pod_stacked_adapter(cfg, n_pods)
            os_abs = st.pod_stacked_opt_state(cfg, n_pods, step.optimizer)
            adspec = jax.tree.map(
                lambda x: jax.sharding.PartitionSpec(
                    "pod", *([None] * (x.ndim - 1))), ad_abs)
            osspec = jax.tree.map(
                lambda x: jax.sharding.PartitionSpec(
                    "pod", *([None] * (x.ndim - 1))), os_abs)
            w_abs = jax.ShapeDtypeStruct((n_pods, n_pods), jnp.float32)
            jitted = jax.jit(
                step,
                in_shardings=(_ns(mesh, pspec), _ns(mesh, adspec),
                              _ns(mesh, osspec), _ns(mesh, bspec), None),
                donate_argnums=(1, 2))
            lowered = jitted.lower(params_abs, ad_abs, os_abs, batch_abs,
                                   w_abs)
        elif sh.kind == "train":
            step = st.make_train_step(cfg, microbatches=microbatches,
                                      attn_impl=attn_impl)
            opt_abs = jax.eval_shape(step.optimizer.init,
                                     params_abs["adapter"])
            if train_layout == "dp":
                # §Perf: pure data-parallel layout for models too small for
                # 16-way TP (whisper): params replicated, batch 256-way over
                # (data × model), collectives = adapter grad psum only
                from repro.models import layers as _layers
                P_ = jax.sharding.PartitionSpec
                pspec_t = jax.tree.map(lambda x: P_(*([None] * x.ndim)),
                                       params_abs)
                ospec = jax.tree.map(lambda x: P_(*([None] * x.ndim)),
                                     opt_abs)
                dp_axes = ("data", "model")
                bspec_t = jax.tree.map(
                    lambda x: P_(dp_axes, *([None] * (x.ndim - 1))),
                    batch_abs)
                rec_layout = "dp"
                with _layers.hint_batch_axes(dp_axes):
                    jitted = jax.jit(
                        step,
                        in_shardings=(_ns(mesh, pspec_t), _ns(mesh, ospec),
                                      _ns(mesh, bspec_t)),
                        out_shardings=(_ns(mesh, pspec_t), _ns(mesh, ospec),
                                       None),
                        donate_argnums=(0, 1))
                    lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            else:
                ospec = shd.param_specs(opt_abs, mesh, cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(_ns(mesh, pspec), _ns(mesh, ospec),
                                  _ns(mesh, bspec)),
                    out_shardings=(_ns(mesh, pspec), _ns(mesh, ospec), None),
                    donate_argnums=(0, 1))
                lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        elif sh.kind == "prefill":
            step = st.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(_ns(mesh, pspec),
                                                 _ns(mesh, bspec)))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            step = st.make_serve_step(cfg)
            cache_abs = st.abstract_cache(cfg, shape_name)
            cspec = shd.cache_specs(cache_abs, mesh, cfg, baxes)
            # serving layout (§Perf): when the frozen weights fit at
            # 1/|model| per chip, drop FSDP — kills per-step weight gathers
            if serve_layout == "auto":
                import sys as _s
                _s.path.insert(0, os.path.join(os.path.dirname(__file__),
                                               "..", "..", "..", "benchmarks"))
                try:
                    from analytic import count_params
                    # replicated-data serving pays off only when weights
                    # are a small fraction of HBM next to the KV cache
                    per_chip = count_params(cfg).total * 2 / 16
                    use_fsdp = per_chip > 6e9
                except Exception:
                    use_fsdp = True
            else:
                use_fsdp = serve_layout == "fsdp"
            pspec_serve = shd.param_specs(params_abs, mesh, cfg,
                                          fsdp=use_fsdp)
            rec_layout = "fsdp" if use_fsdp else "replicated-data"
            # logits stay vocab-sharded over `model` (no unembed gather)
            b_ok = st.SHAPES[shape_name].global_batch % max(
                1, int(jnp.prod(jnp.asarray(
                    [mesh.shape[a] for a in baxes])))) == 0
            lspec = jax.sharding.PartitionSpec(
                (baxes if len(baxes) > 1 else baxes[0]) if b_ok and baxes
                else None,
                "model" if cfg.padded_vocab % mesh.shape["model"] == 0
                else None)
            jitted = jax.jit(
                step,
                in_shardings=(_ns(mesh, pspec_serve), _ns(mesh, cspec),
                              _ns(mesh, bspec)),
                out_shardings=(jax.sharding.NamedSharding(mesh, lspec),
                               _ns(mesh, cspec)),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, batch_abs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec = {
        "arch": arch, "variant": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "layout": locals().get("rec_layout", "mixed"),
        "fed": fed,
        "n_devices": mesh.size,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:  # CPU backend may not implement it
        rec["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))
                       and k in ("flops", "bytes accessed",
                                 "bytes accessed from memory",
                                 "utilization operand", "transcendentals",
                                 "optimal_seconds")}
        rec["cost_raw_keys"] = sorted(cost.keys())[:50]
    except Exception as e:
        rec["cost"] = {"error": str(e)}

    mesh_tag = rec["mesh"] + ("_fed" if fed else "")
    out_dir = os.path.join(art_dir, mesh_tag)
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch.replace('/', '_')}__{shape_name}"
    if save_hlo:
        hlo = compiled.as_text()
        with gzip.open(os.path.join(out_dir, stem + ".hlo.gz"), "wt") as f:
            f.write(hlo)
        rec["hlo_path"] = os.path.join(out_dir, stem + ".hlo.gz")
        rec["hlo_bytes"] = len(hlo)
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, choices=list(st.SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="all assigned (arch × shape) combos")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fed", action="store_true",
                    help="federated pod-round step (multi-pod only)")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--train-layout", default="mixed",
                    choices=["mixed", "dp"])
    ap.add_argument("--fed-bf16", action="store_true",
                    help="quantize the federated C payload to bf16")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches for train")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "ref", "blockwise", "blockwise_cv",
                             "blockwise_hp", "flash"],
                    help="attention backend override (default: the arch "
                         "config's ModelConfig.attn_impl)")
    ap.add_argument("--out-dir", default=ART,
                    help="artifact root (default: <repo>/artifacts/dryrun)")
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ASSIGNED:
            shapes = list(st.SHAPES) if not args.fed else ["train_4k"]
            for s in shapes:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    n_ok = 0
    for arch, shape in combos:
        try:
            rec = lower_combo(arch, shape, multi_pod=args.multi_pod,
                              save_hlo=not args.no_hlo, fed=args.fed,
                              train_layout=args.train_layout,
                              fed_bf16=args.fed_bf16,
                              microbatches=args.microbatch,
                              attn_impl=args.attn_impl,
                              art_dir=args.out_dir)
            flops = rec.get("cost", {}).get("flops", float("nan"))
            temp = rec.get("memory", {}).get("temp_size_in_bytes", -1)
            print(f"OK   {arch:24s} {shape:12s} mesh={rec['mesh']}"
                  f" lower={rec['lower_s']}s compile={rec['compile_s']}s"
                  f" flops={flops:.3e} temp={temp/2**30:.2f}GiB", flush=True)
            n_ok += 1
        except Exception:
            print(f"FAIL {arch:24s} {shape:12s}", flush=True)
            traceback.print_exc()
    print(f"{n_ok}/{len(combos)} combos lowered+compiled")
    return 0 if n_ok == len(combos) else 1


if __name__ == "__main__":
    raise SystemExit(main())
