import os
if __name__ == "__main__":
    # MUST run before any jax import (device count locks at first init).
    # Guarded so importing this module (tests, tooling) never mutates the
    # process' device topology — the dry-run is its own process by design.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and extract the roofline terms from the compiled artifact.

MUST be invoked as its own process (the XLA flag above is read at first jax
init).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun

Per cell it records: per-device memory analysis, HLO FLOPs/bytes
(cost_analysis), per-collective byte totals (parsed from the compiled HLO),
and derived roofline terms for the TPU-v5e-like target
(197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).
"""

import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh, data_axes, activate_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime.chip import enable_compile_cache

# ---- hardware constants (assignment: TPU v5e-like target) ----
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (use 1 link per collective hop)

COLLECTIVE_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)]*?\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2}


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Sum output bytes of every collective op in the (SPMD, per-device) HLO."""
    out: Dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = DTYPE_BYTES.get(dtype, 4)
        if dims:
            for d in dims.split(","):
                nbytes *= int(d)
        out[kind] = out.get(kind, 0.0) + nbytes
    return out


def roofline_terms(flops: float, bytes_acc: float, coll: Dict[str, float]):
    """The three roofline terms, in seconds per step per chip."""
    comm_bytes = sum(coll.values())
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": comm_bytes / ICI_BW,
        "collective_bytes": comm_bytes,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             zero1: bool = True, extra: Optional[dict] = None,
             layers: Optional[int] = None, policy: str = "tp") -> dict:
    """layers: override the scan depth (in scan units: layers for most
    archs, Jamba periods x attn_every for hybrid, both enc+dec for audio).
    Used by the roofline tool to extrapolate per-layer FLOPs/bytes — XLA's
    cost_analysis counts while-loop bodies once, so full-depth numbers come
    from two shallow compiles + linear extrapolation."""
    import dataclasses as _dc
    cfg = ARCHS[arch]
    if layers is not None:
        nl = layers * cfg.attn_every if cfg.attn_every else layers
        cfg = _dc.replace(cfg, n_layers=nl,
                          enc_layers=layers if cfg.enc_layers else 0)
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "multi" if multi_pod else "single",
                 "layers_override": layers}
    if shape.name in cfg.skip_shapes:
        rec["status"] = "SKIP"
        rec["reason"] = ("full-attention arch: quadratic-history 500k decode"
                        if shape.name == "long_500k" else "n/a")
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = data_axes(mesh)
    model = build_model(cfg, remat=(shape.mode == "train"))
    if layers is not None:
        model.scan_unroll = True    # cost_analysis must see every layer
    sh = ST.shardings_for(mesh, model, cfg, shape, zero1=zero1, policy=policy)
    model.hidden_pspec = sh["hidden"]
    model.hidden_divisors = sh["divisors"]
    rec["policy"] = policy
    # grouped MoE dispatch aligned with the data axes (EP over 'model')
    if cfg.n_experts:
        from jax.sharding import PartitionSpec as P
        e_tot = cfg.n_experts + cfg.expert_pad
        model_size = int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
        if e_tot % max(model_size, 1) == 0:
            model.moe_groups = sh["divisors"][0]
            model.moe_buf_pspec = P(tuple(dp), "model", None, None)
            if shape.mode != "decode":
                # manual-collective EP (shard_map) for train/prefill
                model.moe_impl = "shard_map"
                model.moe_mesh = mesh
                model.moe_dp_axes = tuple(dp)
    batch_abs = ST.input_specs(cfg, shape)
    params_abs = ST.abstract_params(model)

    with activate_mesh(mesh):
        if shape.mode == "train":
            opt_abs = jax.eval_shape(lambda p: __import__(
                "repro.optim", fromlist=["adamw_init"]).adamw_init(p), params_abs)
            step = ST.make_train_step(model, AdamWConfig())
            jitted = jax.jit(
                step,
                in_shardings=(ST.named(mesh, sh["params"]),
                              ST.named(mesh, sh["opt"]),
                              ST.named(mesh, {k: sh["batch"][k] for k in batch_abs})),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        elif shape.mode == "prefill":
            step = ST.make_prefill_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(ST.named(mesh, sh["params"]),
                              ST.named(mesh, {k: sh["batch"][k] for k in batch_abs})))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            cache_abs = ST.abstract_cache(model, cfg, shape)
            step = ST.make_serve_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(ST.named(mesh, sh["params"]),
                              ST.named(mesh, sh["cache"]),
                              ST.named(mesh, sh["batch"]["tokens"])),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs,
                                   batch_abs["tokens"])

        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    rec["flops"] = float(cost.get("flops", 0.0))
    rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    rec["collectives"] = parse_collectives(hlo)
    rec["roofline"] = roofline_terms(rec["flops"], rec["bytes_accessed"],
                                     rec["collectives"])
    rec["status"] = "OK"
    if extra:
        rec.update(extra)
    return rec


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="scan-depth override for per-layer cost extraction")
    ap.add_argument("--policy", default="tp", choices=("tp", "fsdp", "dp"))
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.layers is not None:
                    tag += f"__L{args.layers}"
                if args.policy != "tp":
                    tag += f"__{args.policy}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    rec = json.load(open(path))
                    if rec.get("status") in ("OK", "SKIP"):
                        print(f"[cached] {tag}: {rec['status']}")
                        continue
                try:
                    rec = run_cell(arch, shape, mp, zero1=not args.no_zero1,
                                   layers=args.layers, policy=args.policy)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    n_fail += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "OK":
                    r = rec["roofline"]
                    print(f"{tag}: OK lower={rec['lower_s']}s "
                          f"compile={rec['compile_s']}s "
                          f"compute={r['compute_s']:.4f}s "
                          f"mem={r['memory_s']:.4f}s "
                          f"coll={r['collective_s']:.4f}s "
                          f"temp={rec['memory'].get('temp_size_in_bytes', 0) / 2**30:.2f}GiB",
                          flush=True)
                else:
                    print(f"{tag}: {rec['status']} {rec.get('error', rec.get('reason', ''))}",
                          flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
