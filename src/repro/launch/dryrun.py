import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 pods x 256 chips, the
full-size models are lowered from ``ShapeDtypeStruct`` stand-ins (zero
allocation), and a successful ``.compile()`` means GSPMD found a valid
collective schedule for every tensor in the program.

Per cell we record into ``artifacts/dryrun/<mesh>/<arch>__<shape>.json``:
  * memory_analysis()  -- per-chip argument/output/temp/peak bytes
  * cost_analysis()    -- XLA's own flops / bytes-accessed (loop bodies
                          counted once; see hlo_analysis for the fix)
  * hlo_analysis       -- loop-aware flops / HBM traffic / collective bytes
  * model_flops        -- 6 N D analytic (N_active for MoE)

Usage:
  python -m repro.launch.dryrun --all                 # every cell, 2 meshes
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single   # roofline table pass
"""
import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.common import SHAPE_TABLE, make_cell
from repro.launch import mesh as mesh_lib
from repro.launch.hlo_analysis import analyse, roofline_terms
from repro.launch.peaks import peaks_for
from repro.models import build
from repro.models.common import partition_specs, shape_structs
from repro.optim import OptConfig
from repro.parallel.sharding import spec_for, use_rules
from repro.training import (
    TrainConfig,
    make_serve_step,
    make_train_step,
)
from repro.training.train_lib import state_shape_structs, train_state_pspecs

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _opt_cfg(mod) -> OptConfig:
    import jax.numpy as jnp

    dt = jnp.bfloat16 if mod.OPT_STATE_DTYPE == "bfloat16" else jnp.float32
    return OptConfig(state_dtype=dt)


def _train_cfg(mod, microbatches: int = 1) -> TrainConfig:
    return TrainConfig(microbatches=microbatches,
                       optimizer=getattr(mod, "OPTIMIZER", "adamw"))


def _named(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape: str, mesh, rules, *,
               microbatches: int = 1):
    """-> (lowered, cell_info). Raises on sharding errors."""
    mod = configs.get(arch)
    cfg = mod.FULL
    bundle = build(cfg)
    cell = make_cell(cfg, shape)
    opt_cfg = _opt_cfg(mod)

    with use_rules(rules):
        batch_specs = {
            k: spec_for(cell.batch_specs[k].shape, cell.batch_logical[k],
                        rules=rules)
            for k in cell.batch_specs
        }
        batch_shardings = {k: NamedSharding(mesh, s)
                           for k, s in batch_specs.items()}

        if cell.kind == "train":
            tc = _train_cfg(mod, microbatches)
            step = make_train_step(bundle, opt_cfg, tc)
            state_sds = state_shape_structs(bundle, opt_cfg, tc)
            state_specs = train_state_pspecs(bundle, rules, tc)
            state_shardings = _named(state_specs, mesh)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(state_shardings, batch_shardings),
                    out_shardings=(state_shardings, None),
                ).lower(state_sds, cell.batch_specs)
        elif cell.kind == "prefill":
            prefill_step, _ = make_serve_step(bundle)
            params_sds = shape_structs(bundle.params_pspec, cfg.dtype)
            params_specs = partition_specs(bundle.params_pspec, rules=rules,
                                           fsdp_ok=True)
            params_shardings = _named(params_specs, mesh)
            # pin the produced cache to the decode-side layout (seq-sharded)
            cache_pspec = bundle.cache_pspec(cell.batch, cell.seq)
            cache_specs = partition_specs(cache_pspec, rules=rules)
            cache_shardings = _named(cache_specs, mesh)
            with mesh:
                lowered = jax.jit(
                    prefill_step,
                    in_shardings=(params_shardings, batch_shardings),
                    out_shardings=(None, cache_shardings),
                ).lower(params_sds, cell.batch_specs)
        else:  # decode
            _, decode_step = make_serve_step(bundle)
            params_sds = shape_structs(bundle.params_pspec, cfg.dtype)
            params_specs = partition_specs(bundle.params_pspec, rules=rules,
                                           fsdp_ok=True)
            params_shardings = _named(params_specs, mesh)
            cache_pspec = bundle.cache_pspec(cell.cache_batch, cell.cache_len)
            cache_sds = shape_structs(cache_pspec, cfg.dtype)
            cache_specs = partition_specs(cache_pspec, rules=rules)
            cache_shardings = _named(cache_specs, mesh)
            with mesh:
                lowered = jax.jit(
                    decode_step,
                    in_shardings=(params_shardings, cache_shardings,
                                  batch_shardings),
                    out_shardings=(None, cache_shardings),
                ).lower(params_sds, cache_sds, cell.batch_specs)
    return lowered, {"bundle": bundle, "cell": cell}


def model_flops(bundle, cell) -> float:
    """6 N D analytic model flops for the cell (N_active for MoE)."""
    n = bundle.n_active_params
    if cell.kind == "train":
        tokens = cell.batch * cell.seq
        return 6.0 * n * tokens
    if cell.kind == "prefill":
        tokens = cell.batch * cell.seq
        return 2.0 * n * tokens
    return 2.0 * n * cell.batch        # decode: one token per sequence


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str, *,
             force: bool = False) -> dict:
    mod = configs.get(arch)
    if shape in mod.SKIPS:
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "skip", "reason": mod.SKIPS[shape]}
    out_path = os.path.join(out_dir, mesh_name, f"{arch}__{shape}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    multi = mesh_name == "multi"
    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
    # serving cells shard the KV cache sequence dim (prefill writes the
    # cache that decode reads — both sides must agree on its layout)
    seq_shard = SHAPE_TABLE[shape][2] in ("decode", "prefill")
    rules = mesh_lib.make_rules(mesh, fsdp=True, seq_shard=seq_shard)

    t0 = time.time()
    lowered, info = lower_cell(arch, shape, mesh, rules)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    h = analyse(hlo)
    n_chips = mesh.devices.size
    # the production meshes are v5e pods; the placeholder host devices
    # stand in for them
    v5e = peaks_for("TPU v5 lite")
    terms = roofline_terms(
        h, peak_flops=v5e.bf16_flops, hbm_bw=v5e.hbm_bytes_s,
        ici_bw=v5e.ici_bytes_s)
    mf = model_flops(info["bundle"], info["cell"])

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "status": "ok",
        "n_chips": n_chips,
        "n_params": info["bundle"].n_params,
        "n_active_params": info["bundle"].n_active_params,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": ma.peak_memory_in_bytes,
        },
        "cost_analysis": {
            "flops_once": ca.get("flops", 0.0),
            "bytes_accessed_once": ca.get("bytes accessed", 0.0),
        },
        "hlo_analysis": h,
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / max(h["flops"], 1.0),
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def iter_cells():
    for arch in configs.all_arch_ids():
        mod = configs.get(arch)
        for shape in SHAPE_TABLE:
            yield arch, shape, (shape in mod.SKIPS)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    args = ap.parse_args()

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    cells = []
    if args.all:
        cells = [(a, s) for a, s, _ in iter_cells()]
    elif args.arch and args.shape:
        cells = [(args.arch, args.shape)]
    else:
        ap.error("pass --all or both --arch and --shape")

    n_ok = n_skip = n_fail = 0
    for arch, shape in cells:
        for mesh_name in meshes:
            try:
                rec = run_cell(arch, shape, mesh_name, args.out,
                               force=args.force)
            except Exception:
                n_fail += 1
                print(f"FAIL  {arch:24s} {shape:12s} {mesh_name}")
                traceback.print_exc()
                continue
            if rec["status"] == "skip":
                n_skip += 1
                print(f"skip  {arch:24s} {shape:12s} {mesh_name:6s} "
                      f"({rec['reason'][:60]})")
                continue
            n_ok += 1
            r = rec["roofline"]
            print(f"ok    {arch:24s} {shape:12s} {mesh_name:6s} "
                  f"peak={rec['memory']['peak_bytes'] / 2**30:7.2f}GiB "
                  f"c={r['compute_s']:.2e}s m={r['memory_s']:.2e}s "
                  f"x={r['collective_s']:.2e}s dom={r['dominant']} "
                  f"[{rec['compile_s']:.0f}s compile]")
    print(f"\n{n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
