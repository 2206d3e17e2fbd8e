import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ the two lines above MUST run before ANY other import (including repro.*):
#   jax locks the device count on first init.
#
# Multi-pod dry-run driver.
#
# For every (architecture x input shape) cell, lower + compile the REAL
# train/serve step (the same builders the run loops use) against the
# production mesh, print memory_analysis()/cost_analysis(), and record the
# roofline inputs as JSON under experiments/dryrun/.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

import argparse
import json
import time
import traceback

import jax


from repro.configs import ALL_ARCHS, get_config, get_train_overrides
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cell_applicable
from repro.launch.steps import build_cell
from repro.roofline.analysis import (
    model_flops, roofline_terms, mfu_fraction, HW_V5E,
)
from repro.roofline.hlo_parse import analyze as hlo_analyze
from repro.sharding.rules import default_rules
from repro.train.loop import TrainConfig


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             tcfg: TrainConfig | None = None, rules_opts: dict | None = None,
             tag: str = "", verbose: bool = True,
             cfg_overrides: dict | None = None) -> dict:
    import dataclasses as _dc

    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    ok, why = cell_applicable(cfg, shape_name)
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "applicable": ok, "skip_reason": why, "tag": tag,
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_name}{('__' + tag) if tag else ''}.json"
    )
    if not ok:
        with open(fname, "w") as f:
            json.dump(rec, f, indent=1)
        if verbose:
            print(f"[skip] {arch} x {shape_name} ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(mesh, **(rules_opts or {}))
    if tcfg is None:
        tcfg = TrainConfig(**get_train_overrides(arch))
    rec["train_config"] = {
        "microbatches": tcfg.microbatches, "zero1": tcfg.zero1,
        "zero2_grads": tcfg.zero2_grads,
    }
    t0 = time.time()
    try:
        cell = build_cell(cfg, shape_name, mesh, rules, tcfg=tcfg)
        with jax.set_mesh(mesh):
            lowered = cell.fn.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            hlo = compiled.as_text()
        # structural HLO analysis: scan/while bodies scaled by trip counts
        # (XLA's cost_analysis counts each computation once — see hlo_parse)
        scaled = hlo_analyze(hlo)
        n_chips = mesh.devices.size
        flops_dev = float(scaled["flops_scaled"])
        # memory term uses the TPU-fusion traffic model (matmul-boundary +
        # state-update + collective bytes); the all-op upper bound is kept in
        # the record for bracketing
        bytes_dev = float(scaled["traffic_dot_bytes_scaled"])
        coll_dev = float(scaled["collective_bytes"]["total"])
        terms = roofline_terms(flops_dev, bytes_dev, coll_dev)
        mf = model_flops(cfg, SHAPES[shape_name])
        mf_dev = mf / n_chips
        rec.update({
            "n_chips": n_chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
                # XLA:CPU FloatNormalization materializes f32 twins of big
                # bf16 buffers (no native host bf16); a TPU executable does
                # not allocate these — subtract them for the capacity check
                "cpu_bf16_upcast_bytes": scaled["cpu_bf16_upcast_bytes"],
                # floor: the corrected estimate can never drop below the real
                # argument (weights/optimizer/cache) footprint
                "tpu_est_bytes": max(
                    ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    - scaled["cpu_bf16_upcast_bytes"],
                    ma.argument_size_in_bytes,
                ),
                "fits_16g": max(
                    ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    - scaled["cpu_bf16_upcast_bytes"],
                    ma.argument_size_in_bytes,
                ) < HW_V5E["hbm_bytes"],
            },
            "cost": {
                "flops_scaled": flops_dev,
                "traffic_dot_bytes_scaled": bytes_dev,
                "traffic_allop_bytes_scaled": float(scaled["traffic_bytes_scaled"]),
                "xla_cost_flops_unscaled": float(ca.get("flops", 0.0)),
                "xla_cost_bytes_unscaled": float(ca.get("bytes accessed", 0.0)),
            },
            "collectives": {
                "bytes": scaled["collective_bytes"],
                "counts": scaled["collective_counts"],
            },
            "while_trip_counts": scaled["while_trip_counts"],
            "roofline": terms.as_dict(),
            "model_flops_total": mf,
            "model_flops_per_device": mf_dev,
            "useful_flops_ratio": (mf_dev / flops_dev) if flops_dev else None,
            "roofline_fraction": mfu_fraction(terms, mf_dev),
            "sharding_fallbacks": cell.notes,
            "hlo_bytes": len(hlo),
        })
        if verbose:
            mem_gb = rec["memory"]["tpu_est_bytes"] / 1e9
            print(
                f"[ok]  {arch:22s} {shape_name:12s} {mesh_name:16s} "
                f"compile={t_compile:6.1f}s mem={mem_gb:6.2f}G "
                f"dom={terms.dominant:10s} frac={rec['roofline_fraction']:.3f}"
            )
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[FAIL] {arch} x {shape_name} x {mesh_name}: {rec['error'][:200]}")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--tag", default="")
    p.add_argument("--set", action="append", default=[],
                   help="ModelConfig override, e.g. --set q_chunk=2048")
    p.add_argument("--train-set", action="append", default=[],
                   help="TrainConfig override, e.g. --train-set microbatches=8")
    p.add_argument("--seq-shard", action="store_true",
                   help="sequence-parallel activation sharding rules")
    args = p.parse_args()

    def _parse_sets(items):
        out = {}
        for it in items:
            k, v = it.split("=", 1)
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = {"true": True, "false": False}.get(v.lower(), v)
        return out

    cfg_overrides = _parse_sets(args.set)
    tset = _parse_sets(args.train_set)
    rules_opts = {"seq_shard": True} if args.seq_shard else None

    archs = ALL_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for mp in meshes:
        for a in archs:
            for s in shapes:
                tcfg = None
                if tset:
                    base = get_train_overrides(a)
                    tcfg = TrainConfig(**{**base, **tset})
                results.append(run_cell(
                    a, s, mp, args.out, tcfg=tcfg, tag=args.tag,
                    cfg_overrides=cfg_overrides or None,
                    rules_opts=rules_opts,
                ))
    n_ok = sum(1 for r in results if "error" not in r and r.get("applicable"))
    n_skip = sum(1 for r in results if not r.get("applicable"))
    n_fail = sum(1 for r in results if "error" in r)
    print(f"\n=== dry-run: {n_ok} ok, {n_skip} skipped (inapplicable), {n_fail} FAILED ===")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
