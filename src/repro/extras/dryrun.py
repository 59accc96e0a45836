import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402  (the two lines above MUST precede any jax import)
# NOTE: no `from __future__ import annotations` — the XLA_FLAGS lines must
# stay the very first statements in this file.
"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape × mesh) cell:
  jit(step).lower(*ShapeDtypeStructs).compile()
on the production meshes — 16×16 (256 chips, single pod) and 2×16×16
(512 chips, 2 pods) — capturing memory_analysis(), cost_analysis() and the
collective mix for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.extras.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.extras.dryrun --all [--multi-pod-only|--single-pod-only]
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
from jax.sharding import NamedSharding

from repro import optim
from repro.analysis import roofline as rl
from repro.configs import all_archs, get
from repro.configs.base import GNNConfig, LMConfig, RecSysConfig
from repro.extras import shapes as shapes_lib
from repro.launch.mesh import make_production_mesh
from repro.launch.rules import make_rules
from repro.launch.sharding import axis_rules, spec_for

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _opt_cfg_for(cfg) -> optim.AdamWConfig:
    # int8 moments for the models whose optimizer state would not fit HBM
    quant = isinstance(cfg, LMConfig) and cfg.n_params() > 1e11
    return optim.AdamWConfig(quantize_moments=quant)


def _model_flops(entry, cell, cfg) -> float:
    if isinstance(cfg, LMConfig):
        s = shapes_lib.LM_SHAPES[cell.shape_id]
        return rl.model_flops_lm(cfg, s["batch"], s["seq"], cell.kind)
    if isinstance(cfg, GNNConfig):
        s = shapes_lib.GNN_SHAPES[cell.shape_id]
        return rl.model_flops_gnn(cfg, s["n"], s["e"])
    if isinstance(cfg, RecSysConfig):
        s = shapes_lib.REC_SHAPES[cell.shape_id]
        return rl.model_flops_recsys(cfg, s.get("batch", 1), cell.kind)
    return 0.0


def _compile_cell(entry, cell, cfg, opt_cfg, mesh):
    """Shared lower+compile for a (possibly size-reduced) config."""
    rules = make_rules(cfg, cell.kind, mesh)
    with axis_rules(mesh, rules):
        args = cell.abstract_args(cfg, opt_cfg)
        axes = cell.arg_axes(cfg, opt_cfg)

        def _is_axes(x):
            return isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x
            )

        def _to_sharding(a):
            from jax.sharding import PartitionSpec as P

            spec = spec_for(a) if _is_axes(a) else P()
            return NamedSharding(mesh, spec)

        in_shardings = jax.tree.map(_to_sharding, axes, is_leaf=_is_axes)
        fn = cell.fn(cfg, opt_cfg)
        jitted = jax.jit(fn, in_shardings=in_shardings)
        lowered = jitted.lower(*args)
        return lowered, lowered.compile()


def _cost_vector(compiled, n_chips) -> dict:
    cost = compiled.cost_analysis()
    coll = rl.parse_collectives(compiled.as_text(), n_chips)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll.total_bytes,
        "coll_by_kind": coll.bytes_by_kind,
        "coll_count": coll.count_by_kind,
    }


def _vec(f, a, b=None):
    """Element-wise combine of cost vectors (scalar fields + coll_by_kind)."""
    out = {}
    for k in ("flops", "bytes", "coll"):
        out[k] = f(a[k], b[k] if b is not None else None)
    kinds = set(a["coll_by_kind"]) | (set(b["coll_by_kind"]) if b else set())
    out["coll_by_kind"] = {
        kk: f(
            a["coll_by_kind"].get(kk, 0.0),
            b["coll_by_kind"].get(kk, 0.0) if b is not None else None,
        )
        for kk in kinds
    }
    out["coll_count"] = a.get("coll_count", {})
    return out


def _add(a, b):
    return _vec(lambda x, y: x + y, a, b)


def _sub(a, b):
    return _vec(lambda x, y: x - y, a, b)


def _scale(a, s):
    return _vec(lambda x, _: x * s, a)


def _shrink(cfg, n_layers: int, first_k_dense: int | None = None):
    if isinstance(cfg, LMConfig):
        moe = cfg.moe
        if moe is not None and first_k_dense is not None:
            moe = dataclasses.replace(moe, first_k_dense=first_k_dense)
        return dataclasses.replace(cfg, n_layers=n_layers, moe=moe)
    return dataclasses.replace(cfg, n_layers=n_layers)


def measure_cost(entry, shape_id: str, cfg, opt_cfg, mesh) -> dict:
    """Per-device cost, exact in depth: XLA counts scan bodies once, so we
    compile small-depth variants (with attention tile loops unrolled),
    difference out the per-layer marginal cost per stack, and extrapolate
    base + Σ_s L_s · c_s (methodology validated by tests/test_roofline.py)."""
    from repro.models.layers import unrolled_model

    n_chips = int(mesh.devices.size)

    def cost_of(cfg_small):
        cell = shapes_lib.build_cell(
            dataclasses.replace(entry, config=cfg_small), shape_id
        )
        with unrolled_model():
            _, compiled = _compile_cell(entry, cell, cfg_small, opt_cfg, mesh)
        return _cost_vector(compiled, n_chips)

    if isinstance(cfg, LMConfig):
        k = cfg.moe.first_k_dense if cfg.moe is not None else cfg.n_layers
        Lm = cfg.n_layers - k
        if cfg.moe is not None and k > 0 and Lm > 0:
            # two stacks: cost = base + Ld·cd + Lm·cm (3 probes solve it)
            c11 = cost_of(_shrink(cfg, 2, 1))
            c21 = cost_of(_shrink(cfg, 3, 2))
            c12 = cost_of(_shrink(cfg, 3, 1))
            cd = _sub(c21, c11)
            cm = _sub(c12, c11)
            base = _sub(c11, _add(cd, cm))
            return _add(base, _add(_scale(cd, k), _scale(cm, Lm)))
        c1 = cost_of(_shrink(cfg, 1, 0 if cfg.moe is not None else None))
        c2 = cost_of(_shrink(cfg, 2, 0 if cfg.moe is not None else None))
        per = _sub(c2, c1)
        return _add(c1, _scale(per, cfg.n_layers - 1))
    if isinstance(cfg, GNNConfig):
        c1 = cost_of(_shrink(cfg, 1))
        c2 = cost_of(_shrink(cfg, 2))
        return _add(c1, _scale(_sub(c2, c1), cfg.n_layers - 1))
    # recsys: nothing scanned — measure directly
    cell = shapes_lib.build_cell(entry, shape_id)
    _, compiled = _compile_cell(entry, cell, cfg, opt_cfg, mesh)
    return _cost_vector(compiled, n_chips)


def apply_variant(cfg, kind: str, mesh):
    """§Perf optimized variant: grouped MoE dispatch + flash-decoding."""
    from repro.launch.mesh import mesh_axis_size

    if not isinstance(cfg, LMConfig):
        return cfg
    dp = mesh_axis_size(mesh, ("pod", "data"))
    tp = mesh_axis_size(mesh, "model")
    if cfg.moe is not None and kind in ("train", "prefill"):
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch_groups=dp)
        )
    if kind in ("prefill", "decode", "decode_long"):
        # replicating TP-sharded weights over DP must fit HBM (16 GiB v5e):
        # bytes/chip = 2·N / (model × experts-over-dp factor). The split-KV
        # decode blocks only pay off together with replicated weights
        # (iteration 2a/2b in EXPERIMENTS.md §Perf), so both gate on fit.
        ep_dp = (
            dp if cfg.moe is not None and cfg.moe.n_experts % (dp * tp) == 0 else 1
        )
        per_chip = 2.0 * cfg.n_params() / (tp * ep_dp)
        if per_chip < 12e9:  # leave room for the KV cache + activations
            cfg = dataclasses.replace(
                cfg,
                inference_param_sharding="tp_replicated",
                decode_kv_blocks=(tp if kind == "decode" else dp * tp)
                if kind != "prefill"
                else 1,
            )
    if kind == "train" and cfg.n_params() < 1e10:
        # small models don't need remat: trade recompute for bytes (§Perf 4)
        cfg = dataclasses.replace(cfg, remat="none")
    return cfg


def run_cell(
    arch_id: str,
    shape_id: str,
    *,
    multi_pod: bool,
    save: bool = True,
    variant: str | None = None,
) -> dict:
    entry = get(arch_id)
    if shape_id in dict(entry.skipped_shapes):
        out = {
            "arch": arch_id, "shape": shape_id,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "skipped", "reason": dict(entry.skipped_shapes)[shape_id],
        }
        if save:
            ARTIFACTS.mkdir(parents=True, exist_ok=True)
            (ARTIFACTS / f"{arch_id}__{shape_id}__{out['mesh']}.json").write_text(
                json.dumps(out, indent=2)
            )
        return out
    cell = shapes_lib.build_cell(entry, shape_id)
    cfg = entry.config
    opt_cfg = _opt_cfg_for(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(mesh.devices.size)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    if variant == "opt":
        cfg = apply_variant(cfg, cell.kind, mesh)
        entry = dataclasses.replace(entry, config=cfg)
    out: dict = {
        "arch": arch_id, "shape": shape_id, "mesh": mesh_name,
        "kind": cell.kind, "status": "ok", "variant": variant or "baseline",
    }
    t0 = time.time()
    try:
        # 1. the REQUIRED proof: full config lowers + compiles on this mesh
        lowered, compiled = _compile_cell(entry, cell, cfg, opt_cfg, mesh)
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()

        # 2. exact per-device cost via depth extrapolation (scan-once fix)
        cost = measure_cost(entry, shape_id, cfg, opt_cfg, mesh)
        roof = rl.Roofline(
            flops=cost["flops"],
            hbm_bytes=cost["bytes"],
            collective_bytes=cost["coll"],
            n_chips=n_chips,
            peaks=rl.PEAKS["TPU v5 lite"],  # dry runs target a v5e mesh
            model_flops=_model_flops(entry, cell, cfg),
        )
        coll = rl.CollectiveStats(cost["coll_by_kind"], cost["coll_count"])
        out.update(
            {
                "compile_s": round(t_compile, 1),
                "memory": {
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                    "output_bytes": getattr(mem, "output_size_in_bytes", None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                    "generated_code_bytes": getattr(
                        mem, "generated_code_size_in_bytes", None
                    ),
                },
                "collectives": {
                    "bytes_by_kind": coll.bytes_by_kind,
                    "count_by_kind": coll.count_by_kind,
                },
                "roofline": roof.to_dict(),
            }
        )
        print(
            f"[OK] {arch_id} × {shape_id} × {mesh_name}: "
            f"compile {t_compile:.0f}s, "
            f"t_comp {roof.t_compute*1e3:.2f}ms t_mem {roof.t_memory*1e3:.2f}ms "
            f"t_coll {roof.t_collective*1e3:.2f}ms → {roof.bottleneck} "
            f"(roofline frac {roof.roofline_fraction:.2f})"
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        out["status"] = "error"
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch_id} × {shape_id} × {mesh_name}: {out['error']}")
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        suffix = f"__{variant}" if variant else ""
        path = ARTIFACTS / f"{arch_id}__{shape_id}__{mesh_name}{suffix}.json"
        path.write_text(json.dumps(out, indent=2, default=str))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--variant", type=str, default=None, choices=[None, "opt"])
    args = ap.parse_args()

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    results = []
    if args.all:
        for entry in all_archs().values():
            if entry.family not in ("lm", "gnn", "recsys"):
                continue
            for sh in entry.shapes + tuple(s for s, _ in entry.skipped_shapes):
                for mp in meshes:
                    results.append(
                        run_cell(entry.arch_id, sh, multi_pod=mp, variant=args.variant)
                    )
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            results.append(
                run_cell(args.arch, args.shape, multi_pod=mp, variant=args.variant)
            )

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n=== dry-run: {n_ok} ok, {n_skip} skipped, {n_err} failed ===")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
