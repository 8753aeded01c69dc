import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove every (architecture × input shape × mesh) cell
lowers AND compiles under the production meshes, and extract the roofline
inputs from the compiled artifact.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init) — do not move it.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --list

Results accumulate in ``dryrun_results.json`` (incremental: completed cells
are skipped on re-runs; --force recomputes).
"""

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from ..configs import SHAPES, get_config, list_archs          # noqa: E402
from ..models import param_specs                               # noqa: E402
from . import steps as S                                       # noqa: E402
from .hlo_analysis import analyze_hlo_text                     # noqa: E402
from .mesh import make_production_mesh                         # noqa: E402

RESULTS_PATH = os.environ.get("DRYRUN_RESULTS", "dryrun_results.json")


def _jsonable(d):
    out = {}
    for k, v in (d or {}).items():
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            out[str(k)] = str(v)
    return out


def lower_cell(arch: str, shape: str, mesh, *, opt_overrides=None):
    """Build + lower + compile one cell.  Returns (compiled, lowered)."""
    import dataclasses
    cfg = get_config(arch)
    kind = SHAPES[shape]["kind"]
    if kind in ("train", "prefill") and SHAPES[shape]["seq_len"] % 16 == 0:
        # sequence-parallel residual stream (Megatron SP) under the mesh
        cfg = dataclasses.replace(cfg, seq_parallel=True)
    if opt_overrides:
        cfg = dataclasses.replace(cfg, **opt_overrides)
    pspecs = param_specs(cfg)

    with jax.set_mesh(mesh):
        if kind == "train":
            st_struct = S.state_struct(cfg)
            st_specs = S.sanitize_pspecs(S.state_pspecs(cfg), st_struct, mesh)
            step = S.make_train_step(cfg, pspecs=st_specs.master)
            b_struct = S.batch_struct(cfg, shape)
            b_specs = S.sanitize_pspecs(S.batch_pspecs(cfg, shape, mesh),
                                        b_struct, mesh)
            lowered = jax.jit(
                step,
                in_shardings=(st_specs, b_specs),
                out_shardings=(st_specs, None),
            ).lower(st_struct, b_struct)
        elif kind == "prefill":
            step = S.make_prefill_step(cfg)
            p_struct = S.params_struct(cfg)
            p_specs = S.sanitize_pspecs(pspecs, p_struct, mesh)
            b = dict(S.batch_struct(cfg, shape))
            b.pop("labels")
            bp = dict(S.batch_pspecs(cfg, shape, mesh))
            bp.pop("labels")
            bp = S.sanitize_pspecs(bp, b, mesh)
            lowered = jax.jit(
                step, in_shardings=(p_specs, bp),
            ).lower(p_struct, b)
        else:  # decode
            step = S.make_serve_step(cfg)
            sh = SHAPES[shape]
            bsz = sh["global_batch"]
            tok = jax.ShapeDtypeStruct((bsz, 1), jnp.int32)
            cur = jax.ShapeDtypeStruct((), jnp.int32)
            p_struct = S.params_struct(cfg)
            c_struct = S.cache_struct(cfg, shape)
            args = [p_struct, c_struct, tok, cur]
            in_sh = [S.sanitize_pspecs(pspecs, p_struct, mesh),
                     S.sanitize_pspecs(S.cache_pspecs(cfg, shape, mesh),
                                       c_struct, mesh),
                     S.token_pspecs(cfg, shape, mesh), P()]
            if cfg.family == "vlm":
                img = jax.ShapeDtypeStruct(
                    (bsz, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
                args.append(img)
                in_sh.append(S.sanitize_pspecs(
                    S.batch_pspecs(cfg, shape, mesh)["img"], img, mesh))
            lowered = jax.jit(step, in_shardings=tuple(in_sh)).lower(*args)
        compiled = lowered.compile()
    return compiled, lowered


def run_cell(arch: str, shape: str, mesh_kind: str, results: dict,
             force: bool = False) -> dict:
    key = f"{arch}|{shape}|{mesh_kind}"
    cfg = get_config(arch)
    if shape in cfg.skip_shapes:
        rec = {"status": "skipped", "reason": cfg.skip_reason}
        results[key] = rec
        return rec
    if key in results and results[key].get("status") == "ok" and not force:
        return results[key]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    try:
        compiled, lowered = lower_cell(arch, shape, mesh)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = analyze_hlo_text(compiled.as_text())
        rec = {
            "status": "ok",
            "seconds": round(time.time() - t0, 1),
            "ndev": mesh.size,
            "memory": {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
            },
            "cost_analysis": {
                "flops": float(cost.get("flops", -1.0)),
                "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
            },
            "hlo": {
                "flops_per_dev": hlo.flops,
                "bytes_per_dev": hlo.bytes,
                "collective_bytes_per_dev": hlo.collective_bytes,
                "by_collective": _jsonable(hlo.by_collective),
                "dot_count": hlo.dot_count,
                "warnings": hlo.warnings[:20],
            },
            "model_flops_note": {
                "params": cfg.param_count(),
                "active_params": cfg.active_param_count(),
            },
        }
        print(f"[ok] {key}: {rec['seconds']}s  "
              f"hlo_flops/dev={hlo.flops:.3e}  coll/dev={hlo.collective_bytes:.3e}  "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec = {"status": "error", "seconds": round(time.time() - t0, 1),
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        print(f"[ERROR] {key}: {type(e).__name__}: {str(e)[:200]}")
    results[key] = rec
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default=None, choices=["pod", "multipod", None])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=RESULTS_PATH)
    args = ap.parse_args()

    if args.list:
        for a in list_archs():
            cfg = get_config(a)
            print(f"{a:26s} {cfg.family:7s} params={cfg.param_count()/1e9:7.2f}B "
                  f"skips={','.join(cfg.skip_shapes) or '-'}")
        return

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["pod", "multipod"]
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                run_cell(arch, shape, mk, results, force=args.force)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    ok = sum(1 for r in results.values() if r.get("status") == "ok")
    sk = sum(1 for r in results.values() if r.get("status") == "skipped")
    err = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"\n=== dry-run summary: {ok} ok, {sk} skipped, {err} errors "
          f"(of {len(results)} cells) -> {args.out}")


if __name__ == "__main__":
    main()
