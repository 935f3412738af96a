import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede every other import (jax locks the device
count at first init); smoke tests and benchmarks import other modules and
keep seeing one device.

Per cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs the *production* step function — the same
     training/train_loop or models/api entry real runs use,
  3. ``lower()``s it on ShapeDtypeStruct inputs (no allocation),
  4. ``compile()``s, proving the sharding config is coherent,
  5. records memory_analysis / cost_analysis / a collective-traffic census
     parsed from the partitioned HLO (while-loop trip counts folded in)
     into artifacts/dryrun/<mesh>/<arch>--<shape>.json.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--timeout 3600]
The --all driver runs each cell in a subprocess (compile crashes and OOMs
must not kill the sweep) and tolerates per-cell failure, recording it.
"""

import argparse
import json
import re
import subprocess
import sys
import time

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")

# --------------------------------------------------------------------------
# Per-cell dry-run
# --------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (jit_fn, example_args_shapes) for lower()."""
    import math

    import jax

    from ..configs import SHAPES, get_config
    from ..distributed import sharding as shr
    from ..launch.mesh import make_production_mesh
    from ..models import api
    from ..training.train_loop import (TrainOptions, init_train_state,
                                       state_shardings)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = math.prod(mesh.shape[a] for a in ("pod", "data") if a in mesh.axis_names)

    batch_shapes = api.input_specs(cfg, shape)

    def batch_shardings(batch):
        specs = {}
        for k, v in batch.items():
            if k == "cache":
                specs[k] = shr.cache_specs(v, mesh, shape.global_batch)
            elif k == "state":
                specs[k] = shr.state_specs(v, mesh, shape.global_batch)
            else:
                specs[k] = shr.batch_specs({k: v}, mesh, shape.global_batch)[k]
        return shr.named(specs, mesh)

    if shape.kind == "train":
        nm = max(1, shape.global_batch // dp)  # 1 sequence/device/microbatch
        if os.environ.get("REPRO_NM"):
            nm = int(os.environ["REPRO_NM"])
        opts = TrainOptions(
            num_microbatches=nm,
            grad_compression=os.environ.get("REPRO_COMPRESS", "none"))
        from ..training.train_loop import make_train_step
        state_shapes = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.PRNGKey(0), None, opts))
        st_sh = state_shardings(state_shapes, mesh)
        b_sh = batch_shardings(batch_shapes)
        step = make_train_step(cfg, mesh, opts)
        fn = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
                     donate_argnums=(0,))
        args = (state_shapes, batch_shapes)
        extra = {"num_microbatches": nm}
    else:
        params_shapes = jax.eval_shape(lambda: api.init(cfg, jax.random.PRNGKey(0)))
        p_sh = shr.named(shr.param_specs(params_shapes, mesh), mesh)
        b_sh = batch_shardings(batch_shapes)
        if shape.kind == "prefill":
            fn = jax.jit(lambda p, b: api.prefill(p, cfg, b, mesh=mesh),
                         in_shardings=(p_sh, b_sh))
        else:
            fn = jax.jit(lambda p, b: api.decode(p, cfg, b, mesh=mesh),
                         in_shardings=(p_sh, b_sh), donate_argnums=(1,))
        args = (params_shapes, batch_shapes)
        extra = {}
    return mesh, fn, args, extra


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str) -> dict:
    import jax

    from jax import set_mesh

    multi = mesh_kind == "multi"
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "ok": False}
    try:
        mesh, fn, args, extra = build_cell(arch, shape_name, multi)
        record.update(extra, n_devices=int(mesh.devices.size))
        with set_mesh(mesh):
            lowered = fn.lower(*args)
            record["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            record["compile_s"] = round(time.time() - t1, 2)

            ma = compiled.memory_analysis()
            record["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
            }
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):  # older jax: one dict per device
                ca = ca[0] if ca else {}
            record["cost"] = {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))} if ca else {}
            hlo = compiled.as_text()
            record["hlo_bytes"] = len(hlo)
            from .hlo_census import hlo_census
            census = hlo_census(hlo, int(mesh.devices.size))
            record["collectives"] = census.pop("collectives")
            record["census"] = census
            record["ok"] = True
    except Exception as exc:  # noqa: BLE001
        record["error"] = f"{type(exc).__name__}: {exc}"[:2000]
    record["total_s"] = round(time.time() - t0, 2)

    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}--{shape_name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


# --------------------------------------------------------------------------
# Sweep driver
# --------------------------------------------------------------------------

def all_cells() -> list[tuple[str, str]]:
    from ..configs import get_config, list_archs, shapes_for
    cells = []
    for arch in list_archs():
        for shape in shapes_for(get_config(arch)):
            cells.append((arch, shape.name))
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACTS)
    ap.add_argument("--timeout", type=int, default=7200)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if not args.all:
        assert args.arch and args.shape
        for mk in meshes:
            rec = run_cell(args.arch, args.shape, mk,
                           os.path.join(args.out, mk))
            print(json.dumps({k: rec[k] for k in
                              ("arch", "shape", "mesh", "ok", "total_s")
                              if k in rec}
                             | ({"error": rec["error"]} if "error" in rec else {})))
        return 0

    # sweep: one subprocess per cell so a crash cannot kill the sweep
    failures = 0
    for mk in meshes:
        for arch, shape in all_cells():
            out_json = os.path.join(args.out, mk, f"{arch}--{shape}.json")
            if args.skip_done and os.path.exists(out_json):
                with open(out_json) as f:
                    if json.load(f).get("ok"):
                        print(f"[skip] {mk} {arch} {shape}")
                        continue
            t0 = time.time()
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mk, "--out", args.out]
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=args.timeout)
                ok = proc.returncode == 0 and os.path.exists(out_json)
                if ok:
                    with open(out_json) as f:
                        ok = json.load(f).get("ok", False)
                if not ok:
                    failures += 1
                    err = (proc.stderr or "")[-500:]
                    os.makedirs(os.path.dirname(out_json), exist_ok=True)
                    if not os.path.exists(out_json):
                        with open(out_json, "w") as f:
                            json.dump({"arch": arch, "shape": shape, "mesh": mk,
                                       "ok": False, "error": err}, f)
                print(f"[{'ok' if ok else 'FAIL'}] {mk} {arch} {shape} "
                      f"({time.time() - t0:.0f}s)")
            except subprocess.TimeoutExpired:
                failures += 1
                print(f"[TIMEOUT] {mk} {arch} {shape}")
    print(f"sweep done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
