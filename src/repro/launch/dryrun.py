import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any other import (jax locks the platform
and device count at first init).  512 host devices stand in for 2 pods x
256 chips; pinning the CPU keeps the dry-run off any attached chip.

Per cell this script:
  1. builds the production mesh (16x16 or 2x16x16) and the auto policy,
  2. builds ShapeDtypeStruct inputs (zero allocation),
  3. jit(step).lower(...).compile()  with explicit in/out shardings,
  4. prints memory_analysis() and cost_analysis(),
  5. parses the optimized HLO for collective bytes,
  6. writes reports/dryrun/<arch>__<shape>__<mesh>.json for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all            # every runnable cell
  python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import json
import pathlib
import time
import traceback


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: pathlib.Path,
             rdp_batches: int | None = None) -> dict:
    import jax
    from repro.configs import SHAPE_CELLS, cell_supported, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.policies import auto_policy
    from repro.launch.specs import input_specs
    from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
    from repro.roofline.analysis import analyze_compiled

    import dataclasses

    cfg = get_config(arch)
    cell = SHAPE_CELLS[shape]
    ok, reason = cell_supported(cfg, cell)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if rdp_batches:
        mesh_name = f"rdp{rdp_batches}x{mesh_name}"
    tag = f"{arch}__{shape}__{mesh_name}"
    if not ok:
        return {"cell": tag, "status": "skipped", "reason": reason}

    t0 = time.time()
    if rdp_batches:
        # the paper's technique on the mesh: data extent factored into
        # (replica, batch); replica strides across pods -> gradient traffic
        # never crosses the pod boundary (DESIGN.md §2.4)
        from repro.launch.mesh import make_rdp_production_mesh

        mesh, plan = make_rdp_production_mesh(
            rdp_batches, multi_pod=multi_pod
        )
        policy = auto_policy(cfg, cell, mesh)
        policy = dataclasses.replace(policy, dp_axes=("batch",))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        policy = auto_policy(cfg, cell, mesh)
    args, specs = input_specs(cfg, cell, policy, mesh)

    if cell.kind == "train":
        step = make_train_step(cfg, policy, mesh)
    elif cell.kind == "prefill":
        step = make_prefill_step(cfg, policy, mesh, max_len=cell.seq_len)
    else:
        step = make_decode_step(cfg, policy, mesh)

    from jax.sharding import NamedSharding

    in_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    with mesh:
        jitted = jax.jit(step, in_shardings=in_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(f"[{tag}] memory_analysis: {mem}")
    flops = cost.get("flops", 0.0) if cost else 0.0
    print(f"[{tag}] cost_analysis: flops={flops:.3e} "
          f"bytes={cost.get('bytes accessed', 0.0):.3e}" if cost else "n/a")

    report = analyze_compiled(
        compiled, cfg, cell, mesh, policy,
        lower_s=t_lower, compile_s=t_compile,
    )
    report["cell"] = tag
    report["status"] = "ok"
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{tag}.json").write_text(json.dumps(report, indent=2))
    print(f"[{tag}] lower {t_lower:.1f}s compile {t_compile:.1f}s -> ok")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rdp-batches", type=int, default=None,
                    help="factor the data extent into (replica, B) per the paper")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    from repro.configs import ARCH_IDS, SHAPE_CELLS

    use_compile_cache()

    out_dir = pathlib.Path(args.out)
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPE_CELLS:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, out_dir,
                         rdp_batches=args.rdp_batches)
            except Exception as e:  # noqa: BLE001 — report and continue
                traceback.print_exc()
                failures.append((arch, shape, mp, repr(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
