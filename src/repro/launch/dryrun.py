import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)
"""Multi-pod dry-run driver.

For every (architecture x input-shape) cell, builds the production mesh
(single-pod 16x16 = 256 chips, multi-pod 2x16x16 = 512 chips), jits the cell's
step function with explicit in_shardings, ``.lower().compile()``s it on 512
placeholder host devices, and records:

  * memory_analysis()  -> bytes per device (fits-in-HBM evidence)
  * cost_analysis()    -> per-device FLOPs / bytes (roofline numerators)
  * compiled HLO text  -> collective op census (collective roofline term)

Results are written to experiments/dryrun/<cell>__<mesh>.json and summarized
by ``python -m repro.launch.dryrun --all`` (one subprocess per cell for
isolation) or run inline for a single cell.

NOTE: the XLA_FLAGS line above MUST run before any other import touches jax.
"""
import argparse
import json
import subprocess
import sys
import time


def _compile_cell(cell, mesh):
    import jax

    t0 = time.perf_counter()
    with mesh:
        from repro.distributed.act import use_act_sharding

        with use_act_sharding(mesh, cell.cfg):
            jitted = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                donate_argnums=cell.donate_argnums,
            )
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str,
             pallas: bool = False, overrides_json: str = "",
             analysis: bool = True, tag: str = "") -> dict:
    from repro.configs import get_config
    from repro.launch import mesh as meshmod
    from repro.launch.cells import build_cell
    from repro.launch.roofline import analyze_compiled, parse_collectives

    mesh = meshmod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    overrides = json.loads(overrides_json) if overrides_json else None

    # 1. PRODUCTION compile: proves the distribution config; memory analysis.
    cell = build_cell(arch, shape, mesh, pallas=pallas, overrides=overrides)
    compiled, dt = _compile_cell(cell, mesh)
    rf = analyze_compiled(cell.label, mesh_kind, chips, compiled,
                          cell.model_flops, dt, cell.notes)

    # 2. ANALYSIS compiles (nsb=1, nsb=2, unrolled): XLA counts while-loop
    # bodies once, so the production module under-reports flops; the unrolled
    # delta between 2 and 1 superblocks gives the exact per-superblock cost.
    # (The roofline table is single-pod only; multi-pod runs skip analysis.)
    if analysis and mesh_kind != "multi":
        nsb = get_config(arch).num_superblocks
        costs = {}
        for n in (1, 2):
            acell = build_cell(arch, shape, mesh, pallas=pallas,
                               overrides=overrides, analysis_nsb=n)
            acomp, adt = _compile_cell(acell, mesh)
            ca = acomp.cost_analysis()
            coll = parse_collectives(acomp.as_text(), chips)
            costs[n] = dict(
                flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                coll=coll.effective_bytes,
                counts=dict(coll.counts),
                bytes_by_kind=dict(coll.bytes_by_kind),
                compile_s=adt,
            )
        d_flops = costs[2]["flops"] - costs[1]["flops"]
        d_bytes = costs[2]["bytes"] - costs[1]["bytes"]
        d_coll = costs[2]["coll"] - costs[1]["coll"]
        rf.flops_per_device = costs[1]["flops"] + (nsb - 1) * d_flops
        rf.bytes_per_device = costs[1]["bytes"] + (nsb - 1) * d_bytes
        rf.collective_bytes_eff = costs[1]["coll"] + (nsb - 1) * max(d_coll, 0.0)
        rf.notes = (rf.notes + f" | analysis: nsb1={costs[1]['flops']:.3e}f "
                    f"nsb2={costs[2]['flops']:.3e}f extrapolated x{nsb}").strip(" |")

    result = rf.to_dict()
    if analysis and mesh_kind != "multi":
        # per-kind raw collective bytes, extrapolated to full depth
        kinds = set(costs[1]["bytes_by_kind"]) | set(costs[2]["bytes_by_kind"])
        result["collective_bytes_by_kind_extrapolated"] = {
            k: costs[1]["bytes_by_kind"].get(k, 0.0)
            + (nsb - 1) * (costs[2]["bytes_by_kind"].get(k, 0.0)
                           - costs[1]["bytes_by_kind"].get(k, 0.0))
            for k in kinds
        }
        result["collective_counts_analysis"] = {
            k: [costs[1]["counts"].get(k, 0),
                costs[2]["counts"].get(k, 0)] for k in kinds
        }
    print(f"[dryrun] {cell.label} mesh={mesh_kind} chips={chips} "
          f"compile={dt:.1f}s flops/dev={rf.flops_per_device:.3e} "
          f"bytes/dev={rf.bytes_per_device:.3e} "
          f"coll_eff={rf.collective_bytes_eff:.3e} "
          f"peak_mem={result['memory']['peak_bytes_est']/2**30:.2f}GiB "
          f"bottleneck={rf.bottleneck}")
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = f"{arch}__{shape}__{mesh_kind}{suffix}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="run every applicable cell")
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--overrides", type=str, default="", help="JSON ArchConfig overrides")
    ap.add_argument("--tag", type=str, default="", help="suffix for the output file (hillclimb variants)")
    ap.add_argument("--no-analysis", action="store_true",
                    help="production compile only (skip the unrolled nsb=1/2 passes)")
    ap.add_argument("--jobs", type=int, default=2, help="parallel subprocesses for --all")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()

    if args.all:
        from repro.launch.cells import all_cells

        cells = all_cells()
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        jobs = []
        for arch, shape in cells:
            for mk in meshes:
                jobs.append((arch, shape, mk))
        print(f"[dryrun] {len(jobs)} cell-compiles queued")
        procs: list[tuple[tuple, subprocess.Popen]] = []
        failures = []
        t_all = time.perf_counter()

        def drain(block_until_below: int):
            while len([p for _, p in procs if p.poll() is None]) >= block_until_below:
                time.sleep(2.0)
            for job, p in list(procs):
                if p.poll() is not None:
                    if p.returncode != 0:
                        failures.append(job)
                        print(f"[dryrun] FAIL {job} rc={p.returncode}")
                    procs.remove((job, p))

        for job in jobs:
            arch, shape, mk = job
            fname = os.path.join(args.out, f"{arch}__{shape}__{mk}.json")
            if os.path.exists(fname):
                print(f"[dryrun] skip (cached) {job}")
                continue
            drain(args.jobs)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mk, "--out", args.out]
            if args.use_pallas:
                cmd.append("--use-pallas")
            p = subprocess.Popen(cmd, env={**os.environ, "PYTHONPATH": "src"})
            procs.append((job, p))
        drain(1)
        print(f"[dryrun] done in {time.perf_counter()-t_all:.0f}s; "
              f"{len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape, "--arch/--shape required (or --all)"
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        run_cell(args.arch, args.shape, mk, args.out,
                 pallas=args.use_pallas, overrides_json=args.overrides,
                 tag=args.tag, analysis=not args.no_analysis)


if __name__ == "__main__":
    main()
