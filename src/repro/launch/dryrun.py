import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on
the production meshes and record memory/cost/collective artifacts.

CPU-only tool: the lines above give JAX 512 fake host-platform devices and
keep it off any accelerator, in this process and in the worker processes
``--jobs`` starts, so it never holds a chip. They must execute before any
other import (jax locks the device count at first backend init), hence the
unusual module layout. Its roofline uses the v5e peaks it targets
(``repro.roofline.hw.TPU_V5E``); its numbers are compile-time estimates,
never measurements. To compile a kernel for a real v5e without the chip,
describe the topology instead (see tests/test_tpu_compile.py).

Methodology notes (see EXPERIMENTS.md section Dry-run):
* memory_analysis comes from the full-depth scan-over-layers compile — the
  deployable program with accurate peak buffers.
* XLA cost_analysis counts a while-loop body ONCE regardless of trip count
  (verified: a length-8 scan of a matmul reports 1 matmul of flops), so
  flops/bytes/collectives for scanned families (lm, gnn) are derived from
  two fully-UNROLLED depth probes (L=1, L=2):
      total(L) = probe(1) + (L - 1) * (probe(2) - probe(1))
  Layers are homogeneous, so the extrapolation is exact (up to constant
  folding noise). knn cells unroll their ring scans directly; recsys cells
  have no loops. Probes run only on the single-pod mesh (the roofline table
  is single-pod); the multi-pod pass proves the `pod` axis shards.

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--jobs N]

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _named(mesh, specs):
    """PartitionSpecs (None = replicated) as NamedShardings on `mesh`."""
    from jax.sharding import NamedSharding, PartitionSpec

    import jax

    def wrap(s):
        return NamedSharding(mesh, PartitionSpec() if s is None else s)

    return jax.tree.map(
        wrap, specs,
        is_leaf=lambda s: s is None or isinstance(s, PartitionSpec))


def _compile_cell(arch, shape, mesh):
    import jax

    from repro.launch.steps import build_cell

    with jax.set_mesh(mesh):
        cell = build_cell(arch, shape, smoke=False)
        donate = ()
        if shape.kind in ("train", "train_sampled", "train_batched"):
            donate = (0, 1)
        elif shape.kind == "decode":
            donate = (1,)
        jf = jax.jit(cell.fn,
                     in_shardings=_named(mesh, cell.in_specs),
                     out_shardings=_named(mesh, cell.out_specs),
                     donate_argnums=donate)
        lowered = jf.lower(*cell.inputs)
        compiled = lowered.compile()
    return cell, compiled


def _cost_triple(compiled, chips):
    from repro.roofline.analysis import collective_bytes_from_hlo

    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text(), default_group=chips)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            coll.wire_bytes_per_device,
            coll)


def _probe_arch(arch, n_layers):
    m = dataclasses.replace(arch.model, n_layers=n_layers, scan_unroll=True)
    return dataclasses.replace(arch, model=m)


def run_cell(arch_id: str, shape_name: str, mesh_kind: str) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import roofline_terms
    from repro.roofline.hw import TPU_V5E

    arch = get_config(arch_id)
    shape = arch.shape(shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size

    t0 = time.time()
    cell, compiled = _compile_cell(arch, shape, mesh)
    t_full = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)  # the brief requires the raw analyses printed
    f_pd, b_pd, w_pd, coll = _cost_triple(compiled, chips)
    print({"flops": f_pd, "bytes accessed": b_pd})

    # ---- loop-corrected cost totals (single-pod probes only)
    probes = None
    scanned = arch.family in ("lm", "gnn")
    if mesh_kind == "single" and scanned:
        t1 = time.time()
        _, c1 = _compile_cell(_probe_arch(arch, 1), shape, mesh)
        f1, b1, w1, _ = _cost_triple(c1, chips)
        del c1
        _, c2 = _compile_cell(_probe_arch(arch, 2), shape, mesh)
        f2, b2, w2, _ = _cost_triple(c2, chips)
        del c2
        L = arch.model.n_layers
        # clamp: on tiny graphs XLA constant-folding makes the L1/L2 slope
        # noisy (even negative); the full-L compile (body counted once) is a
        # strict lower bound on the true totals.
        f_pd = max(f1 + (L - 1) * (f2 - f1), f_pd, 0.0)
        b_pd = max(b1 + (L - 1) * (b2 - b1), b_pd, 0.0)
        w_pd = max(w1 + (L - 1) * (w2 - w1), w_pd, 0.0)
        probes = {"probe_s": round(time.time() - t1, 1),
                  "l1": {"flops": f1, "bytes": b1, "wire": w1},
                  "l2": {"flops": f2, "bytes": b2, "wire": w2}}

    roof = roofline_terms(
        f_pd * chips, b_pd * chips, w_pd, chips,
        model_flops=cell.meta.get("model_flops", 0), hw=TPU_V5E)
    roof.collective_ops = {k: {"count": coll.op_counts[k],
                               "bytes": coll.op_bytes[k]} for k in coll.op_counts}

    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return {
        "arch": arch_id, "shape": shape_name, "kind": shape.kind,
        "mesh": mesh_kind, "chips": chips, "ok": True,
        "compile_s": round(t_full, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_bytes": per_device,
            "fits_v5e_hbm": bool(per_device <= TPU_V5E.hbm_bytes),
            "hbm_utilization": per_device / TPU_V5E.hbm_bytes,
        },
        "cost_analysis": {
            "flops_per_device": f_pd,
            "bytes_accessed_per_device": b_pd,
            "wire_bytes_per_device": w_pd,
            "loop_corrected": probes is not None,
        },
        "probes": probes,
        "roofline": roof.to_dict(),
        "meta": {k: (v if isinstance(v, str) else int(v))
                 for k, v in cell.meta.items()},
    }


def save(record: dict) -> pathlib.Path:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    path = ARTIFACTS / name.replace("/", "_")
    path.write_text(json.dumps(record, indent=2, default=str))
    return path


def orchestrate(mesh_kinds, jobs: int, archs=None, force=False) -> int:
    """Run every cell in isolated subprocesses (crash isolation; compile is
    single-core-bound so jobs>1 mostly overlaps python tracing with XLA)."""
    from repro.configs import ALL_ARCHS, get_config

    work = []
    for arch_id in archs or ALL_ARCHS:
        cfg = get_config(arch_id)
        for shape in cfg.shapes:
            for mk in mesh_kinds:
                out = ARTIFACTS / f"{arch_id}__{shape.name}__{mk}.json"
                if force or not out.exists() or not json.loads(out.read_text()).get("ok"):
                    work.append((arch_id, shape.name, mk))
    print(f"dry-run: {len(work)} cells to build", flush=True)
    procs = []
    failed = []

    def drain(limit: int):
        while True:
            for w, p in list(procs):
                if p.poll() is not None:
                    procs.remove((w, p))
                    status = "ok  " if p.returncode == 0 else "FAIL"
                    if p.returncode != 0:
                        failed.append(w)
                    print(f"{status} {w[0]}/{w[1]}/{w[2]}", flush=True)
            if len(procs) < limit:
                return
            time.sleep(3)

    for w in work:
        drain(jobs)
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", w[0], "--shape", w[1], "--mesh", w[2]]
        procs.append((w, subprocess.Popen(
            cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)))
    drain(1)
    if failed:
        print(f"{len(failed)} FAILED: {failed}", flush=True)
        return 1
    print("all cells compiled", flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        sys.exit(orchestrate(mesh_kinds, args.jobs, force=args.force))

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    try:
        rec = run_cell(args.arch, args.shape, mesh_kinds[0])
        p = save(rec)
        print(f"wrote {p}")
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": mesh_kinds[0],
               "ok": False, "error": traceback.format_exc()}
        save(rec)
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
