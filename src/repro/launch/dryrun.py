import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (arch x shape) cell on the
production meshes and capture the roofline terms.

The two lines above MUST stay first: jax locks the device count on
first init, and only the dry-run wants 512 placeholder devices (smoke
tests and benches see 1). See the brief, MULTI-POD DRY-RUN step 0.

Usage:
  python -m repro.launch.dryrun --arch mistral_large_123b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod] \
      --out benchmarks/results/dryrun
  (…or --mesh 4x4 for a small debug mesh; --devices N to shrink the
   placeholder device pool.)

Per cell it writes <out>/<arch>.<shape>.<mesh>.json with
memory_analysis, cost_analysis flops/bytes, parsed collective wire
bytes, and the three roofline terms (EXPERIMENTS.md §Dry-run/§Roofline
read these files).
"""

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402


def _mesh_from_arg(arg: str, multi_pod: bool):
    import jax
    from repro.launch.mesh import make_mesh, make_production_mesh

    if arg == "prod":
        return make_production_mesh(multi_pod=multi_pod), (
            "pod2x16x16" if multi_pod else "pod16x16"
        )
    dims = tuple(int(x) for x in arg.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    return make_mesh(dims, axes), arg


HBM_BUDGET = 14e9  # leave ~2 GB headroom on a 16 GB v5e


def pick_strategy(cfg, cell_shape, mesh) -> str:
    """Beyond-paper sharding strategy per cell (EXPERIMENTS.md §Perf):
    train -> pure-FSDP when the global batch covers the mesh and the
    state+saves fit; decode -> TP-only (weights replicated over data)
    when bf16 params/tp + the cache shard fit HBM; else the 2-D
    Megatron x ZeRO default."""
    import numpy as np
    from repro.launch.specs import param_count
    from repro.models.registry import get_model
    from repro.models.shardings import axes_for_mesh as afm

    api = get_model(cfg)
    n_params = param_count(cfg, api)
    n_dev = mesh.devices.size
    shape_d = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = shape_d.get("model", 1)
    if cell_shape.kind == "train":
        ax = afm(mesh, strategy="fsdp")
        if cell_shape.global_batch % max(ax.dp_size, 1):
            return "2d"
        state = n_params * 10 / ax.fsdp_size  # bf16 p + f32 m + f32 v
        tokens_per_chip = cell_shape.global_batch * cell_shape.seq_len / n_dev
        block = cfg.remat_block or cfg.num_layers
        layers_saved = (cfg.num_layers // block) if cfg.remat_block else cfg.num_layers
        saves = layers_saved * tokens_per_chip * cfg.d_model * 2
        return "fsdp" if state + saves < HBM_BUDGET else "2d"
    if cell_shape.kind == "decode":
        cache = api.cache_shape(cfg, cell_shape.global_batch, cell_shape.seq_len)
        import jax
        cache_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                          for s in jax.tree.leaves(cache)) / n_dev
        if n_params * 2 / tp + cache_bytes < HBM_BUDGET:
            return "tp_only"
    return "2d"


def run_cell(arch: str, shape: str, mesh, mesh_name: str, out_dir: str | None,
             verbose: bool = True, strategy: str = "2d") -> dict:
    import jax
    from repro.analysis.roofline import analyze_hlo
    from repro.configs import SHAPES, get_config
    from repro.launch.specs import input_specs
    from repro.models.registry import get_model
    from repro.models.shardings import axes_for_mesh

    cfg = get_config(arch)
    cell_shape = SHAPES[shape]
    api = get_model(cfg)
    if strategy == "auto":
        strategy = pick_strategy(cfg, cell_shape, mesh)
    ax = axes_for_mesh(mesh, strategy=strategy)
    if strategy == "fsdp" and (cell_shape.kind != "train"
                               or cell_shape.global_batch % max(ax.dp_size, 1)):
        ax = axes_for_mesh(mesh)  # strategy is train-only / batch-divisible
        strategy = "2d"
    n_dev = mesh.devices.size

    t0 = time.perf_counter()
    cell = input_specs(cfg, cell_shape, api, ax)

    def shard(tree, specs):
        return jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )

    in_shardings = tuple(shard(a, s) for a, s in zip(cell.args, cell.in_specs))
    import glob
    import shutil
    import tempfile

    dump_dir = tempfile.mkdtemp(prefix="dryrun_hlo_")
    with jax.set_mesh(mesh):
        lowered = jax.jit(cell.step, in_shardings=in_shardings).lower(*cell.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile(
            compiler_options={
                "xla_dump_to": dump_dir,
                "xla_dump_hlo_pass_re": "spmd-partitioning",
            }
        )
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        if verbose:
            print(mem)
        if verbose:
            flops = compiled.cost_analysis().get("flops", 0.0)
            print(f"builtin cost_analysis (per-chip, scan bodies counted once): "
                  f"flops={flops:.3e}")
        # prefer the post-SPMD, pre-backend HLO snapshot: it is the
        # TPU-relevant program (collectives inserted, per-partition
        # shapes, no CPU bf16->f32 normalization artifacts)
        snaps = sorted(glob.glob(os.path.join(dump_dir, "*after_spmd-partitioning*")))
        hlo_text = open(snaps[-1]).read() if snaps else compiled.as_text()
        roof = analyze_hlo(
            hlo_text, arch=arch, shape=shape, mesh_name=mesh_name,
            num_devices=n_dev, model_flops_global=cell.model_flops,
            compiled=compiled,
        )
    shutil.rmtree(dump_dir, ignore_errors=True)

    rec = roof.to_dict()
    rec.update(
        kind=cell.kind,
        strategy=strategy,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        arg_bytes_per_chip=int(mem.argument_size_in_bytes),
        temp_bytes_per_chip=int(mem.temp_size_in_bytes),
        out_bytes_per_chip=int(mem.output_size_in_bytes),
        meta=cell.meta,
    )
    if verbose:
        print(
            f"[{arch} x {shape} x {mesh_name}] kind={cell.kind} "
            f"t_comp={roof.t_compute*1e3:.2f}ms t_mem={roof.t_memory*1e3:.2f}ms "
            f"t_coll={roof.t_collective*1e3:.2f}ms bound={roof.bottleneck} "
            f"useful={roof.useful_flops_ratio:.2f} mfu_bound={roof.mfu_bound:.2f}"
        )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if strategy == "2d" else f".{strategy}"
        fn = os.path.join(out_dir, f"{arch}.{shape}.{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default="prod", help='"prod" or e.g. "4x4"')
    ap.add_argument("--out", default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="shrink the placeholder device pool (debug)")
    ap.add_argument("--strategy", default="2d", choices=["2d", "fsdp", "tp_only", "auto"],
                    help="train-cell sharding strategy (see shardings.axes_for_mesh)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    from repro.configs import ARCH_IDS, SHAPES

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    mesh, mesh_name = _mesh_from_arg(args.mesh, args.multi_pod)

    failures = []
    for arch in archs:
        for shape in shapes:
            try:
                run_cell(arch, shape, mesh, mesh_name, args.out,
                         strategy=args.strategy)
            except Exception:
                traceback.print_exc()
                failures.append((arch, shape))
    if failures:
        print("FAILED cells:", failures)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
