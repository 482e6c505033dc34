"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Single-process engine; the mesh argument scales it from a laptop (no
mesh) through a debug mesh (--devices N --mesh DxM) to the production
pod meshes (driven through the same code by the real TPU runtime). The
CORE checkpoint layer is always on — kill the process mid-run and
relaunch with the same flags to watch restart-from-CORE-restore.
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-sized sibling of --arch (CPU-friendly)")
    ap.add_argument("--mesh", default=None, help='e.g. "2x4" (needs --devices 8)')
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize-v", action="store_true",
                    help="int8 blockwise second moment (8-bit optimizer)")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.train import optimizer as opt
    from repro.train.loop import LoopConfig, Trainer

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, axes)

    lc = LoopConfig(
        steps=args.steps, ckpt_every=args.ckpt_every, log_every=args.log_every,
        seq_len=args.seq_len, global_batch=args.global_batch, seed=args.seed,
    )
    oc = opt.OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 10 + 1),
                       decay_steps=args.steps, quantize_v=args.quantize_v)

    ctx = jax.set_mesh(mesh) if mesh is not None else None
    try:
        if ctx is not None:
            ctx.__enter__()
        trainer = Trainer(cfg, lc, oc, mesh=mesh)
        state = trainer.run()
        print(f"done at step {int(state.step)}; "
              f"final loss {trainer.metrics_log[-1]['loss']:.4f}")
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
