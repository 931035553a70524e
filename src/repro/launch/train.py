"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b-smoke \
        --steps 50 --seq 128 --batch 8 --ckpt-dir /tmp/run1

Restarts resume from the latest checkpoint automatically; pass
``--devices N`` to run on N host placeholder devices with a (data, model)
mesh (set before jax initialises).
"""
import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--devices", type=int, default=0,
                    help="host placeholder devices (0 = real devices)")
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 (data x model)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--registry", default=None,
                    help="tuning-registry path: measured step times are "
                         "written back for later runs/inspection")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import logging
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    from repro.configs import get_config
    from repro.data import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train_loop import TrainConfig, Trainer

    cfg = get_config(args.arch)
    model = build_model(cfg)
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model"))

    tcfg = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir,
                       opt=AdamWConfig(lr=args.lr), seed=args.seed,
                       registry_path=args.registry)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    out = Trainer(model, tcfg, dcfg, mesh=mesh).run()
    h = out["history"]
    print(f"done: steps={len(h)} first_loss={h[0]['loss']:.4f} "
          f"final_loss={h[-1]['loss']:.4f} "
          f"stragglers={len(out['stragglers'])}")


if __name__ == "__main__":
    main()
