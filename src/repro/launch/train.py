"""Training driver.

CPU/demo:   PYTHONPATH=src python -m repro.launch.train --arch yi_6b --smoke --steps 30
Production: launched per-host on a pod slice with the same flags minus
--smoke; the mesh comes from make_production_mesh() and the checkpoint
directory must be shared storage.  The driver enables XLA's latency-hiding
scheduler for compute/communication overlap on TPU.
"""

import argparse
import os

# compute/comm overlap (no effect on CPU, required for perf on TPU)
os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_latency_hiding_scheduler=true",
)

import jax

from repro.configs import registry
from repro.data import synthetic
from repro.launch.compile_cache import use_compile_cache
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod
from repro.train import trainer as trainer_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    cfg = registry.get_smoke(args.arch) if args.smoke else registry.get(args.arch)
    tcfg = ts_mod.TrainConfig(
        arch=cfg,
        opt=opt_mod.AdamWConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                                total_steps=args.steps),
        grad_accum=cfg.train_grad_accum if not args.smoke else 1,
    )
    trainer_cfg = trainer_mod.TrainerConfig(
        train=tcfg, total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every)

    if args.smoke:
        mesh = None  # trainer builds the smoke mesh
    else:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    data_cfg = synthetic.TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=tcfg.seed)
    res = trainer_mod.train(trainer_cfg, mesh=mesh, data_cfg=data_cfg)
    print(f"done: final loss {res['losses'][-1]:.4f} over {args.steps} steps; "
          f"straggler events: {len(res['watchdog'])}")


if __name__ == "__main__":
    main()
