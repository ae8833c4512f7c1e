"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --shape train_4k --steps 200 --local   # CPU smoke (reduced shapes)

``--local`` runs on the locally visible devices with reduced shapes (the
path exercised in CI); without it the production mesh is built (requires a
real slice or the dry-run's forced host devices).
"""

from __future__ import annotations

import argparse
import logging

from repro.configs import get_config, get_shape
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.runtime.optimizer import AdamWConfig
from repro.runtime.steps import MeshPlan
from repro.runtime.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--local", action="store_true",
                    help="local devices + reduced model/shape (smoke mode)")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    if args.local:
        cfg = cfg.reduced()
        mesh = make_local_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    plan = MeshPlan.for_mesh(mesh)
    tcfg = TrainerConfig(num_steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, seed=args.seed,
                         reduced_shapes=args.local)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps)
    trainer = Trainer(cfg, shape, plan, tcfg, opt)
    out = trainer.train()
    print(f"done: step={out['final_step']} last_loss={out['losses'][-1]:.4f} "
          f"recoveries={out['recoveries']} stragglers={out['straggler_flags']}")


if __name__ == "__main__":
    main()
