"""Training launcher.

Single-host/CPU:      PYTHONPATH=src python -m repro.launch.train \
                          --arch smollm-360m --smoke --steps 20
Production meshes use the same Trainer with make_production_mesh(); on real
TPU pods run one process per host (jax.distributed.initialize) — the code
paths are identical, only the mesh differs.
"""
import argparse

from repro import configs as cfgs
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(cfgs.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = cfgs.SMOKE[args.arch] if args.smoke else cfgs.get(args.arch)
    mesh = (make_host_mesh() if args.mesh == "host" else
            make_production_mesh(multi_pod=(args.mesh == "multi")))
    data = SyntheticLM(cfg, DataConfig(global_batch=args.batch,
                                       seq_len=args.seq))
    trainer = Trainer(cfg, mesh,
                      tcfg=TrainerConfig(total_steps=args.steps,
                                         ckpt_period=max(args.steps // 5, 1),
                                         ckpt_dir=args.ckpt_dir),
                      data=data)
    out = trainer.run()
    print(f"done: steps={out['final_step']} "
          f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f} "
          f"events={out['events']}")


if __name__ == "__main__":
    main()
