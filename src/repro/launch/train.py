"""CLI training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch chb-paper-lm-124m \
      --algorithm chb --steps 200 --global-batch 16 --seq-len 256
"""
import argparse

from ..configs import ARCHS, get
from ..train.trainer import TrainConfig, train
from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chb-paper-lm-124m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the tiny smoke variant of the arch")
    ap.add_argument("--algorithm", default="chb",
                    choices=["gd", "hb", "lag", "chb"])
    ap.add_argument("--strategy", default="scan", choices=["scan", "pod"])
    ap.add_argument("--num-workers", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=3e-2)
    ap.add_argument("--beta", type=float, default=0.4)
    ap.add_argument("--eps1-scale", type=float, default=0.1)
    ap.add_argument("--quantize", default=None, choices=["int8"])
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--use-mesh", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None
    if args.use_mesh or args.strategy == "pod":
        mesh = make_local_mesh(args.model_parallel, pods=args.pods
                               if args.strategy == "pod" else 1)
    tc = TrainConfig(algorithm=args.algorithm, strategy=args.strategy,
                     num_workers=args.num_workers, alpha=args.alpha,
                     beta=args.beta, eps1_scale=args.eps1_scale,
                     quantize=args.quantize, global_batch=args.global_batch,
                     seq_len=args.seq_len, steps=args.steps,
                     ckpt_every=args.ckpt_every)
    ctx = mesh if mesh is not None else _null()
    with ctx:
        train(cfg, tc, mesh=mesh)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
