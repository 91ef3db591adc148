"""End-to-end training driver: mamba2-130m (a real ~130M-param config) on
the synthetic token stream, with checkpointing + resume.

    PYTHONPATH=src python examples/train_lm.py --steps 300          # full 130M
    PYTHONPATH=src python examples/train_lm.py --quick --steps 50   # reduced

The full model at seq 128 / batch 4 is CPU-runnable (~10 s/step); on the
production mesh this is exactly what launch/dryrun.py compiles at
train_4k scale.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.train import TrainLoopConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    loop = TrainLoopConfig(steps=args.steps, seq_len=args.seq_len,
                           global_batch=args.global_batch,
                           ckpt_dir=args.ckpt_dir, resume=args.resume,
                           ckpt_every=max(args.steps // 4, 10), log_every=5)
    _, _, hist = train("mamba2-130m", loop, smoke=args.quick)
    print(f"loss: {hist[0]:.4f} -> {hist[-1]:.4f} over {len(hist)} steps")
    assert hist[-1] < hist[0], "loss must decrease"


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
