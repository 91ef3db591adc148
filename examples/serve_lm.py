"""Batched serving demo: prefill + jitted single-token decode loop.

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-130m \
        --batch 4 --prompt-len 64 --gen-len 64 [--quick]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.serve import ServeConfig, serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    gen, stats = serve(args.arch,
                       ServeConfig(batch=args.batch, prompt_len=args.prompt_len,
                                   gen_len=args.gen_len,
                                   temperature=args.temperature),
                       smoke=args.quick)
    print(f"generated {gen.shape} tokens; {stats['tok_per_s']:.1f} tok/s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
