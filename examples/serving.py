"""Batched serving demo over the assigned architectures.

    PYTHONPATH=src python examples/serving.py [--arch rwkv6-3b]
"""
import argparse

from repro.compile_cache import use_compile_cache
from repro.launch.serve import run_serving


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    run_serving(args.arch, batch=args.batch, prompt_len=32, gen_len=16)


if __name__ == "__main__":
    main()
