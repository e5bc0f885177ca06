"""End-to-end training driver: train a ~100M-param llama-family model for a
few hundred steps on the deterministic synthetic LM stream, with async
checkpointing and resume.

Full run (~100M params — heavy on CPU, the real target is the TPU mesh):
    PYTHONPATH=src python examples/train_lm.py --steps 300

CI-scale check (reduced width, same code path):
    PYTHONPATH=src python examples/train_lm.py --steps 60 --ci
"""
import argparse
import dataclasses

import numpy as np

from repro.configs import get_arch
from repro.launch.train import train
import repro.launch.train as T
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ci", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    if args.ci:
        losses = train("llama3.2-1b", steps=args.steps, batch=8, seq=64,
                       smoke=True, ckpt_dir=args.ckpt_dir)
    else:
        # ~100M: llama3.2-1b narrowed (8 layers, d_model 768, vocab 32k)
        cfg = get_arch("llama3.2-1b")
        small = dataclasses.replace(
            cfg, name="llama-100m", n_layers=8, d_model=768, n_heads=12,
            n_kv_heads=4, head_dim=64, d_ff=2048, vocab=32000,
            tie_embeddings=True)
        from repro.configs import ARCHS
        ARCHS[small.name] = small
        losses = train(small.name, steps=args.steps, batch=8, seq=256,
                       smoke=False, ckpt_dir=args.ckpt_dir)
    print(f"final loss {np.mean(losses[-10:]):.4f} "
          f"(start {np.mean(losses[:10]):.4f})")


if __name__ == "__main__":
    main()
