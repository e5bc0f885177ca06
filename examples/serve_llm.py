"""Batched serving example: prefill + decode with KV/state caches for any
assigned architecture (attention, MoE, RWKV, hybrid, enc-dec all share the
same serve API).

    PYTHONPATH=src python examples/serve_llm.py --arch rwkv6-7b
"""
import argparse

from repro.launch.serve import serve
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full", action="store_true",
                    help="full config (TPU-scale; default uses smoke config)")
    args = ap.parse_args()
    r = serve(args.arch, args.batch, args.prompt_len, args.gen,
              smoke=not args.full)
    print(f"arch={args.arch} generated {r['tokens'].shape}")
    print(f"TTFT {r['ttft_s'] * 1e3:.1f} ms   TPOT {r['tpot_s'] * 1e3:.2f} ms")
    print("sample:", r["tokens"][0][:12])


if __name__ == "__main__":
    main()
