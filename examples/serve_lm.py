"""Serving demo: batched prefill + decode generation with KV-cache
management (ring buffers for local-attention layers).

    PYTHONPATH=src python examples/serve_lm.py --arch recurrentgemma-2b
"""

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models.model import count_params, make_params
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    params = make_params(cfg, seed=0)
    eng = ServeEngine(cfg, params, max_seq_len=128, q_chunk=16)
    print(f"{args.arch} (reduced, {count_params(cfg)/1e6:.1f}M): "
          f"batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new_tokens}")

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    src = None
    if cfg.is_encdec:
        src = rng.normal(size=(args.batch, args.prompt_len,
                               cfg.d_model)).astype(np.float32)

    t0 = time.time()
    out = eng.generate(prompts, max_new_tokens=args.new_tokens,
                       temperature=0.8, seed=1, src_embeds=src)
    dt = time.time() - t0
    new = out[:, args.prompt_len:]
    print(f"generated {new.size} tokens in {dt:.1f}s "
          f"(incl. compile): {new.size / dt:.1f} tok/s")
    for i, row in enumerate(new[:2]):
        print(f"  seq{i}: {row.tolist()}")
    assert out.shape == (args.batch, args.prompt_len + args.new_tokens)
    print("shapes ✓")


if __name__ == "__main__":
    main()
