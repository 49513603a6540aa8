"""End-to-end training driver: a small GQA LM trained for a few hundred
steps on CPU with the full production stack — sharded train step, AdamW,
deterministic data pipeline, async checkpointing, fault coordinator
(with an injected failure to demonstrate recovery).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import dataclasses
import tempfile
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models.model import count_params, make_params
from repro.train.checkpoint import CheckpointManager
from repro.train.fault import Coordinator, StragglerDetector
from repro.train.optimizer import OptConfig, init_state
from repro.train.train_loop import build_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--inject-failure", action="store_true", default=True)
    args = ap.parse_args()
    enable_compile_cache()

    # a genuinely trainable-on-CPU config of the selected family
    cfg = dataclasses.replace(
        get_config(args.arch).reduced(),
        num_layers=4, d_model=256, d_ff=1024, vocab_size=2048)
    n = len(jax.devices())
    mesh = jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = ShapeSpec("cpu_demo", "train", args.seq, args.batch)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps,
                        weight_decay=0.01)
    step_fn, shardings, _ = build_train_step(
        cfg, mesh, shape, opt_cfg, q_chunk=args.seq, remat=False)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))

    params = make_params(cfg, seed=0)
    opt = init_state(params)
    print(f"arch family {args.arch}: {count_params(cfg)/1e6:.1f}M params, "
          f"{n} devices, batch {args.batch}x{args.seq}")

    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    batch=args.batch, seq_len=args.seq,
                                    zipf_a=1.2, seed=0))
    ckdir = tempfile.mkdtemp(prefix="repro_ckpt_")
    mgr = CheckpointManager(ckdir, keep=2)

    state = {"params": params, "opt": opt, "step": np.int64(0)}
    injected = {"done": not args.inject_failure}

    def wrapped_step(st, batch):
        if not injected["done"] and int(st["step"]) == args.steps // 2:
            injected["done"] = True
            raise RuntimeError("injected node failure (demo)")
        p, o, metrics = jstep(st["params"], st["opt"], batch)
        return ({"params": p, "opt": o, "step": st["step"] + 1}, metrics)

    losses = []

    def batch_fn(s):
        return {k: jax.numpy.asarray(v) for k, v in
                pipe.batch_at(s).items()}

    coord = Coordinator(wrapped_step, batch_fn, mgr, ckpt_every=50,
                        straggler=StragglerDetector())
    t0 = time.time()
    state, last, hist = coord.run(state, 0, args.steps)
    dt = time.time() - t0

    for h in hist:
        losses.append(h.get("loss", float("nan")))
    first = np.nanmean(losses[:10])
    final = np.nanmean(losses[-10:])
    toks = args.steps * args.batch * args.seq
    print(f"\ntrained {last} steps in {dt:.1f}s "
          f"({toks / dt:.0f} tok/s incl. compile)")
    print(f"loss: first-10 avg {first:.3f} -> last-10 avg {final:.3f}")
    print(f"recoveries: {len(coord.restarts)} "
          f"{[r['error'] for r in coord.restarts]}")
    print(f"checkpoints kept: {mgr.all_steps()} under {ckdir}")
    assert final < first, "loss should decrease"
    print("loss decreased ✓")


if __name__ == "__main__":
    main()
