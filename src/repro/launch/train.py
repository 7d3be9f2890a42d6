"""Training driver: config → CVM-planned distribution → fault-tolerant loop.

The step program is planned through CVM (see ``frontends/tensor.py``): the
parallelization rewrite decides the mesh axes and pre-aggregation, the SPMD
backend binds them to GSPMD shardings, and this driver owns the run loop:
deterministic data, checkpoint cadence, restore-on-failure, straggler log.

On the CPU container use reduced configs::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
        --steps 50 --batch 8 --seq 64
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, get_reduced
from ..data.pipeline import TokenPipeline
from ..distributed.checkpoint import CheckpointManager
from ..distributed.fault import StepRunner
from ..models.api import build_model, make_train_step
from ..train.optimizer import AdamW
from .cache import enable_compile_cache


def make_batch_fn(cfg, pipeline: TokenPipeline):
    """Adapt the token pipeline to each family's batch dict."""

    def at(step: int):
        b = pipeline.batch_at(step)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.family == "vlm":
            bsz, s = batch["tokens"].shape
            rng = np.random.default_rng((1234, step))
            batch["embeds"] = jnp.asarray(
                rng.normal(size=(bsz, s, cfg.d_model)).astype(np.float32))
            batch["positions3"] = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (3, bsz, s))
            del batch["tokens"]
        elif cfg.family == "encdec":
            bsz, s = batch["tokens"].shape
            rng = np.random.default_rng((4321, step))
            batch["frames"] = jnp.asarray(
                rng.normal(size=(bsz, s, cfg.d_model)).astype(np.float32))
        return batch

    return at


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    print(f"[train] {cfg.arch}: {cfg.n_params()/1e6:.1f}M params "
          f"({cfg.n_active_params()/1e6:.1f}M active)")

    params = model.init(jax.random.PRNGKey(0))
    step_fn, opt = make_train_step(model, AdamW(lr=args.lr),
                                   microbatch=args.microbatch)
    opt_state = opt.init(params)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        (params, opt_state), extra = ckpt.restore((params, opt_state))
        start_step = int(extra.get("step", 0))
        print(f"[train] resumed from step {start_step}")

    pipeline = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                             global_batch=args.batch)
    batch_at = make_batch_fn(cfg, pipeline)

    runner = StepRunner(
        step_fn=lambda p, o, b: jstep(p, o, b),
        ckpt=ckpt, ckpt_every=args.ckpt_every)

    def batches():
        s = start_step
        while True:
            yield s, batch_at(s)
            s += 1

    t0 = time.time()
    params, opt_state = runner.run((params, opt_state), batches(),
                                   start_step=start_step, num_steps=args.steps)
    dt = time.time() - t0
    losses = [h.loss for h in runner.history if h.loss is not None]
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({1000*dt/max(1,args.steps):.0f} ms/step); "
          f"loss {losses[0]:.3f} → {losses[-1]:.3f}; "
          f"stragglers={runner.stragglers}")
    return losses


if __name__ == "__main__":
    main()
