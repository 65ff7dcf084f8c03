"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs real training steps on the local device(s) with the same
``make_train_step`` the dry-run lowers, on the synthetic corpus.
``--arch <id>`` trains the published config; ``--arch <id>-smoke`` its
reduced CPU-test variant.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.common import count_params
from repro.training.checkpoint import load_checkpoint, save_checkpoint
from repro.training.data import DataConfig, SyntheticCorpus
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.training.train_step import make_train_step


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    help="registry id: the published config; append "
                         "-smoke for the reduced CPU-test variant")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--resume", default="")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    print(f"# arch={cfg.name} family={cfg.family} layers={cfg.num_layers} "
          f"d_model={cfg.d_model}")
    params = init_params(cfg, jax.random.PRNGKey(0))
    print(f"# params: {count_params(params) / 1e6:.2f}M")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    opt = init_opt_state(opt_cfg, params)
    start = 0
    if args.resume:
        params, opt, start = load_checkpoint(args.resume, params, opt)
        print(f"# resumed from {args.resume} at step {start}")

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, moe_mode="dense",
                                      remat=True))
    data = SyntheticCorpus(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        batch_size=args.batch)).batches()

    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        if cfg.frontend != "none":
            # modality stub: frames/patches instead of token ids
            B, S = batch["tokens"].shape
            batch = {"embeds": jax.random.normal(
                jax.random.PRNGKey(step), (B, S, cfg.d_model)),
                "labels": batch["tokens"]}
        params, opt, stats = step_fn(params, opt, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            tps = (step - start + 1) * args.batch * args.seq \
                / max(time.time() - t0, 1e-9)
            print(f"step {step:5d}  loss {float(stats['loss']):.4f}  "
                  f"ce {float(stats['ce']):.4f}  "
                  f"gnorm {float(stats['grad_norm']):.3f}  "
                  f"tok/s {tps:.0f}", flush=True)
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, opt, args.steps,
                        meta={"arch": cfg.name})
        print(f"# saved {args.checkpoint}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
