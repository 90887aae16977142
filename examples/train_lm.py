"""End-to-end training driver: train a smollm-family model with the full
stack — PRNG-kernel data pipeline, AdamW, async checkpointing, heartbeat
supervision, auto-resume, and integrated profiling.

Default config is CPU-sized (~9M params) so the loop visibly learns in a
couple of minutes; ``--full`` selects a ~100M-param config (what you would
run on real accelerators for a few hundred steps).

Run:  PYTHONPATH=src python examples/train_lm.py --steps 30
      PYTHONPATH=src python examples/train_lm.py --resume   (continues)
"""

import argparse
import dataclasses

from repro.configs import get_smoke_config
from repro.core.compile_cache import use_compile_cache
from repro.models.model import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def small_cfg() -> ModelConfig:
    return dataclasses.replace(
        get_smoke_config("smollm-360m"),
        name="smollm-mini", num_layers=4, d_model=256, n_heads=4,
        n_kv_heads=2, head_dim=64, d_ff=1024, vocab=8192)


def full_cfg() -> ModelConfig:
    # ~100M params: what the paper-scale example would train on device
    return dataclasses.replace(
        get_smoke_config("smollm-360m"),
        name="smollm-100m", num_layers=12, d_model=640, n_heads=10,
        n_kv_heads=2, head_dim=64, d_ff=1792, vocab=49152)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure (demonstrates auto-resume)")
    args = ap.parse_args()

    use_compile_cache()
    cfg = full_cfg() if args.full else small_cfg()
    from repro.models.model import param_count
    print(f"model: {cfg.name} ({param_count(cfg)[0]:,} params)")

    opt = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    tcfg = TrainerConfig(total_steps=args.steps, batch=args.batch,
                         seq=args.seq, ckpt_every=10, log_every=5,
                         ckpt_dir=args.ckpt_dir, fail_at_step=args.fail_at)
    trainer = Trainer(cfg, opt, tcfg)
    result = trainer.run()
    print(f"\nfinal loss: {result['final_loss']:.4f} "
          f"({result['wall_s']:.1f}s wall)")
    print("\n" + trainer.summary())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
