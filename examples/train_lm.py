"""Training-substrate driver: train a small LM with the full runtime stack
(AdamW, schedules, remat, checkpoint/auto-resume, deterministic data).

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

Interrupt it and re-run — it resumes from the newest checkpoint.  The
clustering pipeline (examples/dti_pointcloud.py) is the paper's own
end-to-end driver; this one exercises the LM training path the assigned
architectures run through.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.data.tokens import MarkovTokenStream
from repro.models import transformer as tfm
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.state import init_state, make_train_step
from repro.launch.cache import enable_compile_cache

PRESETS = {
    # ~5M params: CPU-friendly demo
    "tiny": tfm.TransformerConfig(
        name="tiny", n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, d_head=64,
        d_ff=1024, vocab=4096, dtype=jnp.float32, attn_chunk=128,
    ),
    # ~100M params: the assignment's example scale (hours on 1 CPU core;
    # minutes on any accelerator)
    "100m": tfm.TransformerConfig(
        name="100m", n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_head=64,
        d_ff=2048, vocab=32768, dtype=jnp.float32, attn_chunk=256,
    ),
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    print(f"model {cfg.name}: ~{cfg.param_count()/1e6:.1f}M params")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    state = init_state(params)

    opt = AdamWConfig(lr=6e-4, warmup_steps=20, total_steps=args.steps)
    step_fn = jax.jit(make_train_step(lambda p, b: tfm.train_loss(p, b, cfg), opt),
                      donate_argnums=(0,))

    stream = MarkovTokenStream(cfg.vocab, seed=0)

    def batches(step):
        stream._step = step  # deterministic per step => restart-reproducible
        b = stream.next_batch(args.batch, args.seq)
        return {"tokens": jnp.asarray(b["tokens"]), "labels": jnp.asarray(b["labels"])}

    run_training(step_fn, state, batches,
                 TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                                 ckpt_every=50, log_every=10))


if __name__ == "__main__":
    main()
