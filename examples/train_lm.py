"""End-to-end training driver: train a ~100M-parameter llama-family model
for a few hundred steps with the full production stack — data pipeline,
AdamW, remat, checkpointing, fault-tolerant loop — on the local device.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                       # noqa: E402

from repro.configs.shapes import ShapeCell          # noqa: E402
from repro.data.pipeline import DataLoader          # noqa: E402
from repro.launch import specs as lspecs            # noqa: E402
from repro.models.config import LayerKind, ModelConfig  # noqa: E402
from repro.configs import RunOverrides              # noqa: E402
from repro.optim import AdamW, cosine_schedule      # noqa: E402
from repro.training.loop import LoopConfig, Trainer  # noqa: E402
from repro.training.step import make_train_step     # noqa: E402
from repro.utils import enable_compile_cache         # noqa: E402


def model_100m() -> ModelConfig:
    # ~93M params: a llama-family config sized for a CPU-hour
    return ModelConfig(
        name="lm-100m", family="dense", n_layers=10, d_model=768,
        n_heads=12, n_kv=4, d_ff=2304, vocab=32000,
        pattern=(LayerKind(),), tie_embeddings=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = model_100m()
    run = RunOverrides()
    opt = AdamW(cosine_schedule(3e-4, args.steps // 10, args.steps))
    step_fn = jax.jit(make_train_step(cfg, opt, microbatches=1,
                                      remat="dots"),
                      donate_argnums=(0,))
    cell = ShapeCell("train", "train", args.seq, args.batch)
    loader = DataLoader(cfg, cell, 1, seed=0)
    state = lspecs.init_train_state(cfg, None, run, opt,
                                    jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model: {n_params/1e6:.1f}M params, "
          f"{args.batch}x{args.seq} tokens/step")

    tr = Trainer(step_fn, state, loader,
                 LoopConfig(total_steps=args.steps,
                            ckpt_every=max(args.steps // 3, 1),
                            ckpt_dir=args.ckpt_dir, log_every=20))
    resumed = tr.maybe_restore()
    if resumed:
        print(f"resumed from checkpoint at step {tr.step}")
    t0 = time.perf_counter()
    out = tr.run()
    dt = time.perf_counter() - t0
    loader.stop()
    for row in out["log"]:
        print(f"step {row['step']:4d}  loss {row['loss']:.4f}  "
              f"lr {row['lr']:.2e}  {row['sec_per_step']*1e3:.0f} ms/step")
    toks = args.batch * args.seq * (args.steps - (tr.step - args.steps))
    print(f"final loss {out['final_loss']:.4f}; "
          f"{dt:.0f}s wall ({args.batch*args.seq/ (dt/args.steps):.0f} tok/s)")


if __name__ == "__main__":
    main()
