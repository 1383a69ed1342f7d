"""End-to-end driver: train a transformer LM with CWFL gradient aggregation.

This is the shard-mode integration (DESIGN.md §3): clients are data-parallel
groups; the CWFL consensus enters as per-example loss weights + channel
noise. Data is a synthetic Markov token stream (offline container).

Default: a ~6M-parameter model, 300 steps, CPU-friendly (~5 min).
``--large`` trains a ~100M-parameter model (slow on 1 CPU — use fewer steps).

    PYTHONPATH=src python examples/train_lm_cwfl.py [--steps 300] [--large]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.dist.fl_integration import make_fl_plan
from repro.launch.mesh import make_local_mesh
from repro.models.config import ArchConfig, InputShape, LayerSpec
from repro.data import make_token_dataset
from repro.training import dist_steps as ds
from repro.checkpoint import save_checkpoint
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--large", action="store_true")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--snr-db", type=float, default=40.0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    if args.large:   # ~100M params
        cfg = ArchConfig(name="lm-100m", arch_type="dense", num_layers=12,
                         d_model=768, num_heads=12, num_kv_heads=4,
                         d_ff=2048, vocab_size=32768,
                         pattern=(LayerSpec(),), tie_embeddings=True)
    else:            # ~6M params
        cfg = ArchConfig(name="lm-6m", arch_type="dense", num_layers=4,
                         d_model=256, num_heads=4, num_kv_heads=2,
                         d_ff=768, vocab_size=4096,
                         pattern=(LayerSpec(),), tie_embeddings=True)

    from repro.models.transformer import count_params, init_params
    print(f"model: {cfg.name}  params={count_params(cfg)/1e6:.1f}M")

    mesh = make_local_mesh(1, 1)
    shape = InputShape("train", args.seq, args.batch, "train")
    plan = make_fl_plan(args.clients, min(3, args.clients),
                        jax.random.PRNGKey(0), snr_db=args.snr_db)
    print(f"CWFL plan: {args.clients} clients, clusters="
          f"{plan.assignment.tolist()}, channel-noise std={plan.noise_std:.2e}")

    step_fn, _, _ = ds.make_train_step(cfg, shape, mesh, plan=plan,
                                       lr=args.lr, microbatches=1)
    step_fn = jax.jit(step_fn)

    data = make_token_dataset(jax.random.PRNGKey(1), cfg.vocab_size,
                              num_sequences=4096, seq_len=args.seq)
    params = init_params(jax.random.PRNGKey(2), cfg)
    from repro.optim import sgd
    opt_state = sgd(args.lr).init(params)

    key = jax.random.PRNGKey(3)
    t0 = time.time()
    for step in range(args.steps):
        k_it, k_noise, key = jax.random.split(key, 3)
        idx = jax.random.randint(k_it, (args.batch,), 0, data.shape[0])
        seqs = data[idx]
        batch = {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             k_noise)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  ce={float(metrics['ce']):.4f}  "
                  f"({(time.time()-t0):.0f}s)")
    uniform = float(jnp.log(cfg.vocab_size))
    print(f"final ce {float(metrics['ce']):.3f} vs uniform {uniform:.3f}")
    if args.ckpt:
        path = save_checkpoint(args.ckpt, args.steps, params)
        print("checkpoint:", path)


if __name__ == "__main__":
    main()
