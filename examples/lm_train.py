"""LM training driver: train a reduced config of any assigned architecture
for a few hundred steps on CPU (full-scale shardings come from the same
builders — see src/repro/launch/dryrun.py for the 512-chip lowering).

    PYTHONPATH=src python examples/lm_train.py --arch qwen3-1.7b --steps 200
    PYTHONPATH=src python examples/lm_train.py --arch mixtral-8x22b \
        --steps 50 --compress
"""
import argparse
import tempfile

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import ARCH_NAMES, smoke_config
from repro.data.loader import TokenLoader
from repro.launch import train as train_lib
from repro.launch.mesh import make_mesh
from repro.optim.adam import Adam, cosine_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=ARCH_NAMES)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--compress", action="store_true",
                    help="int8+error-feedback gradient compression")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = smoke_config(args.arch).scaled(
        n_layers=max(smoke_config(args.arch).n_layers, 4))
    mesh = make_mesh((1,), ("data",))
    opt = Adam(lr=cosine_schedule(3e-3, warmup=20, total=args.steps),
               clip_norm=1.0, weight_decay=0.01)
    state = train_lib.init_state(jax.random.PRNGKey(0), cfg, opt,
                                 compress=args.compress)
    step_fn, jitted = train_lib.make_train_step(
        cfg, mesh, opt, microbatches=args.microbatches, remat=True,
        compress=args.compress, attn_impl="jnp")
    jstep = jitted(state)
    loader = TokenLoader(cfg, mesh, batch=args.batch, seq=args.seq)

    with tempfile.TemporaryDirectory() as tmp:
        mgr = CheckpointManager(tmp, keep=2)
        for i in range(args.steps):
            state, metrics = jstep(state, next(loader))
            if i % 20 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(metrics.loss):.4f} "
                      f"gnorm={float(metrics.grad_norm):.2f} "
                      f"moe_aux={float(metrics.moe_loss):.3f}")
            if (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, state, sync=False)
        mgr.wait()
        print(f"checkpoints kept: {mgr.steps()}; loader cursor: "
              f"{loader.save_state()}")


if __name__ == "__main__":
    main()
