"""End-to-end driver (deliverable b): train the ~100M-parameter assigned
architecture (xlstm-125m) for a few hundred steps on synthetic token data.

    PYTHONPATH=src python examples/train_e2e.py --steps 200          # full
    PYTHONPATH=src python examples/train_e2e.py --steps 30 --quick   # CI

--quick shrinks seq/batch so the run finishes in minutes on this 1-core CPU
container; the step code, config and sharding rules are identical to what
the dry-run proves out at the production mesh."""
import argparse
import time

import jax

from repro import optim
from repro.checkpoint import save_pytree
from repro.configs import TrainConfig, get_config
from repro.data.tokens import topic_token_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model, make_train_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--ckpt", default="runs/train_e2e/xlstm125m.npz")
    args = ap.parse_args()
    if args.quick:
        args.seq, args.batch = 64, 2

    cfg = get_config("xlstm-125m")   # the ~100M assigned arch, full config
    model = build_model(cfg)
    tc = TrainConfig(optimizer="adamw", learning_rate=6e-4,
                     total_steps=args.steps,
                     warmup_steps=max(5, args.steps // 20))
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    opt = optim.init_opt_state(params, tc.optimizer)
    step = jax.jit(make_train_step(model, tc), donate_argnums=(0, 1))
    print(f"model={cfg.name} params={model.n_params():,} "
          f"steps={args.steps} batch={args.batch} seq={args.seq}")

    mesh = make_host_mesh()
    losses = []
    with mesh:
        t0 = time.time()
        for i in range(args.steps):
            toks = topic_token_batch(jax.random.fold_in(key, i),
                                     batch=args.batch, seq_len=args.seq,
                                     vocab=cfg.vocab_size, topic=i % 8)
            params, opt, m = step(params, opt, {"tokens": toks,
                                                "labels": toks})
            losses.append(float(m["loss"]))
            if i % 10 == 0 or i == args.steps - 1:
                rate = (i + 1) * args.batch * args.seq / (time.time() - t0)
                print(f"step {i:4d} loss={losses[-1]:.4f} "
                      f"({rate:.0f} tok/s)", flush=True)
    w = max(5, args.steps // 10)
    first = sum(losses[:w]) / w
    last = sum(losses[-w:]) / w
    print(f"mean loss first {w} steps {first:.4f} -> last {w} steps "
          f"{last:.4f}")
    if args.steps >= 100:   # short CPU demo runs are too noisy to gate on
        assert last < first, "loss did not decrease"
    save_pytree(args.ckpt, params)
    print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
