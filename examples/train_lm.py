"""End-to-end driver: train a ~100M-parameter LM with the HFEL hierarchical
sync schedule (Algorithm 1 at datacenter scale), checkpointing and restart.

Two "virtual pods" hold independent parameter copies; every step is an
edge-tier update (pod-local), every I-th step a cloud sync averages the
pods — exactly the paper's L/I structure. On a CPU container this runs a
scaled-down profile by default; pass --profile full for the 100M config.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import SyncLevel, SyncSchedule
from repro.data import TokenPipeline
from repro.models import build_model
from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--profile", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--local-iters", type=int, default=5)
    ap.add_argument("--edge-iters", type=int, default=4)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/hfel_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    base = get_config("qwen3-0.6b")
    if args.profile == "full":     # ~100M params
        cfg = dataclasses.replace(base, n_layers=8, d_model=512, n_heads=8,
                                  n_kv_heads=4, head_dim=64, d_ff=2048,
                                  vocab_size=32_768, dtype="float32",
                                  max_seq_len=512)
        batch, seq = 8, 256
    else:
        cfg = base.reduced(n_layers=2, vocab_size=512)
        batch, seq = 4, 64
    model = build_model(cfg)
    print(f"config {cfg.name}: ~{cfg.param_count()/1e6:.1f}M params "
          f"(profile={args.profile})")

    # one parameter copy per virtual pod (HFEL edge tier)
    params = [model.init(jax.random.key(p)) for p in range(args.pods)]
    opt = clip_by_global_norm(adamw(3e-3), 1.0)
    opt_states = [opt.init(p) for p in params]
    # all pods start from pod 0's weights (the paper broadcasts omega^0)
    params = [params[0]] * args.pods

    pipes = [TokenPipeline(cfg.vocab_size, seq, batch, seed=17 + p)
             for p in range(args.pods)]
    sched = SyncSchedule(args.local_iters, args.edge_iters)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)

    @jax.jit
    # hfellint: disable=HFEL006 -- pods alias one params pytree after init
    def train_step(params, opt_state, step, tokens):
        # (and after every cloud sync): donating pod p's buffers would
        # invalidate the other pods' step inputs
        loss, g = jax.value_and_grad(model.loss)(params, {"tokens": tokens})
        upd, opt_state = opt.update(g, opt_state, params, step)
        return apply_updates(params, upd), opt_state, loss

    start = 0
    if mgr.latest_step() is not None:
        s, restored, _ = mgr.restore(template={"params": params,
                                               "opt": opt_states})
        params, opt_states = restored["params"], restored["opt"]
        start = s
        print(f"resumed from checkpoint at step {start}")

    t0 = time.perf_counter()
    for step in range(start, args.steps):
        losses = []
        for p in range(args.pods):
            tokens = jnp.asarray(next(pipes[p]))
            params[p], opt_states[p], loss = train_step(
                params[p], opt_states[p], step, tokens)
            losses.append(float(loss))
        if sched.level(step) == SyncLevel.CLOUD:
            mean = jax.tree.map(lambda *xs: sum(xs) / len(xs), *params)
            params = [mean] * args.pods
        if (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_states})
        if step % 10 == 0 or step == args.steps - 1:
            lvl = sched.level(step).name
            print(f"step {step:4d} loss {sum(losses)/len(losses):.4f} "
                  f"sync={lvl} ({(time.perf_counter()-t0):.1f}s)")
    mgr.wait()
    print("done.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
