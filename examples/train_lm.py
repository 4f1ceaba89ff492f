"""End-to-end driver: train a small LM for a few hundred steps on CPU with
replication-planned data sharding, checkpointing, and a mid-run simulated
failure + restart.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch qwen2-1.5b]

The model is the reduced (same-family) config; pass --full-scale to print the
full-config training setup that the production launcher would use instead.
"""
import argparse
import shutil

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core.service_time import ShiftedExponential
from repro.data import PipelineConfig, SyntheticLM
from repro.distributed import rdp
from repro.models import build_model
from repro.optim import AdamW, cosine_with_warmup
from repro.runtime.train import init_state, make_train_step

CKPT = "/tmp/repro_example_train"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    # 1. replication plan for a 16-worker budget with moderate straggling
    ctl = rdp.ElasticController(ShiftedExponential(delta=0.05, mu=5.0))
    plan = ctl.initial_plan(16)
    print(f"[plan] B={plan.n_batches} shards x r={plan.replication} replicas")

    cfg = get_config(args.arch, smoke=True)
    model = build_model(cfg)
    pipe = SyntheticLM(PipelineConfig(cfg.vocab_size, args.seq, args.batch, seed=1))
    opt = AdamW(cosine_with_warmup(3e-3, 20, args.steps))
    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0,))

    shutil.rmtree(CKPT, ignore_errors=True)
    mgr = CheckpointManager(CKPT, keep=2)
    state = init_state(model, opt, jax.random.key(0))
    ceiling = pipe.bigram_ceiling_loss()
    uniform = float(np.log(cfg.vocab_size))
    print(f"[data] uniform loss {uniform:.3f}, bigram ceiling {ceiling:.3f}")

    crash_at = args.steps // 2
    first_loss = None
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.global_batch(step).items()}
        state, metrics = step_fn(state, batch)
        if first_loss is None:
            first_loss = float(metrics["loss"])
        if step % 25 == 0:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f}")
        if step == crash_at:
            mgr.save(step, state)
            print(f"[failure-injection] crash at step {step}; restarting from checkpoint")
            # simulate process restart: rebuild everything from disk
            state = init_state(model, opt, jax.random.key(0))
            restored, s = mgr.restore(jax.eval_shape(lambda: state))
            state = jax.tree.map(jnp.asarray, restored)
            assert s == crash_at
            # a worker also died: replan replication for the survivors
            tr = ctl.on_membership_change(plan, n_healthy=14)
            print(f"[elastic] replanned: B={tr.new_plan.n_batches} r={tr.new_plan.replication}")
    final = float(metrics["loss"])
    print(f"[done] loss {first_loss:.3f} -> {final:.3f} (ceiling {ceiling:.3f})")
    assert final < first_loss * 0.7, "model failed to learn"
    print("OK: model learned the bigram structure through a failure+restart")


if __name__ == "__main__":
    enable_compile_cache()
    main()
