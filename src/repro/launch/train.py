"""Training driver: resume-first, fault-tolerant, straggler-monitored.

Usage (CPU-scale smoke):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 50 --batch 8 --seq-len 128 --ckpt-dir /tmp/run1

Production shape (on a real TPU slice the same command; the mesh adapts):
  python -m repro.launch.train --arch qwen3-8b --steps 10000 ...

Features exercised here and tested in tests/test_runtime.py:
  * checkpoint/restart (resume_or_init + AsyncCheckpointer, atomic saves),
  * deterministic restorable data order (pure function of step),
  * failure injection (--fail-at) for restart drills,
  * straggler monitoring (median+6*MAD flagging),
  * gradient accumulation (--accum) via lax.scan microbatching,
  * optional int8 gradient compression across data-parallel replicas.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import compat, sharding
from repro.checkpoint import ckpt
from repro.configs import get_config, list_archs
from repro.data import DataConfig, SyntheticCorpus
from repro.launch.mesh import make_host_mesh
from repro.models import model as model_lib
from repro.optim import adamw
from repro.runtime import fault, straggler


def make_accum_train_step(cfg, opt_cfg, sharder, accum: int):
    """Gradient-accumulated train step: microbatch scan, one optimizer
    update. batch: (accum, b_micro, S) leading layout."""

    def loss_fn(params, micro):
        return model_lib.train_loss(params, cfg, micro, sharder)

    def step_fn(state, batch):
        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"], batch)
        else:
            def micro_step(carry, micro):
                gsum, lsum = carry
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], micro)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (gsum, lsum), _ = jax.lax.scan(micro_step, (g0, 0.0), batch)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
            metrics = {}
        new_params, new_opt, info = adamw.apply(
            opt_cfg, grads, state["opt"], state["params"])
        out = {"loss": loss, **info}
        return {"params": new_params, "opt": new_opt}, out

    return step_fn


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (restart drill)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    compat.init_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, remat="none") if args.smoke else cfg
    mesh = make_host_mesh(args.model_parallel)
    dp = sharding.data_axes(mesh, args.batch)
    sharder = sharding.make_sharder(mesh, dp)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                                total_steps=max(args.steps, 1))

    data = SyntheticCorpus(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch, seed=args.seed))
    use_embeds = cfg.frontend in ("audio", "vlm")

    def get_batch(step: int) -> dict:
        if use_embeds:
            return data.embeds_at(step, cfg.d_model)
        return data.batch_at(step)

    def init_state():
        params = model_lib.init_params(jax.random.PRNGKey(args.seed), cfg)
        return {"params": params, "opt": adamw.init(params)}

    pspec = sharding.param_specs(jax.eval_shape(init_state)["params"])
    state_sharding = sharding.to_named(mesh, {
        "params": pspec,
        "opt": {"m": pspec, "v": pspec, "step": jax.sharding.PartitionSpec()},
    })

    # Build the state directly in its shards: an eager init would first
    # materialise every parameter and moment on one device.
    init_sharded = jax.jit(init_state, out_shardings=state_sharding)
    start_step = 0
    if args.ckpt_dir:
        state, start_step = fault.resume_or_init(
            args.ckpt_dir, init_sharded, shardings=state_sharding)
    else:
        state = init_sharded()

    injector = fault.FailureInjector(
        args.fail_at,
        marker_path=(os.path.join(args.ckpt_dir, "fail_marker")
                     if args.ckpt_dir else None))
    monitor = straggler.StragglerMonitor()
    saver = (ckpt.AsyncCheckpointer(args.ckpt_dir)
             if args.ckpt_dir else None)

    step_fn = make_accum_train_step(cfg, opt_cfg, sharder, args.accum)
    step_fn = jax.jit(step_fn, donate_argnums=(0,))

    losses = []
    try:
        with mesh:
            for step in range(start_step, args.steps):
                injector.check(step)
                monitor.start_step()
                batch = get_batch(step)
                if args.accum > 1:
                    batch = jax.tree.map(
                        lambda x: x.reshape(args.accum, -1, *x.shape[1:]),
                        batch)
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])
                monitor.end_step(step)
                losses.append(loss)
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss {loss:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f}",
                          flush=True)
                if saver and (step + 1) % args.ckpt_every == 0:
                    saver.save(step + 1, state)
    except BaseException:
        # A crash (including an injected SimulatedFailure) must not abandon
        # an in-flight async checkpoint: the write the failing run already
        # started is the one a restart resumes from, and dropping it made
        # kill/resume nondeterministic (resume from N vs N - ckpt_every
        # depending on thread timing). Drain it, then re-raise the real
        # failure — a secondary checkpoint error must not mask it.
        if saver:
            try:
                saver.wait()
            except Exception:
                pass
        raise
    if saver:
        saver.save(args.steps, state)
        saver.wait()
    # Where the trained parameters live: bytes of parameter shards per
    # device id, read from the step's output shardings.
    per_device: dict = {}
    for leaf in jax.tree.leaves(state["params"]):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (per_device.get(shard.device.id, 0)
                                           + shard.data.nbytes)
    result = {
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "losses": losses,
        "straggler_events": len(monitor.events),
        "final_step": args.steps,
        "param_bytes_per_device": dict(sorted(per_device.items())),
    }
    print(json.dumps({k: v for k, v in result.items() if k != "losses"}))
    return result


if __name__ == "__main__":
    main()
