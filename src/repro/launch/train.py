"""Distributed trainer: pjit train step with DP/TP/EP/SP sharding,
microbatched gradient accumulation, remat, checkpoint/restart, straggler
watchdog, and preemption-safe exit.

Runnable directly:
    PYTHONPATH=src python -m repro.launch.train --arch quickstart --steps 20
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import sharding as shard_lib
from repro.distributed.fault_tolerance import (Heartbeat, PreemptionGuard,
                                               retry_step)
from repro.models import ModelConfig, init, loss_fn
from repro.models import model as model_lib
from repro.optim.adamw import (AdamWConfig, apply_updates, init_state)


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    grad_accum: int = 1
    log_every: int = 10
    ckpt_every: int = 0            # 0 = only at exit
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_keep: int = 3
    seed: int = 0
    fsdp: bool = False
    seq_shard_acts: bool = False
    straggler_deadline_s: float = 600.0
    step_retries: int = 3          # transient-classified retries per step
    retry_backoff_s: float = 0.5   # jittered-exponential backoff base
    optimizer: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    """Returns train_step(params, opt_state, batch) -> (p, s, metrics).
    batch arrays have a leading grad_accum axis when accum > 1."""

    def single(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)

    def step(params, opt_state, batch):
        if tcfg.grad_accum == 1:
            (loss, metrics), grads = single(params, batch)
        else:
            def micro(carry, mb):
                acc_g, acc_l = carry
                (l, _), g = single(params, mb)
                return (jax.tree.map(jnp.add, acc_g, g), acc_l + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), batch)
            inv = 1.0 / tcfg.grad_accum
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            metrics = {"loss": loss, "aux_loss": jnp.zeros(()),
                       "tokens": jnp.asarray(0., jnp.float32)}
        params, opt_state, opt_metrics = apply_updates(
            params, grads, opt_state, tcfg.optimizer)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return step


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.ckpt_keep)

        abs_params = model_lib.abstract_init(cfg)
        self.param_specs = shard_lib.param_spec_tree(
            abs_params, cfg, fsdp=tcfg.fsdp)
        if mesh is not None:
            self.param_shardings = shard_lib.named_sharding_tree(
                self.param_specs, mesh)
            self.batch_shardings = shard_lib.batch_specs(
                mesh, cfg.input_mode)
            self.act = shard_lib.act_specs(
                mesh, seq_shard=tcfg.seq_shard_acts)
        else:
            self.param_shardings = None
            self.batch_shardings = None
            self.act = None

        step = make_train_step(cfg, tcfg)
        if mesh is not None:
            opt_shard = {"m": self.param_shardings,
                         "v": self.param_shardings,
                         "count": NamedSharding(mesh, P())}
            bshard = dict(self.batch_shardings)
            if tcfg.grad_accum > 1:
                bshard = {k: NamedSharding(
                    mesh, P(None, *v.spec)) for k, v in bshard.items()}
            ns = NamedSharding(mesh, P())
            self._step = jax.jit(
                step,
                in_shardings=(self.param_shardings, opt_shard, bshard),
                out_shardings=(self.param_shardings, opt_shard,
                               {"loss": ns, "aux_loss": ns, "tokens": ns,
                                "grad_norm": ns, "lr": ns}),
                donate_argnums=(0, 1))
        else:
            self._step = jax.jit(step, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def init_params(self):
        if self.mesh is not None:
            init_fn = jax.jit(partial(init, cfg=self.cfg),
                              out_shardings=self.param_shardings)
            with self.mesh:
                params = init_fn(jax.random.PRNGKey(self.tcfg.seed))
        else:
            params = init(jax.random.PRNGKey(self.tcfg.seed), self.cfg)
        opt_state = init_state(params, self.tcfg.optimizer)
        return params, opt_state

    def restore_or_init(self, pipeline=None):
        """Elastic restore: the checkpoint re-lays-out onto this mesh."""
        try:
            abs_params = model_lib.abstract_init(self.cfg)
            step, params, opt_state, meta = self.ckpt.restore(
                None, abs_params, None, shardings=self.param_shardings)
            if opt_state is None:
                opt_state = init_state(params, self.tcfg.optimizer)
            if pipeline is not None and meta.get("data_state"):
                pipeline.load_state_dict(meta["data_state"])
            return step, params, opt_state
        except FileNotFoundError:
            params, opt_state = self.init_params()
            return 0, params, opt_state

    def _device_batch(self, batch: Dict[str, np.ndarray]):
        if self.tcfg.grad_accum > 1:
            def reshape(x):
                a = self.tcfg.grad_accum
                return x.reshape((a, x.shape[0] // a) + x.shape[1:])
            batch = {k: reshape(v) for k, v in batch.items()}
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, batch)
        sh = self.batch_shardings
        if self.tcfg.grad_accum > 1:
            sh = {k: NamedSharding(self.mesh, P(None, *v.spec))
                  for k, v in sh.items()}
        return {k: jax.device_put(v, sh[k]) for k, v in batch.items()}

    def _write_failure(self, step: int, exc: BaseException) -> str:
        """Publish a machine-readable failure report next to the
        checkpoints before the train loop dies."""
        from repro.runtime.guard import FailureReport, classify_error
        report = FailureReport(
            name="train.step", error=str(exc),
            error_type=type(exc).__name__,
            classification=classify_error(exc),
            attempts=1 + self.tcfg.step_retries, time=time.time())
        path = os.path.join(self.tcfg.ckpt_dir,
                            f"failure_step_{step:010d}.json")
        try:
            return report.write(path)
        except OSError:
            return ""

    def run(self, pipeline: SyntheticPipeline, steps: Optional[int] = None):
        steps = steps or self.tcfg.steps
        start, params, opt_state = self.restore_or_init(pipeline)
        hb = Heartbeat(self.tcfg.straggler_deadline_s,
                       on_straggle=lambda dt: print(
                           f"[straggler] step exceeded deadline: {dt:.1f}s"))
        history = []
        ctx = self.mesh if self.mesh is not None else _nullcontext()
        act_ctx = (shard_lib.activation_specs(self.act)
                   if self.act else _nullcontext())
        with PreemptionGuard() as guard, ctx, act_ctx:
            step = start - 1  # a restored ckpt at/past `steps` skips the loop
            for step in range(start, steps):
                batch = self._device_batch(pipeline.next_batch())
                t0 = time.perf_counter()
                try:
                    params, opt_state, metrics = retry_step(
                        self._step, params, opt_state, batch,
                        retries=self.tcfg.step_retries,
                        backoff_s=self.tcfg.retry_backoff_s,
                        seed=self.tcfg.seed,
                        on_retry=lambda a, e: print(
                            f"[retry] step {step} attempt {a}: {e}"))
                except Exception as e:
                    self._write_failure(step, e)
                    raise
                metrics = {k: float(v) for k, v in metrics.items()}
                metrics["step_time_s"] = time.perf_counter() - t0
                hb.beat()
                history.append(metrics)
                if step % self.tcfg.log_every == 0:
                    print(f"step {step}: loss={metrics['loss']:.4f} "
                          f"gnorm={metrics['grad_norm']:.3f} "
                          f"lr={metrics['lr']:.2e} "
                          f"t={metrics['step_time_s']:.3f}s")
                if (self.tcfg.ckpt_every
                        and step and step % self.tcfg.ckpt_every == 0):
                    self.ckpt.save(step, params, opt_state,
                                   pipeline.state_dict())
                if guard.fired:
                    print("[preemption] SIGTERM received; checkpointing")
                    break
            final_step = step + 1 if not guard.fired else step
            self.ckpt.save(final_step, params, opt_state,
                           pipeline.state_dict())
        return params, opt_state, history


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="quickstart")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--grid-lowering", default="",
                    choices=("", "closed_form", "prefetch_lut", "bounding",
                             "mma", "compact"),
                    help="GridPlan lowering for the attention block "
                         "domain (default: the arch's attn_schedule)")
    ap.add_argument("--mesh", default="",
                    help="train on a device mesh: 'host' (all devices, "
                         "tp=1) or 'DATAxMODEL' (e.g. '4x2').  Shared "
                         "by the sharding.py rules and the block-space "
                         "kernels (shard_axis 'data').")
    ap.add_argument("--backend", default="",
                    choices=("", "tpu", "gpu", "tpu-interpret",
                             "gpu-interpret", "interpret"),
                    help="kernel emission target for every block-space "
                         "Pallas call (repro.core.backend; default: "
                         "platform / REPRO_BACKEND)")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=True if args.smoke else None)
    if args.grid_lowering:
        cfg = cfg.replace(grid_lowering=args.grid_lowering)
        print(f"grid lowering: {cfg.grid_mode} "
              f"(xla schedule: {cfg.attn_schedule_resolved})")
    if args.backend:
        from repro.core import backend as backend_lib
        backend_lib.set_default(args.backend)
        print(f"kernel backend: {backend_lib.resolve(None).name}")

    tcfg = TrainConfig(
        steps=args.steps, grad_accum=args.grad_accum,
        ckpt_dir=args.ckpt_dir,
        optimizer=AdamWConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(1, args.steps // 10)))
    pipe = SyntheticPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, input_mode=cfg.input_mode,
        d_model=cfg.d_model))
    from repro.launch.mesh import resolve_cli_mesh
    mesh = resolve_cli_mesh(args.mesh)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.flat)} "
              f"devices (kernels shard over axis 'data')")
    trainer = Trainer(cfg, tcfg, mesh=mesh)
    trainer.run(pipe)


if __name__ == "__main__":
    main()
