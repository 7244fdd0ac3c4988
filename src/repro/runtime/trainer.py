"""Fault-tolerant training runtime.

- one jitted shard_map'd train step (model fwd+bwd, hierarchical grad sync,
  ZeRO-1 AdamW) with donated params/opt-state,
- checkpoint/restart (async sharded saves; exact data-stream reseek),
- step retry + reload-on-failure,
- straggler detection (step-time EWMA watchdog),
- elastic restart hook (rebuild mesh from survivors, reshard from the last
  checkpoint) — exercised by tests via simulated failures.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig, ParallelConfig
from repro.data.pipeline import DataConfig, batch_at
from repro.models import model as M
from repro.optim import adamw, schedule as sched
from repro.parallel.sharding import TPContext

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainConfig:
    total_steps: int = 100
    warmup_steps: int = 10
    base_lr: float = 3e-4
    schedule: str = "cosine"
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    log_every: int = 10
    straggler_factor: float = 3.0      # step slower than EWMA*factor -> flag
    max_retries: int = 2
    seed: int = 0


def make_ctx(cfg: ModelConfig, par: ParallelConfig, mesh,
             plans=None) -> TPContext:
    # a dedicated "ep" axis also carries batch: tokens live on their own EP
    # slice and only the moe_a2a seam crosses it
    dp_axes = tuple(a for a in ("pod", "ep", "data") if a in mesh.axis_names)
    ep_axes = M._ep_axes(cfg, par)
    if plans is None:
        # uniform PlanSet from overlap_mode, overlaid with par.plan_profile
        # (the tuned per-seam profile) when present and fresh
        from repro.tuning import plan_set_from_parallel
        plans = plan_set_from_parallel(par)
    return TPContext(axis="model", dp_axes=dp_axes, ep_axes=ep_axes,
                     mode=par.overlap_mode, comm_chunks=par.comm_chunks,
                     use_kernels=par.kernel_decode, plans=plans)


def batch_pspecs(cfg: ModelConfig, mesh, seq_sharded: bool = True) -> Dict:
    """Batch specs at the shard_map boundary.  Frontend embeds arrive in
    the residual-stream layout (``sharding.activation_spec``): sequence on
    the model axis under SP, replicated otherwise; tokens/labels are always
    full-sequence (the embedding's collective produces the layout)."""
    from repro.parallel.sharding import activation_spec
    dp_axes = tuple(a for a in ("pod", "ep", "data") if a in mesh.axis_names)
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    if cfg.frontend:
        return {"embeds": activation_spec(dp_axes, seq_sharded),
                "labels": P(dp, None)}
    return {"tokens": P(dp, None), "labels": P(dp, None)}


def make_train_step(cfg: ModelConfig, par: ParallelConfig, mesh,
                    opt_cfg: adamw.AdamWConfig, train_cfg: TrainConfig,
                    param_spec_tree) -> Callable:
    """Returns jitted (params, opt, batch, step) -> (params, opt, metrics)."""
    ctx = make_ctx(cfg, par, mesh)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    ep_axis = "ep" if "ep" in mesh.axis_names else None
    ep_n = dict(zip(mesh.axis_names, mesh.devices.shape)).get("ep", 1)
    model_rep = adamw.model_replicated_tree(param_spec_tree)
    ep_rep = (adamw.axis_replicated_tree(param_spec_tree, "ep")
              if ep_axis else None)
    schedule_fn = sched.get_schedule(train_cfg.schedule)
    # batch layout follows the plans' resolved residual layout (the
    # trainer's backward rides the interchanged seam ops either way)
    bspecs = batch_pspecs(cfg, mesh, ctx.seq_sharded)

    params_eval = jax.eval_shape(
        lambda: M.init_model(jax.random.PRNGKey(0), cfg, par))
    opt_specs = adamw.opt_state_specs(param_spec_tree, params_eval,
                                      par.dp, par.tp, ep=max(par.ep, 1))

    def step_fn(params, opt, batch, step):
        loss, grads = jax.value_and_grad(
            lambda p: M.forward_loss(p, batch, ctx, cfg, par))(params)
        # model-replicated leaves: complete their grads over the TP axis
        grads = jax.tree.map(
            lambda g, rep: lax.psum(g, "model") if rep else g,
            grads, model_rep)
        if ep_axis is not None:
            # dedicated EP axis: ep-replicated leaves carry per-EP-shard
            # partial grads (the EP axis shards the batch) -> average them;
            # the EP-sharded expert leaves already SUM every EP rank's token
            # contribution through the a2a backward -> rescale that sum into
            # the same per-shard average
            grads = jax.tree.map(
                lambda g, rep: lax.pmean(g, ep_axis) if rep else g / ep_n,
                grads, ep_rep)
        loss = lax.pmean(loss, ctx.dp_axes)
        lr = schedule_fn(step, base_lr=train_cfg.base_lr,
                         warmup=train_cfg.warmup_steps,
                         total=train_cfg.total_steps)
        params, opt = adamw.adamw_update(
            params, grads, opt, opt_cfg, lr, specs=param_spec_tree,
            dp_axis="data", pod_axis=pod_axis, ep_axis=ep_axis,
            grad_compress=par.grad_compress)
        metrics = {"loss": loss, "lr": lr,
                   "grad_count": opt["count"].astype(jnp.float32)}
        return params, opt, metrics

    sm = compat.shard_map(
        step_fn, mesh=mesh,
        in_specs=(param_spec_tree, opt_specs, bspecs, P()),
        out_specs=(param_spec_tree, opt_specs, {"loss": P(), "lr": P(),
                                                "grad_count": P()}),
        check_vma=False)
    return jax.jit(sm, donate_argnums=(0, 1))


class Trainer:
    def __init__(self, cfg: ModelConfig, par: ParallelConfig, mesh,
                 train_cfg: TrainConfig,
                 opt_cfg: Optional[adamw.AdamWConfig] = None):
        self.cfg = cfg
        self.par = par
        self.mesh = mesh
        self.tc = train_cfg
        self.oc = opt_cfg or adamw.AdamWConfig(lr=train_cfg.base_lr)
        self.step = 0
        self.failures = 0
        self.straggler_events = 0
        self._ewma: Optional[float] = None

        params_eval = jax.eval_shape(
            lambda: M.init_model(jax.random.PRNGKey(train_cfg.seed), cfg, par))
        self.pspecs = M.param_specs(cfg, par, params_eval)
        self.step_fn = make_train_step(cfg, par, mesh, self.oc, train_cfg,
                                       self.pspecs)
        self.ckpt = (Checkpointer(train_cfg.checkpoint_dir)
                     if train_cfg.checkpoint_dir else None)

        self.data_cfg = DataConfig(
            vocab_size=cfg.vocab_size, seq_len=256, global_batch=8,
            seed=train_cfg.seed)

    # ------------------------------------------------------------------ setup
    def init_state(self):
        with self.mesh:
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.pspecs,
                is_leaf=lambda x: isinstance(x, P))
            # partitionable threefry keeps the values layout-invariant,
            # and no device ever holds the whole tree
            params = jax.jit(
                functools.partial(M.init_model, cfg=self.cfg, par=self.par),
                out_shardings=shardings)(jax.random.PRNGKey(self.tc.seed))
            params_eval = jax.eval_shape(
                lambda: M.init_model(jax.random.PRNGKey(0), self.cfg, self.par))
            opt_specs = adamw.opt_state_specs(self.pspecs, params_eval,
                                              self.par.dp, self.par.tp,
                                              ep=max(self.par.ep, 1))
            opt_shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), opt_specs,
                is_leaf=lambda x: isinstance(x, P))
            opt = jax.jit(functools.partial(
                adamw.init_opt_state, moment_dtype=self.oc.moment_dtype),
                out_shardings=opt_shardings)(params)
        return params, opt

    def _data(self, step: int) -> Dict[str, np.ndarray]:
        return batch_at(self.data_cfg, step)

    # ------------------------------------------------------------------ loop
    def train(self, params=None, opt=None, resume: bool = True,
              fault_hook: Optional[Callable[[int], None]] = None):
        """Run to total_steps.  ``fault_hook(step)`` may raise to simulate
        failures (tests); recovery reloads the last checkpoint and reseeks
        the data stream."""
        if params is None:
            params, opt = self.init_state()
        if self.ckpt and resume and self.ckpt.latest_step() is not None:
            state, self.step, _ = self.ckpt.restore(
                {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            log.info("resumed at step %d", self.step)

        metrics_hist = []
        while self.step < self.tc.total_steps:
            t0 = time.perf_counter()
            batch = self._data(self.step)
            try:
                if fault_hook is not None:
                    fault_hook(self.step)
                params, opt, metrics = self.step_fn(
                    params, opt, batch, jnp.asarray(self.step, jnp.int32))
                jax.block_until_ready(metrics["loss"])
            except Exception as e:  # noqa: BLE001 — any failure triggers recovery
                self.failures += 1
                if self.failures > self.tc.max_retries:
                    raise
                log.warning("step %d failed (%s); recovering", self.step, e)
                params, opt = self._recover()
                continue

            dt = time.perf_counter() - t0
            if self._ewma is None:
                self._ewma = dt
            elif dt > self.tc.straggler_factor * self._ewma:
                self.straggler_events += 1
                log.warning("straggler: step %d took %.3fs (ewma %.3fs)",
                            self.step, dt, self._ewma)
            self._ewma = 0.9 * self._ewma + 0.1 * dt if self._ewma else dt

            self.step += 1
            metrics_hist.append(
                {k: float(v) for k, v in metrics.items()})
            if self.ckpt and self.step % self.tc.checkpoint_every == 0:
                self.ckpt.save(self.step, {"params": params, "opt": opt},
                               extra={"step": self.step})
            if self.step % self.tc.log_every == 0:
                log.info("step %d loss %.4f", self.step,
                         metrics_hist[-1]["loss"])
        if self.ckpt:
            self.ckpt.wait()
        return params, opt, metrics_hist

    # ------------------------------------------------------------- recovery
    def _recover(self):
        """Reload the last checkpoint (or re-init) after a failure."""
        params, opt = self.init_state()
        if self.ckpt and self.ckpt.latest_step() is not None:
            state, step, _ = self.ckpt.restore({"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            self.step = step
        else:
            self.step = 0
        return params, opt
