"""Trainer: distributed training loop with ScalAna as a first-class feature.

Responsibilities:
  * build model + optimizer + data from a RunConfig;
  * one jitted ``train_step`` (grad accumulation via ``lax.scan`` over
    microbatches, optional int8 error-feedback gradient compression);
  * sharding: params/opt-state via logical rules, batch over ('pod','data');
  * fault tolerance: async checkpoints + auto-resume; step timeout guard;
  * ScalAna hooks: static PSG at build time, sampled per-vertex profiling
    every K steps (GraphProfiler), per-step wall times feeding abnormal/
    straggler detection, optional injected per-rank delay for case studies.

On CPU this runs real smoke-scale training; on a pod the same code lowers
with NamedShardings (the dry-run compiles exactly ``make_train_step``'s
function for the production meshes).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgbase
from repro.configs import get as get_config
from repro.configs import SHAPES
from repro.core.profiler import GraphProfiler
from repro.core.spans import span
from repro.checkpoint import CheckpointManager
from repro.data import make_dataset
from repro.distributed.axes import spec_for, use_rules
from repro.models.api import ModelBundle, build_model
from repro.optim import adamw_init, adamw_update, warmup_cosine
from repro.optim.compress import error_feedback_update, init_residual

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Pytree
    opt: Any                      # AdamWState
    residual: Optional[Pytree]    # error-feedback residual (or None)
    step: jax.Array               # i32


def make_train_step(model: ModelBundle, run: cfgbase.RunConfig,
                    lr_fn: Callable[[jax.Array], jax.Array]
                    ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the pure train-step function (grad-accum + AdamW [+ EF-int8])."""
    nmicro = max(int(run.microbatch), 1)
    compress = bool(getattr(run, "grad_compress", False))

    def loss_fn(params, batch):
        loss, metrics = model.train_loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single_grads(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    def accum_grads(params, batch):
        # split leading batch dim into (nmicro, B/nmicro, ...); scan
        def split(x):
            b = x.shape[0]
            assert b % nmicro == 0, (b, nmicro)
            return x.reshape((nmicro, b // nmicro) + x.shape[1:])

        micro = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, mb):
            acc, lsum = carry
            loss, metrics, grads = single_grads(params, mb)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / nmicro,
                               acc, grads)
            return (acc, lsum + loss / nmicro), metrics

        (grads, loss), metrics = jax.lax.scan(body, (zero, jnp.zeros(())),
                                              micro)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss, metrics, grads

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        fn = accum_grads if nmicro > 1 else single_grads
        loss, metrics, grads = fn(state.params, batch)
        residual = state.residual
        if compress and residual is not None:
            grads, residual = error_feedback_update(grads, residual)
        lr = lr_fn(state.step)
        params, opt, opt_metrics = adamw_update(
            grads, state.opt, state.params, lr=lr,
            weight_decay=run.weight_decay)
        # "loss" last: under grad accumulation `metrics` carries the last
        # microbatch's values, but the step loss is the microbatch mean
        out = {**metrics, **opt_metrics, "loss": loss}
        return TrainState(params=params, opt=opt, residual=residual,
                          step=state.step + 1), out

    return train_step


class Trainer:
    """End-to-end training driver (data + step + ckpt + ScalAna)."""

    def __init__(self, run: cfgbase.RunConfig, *,
                 mesh=None, rules=None,
                 arch_cfg: Optional[cfgbase.ArchConfig] = None,
                 shape: Optional[cfgbase.ShapeConfig] = None,
                 global_batch: Optional[int] = None,
                 inject_delay: Optional[Dict[int, float]] = None):
        self.run = run
        self.mesh = mesh
        self.rules = rules
        self.cfg = arch_cfg if arch_cfg is not None else get_config(run.arch)
        self.shape = shape if shape is not None else SHAPES[run.shape]
        self.model = build_model(self.cfg)
        self.lr_fn = warmup_cosine(run.learning_rate, run.warmup_steps,
                                   run.total_steps)
        self.train_step_fn = make_train_step(self.model, run, self.lr_fn)
        self.dataset = make_dataset(self.cfg, self.shape, seed=run.seed,
                                    global_batch=global_batch)
        self.ckpt = (CheckpointManager(run.checkpoint_dir,
                                       keep=run.keep_checkpoints)
                     if run.checkpoint_dir else None)
        # ScalAna channels
        self.profiler: Optional[GraphProfiler] = None
        self.step_wall_times: list = []
        self.metrics_log: list = []
        # case-study hook: {rank: extra seconds} host-side injected delay
        self.inject_delay = dict(inject_delay or {})
        self._compiled = None

    # ------------------------------------------------------------------
    def init_state(self, seed: Optional[int] = None) -> TrainState:
        key = jax.random.PRNGKey(self.run.seed if seed is None else seed)
        params = self.model.init(key)
        residual = (init_residual(params)
                    if getattr(self.run, "grad_compress", False) else None)
        return TrainState(params=params, opt=adamw_init(params),
                          residual=residual, step=jnp.zeros((), jnp.int32))

    def state_shardings(self, state_shape) -> Any:
        """NamedShardings for TrainState (params rules; opt mirrors)."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding
        pspecs = self.model.param_partition_specs()

        def like_params(tree):
            flat_p, treedef = jax.tree.flatten(pspecs)
            flat_t = treedef.flatten_up_to(tree)
            return treedef.unflatten(flat_p)

        import jax.sharding as shd
        scalar = shd.NamedSharding(self.mesh, shd.PartitionSpec())
        return TrainState(
            params=jax.tree.map(
                lambda s: shd.NamedSharding(self.mesh, s), pspecs),
            opt=type(state_shape.opt)(
                step=scalar,
                mu=jax.tree.map(lambda s: shd.NamedSharding(self.mesh, s),
                                pspecs),
                nu=jax.tree.map(lambda s: shd.NamedSharding(self.mesh, s),
                                pspecs)),
            residual=(jax.tree.map(
                lambda s: shd.NamedSharding(self.mesh, s), pspecs)
                if state_shape.residual is not None else None),
            step=scalar,
        )

    # ------------------------------------------------------------------
    def _put_batch(self, np_batch: Dict[str, np.ndarray]):
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, np_batch)
        from jax.sharding import NamedSharding

        def put(x):
            axes = ("batch",) + (None,) * (x.ndim - 1)
            return jax.device_put(x, NamedSharding(
                self.mesh, spec_for(axes, x.shape, self.mesh, self.rules)))
        return jax.tree.map(put, np_batch)

    def enable_scalana(self, state: TrainState,
                       example_batch: Dict[str, jax.Array]) -> None:
        """Build PSG + profiler over the real train-step jaxpr."""
        self.profiler = GraphProfiler(
            self.train_step_fn, (state, example_batch),
            sample_every=self.run.scalana_sample_every,
            max_loop_depth=self.run.max_loop_depth)

    # ------------------------------------------------------------------
    def train(self, num_steps: Optional[int] = None,
              state: Optional[TrainState] = None,
              resume: bool = True,
              step_timeout_s: float = 0.0) -> TrainState:
        """Run ``num_steps`` steps.  With a mesh, the loop runs under its
        logical rules and the state and every batch are placed with their
        NamedShardings (data-parallel batch, rule-sharded parameters)
        instead of landing whole on the default device."""
        if self.mesh is None:
            return self._train(num_steps, state, resume, step_timeout_s)
        with use_rules(self.mesh, self.rules):
            return self._train(num_steps, state, resume, step_timeout_s)

    def _train(self, num_steps, state, resume, step_timeout_s):
        num_steps = num_steps or self.run.total_steps
        start_step = 0
        if state is None:
            state = self.init_state()
            if resume and self.ckpt is not None:
                restored = self.ckpt.restore_latest(
                    jax.tree.map(np.asarray, jax.device_get(state)))
                if restored is not None:
                    start_step, tree, _ = restored
                    state = jax.tree.map(jnp.asarray, tree)
        if self.mesh is not None:
            state = jax.device_put(state, self.state_shardings(state))

        if self.run.scalana and self.profiler is None:
            batch0 = self._put_batch(self.dataset.batch(start_step))
            self.enable_scalana(state, batch0)

        step_fn = (self.profiler.step if self.profiler is not None
                   else jax.jit(self.train_step_fn))

        rank = jax.process_index()
        for i in range(start_step, start_step + num_steps):
            with span("trainer.step", step=i):
                with span("trainer.batch"):
                    batch = self._put_batch(self.dataset.batch(i))
                t0 = time.perf_counter()
                if self.inject_delay.get(rank):    # straggler case study
                    time.sleep(self.inject_delay[rank])
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                self.step_wall_times.append(dt)
                if step_timeout_s and dt > step_timeout_s:
                    # straggler mitigation: surface instead of hanging the job
                    self.metrics_log.append({"step": i, "timeout": dt})
                self.metrics_log.append(
                    {"step": i,
                     "loss": float(metrics["loss"]),
                     "grad_norm": float(metrics.get("grad_norm", 0.0)),
                     "wall_s": dt})
                if (self.ckpt is not None and self.run.checkpoint_every
                        and (i + 1) % self.run.checkpoint_every == 0):
                    self.ckpt.save(i + 1, jax.device_get(state))
        if self.ckpt is not None:
            self.ckpt.save(start_step + num_steps, jax.device_get(state),
                           blocking=True)
        return state

    # ------------------------------------------------------------------
    def scalana_artifacts(self):
        """(contracted PSG, per-vertex perf vectors, storage bytes)."""
        if self.profiler is None:
            return None
        return (self.profiler.psg, self.profiler.perf_vectors(),
                self.profiler.storage_bytes())
