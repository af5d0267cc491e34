"""Training: state/step construction under pjit + the CLI driver.

``make_train_step`` builds the jitted SPMD train step for (model, mesh):
gradient accumulation over microbatches (lax.scan), MMA-reduction
global-norm clipping, AdamW with ZeRO-sharded moments, buffer donation.
``run`` is the end-to-end loop: synthetic pipeline, checkpoint/restart
supervisor, metrics logging.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, TrainConfig
from repro.data.pipeline import SyntheticLMData
from repro.distributed import sharding as shd
from repro.distributed import tc_collectives
from repro.distributed.fault_tolerance import TrainSupervisor
from repro.launch.compile_cache import use_compile_cache
from repro.models import model_zoo
from repro.models.param import axes_tree
from repro.optim import adamw

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TrainState:
    params: Any
    opt: adamw.AdamWState
    step: jax.Array


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt", "step"], meta_fields=[])


def batch_axes(batch_like) -> dict:
    """Logical axes for a batch pytree (leading dim = global batch)."""
    def one(k, v):
        return ("batch",) + (None,) * (v.ndim - 1)
    return {k: one(k, v) for k, v in batch_like.items()}


def state_logical_axes(model) -> TrainState:
    paxes = axes_tree(model.specs)
    return TrainState(params=paxes, opt=adamw.state_axes(paxes), step=())


def state_shardings(model, mesh, state_shapes: TrainState) -> TrainState:
    axes = state_logical_axes(model)
    return jax.tree_util.tree_map(
        lambda leaf, ax: shd.sharding_for(leaf.shape, ax, mesh),
        state_shapes, axes,
        is_leaf=lambda l: isinstance(l, (jax.ShapeDtypeStruct, jax.Array)))


def _split_microbatches(batch, k: int):
    """(B, ...) -> (k, B/k, ...) preserving per-microbatch sharding
    (batch index strided so every device participates in every
    microbatch — see docs/design-notes.md §4)."""
    def one(v):
        b = v.shape[0]
        return jnp.moveaxis(v.reshape(b // k, k, *v.shape[1:]), 1, 0)
    return jax.tree_util.tree_map(one, batch)


def make_train_step(model, tconf: TrainConfig, mesh=None):
    """Returns (train_step, make_init_state).

    train_step(state, batch) -> (state, metrics); fully jittable, batch
    sharded over ('pod','data'), params/opt per the logical rules.
    """
    cfg = model.cfg

    def lr_at(step):
        return adamw.cosine_schedule(
            step, base_lr=tconf.learning_rate,
            warmup_steps=tconf.warmup_steps, total_steps=tconf.total_steps)

    def loss_fn(params, mb):
        with shd.axis_rules(mesh):
            return model.loss(params, mb)

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        k = tconf.microbatches
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        if k == 1:
            (loss, metrics), grads = grad_fn(state.params, batch)
        else:
            mbs = _split_microbatches(batch, k)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = grad_fn(state.params, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss_sum), metrics = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            loss = loss_sum / k
            metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)

        with shd.axis_rules(mesh):
            lr = lr_at(state.step)
            new_params, new_opt, om = adamw.update(
                grads, state.opt, state.params, lr=lr, beta1=tconf.beta1,
                beta2=tconf.beta2, eps=tconf.eps,
                weight_decay=tconf.weight_decay,
                grad_clip=tconf.grad_clip,
                reduce_method=cfg.reduce_method)
            # Post-step parameter norm on the same mesh-aware
            # collective as the grad norm (via='gspmd': the param tree
            # is pjit-owned here, so the partitioner schedules the
            # per-leaf squared-sum partials + scalar psums in place;
            # mesh-keyed per-leaf plans under method='auto').
            pnorm = tc_collectives.tc_global_norm(
                new_params, mesh=mesh, method=cfg.reduce_method,
                via="gspmd")
        metrics = dict(metrics, **om, lr=lr, loss=loss,
                       param_norm=pnorm)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    def make_init_state(key) -> TrainState:
        params = model.init(key)
        return TrainState(params=params,
                          opt=adamw.init(params,
                                         moment_dtype=tconf.moment_dtype),
                          step=jnp.zeros((), jnp.int32))

    return train_step, make_init_state


def jit_train_step(model, tconf: TrainConfig, mesh, sample_batch_shapes):
    """AOT-ready jitted step with explicit in/out shardings + donation."""
    train_step, make_init_state = make_train_step(model, tconf, mesh)
    state_shapes = jax.eval_shape(make_init_state,
                                  jax.random.PRNGKey(tconf.seed))
    s_shard = state_shardings(model, mesh, state_shapes)
    b_axes = batch_axes(sample_batch_shapes)
    b_shard = {k: shd.sharding_for(v.shape, b_axes[k], mesh)
               for k, v in sample_batch_shapes.items()}
    step = jax.jit(
        train_step,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, None),
        donate_argnums=(0,),
    )
    return step, make_init_state, s_shard, b_shard


def run(arch: str, *, steps: int = 200, smoke: bool = True,
        shape: str = "train_4k", ckpt_dir: Optional[str] = None,
        data_parallel: int = 1, model_parallel: int = 1,
        batch_override: Optional[int] = None,
        seq_override: Optional[int] = None,
        microbatches: int = 1, log_every: int = 10,
        save_every: int = 100, seed: int = 0,
        plan_store: Optional[str] = None):
    """End-to-end training driver (examples + integration tests).

    ``plan_store`` binds the autotune registry to a shared plan-store
    file (``repro.core.autotune.bind_default_registry``): plans tuned
    by fleet peers merge in at startup and this run's plans are saved
    back (atomic, file-locked, merge-on-save) at the end.
    """
    from repro.configs import registry
    from repro.launch.mesh import make_local_mesh

    cfg = registry.get_config(arch, smoke=smoke)
    shape_cfg = SHAPES[shape]
    if batch_override or seq_override:
        shape_cfg = dataclasses.replace(
            shape_cfg, global_batch=batch_override or shape_cfg.global_batch,
            seq_len=seq_override or shape_cfg.seq_len)
    tconf = TrainConfig(total_steps=steps, warmup_steps=max(steps // 10, 1),
                        microbatches=microbatches, seed=seed)
    if plan_store:
        from repro.core import autotune
        autotune.bind_default_registry(plan_store)
    mesh = make_local_mesh(data_parallel, model_parallel)
    model = model_zoo.build(cfg)

    data_shard = NamedSharding(mesh, P(("data",)))
    data = SyntheticLMData(cfg, shape_cfg, seed=seed, sharding=data_shard)
    sample = model.input_specs(shape_cfg)
    step_fn, make_init_state, s_shard, _ = jit_train_step(
        model, tconf, mesh, sample)

    def init_fn():
        with shd.axis_rules(mesh):
            st = jax.jit(make_init_state,
                         out_shardings=s_shard)(jax.random.PRNGKey(seed))
        return st

    sup = TrainSupervisor(ckpt_dir, save_every=save_every) \
        if ckpt_dir else None
    if sup:
        # Replan hook: this process may be a restart onto a smaller
        # (or re-grown) device set — drop autotuned plans keyed to any
        # other mesh geometry so method='auto' tunes fresh |mesh: keys
        # for the mesh we actually built (fault_tolerance, recovery
        # contract step 5).
        sup.on_remesh(mesh)
        state, start = sup.restore_or_init(init_fn)
    else:
        state, start = init_fn(), 0

    t0 = time.time()
    history = []
    for step_i, batch in zip(range(start, steps), data.iter(start)):
        state, metrics = step_fn(state, batch)
        if step_i % log_every == 0 or step_i == steps - 1:
            loss = float(metrics["loss"])
            history.append((step_i, loss))
            log.info("step %5d loss %.4f (%.2fs)", step_i, loss,
                     time.time() - t0)
            print(f"step {step_i:5d} loss {loss:.4f} "
                  f"grad_norm {float(metrics.get('grad_norm', 0)):.3f}")
        if sup:
            sup.maybe_save(step_i + 1, state)
    if sup:
        sup.finalize(steps, state)
    if plan_store:
        autotune.default_registry().save(plan_store)
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true",
                    help="full config (default: smoke-size)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--plan-store", default=None,
                    help="shared autotune plan-store JSON (merged at "
                         "startup, saved at exit)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()
    run(args.arch, steps=args.steps, smoke=not args.full,
        batch_override=args.batch, seq_override=args.seq,
        microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
        data_parallel=args.data_parallel,
        model_parallel=args.model_parallel,
        plan_store=args.plan_store)


if __name__ == "__main__":
    main()
