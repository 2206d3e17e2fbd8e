"""Train-step builders and the training loop.

Two step flavors:

* **auto** (default): one pjit'd step — XLA SPMD derives every collective
  from the in/out shardings (params TP/EP-sharded over "model", batch over
  the DP axes, optional ZeRO-1 optimizer-state sharding over "data").
  Microbatch gradient accumulation runs as a lax.scan inside the step.

* **manual-dp**: shard_map manual over the DP axes / auto over "model".
  Per-rank grads are reduced with the int8 compressed psum (+error feedback)
  from train/grad_compress.py — the explicit-collective path for cross-pod
  bandwidth-bound training.

Both return metrics and are lowerable with ShapeDtypeStructs (the dry-run
uses exactly these builders — no divergence between dry-run and real step).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.model import Model
from repro.sharding.rules import (
    ShardingRules, batch_axes_for_mesh, build_param_specs,
)
from repro.train import optim
from repro.train.grad_compress import compressed_psum_tree


@dataclasses.dataclass
class TrainConfig:
    opt: optim.AdamWConfig = dataclasses.field(default_factory=optim.AdamWConfig)
    microbatches: int = 1           # gradient-accumulation chunks per step
    zero1: bool = False             # shard optimizer m/v over the data axis
    zero2_grads: bool = False       # keep the grad accumulator DP-sharded
    grad_compress: bool = False     # int8 compressed DP all-reduce (manual-dp)
    mode: str = "auto"              # auto | manual-dp


def _zero1_specs(mesh, param_shardings):
    """Optimizer-state shardings: add 'data' on the first divisible free dim."""

    def reshard(ns: NamedSharding):
        spec = list(ns.spec) if ns.spec else []
        return ns  # placeholder; refined per-leaf with shapes in build step

    return param_shardings


def build_shardings(model: Model, mesh, rules: ShardingRules):
    shapes, logical = model.param_specs()
    param_sh = build_param_specs(mesh, rules, shapes, logical)
    return shapes, logical, param_sh


def _opt_shardings(mesh, rules, shapes, logical, param_sh, zero1: bool):
    if not zero1:
        m = param_sh
    else:
        ba = batch_axes_for_mesh(mesh)
        dp = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1

        def one(struct, ns):
            spec = list(ns.spec) + [None] * (len(struct.shape) - len(ns.spec))
            used = set()
            for e in spec:
                for a in ((e,) if isinstance(e, str) else (e or ())):
                    used.add(a)
            if not set(ba) & used:
                for i, e in enumerate(spec):
                    if e is None and struct.shape[i] % dp == 0 and struct.shape[i] >= dp:
                        spec[i] = ba if len(ba) > 1 else ba[0]
                        break
            return NamedSharding(mesh, P(*spec))

        m = jax.tree.map(one, shapes, param_sh,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return {"m": m, "v": m, "step": NamedSharding(mesh, P())}


def make_train_step(
    model: Model, mesh, rules: ShardingRules, tcfg: TrainConfig,
    extra_batch_specs: Optional[dict] = None,
):
    """Returns (step_fn, shardings dict). step(params, opt_state, batch)."""
    shapes, logical, param_sh = build_shardings(model, mesh, rules)
    opt_sh = _opt_shardings(mesh, rules, shapes, logical, param_sh, tcfg.zero1)
    ba = batch_axes_for_mesh(mesh)
    batch_spec = P(ba if len(ba) > 1 else (ba[0] if ba else None))
    data_sh = NamedSharding(mesh, batch_spec)

    def batch_shardings(batch_template: dict):
        out = {}
        for k in batch_template:
            if extra_batch_specs and k in extra_batch_specs:
                out[k] = NamedSharding(mesh, extra_batch_specs[k])
            else:
                out[k] = data_sh
        return out

    opt_cfg = tcfg.opt
    nm = tcfg.microbatches

    def loss_of(params, batch):
        return model.loss_fn(params, batch, mesh=mesh)

    # ZeRO-2: the f32 gradient accumulator (the largest training temp for
    # big models) stays sharded over the DP axes; XLA inserts a per-microbatch
    # reduce-scatter instead of holding a replicated f32 grad tree
    zero2_sh = (
        _opt_shardings(mesh, rules, shapes, logical, param_sh, True)["m"]
        if tcfg.zero2_grads else None
    )

    def _constrain(tree):
        if zero2_sh is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, zero2_sh)

    def grads_of(params, batch):
        if nm == 1:
            (loss, ex), grads = jax.value_and_grad(loss_of, has_aux=True)(params, batch)
            return loss, ex, _constrain(grads)
        # microbatch accumulation: split the batch dim into nm chunks
        def split(x):
            b = x.shape[0]
            return x.reshape(nm, b // nm, *x.shape[1:])

        # keep the *within-microbatch* batch dim sharded over DP: without the
        # constraint GSPMD shards the microbatch index instead, replicating
        # each microbatch's activations on every DP rank
        mb_spec = NamedSharding(mesh, P(None, *batch_spec))
        mb = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(split(x), mb_spec), batch
        )
        zero = _constrain(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        )

        def body(carry, mbatch):
            acc, loss_acc = carry
            (loss, ex), g = jax.value_and_grad(loss_of, has_aux=True)(params, mbatch)
            acc = _constrain(
                jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
            )
            return (acc, loss_acc + loss), None

        (gacc, loss_sum), _ = jax.lax.scan(body, (zero, 0.0), mb)
        grads = jax.tree.map(lambda g: g / nm, gacc)
        return loss_sum / nm, {"ce": loss_sum / nm, "aux": jnp.zeros(())}, grads

    if tcfg.mode == "auto":
        update_sh = opt_sh["m"] if tcfg.zero1 else None

        def step(params, opt_state, batch):
            loss, ex, grads = grads_of(params, batch)
            new_params, new_opt, om = optim.adamw_update(
                opt_cfg, params, grads, opt_state, update_shardings=update_sh
            )
            metrics = {"loss": loss, **ex, **om}
            return new_params, new_opt, metrics

        jstep = jax.jit(
            step,
            # data_sh is a pytree *prefix* for the whole batch dict: every
            # input leaf gets its leading (batch) dim sharded over the DP axes
            in_shardings=(param_sh, opt_sh, data_sh),
            out_shardings=(param_sh, opt_sh, None),
            donate_argnums=(0, 1),
        )
    elif tcfg.mode == "manual-dp":
        dp_axes = ba
        n_ranks = int(np.prod([mesh.shape[a] for a in dp_axes]))

        def step(params, opt_state, err, batch):
            def inner(params, opt_state, err, batch):
                loss, ex, grads = grads_of(params, batch)
                if tcfg.grad_compress:
                    grads, err = compressed_psum_tree(grads, dp_axes, err, n_ranks)
                else:
                    grads = jax.tree.map(
                        lambda g: jax.lax.pmean(g.astype(jnp.float32), dp_axes[0])
                        if len(dp_axes) == 1
                        else jax.lax.pmean(
                            jax.lax.pmean(g.astype(jnp.float32), dp_axes[0]), dp_axes[1]
                        ),
                        grads,
                    )
                loss = jax.lax.pmean(loss, dp_axes[0])
                new_params, new_opt, om = optim.adamw_update(
                    opt_cfg, params, grads, opt_state
                )
                return new_params, new_opt, err, {"loss": loss, **om}

            return jax.shard_map(
                inner,
                mesh=mesh,
                in_specs=(P(), P(), P(), batch_spec),
                out_specs=(P(), P(), P(), P()),
                axis_names=set(dp_axes),
                check_vma=False,
            )(params, opt_state, err, batch)

        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
    else:
        raise ValueError(tcfg.mode)

    shardings = {
        "params": param_sh, "opt": opt_sh, "data": data_sh,
        "batch_shardings": batch_shardings, "param_shapes": shapes,
    }
    return jstep, shardings


def init_train_state(model: Model, mesh, shardings, seed: int = 0):
    """Sharded init: params materialize directly with their target sharding."""
    param_sh = shardings["params"]

    @partial(jax.jit, out_shardings=param_sh)
    def _init(key):
        return model.init(key)

    with jax.set_mesh(mesh):
        params = _init(jax.random.PRNGKey(seed))
        opt_state = jax.jit(
            optim.init_opt_state, out_shardings=shardings["opt"]
        )(params)
    return params, opt_state


def train_loop(
    model: Model, mesh, rules, tcfg: TrainConfig, dataset, steps: int,
    ckpt_manager=None, ckpt_every: int = 0, hooks: Optional[list] = None,
    params=None, opt_state=None, start_step: int = 0,
):
    """The end-to-end driver loop (examples/train_lm.py uses this)."""
    step_fn, shardings = make_train_step(model, mesh, rules, tcfg)
    if params is None:
        params, opt_state = init_train_state(model, mesh, shardings)
    history = []
    with jax.set_mesh(mesh):
        for step in range(start_step, steps):
            t0 = time.perf_counter()
            batch = dataset(step)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            history.append({"step": step, "loss": loss, "dt": dt})
            for h in hooks or []:
                h(step, params, opt_state, metrics, dt)
            if ckpt_manager is not None and ckpt_every and (step + 1) % ckpt_every == 0:
                ckpt_manager.save(step + 1, params, opt_state)
    return params, opt_state, history
