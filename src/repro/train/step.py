"""Train-step construction: jit + remat + grad accumulation + Hoplite sync.

Baseline data/tensor parallel step:
  * params FSDP(data) x TP(model), replicated over pod;
  * the per-step batch is split into ``num_microbatches`` accumulated with
    a lax.scan (f32 accumulator, sharded like the grads) -- this is what
    bounds activation memory at 4k x 256 global batch;
  * each layer of the scanned block body is wrapped in jax.checkpoint
    (remat policy from options), so a block of several layers keeps one
    layer's activations live in the backward, not all of them;
  * gradients over the data/model axes reduce via GSPMD (XLA's allreduce);
  * with a "pod" axis in the mesh and ``pod_sync`` other than ``gspmd``, the
    step runs manual over "pod" in a partial-manual shard_map and
    ``collectives.grad_sync`` reduces the gradients across it: the Hoplite
    chain (``hoplite_chain``), the 2-D chain (``hoplite_2d``) or XLA's psum
    (``psum``).  On one host the pod axis is the chips' ICI, and the chains
    are cut into the chunks ``planner.POD_CONFIG`` plans.

The returned step has signature  (state, batch) -> (state, metrics)  and
is ready for jit/lower with the shardings attached.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core import collectives, planner
from repro.models import transformer as T
from repro.models.common import abstract_params, init_params
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.sharding import partitioning
from repro.sharding.partitioning import ShardingOptions


# The jax.named_scope names on the step's layers, one per layer boundary and
# never nested: models/attention.attention_fwd, models/transformer._ffn_part,
# optim/adamw.adamw_update and core/collectives.grad_sync.  Every op the
# compiler makes inside one carries its name as a whole segment of op_name
# (the device trace's tf_op): forward, rematerialized and backward.
SCOPES = ("attention", "mlp", "optimizer", "grad_sync")
# Mamba-2's scopes, kept apart from SCOPES, whose four names the benchmark's
# step split reads: ``ssm`` over each whole mixer (models/ssm._mamba2_mix,
# a layer boundary like ``attention``) and ``ssd`` nested in it over the
# chunked scan.
SSM_SCOPES = ("ssm", "ssd")
# How the gradients are reduced over the mesh's "pod" axis: ``gspmd`` leaves
# it to XLA; each other value is the ``collectives.grad_sync`` method.
POD_SYNCS = ("gspmd", "hoplite_chain", "hoplite_2d", "psum")


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    num_microbatches: int = 1
    remat: str = "full"  # none | full | dots
    pod_sync: str = "hoplite_chain"  # one of POD_SYNCS
    adamw: AdamWConfig = AdamWConfig()
    sharding: ShardingOptions = ShardingOptions()


def _remat_wrap(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _loss_with_remat(cfg: ModelConfig, options: TrainOptions):
    """train_loss with each layer of the stage-scan body rematerialized."""
    if options.remat == "none":
        return lambda p, b: T.train_loss(cfg, p, b)

    # monkey-patch-free remat: wrap layer blocks via a rematted stage_fwd
    orig_stage_fwd = T.stage_fwd

    def stage_fwd_remat(cfg_, pattern, stage_params, x, q_pos, positions_3d=None, enc_out=None, causal=True):
        def body(carry, block_params):
            h, aux = carry
            h = T._constrain(h, ("batch", None, None))
            for i, spec in enumerate(pattern):

                def layer(h_, lp, spec=spec):
                    h_, a = T.layer_fwd(cfg_, spec, lp, h_, q_pos, positions_3d, enc_out,
                                        causal=causal)
                    return h_, jnp.float32(0.0) + a

                h, a = _remat_wrap(layer, options.remat)(h, block_params[f"pos{i}"])
                aux = aux + a
            return (h, aux), None

        (x_out, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stage_params)
        return x_out, aux

    def loss_fn(params, batch):
        T.stage_fwd = stage_fwd_remat
        try:
            return T.train_loss(cfg, params, batch)
        finally:
            T.stage_fwd = orig_stage_fwd

    return loss_fn


def _split_micro(batch: Dict[str, jax.Array], n: int):
    """Split global batch into n microbatches along the batch dim."""

    def split(name, x):
        if name == "positions_3d":
            B = x.shape[1]
            return x.reshape(x.shape[0], n, B // n, *x.shape[2:]).transpose(1, 0, 2, 3)
        B = x.shape[0]
        return x.reshape(n, B // n, *x.shape[1:])

    return {k: split(k, v) for k, v in batch.items()}


def make_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeSpec, options: TrainOptions = TrainOptions()):
    """Build (train_step, state_specs, batch_specs).

    state = {"params": ..., "opt": {m, v, count}, "step": i32}
    """
    loss_fn = _loss_with_remat(cfg, options)
    multi_pod = "pod" in mesh.axis_names
    use_hoplite_pod = multi_pod and options.pod_sync != "gspmd"

    def grads_of(params, batch):
        n = options.num_microbatches
        if n == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            return loss, grads

        # Hoist the embedding gather OUT of the accumulation scan: the SPMD
        # partitioner mis-compiles sharded gathers inside while bodies at
        # 256+ devices (invalid dynamic-slice).  Embed the full batch once,
        # scan over embedding slices, and fold the table gradient back in
        # through the saved vjp.
        assert "lm_head" in params or not cfg.tie_embeddings
        tokens = batch["tokens"]

        def embed_fn(tbl):
            return jnp.take(tbl, tokens, axis=0)

        x_emb, embed_vjp = jax.vjp(embed_fn, params["embed"])
        micro = _split_micro(
            {k: v for k, v in dict(batch, x_embed=x_emb).items() if k != "tokens"}, n
        )

        def body(carry, mb):
            loss_acc, gacc = carry

            def loss2(p, xe):
                return loss_fn(p, dict(mb, x_embed=xe))

            loss, (gp, gx) = jax.value_and_grad(loss2, argnums=(0, 1))(
                params, mb["x_embed"]
            )
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, gp
            )
            return (loss_acc + loss, gacc), gx

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, gsum), gx_stack = jax.lax.scan(body, (jnp.float32(0.0), g0), micro)
        # (n, B/n, S, d) -> (B, S, d); fold table grad through the vjp
        gx_full = gx_stack.reshape((tokens.shape[0],) + gx_stack.shape[2:])
        (d_table,) = embed_vjp(gx_full.astype(x_emb.dtype))
        gsum["embed"] = gsum["embed"] + d_table.astype(jnp.float32)
        inv = 1.0 / n
        return loss_sum * inv, jax.tree_util.tree_map(lambda g: g * inv, gsum)

    def step_core(state, batch):
        loss, grads = grads_of(state["params"], batch)
        if use_hoplite_pod:
            grads = collectives.grad_sync(grads, "pod", options.pod_sync, planner.POD_CONFIG)
            # scalar: the small-object fast path (psum), per the dispatcher
            loss = jax.lax.psum(loss, "pod") / mesh.shape["pod"]
        new_params, new_opt, metrics = adamw.adamw_update(
            grads, state["opt"], state["params"], options.adamw
        )
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        metrics = dict(metrics, loss=loss)
        return new_state, metrics

    if use_hoplite_pod:
        # manual over 'pod' (the chips' ICI on one host), where grad_sync
        # runs; GSPMD handles data/model.
        skel = T.model_skel(cfg)
        pspecs = partitioning.param_specs(cfg, skel, mesh, options.sharding)
        bspecs = partitioning.batch_specs(cfg, mesh, shape, options.sharding)

        # state replicated over pod; batch sharded over pod on dim 0 (dim 1
        # for positions_3d)
        state_in_specs = {
            "params": jax.tree_util.tree_map(lambda _: P(), pspecs),
            "opt": {
                "m": jax.tree_util.tree_map(lambda _: P(), pspecs),
                "v": jax.tree_util.tree_map(lambda _: P(), pspecs),
                "count": P(),
            },
            "step": P(),
        }
        batch_in_specs = {
            k: P(*["pod" if (isinstance(e, tuple) and "pod" in e) or e == "pod" else None for e in spec])
            for k, spec in bspecs.items()
        }
        metrics_specs = {"grad_norm": P(), "lr": P(), "loss": P()}

        base_step = jax.shard_map(
            step_core,
            mesh=mesh,
            in_specs=(state_in_specs, batch_in_specs),
            out_specs=(state_in_specs, metrics_specs),
            axis_names={"pod"},
            check_vma=False,
        )
        act_batch_axes: Any = (options.sharding.fsdp_axis,)  # no "pod": manual there
    else:
        base_step = step_core
        act_batch_axes = tuple(
            a for a in options.sharding.dp_axes if a in mesh.axis_names
        )

    def train_step(state, batch):
        # activation-sharding policy active during tracing (see T._constrain)
        prev = dict(T.ACTIVATION_SHARDING)
        T.set_activation_sharding(act_batch_axes, options.sharding.tp_axis)
        try:
            return base_step(state, batch)
        finally:
            T.ACTIVATION_SHARDING.update(prev)

    return train_step


def state_shardings(cfg: ModelConfig, mesh: Mesh, options: TrainOptions = TrainOptions()):
    skel = T.model_skel(cfg)
    pspecs = partitioning.param_specs(cfg, skel, mesh, options.sharding)
    to_sharding = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree
    )
    return {
        "params": to_sharding(pspecs),
        "opt": {
            "m": to_sharding(pspecs),
            "v": to_sharding(pspecs),
            "count": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }


def abstract_state(cfg: ModelConfig):
    skel = T.model_skel(cfg)
    aparams = abstract_params(skel, jnp.dtype(cfg.param_dtype))  # as init_state
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return {
        "params": aparams,
        "opt": {
            "m": jax.tree_util.tree_map(f32, aparams),
            "v": jax.tree_util.tree_map(f32, aparams),
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        },
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def init_state(cfg: ModelConfig, key, mesh: Optional[Mesh] = None, options: TrainOptions = TrainOptions()):
    skel = T.model_skel(cfg)
    params = init_params(skel, key, dtype_override=jnp.dtype(cfg.param_dtype))
    state = {
        "params": params,
        "opt": adamw.init_opt_state(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if mesh is not None:
        shardings = state_shardings(cfg, mesh, options)
        state = jax.tree_util.tree_map(jax.device_put, state, shardings)
    return state
