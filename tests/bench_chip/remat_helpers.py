"""The train step's loss as it was before each layer became its own
checkpoint: one ``jax.checkpoint`` around each pattern block."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.train import step as TS


def block_remat_loss(cfg, options):
    """The step's loss as it was: one checkpoint around each pattern block."""
    orig = T.stage_fwd

    def stage_fwd_remat(cfg_, pattern, stage_params, x, q_pos, positions_3d=None, enc_out=None,
                        causal=True):
        def body(carry, block_params):
            h, aux = carry
            h = T._constrain(h, ("batch", None, None))

            def inner(h_, block_params_):
                a_total = jnp.float32(0.0)
                for i, spec in enumerate(pattern):
                    h_, a = T.layer_fwd(cfg_, spec, block_params_[f"pos{i}"], h_, q_pos,
                                        positions_3d, enc_out, causal=causal)
                    a_total = a_total + a
                return h_, a_total

            h, a = TS._remat_wrap(inner, options.remat)(h, block_params)
            return (h, aux + a), None

        (x_out, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stage_params)
        return x_out, aux

    def loss_fn(params, batch):
        T.stage_fwd = stage_fwd_remat
        try:
            return T.train_loss(cfg, params, batch)
        finally:
            T.stage_fwd = orig

    return loss_fn
