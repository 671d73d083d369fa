"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

For multi-pod deployments the "pod" axis can run pipeline stages instead of
pure data parallelism: each stage holds ``n_layers / n_stages`` layers and
microbatches stream through with collective_permute hops. This module
implements the schedule explicitly (it cannot be expressed as a GSPMD
annotation) and is validated at small scale in tests; the production dry-run
keeps "pod" as a DP axis by default (DESIGN.md §4).

Schedule: loop-per-microbatch over (fwd hop) with bubble = (S−1)/(M+S−1);
losses are computed on the last stage and psum'd back.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_micro: jax.Array,
    axis: str,
    n_stages: int = None,
):
    """Run inside shard_map. stage_params: this stage's layer stack;
    x_micro: [M, mb, ...] microbatches (same on every stage; only stage 0's
    input matters). Returns last stage's outputs [M, mb, ...].

    The rotating-buffer schedule: at tick t, stage s processes microbatch
    t − s (if in range), then the activations ppermute one hop right.
    """
    s_idx = jax.lax.axis_index(axis)
    if n_stages is None:
        # static stage count; jax<0.5 has no lax.axis_size — callers with a
        # mesh in hand pass it explicitly (make_pipeline_apply does)
        n_stages = jax.lax.axis_size(axis)
    m = x_micro.shape[0]
    ticks = m + n_stages - 1
    buf = jnp.zeros_like(x_micro[0])
    outs = jnp.zeros((m,) + x_micro.shape[1:], x_micro.dtype)

    def tick(carry, t):
        buf, outs = carry
        mb_idx = t - s_idx
        # stage 0 ingests a fresh microbatch at its tick
        fresh = x_micro[jnp.clip(mb_idx, 0, m - 1)]
        h = jnp.where(s_idx == 0, fresh, buf)
        active = (mb_idx >= 0) & (mb_idx < m)
        y = stage_fn(stage_params, h)
        y = jnp.where(active, y, buf)
        # last stage records finished microbatches
        record = active & (s_idx == n_stages - 1)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(record, y, outs[jnp.clip(mb_idx, 0, m - 1)]),
            jnp.clip(mb_idx, 0, m - 1),
            axis=0,
        )
        # hop activations to the next stage
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, outs), None

    (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
    # only the last stage recorded outputs (other stages hold zeros);
    # psum replicates the result so out_specs=P() is well-defined.
    return jax.lax.psum(outs, axis)


def make_pipelined_apply(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable,
    n_microbatches: int,
):
    """Wrap a per-stage apply into a pipelined whole-model apply.

    stage_params must be sharded stage-major on ``axis`` (leading dim =
    n_stages). Inputs [B, ...] are split into microbatches host-side.
    """

    def apply(stage_params, x):
        b = x.shape[0]
        mb = b // n_microbatches
        x_micro = x.reshape((n_microbatches, mb) + x.shape[1:])

        def inner(sp, xm):
            sp = jax.tree.map(lambda a: a[0], sp)  # this stage's slice
            return pipeline_forward(stage_fn, sp, xm, axis,
                                    n_stages=mesh.shape[axis])

        shard = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
            check_vma=False,
        )
        y_micro = shard(stage_params, x_micro)
        return y_micro.reshape((b,) + y_micro.shape[2:])

    return apply
