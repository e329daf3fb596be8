"""Pipeline parallelism over the pod axis (GPipe schedule).

At 2+ pods the cross-pod DCN hop is the slowest link; instead of extending
data-parallelism across pods (gradient all-reduce over DCN every step), the
pod axis can act as a pipeline: each pod owns a contiguous block of layers,
microbatches stream through, and the only cross-pod traffic is one
activation tensor per microbatch per direction — O(B*T*D) instead of
O(params) per step.

`pipeline_apply` runs a GPipe forward over `pod_axis` inside shard_map:
stage s holds its own stage parameters (sliced by shard_map), microbatches
enter at stage 0, activations hop stage->stage+1 via `ppermute`, and the
last stage's outputs are summed back to all pods (masked psum).  The whole
schedule is differentiable — `ppermute`'s transpose is the reverse
permute, so jax.grad yields the standard GPipe backward (bubble included).

Bubble fraction = (P-1)/(M+P-1) for P stages and M microbatches — pick
M >= 4*(P-1) to keep it under ~20%.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

__all__ = ["gpipe_schedule", "pipeline_apply"]


def gpipe_schedule(stage_fn, stage_params, x_mb, *, axis: str):
    """Run inside shard_map. stage_params: THIS stage's params; x_mb
    (M, ...) microbatch inputs (meaningful at stage 0).  Returns (M, ...)
    outputs (meaningful at the last stage; zeros elsewhere)."""
    p = jax.lax.axis_size(axis)
    sid = jax.lax.axis_index(axis)
    m = x_mb.shape[0]
    fwd = [(i, (i + 1) % p) for i in range(p)]

    buf = jnp.zeros_like(x_mb[0])
    outs = jnp.zeros((m, *jax.eval_shape(stage_fn, stage_params,
                                         x_mb[0]).shape),
                     x_mb.dtype)
    is_first = sid == 0
    is_last = sid == p - 1
    for t in range(m + p - 1):
        feed = x_mb[min(t, m - 1)]
        x_in = jnp.where(is_first, feed, buf)
        y = stage_fn(stage_params, x_in)
        # retire a finished microbatch at the last stage
        oi = t - (p - 1)
        if oi >= 0:
            upd = outs.at[oi].set(y)
            outs = jnp.where(is_last, upd, outs)
        buf = jax.lax.ppermute(y, axis, fwd)
    return outs


def pipeline_apply(mesh, stage_fn, all_stage_params, x_mb, *,
                   pod_axis: str = "pod", params_spec=None):
    """GPipe over `pod_axis` of `mesh`.

    all_stage_params: pytree whose leaves have a leading stage dim == pod
    size (stage s gets slice s).  x_mb (M, ...) microbatches, replicated.
    Returns (M, ...) outputs replicated over the pod axis.
    """
    p = mesh.shape[pod_axis]

    def spec_of(leaf):
        return PS(pod_axis, *([None] * (leaf.ndim - 1)))

    in_specs = (
        jax.tree.map(spec_of, all_stage_params) if params_spec is None
        else params_spec,
        PS(),
    )

    def body(params_stage, x_local):
        # shard_map gives a leading stage dim of 1: drop it
        params = jax.tree.map(lambda a: a[0], params_stage)
        outs = gpipe_schedule(stage_fn, params, x_local, axis=pod_axis)
        # broadcast the last stage's outputs to every pod
        is_last = jax.lax.axis_index(pod_axis) == p - 1
        outs = jnp.where(is_last, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, pod_axis)

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=PS(),
        check_vma=False,
    )(all_stage_params, x_mb)
