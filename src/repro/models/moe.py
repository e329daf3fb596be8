"""Expert-parallel MoE with *local dispatch* (no all-to-all).

Design (DESIGN.md §6): activations entering the FFN block are replicated over
the model axis (they just left the attention TP psum), so every model shard
already holds *all* tokens of its data shard.  Experts are sharded over the
model axis; each shard simply *selects* the tokens routed to its own experts
(sort + capacity buffer), runs them through its expert FFNs, scatters the
results back to token order, and the per-shard partial outputs merge in one
psum over the model axis — the same collective a dense TP FFN needs.  Router
and dispatch are computed redundantly per shard; the redundant compute is
O(tokens * experts) router FLOPs, negligible against the expert matmuls.

Token capacity is static: C = ceil(local_tokens * top_k / n_experts * cf),
over-capacity tokens are dropped (standard Switch semantics).  Expert counts
that do not divide the model axis are padded with dead experts whose router
logits are -inf (granite 40 -> 48 on tp=16).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.parallel import sharding as shd
from .layers import P, matmul_out_dtype

__all__ = ["MoEConfig", "moe_schema", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25
    n_shared: int = 0  # shared-expert width multiplier (kimi-k2: 1)
    aux_weight: float = 0.01

    def padded_experts(self, tp: int) -> int:
        return -(-self.n_experts // tp) * tp


def moe_schema(d_model: int, moe: MoEConfig, *, gated: bool, tp_hint: int = 16) -> dict:
    # FSDP dim is the expert-internal F axis (not D): the 'resident' serving
    # dispatch then computes within-expert partial sums over the data axis
    # with zero weight movement (gate/up activations are elementwise in F).
    ep = moe.padded_experts(tp_hint)
    f = moe.d_ff
    s = {
        "router": P((d_model, ep), ("fsdp", None), fan_in=d_model),
        "wo": P((ep, f, d_model), ("expert", "fsdp", None), fan_in=f),
    }
    if gated:
        s["wi"] = P((2, ep, d_model, f), (None, "expert", None, "fsdp"), fan_in=d_model)
    else:
        s["wi"] = P((ep, d_model, f), ("expert", None, "fsdp"), fan_in=d_model)
    return s


def _expert_ffn(xbuf, wi, wo, *, gated: bool, activation_fn):
    """xbuf (E, C, D); wi/wo expert weight blocks."""
    pt = matmul_out_dtype()
    if gated:
        gate = jnp.einsum("ecd,edf->ecf", xbuf, wi[0],
                          preferred_element_type=pt)
        up = jnp.einsum("ecd,edf->ecf", xbuf, wi[1],
                        preferred_element_type=pt)
        h = (activation_fn(gate.astype(jnp.float32)).astype(xbuf.dtype)
             * up.astype(xbuf.dtype))
    else:
        h = jnp.einsum("ecd,edf->ecf", xbuf, wi,
                       preferred_element_type=pt)
        h = activation_fn(h.astype(jnp.float32)).astype(xbuf.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wo,
                      preferred_element_type=pt).astype(xbuf.dtype)


def _route_and_pack(xf, router, moe, ep, e_loc, e0, capacity):
    """Shared routing: sort/capacity-pack tokens for the local expert range.

    Returns (slot_tok, slot_w, aux) where slot i of the (E_loc * C) buffer
    reads token slot_tok[i] with combine weight slot_w[i]."""
    n, d = xf.shape
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        router.astype(jnp.float32))
    if ep != moe.n_experts:  # dead padding experts never win top-k
        logits = jnp.where(jnp.arange(ep)[None] < moe.n_experts, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, moe.top_k)  # (N, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    ids = topi.reshape(-1)                          # (N*k,)
    wts = topw.reshape(-1).astype(jnp.float32)
    tok = jnp.arange(n * moe.top_k) // moe.top_k    # owning token of each slot
    order = jnp.argsort(ids)                        # stable
    ids_s, tok_s, w_s = ids[order], tok[order], wts[order]
    starts = jnp.searchsorted(ids_s, jnp.arange(ep))
    pos = jnp.arange(n * moe.top_k) - starts[ids_s]

    local = (ids_s >= e0) & (ids_s < e0 + e_loc) & (pos < capacity)
    slot = jnp.where(local, (ids_s - e0) * capacity + pos,
                     n * moe.top_k + capacity * e_loc)
    slot_tok = jnp.zeros((e_loc * capacity,), jnp.int32).at[slot].set(
        tok_s.astype(jnp.int32), mode="drop")
    slot_w = jnp.zeros((e_loc * capacity,), jnp.float32).at[slot].set(
        w_s, mode="drop")

    # switch-style load-balance loss
    counts = jnp.diff(jnp.append(starts, n * moe.top_k)).astype(jnp.float32)
    frac = counts / (n * moe.top_k)
    pmean = jnp.mean(probs, axis=0)
    aux = moe.n_experts * jnp.sum(frac * pmean)
    return slot_tok, slot_w, aux


def _moe_body(
    x, router, wi, wo, *,
    moe: MoEConfig, ep: int, e_loc: int, e0,
    capacity: int, gated: bool, activation_fn,
    fsdp_axis, model_axis, gather=(False, False, False),
):
    """gather-weights dispatch (training posture): tokens stay put, the
    fsdp-sharded expert weights are gathered per layer (ZeRO-3)."""
    bl, t, d = x.shape
    nl = bl * t
    if fsdp_axis is not None:
        if gather[0]:
            router = jax.lax.all_gather(router, fsdp_axis, axis=0, tiled=True)
        if gather[1]:
            wi = jax.lax.all_gather(wi, fsdp_axis, axis=3 if gated else 2, tiled=True)
        if gather[2]:
            wo = jax.lax.all_gather(wo, fsdp_axis, axis=1, tiled=True)
    xf = x.reshape(nl, d)
    slot_tok, slot_w, aux = _route_and_pack(xf, router, moe, ep, e_loc, e0,
                                            capacity)
    xbuf = jnp.take(xf, slot_tok, axis=0).reshape(e_loc, capacity, d)
    ybuf = _expert_ffn(xbuf, wi, wo, gated=gated, activation_fn=activation_fn)
    yflat = ybuf.reshape(e_loc * capacity, d) * slot_w[:, None].astype(ybuf.dtype)

    out = jnp.zeros((nl, d), x.dtype).at[slot_tok].add(yflat)
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out.reshape(bl, t, d), aux


def _moe_body_resident(
    x, router, wi, wo, *,
    moe: MoEConfig, ep: int, e_loc: int, e0,
    gated: bool, activation_fn,
    fsdp_axis, model_axis, batch_axes, gather_router: bool,
):
    """resident-weights dispatch (serving posture): expert weights never
    move — tokens are all-gathered over the data axes (tiny at decode), every
    (expert-shard, F-shard) device computes its partial expert FFN, and one
    psum over (model, data) completes both the within-expert F reduction and
    the cross-expert combine.  Weight traffic per layer: zero (vs ~2 GB/layer
    gathered for a 1T-param MoE under ZeRO-3)."""
    bl, t, d = x.shape
    if gather_router and fsdp_axis is not None:
        router = jax.lax.all_gather(router, fsdp_axis, axis=0, tiled=True)
    if batch_axes:
        xg = jax.lax.all_gather(x, batch_axes, axis=0, tiled=True)  # (B, T, D)
    else:
        xg = x
    ng = xg.shape[0] * t
    xf = xg.reshape(ng, d)
    capacity = _capacity(ng, moe)
    slot_tok, slot_w, aux = _route_and_pack(xf, router, moe, ep, e_loc, e0,
                                            capacity)
    xbuf = jnp.take(xf, slot_tok, axis=0).reshape(e_loc, capacity, d)
    # wi/wo are F-sharded over fsdp: partial expert outputs, summed below
    ybuf = _expert_ffn(xbuf, wi, wo, gated=gated, activation_fn=activation_fn)
    yflat = ybuf.reshape(e_loc * capacity, d) * slot_w[:, None].astype(ybuf.dtype)
    out = jnp.zeros((ng, d), jnp.float32).at[slot_tok].add(
        yflat.astype(jnp.float32))
    axes = tuple(a for a in ((model_axis,) if model_axis else ())
                 + ((fsdp_axis,) if fsdp_axis else ()))
    if axes:
        out = jax.lax.psum(out, axes)
    out = out.astype(x.dtype)
    if batch_axes:
        flat = tuple(batch_axes) if isinstance(batch_axes, (tuple, list)) else (batch_axes,)
        my = jnp.int32(0)
        for a in flat:
            my = my * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        out = jax.lax.dynamic_slice_in_dim(out, my * (bl * t), bl * t, axis=0)
    return out.reshape(bl, t, d), aux


def moe_apply(params: dict, x: jax.Array, moe: MoEConfig, *, gated: bool,
              activation_fn=jax.nn.silu, dispatch: str = "gather_weights"):
    """Returns (y, aux_loss). Dispatch is shard_mapped when a mesh is active.

    dispatch='gather_weights' — training posture (tokens stay, ZeRO-3 weight
    gathers); 'resident' — serving posture (weights stay, tokens move)."""
    ctx = shd.current()
    router, wi, wo = params["router"], params["wi"], params["wo"]

    if ctx is None:
        ep = router.shape[1]
        y, aux = _moe_body(
            x, router, wi, wo, moe=moe, ep=ep, e_loc=ep, e0=0,
            capacity=_capacity(x.shape[0] * x.shape[1], moe),
            gated=gated, activation_fn=activation_fn,
            fsdp_axis=None, model_axis=None,
        )
        return y, aux

    mesh, rules = ctx.mesh, ctx.rules
    model_axis = rules.get("expert")
    model_axis = model_axis if model_axis in mesh.shape else None
    fsdp_axis = rules.get("fsdp")
    fsdp_axis = fsdp_axis if fsdp_axis in mesh.shape else None
    batch_phys = rules.get("batch")
    batch_phys = tuple(p for p in (batch_phys if isinstance(batch_phys, tuple) else (batch_phys,))
                       if p in mesh.shape) or None

    tp = mesh.shape[model_axis] if model_axis else 1
    ep = router.shape[1]
    e_loc = ep // tp
    b, t, _ = x.shape
    dp = math.prod(mesh.shape[p] for p in (batch_phys or ())) or 1
    if b % dp:  # batch too small to shard (e.g. long_500k B=1): replicate
        batch_phys, dp = None, 1
    nl = (b // dp) * t
    capacity = _capacity(nl, moe)

    def spec(axes, shape):
        return shd.spec_for(axes, mesh=mesh, rules=rules, shape=shape)

    wi_axes = (None, "expert", None, "fsdp") if gated else ("expert", None, "fsdp")
    in_specs = (
        PS(batch_phys, None, None),
        spec(("fsdp", None), router.shape),
        spec(wi_axes, wi.shape),
        spec(("expert", "fsdp", None), wo.shape),
    )
    out_specs = (PS(batch_phys, None, None), PS())

    def body(x_l, router_l, wi_l, wo_l):
        e0 = jax.lax.axis_index(model_axis) * e_loc if model_axis else 0
        if dispatch == "resident":
            y, aux = _moe_body_resident(
                x_l, router_l, wi_l, wo_l, moe=moe, ep=ep, e_loc=e_loc,
                e0=e0, gated=gated, activation_fn=activation_fn,
                fsdp_axis=fsdp_axis if _sharded(in_specs[2], fsdp_axis) else None,
                model_axis=model_axis, batch_axes=batch_phys,
                gather_router=_sharded(in_specs[1], fsdp_axis),
            )
        else:
            y, aux = _moe_body(
                x_l, router_l, wi_l, wo_l, moe=moe, ep=ep, e_loc=e_loc, e0=e0,
                capacity=capacity, gated=gated, activation_fn=activation_fn,
                fsdp_axis=fsdp_axis, model_axis=model_axis,
                gather=tuple(_sharded(s, fsdp_axis) for s in in_specs[1:]),
            )
        if batch_phys:
            aux = jax.lax.pmean(aux, batch_phys)
        return y, aux

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, router, wi, wo)
    return y, aux


def _sharded(pspec: PS, axis) -> bool:
    return axis is not None and any(
        (p == axis or (isinstance(p, tuple) and axis in p)) for p in pspec if p
    )


def _capacity(local_tokens: int, moe: MoEConfig) -> int:
    c = math.ceil(local_tokens * moe.top_k / moe.n_experts * moe.capacity_factor)
    return max(8, -(-c // 8) * 8)
