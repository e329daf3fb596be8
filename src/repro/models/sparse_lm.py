"""Vector-sparse FFN for the LM serving path — the paper's technique applied
beyond CNNs (DESIGN.md §4 'beyond paper').

Weights are stored in the VectorSparse balanced block-CSR (only nonzero
(vk, vn) vectors exist; FLOPs and weight bytes scale with density exactly as
the paper's SRAM/cycle accounting does).  TP layout under shard_map:

  wi  (D, F):  output strips (F) sharded over the model axis; K = D is
               replicated, so index gathers are local.
  wo  (F, D):  K = F is model-sharded, so the CSR is *shard-local*: each
               model shard stores a balanced CSR over its own F/tp K-range
               (leading tp dim on the vals/idx params).  Partial outputs
               merge in the same psum a dense TP FFN needs.

The structural jnp path lowers everywhere (GSPMD-friendly); on TPU the
`repro.kernels.vsmm` Pallas kernel additionally skips dynamically-zero
activation vectors (the paper's input-side skip — real for squared-ReLU /
ReLU activations).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.parallel import sharding as shd
from .layers import P

__all__ = ["sparse_mlp_schema", "sparse_mlp_apply"]


def _s_of(kb: int, density: float) -> int:
    return max(1, round(kb * density))


def _fit(pref: int, dim: int) -> int:
    """Largest divisor of dim <= pref (tile-size guard for small configs)."""
    v = min(pref, dim)
    while dim % v:
        v -= 1
    return v


def sparse_mlp_schema(cfg, sp) -> dict:
    """Schema for a vector-sparse (gated or plain) FFN block."""
    d, f = cfg.d_model, cfg.d_ff
    tp = cfg.tp_hint
    f_loc = f // tp
    gated = cfg.activation in ("swiglu", "geglu")
    vk, vn = _fit(sp.vk, d), _fit(sp.vn, f_loc)
    nb_i, kb_i = f // vn, d // vk
    s_i = _s_of(kb_i, sp.density)
    vk_o, vn_o = _fit(sp.vk, f_loc), _fit(sp.vn, d)
    nb_o, kb_o = d // vn_o, f_loc // vk_o
    s_o = _s_of(kb_o, sp.density)
    lead = (2,) if gated else ()
    return {
        "wi_vals": P((*lead, nb_i, s_i, vk, vn),
                     (*(None,) * len(lead), "ff", None, None, None),
                     fan_in=d),
        "wi_idx": P((*lead, nb_i, s_i),
                    (*(None,) * len(lead), "ff", None),
                    init="vs_idx", fan_in=kb_i, dtype=jnp.int32),
        "wo_vals": P((tp, nb_o, s_o, vk_o, vn_o),
                     ("ff", None, None, None, None), fan_in=f),
        "wo_idx": P((tp, nb_o, s_o), ("ff", None, None),
                    init="vs_idx", fan_in=kb_o, dtype=jnp.int32),
    }


def _vs_mm(x2, vals, idx):
    """x2 (M, KB, vk) x CSR vals (NB, S, vk, vn), idx (NB, S) -> (M, NB*vn).

    FLOPs = S/KB * dense — the paper's weight-vector skip, structurally.
    """
    nb, s, vk, vn = vals.shape

    def step(acc, sv):
        idx_s, w_s = sv  # (NB,), (NB, vk, vn)
        xg = jnp.take(x2, idx_s, axis=1)  # (M, NB, vk)
        acc = acc + jnp.einsum("mjk,jkn->mjn", xg, w_s,
                               preferred_element_type=jnp.float32)
        return acc, None

    acc0 = jnp.zeros((x2.shape[0], nb, vn), jnp.float32)
    acc, _ = jax.lax.scan(
        step, acc0, (jnp.swapaxes(idx, 0, 1),
                     jnp.swapaxes(vals, 0, 1)))
    return acc.reshape(x2.shape[0], nb * vn)


def _act(h, kind):
    if kind in ("swiglu",):
        return jax.nn.silu(h)
    if kind in ("geglu", "gelu"):
        return jax.nn.gelu(h)
    if kind == "relu2":
        r = jax.nn.relu(h)
        return r * r
    return jax.nn.relu(h)


def _body(x, wi_vals, wi_idx, wo_vals, wo_idx, *, cfg, model_axis):
    """Per-shard sparse FFN. x (B, T, D); wo_* carry a leading local-shard
    dim of size 1 under shard_map (tp when unmapped)."""
    b, t, d = x.shape
    gated = cfg.activation in ("swiglu", "geglu")
    vk = wi_vals.shape[-2]
    x2 = x.reshape(b * t, d // vk, vk)
    if gated:
        gate = _vs_mm(x2, wi_vals[0], wi_idx[0])
        up = _vs_mm(x2, wi_vals[1], wi_idx[1])
        h = (_act(gate, cfg.activation) * up).astype(x.dtype)
    else:
        h = _act(_vs_mm(x2, wi_vals, wi_idx), cfg.activation).astype(x.dtype)
    # wo: shard-local CSR over this shard's F-slice
    wo_v, wo_i = wo_vals[0], wo_idx[0]
    vko = wo_v.shape[-2]
    h2 = h.reshape(b * t, h.shape[-1] // vko, vko)
    y = _vs_mm(h2, wo_v, wo_i).astype(x.dtype)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    return y.reshape(b, t, d)


def sparse_mlp_apply(params, x, cfg) -> jax.Array:
    ctx = shd.current()
    if ctx is None:
        # single-device: concatenate the shard-local wo CSRs sequentially
        tp = params["wo_vals"].shape[0]
        gated = params["wi_vals"].ndim == 5
        b, t, d = x.shape
        vk = params["wi_vals"].shape[-2]
        x2 = x.reshape(b * t, d // vk, vk)
        if gated:
            gate = _vs_mm(x2, params["wi_vals"][0], params["wi_idx"][0])
            up = _vs_mm(x2, params["wi_vals"][1], params["wi_idx"][1])
            h = (_act(gate, cfg.activation) * up).astype(x.dtype)
        else:
            h = _act(_vs_mm(x2, params["wi_vals"], params["wi_idx"]),
                     cfg.activation).astype(x.dtype)
        f_loc = h.shape[-1] // tp
        vko = params["wo_vals"].shape[-2]
        y = 0.0
        for r in range(tp):
            h_r = h[:, r * f_loc:(r + 1) * f_loc]
            h2 = h_r.reshape(b * t, f_loc // vko, vko)
            y = y + _vs_mm(h2, params["wo_vals"][r], params["wo_idx"][r])
        return y.reshape(b, t, d).astype(x.dtype)

    mesh, rules = ctx.mesh, ctx.rules
    model_axis = rules.get("ff")
    model_axis = model_axis if model_axis in mesh.shape else None
    batch_phys = rules.get("batch")
    batch_phys = tuple(p for p in (batch_phys if isinstance(batch_phys, tuple)
                                   else (batch_phys,)) if p in mesh.shape) or None
    if batch_phys:
        import math
        dp = math.prod(mesh.shape[p] for p in batch_phys)
        if x.shape[0] % dp:
            batch_phys = None

    def spec(axes, shape):
        return shd.spec_for(axes, mesh=mesh, rules=rules, shape=shape)

    gated = params["wi_vals"].ndim == 5
    lead = (None,) if gated else ()
    in_specs = (
        PS(batch_phys, None, None),
        spec((*lead, "ff", None, None, None), params["wi_vals"].shape),
        spec((*lead, "ff", None), params["wi_idx"].shape),
        spec(("ff", None, None, None, None), params["wo_vals"].shape),
        spec(("ff", None, None), params["wo_idx"].shape),
    )
    y = jax.shard_map(
        lambda *a: _body(*a, cfg=cfg, model_axis=model_axis),
        mesh=mesh, in_specs=in_specs,
        out_specs=PS(batch_phys, None, None), check_vma=False,
    )(x, params["wi_vals"], params["wi_idx"], params["wo_vals"],
      params["wo_idx"])
    return y
