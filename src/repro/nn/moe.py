"""Top-k mixture-of-experts with capacity-bounded gather dispatch.

Dispatch is gather-based (per-expert top-C token selection) rather than the
GShard one-hot [T, E, C] tensor — the one-hot dispatch tensor for e.g.
granite-moe (T=4096, E=40, C=1024) would be 167M elements per device and
O(S·E·C·d) combine FLOPs; the gather form keeps only [E, C] indices and
[E, C, d] activations.

Distribution: under a mesh with a "model" axis the expert computation runs
inside shard_map — experts sharded over "model" (expert parallelism), batch
over "data"("pod","data") — because GSPMD's sharding propagation falls back
to full-batch replication for the batched scatter/gather pair this dispatch
needs (measured: +600 GB/device of all-gather/all-reduce per train step on
granite-moe). Inside shard_map every gather/scatter is shard-local and the
only communication is one f32 psum of the combined output over "model".

Expert count is physically padded to a multiple of 16 at init (router stays
at the logical E; padded experts are never routed to) so the expert dim
always divides the mesh "model" axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import ambient_mesh
from repro.nn.linear import init_linear


def _phys_experts(n_experts: int) -> int:
    """Experts >= 16 are padded to a multiple of 16 (the mesh model-axis)."""
    return n_experts if n_experts < 16 else 16 * math.ceil(n_experts / 16)


def init_moe(key, dim: int, hidden: int, n_experts: int, *, dtype=jnp.float32):
    ks = jax.random.split(key, 4)

    def one_expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wg": init_linear(k1, dim, hidden, dtype=dtype)["w"],
            "wu": init_linear(k2, dim, hidden, dtype=dtype)["w"],
            "wd": init_linear(k3, hidden, dim, dtype=dtype)["w"],
        }

    E_phys = _phys_experts(n_experts)
    experts = jax.vmap(one_expert)(jax.random.split(ks[0], E_phys))
    return {
        "router": init_linear(ks[1], dim, n_experts, dtype=jnp.float32),
        "experts": experts,   # each leaf [E_phys, ...]
    }


def _route(params, x, *, top_k: int, capacity_factor: float, E_phys: int):
    """Router + per-(row, expert) top-C dispatch plan.

    Returns gsel/tok_idx [B, E_phys, C], probs [B, S, E] and C. Scatter-free:
    gates are built with a one-hot sum over the k choices so GSPMD never sees
    a batched scatter here.
    """
    B, S, _ = x.shape
    E = params["router"]["w"].shape[1]
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"]["w"])                          # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                          # [B,S,k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # gates[b, s, e] = weight if expert e chosen for token s else 0
    onehot = (top_e[..., None] == jnp.arange(E_phys)[None, None, None])  # [B,S,k,E+]
    gates = jnp.einsum("bsk,bske->bse", top_p,
                       onehot.astype(jnp.float32))                      # [B,S,E+]

    C = max(1, min(S, int(capacity_factor * S * top_k / E)))
    gsel, tok_idx = jax.lax.top_k(gates.transpose(0, 2, 1), C)          # [B,E+,C]
    return gsel, tok_idx, probs, C


def _expert_ffn(xe, wg, wu, wd):
    """xe [..., E, C, d] with stacked expert weights [E, d, f] / [E, f, d]."""
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, wg))
    h = h * jnp.einsum("becd,edf->becf", xe, wu)
    return jnp.einsum("becf,efd->becd", h, wd)


def _dispatch_compute_combine(x, gsel, tok_idx, wg, wu, wd):
    """Shard-local: gather tokens per expert, run the FFN, scatter-add back.
    x [B, S, d]; gsel/tok_idx [B, E, C] -> y [B, S, d] (f32)."""
    B, S, d = x.shape
    xe = jnp.take_along_axis(x[:, None], tok_idx[..., None], axis=2)    # [B,E,C,d]
    ye = _expert_ffn(xe, wg.astype(xe.dtype), wu.astype(xe.dtype),
                     wd.astype(xe.dtype))
    ye = ye * (gsel * (gsel > 0))[..., None].astype(ye.dtype)
    y = jnp.zeros((B, S, d), dtype=jnp.promote_types(ye.dtype, jnp.float32))
    bidx = jnp.arange(B)[:, None, None]
    y = y.at[jnp.broadcast_to(bidx, tok_idx.shape), tok_idx].add(ye)
    return y


def _aux(params, gsel, probs, E: int):
    """Switch-style load-balance loss + dropped-token fraction."""
    # fraction of routed slots per expert (padded experts contribute 0)
    B, S, _ = probs.shape
    used = (gsel > 0).astype(jnp.float32)                               # [B,E+,C]
    frac_tokens = used.sum(axis=(0, 2))[:E] / jnp.maximum(used.sum(), 1.0)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    # combined capacity vs demand: demanded slots = B*S*k, granted = used
    dropped = 1.0 - used.sum() / jnp.maximum(B * S * probs.shape[-1], 1)
    return {"lb_loss": lb_loss,
            "dropped_frac": jnp.clip(dropped, 0.0, 1.0)}


def moe(params, x, *, top_k: int, capacity_factor: float = 1.25,
        ep2d: bool = False):
    """x [B, S, d] -> (y [B, S, d], aux). Expert-parallel under a mesh.

    ``ep2d`` (decode path for 100B+ models): expert weights stay RESIDENT,
    two-axis sharded — E over "model", d_ff over "data" — and the tiny
    per-token activations are psum'd over both axes instead of re-gathering
    hundreds of GB of expert weights every decode step.
    """
    E = params["router"]["w"].shape[1]
    E_phys = params["experts"]["wg"].shape[0]
    gsel, tok_idx, probs, C = _route(params, x, top_k=top_k,
                                     capacity_factor=capacity_factor,
                                     E_phys=E_phys)
    mesh = ambient_mesh()
    ep = (mesh is not None and mesh.axis_names and
          "model" in mesh.axis_names and E_phys % mesh.shape["model"] == 0)
    w = params["experts"]
    if ep and ep2d and "data" in mesh.axis_names:

        def body2d(x_l, gsel_l, tok_l, wg_l, wu_l, wd_l):
            # x replicated (decode tokens are ~MBs); weights stay sharded:
            # wg/wu [E_loc, d, ff_loc], wd [E_loc, ff_loc, d]
            xe = jnp.take_along_axis(x_l[:, None], tok_l[..., None], axis=2)
            h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe,
                                       wg_l.astype(xe.dtype)))
            h = h * jnp.einsum("becd,edf->becf", xe, wu_l.astype(xe.dtype))
            ye = jnp.einsum("becf,efd->becd", h, wd_l.astype(xe.dtype))
            ye = ye * (gsel_l * (gsel_l > 0))[..., None].astype(ye.dtype)
            B, S, d = x_l.shape
            y = jnp.zeros((B, S, d), jnp.promote_types(ye.dtype, jnp.float32))
            bidx = jnp.arange(B)[:, None, None]
            y = y.at[jnp.broadcast_to(bidx, tok_l.shape), tok_l].add(ye)
            return jax.lax.psum(y, ("model", "data"))

        y = jax.shard_map(
            body2d, mesh=mesh,
            in_specs=(P(None, None, None), P(None, "model", None),
                      P(None, "model", None), P("model", None, "data"),
                      P("model", None, "data"), P("model", "data", None)),
            out_specs=P(None, None, None),
        )(x, gsel, tok_idx, w["wg"], w["wu"], w["wd"])
    elif ep:
        dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]
        if x.shape[0] % n_dp != 0:
            dp = None          # e.g. batch=1 long-context decode: replicate B

        def body(x_l, gsel_l, tok_l, wg_l, wu_l, wd_l):
            y = _dispatch_compute_combine(x_l, gsel_l, tok_l, wg_l, wu_l, wd_l)
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, "model", None),
                      P(dp, "model", None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=P(dp, None, None),
        )(x, gsel, tok_idx, w["wg"], w["wu"], w["wd"])
    else:
        y = _dispatch_compute_combine(x, gsel, tok_idx,
                                      w["wg"], w["wu"], w["wd"])
    return y.astype(x.dtype), _aux(params, gsel, probs, E)
