"""Feed-forward blocks: SwiGLU, GELU MLP, and top-k MoE.

MoE strategy (DESIGN.md §4): expert weights are sharded over the mesh's
'model' axis (expert parallelism). On-mesh, the layer runs as a shard_map
island — tokens are replicated across the model axis (they are already
only batch-sharded), each model shard gathers the tokens routed to *its*
expert slice into an (E_loc, C, D) buffer, runs the expert GEMMs, scatters
back its partial output and psums over 'model'. No all-to-all is needed
because token activations are model-replicated; the psum is the same
collective a row-parallel dense FFN would pay. Capacity C drops overflow
tokens deterministically (GShard-style), with router weights renormalized
over surviving assignments.

Off-mesh (smoke tests) a mathematically identical jnp fallback runs the
same gather/scatter with E_loc = E.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .common import dense_init, split_keys


# ---------------------------------------------------------------- dense FFN
def init_swiglu(key, d_model: int, d_ff: int, n_layers: int):
    ks = split_keys(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff),
        "w_up": dense_init(ks[1], d_model, d_ff),
        "w_down": dense_init(ks[2], d_ff, d_model,
                             scale=1.0 / (2 * n_layers) ** 0.5),
    }


def swiglu(p, x):
    g = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
    u = x @ p["w_up"].astype(x.dtype)
    return (g * u) @ p["w_down"].astype(x.dtype)


def init_gelu_mlp(key, d_model: int, d_ff: int, n_layers: int,
                  use_bias: bool = True):
    ks = split_keys(key, 2)
    p = {
        "w_up": dense_init(ks[0], d_model, d_ff),
        "w_down": dense_init(ks[1], d_ff, d_model,
                             scale=1.0 / (2 * n_layers) ** 0.5),
    }
    if use_bias:
        p.update(b_up=jnp.zeros((d_ff,)), b_down=jnp.zeros((d_model,)))
    return p


def gelu_mlp(p, x):
    h = x @ p["w_up"].astype(x.dtype)
    if "b_up" in p:
        h = h + p["b_up"].astype(x.dtype)
    h = jax.nn.gelu(h)
    out = h @ p["w_down"].astype(x.dtype)
    if "b_down" in p:
        out = out + p["b_down"].astype(x.dtype)
    return out


# --------------------------------------------------------------------- MoE
def init_moe(key, cfg):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], D, E, scale=0.1),
        "moe_gate": _stack_expert_init(ks[1], E, D, F),
        "moe_up": _stack_expert_init(ks[2], E, D, F),
        "moe_down": _stack_expert_init(ks[3], E, F, D,
                                       scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_swiglu(ks[4], D,
                                  cfg.n_shared_experts * F, cfg.n_layers)
    return p


def _stack_expert_init(key, E, d_in, d_out, scale=1.0):
    keys = jax.random.split(key, E)
    return jnp.stack([dense_init(k, d_in, d_out, scale=scale) for k in keys])


def _route(x2d, router_w, top_k: int):
    """Top-k softmax routing. x2d: (T, D). Returns gates (T,K) f32,
    expert ids (T,K) int32."""
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eidx.astype(jnp.int32)


def _expert_pass(xt, gates, eidx, wg, wu, wd, e0, E_loc, C):
    """Gather tokens of experts [e0, e0+E_loc), run GEMMs, scatter back.

    xt: (T, D); wg/wu/wd: (E_loc, D, F)/(E_loc, D, F)/(E_loc, F, D)."""
    T, D = xt.shape
    K = eidx.shape[1]
    # Position of each (token, k) assignment within its expert's queue,
    # counted in flattened (T*K) assignment order (deterministic drop
    # policy). The one-hot/cumsum is over the *local* expert slice only,
    # so its footprint is (T*K, E_loc), not (T*K, E_total).
    flat_e = eidx.reshape(-1)                                   # (T*K,)
    e_rel = flat_e - e0
    in_slice = (e_rel >= 0) & (e_rel < E_loc)
    oh = jax.nn.one_hot(jnp.where(in_slice, e_rel, E_loc),
                        E_loc + 1, dtype=jnp.int32)[:, :E_loc]  # (T*K, E_loc)
    pos = (jnp.cumsum(oh, axis=0) - oh)                         # prior count
    pos = jnp.sum(pos * oh, axis=-1)                            # (T*K,)
    keep = in_slice & (pos < C)
    e_safe = jnp.clip(e_rel, 0, E_loc - 1)
    p_safe = jnp.clip(pos, 0, C - 1)

    xt_rep = jnp.broadcast_to(xt[:, None, :], (T, K, D)).reshape(T * K, D)
    buf = jnp.zeros((E_loc, C, D), xt.dtype)
    buf = buf.at[e_safe, p_safe].add(
        jnp.where(keep[:, None], xt_rep, 0.0))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype))
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(xt.dtype))
    got = y[e_safe, p_safe]                                     # (T*K, D)
    gate_flat = gates.reshape(-1).astype(xt.dtype)
    got = got * jnp.where(keep, gate_flat, 0.0)[:, None]
    return got.reshape(T, K, D).sum(axis=1)                     # (T, D)


def moe_apply(cfg, ctx, p, x, *, capacity_factor: float | None = None):
    """x: (B, S, D) -> (B, S, D). ctx: ShardingCtx."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor

    def full_local(xl, router_w, wg, wu, wd, e0, E_loc):
        T = xl.shape[0] * xl.shape[1]
        xt = xl.reshape(T, D)
        gates, eidx = _route(xt, router_w, K)
        C = max(1, int(T * K * capacity_factor) // E)
        out = _expert_pass(xt, gates, eidx, wg, wu, wd, e0, E_loc, C)
        return out.reshape(xl.shape)

    if ctx.mesh is not None and ctx.tp_axis is not None \
            and E % ctx.axis_size(ctx.tp_axis) == 0 \
            and B % ctx.axis_size(ctx.dp_axes) == 0:
        # (decode with tiny batch falls through to the local path below —
        # at one token per step the expert GEMMs are negligible)
        tp = ctx.tp_axis
        E_loc = E // ctx.axis_size(tp)
        dp = ctx.dp_axes

        def island(xl, router_w, wg, wu, wd):
            e0 = jax.lax.axis_index(tp) * E_loc
            out = full_local(xl, router_w, wg, wu, wd, e0, E_loc)
            return jax.lax.psum(out, tp)

        other = tuple(a for a in ctx.mesh.axis_names
                      if a not in dp and a != tp)
        xspec = P(dp, None, None)
        wspec = P(tp, None, None)
        fn = shard_map(
            island, mesh=ctx.mesh,
            in_specs=(xspec, P(None, None), wspec, wspec, wspec),
            out_specs=xspec, check_vma=False)
        del other
        # cast expert weights BEFORE the island boundary: the FSDP
        # all-gather implied by the in_specs then moves bf16, not f32
        # (2x collective bytes + gathered-buffer memory otherwise)
        y = fn(x, p["router"],
               p["moe_gate"].astype(x.dtype),
               p["moe_up"].astype(x.dtype),
               p["moe_down"].astype(x.dtype))
    else:
        y = full_local(x, p["router"], p["moe_gate"], p["moe_up"],
                       p["moe_down"], 0, E)

    if cfg.n_shared_experts:
        y = y + swiglu(p["shared"], x)
    return y
