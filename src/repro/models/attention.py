"""Attention: GQA with blockwise (flash-style) train/prefill path, cached
decode path, and DeepSeek-V2 MLA (latent KV) with absorbed decode.

Memory discipline: full (Sq, Skv) score materialization at 32k tokens is
~4 TB — the train/prefill path therefore runs a blockwise online-softmax
(lax.scan over KV chunks inside a scan over Q chunks), keeping live scores
at (q_chunk, kv_chunk). This is the flash-attention *algorithm* expressed
in jnp; on TPU the MXU-tiled matmuls inside each block are what the
hardware wants, and XLA keeps the running (m, l, acc) carries in
registers/VMEM.

GQA layout: q is grouped as (B, S, KVH, G, dh) so every block matmul
contracts over full tiles without materializing repeated K/V.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import rotary
from .common import dense_init, rms_norm, split_keys

_NEG_INF = -1e30


# --------------------------------------------------------------------------
# blockwise attention core
# --------------------------------------------------------------------------
def blockwise_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   causal: bool = True, q_offset=0,
                   q_chunk: int = 1024, kv_chunk: int = 1024,
                   skip_masked_blocks: bool = False) -> jnp.ndarray:
    """q: (B, Sq, H, dh); k/v: (B, Skv, KVH, dh) -> (B, Sq, H, dh).

    ``skip_masked_blocks`` wraps fully-masked KV blocks in lax.cond so the
    causal lower triangle costs ~half the FLOPs (beyond-baseline perf
    switch; see EXPERIMENTS.md §Perf)."""
    B, Sq, H, dh = q.shape
    _, Skv, KVH, _ = k.shape
    dv = v.shape[-1]            # MLA: value dim may differ from q/k dim
    G = H // KVH
    qc, kvc = min(q_chunk, Sq), min(kv_chunk, Skv)
    if Sq % qc:      # non-divisible (odd test shapes): single chunk
        qc = Sq
    if Skv % kvc:
        kvc = Skv
    nq, nkv = Sq // qc, Skv // kvc
    scale = dh ** -0.5

    qr = q.reshape(B, nq, qc, KVH, G, dh).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(B, nkv, kvc, KVH, dh).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nkv, kvc, KVH, dv).transpose(1, 0, 2, 3, 4)

    def q_step(_, iq_qb):
        iq, qb = iq_qb                      # qb: (B, qc, KVH, G, dh)
        q_pos = q_offset + iq * qc + jnp.arange(qc)

        def kv_step(carry, ikv_kb):
            m_run, l_run, acc = carry
            ikv, kb, vb = ikv_kb            # kb/vb: (B, kvc, KVH, dh)

            # checkpointed: the (qc, kvc) score/prob blocks are
            # rematerialized in the backward pass (flash-attention's
            # recompute trade) instead of being stacked as scan residuals
            # — that stack is O(S^2) bytes and dwarfs HBM at 32k tokens.
            @jax.checkpoint
            def compute(args):
                m_run, l_run, acc = args
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
                if causal:
                    kv_pos = ikv * kvc + jnp.arange(kvc)
                    mask = q_pos[:, None] >= kv_pos[None, :]
                    s = jnp.where(mask[None, None, None], s, _NEG_INF)
                m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m_run - m_new)
                l_new = l_run * corr + jnp.sum(p, axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc

            if causal and skip_masked_blocks:
                # block is fully masked iff its first kv pos > last q pos
                live = (ikv * kvc) <= (q_offset + iq * qc + qc - 1)
                carry = jax.lax.cond(live, compute, lambda a: a,
                                     (m_run, l_run, acc))
            else:
                carry = compute((m_run, l_run, acc))
            return carry, None

        m0 = jnp.full((B, KVH, G, qc), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, qc, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nkv), kr, vr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B, KVH, G, qc, dh)
        return None, out.transpose(0, 3, 1, 2, 4)      # (B, qc, KVH, G, dh)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qr))
    # outs: (nq, B, qc, KVH, G, dv) -> (B, Sq, H, dv)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, dv)
    return out.astype(q.dtype)


def seq_parallel_attention(ctx, q, k, v, *, causal=True, q_chunk=1024,
                           kv_chunk=1024, skip_masked_blocks=False):
    """Ulysses-style sequence-parallel attention island: the query
    sequence is sharded over the model axis (each device runs the
    blockwise kernel over its local q chunks against replicated K/V,
    with q_offset fixing causality). Divides O(S^2) attention compute by
    the TP degree for archs whose head count cannot shard (smollm: 9
    heads on a 16-way axis -> 16x replicated attention otherwise).
    K/V replication is cheap for small-KV GQA. Falls back to plain
    blockwise attention when S doesn't divide."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    B, S = q.shape[0], q.shape[1]
    tp = ctx.tp_axis
    dp = ctx.dp_axes if B % ctx.axis_size(ctx.dp_axes) == 0 else ()
    if tp is None or S % ctx.axis_size(tp) != 0:
        return blockwise_attn(q, k, v, causal=causal, q_chunk=q_chunk,
                              kv_chunk=kv_chunk,
                              skip_masked_blocks=skip_masked_blocks)
    S_loc = S // ctx.axis_size(tp)

    def island(q_, k_, v_):
        off = jax.lax.axis_index(tp) * S_loc
        return blockwise_attn(q_, k_, v_, causal=causal, q_offset=off,
                              q_chunk=min(q_chunk, S_loc),
                              kv_chunk=kv_chunk,
                              skip_masked_blocks=skip_masked_blocks)

    qspec = P(dp, tp, None, None)
    kvspec = P(dp, None, None, None)
    fn = shard_map(island, mesh=ctx.mesh,
                   in_specs=(qspec, kvspec, kvspec), out_specs=qspec,
                   check_vma=False)
    return fn(q, k, v)


def decode_attn_island(ctx, q, k_cache, v_cache, pos, k_new, v_new):
    """Distributed cached decode as an explicit shard_map island.

    Layout: batch over DP (when divisible), cache *sequence* over the
    model axis (context-parallel decode; long-context batch-1 cells
    spread S over data x model). Each device updates its own cache shard
    in place and computes a local online-softmax partial; the shards
    combine with O(B*H*dh) psums. This bypasses GSPMD entirely for the
    cache — the observed alternative was a full-cache regather per step
    (10-30x the useful bytes) plus an f32 upcast copy on backends without
    native bf16 dots.

    q/k_new/v_new: (B, 1, H|KVH, dh); caches: (B, S, KVH, dh).
    Returns (attn out (B, 1, H, dh), new k_cache, new v_cache)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    B, S, KVH, _ = k_cache.shape
    H, dh = q.shape[2], q.shape[3]
    dp_ok = B % ctx.axis_size(ctx.dp_axes) == 0
    dp = ctx.dp_axes if dp_ok else ()
    if dp_ok:
        seq_axes = (ctx.tp_axis,)
    else:  # long-context single-sequence: 2-D context parallelism
        seq_axes = tuple(a for a in (ctx.fsdp_axis, ctx.tp_axis) if a)
    if not seq_axes or S % ctx.axis_size(seq_axes) != 0:
        k_c = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
        v_c = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
        return decode_attn(q, k_c, v_c, pos + 1), k_c, v_c

    def island(q_, kc, vc, pos_, kn, vn):
        S_loc = kc.shape[1]
        off = jnp.int32(0)
        for a in seq_axes:
            off = off * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        start = off * S_loc
        rel = pos_ - start
        ok = (rel >= 0) & (rel < S_loc)
        safe = jnp.clip(rel, 0, S_loc - 1)

        def upd(cache, new):   # masked in-place row update of this shard
            cur = jax.lax.dynamic_slice_in_dim(cache, safe, 1, axis=1)
            val = jnp.where(ok, new.astype(cache.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(cache, val, safe,
                                                       axis=1)

        kc = upd(kc, kn)
        vc = upd(vc, vn)
        G = H // KVH
        qr = q_.reshape(q_.shape[0], KVH, G, dh)
        s = jnp.einsum("bhgd,bkhd->bhgk", qr, kc).astype(jnp.float32)
        s = s * dh ** -0.5
        valid = (start + jnp.arange(S_loc))[None] <= pos_
        s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhgk,bkhd->bhgd", p.astype(vc.dtype), vc
                           ).astype(jnp.float32)
        m = jax.lax.pmax(m_loc, seq_axes)
        corr = jnp.exp(m_loc - m)
        l = jax.lax.psum(l_loc * corr, seq_axes)
        o = jax.lax.psum(o_loc * corr[..., None], seq_axes)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return o.astype(q_.dtype), kc, vc

    qspec = P(dp, None, None, None)
    cspec = P(dp, seq_axes, None, None)
    fn = shard_map(island, mesh=ctx.mesh,
                   in_specs=(qspec, cspec, cspec, P(), qspec, qspec),
                   out_specs=(qspec, cspec, cspec), check_vma=False)
    o, k_c, v_c = fn(q, k_cache, v_cache, pos, k_new, v_new)
    return o.reshape(B, 1, H, dh), k_c, v_c


def decode_attn(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                valid_len) -> jnp.ndarray:
    """Single-token attention over a cache.

    q: (B, 1, H, dh); caches: (B, S, KVH, dh); valid_len: scalar or (B,).
    """
    B, _, H, dh = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    qr = q.reshape(B, KVH, G, dh)
    # NB: operand-dtype dot (bf16): the TPU MXU accumulates f32 anyway;
    # asking XLA-CPU for preferred f32 materializes an f32 copy of the
    # whole cache (2x HBM) before the dot. Scores upcast after.
    s = jnp.einsum("bhgd,bkhd->bhgk", qr, k_cache
                   ).astype(jnp.float32) * dh ** -0.5
    pos = jnp.arange(S)
    valid = jnp.asarray(valid_len)
    mask = pos[None, :] < valid.reshape(-1, 1)         # (B or 1, S)
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, dh).astype(q.dtype)


# --------------------------------------------------------------------------
# GQA attention block
# --------------------------------------------------------------------------
def init_gqa(key, cfg):
    D, H, KVH, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * dh),
        "wk": dense_init(ks[1], D, KVH * dh),
        "wv": dense_init(ks[2], D, KVH * dh),
        "wo": dense_init(ks[3], H * dh, D, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.use_bias:
        p.update(bq=jnp.zeros((H * dh,)), bk=jnp.zeros((KVH * dh,)),
                 bv=jnp.zeros((KVH * dh,)), bo=jnp.zeros((D,)))
    return p


def gqa_qkv(cfg, p, x, positions, *, rope: bool = True):
    """Project + rotate. x: (B, S, D); positions: (B, S) or (3, B, S)."""
    B, S, _ = x.shape
    H, KVH, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KVH, dh)
    v = v.reshape(B, S, KVH, dh)
    if rope:
        if cfg.mrope:
            q = rotary.apply_mrope(q, positions, cfg.rope_theta,
                                   cfg.mrope_sections)
            k = rotary.apply_mrope(k, positions, cfg.rope_theta,
                                   cfg.mrope_sections)
        else:
            q = rotary.apply_rope(q, positions, cfg.rope_theta)
            k = rotary.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_out(cfg, p, attn_out, dtype):
    B, S = attn_out.shape[:2]
    out = attn_out.reshape(B, S, -1) @ p["wo"].astype(dtype)
    if cfg.use_bias:
        out = out + p["bo"].astype(dtype)
    return out


def gqa_train(cfg, p, x, positions, *, q_chunk=1024, kv_chunk=1024,
              skip_masked_blocks=False, rope=True, causal=True, ctx=None,
              seq_parallel=False):
    q, k, v = gqa_qkv(cfg, p, x, positions, rope=rope)
    if seq_parallel and ctx is not None and ctx.mesh is not None:
        o = seq_parallel_attention(ctx, q, k, v, causal=causal,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk,
                                   skip_masked_blocks=skip_masked_blocks)
    else:
        o = blockwise_attn(q, k, v, causal=causal, q_chunk=q_chunk,
                           kv_chunk=kv_chunk,
                           skip_masked_blocks=skip_masked_blocks)
    return gqa_out(cfg, p, o, x.dtype)


def gqa_prefill(cfg, p, x, positions, cache_len, *, q_chunk=1024,
                kv_chunk=1024, skip_masked_blocks=False, ctx=None,
                seq_parallel=False):
    """Returns (out, (k_cache, v_cache)) — caches padded to cache_len."""
    q, k, v = gqa_qkv(cfg, p, x, positions)
    if seq_parallel and ctx is not None and ctx.mesh is not None:
        o = seq_parallel_attention(ctx, q, k, v, causal=True,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk,
                                   skip_masked_blocks=skip_masked_blocks)
    else:
        o = blockwise_attn(q, k, v, causal=True, q_chunk=q_chunk,
                           kv_chunk=kv_chunk,
                           skip_masked_blocks=skip_masked_blocks)
    S = x.shape[1]
    pad = cache_len - S
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return gqa_out(cfg, p, o, x.dtype), (k, v)


def gqa_decode(cfg, p, x, pos, cache, *, rope: bool = True, ctx=None):
    """One-token step. x: (B, 1, D); pos: scalar current index; cache:
    (k, v) each (B, S_max, KVH, dh). Returns (out, new_cache).

    The new-token K/V are constrained to the cache's own layout before
    the dynamic update — without this GSPMD re-replicates the whole cache
    around the DUS (a ~10x per-step all-gather at 32k context)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions, (3, B, 1))
    q, k_new, v_new = gqa_qkv(cfg, p, x, positions, rope=rope)
    k_cache, v_cache = cache
    if ctx is not None and ctx.mesh is not None:
        o, k_cache, v_cache = decode_attn_island(
            ctx, q, k_cache, v_cache, pos, k_new, v_new)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
        o = decode_attn(q, k_cache, v_cache, pos + 1)
    return gqa_out(cfg, p, o, x.dtype), (k_cache, v_cache)


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2): latent KV compression, absorbed decode
# --------------------------------------------------------------------------
def init_mla(key, cfg):
    D, H = cfg.d_model, cfg.n_heads
    r, qr_ = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = split_keys(key, 6)
    p = {
        "wkv_a": dense_init(ks[0], D, r + dr),          # -> [ckv, k_rope]
        "kv_norm": jnp.ones((r,)),
        "wkv_b": dense_init(ks[1], r, H * (dn + dv)),   # latent -> k_nope,v
        "wo": dense_init(ks[2], H * dv, D,
                         scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if qr_:
        p["wq_a"] = dense_init(ks[3], D, qr_)
        p["q_norm"] = jnp.ones((qr_,))
        p["wq_b"] = dense_init(ks[4], qr_, H * (dn + dr))
    else:
        p["wq"] = dense_init(ks[5], D, H * (dn + dr))
    return p


def _mla_q(cfg, p, x, positions):
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        ql = rms_norm(x @ p["wq_a"].astype(x.dtype), p["q_norm"],
                      cfg.norm_eps)
        q = ql @ p["wq_b"].astype(x.dtype)
    else:
        q = x @ p["wq"].astype(x.dtype)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rotary.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg, p, x, positions):
    """ckv (B,S,r) normalized latent + rotated shared k_rope (B,S,1,dr)."""
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = x @ p["wkv_a"].astype(x.dtype)
    ckv, k_rope = kv[..., :r], kv[..., r:]
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = rotary.apply_rope(k_rope[:, :, None, :], positions,
                               cfg.rope_theta)
    return ckv, k_rope


def mla_train(cfg, p, x, positions, *, q_chunk=1024, kv_chunk=1024,
              skip_masked_blocks=False):
    """Training/prefill: expand latent to full per-head K/V (standard)."""
    B, S, _ = x.shape
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    ckv, k_rope = _mla_latent(cfg, p, x, positions)
    kv = (ckv @ p["wkv_b"].astype(x.dtype)).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    o = blockwise_attn(q, k, v, causal=True, q_chunk=q_chunk,
                       kv_chunk=kv_chunk,
                       skip_masked_blocks=skip_masked_blocks)
    return o.reshape(B, S, H * dv) @ p["wo"].astype(x.dtype)


def mla_prefill(cfg, p, x, positions, cache_len, **kw):
    """Returns (out, (ckv_cache, k_rope_cache)) — the *latent* cache: this
    is MLA's contribution, 576 floats/token instead of H*(dn+dv)."""
    out = mla_train(cfg, p, x, positions, **kw)
    ckv, k_rope = _mla_latent(cfg, p, x, positions)
    S, pad = x.shape[1], cache_len - x.shape[1]
    if pad > 0:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))
    del S
    return out, (ckv, k_rope[:, :, 0, :])


def mla_decode(cfg, p, x, pos, cache, *, ctx=None):
    """Absorbed decode (the deployment path in arXiv:2405.04434): scores
    and context are taken against the latent cache directly; W_UK folds
    into the query and W_UV into the output."""
    B = x.shape[0]
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)        # (B,1,H,dn/dr)
    ckv_new, k_rope_new = _mla_latent(cfg, p, x, positions)

    ckv_cache, k_rope_cache = cache                      # (B,S,r), (B,S,dr)

    def pin(t, tp_ok=True):
        del tp_ok
        if ctx is None:
            return t
        return ctx.constrain(t, ctx.dp_axes, ctx.tp_axis, None)

    ckv_cache = pin(jax.lax.dynamic_update_slice_in_dim(
        ckv_cache, pin(ckv_new.astype(ckv_cache.dtype)), pos, axis=1))
    k_rope_cache = pin(jax.lax.dynamic_update_slice_in_dim(
        k_rope_cache,
        pin(k_rope_new[:, :, 0, :].astype(k_rope_cache.dtype), False),
        pos, axis=1), False)

    wkv_b = p["wkv_b"].reshape(r, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]        # (r,H,dn),(r,H,dv)
    # absorb W_UK into q: (B,1,H,dn) x (r,H,dn) -> (B,H,r)
    q_lat = jnp.einsum("bqhd,rhd->bhr", q_nope, w_uk.astype(x.dtype))
    s = jnp.einsum("bhr,bkr->bhk", q_lat,
                   ckv_cache).astype(jnp.float32)
    s = s + jnp.einsum("bqhd,bkd->bhk", q_rope,
                       k_rope_cache).astype(jnp.float32)
    s = s * (dn + dr) ** -0.5
    S = ckv_cache.shape[1]
    mask = jnp.arange(S)[None, None, :] < (pos + 1)
    s = jnp.where(mask, s, _NEG_INF)
    pweights = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhk,bkr->bhr", pweights.astype(x.dtype), ckv_cache)
    o = jnp.einsum("bhr,rhd->bhd", ctx_lat, w_uv.astype(x.dtype))
    out = o.reshape(B, 1, H * dv) @ p["wo"].astype(x.dtype)
    return out, (ckv_cache, k_rope_cache)
