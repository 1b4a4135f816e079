"""LIN-{EM,MC}-CLS: linear binary SVM via data augmentation (paper Sec 2, 4).

One iteration over a *local* data shard (rows of other shards live on other
devices; reductions go through ``stats.reduce``):

  E-step   gamma_d from the residual y_d - w^T x_d      O(NK/P)
  stats    Sigma^p = X^T diag(1/gamma) X                O(NK^2/P)   <- Pallas
           mu^p    = X^T (y (1 + 1/gamma))              O(NK/P)     <- fused
  reduce   psum over data axes                          O(K^2 log P)
  M-step   Cholesky solve (EM) / Gaussian draw (MC)     O(K^3), replicated

Padding convention: invalid rows have X-row == 0 and target == 0, which
makes their statistics contributions exactly zero; ``mask`` only enters the
objective.

``k_shard``: beyond-paper optimization (DESIGN.md §Perf/k-shard) —
additionally split the Sigma^p *column blocks* over the mesh's model
axis, turning the paper's 1-D data-parallel statistic into a 2-D
(data x model) one. Each model shard computes X^T diag(w) X[:, cols]
INSIDE the single-stream fused kernel (the ``col_window`` parameter of
``ops.fused_stats`` / ``ops.nystrom_fused_stats``, so EM, MC and the
Nystrom phi path all stay one X stream on the 2-D layout); the blocks
ride one packed psum over the data axes with b and are all-gathered
over the model axis (``stats.reduce_kshard``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from repro.kernels import ops
from . import augment, objective, stats


class SVMData(NamedTuple):
    """A (possibly local-shard) view of the training set."""
    X: jnp.ndarray       # (N, K) rows zeroed where mask == 0
    target: jnp.ndarray  # y in {+-1} (CLS), float (SVR), int (MLT); 0 if padded
    mask: jnp.ndarray    # (N,) 1.0 valid / 0.0 padding


@dataclasses.dataclass(frozen=True)
class PhiSpec:
    """Static half of a Nystrom feature map (core/nystrom.py).

    The array half — the (m, D) landmark strip and the (m, m)
    ``K_mm^{-1/2}`` projection — travels separately as a ``phi``
    operand pair through every step/chunk function, because SVMConfig
    must stay hashable (the solver lru-caches jitted builders on it)
    and the arrays must stay traced (no retrace per fit).

    With a PhiSpec present, the chunk-callable statistics featurize
    on device: data.X holds RAW rows (D-wide), and the state/statistic
    dimension is ``proj.shape[1] + add_bias``. ``add_bias`` appends the
    phi-space bias column (mask-valued, so padding stays a no-op) —
    the X-space ``SVMConfig.add_bias`` must be False in this mode.
    """
    sigma: float = 1.0
    kind: str = "rbf"
    add_bias: bool = True


def accumulate_stats(X: jnp.ndarray, rho: jnp.ndarray, beta: jnp.ndarray,
                     w: jnp.ndarray, *, mode: str, key: jax.Array | None,
                     eps: float, backend: str | None,
                     row0: jnp.ndarray | int = 0,
                     phi=None, phi_spec: PhiSpec | None = None,
                     mask: jnp.ndarray | None = None,
                     col_window: tuple | None = None,
                     rng: str = "host", chain0: int = 0):
    """(margin, gamma, Sigma^p, mu^p) for the generic hinge over one row
    block — THE chunk-callable statistic every driver shares: the
    in-memory drivers call it on the whole (padded) set, the mesh SPMD
    step calls it on the local shard, and ``driver="stream"`` calls it
    per chunk and sums (the statistics are exact sums over rows, paper
    Fig. 1, so chunk accumulation is exact). Shared by CLS (rho=beta=y)
    and each Crammer-Singer class update.

    Padded rows (X-row = 0, rho = beta = 0) contribute exactly zero to
    Sigma and b, so a partially-valid block needs no special casing.

    ``row0`` is the block's global row offset: MC gamma draws are keyed
    per global row so the sampled chain is invariant to chunking and
    sharding layout.

    BOTH modes stream X once through ``fused_stats``: EM with the
    ``em_hinge`` epilogue (today's path), MC with ``mc_hinge`` — the
    per-row (nu, u) noise is pre-drawn here (``augment.draw_ig_noise``,
    rowwise-keyed, bitwise-identical to the ``gamma_mc_rowwise``
    oracle) and the inverse-Gaussian transform runs INSIDE the kernel
    on the margin tile, so the draw no longer forces a separate margin
    pass + SYRK (3 X streams -> 1; DESIGN.md §Perf/MC-SVR).

    ``phi``/``phi_spec`` switch the statistic to Nystrom phi-space
    (core/nystrom.py): X holds RAW rows and phi = (landmarks, proj) is
    featurized ON DEVICE inside the statistic. Both modes fuse
    featurization into the single X sweep (``ops.nystrom_fused_stats``
    — the (N, m) phi matrix never exists, for EM *and* MC). ``mask``
    is required in phi-space — a zero X row is NOT a zero phi row, so
    padding must be masked rather than relying on the zero-row layout.

    ``col_window = (start, blk)`` narrows Sigma to its column block —
    the 2-D (data x model) ``k_shard_axis`` statistic (DESIGN.md
    §Perf/k-shard). The window composes with BOTH modes and with the
    phi path (where it selects PHI columns), so the single-X-stream
    property carries to the 2-D layout unchanged; margin/gamma/b stay
    full width.

    ``rng`` selects the MC noise source (DESIGN.md §Perf/RNG):
    'host' pre-draws the fold_in-keyed (nu, u) operands
    (``augment.draw_ig_noise``, today's path); 'fused' ships only the
    (4,) uint32 counter seed and the kernels derive the bits in-body;
    'fused_predraw' materializes the SAME counter stream on the host
    (``augment.draw_fused_noise``) and feeds it through the legacy
    operand path — the whole-fit bitwise oracle for 'fused'.
    ``chain0`` offsets the counter's chain coordinate; a 2-D (K, C)
    ``w`` under 'fused' runs C Gibbs chains over the one X stream
    (margin/gamma (N, C), b (K, C), S (C, K, K)).
    """
    if mode == "EM":
        epilogue, noise, seed = "em_hinge", None, None
    elif rng == "host":
        epilogue, seed = "mc_hinge", None
        noise = augment.draw_ig_noise(key, X.shape[0], row0)
    elif rng == "fused_predraw":
        epilogue, seed = "mc_hinge", None
        noise = augment.draw_fused_noise(key, X.shape[0], row0, chain0, 2)
    else:
        assert rng == "fused", rng
        epilogue, noise = "mc_hinge", None
        seed = augment.pack_seed(key, row0, chain0)
    if phi_spec is not None:
        landmarks, proj = phi
        if mask is None:
            mask = jnp.ones((X.shape[0],), jnp.float32)
        margin, gamma, b, S = ops.nystrom_fused_stats(
            X, landmarks, proj, rho, beta, w, mask, noise,
            sigma=phi_spec.sigma, kind=phi_spec.kind,
            add_bias=phi_spec.add_bias, epilogue=epilogue, eps=eps,
            col_window=col_window, seed=seed, backend=backend)
    else:
        margin, gamma, b, S = ops.fused_stats(
            X, rho, beta, w, None, noise, epilogue=epilogue, eps=eps,
            col_window=col_window, seed=seed, backend=backend)
    return margin, gamma, S, b


def _k_block(width: int, axis_name: str):
    """(start, blk) Sigma column window of the width-K statistic for
    this model-axis shard — ``blk`` is static, ``start`` traced
    (``axis_index * blk``); the pair feeds ``accumulate_stats``'s
    ``col_window`` directly. ``width`` is the STATISTIC dimension:
    X columns for LIN, the phi width (``w.shape[0]``) in phi-space.

    The model-axis size must divide K: a truncating ``K // n`` here
    would silently drop the trailing ``K % n`` columns of Sigma (the
    all-gather would rebuild a (K, n*(K//n)) matrix) and corrupt the
    posterior.
    """
    n = jax.lax.axis_size(axis_name)
    if width % n != 0:
        raise ValueError(
            f"k_shard_axis {axis_name!r} of size {n} does not divide "
            f"K={width}; pad the feature dimension to a multiple of "
            f"{n} with explicit zero columns "
            f"(data.pipeline.pad_features_to / SVMConfig.pad_features) "
            f"or drop k_shard_axis.")
    blk = width // n
    return jax.lax.axis_index(axis_name) * blk, blk


def resident_step(chunk_stats, data: SVMData, w: jnp.ndarray,
                  key: jax.Array, *, mode: str, lam: float, jitter: float,
                  axes: Sequence[str], triangle: bool,
                  k_shard_axis: str | None, reduce_dtype: str | None,
                  live: jnp.ndarray | None, rng: str, n_chains: int,
                  chain0: int, **stat_kw):
    """One in-memory LIN iteration of CLS or SVR, composed from the
    task's chunk statistic ``chunk_stats`` (``cls_chunk_stats`` /
    ``svr.svr_chunk_stats``) — the same E-step the stream driver sums —
    over this shard's rows, then ``stats.reduce``, ``stats.m_step``,
    and the aux from the psummed scalars. Returns (w_new, aux).

    The statistic's scalars are chain sums; here each is psummed first
    and averaged over chains after (the stream driver averages each
    chunk before its sum instead). ``loss`` makes the objective; every
    other ``<v>_sum`` is reported as ``<v>_mean`` over the ``mask_sum``
    valid rows (``stats.masked_mean``), and any other scalar (``n_sv``)
    as its chain mean."""
    # Rowwise MC draws are keyed by global row index, so shards need no
    # per-shard key folds — the row offset decorrelates them and keeps
    # the chain identical to the single-device and streaming drivers.
    row0 = stats.shard_row_offset(data.X.shape[0], axes)
    # 2-D (data x model) statistic: this model-shard computes only its
    # Sigma column block — INSIDE the same single-stream fused kernel
    # (col_window), for EM and MC, X- and phi-space alike; the packed
    # psum + block all-gather rebuild the full Sigma (stats.reduce).
    col_window = (_k_block(w.shape[0], k_shard_axis)
                  if k_shard_axis is not None else None)
    sums = chunk_stats(data, w, key, row0, mode=mode, rng=rng,
                       n_chains=n_chains, chain0=chain0,
                       col_window=col_window, **stat_kw)
    S, b = stats.reduce(sums.pop("S"), sums.pop("b"), axes,
                        k_shard_axis=k_shard_axis, triangle=triangle,
                        reduce_dtype=reduce_dtype, live=live)
    w_new = stats.m_step(S, b, key, mode=mode, lam=lam, jitter=jitter,
                         rng=rng, chain0=chain0, n_chains=n_chains)
    count = sums.pop("mask_sum")
    aux = {"objective": (
        stats.chain_mean(objective.l2_reg(w_new, lam), n_chains)
        + stats.chain_mean(stats.preduce(sums.pop("loss"), axes, live),
                           n_chains))}
    for k, v in sums.items():
        if k.endswith("_sum"):
            aux[k[:-len("_sum")] + "_mean"] = stats.masked_mean(
                v, count, axes, live)
        else:
            aux[k] = stats.chain_mean(stats.preduce(v, axes, live),
                                      n_chains)
    return w_new, aux


@partial(jax.jit, static_argnames=("mode", "lam", "eps", "jitter", "axes",
                                   "triangle", "backend", "k_shard_axis",
                                   "reduce_dtype", "phi_spec", "rng",
                                   "n_chains", "chain0"))
def cls_step(data: SVMData, w: jnp.ndarray, key: jax.Array, *,
             mode: str = "EM", lam: float = 1.0, eps: float = 1e-6,
             jitter: float = 1e-6, axes: Sequence[str] = (),
             triangle: bool = True, backend: str | None = None,
             k_shard_axis: str | None = None,
             reduce_dtype: str | None = None,
             phi=None, phi_spec: PhiSpec | None = None,
             live: jnp.ndarray | None = None,
             rng: str = "host", n_chains: int = 1, chain0: int = 0):
    """One LIN-*-CLS iteration. Returns (w_new, aux dict) with aux keys
    ``objective``, ``gamma_mean`` and ``n_sv`` (``resident_step``).

    ``live`` (this shard's liveness weight) renormalizes every reduction
    around dropped replicas — see ``stats.preduce``; all-ones is bitwise
    the plain psum.

    ``rng``/``chain0`` select the MC noise source (see
    ``accumulate_stats``). ``n_chains > 1`` (counter rng only) carries
    the weight state CHAIN-MAJOR as (C, K): the statistic runs all C
    chains over one X stream, the C posterior solves are vmapped, and
    the reported objective/diagnostics are cross-chain means."""
    return resident_step(
        cls_chunk_stats, data, w, key, mode=mode, lam=lam, jitter=jitter,
        axes=axes, triangle=triangle, k_shard_axis=k_shard_axis,
        reduce_dtype=reduce_dtype, live=live, rng=rng, n_chains=n_chains,
        chain0=chain0, eps=eps, backend=backend, phi=phi,
        phi_spec=phi_spec)


def cls_chunk_stats(chunk: SVMData, w: jnp.ndarray, key: jax.Array,
                    row0: jnp.ndarray, *, mode: str, eps: float,
                    backend: str | None, phi=None,
                    phi_spec: PhiSpec | None = None,
                    rng: str = "host", n_chains: int = 1,
                    chain0: int = 0,
                    col_window: tuple | None = None) -> dict:
    """THE CLS E-step over one row block: its additive contributions.

    Every field is an exact sum over the block's valid rows, so the
    stream driver tree-sums these dicts across chunks and lands on the
    same (Sigma, b, loss, aux) the in-memory step (``resident_step``,
    which runs this over its whole shard) computes in one shot (padded
    rows contribute zero by the layout convention; in phi-space the
    mask enforces it — see ``accumulate_stats``). ``col_window`` is the
    k-shard column block (``accumulate_stats``).

    Multichain (counter rng) blocks carry S (C, K, K) / b (K, C), and
    each scalar sums over (row, chain) pairs — ``mask_sum`` too; each
    driver takes the chain mean (``stats.chain_mean``) in its own float
    order. The counter keying makes the draws — and therefore the whole
    chain — invariant to the chunk grid, which is what the elastic
    mid-pass resume test pins bitwise.
    """
    X, y, mask = chunk
    multi = n_chains > 1
    margin, gamma, S, b = accumulate_stats(
        X, y, y, w.T if multi else w, mode=mode, key=key, eps=eps,
        backend=backend, row0=row0, phi=phi, phi_spec=phi_spec, mask=mask,
        col_window=col_window, rng=rng, chain0=chain0)
    if multi:
        y, mask = y[:, None], jnp.broadcast_to(mask[:, None], margin.shape)
    return {
        "S": S,
        "b": b,
        "loss": objective.hinge_obj_terms(margin, y, mask),
        "n_sv": jnp.sum(mask * (gamma <= 2.0 * eps)),
        "gamma_sum": jnp.sum(gamma * mask),
        "mask_sum": jnp.sum(mask),
    }


def decision_function(w: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    return X.astype(jnp.float32) @ w.astype(jnp.float32)


def init_weight(K: int) -> jnp.ndarray:
    return jnp.zeros((K,), jnp.float32)
