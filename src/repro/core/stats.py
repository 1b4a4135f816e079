"""Sufficient statistics and their (optionally compressed) reductions.

The paper's parallel structure (Sec 4.1, Fig. 1): every worker computes

    Sigma^p = sum_d (1/gamma_d) x_d x_d^T        (K x K)
    mu^p    = sum_d (rho_d/gamma_d + beta_d) x_d (K,)

and the global statistics are plain sums over workers. On TPU the reduce is
``jax.lax.psum`` over the mesh data axes. The paper notes (Sec 4.1) that
Sigma^p is symmetric so "it suffices to compute only the upper or lower
triangle" — we exploit that as a *triangle-packed* psum, reducing the
dominant collective from K^2 to K(K+1)/2 elements.
"""
from __future__ import annotations

import itertools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def shard_row_offset(local_n: int, axes: Sequence[str]) -> jnp.ndarray:
    """Global row index of this shard's first row, inside shard_map.

    ``distributed.shard_rows`` lays rows out row-major over ``axes`` in
    order, so the linear shard index is the mixed-radix number over the
    axis indices; times the local row count gives the offset. Identity
    (0) outside a mesh. The LIN steps feed this to the rowwise MC gamma
    draw (``augment.gamma_mc_rowwise``) so a mesh fit draws the *same*
    gammas as the single-device and streaming drivers — sharding layout
    no longer changes the chain."""
    if not axes:
        return jnp.int32(0)
    off = jnp.int32(0)
    for ax in axes:
        off = off * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return off * local_n


def triangle_pack(S: jnp.ndarray) -> jnp.ndarray:
    """Pack a symmetric (K, K) matrix into its K(K+1)/2 lower triangle."""
    K = S.shape[0]
    idx = jnp.tril_indices(K)
    return S[idx]


def triangle_unpack(packed: jnp.ndarray, K: int) -> jnp.ndarray:
    """Inverse of triangle_pack: rebuild the full symmetric matrix."""
    idx = jnp.tril_indices(K)
    S = jnp.zeros((K, K), packed.dtype).at[idx].set(packed)
    return S + jnp.tril(S, -1).T


def preduce(x: jnp.ndarray, axes: Sequence[str] | None,
            live: jnp.ndarray | None = None) -> jnp.ndarray:
    """psum over mesh axes when running inside shard_map; identity otherwise.

    ``live`` (this shard's liveness weight, shape () or (1,)) switches to
    the failure-tolerant renormalized reduction: sum_p live_p x_p scaled
    by P / sum_p live_p. A dead replica (live = 0) drops out and the
    statistic stays an unbiased estimate of the full-data sum — the SVM's
    statistics are sums over rows, so dropping a shard and scaling is
    exactly the bootstrap-style estimate DESIGN.md §Reliability argues
    for. With live = 1 everywhere this is BITWISE the plain psum
    (x * 1.0 and * (P/P) are exact), so the solver can thread it
    unconditionally on the mesh path."""
    if not axes:
        return x
    with jax.named_scope("psum"):
        if live is None:
            return jax.lax.psum(x, tuple(axes))
        lv = jnp.reshape(live, ())
        # Weight in x's dtype (liveness is 0/1 — exact even in bf16) so a
        # reduce_dtype-compressed payload stays compressed on the wire;
        # the den psum is one fp32 scalar.
        num = jax.lax.psum(lv.astype(x.dtype) * x, tuple(axes))
        den = jax.lax.psum(lv.astype(jnp.float32), tuple(axes))
        total = float(np.prod([jax.lax.axis_size(a) for a in axes]))
        scale = total / jnp.maximum(den, 1.0)
        return num * scale.astype(num.dtype)


def masked_mean(total: jnp.ndarray, count: jnp.ndarray,
                axes: Sequence[str] | None,
                live: jnp.ndarray | None = None) -> jnp.ndarray:
    """Globally-reduced mean over valid rows (diagnostics) from this
    shard's masked row sum ``total`` and its valid-row ``count``. The
    ``live`` renormalization factors cancel between num and den, so the
    dropped-shard mean is the mean over surviving rows — the right
    diagnostic."""
    num = preduce(total, axes, live)
    den = preduce(count, axes, live)
    return num / jnp.maximum(den, 1.0)


def reduce_stats(S: jnp.ndarray, b: jnp.ndarray,
                 axes: Sequence[str] | None,
                 triangle: bool = True,
                 reduce_dtype: str | None = None,
                 live: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """All-reduce (Sigma^p, mu^p) across data-parallel workers.

    ``triangle=True`` concatenates the packed triangle of S with b into one
    fused psum — half the collective bytes of a dense K x K reduce plus one
    fewer collective launch (paper Sec 4.1's symmetry observation, made
    wire-level).

    ``reduce_dtype='bfloat16'`` compresses the reduction payload 2x more
    (gradient-compression analogue for the paper's statistic). int8
    transport is NOT expressible as an XLA all-reduce — the on-wire
    accumulator would overflow at 512 workers — so bf16 is the honest
    compressed option on TPU; the fp32 magnitude is restored after the
    reduce. CAUTION (measured, EXPERIMENTS.md §Perf A4): requires the
    gamma clamp eps >= 1e-3 — at the default 1e-6 clamp the 1/gamma
    dynamic range (1e6) exceeds bf16's 8-bit mantissa and the posterior
    solve collapses to chance accuracy.

    ``live`` threads the failure-tolerant renormalized reduction (see
    ``preduce``) through the fused collective.

    A multichain statistic — S (C, K, K), b (K, C) — packs each chain's
    triangle into the same single fused psum (C * K(K+1)/2 + C*K
    payload): the symmetry win and the one-collective launch carry to C
    chains unchanged."""
    if not axes:
        return S, b

    def maybe_cast(x):
        return x.astype(reduce_dtype) if reduce_dtype else x

    def uncast(x):
        return x.astype(jnp.float32) if reduce_dtype else x

    with jax.named_scope("psum"):
        if not triangle:
            return (uncast(preduce(maybe_cast(S), axes, live)),
                    uncast(preduce(maybe_cast(b), axes, live)))
        if S.ndim == 3:
            C, K = S.shape[0], S.shape[1]
            tri = K * (K + 1) // 2
            fused = jnp.concatenate(
                [jax.vmap(triangle_pack)(S).reshape(-1), b.reshape(-1)])
            fused = uncast(preduce(maybe_cast(fused), axes, live))
            S = jax.vmap(lambda p: triangle_unpack(p, K))(
                fused[: C * tri].reshape(C, tri))
            return S, fused[C * tri:].reshape(b.shape)
        K = S.shape[0]
        fused = jnp.concatenate([triangle_pack(S), b])
        fused = uncast(preduce(maybe_cast(fused), axes, live))
        tri = K * (K + 1) // 2
        return triangle_unpack(fused[:tri], K), fused[tri:]


def reduce_kshard(S_blk: jnp.ndarray, b: jnp.ndarray,
                  axes: Sequence[str] | None, k_shard_axis: str,
                  reduce_dtype: str | None = None,
                  live: jnp.ndarray | None = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Reduce the 2-D (data x model) statistic: ONE packed psum of this
    model-shard's (K, K/n) Sigma column block concatenated with b over
    the data axes (mirroring ``reduce_stats``'s triangle+mu packing —
    one collective launch instead of the former separate S_blk and b
    psums), then an all-gather of the column blocks over the model axis
    rebuilding the full (K, K) Sigma.

    The block is an off-diagonal rectangle, so there is no triangle to
    pack — the payload per device is already K*K/n + K, a factor n
    below the 1-D dense reduce (and 2/n below the triangle-packed one
    for n >= 2: the 2-D layout's collective win, DESIGN.md
    §Perf/k-shard). ``reduce_dtype`` compresses the psum payload like
    ``reduce_stats`` (same bf16 clamp caveat); the all-gather stays
    fp32 — it is 1/n of the psum bytes and rebuilds the matrix the
    replicated solve factorizes.
    """
    K, blk = S_blk.shape

    def maybe_cast(x):
        return x.astype(reduce_dtype) if reduce_dtype else x

    def uncast(x):
        return x.astype(jnp.float32) if reduce_dtype else x

    fused = jnp.concatenate([S_blk.reshape(-1), b])
    # live is a DATA-axis weight, replicated over the model axis, so
    # every model shard renormalizes by the same factor and the
    # all-gathered Sigma stays consistent.
    fused = uncast(preduce(maybe_cast(fused), axes, live))
    S_blk = fused[: K * blk].reshape(K, blk)
    b = fused[K * blk:]
    S = jax.lax.all_gather(S_blk, k_shard_axis, axis=1, tiled=True)
    return S, b


def reduce(S: jnp.ndarray, b: jnp.ndarray, axes: Sequence[str] | None,
           *, k_shard_axis: str | None = None, triangle: bool = True,
           reduce_dtype: str | None = None,
           live: jnp.ndarray | None = None
           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """THE step's all-reduce of (Sigma^p, mu^p): the 1-D data-parallel
    ``reduce_stats``, or ``reduce_kshard`` when the statistic is the
    2-D (data x model) column block of ``k_shard_axis``."""
    if k_shard_axis is None:
        return reduce_stats(S, b, axes, triangle=triangle,
                            reduce_dtype=reduce_dtype, live=live)
    return reduce_kshard(S, b, axes, k_shard_axis,
                         reduce_dtype=reduce_dtype, live=live)


def posterior_params(S: jnp.ndarray, b: jnp.ndarray, lam: float,
                     prior_precision: jnp.ndarray | None = None,
                     jitter: float = 0.0):
    """Return (L, mu) for the Gaussian conditional p(w | gamma, D) (Eq. 4/6).

    Precision P = lam*I + S (linear) or lam*K + S (kernel, pass
    ``prior_precision=K``); L is its lower Cholesky factor and mu = P^{-1} b.
    The solve is replicated on every device — the paper's "master" reduce +
    broadcast steps collapse into the all-reduce (DESIGN.md §6.1).
    """
    K = S.shape[0]
    if prior_precision is None:
        P = S + lam * jnp.eye(K, dtype=S.dtype)
    else:
        P = S + lam * prior_precision
    P = 0.5 * (P + P.T)  # exact symmetry for the factorization
    # Relative jitter: fp32 Gram/SYRK statistics carry O(eps * trace/K)
    # negative eigenvalue noise; scale the ridge to the problem.
    scale = jnp.trace(P) / K
    P = P + (jitter * scale) * jnp.eye(K, dtype=S.dtype)
    L = jnp.linalg.cholesky(P)
    mu = jax.scipy.linalg.cho_solve((L, True), b)
    return L, mu


def draw_weight(key: jax.Array, L: jnp.ndarray, mu: jnp.ndarray) -> jnp.ndarray:
    """MC draw w ~ N(mu, P^{-1}) via w = mu + L^{-T} z (paper Eq. 4)."""
    z = jax.random.normal(key, mu.shape, dtype=mu.dtype)
    return mu + jax.scipy.linalg.solve_triangular(L.T, z, lower=False)


def chain_keys(key: jax.Array, chain0: int, n_chains: int) -> jax.Array:
    """Per-chain weight-draw keys: ``fold_in(key, chain0 + c)``.

    Under the counter rng modes EVERY weight draw is chain-keyed (even
    n_chains = 1), so chain c's draw depends only on (iteration key,
    absolute chain id) — never on how many chains ride the same fit."""
    ids = jnp.asarray(chain0, jnp.int32) + jnp.arange(n_chains,
                                                      dtype=jnp.int32)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, ids)


def m_step(S: jnp.ndarray, b: jnp.ndarray, key: jax.Array, *, mode: str,
           lam: float, jitter: float, rng: str = "host", chain0: int = 0,
           n_chains: int = 1,
           prior_precision: jnp.ndarray | None = None) -> jnp.ndarray:
    """THE M-step every step and driver runs on its reduced statistic:
    the posterior solve, then the EM mean (``mode='EM'``) or one MC
    Gibbs draw of the weight.

    The draw's key rule lives here alone: ``key`` as given under
    ``rng='host'``, ``fold_in(key, chain0 + c)`` for chain c under the
    counter modes (the MLT class sweep passes its per-class key
    ``fold_in(key, y)``). ``n_chains > 1`` takes S (C, K, K) and
    b (K, C) and returns the (C, K) chain-major draws: C vmapped
    Cholesky factorizations of lam*I + S_c. ``prior_precision`` is the
    exact kernel SVM's lam*K prior (``posterior_params``)."""
    with jax.named_scope("mstep"):
        if n_chains > 1:
            L, mu = jax.vmap(
                lambda Sc, bc: posterior_params(Sc, bc, lam, jitter=jitter)
            )(S, b.T)
            return jax.vmap(draw_weight)(chain_keys(key, chain0, n_chains),
                                         L, mu)
        L, mu = posterior_params(S, b, lam, prior_precision=prior_precision,
                                 jitter=jitter)
        if mode == "EM":
            return mu
        if rng != "host":
            key = jax.random.fold_in(key, chain0)
        return draw_weight(key, L, mu)


def chain_mean(x: jnp.ndarray, n_chains: int) -> jnp.ndarray:
    """A chain-summed scalar as its cross-chain mean (identity for one
    chain). Each driver applies it in its own float order: the resident
    step after the psum, the stream driver on each chunk before the
    sum."""
    return x / n_chains if n_chains > 1 else x


class StatsWindow:
    """Hard-expiry ring of per-generation (Sigma, b) statistic partials
    — the windowed alternative to the geometric ``SVMConfig.decay``
    warm start (DESIGN.md §Reliability).

    Decay folds the previous generation's EFFECTIVE statistics in at
    weight d, so every generation ever seen keeps a geometric tail —
    old data never fully leaves the model. A window instead retains the
    FRESH partials of the last ``horizon - 1`` generations verbatim and
    sums them at full weight; a generation older than the horizon is
    dropped outright. Because (Sigma, b) are plain sums over rows, the
    drop is EXACT data expiry: the expired rows' contribution to the
    effective statistic is identically zero afterwards — the semantics
    GDPR-style retention horizons need and decay cannot give.

    ``entries[0]`` is the newest retained previous generation. The ring
    is frozen for the whole fit (generations advance per fit, not per
    iteration — same contract as decay) and rides the checkpoint
    payload verbatim (``pack``/``unpack``), so a killed fit resumes
    folding bit-identical sums: resume-exactness reduces to the ring
    arrays being restored as saved, which ``core.resume`` tests pin.
    """

    def __init__(self, horizon: int, entries=()):
        assert horizon >= 1, horizon
        self.horizon = int(horizon)
        self.entries = [dict(e) for e in entries][: self.horizon - 1]

    def folded(self, fresh: dict) -> dict:
        """Effective statistics for the M-step: fresh + every retained
        generation at full weight (newest first — a fixed association
        order, so repeated folds are bitwise reproducible)."""
        out = dict(fresh)
        for e in self.entries:
            out["S"] = out["S"] + e["S"]
            out["b"] = out["b"] + e["b"]
        return out

    def advance(self, fresh: dict) -> list[dict]:
        """The ring the NEXT generation carries: this generation's fresh
        partials pushed in front, hard-truncated to the horizon."""
        head = [{k: np.asarray(fresh[k]) for k in ("S", "b")}]
        return (head + self.entries)[: self.horizon - 1]

    @staticmethod
    def pack(entries) -> dict:
        """Flat ``{win{i}_{S,b}: array}`` dict for the checkpoint
        payload (``core.resume.save_snapshot``)."""
        return {f"win{i}_{k}": np.asarray(e[k])
                for i, e in enumerate(entries) for k in ("S", "b")}

    @staticmethod
    def unpack(arrays: dict) -> list:
        """Inverse of ``pack`` over a flat checkpoint-arrays dict."""
        out: list[dict] = []
        for i in itertools.count():
            if f"win{i}_S" not in arrays:
                break
            out.append({"S": np.asarray(arrays[f"win{i}_S"]),
                        "b": np.asarray(arrays[f"win{i}_b"])})
        return out
