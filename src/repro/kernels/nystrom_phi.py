"""Pallas TPU kernels: fused Nystrom featurize(-and-accumulate).

The Nystrom path (core/nystrom.py) turns the kernel SVM into the linear
PEMSVM on phi(x) = K_mm^{-1/2} k_m(x). Naively that is three passes with
two HBM round-trips of an (N, m) intermediate:

    K_nm = rbf(X, landmarks)      (N, m)  -> HBM
    phi  = K_nm @ proj            (N, m)  -> HBM
    stats = fused_stats(phi, ...)         <- HBM

Both kernels here keep phi tile-local in VMEM instead. Per (bn, D)
X block they compute the RBF cross-Gram against the (m, D) landmark
strip (the ``rbf_gram`` tile body, shared code), apply the precomputed
(m, m) ``K_mm^{-1/2}`` projection on the MXU, and then either

  * ``nystrom_phi``         — write the phi tile out (the device-side
    featurizer: prediction, and MLT's M-pass class sweep where one
    featurize serves all M statistics passes), or
  * ``nystrom_fused_stats`` — feed the phi tile straight into the
    one-sweep statistic (margin, aug, b, Sigma) of ``fused_stats``,
    under ANY augmentation epilogue (``epilogues.py``: EM/MC hinge,
    SVR's double mixture — MC noise is pre-drawn and streamed in as
    (N,) operands): X streams HBM->VMEM ONCE and phi NEVER exists as
    an (N, m) array, for EM and MC, CLS and SVR alike.

Layout conventions (match the solver's padding scheme):

  * ``mask`` zeroes phi rows explicitly — unlike LIN, a zero X row does
    NOT give a zero phi row (rbf k(0, l) = exp(-||l||^2/2 sigma^2)), so
    padded rows must be killed by the mask, not the data.
  * ``add_bias`` appends the phi-space bias as column m with value
    ``mask`` (1 for valid rows, 0 for padding) — the same
    bias-column-is-the-mask trick the stream driver uses for X.

VMEM per grid step (fp32, padded dims): the X tile bn*D, the landmark
strip m*D, the projection m*M, the cross tile bn*m, the phi tile bn*M,
and the (M, M) Sigma accumulator (M = m + add_bias). ``ops.py`` holds
the byte-budget check and falls back to featurize-then-accumulate
(``nystrom_phi`` + the K-tiled ``fused_stats``) when it does not fit —
see DESIGN.md §Perf/Nystrom for the accounting and the roofline
argument for why the fusion wins in the m <= sqrt(N) regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import epilogues
from .fused_stats import aligned_window_base, col_window_geometry
from .rbf_gram import COMPILER_PARAMS, rbf_tile

HIGHEST = jax.lax.Precision.HIGHEST


def _phi_tile(x, lm, pj, maskv, *, kind: str, inv_two_sigma_sq: float,
              bias_col: int | None):
    """One (bn, M) phi tile from a (bn, D) X tile, entirely in VMEM.

    The map is float32: the cross-Gram and the projection dots run at
    HIGHEST precision, since K_mm^{-1/2} has entries up to
    floor^{-1/2} and amplifies a bf16 pass's rounding into a different
    model.

    x: (bn, Dp); lm: (Lp, Dp) landmark strip; pj: (Lp, Wp) projection
    (zero-padded rows/cols are exact no-ops); maskv: (bn, 1).
    ``bias_col`` (static) is the column index receiving the mask-valued
    bias, or None.
    """
    if kind == "rbf":
        kmat = rbf_tile(x, lm, inv_two_sigma_sq)            # (bn, Lp)
    elif kind == "linear":  # the cross-Gram IS the inner product
        kmat = jax.lax.dot_general(
            x, lm, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
    else:  # match the ref oracle: never silently fall through
        raise ValueError(f"unknown kernel kind {kind!r}")
    phi = jax.lax.dot_general(                               # (bn, Wp)
        kmat, pj, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    if bias_col is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, phi.shape, 1)
        phi = phi + jnp.where(cols == bias_col, 1.0, 0.0)
    return phi * maskv


def _make_phi_kernel(kind: str, inv_two_sigma_sq: float,
                     bias_col: int | None):
    def _kernel(x_ref, lm_ref, pj_ref, mask_ref, out_ref):
        out_ref[...] = _phi_tile(
            x_ref[...].astype(jnp.float32),
            lm_ref[...].astype(jnp.float32),
            pj_ref[...].astype(jnp.float32),
            mask_ref[...].astype(jnp.float32),
            kind=kind, inv_two_sigma_sq=inv_two_sigma_sq,
            bias_col=bias_col)
    return _kernel


def _make_score_kernel(kind: str, inv_two_sigma_sq: float,
                       bias_col: int | None):
    """The *scoring* epilogue (serving): phi tile -> margin columns.

    Instead of accumulating (b, Sigma) like the fit-time epilogues, the
    per-tile phi feeds one MXU matmul against the resident (Wp, Cp)
    weight block — C score columns per row (one per tenant/class/
    uncertainty direction) — and phi dies in VMEM. This is predict-time
    single-stream: X is read once and the only HBM write is the (bn, Cp)
    score tile."""
    def _kernel(x_ref, lm_ref, pj_ref, mask_ref, w_ref, out_ref):
        phi = _phi_tile(
            x_ref[...].astype(jnp.float32),
            lm_ref[...].astype(jnp.float32),
            pj_ref[...].astype(jnp.float32),
            mask_ref[...].astype(jnp.float32),
            kind=kind, inv_two_sigma_sq=inv_two_sigma_sq,
            bias_col=bias_col)
        out_ref[...] = jax.lax.dot_general(
            phi, w_ref[...].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return _kernel


def _make_fused_kernel(kind: str, inv_two_sigma_sq: float,
                       bias_col: int | None, epilogue: str, eps: float,
                       eps_ins: float, n_noise: int, n_aug: int,
                       windowed: bool = False, rng: bool = False):
    def _kernel(*refs):
        if rng:
            seed_ref, refs = refs[0], refs[1:]
        if windowed:
            c0_ref, refs = refs[0], refs[1:]
        x_ref, lm_ref, pj_ref, mask_ref, rho_ref, beta_ref, w_ref = refs[:7]
        noise_refs = refs[7:7 + n_noise]
        outs = refs[7 + n_noise:]
        if windowed:                    # trailing VMEM scratch: phi tile
            outs, phi_ref = outs[:-1], outs[-1]
        margin_ref, aug_refs = outs[0], outs[1:1 + n_aug]
        b_ref, s_ref = outs[-2], outs[-1]

        maskv = mask_ref[...].astype(jnp.float32)            # (bn, 1)
        phi = _phi_tile(
            x_ref[...].astype(jnp.float32),
            lm_ref[...].astype(jnp.float32),
            pj_ref[...].astype(jnp.float32),
            maskv, kind=kind, inv_two_sigma_sq=inv_two_sigma_sq,
            bias_col=bias_col)
        rho = rho_ref[...].astype(jnp.float32)               # (bn, 1)
        beta = beta_ref[...].astype(jnp.float32)             # (bn, 1)
        wv = w_ref[...].astype(jnp.float32)                  # (Wp, 1)

        # From here this is fused_stats' tile body with X := phi, its dots
        # at HIGHEST: the RBF features are strongly correlated, so a bf16
        # pass over Sigma = phi^T Gamma^-1 phi moves the EM solution.
        margin = jax.lax.dot_general(
            phi, wv, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
        margin_ref[...] = margin
        if rng:                                  # in-kernel counter RNG
            noise = epilogues.fused_noise(
                seed_ref, pl.program_id(0) * phi.shape[0], margin.shape,
                epilogue)
        else:                                    # pre-drawn operands
            noise = tuple(r[...].astype(jnp.float32) for r in noise_refs)
        aug, weight, coef = epilogues.apply_epilogue(
            epilogue, margin, rho, beta, noise, eps, eps_ins)
        for ref, a in zip(aug_refs, aug):
            ref[...] = a

        @pl.when(pl.program_id(0) == 0)
        def _init():
            b_ref[...] = jnp.zeros_like(b_ref)
            s_ref[...] = jnp.zeros_like(s_ref)

        b_ref[...] += jax.lax.dot_general(                   # phi^T coef
            phi, coef, dimension_numbers=(((0,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
        pw = phi * (maskv * weight)                          # weighted rows
        if windowed:                    # aligned phi-column window, VMEM
            # The TPU kernel compiler slices refs, not loaded values, at
            # a traced offset: stage phi and load the window back.
            phi_ref[...] = phi
            a0 = pl.multiple_of(c0_ref[0], 128)
            pc = phi_ref[:, pl.ds(a0, s_ref.shape[1])]
        else:
            pc = phi
        s_ref[...] += jax.lax.dot_general(                   # phi^T D phi_w
            pw, pc, dimension_numbers=(((0,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
    return _kernel


def _pad_operands(X, landmarks, proj, mask, add_bias, bn):
    """Zero-pad every operand to tile multiples; returns the padded
    arrays plus (Np, Wp, M) where M = proj cols + add_bias."""
    N, D = X.shape
    m, P = proj.shape
    assert landmarks.shape == (m, D), (landmarks.shape, (m, D))
    M = P + int(add_bias)
    Dp = _round_up(D, 128)
    Lp = _round_up(m, 128)   # lane dim of the (bn, m) cross tile
    Wp = _round_up(max(M, 1), 128)
    Np = _round_up(N, bn)
    if mask is None:
        mask = jnp.ones((N,), jnp.float32)
    X = jnp.pad(X, ((0, Np - N), (0, Dp - D)))
    mask = jnp.pad(mask.astype(jnp.float32), (0, Np - N))
    landmarks = jnp.pad(landmarks, ((0, Lp - m), (0, Dp - D)))
    proj = jnp.pad(proj, ((0, Lp - m), (0, Wp - P)))
    return X, landmarks, proj, mask, Np, Wp, M


@functools.partial(jax.jit, static_argnames=("sigma", "kind", "add_bias",
                                             "block_n", "interpret"))
def nystrom_phi(X: jnp.ndarray, landmarks: jnp.ndarray, proj: jnp.ndarray,
                mask: jnp.ndarray | None = None, *, sigma: float = 1.0,
                kind: str = "rbf", add_bias: bool = False,
                block_n: int = 256, interpret: bool = False) -> jnp.ndarray:
    """phi = [rbf(X, landmarks) @ proj, bias] — (N, M) f32, M = m + bias.

    One X stream, no (N, m) cross-Gram intermediate. ``mask`` zeroes
    invalid rows (see module docstring); None means all rows valid.
    """
    N, D = X.shape
    bn = min(block_n, _round_up(N, 8))
    X, landmarks, proj, mask, Np, Wp, M = _pad_operands(
        X, landmarks, proj, mask, add_bias, bn)
    out = pl.pallas_call(
        _make_phi_kernel(kind, 1.0 / (2.0 * float(sigma) ** 2),
                         M - 1 if add_bias else None),
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((bn, X.shape[1]), lambda n: (n, 0)),
            pl.BlockSpec(landmarks.shape, lambda n: (0, 0)),
            pl.BlockSpec(proj.shape, lambda n: (0, 0)),
            pl.BlockSpec((bn, 1), lambda n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((bn, Wp), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Wp), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(X, landmarks, proj, mask.reshape(Np, 1))
    return out[:N, :M]


@functools.partial(jax.jit, static_argnames=("sigma", "kind", "add_bias",
                                             "block_n", "interpret"))
def nystrom_score(X: jnp.ndarray, landmarks: jnp.ndarray,
                  proj: jnp.ndarray, W: jnp.ndarray,
                  mask: jnp.ndarray | None = None, *, sigma: float = 1.0,
                  kind: str = "rbf", add_bias: bool = False,
                  block_n: int = 256,
                  interpret: bool = False) -> jnp.ndarray:
    """scores = nystrom_phi(X, ...) @ W — (N, C) f32, phi never in HBM.

    The predict-side counterpart of ``nystrom_fused_stats``: the same
    in-VMEM phi tile, but the epilogue is a matmul against a (M, C)
    multi-output weight block (C = tenants x classes x uncertainty
    directions) instead of the Sigma accumulation. Masked rows score 0
    in every column. One X stream; HBM traffic is X in + (N, C) out.
    """
    N, D = X.shape
    MW, C = W.shape
    bn = min(block_n, _round_up(N, 8))
    X, landmarks, proj, mask, Np, Wp, M = _pad_operands(
        X, landmarks, proj, mask, add_bias, bn)
    assert MW == M, (
        f"W rows ({MW}) must equal the phi width "
        f"(proj cols + add_bias = {M})")
    Cp = _round_up(C, 128)
    Wmat = jnp.pad(W.astype(jnp.float32), ((0, Wp - M), (0, Cp - C)))
    out = pl.pallas_call(
        _make_score_kernel(kind, 1.0 / (2.0 * float(sigma) ** 2),
                           M - 1 if add_bias else None),
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((bn, X.shape[1]), lambda n: (n, 0)),
            pl.BlockSpec(landmarks.shape, lambda n: (0, 0)),
            pl.BlockSpec(proj.shape, lambda n: (0, 0)),
            pl.BlockSpec((bn, 1), lambda n: (n, 0)),
            pl.BlockSpec(Wmat.shape, lambda n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, Cp), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Cp), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(X, landmarks, proj, mask.reshape(Np, 1), Wmat)
    return out[:N, :C]


@functools.partial(jax.jit, static_argnames=("sigma", "kind", "add_bias",
                                             "epilogue", "eps", "eps_ins",
                                             "block_n", "col_blk",
                                             "interpret"))
def nystrom_fused_stats(X: jnp.ndarray, landmarks: jnp.ndarray,
                        proj: jnp.ndarray, rho: jnp.ndarray,
                        beta: jnp.ndarray, wvec: jnp.ndarray,
                        mask: jnp.ndarray | None = None,
                        noise: tuple | None = None,
                        col_start: jnp.ndarray | int | None = None,
                        seed: jnp.ndarray | None = None, *,
                        sigma: float = 1.0, kind: str = "rbf",
                        add_bias: bool = False,
                        epilogue: str = "em_hinge", eps: float = 1e-6,
                        eps_ins: float = 0.0,
                        block_n: int = 256, col_blk: int | None = None,
                        interpret: bool = False):
    """The whole phi-space iteration statistic in ONE X pass.

    Returns (margin (N,), *aug (N,) each, b (M,), S), all f32 —
    exactly ``fused_stats`` (same epilogue family: EM/MC hinge, SVR's
    double mixture) evaluated on phi = nystrom_phi(X, ...), except phi
    never leaves VMEM. S is (M, M), or the (M, col_blk) PHI-column
    block S[:, start:start+blk] under a ``(col_start, col_blk)`` window
    — the ``k_shard_axis`` x Nystrom composition: the phi tile is
    computed in-kernel against the full landmark strip and only the
    windowed phi columns feed the Sigma accumulator (static blk shapes
    the accumulator; the traced 128-aligned base rides in SMEM, exactly
    ``fused_stats``'s windowing). MC epilogues consume pre-drawn
    per-row ``noise`` operands like ``fused_stats`` does. Padded/masked
    rows contribute zero to b and S (phi row zeroed, and the Sigma
    weight is mask-scaled; the hinge coef is additionally zero at
    rho = beta = 0).
    """
    N, D = X.shape
    windowed = col_blk is not None
    assert windowed == (col_start is not None), (
        "col_start and col_blk must be given together")
    rng = seed is not None
    n_aug = epilogues.aug_arity(epilogue)
    noise = tuple(noise) if noise is not None else ()
    if rng:
        assert not noise, (
            "seed (in-kernel RNG) and pre-drawn noise operands are "
            "mutually exclusive")
        n_noise = 0
    else:
        n_noise = epilogues.noise_arity(epilogue)
        assert len(noise) == n_noise, (
            f"epilogue {epilogue!r} needs {n_noise} noise operands, "
            f"got {len(noise)}")
    bn = min(block_n, _round_up(N, 8))
    X, landmarks, proj, mask, Np, Wp, M = _pad_operands(
        X, landmarks, proj, mask, add_bias, bn)
    rho = jnp.pad(rho.astype(jnp.float32), (0, Np - N))
    beta = jnp.pad(beta.astype(jnp.float32), (0, Np - N))
    wvec = jnp.pad(wvec.astype(jnp.float32), (0, Wp - M))
    noise = tuple(jnp.pad(z.astype(jnp.float32), (0, Np - N))
                  for z in noise)

    extra_specs: list = []
    extra_ops: tuple = ()
    if rng:
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        extra_ops += (seed,)
    if windowed:
        Sw = col_window_geometry(Wp, col_blk)
        a0, off = aligned_window_base(col_start, Wp, Sw)
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        extra_ops += (a0.reshape(1),)
    else:
        Sw = Wp

    row_spec = pl.BlockSpec((bn, 1), lambda n: (n, 0))
    outs = pl.pallas_call(
        _make_fused_kernel(kind, 1.0 / (2.0 * float(sigma) ** 2),
                           M - 1 if add_bias else None, epilogue,
                           float(eps), float(eps_ins), n_noise, n_aug,
                           windowed, rng),
        grid=(Np // bn,),
        in_specs=extra_specs + [                            # [aligned base]
            pl.BlockSpec((bn, X.shape[1]), lambda n: (n, 0)),   # X rows
            pl.BlockSpec(landmarks.shape, lambda n: (0, 0)),    # strip
            pl.BlockSpec(proj.shape, lambda n: (0, 0)),         # K_mm^-1/2
            row_spec,                                           # mask
            row_spec,                                           # rho
            row_spec,                                           # beta
            pl.BlockSpec((Wp, 1), lambda n: (0, 0)),            # w
        ] + [row_spec] * n_noise,                               # noise
        out_specs=[row_spec]                                    # margin
        + [row_spec] * n_aug                                    # gamma(,omega)
        + [
            pl.BlockSpec((Wp, 1), lambda n: (0, 0)),            # b (revisit)
            pl.BlockSpec((Wp, Sw), lambda n: (0, 0)),           # S (revisit)
        ],
        out_shape=[jax.ShapeDtypeStruct((Np, 1), jnp.float32)]
        * (1 + n_aug)
        + [
            jax.ShapeDtypeStruct((Wp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Wp, Sw), jnp.float32),
        ],
        scratch_shapes=([pltpu.VMEM((bn, Wp), jnp.float32)] if windowed
                        else []),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*extra_ops, X, landmarks, proj, mask.reshape(Np, 1),
      rho.reshape(Np, 1), beta.reshape(Np, 1), wvec.reshape(Wp, 1),
      *(z.reshape(Np, 1) for z in noise))
    per_row, (b, S) = outs[:1 + n_aug], outs[-2:]
    if windowed:
        S = jax.lax.dynamic_slice(S[:M], (jnp.int32(0), off),
                                  (M, col_blk))
    else:
        S = S[:M, :M]
    return (*(v[:N, 0] for v in per_row), b[:M, 0], S)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
