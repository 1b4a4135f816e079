"""Pallas TPU kernel: the WHOLE per-iteration statistic in one X pass.

``fused_estep`` already fuses (margin, gamma, b); the Sigma statistic was
a second full pass over X (``weighted_gram``/``syrk_tri``). This kernel
emits every output of one iteration from a single ``pallas_call``:

    margin_d  = w^T x_d
    aug_d     = per-row augmentation update on the margin tile
                (an EPILOGUE from ``epilogues.py``: EM gamma, the MC
                inverse-Gaussian transform of pre-drawn (nu, u) noise,
                or SVR's double (gamma, omega) mixture — Eq. 9/5/25-28)
    b         = sum_d coef_d x_d                 (Eq. 6/28/39 numerator)
    S         = sum_d (m_d * weight_d) x_d x_d^T (Sigma^p, Table 9)

so X streams HBM->VMEM ONCE per iteration instead of two (EM) or three
(the pre-fusion MC/SVR paths: margin matmul, b matmul, SYRK) — on a
memory-bound statistic stream count IS iteration time (DESIGN.md
§Perf, §Perf/MC-SVR). ``m_d`` is an optional extra weight mask on the
Sigma weights only (the KRN path suppresses padded Gram rows with it;
LIN passes ones). MC epilogues consume pre-drawn per-row noise streamed
in as extra (N,) operands — O(N) bytes next to the N*K*4 X stream — so
the kernel stays PRNG-free and the draws stay bitwise identical to the
``augment.gamma_mc_rowwise`` oracle (see ``epilogues.py``).

Grid is 1-D over N-blocks; each step holds a (bn, K) X tile, the (K, 1)
weight vector and the full (K, K) fp32 Sigma accumulator in VMEM. With
Pallas' double-buffered blocks that bounds the usable K under the
default 16 MiB scoped VMEM: K <= 1024 at bn=512 by the accounting in
``ops.fused_stats_fits`` (the compiler itself takes 1152). Larger K uses
the split pair (tiled K). The SVM regime of the paper (K = 54..800
after bias) sits inside.

``col_start``/``col_blk`` switch Sigma to a COLUMN-WINDOWED output
S_blk = X^T diag(m*w) X[:, start:start+blk] — the 2-D (data x model)
``k_shard_axis`` statistic (DESIGN.md §Perf/k-shard): each model shard
accumulates only its (K, K/n) column block, margin/aug/b unchanged, so
the 2-D layout keeps the one-X-stream property. ``col_blk`` is static
(it shapes the accumulator); ``col_start`` is a TRACED scalar — inside
``shard_map`` it is ``axis_index * blk``, which no static argument can
express. The kernel therefore loads the window from the X tile's VMEM
ref at a 128-ALIGNED traced base (the scalar rides in SMEM; the TPU
kernel compiler lowers an aligned ``pl.ds`` ref load, not a dynamic
slice of a loaded value), over-fetching up to one lane-tile on each
side; the wrapper slices the exact [start, start+blk) columns out of
the aligned result.
The narrowed (K, Cw) accumulator is what lets K beyond the full-width
cap still fuse (``ops.fused_stats_fits``).

Unlike ``syrk_tri`` the Sigma accumulation here is a dense rank-bn
update: the triangle trick does not compose with single-pass streaming
(a triangle block grid must revisit X tiles per (i, j) pair, which is
exactly the second pass we are eliminating). Dense-SYRK FLOPs at a
third to half the HBM traffic vs half the FLOPs at full traffic — the
roofline in DESIGN.md §Perf says fused wins whenever the statistic is
memory-bound, i.e. precisely when N >> K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import epilogues


def _make_kernel(epilogue: str, eps: float, eps_ins: float,
                 n_noise: int, n_aug: int, windowed: bool = False,
                 rng: bool = False, n_chains: int = 1, precision=None):
    def _kernel(*refs):
        if rng:
            seed_ref, refs = refs[0], refs[1:]
        if windowed:
            c0_ref, refs = refs[0], refs[1:]
        x_ref, rho_ref, beta_ref, wmask_ref, w_ref = refs[:5]
        noise_refs = refs[5:5 + n_noise]
        outs = refs[5 + n_noise:]
        margin_ref, aug_refs = outs[0], outs[1:1 + n_aug]
        b_ref, s_ref = outs[-2], outs[-1]

        x = x_ref[...].astype(jnp.float32)          # (bn, K)
        wv = w_ref[...].astype(jnp.float32)         # (K, C)
        rho = rho_ref[...].astype(jnp.float32)      # (bn, 1)
        beta = beta_ref[...].astype(jnp.float32)    # (bn, 1)
        wmask = wmask_ref[...].astype(jnp.float32)  # (bn, 1)

        margin = jax.lax.dot_general(                # (bn, C) on the MXU
            x, wv, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        margin_ref[...] = margin
        if rng:                                      # in-kernel counter RNG
            noise = epilogues.fused_noise(
                seed_ref, pl.program_id(0) * x.shape[0], margin.shape,
                epilogue)
        else:                                        # pre-drawn operands
            noise = tuple(r[...].astype(jnp.float32) for r in noise_refs)
        aug, weight, coef = epilogues.apply_epilogue(
            epilogue, margin, rho, beta, noise, eps, eps_ins)
        for ref, a in zip(aug_refs, aug):
            ref[...] = a

        @pl.when(pl.program_id(0) == 0)
        def _init():
            b_ref[...] = jnp.zeros_like(b_ref)
            s_ref[...] = jnp.zeros_like(s_ref)

        b_ref[...] += jax.lax.dot_general(           # x^T coef: (K, C)
            x, coef, dimension_numbers=(((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        if windowed:                                 # aligned column window
            a0 = pl.multiple_of(c0_ref[0], 128)
            xc = x_ref[:, pl.ds(a0, s_ref.shape[1])].astype(jnp.float32)
        else:
            xc = x
        if n_chains == 1:
            xw = x * (wmask * weight)                # (bn, K) weighted rows
            s_ref[...] += jax.lax.dot_general(       # x^T diag(m*w) x[:, w]
                xw, xc, dimension_numbers=(((0,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
        else:
            # One Sigma block per chain, laid side by side in a 2-D
            # (Kp, C*Kp) accumulator: static per-chain column slices
            # keep every block 128-lane aligned without a 3-D BlockSpec.
            # The X tile is loaded ONCE; only the rank-bn updates (pure
            # MXU work) scale with C — that is the nearly-free-chains
            # claim.
            cw = s_ref.shape[1] // n_chains
            for c in range(n_chains):
                xw = x * (wmask * weight[:, c:c + 1])
                s_ref[:, c * cw:(c + 1) * cw] += jax.lax.dot_general(
                    xw, xc, dimension_numbers=(((0,), (0,)), ((), ())),
                    precision=precision,
                    preferred_element_type=jnp.float32)
    return _kernel


def col_window_geometry(Kp: int, col_blk: int) -> int:
    """Width of the ALIGNED in-kernel column window: the requested blk
    rounded to lanes plus one extra lane-tile of slack so any unaligned
    traced start lands inside a 128-aligned slice, capped at the padded
    width (then the 'window' is just the full accumulator)."""
    return min(Kp, _round_up(col_blk, 128) + 128)


def aligned_window_base(col_start, Kp: int, Cw: int):
    """(a0, off): 128-aligned traced base covering [start, start+blk)
    within [0, Kp - Cw], and the offset of ``col_start`` inside it."""
    c0 = jnp.asarray(col_start, jnp.int32)
    a0 = jnp.clip((c0 // 128) * 128, 0, Kp - Cw)
    return a0, c0 - a0


@functools.partial(jax.jit,
                   static_argnames=("epilogue", "eps", "eps_ins",
                                    "block_n", "col_blk", "precision",
                                    "interpret"))
def fused_stats(X: jnp.ndarray, rho: jnp.ndarray, beta: jnp.ndarray,
                wvec: jnp.ndarray, wmask: jnp.ndarray | None = None,
                noise: tuple | None = None,
                col_start: jnp.ndarray | int | None = None,
                seed: jnp.ndarray | None = None, *,
                epilogue: str = "em_hinge", eps: float = 1e-6,
                eps_ins: float = 0.0, block_n: int = 512,
                col_blk: int | None = None, precision=None,
                interpret: bool = False):
    """Returns (margin (N,), *aug (N,) each, b (K,), S), all f32 — aug
    is (gamma,) for the hinge epilogues, (gamma, omega) for SVR. S is
    (K, K), or the (K, col_blk) column block S[:, start:start+blk]
    when a ``(col_start, col_blk)`` window is given (module docstring:
    static blk shapes the accumulator, traced start rides in SMEM).

    X: (N, K), or (N, Kp) with Kp = ``round_up(K, 128)``: an X stored
    at the kernel's lane width (zero columns past K, as
    ``distributed.upload_rows`` builds the model's X) is read in place
    and only w is padded, where a K-wide X is padded to Kp on every
    call (scope ``xpad``). rho/beta/wmask: (N,); wvec: (K,), its length
    is the model width K; noise: ``noise_arity`` pre-drawn (N,) arrays
    for the MC epilogues (see ``epilogues.py``).
    ``seed`` (a (4,) uint32 [k0, k1, row0, chain0] from
    ``rng.pack_seed``) switches the MC epilogues to the IN-KERNEL
    counter RNG: no noise operands enter the kernel at all, the (nu, u)
    streams are derived per (global row, chain) inside the body and are
    bitwise equal to ``rng.draw_fused_noise`` — so the whole draw is
    chunk/shard/mesh-invariant with ZERO extra HBM traffic.

    A 2-D ``wvec`` of shape (K, C) runs C Gibbs chains over the single
    X stream (requires ``seed``; incompatible with a column window):
    margin/aug become (N, C), b becomes (K, C) and S becomes (C, K, K)
    — the X tile is read once and only MXU work scales with C.
    Zero-padded rows carry rho = beta = 0 so the hinge coef is exactly
    0, and their X-row is 0 so the b/S contributions vanish regardless
    of the augmentation values (SVR's MC coef is nonzero on padded rows
    — the zero X-row alone makes it a no-op).

    ``precision`` (a ``jax.lax.Precision``) is that of the margin, b and
    Sigma dots; None is the TPU's default, one bf16 pass.
    """
    N, Kx = X.shape
    K = wvec.shape[0]
    multi = wvec.ndim == 2
    C = wvec.shape[1] if multi else 1
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
    assert not (multi and windowed), (
        "multichain fused_stats does not compose with a column window")
    assert not multi or rng, (
        "multichain fused_stats requires the in-kernel RNG seed")
    if wmask is None:
        wmask = jnp.ones((N,), jnp.float32)
    bn = min(block_n, _round_up(N, 8))
    Kp = _round_up(K, 128)
    assert Kx in (K, Kp), (
        f"X is {Kx} wide: the model width {K} or its lane width {Kp}")
    Np = _round_up(N, bn)
    if (Np, Kp) != (N, Kx):
        with jax.named_scope("xpad"):
            X = jnp.pad(X, ((0, Np - N), (0, Kp - Kx)))
            rho = jnp.pad(rho, (0, Np - N))
            beta = jnp.pad(beta, (0, Np - N))
            wmask = jnp.pad(wmask, (0, Np - N))
            noise = tuple(jnp.pad(z, (0, Np - N)) for z in noise)
    if Kp != K:
        wvec = (jnp.pad(wvec, ((0, Kp - K), (0, 0))) if multi
                else jnp.pad(wvec, (0, Kp - K)))

    extra_specs: list = []
    extra_ops: tuple = ()
    if rng:
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        extra_ops += (seed,)
    if windowed:
        Sw = col_window_geometry(Kp, col_blk)
        a0, off = aligned_window_base(col_start, Kp, Sw)
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        extra_ops += (a0.reshape(1),)
    else:
        Sw = Kp

    grid = (Np // bn,)
    row_spec = pl.BlockSpec((bn, 1), lambda n: (n, 0))
    chn_spec = pl.BlockSpec((bn, C), lambda n: (n, 0))
    outs = pl.pallas_call(
        _make_kernel(epilogue, float(eps), float(eps_ins), n_noise,
                     n_aug, windowed, rng, C, precision),
        grid=grid,
        in_specs=extra_specs + [                        # [seed] [base]
            pl.BlockSpec((bn, Kp), lambda n: (n, 0)),   # X rows
            row_spec,                                   # rho
            row_spec,                                   # beta
            row_spec,                                   # Sigma weight mask
            pl.BlockSpec((Kp, C), lambda n: (0, 0)),    # w (replicated)
        ] + [row_spec] * n_noise,                       # pre-drawn noise
        out_specs=[chn_spec]                            # margin
        + [chn_spec] * n_aug                            # gamma (, omega)
        + [
            pl.BlockSpec((Kp, C), lambda n: (0, 0)),    # b (revisited)
            pl.BlockSpec((Kp, C * Sw), lambda n: (0, 0)),  # S (revisited)
        ],
        out_shape=[jax.ShapeDtypeStruct((Np, C), jnp.float32)]
        * (1 + n_aug)
        + [
            jax.ShapeDtypeStruct((Kp, C), jnp.float32),
            jax.ShapeDtypeStruct((Kp, C * Sw), jnp.float32),
        ],
        interpret=interpret,
        name="fused_stats",
    )(*extra_ops, X, rho.reshape(Np, 1), beta.reshape(Np, 1),
      wmask.reshape(Np, 1),
      wvec.reshape(Kp, C),
      *(z.reshape(Np, 1) for z in noise))
    per_row, (b, S) = outs[:1 + n_aug], outs[-2:]
    if windowed:
        S = jax.lax.dynamic_slice(S[:K], (jnp.int32(0), off),
                                  (K, col_blk))
    elif multi:
        S = jnp.stack([S[:K, c * Kp:c * Kp + K] for c in range(C)])
    else:
        S = S[:K, :K]
    if multi:
        return (*(v[:N] for v in per_row), b[:K], S)
    return (*(v[:N, 0] for v in per_row), b[:K, 0], S)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
