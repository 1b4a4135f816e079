"""Backend-dispatching wrappers around the Pallas kernels.

Every op exists in three flavors:
  * ``ref``       — pure jnp oracle (ref.py); default on CPU hosts.
  * ``interpret`` — Pallas kernel executed by the interpreter (CPU
                    correctness validation of the real kernel body).
  * ``pallas``    — compiled Pallas TPU kernel; default on TPU.

``backend=None`` picks by ``jax.default_backend()``. The SVM solvers thread
a backend choice through so the same code serves tests (interpret), CPU
benchmarks (ref → XLA) and TPU production (pallas).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import epilogues
from . import fused_estep as _fused_estep
from . import fused_stats as _fused_stats
from . import nystrom_phi as _nystrom_phi
from . import rbf_gram as _rbf_gram
from . import ref
from . import syrk as _syrk
from . import weighted_gram as _weighted_gram

VALID_BACKENDS = ("ref", "interpret", "pallas")


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(backend: str | None) -> str:
    backend = backend or default_backend()
    if backend not in VALID_BACKENDS:
        raise ValueError(f"backend must be one of {VALID_BACKENDS}, got {backend!r}")
    return backend


def _check_noise(epilogue: str, noise: tuple | None,
                 seed=None) -> None:
    """Validate the noise configuration HERE, once, so every route —
    ref, kernel, K-tiled and VMEM fallbacks — fails with the same
    message instead of an opaque unpack error inside the epilogue.

    Exactly one noise source is allowed: pre-drawn (N,) operands
    (rng mode 'host'/'fused_predraw') or the in-kernel counter ``seed``
    (rng mode 'fused') — never both."""
    got = 0 if noise is None else len(noise)
    if seed is not None:
        if got:
            raise ValueError(
                f"rng='fused' derives the {epilogue!r} noise in-kernel "
                f"from the counter seed, but {got} pre-drawn noise= "
                "operand(s) (augment.draw_ig_noise) were passed as "
                "well — drop the noise= operands or set "
                "SVMConfig.rng='host' to stream pre-drawn noise")
        return
    want = epilogues.noise_arity(epilogue)
    if got != want:
        raise ValueError(
            f"epilogue {epilogue!r} needs {want} pre-drawn noise "
            f"operands (augment.draw_ig_noise), got {got} — or pass "
            "seed= (SVMConfig.rng='fused') to derive them in-kernel")


def weighted_gram(X: jnp.ndarray, w: jnp.ndarray, *,
                  backend: str | None = None, **kw) -> jnp.ndarray:
    """S = X^T diag(w) X, (K, K) f32."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.weighted_gram(X, w)
    return _weighted_gram.weighted_gram(
        X, w, interpret=(backend == "interpret"), **kw)


def syrk_tri(X: jnp.ndarray, w: jnp.ndarray, *, precision=None,
             backend: str | None = None, **kw) -> jnp.ndarray:
    """S = X^T diag(w) X computing only lower-triangle blocks (~2x fewer
    FLOPs than ``weighted_gram``); result is the full symmetric matrix."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.syrk_tri(X, w, precision)
    return _syrk.syrk_tri(X, w, precision=precision,
                          interpret=(backend == "interpret"), **kw)


def _ru(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# The TPU's lane width: ``fused_stats`` reads X tiles of
# ``round_up(K, LANE)`` columns, and the resident LIN path stores the
# model's X at that width (``distributed.upload_rows``).
LANE = 128

# The LIN kernels do not raise their VMEM limit, so each runs under the
# TPU compiler's default scoped limit (16 MiB on v5e); a working set
# past it is refused at compile time (RESOURCE_EXHAUSTED in vmem). The
# Nystrom kernels and ``rbf_gram`` raise theirs (``_NYSTROM_VMEM_BUDGET``).
_SCOPED_VMEM_BYTES = 16 * 2 ** 20


def _fused_stats_vmem_bytes(n_features: int, col_blk: int | None,
                            block_n: int, epilogue: str,
                            n_chains: int = 1, x_bytes: int = 4,
                            precision=None) -> int:
    """Upper bound on the scoped VMEM of one ``fused_stats`` grid step
    (DESIGN.md §Perf, "VMEM accounting"), as Pallas lays it out:

      * every pipelined block twice (double-buffered): the (bn, Kp) X
        tile, the (bn, 1) rho/beta/mask vectors and the (bn, C) margin
        and aug outputs, the (Kp, C) w and b blocks — each narrow block
        padded to 128 lanes;
      * the epilogue's noise vectors twice as well — streamed operands,
        or under the in-kernel RNG the same-shaped cipher temporaries;
      * the (Kp, C*Cw) Sigma accumulator once, plus one (bn, Kp) f32
        weighted-row temporary (and the f32 cast of a narrower X tile);
      * at a ``precision`` above the default, the dots' operands split
        into bf16 parts, 1.5 words an entry: the X tile, the weighted
        rows and the (bn, Cw) Sigma column block.

    Checked against the compiler's own figures at the boundary (the
    largest admitted shape compiles, ``tests/test_tpu_compile.py``)."""
    Kp = _ru(n_features, 128)
    Cw = Kp if col_blk is None else min(Kp, _ru(col_blk, 128) + 128)
    lanes = _ru(n_chains, 128)
    rows = (3 * 128 + epilogues.noise_arity(epilogue) * lanes
            + (1 + epilogues.aug_arity(epilogue)) * lanes)
    streamed = block_n * Kp * x_bytes + 4 * (block_n * rows
                                             + 2 * Kp * lanes)
    temps = 4 * block_n * Kp * (1 + (x_bytes < 4))
    if precision not in (None, jax.lax.Precision.DEFAULT):
        temps += 6 * block_n * (2 * Kp + Cw)
    return 2 * streamed + 4 * Kp * n_chains * Cw + temps


def fused_stats_fits(n_features: int, col_blk: int | None = None,
                     block_n: int = 512,
                     epilogue: str = "em_hinge",
                     n_chains: int = 1, x_bytes: int = 4,
                     precision=None) -> bool:
    """Whether the one-pass fused-statistic kernel fits the default
    scoped VMEM limit. A column window narrows the accumulator to
    (K, Cw), so K beyond the full-width cap can still fuse."""
    return _fused_stats_vmem_bytes(n_features, col_blk, block_n,
                                   epilogue, n_chains, x_bytes,
                                   precision) <= _SCOPED_VMEM_BYTES


# Largest full-width K (a lane multiple) the single-chain kernel takes
# at the default block: past it the dispatch uses the split fallback.
FUSED_STATS_MAX_K = max(k for k in range(128, 4096, 128)
                        if fused_stats_fits(k))


def fused_stats_width(n_features: int, *, backend: str | None = None,
                      epilogue: str = "em_hinge",
                      n_chains: int = 1) -> int:
    """The width to store an X of ``n_features`` columns at, so that
    ``fused_stats`` reads it as it is: the lane width round_up(K, LANE)
    where the call takes the full-width kernel, and K where it takes the
    jnp oracle or the split fallback, which read K columns."""
    if (_resolve(backend) == "ref"
            or not fused_stats_fits(n_features, epilogue=epilogue,
                                    n_chains=n_chains)):
        return n_features
    return _ru(n_features, LANE)


def fused_stats(X: jnp.ndarray, rho: jnp.ndarray, beta: jnp.ndarray,
                wvec: jnp.ndarray, wmask: jnp.ndarray | None = None,
                noise: tuple | None = None, *,
                epilogue: str = "em_hinge", eps: float = 1e-6,
                eps_ins: float = 0.0, col_window: tuple | None = None,
                seed: jnp.ndarray | None = None, precision=None,
                backend: str | None = None, **kw):
    """(margin, *aug, b, S): the whole iteration statistic in one X
    pass (single HBM stream instead of the split margin/b/Sigma
    passes), under any augmentation ``epilogue`` (``epilogues.py``):
    em_hinge/mc_hinge return (margin, gamma, b, S); the SVR double
    mixture returns (margin, gamma, omega, b, S). MC flavors consume
    pre-drawn per-row ``noise`` arrays (``augment.draw_ig_noise``) OR,
    when ``seed`` (the (4,) uint32 counter seed from ``rng.pack_seed``)
    is given, derive them in-kernel with zero extra operands (rng mode
    'fused'; mixing both sources is rejected).

    A 2-D (K, C) ``wvec`` with ``seed`` runs C Gibbs chains over the
    single X stream: margin/aug (N, C), b (K, C), S (C, K, K).

    ``col_window = (start, blk)`` narrows Sigma to its column block
    X^T diag(w) X[:, start:start+blk] — the 2-D (data x model)
    ``k_shard_axis`` statistic stays single-stream: ``blk`` is static,
    ``start`` may be traced (``axis_index * blk`` inside shard_map).

    When the working set exceeds the scoped VMEM limit
    (``fused_stats_fits``: full width past FUSED_STATS_MAX_K, fewer K
    for C chains or at a higher ``precision``, or a window too wide)
    the Pallas flavors fall back to the K-tiled split pair (E-step +
    syrk_tri; windowed: plain-XLA column block) rather than blow VMEM —
    callers get the same outputs either way.

    ``precision`` (a ``jax.lax.Precision``) is that of every dot of the
    statistic, on each path: None, the TPU's default single bf16 pass,
    for LIN; the Nystrom fallback asks for HIGHEST.

    The kernel also takes X stored at its lane width, round_up(K, LANE)
    columns with zeros past K, and reads it in place; the other routes
    take a K-wide X (``fused_stats_width`` says which to store)."""
    backend = _resolve(backend)
    _check_noise(epilogue, noise, seed)
    multi = wvec.ndim == 2
    n_chains = wvec.shape[1] if multi else 1
    if backend == "ref":
        return ref.fused_stats(X, rho, beta, wvec, wmask, eps,
                               epilogue=epilogue, noise=noise,
                               eps_ins=eps_ins, col_window=col_window,
                               seed=seed, precision=precision)
    fits = functools.partial(
        fused_stats_fits, X.shape[1], block_n=kw.get("block_n", 512),
        epilogue=epilogue, n_chains=n_chains,
        x_bytes=jnp.dtype(X.dtype).itemsize, precision=precision)
    if col_window is not None:
        start, blk = col_window
        if not fits(blk):
            # Windowed split fallback: the narrowed Sigma block is a
            # plain (weighted X)^T Xcols matmul XLA tiles itself —
            # the compute-bound regime where stream count stops being
            # the bound (the triangle SYRK does not apply to an
            # off-diagonal rectangular block).
            return ref.fused_stats(X, rho, beta, wvec, wmask, eps,
                                   epilogue=epilogue, noise=noise,
                                   eps_ins=eps_ins,
                                   col_window=col_window, seed=seed,
                                   precision=precision)
        return _fused_stats.fused_stats(
            X, rho, beta, wvec, wmask, noise, start, seed,
            epilogue=epilogue, eps=eps, eps_ins=eps_ins, col_blk=blk,
            precision=precision, interpret=(backend == "interpret"), **kw)
    if not fits(None):
        kw.pop("block_n", None)
        if multi:
            # Multichain past the VMEM cap: the C stacked Sigma blocks
            # are plain XLA matmuls (compute-bound regime).
            return ref.fused_stats(X, rho, beta, wvec, wmask, eps,
                                   epilogue=epilogue, noise=noise,
                                   eps_ins=eps_ins, seed=seed,
                                   precision=precision)
        # Split fallback: the O(NK) E-step (margin, aug, coef) runs as
        # plain XLA; only the O(NK^2) Sigma goes through the K-tiled
        # SYRK kernel, whose blocks fit VMEM at any K. 3 X streams —
        # the compute-bound regime where stream count stops being the
        # bound anyway.
        if seed is not None:
            noise = ref.seed_noise(seed, X.shape[0], 1, epilogue)
        Xf = X.astype(jnp.float32)
        margin = jnp.matmul(Xf, wvec.astype(jnp.float32),
                            precision=precision)
        aug, weight, coef = epilogues.apply_epilogue(
            epilogue, margin, rho.astype(jnp.float32),
            beta.astype(jnp.float32), noise, eps, eps_ins)
        w = weight if wmask is None else wmask.astype(jnp.float32) * weight
        b = jnp.matmul(Xf.T, coef, precision=precision)
        return (margin, *aug, b, syrk_tri(X, w, precision=precision,
                                          backend=backend))
    return _fused_stats.fused_stats(
        X, rho, beta, wvec, wmask, noise, None, seed,
        epilogue=epilogue, eps=eps, eps_ins=eps_ins, precision=precision,
        interpret=(backend == "interpret"), **kw)


def fused_estep(X: jnp.ndarray, rho: jnp.ndarray, beta: jnp.ndarray,
                wvec: jnp.ndarray, *, eps: float = 1e-6,
                backend: str | None = None, **kw):
    """(gamma, b): EM gamma update fused with the mu-numerator statistic."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.fused_estep(X, rho, beta, wvec, eps)
    return _fused_estep.fused_estep(
        X, rho, beta, wvec, eps=eps, interpret=(backend == "interpret"), **kw)


def rbf_gram(X1: jnp.ndarray, X2: jnp.ndarray, *, sigma: float = 1.0,
             backend: str | None = None, **kw) -> jnp.ndarray:
    """RBF Gram matrix (N1, N2) f32."""
    backend = _resolve(backend)
    if backend == "ref":
        return ref.rbf_gram(X1, X2, sigma)
    return _rbf_gram.rbf_gram(
        X1, X2, sigma=float(sigma), interpret=(backend == "interpret"), **kw)


# The fused Nystrom kernel holds the landmark strip, the projection, the
# phi tile AND the (M, M) Sigma accumulator in VMEM at once; past this
# landmark count (or the byte budget below, for wide D) it must not be
# attempted. The fallback — featurize (nystrom_phi) then accumulate
# (fused_stats, itself K-tiled past FUSED_STATS_MAX_K) — is the right
# regime anyway: at large m the statistic turns compute-bound and the
# fusion's HBM saving stops mattering (DESIGN.md §Perf/Nystrom).
NYSTROM_FUSED_MAX_M = 1024
# The Nystrom kernels raise their scoped VMEM limit to this figure.
_NYSTROM_VMEM_BUDGET = _rbf_gram.VMEM_LIMIT_BYTES


def _nystrom_vmem_words(n_landmarks: int, n_features: int, add_bias: bool,
                        block_n: int, with_stats: bool,
                        epilogue: str = "em_hinge",
                        col_blk: int | None = None,
                        rng: bool = False, score_cols: int = 0) -> int:
    """Upper bound on the f32 words of scoped VMEM one grid step of a
    Nystrom kernel holds (DESIGN.md §Perf/Nystrom accounting), padded
    dims (Lp landmarks, Dp features, Wp phi columns):

      * every block twice (double-buffered), each narrow one padded to
        128 lanes: the (bn, Dp) X tile, the mask, the (Lp, Dp) landmark
        strip and the (Lp, Wp) projection; then ``with_stats``: rho,
        beta, margin, the epilogue's aug outputs and pre-drawn noise
        (none under the in-kernel RNG), w, b and the (Wp, Cw) Sigma
        accumulator (a k-shard ``col_blk`` window narrows Cw); or
        ``score_cols``: the (Wp, Cp) weight block and the (bn, Cp)
        score tile; or else the (bn, Wp) phi tile written out;
      * the (bn, Lp) cross tile and the (bn, Wp) phi tile once, and with
        the statistic its weighted rows (and the phi tile staged for a
        window load);
      * the HIGHEST-precision dots' operands split into three bf16
        parts, 1.5 words an entry: X tile, strip, cross tile and
        projection, and with the statistic phi, its weighted rows and
        the Sigma column window.

    The compiler's own figure for the same shapes stays under this
    bound; the boundary the budget draws compiles
    (``tests/test_tpu_compile.py``)."""
    Lp = _ru(n_landmarks, 128)
    Dp = _ru(n_features, 128)
    Wp = _ru(n_landmarks + int(add_bias), 128)
    blocks = block_n * Dp + Lp * Dp + Lp * Wp + 128 * block_n    # X..mask
    temps = block_n * Lp + block_n * Wp                    # cross, phi
    split = block_n * Dp + Lp * Dp + block_n * Lp + Lp * Wp
    if with_stats:
        per_row = (3                                       # rho/beta/margin
                   + (0 if rng else epilogues.noise_arity(epilogue))
                   + epilogues.aug_arity(epilogue))
        Cw = Wp if col_blk is None else min(Wp, _ru(col_blk, 128) + 128)
        blocks += 128 * block_n * per_row + 2 * 128 * Wp + Wp * Cw
        temps += block_n * Wp * (1 + (col_blk is not None))
        split += 2 * block_n * Wp + block_n * Cw
    elif score_cols:
        Cp = _ru(score_cols, 128)
        blocks += Wp * Cp + block_n * Cp
    else:
        blocks += block_n * Wp
    return 2 * blocks + temps + 3 * split // 2


def nystrom_fused_fits(n_landmarks: int, n_features: int,
                       add_bias: bool = True, block_n: int = 256,
                       epilogue: str = "em_hinge",
                       col_blk: int | None = None,
                       rng: bool = False) -> bool:
    """Whether the one-pass featurize-and-accumulate kernel's working
    set fits the VMEM budget (epilogue-aware: MC/SVR flavors carry up
    to 6 extra per-row vectors — zero under the in-kernel RNG; a
    k-shard column window narrows the Sigma accumulator)."""
    if n_landmarks > NYSTROM_FUSED_MAX_M:
        return False
    return 4 * _nystrom_vmem_words(n_landmarks, n_features, add_bias,
                                   block_n, True, epilogue,
                                   col_blk, rng) <= _NYSTROM_VMEM_BUDGET


def _nystrom_phi_fits(n_landmarks: int, n_features: int,
                      add_bias: bool = True, block_n: int = 256) -> bool:
    """Featurize-only working set — no Sigma/b accumulators, so the phi
    kernel keeps serving shapes the fused budget rejects (e.g. wide D
    at m near the cap)."""
    if n_landmarks > NYSTROM_FUSED_MAX_M:
        return False
    return 4 * _nystrom_vmem_words(n_landmarks, n_features, add_bias,
                                   block_n, False) <= _NYSTROM_VMEM_BUDGET


def nystrom_phi(X: jnp.ndarray, landmarks: jnp.ndarray, proj: jnp.ndarray,
                mask: jnp.ndarray | None = None, *, sigma: float = 1.0,
                kind: str = "rbf", add_bias: bool = False,
                backend: str | None = None, **kw) -> jnp.ndarray:
    """Device-side Nystrom featurizer: phi = k(X, landmarks) @ proj with
    masked rows zeroed and an optional mask-valued bias column.

    (N, M) f32, M = proj.shape[1] + add_bias. One X stream, no (N, m)
    cross-Gram intermediate. Oversized landmark strips fall back to the
    jnp oracle (XLA tiles the matmuls itself)."""
    backend = _resolve(backend)
    if backend != "ref" and _nystrom_phi_fits(
            landmarks.shape[0], X.shape[1], add_bias,
            kw.get("block_n", 256)):
        return _nystrom_phi.nystrom_phi(
            X, landmarks, proj, mask, sigma=float(sigma), kind=kind,
            add_bias=add_bias, interpret=(backend == "interpret"), **kw)
    return ref.nystrom_phi(X, landmarks, proj, mask, float(sigma), kind,
                           add_bias)


def nystrom_score_fits(n_landmarks: int, n_features: int,
                       n_score_cols: int, add_bias: bool = False,
                       block_n: int = 256) -> bool:
    """Whether the fused scoring epilogue's working set fits VMEM: the
    featurize-only set with the resident (Wp, Cp) weight block and the
    (bn, Cp) score tile (serving's only HBM write) in place of the phi
    tile written out."""
    if n_landmarks > NYSTROM_FUSED_MAX_M:
        return False
    return 4 * _nystrom_vmem_words(
        n_landmarks, n_features, add_bias, block_n, False,
        score_cols=n_score_cols) <= _NYSTROM_VMEM_BUDGET


def nystrom_score(X: jnp.ndarray, landmarks: jnp.ndarray,
                  proj: jnp.ndarray, W: jnp.ndarray,
                  mask: jnp.ndarray | None = None, *,
                  sigma: float = 1.0, kind: str = "rbf",
                  add_bias: bool = False,
                  backend: str | None = None, **kw) -> jnp.ndarray:
    """(N, C) scores = nystrom_phi(X, ...) @ W in one fused pass — the
    predict-side epilogue: phi stays a per-row-block VMEM tile and dies
    after one MXU matmul against the resident (M, C) weight block, so
    serving never materializes the (N, M) feature matrix in HBM. C
    columns carry tenants/classes/uncertainty directions. Oversized
    working sets fall back to featurize-then-matmul (ref oracle)."""
    backend = _resolve(backend)
    if backend != "ref" and nystrom_score_fits(
            landmarks.shape[0], X.shape[1], W.shape[1], add_bias,
            kw.get("block_n", 256)):
        return _nystrom_phi.nystrom_score(
            X, landmarks, proj, W, mask, sigma=float(sigma), kind=kind,
            add_bias=add_bias, interpret=(backend == "interpret"), **kw)
    return ref.nystrom_score(X, landmarks, proj, W, mask, float(sigma),
                             kind, add_bias)


def nystrom_fused_stats(X: jnp.ndarray, landmarks: jnp.ndarray,
                        proj: jnp.ndarray, rho: jnp.ndarray,
                        beta: jnp.ndarray, wvec: jnp.ndarray,
                        mask: jnp.ndarray | None = None,
                        noise: tuple | None = None, *,
                        sigma: float = 1.0, kind: str = "rbf",
                        add_bias: bool = False,
                        epilogue: str = "em_hinge", eps: float = 1e-6,
                        eps_ins: float = 0.0,
                        col_window: tuple | None = None,
                        seed: jnp.ndarray | None = None,
                        backend: str | None = None, **kw):
    """(margin, *aug, b, S): the whole phi-space iteration statistic in
    one X pass — ``fused_stats`` (any augmentation epilogue: EM/MC
    hinge, SVR's double mixture) on nystrom_phi(X) with phi never
    leaving VMEM (so the (N, m) feature matrix never exists in HBM).
    ``col_window = (start, blk)`` narrows Sigma to a PHI-column block —
    the ``k_shard_axis`` x Nystrom composition, still one X stream (the
    phi tile is featurized in-kernel and only its windowed columns feed
    the accumulator).

    When the landmark strip + projection + Sigma accumulator (+ the
    epilogue's per-row noise/aug vectors) exceed the VMEM budget
    (``nystrom_fused_fits``), falls back to featurize-then-accumulate:
    nystrom_phi materializes phi for this row block and fused_stats
    (K-tiled past its own cap, window passed through) consumes it under
    the same epilogue — callers get the same outputs either way: both
    paths run every dot at HIGHEST (the statistic of RBF features, which
    are strongly correlated, moves the EM solution at one bf16 pass)."""
    backend = _resolve(backend)
    _check_noise(epilogue, noise, seed)
    if backend == "ref":
        return ref.nystrom_fused_stats(X, landmarks, proj, rho, beta,
                                       wvec, mask, float(sigma), kind,
                                       add_bias, eps, epilogue=epilogue,
                                       noise=noise, eps_ins=eps_ins,
                                       col_window=col_window, seed=seed)
    if not nystrom_fused_fits(landmarks.shape[0], X.shape[1], add_bias,
                              kw.get("block_n", 256), epilogue,
                              col_window[1] if col_window else None,
                              seed is not None):
        phi = nystrom_phi(X, landmarks, proj, mask, sigma=sigma, kind=kind,
                          add_bias=add_bias, backend=backend)
        return fused_stats(phi, rho, beta, wvec, mask, noise,
                           epilogue=epilogue, eps=eps, eps_ins=eps_ins,
                           col_window=col_window, seed=seed,
                           precision=_nystrom_phi.HIGHEST, backend=backend)
    if col_window is not None:
        start, blk = col_window
        return _nystrom_phi.nystrom_fused_stats(
            X, landmarks, proj, rho, beta, wvec, mask, noise, start,
            seed, sigma=float(sigma), kind=kind, add_bias=add_bias,
            epilogue=epilogue, eps=eps, eps_ins=eps_ins, col_blk=blk,
            interpret=(backend == "interpret"), **kw)
    return _nystrom_phi.nystrom_fused_stats(
        X, landmarks, proj, rho, beta, wvec, mask, noise, None, seed,
        sigma=float(sigma), kind=kind, add_bias=add_bias,
        epilogue=epilogue, eps=eps, eps_ins=eps_ins,
        interpret=(backend == "interpret"), **kw)
