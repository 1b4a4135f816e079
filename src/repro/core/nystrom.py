"""Nyström-approximated kernel SVM — answering the paper's open question.

Paper Sec 4.3 (KRN): "PSVM approximates the N by N kernel matrix with an
N by sqrt(N) matrix, and gets very good accuracy. Maybe there is a way to
do something similar with the sampling kernel SVM formulation?"

Yes — and it composes exactly with the augmentation. Pick m landmarks
(paper-suggested m = sqrt(N)); with K_mm the landmark Gram and K_nm the
cross-Gram, the Nyström feature map

    phi(x) = K_mm^{-1/2} k_m(x)      (m-dimensional)

satisfies phi(x)^T phi(x') ~= k(x, x'). Substituting w = sum_d a_d phi(x_d)
into the kernel problem (paper Eq. 12) turns the pseudo-prior
N(0, (lam K)^{-1}) into N(0, lam^{-1} I_m) in phi-space: the kernel SVM
becomes EXACTLY the linear PEMSVM on phi features. Every piece of the
parallel machinery then applies unchanged:

  * iteration cost falls from O(N^2[N/P + log N]) to O(m^2[N/P + log m])
    = O(N[N/P + ...]) at m = sqrt(N) — the cubic-in-N blocker the paper
    names is gone;
  * the map step is embarrassingly parallel over rows; the reduce is the
    familiar m x m triangle psum;
  * EM/MC x CLS/SVR/MLT all inherit the approximation for free, INCLUDING
    the drivers: ``NystromSVM`` delegates to the linear PEMSVM with
    ``config.phi_spec`` set, so ``driver="scan"`` (chunked on-device) and
    ``driver="stream"`` (out-of-core over RAW rows) both work — the
    nonlinear path inherits every hot-path optimization of the linear one.

Featurization happens ON DEVICE inside the statistic kernels
(``kernels/nystrom_phi.py``): the EM hot path fuses the RBF cross-Gram,
the K_mm^{-1/2} projection and the (margin, gamma, b, Sigma) accumulation
into one X sweep — the (N, m) phi matrix never exists in HBM, and the
stream driver's device residency is bounded by (prefetch + 2) raw D-wide
chunks regardless of m (DESIGN.md §Perf/Nystrom).

Host-side work is exactly two one-time O(m^2)-memory steps: landmark
selection (uniform; reservoir-sampled for out-of-core sources) and the
``K_mm^{-1/2}`` eigendecomposition with a spectral floor — cached on the
model, so prediction never refactorizes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from . import kernel as krn
from .linear import PhiSpec
from .solver import FitResult, PEMSVM, SVMConfig


def nystrom_projection(landmarks: np.ndarray, *, kind: str = "rbf",
                       sigma: float = 1.0, spectral_floor: float = 1e-6,
                       backend: str | None = None) -> np.ndarray:
    """K_mm^{-1/2} (m, m) float64 via one eigendecomposition.

    The spectral floor truncates near-null directions of the landmark
    Gram (rank deficiency from duplicate/near-duplicate landmarks) so
    the inverse square root stays bounded. This is the ONLY
    decomposition the Nyström path ever runs — fit computes it once and
    caches it; prediction reuses it.
    """
    return _inverse_sqrt(_landmark_gram(landmarks, kind, sigma, backend),
                         spectral_floor)[0]


def _landmark_gram(landmarks, kind: str, sigma: float,
                   backend: str | None) -> np.ndarray:
    """K_mm (m, m) float64, computed by the kernels backend in float32."""
    return np.asarray(krn.gram_matrix(
        jnp.asarray(landmarks), jnp.asarray(landmarks), kind=kind,
        sigma=sigma, backend=backend), np.float64)


def _inverse_sqrt(K_mm: np.ndarray,
                  spectral_floor: float) -> tuple[np.ndarray, int]:
    """(K_mm^{-1/2} over the eigenvalues above ``spectral_floor`` times
    the largest, how many eigenvalues that keeps)."""
    w, V = np.linalg.eigh(0.5 * (K_mm + K_mm.T))
    floor = spectral_floor * max(w.max(), 1e-30)
    keep = w > floor
    return (V[:, keep] / np.sqrt(w[keep])) @ V[:, keep].T, int(keep.sum())


def nystrom_features(X: np.ndarray, landmarks: np.ndarray, *,
                     kind: str = "rbf", sigma: float = 1.0,
                     spectral_floor: float = 1e-6,
                     backend: str | None = None) -> np.ndarray:
    """phi = K_nm @ K_mm^{-1/2}: (N, m) Nyström features.

    Host float64 featurization that MATERIALIZES phi — kept as the
    accuracy oracle and benchmark baseline; the fit path uses the
    on-device fused kernels instead (see module docstring)."""
    proj = nystrom_projection(landmarks, kind=kind, sigma=sigma,
                              spectral_floor=spectral_floor,
                              backend=backend)
    K_nm = np.asarray(krn.gram_matrix(
        jnp.asarray(X), jnp.asarray(landmarks), kind=kind, sigma=sigma,
        backend=backend), np.float64)
    return (K_nm @ proj).astype(np.float32)


class NystromSVM:
    """KRN-{EM,MC}-{CLS,SVR,MLT} via on-device Nyström featurization +
    the linear parallel solver. m defaults to ceil(sqrt(N)) per the
    paper's PSVM reference.

    Accepts any KRN ``SVMConfig`` — including ``driver="stream"`` (the
    out-of-core nonlinear fit; raw rows stream, phi never materializes)
    and the SVR/MLT tasks the exact Gram solver cannot serve.
    """

    def __init__(self, config: SVMConfig, n_landmarks: int | None = None,
                 mesh=None, data_axes=None, seed: int = 0,
                 spectral_floor: float = 1e-6):
        assert config.formulation == "KRN", "NystromSVM approximates KRN"
        self.config = config
        self.kernel_kind = config.kernel
        self.sigma = config.sigma
        self.n_landmarks = n_landmarks
        self.seed = seed
        self.spectral_floor = spectral_floor
        # Delegate to the LIN machinery in phi-space; lam carries over
        # because the phi-space pseudo-prior is lam^{-1} I exactly.
        # dataclasses.replace propagates EVERY config field (driver,
        # scan_chunk, chunk_rows, prefetch, jitter, k_shard_axis, and
        # whatever is added next) — only the three phi-mode fields are
        # overridden: the bias moves to phi-space (add_bias=False +
        # PhiSpec.add_bias=True; an X-space bias column would perturb
        # the RBF distances).
        lin_cfg = dataclasses.replace(
            config, formulation="LIN", add_bias=False,
            phi_spec=PhiSpec(sigma=config.sigma, kind=config.kernel,
                             add_bias=True))
        self.svm = PEMSVM(lin_cfg, mesh=mesh, data_axes=data_axes)
        self._landmarks: np.ndarray | None = None
        self._proj: np.ndarray | None = None

    # ------------------------------------------------------------ fitting
    def _install_featurizer(self, landmarks: np.ndarray) -> None:
        """The one-time host-side setup: cache the landmark strip and
        K_mm^{-1/2}, and hand both to the delegate's device path.
        ``eigh`` runs exactly once per fit; predict/score/
        decision_function reuse the cache. Span ``nystrom.projection``
        (landmarks, rank): K_mm, its eigendecomposition and the cast."""
        with TraceAnnotation("nystrom.projection",
                             landmarks=len(landmarks)) as span:
            self._landmarks = np.asarray(landmarks, np.float32)
            proj, rank = _inverse_sqrt(
                _landmark_gram(self._landmarks, self.kernel_kind,
                               self.sigma, self.svm.config.backend),
                self.spectral_floor)
            self._proj = proj.astype(np.float32)
            span.set_metadata(rank=rank)
        self.svm._phi_arrays = (self._landmarks, self._proj)

    @staticmethod
    def _continuing(fit_kw: dict) -> bool:
        """A resumed/warm-started fit must REUSE the featurizer that
        produced the checkpointed phi-space weights — re-drawing
        landmarks would silently change the feature map under them."""
        return (fit_kw.get("resume_from") is not None
                or fit_kw.get("warm_start") is not None)

    def fit(self, X: np.ndarray, y: np.ndarray, **fit_kw) -> FitResult:
        """``fit_kw`` forwards the elastic surface (resume_from /
        warm_start / fault_hook / ...) — see ``PEMSVM.fit``. Landmark
        selection is seed-deterministic, and is skipped entirely when
        continuing a fit whose featurizer is already installed. Span
        ``nystrom.landmarks`` (rows, landmarks): the float32 view of X
        and the seeded row draw."""
        landmarks = None
        with TraceAnnotation("nystrom.landmarks") as span:
            X = np.asarray(X, np.float32)
            N = X.shape[0]
            if not (self._continuing(fit_kw)
                    and self._landmarks is not None):
                m = self.n_landmarks or int(np.ceil(np.sqrt(N)))
                rng = np.random.default_rng(self.seed)
                landmarks = X[rng.choice(N, size=min(m, N), replace=False)]
            span.set_metadata(rows=N, landmarks=(
                0 if landmarks is None else len(landmarks)))
        if landmarks is not None:
            self._install_featurizer(landmarks)
        return self.svm.fit(X, y, **fit_kw)

    def fit_libsvm(self, path: str, n_features: int,
                   **fit_kw) -> FitResult:
        """Out-of-core nonlinear fit from a libsvm file.

        One reservoir-sampling pass picks the landmarks (O(m D) host
        memory), then the delegate streams RAW rows chunk by chunk —
        featurize-and-accumulate on device, so peak device input
        residency is (prefetch + 2) D-wide chunks and the dataset is
        never resident on host or device. ``fit_kw`` forwards the
        elastic surface; continuing a fit (resume/warm start) reuses
        the installed featurizer and skips the sampling pass."""
        from repro.data import iter_libsvm, reservoir_rows

        cfg = self.svm.config
        if not (self._continuing(fit_kw) and self._landmarks is not None):
            chunks = iter_libsvm(path, cfg.chunk_rows, n_features)
            if self.n_landmarks:
                landmarks, _ = reservoir_rows(chunks, self.n_landmarks,
                                              seed=self.seed)
            else:
                # m = ceil(sqrt(N)) needs N first: count on a cheap extra
                # pass (the file is re-read every iteration anyway).
                n_valid = sum(int(np.sum(np.asarray(mc) > 0))
                              for _, _, mc in chunks)
                m = int(np.ceil(np.sqrt(n_valid)))
                landmarks, _ = reservoir_rows(
                    iter_libsvm(path, cfg.chunk_rows, n_features), m,
                    seed=self.seed)
            self._install_featurizer(landmarks)
        return self.svm.fit_libsvm(path, n_features, **fit_kw)

    # ---------------------------------------------------------- inference
    def _phi(self, X: np.ndarray, add_bias: bool = False) -> np.ndarray:
        """(N, m [+1]) Nyström features from the CACHED projection (no
        eigendecomposition; host-precision oracle path).

        Feature order is PINNED to the device path
        (``kernels.ref.nystrom_phi`` / the fused kernels): the
        phi-space bias column, when requested, is appended LAST — after
        the projected features — and any zero-column padding would come
        after that (the delegate config forbids ``pad_features`` with
        ``phi_spec``, so phi width is landmark count + bias, exactly).
        ``tests/test_svm_serving.py`` holds the parity test."""
        assert self._proj is not None, "fit first"
        K_nm = np.asarray(krn.gram_matrix(
            jnp.asarray(np.asarray(X, np.float32)),
            jnp.asarray(self._landmarks), kind=self.kernel_kind,
            sigma=self.sigma, backend=self.svm.config.backend), np.float64)
        phi = (K_nm @ self._proj.astype(np.float64)).astype(np.float32)
        if add_bias:
            phi = np.concatenate(
                [phi, np.ones((phi.shape[0], 1), np.float32)], axis=1)
        return phi

    def export_servable(self, *, name: str = "svm",
                        posterior_from: tuple | None = None):
        """Freeze into a ``serving.ServableModel`` (fused Nystrom score
        cell; ``posterior_from=(X, y)`` adds the phi-space posterior
        uncertainty columns — exact here, since the phi-space prior is
        lam^{-1} I). See ``PEMSVM.export_servable``."""
        return self.svm.export_servable(name=name,
                                        posterior_from=posterior_from)

    def scorer(self):
        """Cached device-resident ``serving.SVMScorer`` (see
        ``PEMSVM.scorer``)."""
        return self.svm.scorer()

    def predict(self, X: np.ndarray) -> np.ndarray:
        return self.svm.predict(np.asarray(X, np.float32))

    def decision_function(self, X: np.ndarray) -> np.ndarray:
        return self.svm.decision_function(np.asarray(X, np.float32))

    def rmse(self, X: np.ndarray, y: np.ndarray) -> float:
        return self.svm.rmse(np.asarray(X, np.float32), y)

    def score(self, X: np.ndarray, y: np.ndarray) -> float:
        return self.svm.score(np.asarray(X, np.float32), y)
