"""PEMSVM driver: the paper's solver facade.

Option axes exactly as paper Sec 4.2 — formulation LIN|KRN, algorithm
EM|MC, task CLS|MLT|SVR — addressable as option strings like "LIN-EM-CLS".

Implements the paper's run protocol:
  * objective evaluated every iteration; stop when the iterative change
    falls to tol*N (Sec 5.5, tol = 0.001),
  * gamma clamping for support vectors (Sec 5.7.3),
  * MC posterior averaging with a burn-in (Sec 5.13): the reported weight
    is the running average of samples after ``burnin`` iterations,
  * bias absorbed as a fixed unit feature (Sec 2.1).

With ``mesh`` given, data is row-sharded over the mesh's data axes and every
iteration is one SPMD step (map -> psum -> replicated solve), the Fig. 1
architecture. Without a mesh it runs the identical code single-device.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.kernels import ops
from repro.runtime.policy import FaultPolicy, StragglerError
from repro.runtime.straggler import StepTimeMonitor

from . import (distributed, kernel as krn, linear, multiclass, objective,
               resume as resume_mod, stats, svr)
from .linear import PhiSpec, SVMData

FORMULATIONS = ("LIN", "KRN")
ALGORITHMS = ("EM", "MC")
TASKS = ("CLS", "MLT", "SVR")


def lam_from_C(C: float) -> float:
    """Paper Eq. 1: min 1/2 lam ||w||^2 + 2 sum xi  <=>  C = 2/lam."""
    return 2.0 / C


@dataclasses.dataclass(frozen=True)
class SVMConfig:
    formulation: str = "LIN"
    algorithm: str = "EM"
    task: str = "CLS"
    lam: float = 1.0
    eps: float = 1e-6            # gamma clamp (paper Sec 5.7.3)
    eps_ins: float = 1e-3        # SVR precision (paper Sec 3.2 footnote)
    num_classes: int = 2
    kernel: str = "rbf"
    sigma: float = 1.0
    max_iters: int = 200
    min_iters: int = 10          # guard against flat-start plateaus
    patience: int = 1            # consecutive small-change iters required
    tol: float = 1e-3            # stop at |delta obj| <= tol * N (Sec 5.5)
    driver: str = "scan"         # scan = chunked on-device lax.scan driver
    scan_chunk: int = 16         # device iterations per host sync
    chunk_rows: int = 4096       # stream driver: rows device-resident at once
    prefetch: int = 2            # stream driver: host->device lookahead depth
    burnin: int = 10             # MC burn-in (Sec 5.13)
    jitter: float | None = None  # None -> 1e-7 (LIN), 1e-4 (KRN fp32 Gram)
    triangle_reduce: bool = True
    reduce_dtype: str | None = None  # 'bfloat16' = compressed reduction
    backend: str | None = None   # kernels backend: ref | interpret | pallas
    add_bias: bool = True
    seed: int = 0
    k_shard_axis: str | None = None  # beyond-paper 2-D Sigma statistic
    pad_features: int | None = None  # zero-pad LIN width to a multiple
    phi_spec: PhiSpec | None = None  # Nystrom phi-space mode (NystromSVM)
    fault: FaultPolicy | None = None  # checkpoint/retry/straggler policy
    decay: float = 0.0           # warm-start statistic decay (stream only)
    window: int = 0              # hard-expiry statistics horizon in fit
                                 # generations (stream only; 0 = off) —
                                 # the ring-of-partials alternative to
                                 # decay (stats.StatsWindow)
    rng: str = "host"            # MC noise source: host pre-draw |
                                 # fused (in-kernel counter cipher) |
                                 # fused_predraw (counter stream fed
                                 # through the operand path — the
                                 # bitwise oracle for 'fused')
    n_chains: int = 1            # parallel Gibbs chains over one X
                                 # stream (rng='fused', CLS/SVR LIN)
    chain0: int = 0              # first chain id (counter plane offset)

    def __post_init__(self):
        assert self.formulation in FORMULATIONS, self.formulation
        assert self.algorithm in ALGORITHMS, self.algorithm
        assert self.task in TASKS, self.task
        assert self.driver in ("scan", "loop", "stream"), self.driver
        assert self.scan_chunk >= 1, self.scan_chunk
        assert self.rng in ("host", "fused", "fused_predraw"), self.rng
        assert self.n_chains >= 1, self.n_chains
        assert self.chain0 >= 0, self.chain0
        if self.rng != "host":
            # The counter modes replace the MC Gibbs draws; EM has no
            # draws. The exact-Gram KRN step has no counter plumbing,
            # but a KRN config is also the user-facing surface of
            # NystromSVM (which replaces it with a LIN + phi_spec
            # delegate), so the formulation check lives in
            # PEMSVM.__init__ where only real exact-Gram fits land.
            assert self.algorithm == "MC", (
                f"rng={self.rng!r} selects the MC noise source; "
                "algorithm='EM' draws no noise")
        if self.n_chains > 1:
            # Multichain = C counter planes over one X stream: only the
            # in-kernel counter can address them (the operand paths
            # carry one (N,) stream), and the multichain kernel is the
            # full-width linear CLS/SVR statistic.
            assert self.rng == "fused", (
                "n_chains > 1 requires rng='fused' (the per-chain noise "
                "is derived in-kernel from the chain counter plane)")
            assert self.task in ("CLS", "SVR"), (
                "n_chains > 1 covers CLS/SVR; MLT's class sweep is one "
                "chain (run separate fits with distinct chain0 instead)")
            assert self.phi_spec is None, (
                "n_chains > 1 is the LIN X-space multichain kernel; "
                "the Nystrom phi route is single-chain")
            assert self.k_shard_axis is None, (
                "n_chains > 1 does not compose with the 2-D column-"
                "windowed statistic; drop k_shard_axis")
        # pad_features targets the LIN X-space statistic width (the
        # k_shard divisibility helper); phi-space width is the landmark
        # count + bias, which the user picks directly.
        assert self.pad_features is None or (
            self.pad_features >= 1 and self.phi_spec is None
            and self.formulation == "LIN"), self.pad_features
        assert self.chunk_rows >= 1, self.chunk_rows
        assert self.prefetch >= 1, self.prefetch  # residency = prefetch+2
        # decay re-weights ACCUMULATED statistics between fits — only the
        # stream driver keeps the summed (S, b) on the host-visible path
        # where the frozen previous-fit statistic can be folded in.
        assert 0.0 <= self.decay < 1.0, self.decay
        assert self.decay == 0.0 or self.driver == "stream", (
            "decay (online warm-start statistics) requires "
            "driver='stream'")
        # window is decay's hard-expiry sibling: a ring of the last
        # window-1 generations' FRESH (S, b) partials summed at full
        # weight, older generations dropped exactly. Same stream-only
        # constraint, and the two semantics are mutually exclusive.
        assert self.window >= 0, self.window
        assert self.window == 0 or self.driver == "stream", (
            "window (hard-expiry warm-start statistics) requires "
            "driver='stream'")
        assert self.window == 0 or self.decay == 0.0, (
            "window and decay are competing warm-start semantics "
            "(hard expiry vs geometric); pick one")
        # KRN x {SVR, MLT, stream} is valid CONFIGURATION now: NystromSVM
        # serves all of it through the phi-space route. Only the exact
        # N x N-Gram solver (PEMSVM) rejects those combinations, at fit
        # time — see PEMSVM._prepare / fit.
        if self.phi_spec is not None:
            assert self.formulation == "LIN", (
                "phi_spec is the LIN-delegate mode NystromSVM builds; "
                "construct a KRN config and wrap it in NystromSVM")
            assert not self.add_bias, (
                "phi_spec carries its own phi-space bias column; "
                "X-space add_bias must be False (a bias feature would "
                "perturb the RBF distances)")
        if self.jitter is None:
            object.__setattr__(
                self, "jitter",
                1e-4 if self.formulation == "KRN" else 1e-7)

    @classmethod
    def from_options(cls, options: str, **kw) -> "SVMConfig":
        f, a, t = options.upper().split("-")
        return cls(formulation=f, algorithm=a, task=t, **kw)

    @property
    def options(self) -> str:
        return f"{self.formulation}-{self.algorithm}-{self.task}"


@dataclasses.dataclass
class FitResult:
    weights: np.ndarray             # averaged weights (MC) / final (EM)
    last_sample: np.ndarray
    objective: list
    aux_history: dict
    n_iters: int
    converged: bool
    n_host_syncs: int = 0           # device->host objective transfers
    peak_input_bytes: int = 0       # stream driver: max device-resident input
    stats: dict | None = None       # effective (S, b) at the final M-step
    #                                 (stream driver with decay > 0 or
    #                                 window >= 1) — feed back via
    #                                 fit(warm_start=result)
    straggler_events: list = dataclasses.field(default_factory=list)
    resumed_at: int | None = None   # completed iterations restored from
    #                                 checkpoint (None = fresh fit)
    n_checkpoints: int = 0          # snapshots committed during this fit
    stats_window: list | None = None  # hard-expiry ring for the NEXT
    #                                 generation (stream, window >= 1):
    #                                 this fit's fresh (S, b) plus the
    #                                 retained donors, newest first
    loader_retries: int = 0         # transient loader failures absorbed
    #                                 by retrying_chunks during this fit
    loader_backoff_s: float = 0.0   # seconds slept backing those off
    chain_weights: np.ndarray | None = None  # (C, K) per-chain posterior
    #                                 means (n_chains > 1) — ``weights``
    #                                 is their cross-chain mean
    chain_std: np.ndarray | None = None      # (K,) cross-chain std
    #                                 (ddof=1) of the per-chain means


@functools.lru_cache(maxsize=256)
def _build_step_fn(cfg: SVMConfig, mesh: Mesh | None,
                   data_axes: tuple, has_prior: bool,
                   has_live: bool = False):
    """One-iteration step function for (config, mesh). Module-level and
    lru-cached so the jit/scan caches are shared across PEMSVM instances
    with identical configuration (SVMConfig is frozen, hence hashable).

    ``has_live`` appends a trailing liveness-vector operand (mesh path
    only): each data shard's 0/1 weight, renormalizing the reductions
    around dropped replicas (``stats.preduce``); all-ones is bitwise the
    plain psum, so the mesh drivers thread it unconditionally.
    """
    axes = data_axes if mesh is not None else ()
    common = dict(mode=cfg.algorithm, lam=cfg.lam, eps=cfg.eps,
                  jitter=cfg.jitter, axes=tuple(axes),
                  triangle=cfg.triangle_reduce, backend=cfg.backend,
                  reduce_dtype=cfg.reduce_dtype)

    def _live(rest):
        return rest[0] if rest else None

    if cfg.formulation == "KRN":
        # The exact-Gram step keeps the host draw; the config rejects
        # rng != 'host' there.
        def step(data, prior, state, key, *rest):
            return krn.krn_step(data, prior, state, key,
                                live=_live(rest), **common)
    else:
        task_step, task_kw = {
            "CLS": (linear.cls_step, dict(n_chains=cfg.n_chains)),
            "SVR": (svr.svr_step, dict(eps_ins=cfg.eps_ins,
                                       n_chains=cfg.n_chains)),
            "MLT": (multiclass.mlt_step,
                    dict(num_classes=cfg.num_classes)),
        }[cfg.task]
        common.update(task_kw, rng=cfg.rng, chain0=cfg.chain0,
                      k_shard_axis=cfg.k_shard_axis,
                      phi_spec=cfg.phi_spec)
        if cfg.phi_spec is None:
            def step(data, state, key, *rest):
                return task_step(data, state, key, live=_live(rest),
                                 **common)
        else:
            # Nystrom phi-space: the featurizer arrays (landmarks,
            # K_mm^{-1/2}) ride the replicated ``prior`` slot — the
            # same plumbing the exact-KRN Gram prior uses — so the scan
            # driver and shard_wrap carry them without a second
            # mechanism.
            def step(data, prior, state, key, *rest):
                return task_step(data, state, key, phi=prior,
                                 live=_live(rest), **common)

    if mesh is None:
        return step
    state_spec = (P(None, None) if cfg.task == "MLT" or cfg.n_chains > 1
                  else P(None))
    prior_spec = ((P(None, None), P(None, None))
                  if cfg.phi_spec is not None else P(None, None))
    return distributed.shard_wrap(mesh, data_axes, step,
                                  state_spec=state_spec,
                                  has_prior=has_prior,
                                  prior_spec=prior_spec,
                                  has_live=has_live)


@functools.lru_cache(maxsize=256)
def _chunk_runner(cfg: SVMConfig, mesh: Mesh | None, data_axes: tuple,
                  has_prior: bool, has_live: bool = False):
    """Jitted scan-of-steps chunk runner for the scan driver.

    Runs len(its) iterations fully on device, carrying the MC sample
    sum and the Sec 5.5 objective-change stopping statistic in scan
    state, and stacking the per-iteration aux dict as the trace.
    lru-cached (jit caches key on function identity) so same-config
    fits never retrace.
    """
    step = _build_step_fn(cfg, mesh, data_axes, has_prior, has_live)
    is_mc = cfg.algorithm == "MC"

    def body(operands, carry, it):
        data, prior, tol_n, live = operands
        (state, samp_sum, n_avg, key, prev_obj, n_small, done,
         it_done) = carry
        key, sub = jax.random.split(key)
        args = (data, prior, state, sub) if has_prior else (
            data, state, sub)
        if has_live:
            args = args + (live,)
        new_state, aux = step(*args)
        obj = aux["objective"]
        # Freeze every statistic once converged; the loop driver would
        # have stopped here, so later iterations are exact no-ops.
        state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(done, old, new), new_state, state)
        take = jnp.logical_and(~done, is_mc & (it > cfg.burnin))
        n_avg_new = n_avg + take.astype(jnp.int32)
        # Per-chunk fp32 sample sum; the host zeroes it between chunks
        # and combines the chunk sums in float64 (see _fit_scan).
        samp_sum = jnp.where(take, samp_sum + new_state, samp_sum)
        # Paper Sec 5.5 stopping rule on the objective change
        # (patience > 1 hardens it against flat starts / MC noise,
        # cf. the paper's multiple-local-minima caveat in 5.13).
        small = jnp.abs(obj - prev_obj) <= tol_n
        n_small = jnp.where(done, n_small,
                            jnp.where(small, n_small + 1, 0))
        conv_now = jnp.logical_and(
            ~done,
            (it >= cfg.min_iters) & (n_small >= cfg.patience)
            & ((not is_mc) | (n_avg_new >= 1)))
        it_done = jnp.where(conv_now, it, it_done)
        prev_obj = jnp.where(done, prev_obj, obj)
        carry = (state, samp_sum, n_avg_new, key, prev_obj, n_small,
                 done | conv_now, it_done)
        return carry, aux

    def runner(data, prior, carry, its, tol_n, live=None):
        return jax.lax.scan(
            functools.partial(body, (data, prior, tol_n, live)), carry,
            its)

    return jax.jit(runner)


@functools.lru_cache(maxsize=256)
def _stream_fns(cfg: SVMConfig):
    """Jitted per-chunk accumulators + replicated M-step for the stream
    driver. lru-cached on the frozen config so repeated fits share jit
    caches; shapes fixed by chunk_rows mean ONE trace per dataset width.

    Contract: ``chunk`` maps one (chunk_rows, K) block to a dict of
    row-additive contributions; ``add`` tree-sums them; ``mstep`` is
    ``stats.m_step``, the resident steps' M-step, on the summed
    statistics.
    For MLT, ``chunk``/``mstep`` additionally take the traced class
    index (one solve per class per sweep) and ``obj`` scores the
    end-of-sweep W on one block.

    Every chunk/obj fn takes a trailing ``phi`` operand — None for LIN,
    the (landmarks, projection) pair for the Nystrom phi-space route,
    in which case the chunk featurizes ON DEVICE and the raw D-wide
    rows are all that ever crosses host->device.
    """
    common = dict(mode=cfg.algorithm, eps=cfg.eps, backend=cfg.backend,
                  phi_spec=cfg.phi_spec)
    mkw = dict(mode=cfg.algorithm, lam=cfg.lam, jitter=cfg.jitter,
               rng=cfg.rng, chain0=cfg.chain0)
    add = jax.jit(functools.partial(jax.tree_util.tree_map, jnp.add))

    if cfg.task == "MLT":
        @jax.jit
        def chunk(data, W, key, row0, y_cls, phi):
            return multiclass.mlt_class_chunk_stats(
                data, W, key, row0, y_cls,
                num_classes=cfg.num_classes, phi=phi, **common,
                rng=cfg.rng, chain0=cfg.chain0)

        @jax.jit
        def mstep(W, S, b, key, y_cls):
            return W.at[y_cls].set(stats.m_step(
                S, b, jax.random.fold_in(key, y_cls), **mkw))

        @jax.jit
        def obj(data, W, phi):
            return multiclass.mlt_chunk_obj(data, W, phi, cfg.phi_spec,
                                            cfg.backend)

        @jax.jit
        def obj_total(W, loss_sum):
            return objective.l2_reg(W, cfg.lam) + loss_sum

        return dict(chunk=chunk, add=add, mstep=mstep, obj=obj,
                    obj_total=obj_total)

    chains = dict(rng=cfg.rng, n_chains=cfg.n_chains, chain0=cfg.chain0)
    task_chunk = (functools.partial(svr.svr_chunk_stats, eps_ins=cfg.eps_ins)
                  if cfg.task == "SVR" else linear.cls_chunk_stats)

    @jax.jit
    def chunk(data, w, key, row0, phi):
        # Each chunk's chain sums become chain means before the chunks
        # are summed (the resident step averages after its psum).
        return {k: v if k in ("S", "b") else stats.chain_mean(
                    v, cfg.n_chains)
                for k, v in task_chunk(data, w, key, row0, phi=phi,
                                       **common, **chains).items()}

    @jax.jit
    def mstep(S, b, loss_sum, key):
        # The chunk loss is already the cross-chain mean.
        w_new = stats.m_step(S, b, key, n_chains=cfg.n_chains, **mkw)
        return w_new, (stats.chain_mean(objective.l2_reg(w_new, cfg.lam),
                                        cfg.n_chains) + loss_sum)

    return dict(chunk=chunk, add=add, mstep=mstep)


class _FitRuntime:
    """Per-fit reliability state (DESIGN.md §Reliability): fault policy,
    checkpointer, straggler monitor, the restored resume payload, the
    per-shard liveness vector, and the host loop's scalar state — owned
    HERE (not in loop locals) so the stream driver's mid-pass saver sees
    a consistent snapshot of iteration counters and histories.
    """

    def __init__(self, svm: "PEMSVM", resume_from, resume_step,
                 warm_start, live, fault_hook, epoch: int | None = None):
        cfg = svm.config
        self.svm = svm
        self.policy = cfg.fault or FaultPolicy()
        self.monitor = StepTimeMonitor.from_policy(self.policy)
        self.hook = fault_hook
        self.events: list = []
        self.n_checkpoints = 0
        self.last_saved_it = 0
        self.resumed_at: int | None = None
        self.midpass: dict | None = None
        self.pending_sub = None
        self.cur_it = 0
        from repro.data.pipeline import RetryStats
        self.retry_stats = RetryStats()

        if resume_from is not None and warm_start is not None:
            raise ValueError(
                "resume_from (continue THIS fit from its checkpoint) and "
                "warm_start (start a NEW fit from a finished model) are "
                "mutually exclusive")
        if resume_step is not None and resume_from is None:
            raise ValueError("resume_step without resume_from")

        # ``epoch`` is the attempt's fence token (minted by an outer
        # controller / lease takeover): the writer advances the shared
        # FENCE at open — raising FencedWriterError if this attempt is
        # already superseded — and every commit re-checks it at the
        # rename boundary, so an abandoned zombie attempt can never
        # land a stale snapshot over its successor's line. None keeps
        # the legacy unfenced single-writer behavior.
        self.epoch = epoch
        self.ckpt = (Checkpointer(self.policy.ckpt_dir,
                                  keep_k=self.policy.keep_k,
                                  epoch=epoch)
                     if self.policy.checkpoints_enabled else None)

        self.payload: dict | None = None
        if resume_from is not None:
            src = (resume_from if isinstance(resume_from, Checkpointer)
                   else Checkpointer(str(resume_from),
                                     keep_k=self.policy.keep_k,
                                     epoch=(epoch if self.ckpt is None
                                            else None)))
            self.payload = resume_mod.load_snapshot(src, resume_step)
            resume_mod.check_compatible(self.payload, cfg)
            self.resumed_at = int(self.payload["it"])
            if self.ckpt is None:
                # keep committing to the directory we resumed from, so
                # a chain of preemptions never loses progress
                self.ckpt = src

        self.warm_state = None
        self.prev_stats: dict | None = None
        self.window_entries: list = []
        if warm_start is not None:
            self.warm_state = np.asarray(warm_start.last_sample,
                                         np.float32)
            if cfg.decay > 0.0:
                if warm_start.stats is None:
                    raise ValueError(
                        "decay > 0 folds the previous fit's statistics "
                        "into the new one, but warm_start.stats is None "
                        "— the donor fit must itself run driver='stream' "
                        "with decay > 0 (which populates FitResult.stats)")
                self.prev_stats = {k: np.asarray(v)
                                   for k, v in warm_start.stats.items()}
            if cfg.window >= 2:
                if warm_start.stats_window is None:
                    raise ValueError(
                        "window >= 2 retains the previous generations' "
                        "fresh statistics, but warm_start.stats_window "
                        "is None — the donor fit must itself run "
                        "driver='stream' with window >= 1 (which "
                        "populates FitResult.stats_window)")
                # Hard expiry happens HERE: entries beyond the horizon
                # are dropped before the fit ever folds them.
                self.window_entries = [
                    {k: np.asarray(v) for k, v in e.items()}
                    for e in warm_start.stats_window][: cfg.window - 1]
        if self.payload is not None and self.payload.get("prev_stats"):
            self.prev_stats = self.payload["prev_stats"]
        if self.payload is not None and self.payload.get("window_stats"):
            self.window_entries = self.payload["window_stats"]

        self.live_dev = None
        self._live_host: np.ndarray | None = None
        if svm.mesh is not None:
            n = distributed.num_shards(svm.mesh, svm.data_axes)
            vec = np.ones((n,), np.float32)
            if live is not None:
                live = np.asarray(live, np.float32)
                if live.shape != (n,):
                    raise ValueError(
                        f"live must be one weight per data shard, shape "
                        f"({n},); got {live.shape}")
                vec = live.copy()
            self._live_host = vec
            self._place_live()
        elif live is not None:
            raise ValueError("live (per-shard liveness weights) needs a "
                             "mesh — single-device fits have no shards "
                             "to drop")

    def _place_live(self) -> None:
        svm = self.svm
        sh = NamedSharding(svm.mesh, P(tuple(svm.data_axes)))
        self.live_dev = jax.device_put(self._live_host, sh)

    def drop_shards(self, idxs) -> None:
        """Zero the liveness weight of the given data shards — their
        statistics contributions drop and the psums renormalize
        (``stats.preduce``), the unbiased sum-statistic estimate."""
        if self._live_host is None or not idxs:
            return
        for i in idxs:
            self._live_host[int(i)] = 0.0
        self._place_live()

    # ---------------------------------------------------- host loop state
    def init_loop(self, state0):
        """Restore-or-init the loop scalar state; returns the initial
        device state (restored arrays are placed through
        ``runtime.elastic.remesh``, so a checkpoint written on one mesh
        layout resumes onto whatever mesh this PEMSVM holds)."""
        cfg = self.svm.config
        p = self.payload
        if p is not None:
            restored = np.asarray(p["state"], np.float32)
            if restored.shape != tuple(np.shape(state0)):
                raise ValueError(
                    f"checkpoint state has shape {restored.shape}, this "
                    f"fit expects {tuple(np.shape(state0))} — same "
                    "dataset/featurization required to resume")
            self.key = jnp.asarray(p["key"])
            self.it0 = int(p["it"])
            self.objs = [float(v) for v in p["objs"]]
            self.aux_hist = {k: list(v) for k, v in p["aux"].items()}
            self.n_avg = int(p["n_avg"])
            self.n_small = int(p["n_small"])
            self.mean_w = (np.asarray(p["samp_sum"], np.float64)
                           / self.n_avg if self.n_avg > 0 else None)
            state = self._place_state(restored, state0)
            if p["in_pass"]:
                self.pending_sub = jnp.asarray(p["sub"])
                self.midpass = {
                    "totals": {k: jnp.asarray(v)
                               for k, v in p["totals"].items()},
                    "skip": int(p["chunk_idx"]),
                    "row0": int(p["row0"]),
                }
        else:
            self.key = jax.random.PRNGKey(cfg.seed)
            self.it0 = 0
            self.objs = []
            self.aux_hist = {}
            self.n_avg = 0
            self.n_small = 0
            self.mean_w = None
            state = state0
            if self.warm_state is not None:
                if self.warm_state.shape != tuple(np.shape(state0)):
                    raise ValueError(
                        f"warm_start weights have shape "
                        f"{self.warm_state.shape}, this fit expects "
                        f"{tuple(np.shape(state0))}")
                state = self._place_state(self.warm_state, state0)
        self.last_saved_it = self.it0
        return state

    def _place_state(self, host_state: np.ndarray, like):
        svm = self.svm
        if svm.mesh is None:
            return jnp.asarray(host_state)
        from repro.runtime.elastic import remesh
        spec = P(*(None,) * np.ndim(host_state))
        return remesh(host_state, NamedSharding(svm.mesh, spec))

    # -------------------------------------------------------- checkpoints
    def samp_sum_of(self, state) -> np.ndarray:
        if self.mean_w is not None:
            return np.asarray(self.mean_w, np.float64) * self.n_avg
        return np.zeros(np.shape(state), np.float64)

    def boundary_due(self, it: int) -> bool:
        return (self.ckpt is not None and self.policy.ckpt_every > 0
                and it - self.last_saved_it >= self.policy.ckpt_every)

    def save_snapshot(self, it: int, state, *, converged: bool = False,
                      samp_sum=None, n_syncs: int | None = None,
                      sub=None, totals: dict | None = None,
                      chunk_idx: int = 0, row0: int = 0,
                      blocking: bool = False) -> None:
        if self.ckpt is None:
            return
        resume_mod.save_snapshot(
            self.ckpt, self.svm.config, it=it, state=state, key=self.key,
            samp_sum=(self.samp_sum_of(state) if samp_sum is None
                      else samp_sum),
            n_avg=self.n_avg, n_small=self.n_small, objs=self.objs,
            aux_hist=self.aux_hist,
            n_syncs=len(self.objs) if n_syncs is None else n_syncs,
            converged=converged, prev_stats=self.prev_stats,
            window_stats=self.window_entries or None, sub=sub,
            totals=totals, chunk_idx=chunk_idx, row0=row0,
            blocking=blocking)
        self.n_checkpoints += 1
        if totals is None:
            self.last_saved_it = it

    def flush(self) -> None:
        """Drain the async checkpoint writer at fit exit — normal OR
        unwinding (preemption/straggler): once fit returns or raises,
        every enqueued snapshot is committed, so the caller can resume
        from the directory immediately without racing the writer. A
        background write failure is recorded as an event rather than
        raised (it must not mask the exception being unwound; the
        on-disk state simply stays at the previous commit)."""
        if self.ckpt is None:
            return
        try:
            self.ckpt.wait()
        except Exception as e:  # noqa: BLE001
            self.events.append({"checkpoint_error": repr(e)})

    # ---------------------------------------------------------- straggler
    def observe(self, it: int, seconds: float) -> None:
        if not self.monitor.observe(it, seconds):
            return
        self.events.append(
            {"it": it, "seconds": float(seconds),
             "ema": float(self.monitor.ema)})
        pol = self.policy
        if pol.on_straggler == "raise":
            raise StragglerError(
                f"iteration {it} took {seconds:.4f}s > "
                f"{pol.straggler_threshold} x EMA "
                f"{self.monitor.ema:.4f}s")
        if pol.on_straggler == "drop":
            self.drop_shards(self.svm._suspect_shards)
            self.svm._suspect_shards.clear()


class PEMSVM:
    """Parallel EM/MCMC SVM (paper's PEMSVM)."""

    def __init__(self, config: SVMConfig, mesh: Mesh | None = None,
                 data_axes: Sequence[str] | None = None):
        if config.formulation == "KRN" and config.rng != "host":
            # NystromSVM never forwards its KRN surface config here (it
            # builds a LIN + phi_spec delegate), so any KRN config that
            # reaches PEMSVM is a real exact-Gram fit.
            raise ValueError(
                f"rng={config.rng!r} needs the fused LIN statistics; the "
                "exact-Gram KRN step has no counter plumbing — use "
                "NystromSVM for kernel models")
        self.config = config
        self.mesh = mesh
        if mesh is not None and data_axes is None:
            excl = (config.k_shard_axis,) if config.k_shard_axis else ()
            data_axes = distributed.data_axes_of(mesh, model_axes=excl)
        self.data_axes: tuple[str, ...] = tuple(data_axes or ())
        self._train_X: np.ndarray | None = None  # kept for KRN prediction
        # Nystrom phi-space featurizer arrays (landmarks, K_mm^{-1/2});
        # set by NystromSVM before fit when config.phi_spec is present.
        self._phi_arrays: tuple | None = None
        # Raw request width D (pre-bias, pre-pad) — recorded at fit so
        # the serving export can validate request shapes.
        self._n_features: int | None = None
        # (source arrays, SVMScorer) — the device-resident scorer is
        # built once per fitted model; identity of the source arrays is
        # the invalidation key (a refit assigns new objects, and the
        # cache holds the old ones alive so ids cannot be recycled).
        self._scorer_cache: tuple | None = None
        # data-shard indices a health probe has flagged; consumed by the
        # fault policy's on_straggler='drop' reaction.
        self._suspect_shards: set[int] = set()
        # (C, K) per-chain posterior means of the last multichain fit
        # (None otherwise) — the serving export turns these into
        # ensemble uncertainty columns.
        self._chain_weights: np.ndarray | None = None

    def report_slow_shard(self, *shard_idx: int) -> None:
        """Designate data-shard indices as straggler suspects. With
        ``FaultPolicy(on_straggler='drop')``, the next straggler event
        zeroes their liveness weight: their statistics contributions
        drop out and every reduction renormalizes (unbiased for the
        SVM's sum-statistics; see ``stats.preduce``). On a real
        multi-host deployment the per-host health probe feeds this; in
        tests the fault harness does."""
        self._suspect_shards.update(int(i) for i in shard_idx)

    # ------------------------------------------------------------- fitting
    def _phi_width(self) -> int:
        """State/statistic dimension in phi-space: projection columns
        plus the phi-space bias column."""
        assert self._phi_arrays is not None, (
            "config.phi_spec is set but no featurizer arrays were "
            "installed; fit through NystromSVM, which selects landmarks "
            "and computes K_mm^{-1/2} before delegating")
        return (self._phi_arrays[1].shape[1]
                + int(self.config.phi_spec.add_bias))

    def fit(self, X: np.ndarray, y: np.ndarray, *,
            resume_from=None, resume_step: int | None = None,
            warm_start: FitResult | None = None,
            live=None, fault_hook: Callable | None = None,
            epoch: int | None = None) -> FitResult:
        """Fit. The keyword group is the elastic/preemption-safe surface:

        ``resume_from`` (dir path or ``Checkpointer``) continues a
        preempted fit from its last committed snapshot (``resume_step``
        pins a specific one) — onto whatever driver/mesh THIS solver
        holds, since checkpoints store logical host tensors
        (``core.resume``). ``warm_start`` (a previous ``FitResult``)
        starts a NEW fit from the donor's last sample; with
        ``config.decay > 0`` (stream driver) the donor's statistics are
        folded in at weight ``decay`` so fresh chunks update an existing
        model instead of refitting from scratch. ``live`` is an initial
        per-data-shard liveness vector (mesh only). ``fault_hook(it)``
        is called once per completed iteration — the deterministic
        fault-injection seam (``repro.runtime.faults``). ``epoch`` is
        the attempt's fence token under multi-controller co-supervision
        (``HostContext.epoch``): commits carry it, restore orders by
        (epoch, step), and a superseded attempt's commits are rejected
        at the rename boundary (DESIGN.md §Reliability).
        """
        cfg = self.config
        with TraceAnnotation("pemsvm.fit", driver=cfg.driver) as fit_span:
            rt = _FitRuntime(self, resume_from, resume_step, warm_start,
                             live, fault_hook, epoch)
            with TraceAnnotation("pemsvm.bias") as span:
                Xf = np.asarray(X, np.float32)
                copied = (0 if isinstance(X, np.ndarray)
                          and np.may_share_memory(Xf, X) else Xf.nbytes)
                X = Xf
                y = np.asarray(y)
                fit_span.set_metadata(rows=X.shape[0], width=X.shape[1])
                self._n_features = X.shape[1]
                if cfg.driver == "stream":
                    # The stream driver uploads host chunks one at a
                    # time, so their columns are built here; the resident
                    # drivers build them on the device (``_prepare``).
                    X, more = self._host_columns(X)
                    copied += more
                span.set_metadata(bytes=X.nbytes, host_bytes=copied)
            N = X.shape[0]

            try:
                if cfg.driver == "stream":
                    if cfg.formulation == "KRN":
                        raise NotImplementedError(
                            "driver='stream' cannot use the exact N x N "
                            "Gram statistic (not row-chunk-additive); use "
                            "NystromSVM, whose phi-space route streams raw "
                            "rows")
                    return self._fit_stream_arrays(X, y, rt)

                data, prior, state = self._prepare(X, y)
                if cfg.driver == "loop":
                    step = self._build_step(prior is not None,
                                            self.mesh is not None)
                    return self._fit_loop(data, prior, state, step, N, rt)
                return self._fit_scan(data, prior, state, N, rt)
            finally:
                rt.flush()

    def fit_libsvm(self, path: str, n_features: int, rank: int = 0,
                   world: int = 1, **fit_kw) -> FitResult:
        """Fit directly from a libsvm file.

        With ``driver="stream"`` the file is re-read chunk by chunk every
        pass (``data.libsvm.iter_libsvm`` + prefetch) and the dataset is
        never materialized — host AND device residency are bounded by
        ``chunk_rows``. Other drivers load it resident and defer to
        ``fit``. ``rank``/``world`` stripe lines per host (paper Sec 5.6).
        ``fit_kw`` forwards the elastic surface (resume_from /
        warm_start / fault_hook / ...) — see ``fit``.
        """
        from repro.data import iter_libsvm, load_libsvm

        cfg = self.config
        if cfg.driver != "stream":
            X, y = load_libsvm(path, n_features, rank=rank, world=world)
            return self.fit(X, y, **fit_kw)
        if world > 1:
            # A rank stripe is a PARTIAL dataset; stream has no
            # cross-rank reduction (it rejects meshes), so fitting a
            # stripe would silently return weights trained on 1/world
            # of the rows.
            raise NotImplementedError(
                "driver='stream' with world > 1 needs a cross-host "
                "reduction that does not exist yet; stream the full "
                "file (world=1) or use a resident driver on a mesh")
        if cfg.pad_features:
            from repro.data.pipeline import pad_features_to
        self._n_features = n_features
        K = (self._phi_width() if cfg.phi_spec is not None
             else n_features + (1 if cfg.add_bias else 0))
        if cfg.pad_features:
            K = K + (-K) % cfg.pad_features

        def make_chunks():
            for Xc, yc, mc in iter_libsvm(path, cfg.chunk_rows,
                                          n_features, rank=rank,
                                          world=world):
                if cfg.add_bias:
                    # bias column = mask: padded rows keep all-zero X.
                    Xc = np.concatenate([Xc, mc[:, None]], axis=1)
                if cfg.pad_features:
                    Xc = pad_features_to(Xc, cfg.pad_features)
                yield SVMData(Xc, self._stream_target(yc, mc), mc)

        return self.fit_chunks(make_chunks, K, **fit_kw)

    def fit_chunks(self, make_chunks: Callable, K: int, *,
                   resume_from=None, resume_step: int | None = None,
                   warm_start: FitResult | None = None,
                   fault_hook: Callable | None = None,
                   epoch: int | None = None) -> FitResult:
        """Out-of-core fit over an arbitrary restartable chunk source.

        ``make_chunks()`` returns a fresh iterator of host
        ``(X, target, mask)`` blocks with the statistic width already
        final (bias column appended, features padded); ``K`` is that
        width. This is the seam the fault-injection harness wraps
        (``runtime.faults.kill_after_chunks`` etc.) and the entry point
        ``fit_libsvm`` builds on. Loader retries, mid-pass checkpoints
        and resume skipping compose around the factory per
        ``config.fault``; see ``fit`` for the keyword group.
        """
        cfg = self.config
        if cfg.driver != "stream":
            raise ValueError(
                f"fit_chunks is the stream driver's entry point; "
                f"config.driver is {cfg.driver!r}")
        if cfg.formulation == "KRN":
            raise NotImplementedError(
                "driver='stream' cannot use the exact N x N Gram "
                "statistic; use NystromSVM (phi-space streams raw rows)")
        rt = _FitRuntime(self, resume_from, resume_step, warm_start,
                         None, fault_hook, epoch)
        try:
            return self._fit_stream(make_chunks, K, rt)
        finally:
            rt.flush()

    def _host_columns(self, X: np.ndarray) -> tuple[np.ndarray, int]:
        """X with the LIN bias column and any ``pad_features`` zero
        columns appended in host copies, and the bytes those copies
        wrote. Zero-column padding of the (post-bias) statistic width is
        the supported route to a k_shard-divisible K (padded columns
        carry zero statistics; the ridge pins their weights to 0, so
        predictions are unchanged)."""
        cfg = self.config
        copied = 0
        if cfg.add_bias and cfg.formulation == "LIN":
            X = np.concatenate([X, np.ones((X.shape[0], 1), np.float32)], 1)
            copied += X.nbytes
        if cfg.pad_features:
            from repro.data.pipeline import pad_features_to
            Xq = pad_features_to(X, cfg.pad_features)
            copied += 0 if Xq is X else Xq.nbytes
            X = Xq
        return X, copied

    def _stream_target(self, y: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Validate + cast one chunk's labels (the _prepare checks,
        applied chunk-locally)."""
        task = self.config.task
        if task == "MLT":
            return np.asarray(y, np.int32)
        y = np.asarray(y, np.float32)
        if task == "CLS":
            valid = y[np.asarray(mask) > 0]
            bad = set(np.unique(valid).tolist()) - {-1.0, 1.0}
            assert not bad, f"CLS labels must be +-1, got extras {bad}"
        return y

    def _fit_stream_arrays(self, X: np.ndarray, y: np.ndarray,
                           rt: "_FitRuntime") -> FitResult:
        """driver='stream' on in-memory arrays: chunk views, zero-copy
        per pass (the out-of-core entry point is ``fit_libsvm``)."""
        cfg = self.config
        target = self._stream_target(np.asarray(y), np.ones(len(y)))
        Xp, tp, mask = distributed.pad_rows(X, target, 1,
                                            multiple=cfg.chunk_rows)
        cr = cfg.chunk_rows

        def make_chunks():
            for i0 in range(0, Xp.shape[0], cr):
                yield SVMData(Xp[i0:i0 + cr], tp[i0:i0 + cr],
                              mask[i0:i0 + cr])

        K = (self._phi_width() if cfg.phi_spec is not None
             else X.shape[1])
        return self._fit_stream(make_chunks, K, rt)

    def _fit_scan(self, data, prior, state, N: int,
                  rt: "_FitRuntime") -> FitResult:
        """Chunked on-device driver (DESIGN.md §Perf).

        The per-iteration loop driver blocks on a device->host transfer
        EVERY iteration (``float(aux["objective"])``), serializing
        dispatch with compute. Here ``scan_chunk`` iterations run as one
        ``lax.scan`` with the MC sample accumulator and the Sec 5.5
        objective-change stopping statistic carried in scan state; the
        host sees one transfer per chunk (the stacked aux trace plus the
        convergence flags) and decides whether to launch the next chunk.
        Total host syncs <= ceil(max_iters / scan_chunk).

        The MC posterior average accumulates a per-chunk fp32 sample sum
        on device and combines the chunk sums in float64 on host, so its
        rounding error matches the loop driver's f64 running mean to
        within one chunk's worth of fp32 additions regardless of chain
        length.

        Iterations after the in-chunk convergence point still execute
        (at most scan_chunk - 1 of them, once) but their updates are
        masked out, so results match the loop driver exactly: the same
        per-iteration key splits, the same update-then-check ordering,
        and the trace truncated at the converged iteration.

        Reliability: resume restores the whole carry from a boundary
        snapshot (state, key chain, f64 sample sum, stopping counters)
        and checkpoints/straggler-observes once per host sync — the
        chunk boundary is the natural commit point, since the carry is
        only consistent on host there.
        """
        cfg = self.config
        has_live = self.mesh is not None
        runner = _chunk_runner(cfg, self.mesh, tuple(self.data_axes),
                               prior is not None, has_live)
        tol_n = jnp.float32(cfg.tol * N)
        state = rt.init_loop(state)
        objs = rt.objs
        aux_hist = rt.aux_hist
        # f64 host accumulator of the MC sample sum (driver-independent:
        # the checkpoint stores mean * n_avg, which is exactly this).
        samp_sum = rt.samp_sum_of(state)
        n_syncs = int(rt.payload["n_syncs"]) if rt.payload else 0
        carry = (
            state,                          # current weight / sample
            jnp.zeros_like(state),          # this chunk's MC sample sum
            jnp.int32(rt.n_avg),            # total samples accumulated
            rt.key,                         # iteration key chain
            jnp.float32(objs[-1] if objs else np.inf),  # previous objective
            jnp.int32(rt.n_small),          # consecutive small-change count
            jnp.asarray(False),             # converged flag
            jnp.int32(0),                   # iteration convergence hit
        )
        it0 = rt.it0
        converged = False
        it_done = 0
        while it0 < cfg.max_iters:
            t0 = time.perf_counter()
            chunk = min(cfg.scan_chunk, cfg.max_iters - it0)
            with TraceAnnotation("pemsvm.chunk", it0=it0, iters=chunk):
                its = jnp.arange(it0 + 1, it0 + chunk + 1, dtype=jnp.int32)
                with TraceAnnotation("pemsvm.dispatch"):
                    carry, aux_stack = runner(data, prior, carry, its,
                                              tol_n, rt.live_dev)
                # The single per-chunk host sync: flags, the chunk's
                # sample sum, and the stacked aux trace in one transfer.
                with TraceAnnotation("pemsvm.sync"):
                    aux_np, chunk_sum, done_np, it_done_np = jax.device_get(
                        (aux_stack, carry[1], carry[6], carry[7]))
                converged = bool(done_np)
                it_done = int(it_done_np)
                n_syncs += 1
                samp_sum += np.asarray(chunk_sum, np.float64)
                carry = (carry[0], jnp.zeros_like(carry[1])) + carry[2:]
                valid = (it_done - it0) if converged else chunk
                objs.extend(float(v) for v in aux_np["objective"][:valid])
                for k, v in aux_np.items():
                    aux_hist.setdefault(k, []).extend(
                        float(x) for x in v[:valid])
                it0 += chunk
                done_its = it_done if converged else it0
                # Mirror the carry scalars into rt so snapshots see the
                # same loop state the host-loop drivers would.
                rt.key = carry[3]
                rt.n_avg = int(carry[2])
                rt.n_small = int(carry[5])
                rt.cur_it = done_its
                if rt.n_avg > 0:
                    rt.mean_w = samp_sum / rt.n_avg
                if not converged and rt.boundary_due(done_its):
                    rt.save_snapshot(done_its, carry[0], samp_sum=samp_sum,
                                     n_syncs=n_syncs)
                if rt.hook is not None:
                    rt.hook(done_its)
                rt.observe(done_its, time.perf_counter() - t0)
            if converged:
                break

        with TraceAnnotation("pemsvm.finalize"):
            n_iters = it_done if converged else it0
            last = np.asarray(carry[0], np.float32)
            n_avg = int(carry[2])
            weights = ((samp_sum / n_avg).astype(np.float32)
                       if n_avg > 0 else last)
            self._weights = weights
            if rt.ckpt is not None and n_iters > rt.last_saved_it:
                rt.save_snapshot(n_iters, carry[0], converged=converged,
                                 samp_sum=samp_sum, n_syncs=n_syncs,
                                 blocking=True)
            return self._finalize_chains(FitResult(
                weights=weights, last_sample=last, objective=objs,
                aux_history=aux_hist, n_iters=n_iters,
                converged=converged, n_host_syncs=n_syncs,
                straggler_events=rt.events,
                resumed_at=rt.resumed_at,
                n_checkpoints=rt.n_checkpoints,
                loader_retries=rt.retry_stats.retries,
                loader_backoff_s=rt.retry_stats.backoff_s))

    def _finalize_chains(self, result: FitResult) -> FitResult:
        """Multichain post-processing, shared by every driver: the raw
        fit state is the (C, K) per-chain posterior means — expose them
        as ``chain_weights``, report their cross-chain mean as THE
        weights (a C-chain posterior-mean estimate), and their ddof=1
        std as the per-coordinate ensemble spread. Single-chain fits
        pass through untouched."""
        if self.config.n_chains <= 1:
            self._chain_weights = None
            return result
        cw = np.asarray(result.weights, np.float32)
        result.chain_weights = cw
        result.chain_std = np.std(cw.astype(np.float64), axis=0,
                                  ddof=1).astype(np.float32)
        result.weights = np.mean(cw.astype(np.float64),
                                 axis=0).astype(np.float32)
        self._weights = result.weights
        self._chain_weights = cw
        return result

    def _fit_host_loop(self, iterate, state0,
                       rt: "_FitRuntime") -> FitResult:
        """Shared host-loop tail for the loop and stream drivers: key
        chain, trace bookkeeping, MC posterior averaging (f64 running
        mean) and the paper's Sec 5.5 stopping rule, in ONE place so the
        drivers cannot drift apart semantically.

        ``iterate(sub_key, state) -> (state, aux dict, n_valid)`` runs
        one full iteration (n_valid = valid-row count for the tol*N
        stopping threshold; the stream driver only knows it after its
        first pass, hence per-iteration).

        Reliability (DESIGN.md §Reliability): the loop scalars live on
        ``rt``, which restores them from a checkpoint (``init_loop``)
        and snapshots them at the ``ckpt_every`` cadence. Per-iteration
        order — subkey (a mid-pass resume consumes the SAVED subkey
        instead of splitting, so the chain is exactly the uninterrupted
        one) -> iterate -> histories/averages/stopping counters ->
        boundary snapshot -> fault hook -> straggler observe ->
        convergence. The snapshot precedes the hook so a simulated kill
        at iteration k resumes from k's own commit; snapshots are async
        (a kill racing an in-flight commit just resumes from the
        previous boundary, which replays identical subkeys to the same
        result).
        """
        cfg = self.config
        state = rt.init_loop(state0)
        objs = rt.objs
        aux_hist = rt.aux_hist
        converged = False
        it = rt.it0
        for it in range(rt.it0 + 1, cfg.max_iters + 1):
            t0 = time.perf_counter()
            if rt.pending_sub is not None:
                sub, rt.pending_sub = rt.pending_sub, None
            else:
                rt.key, sub = jax.random.split(rt.key)
            rt.cur_it = it
            state, aux, n_valid = iterate(sub, state)
            objs.append(float(aux["objective"]))
            for k, v in aux.items():
                aux_hist.setdefault(k, []).append(float(v))
            if cfg.algorithm == "MC" and it > cfg.burnin:
                w_np = np.asarray(state, np.float64)
                rt.mean_w = w_np if rt.mean_w is None else (
                    rt.mean_w * rt.n_avg + w_np) / (rt.n_avg + 1)
                rt.n_avg += 1
            # Paper Sec 5.5 stopping rule on the objective change.
            if (len(objs) >= 2
                    and abs(objs[-1] - objs[-2]) <= cfg.tol * n_valid):
                rt.n_small += 1
            else:
                rt.n_small = 0
            if rt.boundary_due(it):
                rt.save_snapshot(it, state)
            if rt.hook is not None:
                rt.hook(it)
            rt.observe(it, time.perf_counter() - t0)
            if it >= cfg.min_iters and rt.n_small >= cfg.patience:
                if cfg.algorithm == "EM" or rt.n_avg >= 1:
                    converged = True
                    break

        if rt.ckpt is not None and it > rt.last_saved_it:
            rt.save_snapshot(it, state, converged=converged,
                             blocking=True)
        last = np.asarray(state, np.float32)
        weights = (np.asarray(rt.mean_w, np.float32)
                   if rt.mean_w is not None else last)
        self._weights = weights
        return self._finalize_chains(FitResult(
                         weights=weights, last_sample=last, objective=objs,
                         aux_history=aux_hist, n_iters=it,
                         converged=converged, n_host_syncs=len(objs),
                         straggler_events=rt.events,
                         resumed_at=rt.resumed_at,
                         n_checkpoints=rt.n_checkpoints,
                         loader_retries=rt.retry_stats.retries,
                         loader_backoff_s=rt.retry_stats.backoff_s))

    def _fit_loop(self, data, prior, state, step, N: int,
                  rt: "_FitRuntime") -> FitResult:
        """Per-iteration Python driver: one host sync per iteration.

        Kept as the semantic oracle for the scan driver (tests compare
        the two traces) and as an escape hatch for step functions whose
        aux is not scan-stackable."""
        has_live = self.mesh is not None

        def iterate(sub, state):
            args = ((data, prior, state, sub) if prior is not None
                    else (data, state, sub))
            if has_live:
                args = args + (rt.live_dev,)
            state, aux = step(*args)
            return state, aux, N

        return self._fit_host_loop(iterate, state, rt)

    def _fit_stream(self, make_chunks, K: int,
                    rt: "_FitRuntime") -> FitResult:
        """Out-of-core driver (DESIGN.md §Perf/Streaming).

        The paper's Fig. 1 iteration is a map-reduce over row shards:
        Sigma and the mu-numerator are exact sums over rows, so the
        E-step streams fixed-shape chunks through the same fused/SYRK
        kernels the resident drivers use (``accumulate_stats``),
        tree-summing per-chunk contributions on device, then runs the
        unchanged replicated M-step. Peak device residency is the
        (prefetch + 2) in-flight chunks plus the O(K^2) statistics —
        independent of N (``FitResult.peak_input_bytes``).

        Host-loop semantics (stopping rule, key chain, MC posterior
        averaging) are literally ``_fit_loop``'s — both feed the shared
        ``_fit_host_loop`` tail; with the rowwise MC gamma draw the
        sampled chain is also chunking-invariant, so stream fits match
        the resident drivers to fp32 reassociation tolerance for BOTH
        algorithms. One host sync per pass (the summed statistics),
        M + 1 passes per iteration for MLT.

        Reliability (DESIGN.md §Reliability): the chunk source is
        wrapped in ``retrying_chunks`` per the fault policy (flaky
        loaders degrade to retries, restarting the source past the
        chunks already folded); with ``ckpt_chunks > 0`` a MID-PASS
        snapshot commits every n chunks — pre-iteration state, the
        iteration subkey and the partial totals — and resume skips the
        already-folded chunks and continues the same pass, bit-for-bit.
        With ``config.decay > 0`` a warm-started fit folds the donor's
        statistics in at weight decay each M-step (an exponentially
        decayed window over fit generations); with ``config.window >= 1``
        it instead folds a HARD-EXPIRY ring of the last window-1
        generations' fresh partials at full weight
        (``stats.StatsWindow`` — exact data expiry for the online
        scenario). Either way the loss/objective stays fresh-data-only;
        ``FitResult.stats`` carries the effective statistics and
        ``FitResult.stats_window`` the advanced ring for the next
        generation.
        """
        cfg = self.config
        if self.mesh is not None:
            raise NotImplementedError(
                "driver='stream' is single-process: on a mesh, stream "
                "per-host shards via data_axes striping instead "
                "(rank/world in fit_libsvm)")
        from repro.data import ChunkPrefetcher, retrying_chunks

        fns = _stream_fns(cfg)
        is_mlt = cfg.task == "MLT"
        if is_mlt:
            state0 = jnp.zeros((cfg.num_classes, K), jnp.float32)
        elif cfg.n_chains > 1:
            state0 = jnp.zeros((cfg.n_chains, K), jnp.float32)
        else:
            state0 = jnp.zeros((K,), jnp.float32)
        # Nystrom featurizer arrays ride along to every chunk call; the
        # raw D-wide rows are the only per-chunk host->device traffic.
        phi = (tuple(jnp.asarray(a) for a in self._phi_arrays)
               if cfg.phi_spec is not None else None)
        pol = rt.policy
        # Donor statistics (decay > 0 warm start): frozen for the whole
        # fit — the window decays per fit GENERATION, not per iteration.
        prev = (None if rt.prev_stats is None else
                {k: jnp.asarray(v) for k, v in rt.prev_stats.items()})
        # Hard-expiry ring (window >= 1): the retained generations'
        # fresh partials, device-resident, frozen for the whole fit.
        win = (stats.StatsWindow(
                   cfg.window,
                   [{k: jnp.asarray(v) for k, v in e.items()}
                    for e in rt.window_entries])
               if cfg.window >= 1 else None)
        eff_stats = None
        fresh_stats = None
        peak_bytes = 0

        def chunk_source(skip):
            it = make_chunks()
            return itertools.islice(it, skip, None) if skip else it

        def stream(skip0):
            """Prefetched chunk iterator starting at chunk index skip0,
            with loader retries restarting past what already arrived."""
            if pol.loader_retries > 0:
                src = retrying_chunks(
                    lambda done: chunk_source(skip0 + done),
                    retries=pol.loader_retries,
                    backoff=pol.loader_backoff,
                    jitter=pol.loader_jitter, seed=cfg.seed,
                    stats=rt.retry_stats)
            else:
                src = chunk_source(skip0)
            return ChunkPrefetcher(src, depth=cfg.prefetch)

        def sweep(fn, skip0=0, totals0=None, row00=0, saver=None):
            """One pass over the data: tree-sum fn(chunk, row0)
            contributions on device (one host transfer per pass).
            ``skip0``/``totals0``/``row00`` continue a partially-swept
            pass (mid-pass resume); ``saver`` commits the partial totals
            every ``ckpt_chunks`` chunks."""
            nonlocal peak_bytes
            pf = stream(skip0)
            totals = totals0
            row0 = row00
            consumed = skip0
            for chunk in pf:
                data = SVMData(*chunk)
                part = fn(data, jnp.int32(row0))
                totals = part if totals is None else fns["add"](totals,
                                                                part)
                row0 += data.X.shape[0]
                consumed += 1
                if (saver is not None and pol.ckpt_chunks > 0
                        and consumed % pol.ckpt_chunks == 0):
                    saver(totals, consumed, row0)
            if totals is None:
                raise ValueError("stream source yielded no chunks")
            peak_bytes = max(peak_bytes, pf.max_resident_bytes)
            return totals

        def iterate(sub, state):
            # One blocking device->host transfer per iteration: the
            # statistics stay on device through every sweep/solve and
            # the scalar trace comes down in a single device_get.
            nonlocal eff_stats, fresh_stats
            midpass, rt.midpass = rt.midpass, None
            keep_stats = cfg.decay > 0.0 or win is not None
            if is_mlt:
                # MLT snapshots at iteration boundaries only (a sweep
                # is per class; a mid-sweep cursor would also need the
                # class index — not worth the surface).
                eff_S, eff_b, fr_S, fr_b = [], [], [], []
                for y_cls in range(cfg.num_classes):
                    t = sweep(lambda d, r0, _y=jnp.int32(y_cls):
                              fns["chunk"](d, state, sub, r0, _y, phi))
                    S, b = t["S"], t["b"]
                    fr_S.append(S)
                    fr_b.append(b)
                    if cfg.decay > 0.0 and prev is not None:
                        S = S + cfg.decay * prev["S"][y_cls]
                        b = b + cfg.decay * prev["b"][y_cls]
                    if win is not None:
                        for e in win.entries:  # newest first, like folded
                            S = S + e["S"][y_cls]
                            b = b + e["b"][y_cls]
                    if keep_stats:
                        eff_S.append(S)
                        eff_b.append(b)
                    state = fns["mstep"](state, S, b, sub,
                                         jnp.int32(y_cls))
                if keep_stats:
                    eff_stats = {"S": jnp.stack(eff_S),
                                 "b": jnp.stack(eff_b)}
                    fresh_stats = {"S": jnp.stack(fr_S),
                                   "b": jnp.stack(fr_b)}
                t = sweep(lambda d, r0: fns["obj"](d, state, phi))
                obj, mask_sum = jax.device_get(
                    (fns["obj_total"](state, t["loss"]), t["mask_sum"]))
                aux = {"objective": float(obj)}
            else:
                def saver(totals, consumed, row0):
                    # Pre-iteration state + this iteration's subkey +
                    # the partial totals: resume replays the remainder
                    # of THIS pass on the identical chain.
                    rt.save_snapshot(rt.cur_it - 1, state, sub=sub,
                                     totals=totals, chunk_idx=consumed,
                                     row0=row0)

                sv = saver if rt.ckpt is not None else None
                body = lambda d, r0: fns["chunk"](d, state, sub, r0, phi)
                if midpass is not None:
                    t = sweep(body, skip0=midpass["skip"],
                              totals0=midpass["totals"],
                              row00=midpass["row0"], saver=sv)
                else:
                    t = sweep(body, saver=sv)
                if keep_stats:
                    fresh_stats = {"S": t["S"], "b": t["b"]}
                    t = dict(t)
                    if cfg.decay > 0.0 and prev is not None:
                        t["S"] = t["S"] + cfg.decay * prev["S"]
                        t["b"] = t["b"] + cfg.decay * prev["b"]
                    if win is not None:
                        folded = win.folded(fresh_stats)
                        t["S"], t["b"] = folded["S"], folded["b"]
                    eff_stats = {"S": t["S"], "b": t["b"]}
                state, obj_dev = fns["mstep"](t["S"], t["b"], t["loss"],
                                              sub)
                obj, scalars = jax.device_get(
                    (obj_dev, {k: v for k, v in t.items()
                               if k not in ("S", "b")}))
                mask_sum = scalars["mask_sum"]
                den = max(float(mask_sum), 1.0)
                aux = {"objective": float(obj),
                       "gamma_mean": float(scalars["gamma_sum"]) / den}
                if cfg.task == "SVR":
                    aux["omega_mean"] = float(scalars["omega_sum"]) / den
                else:
                    aux["n_sv"] = float(scalars["n_sv"])
            return state, aux, float(mask_sum)

        result = self._fit_host_loop(iterate, state0, rt)
        result.peak_input_bytes = int(peak_bytes)
        if eff_stats is not None:
            result.stats = {k: np.asarray(v)
                            for k, v in eff_stats.items()}
        if win is not None and fresh_stats is not None:
            # The ring the NEXT generation folds: this fit's fresh
            # partials pushed in front, horizon enforced.
            result.stats_window = win.advance(
                {k: np.asarray(v) for k, v in fresh_stats.items()})
        return result

    # ------------------------------------------------------ setup helpers
    def _prepare(self, X: np.ndarray, y: np.ndarray):
        """(data, prior, state) on the device(s) from the caller's
        float32 rows: the model's columns and pad rows are built there."""
        cfg = self.config
        N, K = X.shape
        with TraceAnnotation("pemsvm.labels", rows=N):
            if cfg.task == "CLS":
                target = np.asarray(y, np.float32)
                uniq = set(np.unique(target).tolist())
                assert uniq <= {-1.0, 1.0}, (
                    f"CLS labels must be +-1, got {uniq}")
            elif cfg.task == "MLT":
                target = np.asarray(y, np.int32)
            else:
                target = np.asarray(y, np.float32)

        if cfg.formulation == "KRN":
            if cfg.task != "CLS":
                raise NotImplementedError(
                    "the paper's exact KRN solver covers binary "
                    "classification only; NystromSVM serves KRN "
                    f"{cfg.task} through the phi-space route")
            self._train_X = X
            G = np.asarray(krn.gram_matrix(
                jnp.asarray(X), jnp.asarray(X), kind=cfg.kernel,
                sigma=cfg.sigma, backend=cfg.backend))
            shards = (distributed.num_shards(self.mesh, self.data_axes)
                      if self.mesh else 1)
            chunk = shards * 8
            Npad = ((N + chunk - 1) // chunk) * chunk - N
            Gp = np.asarray(krn.pad_gram(jnp.asarray(G), Npad))
            tp = np.concatenate([target, np.zeros((Npad,), target.dtype)])
            if self.mesh is not None:
                data = distributed.shard_rows(self.mesh, self.data_axes,
                                              Gp, tp)
                prior = jax.device_put(
                    Gp, NamedSharding(self.mesh, P(None, None)))
            else:
                mask = np.concatenate([np.ones(N, np.float32),
                                       np.zeros(Npad, np.float32)])
                data = SVMData(jnp.asarray(Gp), jnp.asarray(tp),
                               jnp.asarray(mask))
                prior = jnp.asarray(Gp)
            state = jnp.zeros((Gp.shape[0],), jnp.float32)
            return data, prior, state

        # LIN: the caller's rows go to the device once and the bias and
        # zero columns and rows are appended there (raw rows in
        # phi-space mode: featurization happens inside the step, so only
        # D-wide rows are sharded/resident).
        fpad = ((-(K + cfg.add_bias)) % cfg.pad_features
                if cfg.pad_features else 0)
        K = K + cfg.add_bias + fpad
        # The statistic's X is stored at the width the fused kernel
        # reads, so no kernel call pads a copy of it.
        width = K if cfg.phi_spec is not None else ops.fused_stats_width(
            K, backend=cfg.backend, n_chains=cfg.n_chains,
            epilogue=(("em_" if cfg.algorithm == "EM" else "mc_")
                      + ("svr" if cfg.task == "SVR" else "hinge")))
        data = distributed.upload_rows(
            X, target, self.mesh, self.data_axes, bias=cfg.add_bias,
            fpad=fpad, width=width)
        with TraceAnnotation("pemsvm.upload") as span:
            prior = None
            if cfg.phi_spec is not None:
                K = self._phi_width()
                prior = tuple(jnp.asarray(a, jnp.float32)
                              for a in self._phi_arrays)
                if self.mesh is not None:
                    rep = NamedSharding(self.mesh, P(None, None))
                    prior = tuple(jax.device_put(a, rep) for a in prior)
            if cfg.task == "MLT":
                state = jnp.zeros((cfg.num_classes, K), jnp.float32)
            elif cfg.n_chains > 1:
                state = jnp.zeros((cfg.n_chains, K), jnp.float32)
            else:
                state = jnp.zeros((K,), jnp.float32)
            if self.mesh is not None:
                state = jax.device_put(state, NamedSharding(
                    self.mesh, P(*(None,) * state.ndim)))
            span.set_metadata(bytes=state.nbytes + sum(
                a.nbytes for a in prior or ()))
        return data, prior, state

    def _build_step(self, has_prior: bool, has_live: bool = False):
        return _build_step_fn(self.config, self.mesh,
                              tuple(self.data_axes), has_prior, has_live)

    # ---------------------------------------------------------- inference
    def export_servable(self, *, name: str = "svm",
                        posterior_from: tuple | None = None):
        """Freeze this fitted model into a ``serving.ServableModel`` —
        the serving path's whole view of it (no reaching back into
        ``_weights``/``_train_X``/``_phi_arrays``).

        The exact-KRN model rides the SAME fused Nystrom score cell:
        landmarks are the train rows, the projection is the dual weight
        column omega[:, None], and the score weight is [[1.]] — so
        score = k(X, X_train) @ omega with the cross-Gram tile never
        leaving VMEM.

        ``posterior_from=(X, y)`` appends the MC-posterior uncertainty
        directions U = L^{-T} as extra weight columns (one E-step at
        the fitted weights rebuilds (S, b); L = chol(lam I + S)), so a
        scorer serves margin +- calibrated std in one dispatch
        (``SVMScorer.score_with_std``).
        """
        from repro.serving.svm_serve import ServableModel

        cfg = self.config
        assert self._weights is not None, "fit first"
        w = np.asarray(self._weights, np.float32)
        task = cfg.task.lower()
        if cfg.formulation == "KRN":
            if posterior_from is not None:
                raise NotImplementedError(
                    "posterior serving for the exact-Gram model needs "
                    "the kernel prior precision; fit NystromSVM, whose "
                    "phi-space posterior is lam^{-1} I exactly")
            ntrain = self._train_X.shape[0]
            return ServableModel(
                task=task, weights=np.ones((1, 1), np.float32),
                n_outputs=1, n_features=self._train_X.shape[1],
                landmarks=self._train_X, proj=w[:ntrain, None],
                phi_kind=cfg.kernel, phi_sigma=cfg.sigma,
                phi_add_bias=False, backend=cfg.backend, name=name)
        if cfg.task == "MLT":
            W, n_out = np.ascontiguousarray(w.T), cfg.num_classes
        else:
            W, n_out = w[:, None], 1
        if posterior_from is not None:
            U = self._posterior_columns(*posterior_from)
            W = np.concatenate([W, U], axis=1)
        elif self._chain_weights is not None:
            # Multichain ensemble uncertainty: extra columns
            # (w_c - wbar) / sqrt(C - 1), so the scorer's row-wise
            # ||x @ U|| (score_with_std) IS the ddof=1 std of the C
            # chains' margins — posterior spread served from the same
            # single fused dispatch as the mean margin.
            cw = self._chain_weights.astype(np.float64)
            U = (cw - cw.mean(axis=0)) / np.sqrt(cw.shape[0] - 1)
            W = np.concatenate([W, U.T.astype(np.float32)], axis=1)
        if cfg.phi_spec is not None:
            lm, pj = self._phi_arrays
            return ServableModel(
                task=task, weights=W, n_outputs=n_out,
                n_features=lm.shape[1], landmarks=lm, proj=pj,
                phi_kind=cfg.phi_spec.kind, phi_sigma=cfg.phi_spec.sigma,
                phi_add_bias=cfg.phi_spec.add_bias, backend=cfg.backend,
                name=name)
        D = self._n_features
        if D is None:
            if cfg.pad_features:
                raise ValueError(
                    "raw feature width unknown (fit_chunks with "
                    "pad_features); set svm._n_features or fit via "
                    "fit/fit_libsvm")
            D = W.shape[0] - int(cfg.add_bias)
        expect = D + int(cfg.add_bias)
        if cfg.pad_features:
            expect += (-expect) % cfg.pad_features
        assert expect == W.shape[0], (
            f"recorded request width {D} preps to {expect} columns but "
            f"the fitted weights have {W.shape[0]}")
        return ServableModel(task=task, weights=W, n_outputs=n_out,
                             n_features=D, add_bias=cfg.add_bias,
                             backend=cfg.backend, name=name)

    def _posterior_columns(self, X: np.ndarray, y: np.ndarray
                           ) -> np.ndarray:
        """U = L^{-T} (Kfit, Kfit) f32: the uncertainty directions of
        the weight posterior N(mu, P^{-1}) at the FITTED weights — one
        E-step over (X, y) rebuilds the sufficient statistic S, then
        P = lam I + S (+ the config's relative jitter, mirroring
        ``stats.posterior_params``) and L = chol(P). Served std is
        ||phi U|| = sqrt(phi^T P^{-1} phi)."""
        cfg = self.config
        if cfg.task == "MLT":
            raise NotImplementedError(
                "MLT posterior columns need per-class statistics; "
                "export per-class binary models instead")
        X = np.asarray(X, np.float32)
        if cfg.phi_spec is not None:
            lm, pj = (jnp.asarray(a, jnp.float32)
                      for a in self._phi_arrays)
            Xp = ops.nystrom_phi(
                jnp.asarray(X), lm, pj, None, sigma=cfg.phi_spec.sigma,
                kind=cfg.phi_spec.kind, add_bias=cfg.phi_spec.add_bias,
                backend=cfg.backend)
        else:
            Xp = jnp.asarray(self._host_columns(X)[0])
        yf = jnp.asarray(np.asarray(y, np.float32))
        beta = yf if cfg.task == "CLS" else jnp.zeros_like(yf)
        epi = "em_hinge" if cfg.task == "CLS" else "em_svr"
        out = ops.fused_stats(Xp, yf, beta, jnp.asarray(self._weights),
                              None, None, epilogue=epi, eps=cfg.eps,
                              eps_ins=cfg.eps_ins, backend=cfg.backend)
        S = np.asarray(out[-1], np.float64)
        K = S.shape[0]
        P = S + cfg.lam * np.eye(K)
        P = 0.5 * (P + P.T)
        P += (cfg.jitter * np.trace(P) / K) * np.eye(K)
        L = np.linalg.cholesky(P)
        return np.linalg.solve(L, np.eye(K)).T.astype(np.float32)

    def scorer(self):
        """The device-resident ``serving.SVMScorer`` for this fitted
        model, built ONCE per fit: weights/featurizer arrays are
        device-put at construction and every ``decision_function`` /
        ``predict`` call reuses them (no per-call host->device
        re-upload, no re-jit — the no-retrace regression tests gate
        this). A refit assigns new source arrays, which invalidates
        the cache by identity."""
        from repro.serving.svm_serve import SVMScorer

        src = (self._weights, self._train_X, self._phi_arrays)
        if (self._scorer_cache is None
                or any(a is not b
                       for a, b in zip(self._scorer_cache[0], src))):
            self._scorer_cache = (src, SVMScorer(self.export_servable()))
        return self._scorer_cache[1]

    def decision_function(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X, np.float32)
        if self._n_features is None:  # fit_chunks-direct fits
            self._n_features = X.shape[1]
        return self.scorer().margins(X)

    def predict(self, X: np.ndarray) -> np.ndarray:
        f = self.decision_function(X)
        if self.config.task == "MLT":
            return np.argmax(f, axis=1)
        if self.config.task == "SVR":
            return f
        return np.where(f >= 0, 1, -1)

    def rmse(self, X: np.ndarray, y: np.ndarray) -> float:
        """Root-mean-square prediction error (SVR)."""
        assert self.config.task == "SVR", "rmse is the SVR error metric"
        pred = self.predict(X)
        return float(np.sqrt(np.mean(
            (pred - np.asarray(y, np.float32)) ** 2)))

    def score(self, X: np.ndarray, y: np.ndarray) -> float:
        """HIGHER IS BETTER for every task: accuracy for CLS/MLT and
        *negated* RMSE for SVR (use ``rmse`` for the raw error). The
        old behavior returned raw RMSE here, silently inverting the
        ordering for callers comparing scores across tasks."""
        if self.config.task == "SVR":
            return -self.rmse(X, y)
        pred = self.predict(X)
        return float(np.mean(pred == np.asarray(y)))
