"""The paper's map-reduce architecture (Sec 4, Fig. 1) on a JAX mesh.

Every step function in this package is written over a *local* shard with
explicit ``psum`` reductions over ``axes``; this module supplies the
machinery around them:

  * ``upload_rows`` — place the caller's rows on the device(s) once and
    build the model's X there (bias column, zero feature and lane
    columns, zero pad rows), row-sharded over the mesh's data axes
    exactly like the paper assigns D^p to process p (padding rows are
    zeroed and masked so statistics are exact). ``shard_rows`` places a
    matrix the host has already built (the exact-KRN Gram).
  * ``shard_wrap`` — wrap a step function in ``shard_map`` so each device
    runs the identical SPMD program (the paper's observation that all
    slaves perform the same operations — hence minimal sync latency — is
    preserved; the master is replaced by a replicated solve, DESIGN.md §6).
  * ``FaultTolerantReduce`` semantics: reductions take a per-shard liveness
    weight so a failed/evicted replica contributes zero and the global
    statistic renormalizes (Sec "large-scale runnability"); see
    ``repro.runtime`` for the detection side.

The SVM is embarrassingly data-parallel, so by default it consumes *every*
mesh axis as a data axis (the paper scales to 480 cores with pure data
parallelism; on a 2x16x16 pod-slice that is 512-way). ``k_shard_axis``
optionally switches the Sigma statistic to the 2-D (data x model) scheme
(beyond-paper; see linear.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .linear import SVMData
from .stats import shard_row_offset  # noqa: F401 — re-export (public API)


def data_axes_of(mesh: Mesh, model_axes: Sequence[str] = ()) -> tuple[str, ...]:
    """All mesh axes not reserved for the model — the SVM's worker grid."""
    return tuple(a for a in mesh.axis_names if a not in model_axes)


def num_shards(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))


def pad_rows(X: np.ndarray, target: np.ndarray, shards: int,
             multiple: int = 8):
    """Zero-pad rows to a multiple of (shards * multiple) in host copies;
    returns SVMData host arrays. Padded rows: X-row = 0, target = 0,
    mask = 0.

    Used where the host builds the arrays it uploads: the stream driver's
    chunks and, through ``shard_rows``, the exact-KRN Gram. The resident
    LIN path pads on the device instead (``upload_rows``)."""
    N = X.shape[0]
    chunk = shards * multiple
    Np = ((N + chunk - 1) // chunk) * chunk
    pad = Np - N
    with TraceAnnotation("pemsvm.pad_rows", rows=N, pad_rows=pad) as span:
        Xp = np.concatenate([X, np.zeros((pad,) + X.shape[1:], X.dtype)],
                            axis=0)
        tp = np.concatenate([target, np.zeros((pad,), target.dtype)], axis=0)
        mask = np.concatenate([np.ones((N,), np.float32),
                               np.zeros((pad,), np.float32)], axis=0)
        span.set_metadata(host_bytes=Xp.nbytes)
    return Xp, tp, mask


def shard_rows(mesh: Mesh, axes: Sequence[str], X: np.ndarray,
               target: np.ndarray) -> SVMData:
    """Place a host-built training matrix row-sharded over ``axes``
    (paper Sec 4.1), padded by ``pad_rows``.

    The exact-KRN path places its Gram through here; the resident LIN
    path uploads raw rows with ``upload_rows``.

    I/O note (paper Sec 5.6): in a real multi-host deployment each host
    feeds only its addressable shard (repro.data.pipeline); here the
    single-host path materializes and shards.
    """
    shards = num_shards(mesh, axes)
    Xp, tp, mask = pad_rows(X, target, shards)
    row_spec = P(tuple(axes))
    with TraceAnnotation("pemsvm.upload",
                         bytes=Xp.nbytes + tp.nbytes + mask.nbytes):
        return SVMData(
            X=jax.device_put(Xp, NamedSharding(mesh, P(tuple(axes), None))),
            target=jax.device_put(tp, NamedSharding(mesh, row_spec)),
            mask=jax.device_put(mask, NamedSharding(mesh, row_spec)),
        )


@functools.lru_cache(maxsize=None)
def _augment_program(pad: int, bias: bool, fpad: int, sharding=None):
    """Jitted (X, mask) -> X with ``pad`` zero rows, then the bias column
    and ``fpad`` zero columns, appended. The bias column is the mask: 1
    on real rows, 0 on pad rows. ``fpad`` counts the ``pad_features``
    columns and the lane columns together: the program writes the
    stored X at its final width, once a fit. Row-local, so a row-sharded
    X is augmented block by block with no communication.

    The bias column is selected into the padded X rather than
    concatenated, so the mask is never laid out as an (N, 1) column
    (tiled to 128 lanes, 128x its bytes on a TPU)."""
    def augment(X, mask):
        with jax.named_scope("augment"):
            D = X.shape[1]
            X = jnp.pad(X, ((0, pad), (0, bias + fpad)))
            if bias:
                col = jax.lax.broadcasted_iota(jnp.int32, X.shape, 1)
                X = jnp.where(col == D, mask[:, None], X)
            return X
    return jax.jit(augment, out_shardings=sharding)


def upload_rows(X: np.ndarray, target: np.ndarray, mesh: Mesh | None,
                axes: Sequence[str], *, bias: bool,
                fpad: int = 0, width: int | None = None) -> SVMData:
    """Send the caller's (N, D) rows to the device(s) once and build the
    model's X there: the bias column (``bias``), ``fpad`` zero feature
    columns, zero lane columns up to ``width`` columns, and zero rows up
    to a multiple of (shards * 8), as ``pad_rows`` pads.

    The model width is K = D + bias + fpad, the stored width ``width``
    (K by default). Stored at the fused kernel's lane width
    (``ops.fused_stats_width``), X is read by the kernel in place
    rather than padded in a copy on every call; the weights stay K
    wide, and the other readers of the stored X take its first K
    columns. The ``pemsvm.upload`` span's ``lane_cols`` counts the lane
    columns.

    Bitwise the array ``pad_rows`` + ``shard_rows`` build from a host
    copy with the columns appended, in the same row order, but the host
    copies no row of a float32 X: one device pads its rows on the
    device; on a mesh each device's block is a view of X, and only a
    block that reaches past row N is copied into a zero block. The
    ``pemsvm.pad_rows`` span's ``host_bytes`` counts those copies.
    """
    N, D = X.shape
    lane_cols = 0 if width is None else width - (D + bias + fpad)
    shards = num_shards(mesh, axes) if mesh is not None else 1
    chunk = shards * 8
    Np = ((N + chunk - 1) // chunk) * chunk
    pad = Np - N
    with TraceAnnotation("pemsvm.pad_rows", rows=N, pad_rows=pad) as span:
        tp = np.concatenate([target, np.zeros((pad,), target.dtype)])
        mask = np.concatenate([np.ones((N,), np.float32),
                               np.zeros((pad,), np.float32)])
        copied = 0
        if mesh is not None:
            sharding = NamedSharding(mesh, P(tuple(axes), None))
            blocks, by_rows = {}, {}
            for dev, idx in sharding.addressable_devices_indices_map(
                    (Np, D)).items():
                a, b, _ = idx[0].indices(Np)
                if (a, b) not in by_rows:
                    if b <= N:
                        by_rows[a, b] = X[a:b]
                    else:
                        blk = np.zeros((b - a, D), X.dtype)
                        blk[:max(N - a, 0)] = X[a:N]
                        by_rows[a, b] = blk
                        copied += blk.nbytes
                blocks[dev] = by_rows[a, b]
        span.set_metadata(host_bytes=copied)
    with TraceAnnotation("pemsvm.upload") as span:
        if mesh is None:
            raw = jnp.asarray(X)
            tp_d, mask_d = jnp.asarray(tp), jnp.asarray(mask)
            dev_pad, out_sharding, up = pad, None, X.nbytes
        else:
            devs = list(blocks)
            raw = jax.make_array_from_single_device_arrays(
                (Np, D), sharding,
                jax.device_put([blocks[d] for d in devs], devs))
            row_sharding = NamedSharding(mesh, P(tuple(axes)))
            tp_d = jax.device_put(tp, row_sharding)
            mask_d = jax.device_put(mask, row_sharding)
            dev_pad, out_sharding = 0, sharding
            up = sum(blocks[d].nbytes for d in devs)
        span.set_metadata(bytes=up + tp.nbytes + mask.nbytes,
                          lane_cols=lane_cols)
        if not (dev_pad or bias or fpad or lane_cols):
            return SVMData(raw, tp_d, mask_d)
        # The raw rows go with this frame: their device memory is freed
        # once the program has read them.
        program = _augment_program(dev_pad, bias, fpad + lane_cols,
                                   out_sharding)
        return SVMData(program(raw, mask_d), tp_d, mask_d)


def shard_wrap(mesh: Mesh, axes: Sequence[str],
               step_fn: Callable, *, state_spec=P(None),
               has_prior: bool = False,
               prior_spec=P(None, None),
               has_live: bool = False) -> Callable:
    """shard_map a step(data, [prior,] state, key[, live]) -> (state, aux)
    function.

    data is row-sharded over ``axes``; state/key/prior replicated; outputs
    replicated (the psum/replicated-solve structure guarantees it).
    ``prior_spec`` is the (pytree of) replicated spec(s) for the prior
    slot — a single (N, N) Gram for exact KRN, or the Nystrom
    (landmarks, projection) pair.

    ``has_live`` appends a liveness-vector slot: a (num_shards,) fp32
    array sharded over the data axes like the rows, so each shard
    receives its own scalar weight and the step's reductions renormalize
    around dropped replicas (``stats.preduce``). An all-ones vector is
    bitwise the plain psum, so the solver passes it unconditionally on
    the mesh path.
    """
    dspec = P(tuple(axes))
    data_specs = SVMData(X=P(tuple(axes), None), target=dspec, mask=dspec)
    in_specs = ((data_specs, prior_spec, state_spec, P(None)) if has_prior
                else (data_specs, state_spec, P(None)))
    if has_live:
        in_specs = in_specs + (dspec,)
    out_specs = (state_spec, P())  # P() = replicated scalars in the aux dict

    wrapped = shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    return jax.jit(wrapped)


def live_weighted_psum(x: jnp.ndarray, live: jnp.ndarray,
                       axes: Sequence[str]) -> jnp.ndarray:
    """Failure-tolerant mean-preserving reduction: sum_p live_p x_p scaled
    by P / sum_p live_p. A dead replica (live=0) drops out and the
    statistic renormalizes — the SVM's sums are over data, so this is the
    unbiased estimate the paper's stopping rule keeps working with.
    (Thin alias of ``stats.preduce(..., live=...)``, which the step
    functions call directly so the fused collectives stay fused.)"""
    from . import stats as _stats
    return _stats.preduce(x, tuple(axes), live)
