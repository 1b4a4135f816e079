"""The main path's Pallas kernels compile for a TPU v5e.

Nothing runs here: each case lowers the kernel call exactly as
``kernels/ops.py`` dispatches it on a TPU (``backend="pallas"``) and
compiles it for one chip of a *described* v5e:2x2 topology, so the TPU
compiler's own refusals (unsupported casts, unlowerable primitives,
scoped-VMEM overflow) fail here instead of on the chip. The topology
is described inside a fixture, never at import, so that parallel test
workers collect the same tests and only the worker running this file
loads the TPU compiler. The scan driver's chunk program is compiled
the same way, at a chip's real row count, to read which ops of it write
an array of X's size.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 8192          # rows; compile time does not depend on it


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _stats_call(one_chip, K, epilogue, *, seed=False, col_blk=None):
    """(fn, shapes) for ops.fused_stats at width K."""
    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = [sd((N, K)), sd((N,)), sd((N,)), sd((K,))]
    n_noise = 0 if seed else {"mc_hinge": 2, "mc_svr": 4}.get(epilogue, 0)
    shapes += [sd((4,), jnp.uint32)] if seed else [sd((N,))] * n_noise

    def fn(X, rho, beta, w, *rest):
        window = None if col_blk is None else (jnp.int32(col_blk), col_blk)
        return ops.fused_stats(
            X, rho, beta, w, None, tuple(rest) if n_noise else None,
            epilogue=epilogue, eps_ins=0.3, col_window=window,
            seed=rest[0] if seed else None, backend="pallas")
    return fn, shapes


@pytest.mark.parametrize("K,epilogue,seed,col_blk", [
    (801, "em_hinge", False, None),     # dna + bias, LIN-EM
    (801, "mc_hinge", True, None),      # dna, in-kernel counter RNG
    (91, "mc_svr", True, None),         # year + bias, SVR double mixture
    (800, "em_hinge", False, 400),      # 2-D k-shard column window
    (1536, "em_hinge", False, None),    # past the cap: split fallback
    (1536, "mc_svr", True, None),
])
def test_fused_stats_compiles(one_chip, K, epilogue, seed, col_blk):
    fn, shapes = _stats_call(one_chip, K, epilogue, seed=seed,
                             col_blk=col_blk)
    assert "tpu_custom_call" in _compiled_hlo(fn, *shapes)


def test_fits_boundary_compiles(one_chip):
    """The largest K the VMEM accounting admits compiles as ONE fused
    kernel, and one lane-tile more is refused by the accounting."""
    K = ops.FUSED_STATS_MAX_K
    assert ops.fused_stats_fits(K) and not ops.fused_stats_fits(K + 128)
    fn, shapes = _stats_call(one_chip, K, "em_hinge")
    assert _compiled_hlo(fn, *shapes).count("tpu_custom_call") == 1


def test_fits_boundary_compiles_at_highest(one_chip):
    """At HIGHEST (the Nystrom fallback's statistic) the widest K the
    accounting admits compiles as ONE kernel under the default limit,
    and one lane-tile more is refused by the accounting."""
    hi = jax.lax.Precision.HIGHEST
    K = max(k for k in range(128, 4096, 128)
            if ops.fused_stats_fits(k, precision=hi))
    assert not ops.fused_stats_fits(K + 128, precision=hi)

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fn(X, rho, beta, w):
        return ops.fused_stats(X, rho, beta, w, precision=hi,
                               backend="pallas")
    hlo = _compiled_hlo(fn, sd((N, K)), sd((N,)), sd((N,)), sd((K,)))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("D", [54, 3072])
def test_rbf_gram_compiles(one_chip, D):
    """The exact-KRN Gram kernel, whose tile runs its cross dot at
    HIGHEST: covtype's width, and the widest lane multiple it compiled
    for at the default precision under the default limit."""
    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fn(X1, X2):
        return ops.rbf_gram(X1, X2, sigma=1.0, backend="pallas")
    assert "tpu_custom_call" in _compiled_hlo(fn, sd((N, D)), sd((N, D)))


@pytest.mark.parametrize("seed", [False, True])
def test_nystrom_fused_stats_compiles(one_chip, seed):
    m, D = 512, 784

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = [sd((N, D)), sd((m, D)), sd((m, m)), sd((N,)), sd((N,)),
              sd((m + 1,)), sd((N,))] + ([sd((4,), jnp.uint32)] if seed
                                         else [])

    def fn(X, lm, pj, rho, beta, w, mask, *rest):
        return ops.nystrom_fused_stats(
            X, lm, pj, rho, beta, w, mask, None, sigma=1.0, add_bias=True,
            epilogue="mc_hinge" if seed else "em_hinge",
            seed=rest[0] if seed else None, backend="pallas")
    assert ops.nystrom_fused_fits(m, D, epilogue="mc_hinge", rng=seed)
    assert "tpu_custom_call" in _compiled_hlo(fn, *shapes)


def test_nystrom_score_compiles(one_chip):
    m, D, tile = 512, 500, 128

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fn(X, lm, pj, W, mask):
        return ops.nystrom_score(X, lm, pj, W, mask, sigma=1.0,
                                 add_bias=True, backend="pallas",
                                 block_n=tile)
    hlo = _compiled_hlo(fn, sd((tile, D)), sd((m, D)), sd((m, m)),
                        sd((m + 1, 1)), sd((tile,)))
    assert "tpu_custom_call" in hlo


def _nystrom_call(one_chip, m, D):
    """(fn, shapes) for ops.nystrom_fused_stats, LIN-EM in phi-space
    with the phi bias, over N rows of width D against m landmarks."""
    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fn(X, lm, pj, rho, beta, w, mask):
        return ops.nystrom_fused_stats(X, lm, pj, rho, beta, w, mask,
                                       sigma=1.0, add_bias=True,
                                       backend="pallas")
    return fn, [sd((N, D)), sd((m, D)), sd((m, m)), sd((N,)), sd((N,)),
                sd((m + 1,)), sd((N,))]


def test_nystrom_kernels_compile_at_covtype(one_chip):
    """covtype.binary's shape, m = ceil(sqrt(522,910)) = 724 landmarks
    over D = 54: the fused statistic (every dot at HIGHEST) is one
    kernel, and the score kernel, which shares the RBF tile, compiles
    too."""
    m, D = 724, 54
    assert ops.nystrom_fused_fits(m, D)
    fn, shapes = _nystrom_call(one_chip, m, D)
    assert _compiled_hlo(fn, *shapes).count("tpu_custom_call") == 1

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def score(X, lm, pj, W, mask):
        return ops.nystrom_score(X, lm, pj, W, mask, sigma=1.0,
                                 add_bias=True, backend="pallas")
    assert ops.nystrom_score_fits(m, D, 1, add_bias=True)
    assert "tpu_custom_call" in _compiled_hlo(
        score, sd((N, D)), sd((m, D)), sd((m, m)), sd((m + 1, 1)),
        sd((N,)))


@pytest.mark.parametrize("m", [724, ops.NYSTROM_FUSED_MAX_M])
def test_nystrom_fits_boundary_compiles(one_chip, m):
    """The widest D the VMEM accounting admits at m landmarks compiles
    as ONE fused kernel under the kernels' raised limit, and one
    lane-tile more is refused by the accounting."""
    D = max(d for d in range(128, 8192, 128)
            if ops.nystrom_fused_fits(m, d))
    assert not ops.nystrom_fused_fits(m, D + 128)
    fn, shapes = _nystrom_call(one_chip, m, D)
    assert _compiled_hlo(fn, *shapes).count("tpu_custom_call") == 1


@pytest.mark.parametrize("m,D,kernels", [
    (512, 3200, 2),     # wide D: phi kernel, then the LIN kernel on phi
    (724, 1920, 2),     # phi kernel, then the split pair (SYRK kernel)
    (1100, 54, 1),      # past the landmark cap: XLA phi, split pair
])
def test_nystrom_fallback_compiles(one_chip, m, D, kernels):
    """Past the fused budget the featurize-then-accumulate fallback,
    every dot at HIGHEST, compiles on each of its routes."""
    assert not ops.nystrom_fused_fits(m, D)
    fn, shapes = _nystrom_call(one_chip, m, D)
    assert _compiled_hlo(fn, *shapes).count("tpu_custom_call") == kernels


ROWS = 524_288    # mnist8m-fit's rows on one chip
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_ARRAY_OP = re.compile(
    r"^(?:ROOT )?%(\S+) = f32\[(\d+),(\d+)\]\{[^}]*\} ([\w\-]+)\(")


def x_sized_writes(hlo: str, rows: int, widths) -> list[str]:
    """The ops of a compiled module that write an f32 (rows, width)
    array, for any width in ``widths``: every op outside a fused
    computation, other than a parameter, a tuple element or a bitcast,
    whose result is such an array."""
    comps: dict[str, list[str]] = {}
    fused, cur = set(), None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m[1], [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    return [f"{m[4]} {m[1]}" for name, lines in comps.items()
            if name not in fused for m in map(_ARRAY_OP.match, lines)
            if m and int(m[2]) == rows and int(m[3]) in widths
            and m[4] not in ("parameter", "get-tuple-element", "bitcast")]


def _chunk_program(one_chip, task, K, width) -> str:
    """The scan driver's chunk program (``solver._chunk_runner``) for a
    resident LIN EM fit on one chip, with the model's X stored
    ``width`` wide, compiled."""
    from repro.core import SVMConfig, solver

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    M = 10 if task == "MLT" else 2
    cfg = SVMConfig(task=task, num_classes=M, backend="pallas",
                    max_iters=4, scan_chunk=2)
    runner = solver._chunk_runner(cfg, None, (), False, False)
    state = (M, K) if task == "MLT" else (K,)
    target = jnp.int32 if task == "MLT" else jnp.float32
    data = solver.SVMData(sd((ROWS, width)), sd((ROWS,), target),
                          sd((ROWS,)))
    carry = (sd(state), sd(state), sd((), jnp.int32), sd((2,), jnp.uint32),
             sd(()), sd((), jnp.int32), sd((), jnp.bool_),
             sd((), jnp.int32))
    return _compiled_hlo(runner, data, None, carry, sd((2,), jnp.int32),
                         sd(()))


@pytest.mark.parametrize("task,K", [("CLS", 801), ("MLT", 785)])
def test_chunk_program_reads_lane_width_x_in_place(one_chip, task, K):
    """dna's and mnist8m's widths with X stored at the lane width, as
    ``distributed.upload_rows`` stores it: the kernel reads X in place,
    and no op writes an array of X's size (no pad, copy or transpose of
    X; MLT's score reads X[:, :K] through a bitcast)."""
    hlo = _chunk_program(one_chip, task, K, 896)
    assert "tpu_custom_call" in hlo
    assert x_sized_writes(hlo, ROWS, {896, K}) == []


def test_chunk_program_copies_a_k_wide_x(one_chip):
    """X stored at mnist8m's K = 785: the compiler lays the parameter
    out column-major, copies it row-major and pads it to 896 on every
    kernel call. The check above has to see those copies."""
    hlo = _chunk_program(one_chip, "MLT", 785, 785)
    writes = x_sized_writes(hlo, ROWS, {896, 785})
    assert any(w.startswith("copy ") for w in writes), writes
    assert any(w.startswith("pad ") for w in writes), writes
