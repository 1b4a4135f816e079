"""The main path's Pallas kernels compile for a TPU v5e.

Nothing runs here: each case lowers the kernel call exactly as
``kernels/ops.py`` dispatches it on a TPU (``backend="pallas"``) and
compiles it for one chip of a *described* v5e:2x2 topology, so the TPU
compiler's own refusals (unsupported casts, unlowerable primitives,
scoped-VMEM overflow) fail here instead of on the chip. The topology
is described inside a fixture, never at import, so that parallel test
workers collect the same tests and only the worker running this file
loads the TPU compiler.
"""
import os

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
