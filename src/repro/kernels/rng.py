"""Counter-based RNG for in-kernel Gibbs noise (DESIGN.md §Perf).

The MC epilogues need two uniform streams per row (``nu`` -> N(0,1) via
inverse-CDF, ``u`` -> U(0,1)) per inverse-Gaussian mixture.  Instead of
pre-drawing them on the host and streaming (N,) operands into the fused
kernels, we derive the bits on the fly from a stateless counter cipher:

    bits = threefry2x32(k0, k1,
                        c0 = global_row,
                        c1 = chain_id * 4 + mixture_word)

``(k0, k1)`` are the raw 32-bit words of the per-iteration PRNG subkey
(per-class ``fold_in`` for MLT happens before the words are extracted),
``global_row = shard_row_offset + chunk_row0 + tile_row`` and
``mixture`` is 0 for the gamma draw and 1 for the SVR omega draw.  The
counter fixes the draw for a (seed, row, chain, iteration) coordinate,
so the stream is chunking-, sharding- and mesh-layout-invariant by
construction, and C chains are C counter planes over one X stream.

Everything here is plain uint32/float32 ``jnp`` arithmetic -- the SAME
code runs on the host (the materialized-noise oracle, ``rng mode
'fused_predraw'``), in the ``ref`` path, and inside Pallas kernel
bodies, which is what makes the in-kernel draws *bitwise* equal to the
oracle.  We deliberately do NOT use ``pltpu.prng_random_bits``: the TPU
hardware generator cannot be replayed bit-exactly on the host, and the
whole verification story (and elastic resume) rests on replayability.

Bitwise stability across EVAL CONTEXTS (eager vs jit vs kernel body) is
load-bearing and shapes the float pipeline: under jit XLA contracts
``a * b + c`` into an FMA, while op-by-op eager execution cannot, so
any polynomial (Horner) evaluation would round differently inside a
jitted kernel than in an eager oracle call.  The bits->float maps below
therefore use only single-primitive transcendentals (log, sqrt, cos)
joined by bare multiplies -- Box-Muller for the normal, never an
erfinv polynomial -- leaving nothing for the compiler to contract.

This module must stay import-free of ``repro.core`` (kernel layer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_U32 = jnp.uint32
# Threefry-2x32, 20 rounds: 5 groups of 4 with alternating rotation
# schedules and a key injection after each group.
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA
_TWO_PI = 6.283185307179586


def _rotl(x, d: int):
    return (x << _U32(d)) | (x >> _U32(32 - d))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32 block cipher (20 rounds), pure uint32 jnp ops.

    ``k0``/``k1`` are uint32 key words; ``c0``/``c1`` uint32 counter
    words (scalars or arrays, broadcast together).  Returns the two
    uint32 output words.  Runs identically on host, ref and Pallas
    backends -- no primitive RNG involved.
    """
    k0 = jnp.asarray(k0, _U32)
    k1 = jnp.asarray(k1, _U32)
    ks = (k0, k1, k0 ^ k1 ^ _U32(_PARITY))
    x0 = jnp.asarray(c0, _U32) + ks[0]
    x1 = jnp.asarray(c1, _U32) + ks[1]
    for i in range(5):
        for d in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, d)
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + _U32(i + 1)
    return x0, x1


def uniform_from_bits(bits):
    """uint32 bits -> f32 uniform, strictly inside (0, 1).

    Uses the top 23 bits so that ``int + 0.5`` stays exactly
    representable in f32 (24-bit significand): the result is
    ``(i + 0.5) * 2^-23`` for i in [0, 2^23), i.e. in
    [2^-24, 1 - 2^-24] -- never 0 or 1, so the Box-Muller log below
    stays finite.  ``(i + 0.5) * c`` is add-then-mul, not an FMA shape.
    The int -> float conversion goes through int32 because the TPU
    kernel compiler has no uint32 -> float32 cast; i < 2^23, so the
    detour is exact.
    """
    i = (bits >> _U32(9)).astype(jnp.int32).astype(jnp.float32)
    return (i + jnp.float32(0.5)) * jnp.float32(2.0 ** -23)


def normal_from_bits(bits0, bits1):
    """Two uint32 words -> one f32 standard normal via Box-Muller.

    nu = sqrt(-2 ln u1) * cos(2 pi u2).  Only single-primitive
    transcendentals joined by bare multiplies (module docstring: no
    ``a*b + c`` pattern the compiler could FMA-contract), so the value
    is bitwise identical in eager, jit and kernel-body evaluation.
    u1 is bounded away from 0 (``uniform_from_bits``), so the log and
    the result stay finite: |nu| <= sqrt(-2 ln 2^-24) ~ 5.77.
    """
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(uniform_from_bits(bits0)))
    return r * jnp.cos(jnp.float32(_TWO_PI) * uniform_from_bits(bits1))


def counter_noise(k0, k1, rows, chains, n_noise: int):
    """The (nu, u[, nu_o, u_o]) tuple for given row/chain coordinates.

    ``rows``/``chains`` are int32 (arrays or scalars, broadcastable);
    ``n_noise`` is the epilogue's noise arity (2 for the single gamma
    mixture, 4 for SVR's gamma+omega double mixture).  Mixture m uses
    counter words ``c1 = chain*4 + 2m`` (both cipher output words feed
    the Box-Muller normal) and ``c1 = chain*4 + 2m + 1`` (word 0 is the
    accept-reject uniform).  Pure elementwise math, so the values are
    bitwise identical whether evaluated on (N,) host rows, (bn, 1)
    kernel tiles or (bn, C) multichain tiles.
    """
    assert n_noise in (2, 4), n_noise
    rows = jnp.asarray(rows, _U32)
    out = []
    for m in range(n_noise // 2):
        base = (jnp.asarray(chains, _U32) << _U32(2)) | _U32(2 * m)
        n0, n1 = threefry2x32(k0, k1, rows, base)
        u0, _ = threefry2x32(k0, k1, rows, base | _U32(1))
        out.append(normal_from_bits(n0, n1))
        out.append(uniform_from_bits(u0))
    return tuple(out)


def key_words(key):
    """Raw (k0, k1) uint32 words of a JAX PRNG key (typed or legacy)."""
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    key = jnp.asarray(key)
    return key[..., 0].astype(_U32), key[..., 1].astype(_U32)


def pack_seed(key, row0=0, chain0=0):
    """(4,) uint32 seed operand [k0, k1, row0, chain0] for the kernels.

    ``row0``/``chain0`` may be traced (shard row offsets are); they are
    carried as uint32 and re-interpreted as int32 inside the kernel, so
    the packing is exact for any non-negative 31-bit offset.
    """
    k0, k1 = key_words(key)
    return jnp.stack([
        k0, k1,
        jnp.asarray(row0, jnp.int32).astype(_U32),
        jnp.asarray(chain0, jnp.int32).astype(_U32),
    ])


def tile_noise(seed, tile_row0, shape, n_noise: int):
    """Noise tuple for one (bn, C) kernel tile.

    ``seed`` is the unpacked (4,) uint32 seed (indexable: a loaded SMEM
    ref or a host array); ``tile_row0`` the tile's first row relative
    to the operand (caller adds ``program_id * block_n``).  Row ids use
    a 2-D broadcasted iota over dim 0 and chain ids over dim 1 (TPU
    requires >= 2-D iota).
    """
    rows = (seed[2].astype(jnp.int32) + tile_row0
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    chains = (seed[3].astype(jnp.int32)
              + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    return counter_noise(seed[0], seed[1], rows, chains, n_noise)


def draw_fused_noise(key, n: int, row0=0, chain=0, n_noise: int = 2):
    """Host materialization of the counter stream (the bitwise oracle).

    Returns ``n_noise`` arrays of shape (n,): exactly the values the
    fused kernels generate in-body for rows [row0, row0 + n) of chain
    ``chain`` -- rng mode 'fused_predraw' feeds these through the
    legacy (N,) operand path to pin whole-fit bitwise parity.
    """
    k0, k1 = key_words(key)
    rows = jnp.asarray(row0, jnp.int32) + jnp.arange(n, dtype=jnp.int32)
    return counter_noise(k0, k1, rows, jnp.asarray(chain, jnp.int32),
                         n_noise)
