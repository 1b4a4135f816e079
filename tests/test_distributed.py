"""Multi-device distribution tests. These MUST run in subprocesses: the
host device count is locked at first jax init, and the main test process
stays single-device (see conftest note)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


HEADER = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import PEMSVM, SVMConfig
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N, K = 1037, 23
w_true = rng.normal(size=K)
X = rng.normal(size=(N, K)).astype(np.float32)
y = np.where(X @ w_true + 0.3 * rng.normal(size=N) > 0, 1.0, -1.0)
"""


def test_sharded_em_single_step_exact():
    run_with_devices(HEADER + """
cfg = SVMConfig(max_iters=1, min_iters=1)
r1 = PEMSVM(cfg).fit(X, y)
r8 = PEMSVM(cfg, mesh=mesh).fit(X, y)
np.testing.assert_allclose(r8.weights, r1.weights, rtol=1e-4, atol=1e-5)
""")


def test_sharded_em_convergence_agreement():
    run_with_devices(HEADER + """
cfg = SVMConfig(max_iters=40)
r1 = PEMSVM(cfg).fit(X, y)
s8 = PEMSVM(cfg, mesh=mesh); r8 = s8.fit(X, y)
rel = abs(r1.objective[-1] - r8.objective[-1]) / abs(r1.objective[-1])
# fp32 reduction-order divergence compounds over 40 iterations; the
# emulated-device CPU backend needs a slightly looser band than TPU.
assert rel < 2e-2, rel
assert s8.score(X, y) > 0.95
""")


def test_sharded_triangle_vs_dense_reduce_equal():
    run_with_devices(HEADER + """
a = PEMSVM(SVMConfig(max_iters=5, min_iters=1, triangle_reduce=True),
           mesh=mesh).fit(X, y)
b = PEMSVM(SVMConfig(max_iters=5, min_iters=1, triangle_reduce=False),
           mesh=mesh).fit(X, y)
np.testing.assert_allclose(a.weights, b.weights, rtol=1e-3, atol=1e-4)
""")


def test_sharded_compressed_reduce_needs_coarser_clamp():
    """bf16 compressed reduction: parity at gamma clamp >= 1e-3; at 1e-6
    the 1/gamma dynamic range (1e6) exceeds the 8-bit mantissa and the
    solve collapses (EXPERIMENTS.md §Perf A4)."""
    run_with_devices(HEADER + """
a = PEMSVM(SVMConfig(max_iters=30, eps=1e-3), mesh=mesh)
b = PEMSVM(SVMConfig(max_iters=30, eps=1e-3, reduce_dtype="bfloat16"),
           mesh=mesh)
a.fit(X, y); b.fit(X, y)
assert abs(a.score(X, y) - b.score(X, y)) < 0.02, (
    a.score(X, y), b.score(X, y))
# regression: the documented failure mode at the default tight clamp
c = PEMSVM(SVMConfig(max_iters=30, eps=1e-6, reduce_dtype="bfloat16"),
           mesh=mesh)
c.fit(X, y)
assert c.score(X, y) < 0.9   # collapses -> do NOT use bf16 with eps=1e-6
""")


def test_k_shard_two_dimensional_statistic():
    run_with_devices(HEADER + """
Xp = np.concatenate([X, np.ones((N, 1), np.float32)], 1)
base = PEMSVM(SVMConfig(max_iters=30, add_bias=False)).fit(Xp, y)
ks = PEMSVM(SVMConfig(max_iters=30, add_bias=False, k_shard_axis="model"),
            mesh=mesh, data_axes=("data",)).fit(Xp, y)
rel = abs(base.objective[-1] - ks.objective[-1]) / abs(base.objective[-1])
assert rel < 1e-2, rel
""")


def test_sharded_mc_mlt_svr_krn():
    run_with_devices(HEADER + """
mc = PEMSVM(SVMConfig(algorithm="MC", max_iters=40), mesh=mesh)
mc.fit(X, y); assert mc.score(X, y) > 0.93
M = 3
Wt = rng.normal(size=(M, K))
labels = np.argmax(X @ Wt.T, axis=1).astype(np.int32)
m = PEMSVM(SVMConfig(algorithm="MC", task="MLT", num_classes=M,
                     max_iters=30), mesh=mesh)
m.fit(X, labels); assert m.score(X, labels) > 0.9
ys = (X @ w_true).astype(np.float32)
s = PEMSVM(SVMConfig(task="SVR", lam=0.1, max_iters=30), mesh=mesh)
s.fit(X, ys); assert s.rmse(X, ys) < 0.1
r_ = np.concatenate([rng.uniform(0, 1, 150), rng.uniform(1.5, 2.5, 150)])
th = rng.uniform(0, 2 * np.pi, 300)
Xc = np.stack([r_ * np.cos(th), r_ * np.sin(th)], 1).astype(np.float32)
yc = np.concatenate([np.ones(150), -np.ones(150)]).astype(np.float32)
k = PEMSVM(SVMConfig(formulation="KRN", lam=0.1, sigma=0.7, max_iters=30),
           mesh=mesh)
k.fit(Xc, yc); assert k.score(Xc, yc) > 0.97
""", timeout=900)


def test_krn_mc_chain_is_mesh_layout_invariant():
    """KRN MC gamma draws are keyed per GLOBAL row (PR-3, mirroring the
    LIN rowwise keying of PR-2): a mesh fit draws the SAME gamma chain
    as the single-device one. The assertion target is the first
    iteration's gamma_mean — margins are exactly 0 at omega = 0, so the
    draws are bitwise-identical iff the keying is layout-invariant; the
    pre-fix per-axis key folds shifted it by O(1/sqrt(N)). (Weight-level
    parity is NOT testable for KRN: the near-singular lam*K + S solve
    amplifies psum-reordering noise to O(1), same reason the EM mesh
    test gates on score.) N = 320 divides both layouts' padding chunks
    (8 and 64) so the two runs see identical padded shapes."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import PEMSVM, SVMConfig
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N = 320
r_ = np.concatenate([rng.uniform(0, 1, N // 2),
                     rng.uniform(1.5, 2.5, N // 2)])
th = rng.uniform(0, 2 * np.pi, N)
X = np.stack([r_ * np.cos(th), r_ * np.sin(th)], 1).astype(np.float32)
y = np.concatenate([np.ones(N // 2), -np.ones(N // 2)]).astype(np.float32)
cfg = SVMConfig(formulation="KRN", algorithm="MC", lam=0.1, sigma=0.7,
                burnin=0, max_iters=1, min_iters=1)
r1 = PEMSVM(cfg).fit(X, y)
r8 = PEMSVM(cfg, mesh=mesh).fit(X, y)
assert r1.weights.shape == r8.weights.shape, (r1.weights.shape,
                                              r8.weights.shape)
g1 = r1.aux_history["gamma_mean"][0]
g8 = r8.aux_history["gamma_mean"][0]
np.testing.assert_allclose(g8, g1, rtol=1e-5)
np.testing.assert_allclose(r8.objective[0], r1.objective[0], rtol=1e-4)
""")


def test_nystrom_mesh_matches_single_device():
    """The phi-space delegate on a mesh: raw rows are sharded, the
    featurizer arrays ride the replicated prior slot, and the EM fit
    matches the single-device one."""
    run_with_devices("""
import jax
import numpy as np
from repro.core import NystromSVM, SVMConfig
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
N, D = 1024, 12
X = rng.normal(size=(N, D)).astype(np.float32)
wt = rng.normal(size=D)
y = np.where(np.tanh(X @ wt) + 0.3 * rng.normal(size=N) > 0,
             1.0, -1.0).astype(np.float32)
cfg = SVMConfig(formulation="KRN", lam=1.0, sigma=3.0, eps=1e-2,
                max_iters=10, min_iters=10)
a = NystromSVM(cfg, n_landmarks=32)
r1 = a.fit(X, y)
b = NystromSVM(cfg, mesh=mesh, data_axes=("data", "model"),
               n_landmarks=32)
r8 = b.fit(X, y)
rel = np.abs(r8.weights - r1.weights).max() / np.abs(r1.weights).max()
assert rel < 1e-3, rel
assert abs(a.score(X, y) - b.score(X, y)) < 1e-2
""")


def test_k_shard_indivisible_K_raises():
    """K=23 over a model axis of 2: _k_block must raise, not silently
    drop the trailing column of Sigma."""
    run_with_devices(HEADER + """
try:
    PEMSVM(SVMConfig(max_iters=2, min_iters=1, add_bias=False,
                     k_shard_axis="model"),
           mesh=mesh, data_axes=("data",)).fit(X, y)
except ValueError as e:
    assert "does not divide" in str(e), e
else:
    raise SystemExit("expected ValueError for K=23 over 2-way model axis")
""")


def test_live_weighted_psum_drops_dead_replica():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.distributed import live_weighted_psum
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
def f(x, live):
    return live_weighted_psum(x, live, ("data",))
g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=P("data"), check_vma=False))
x = jnp.arange(8.0)          # one value per replica
live = jnp.ones(8).at[3].set(0.0)   # replica 3 died
out = np.asarray(g(x, live))
# unbiased mean-preserving: sum of the 7 live values * 8/7
want = (x.sum() - 3.0) * 8.0 / 7.0
np.testing.assert_allclose(out, want, rtol=1e-6)
""")


def test_elastic_remesh_roundtrip():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.runtime import remesh, scale_batch_schedule
m1 = jax.make_mesh((8,), ("data",),
                   axis_types=(jax.sharding.AxisType.Auto,))
m2 = jax.make_mesh((4, 2), ("data", "model"),
                   axis_types=(jax.sharding.AxisType.Auto,) * 2)
tree = {"w": jnp.arange(64.0).reshape(8, 8)}
t1 = jax.device_put(tree, NamedSharding(m1, P("data", None)))
t2 = remesh(t1, {"w": NamedSharding(m2, P("model", "data"))})
np.testing.assert_allclose(np.asarray(t2["w"]),
                           np.arange(64.0).reshape(8, 8))
gb, lr = scale_batch_schedule(256, 8, 4, keep_global=True)
assert (gb, lr) == (256, 1.0)
gb, lr = scale_batch_schedule(256, 8, 16, keep_global=False)
assert gb == 512 and lr == 2.0
""")


def test_seq_parallel_attention_matches_blockwise():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.models.attention import blockwise_attn, seq_parallel_attention
from repro.sharding import ShardingCtx
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                  fsdp_axis="data")
key = jax.random.PRNGKey(0)
B, S, H, KVH, dh = 2, 64, 3, 3, 16   # H=3: not divisible by model axis
q = jax.random.normal(key, (B, S, H, dh))
k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, dh))
v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, dh))
ref = blockwise_attn(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
with jax.set_mesh(mesh):
    got = jax.jit(lambda a, b, c: seq_parallel_attention(
        ctx, a, b, c, causal=True, q_chunk=16, kv_chunk=16))(q, k, v)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4,
                           atol=2e-4)
print("seq-parallel attention OK")
""")


def test_decode_island_matches_dense_decode():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.models.attention import decode_attn, decode_attn_island
from repro.sharding import ShardingCtx
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                  fsdp_axis="data")
key = jax.random.PRNGKey(0)
B, S, H, KVH, dh = 4, 32, 4, 2, 8
pos = 17
kc = jax.random.normal(key, (B, S, KVH, dh))
vc = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, dh))
q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, dh))
kn = jax.random.normal(jax.random.PRNGKey(3), (B, 1, KVH, dh))
vn = jax.random.normal(jax.random.PRNGKey(4), (B, 1, KVH, dh))
# dense reference
kc_ref = jax.lax.dynamic_update_slice_in_dim(kc, kn, pos, axis=1)
vc_ref = jax.lax.dynamic_update_slice_in_dim(vc, vn, pos, axis=1)
ref = decode_attn(q, kc_ref, vc_ref, pos + 1)
with jax.set_mesh(mesh):
    o, kc2, vc2 = jax.jit(lambda *a: decode_attn_island(ctx, *a))(
        q, kc, vc, jnp.int32(pos), kn, vn)
np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=2e-4,
                           atol=2e-4)
np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref), rtol=1e-5)
print("decode island OK")
""")
