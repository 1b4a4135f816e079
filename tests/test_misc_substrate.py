"""Data pipeline, sharding rules, runtime monitor, objective properties,
HLO cost analyzer, head pooling."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

import pytest

from repro.core import head, objective
from repro.data import (ShardedBatcher, iter_libsvm, load_libsvm,
                        make_lm_tokens, save_libsvm)
from repro.launch.hlo_cost import analyze
from repro.runtime import StepTimeMonitor
from repro.sharding import ShardingCtx, param_spec


# ------------------------------------------------------------------- data
def test_libsvm_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    X = (rng.random((20, 6)) * (rng.random((20, 6)) > 0.5)).astype(
        np.float32)
    y = rng.choice([-1.0, 1.0], 20)
    p = str(tmp_path / "d.txt")
    save_libsvm(p, X, y)
    X2, y2 = load_libsvm(p, n_features=6)
    np.testing.assert_allclose(X2, X, atol=1e-5)
    np.testing.assert_allclose(y2, y)


def test_libsvm_striped_ranks(tmp_path):
    rng = np.random.default_rng(1)
    X = rng.random((10, 3)).astype(np.float32)
    y = np.ones(10)
    p = str(tmp_path / "d.txt")
    save_libsvm(p, X, y)
    parts = [load_libsvm(p, n_features=3, rank=r, world=2)[0]
             for r in range(2)]
    assert parts[0].shape[0] + parts[1].shape[0] == 10
    np.testing.assert_allclose(np.sort(np.vstack(parts), axis=0),
                               np.sort(X, axis=0), atol=1e-5)


def test_batcher_deterministic_and_seekable():
    stream = make_lm_tokens(50_000, 128, seed=0)
    b1 = ShardedBatcher(stream, 4, 64, seed=1)
    it = iter(b1)
    batches = [next(it) for _ in range(3)]
    b2 = ShardedBatcher(stream, 4, 64, seed=1)
    b2.seek(2)
    t2, l2 = next(iter(b2))
    np.testing.assert_array_equal(np.asarray(batches[2][0]), np.asarray(t2))
    # next-token alignment
    np.testing.assert_array_equal(np.asarray(batches[0][0][:, 1:]),
                                  np.asarray(batches[0][1][:, :-1]))


def test_libsvm_tolerates_comments_and_blanks(tmp_path):
    p = str(tmp_path / "d.txt")
    with open(p, "w") as f:
        f.write("# a header comment\n"
                "\n"
                "1 1:0.5 3:2.0   # trailing comment\n"
                "   \n"
                "-1 2:1.25\n")
    X, y = load_libsvm(p, n_features=3)
    np.testing.assert_allclose(y, [1.0, -1.0])
    np.testing.assert_allclose(X, [[0.5, 0.0, 2.0], [0.0, 1.25, 0.0]])


@pytest.mark.parametrize("bad,msg", [
    ("1 2:0.5 3\n", "malformed 'idx:val' token '3'"),
    ("1 x:0.5\n", "malformed 'idx:val' token 'x:0.5'"),
    ("1 2:abc\n", "malformed 'idx:val' token '2:abc'"),
    ("spam 1:1\n", "label 'spam'"),
    ("1 0:1\n", "feature index 0 out of range"),
])
def test_libsvm_malformed_tokens_raise_clear_errors(tmp_path, bad, msg):
    p = str(tmp_path / "d.txt")
    with open(p, "w") as f:
        f.write("1 1:1.0\n" + bad)
    with pytest.raises(ValueError, match="line 2"):
        load_libsvm(p, n_features=3)
    try:
        load_libsvm(p, n_features=3)
    except ValueError as e:
        assert msg in str(e), e


def test_iter_libsvm_chunks_match_load(tmp_path):
    """Chunked reader == resident loader: concatenated valid rows are
    identical, every block has the fixed shape, tail is masked."""
    rng = np.random.default_rng(5)
    X = (rng.random((23, 4)) * (rng.random((23, 4)) > 0.4)).astype(
        np.float32)
    y = rng.choice([-1.0, 1.0], 23)
    p = str(tmp_path / "d.txt")
    save_libsvm(p, X, y)
    blocks = list(iter_libsvm(p, chunk_rows=7, n_features=4))
    assert len(blocks) == 4
    assert all(b[0].shape == (7, 4) for b in blocks)
    mask = np.concatenate([b[2] for b in blocks])
    assert mask.sum() == 23 and blocks[-1][2].sum() == 2  # 23 = 3*7 + 2
    Xc = np.concatenate([b[0] for b in blocks])[mask > 0]
    yc = np.concatenate([b[1] for b in blocks])[mask > 0]
    Xr, yr = load_libsvm(p, n_features=4)
    np.testing.assert_allclose(Xc, Xr, atol=1e-5)
    np.testing.assert_allclose(yc, yr)
    # padded rows are exact zeros (the stats no-op convention)
    assert np.all(blocks[-1][0][2:] == 0.0) and np.all(
        blocks[-1][1][2:] == 0.0)


def test_iter_libsvm_striped_ranks(tmp_path):
    rng = np.random.default_rng(6)
    X = rng.random((10, 3)).astype(np.float32)
    p = str(tmp_path / "d.txt")
    save_libsvm(p, X, np.ones(10))
    parts = []
    for r in range(2):
        for Xb, yb, mb in iter_libsvm(p, 4, 3, rank=r, world=2):
            parts.append(Xb[mb > 0])
    got = np.sort(np.vstack(parts), axis=0)
    np.testing.assert_allclose(got, np.sort(X, axis=0), atol=1e-5)


def test_batcher_seek_mid_iteration_discards_stale_prefetch():
    """Regression: seek() after the iterator started must not yield
    already-prefetched stale steps — resume must be deterministic."""
    stream = make_lm_tokens(50_000, 128, seed=0)
    ref = ShardedBatcher(stream, 4, 64, seed=1)
    it_ref = iter(ref)
    want = [np.asarray(next(it_ref)[0]) for _ in range(4)]

    b = ShardedBatcher(stream, 4, 64, seed=1, prefetch=3)
    it = iter(b)
    for _ in range(3):
        next(it)               # worker has prefetched steps ~3..5 already
    b.seek(0)                  # checkpoint-restore semantics
    got = np.asarray(next(it)[0])
    np.testing.assert_array_equal(got, want[0])
    # and the sequence continues deterministically from there
    np.testing.assert_array_equal(np.asarray(next(it)[0]), want[1])
    assert b.step == 2


def test_lm_tokens_learnable_structure():
    s = make_lm_tokens(100_000, 512, seed=0)
    assert s.min() >= 0 and s.max() < 512
    # zipf: top-10 tokens cover a large fraction
    _, counts = np.unique(s, return_counts=True)
    assert np.sort(counts)[-10:].sum() > 0.3 * len(s)


# --------------------------------------------------------------- sharding
def test_param_spec_divisibility_filter():
    import jax as _jax
    devs = _jax.devices()
    if len(devs) < 1:
        return
    mesh = _jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                      fsdp_axis="data")
    # divisible: sharded; mesh axes are size 1 so everything divides —
    # check the orientation rules instead
    s = param_spec(ctx, "layers/attn/wq", (4, 64, 64))
    assert s == jax.sharding.PartitionSpec(None, "data", "model")
    s = param_spec(ctx, "layers/attn/wo", (4, 64, 64))
    assert s == jax.sharding.PartitionSpec(None, "model", "data")
    s = param_spec(ctx, "layers/moe/moe_up", (4, 8, 64, 32))
    assert s == jax.sharding.PartitionSpec(None, "model", "data", None)
    s = param_spec(ctx, "embed/table", (100, 64))
    assert s == jax.sharding.PartitionSpec("model", "data")


def test_spec_drops_non_divisible():
    import jax as _jax
    if len(_jax.devices()) != 1:
        return
    mesh = _jax.make_mesh((1,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis=None,
                      fsdp_axis="data")
    # everything divides by 1; exercise the API contract
    assert ctx.spec((5, 3), "data", None)[0] == "data"
    assert ctx.axis_size("data") == 1


# ---------------------------------------------------------------- runtime
def test_straggler_monitor_flags_slow_steps():
    m = StepTimeMonitor(warmup_steps=2, threshold=2.0)
    flags = [m.observe(i, t) for i, t in enumerate(
        [1.0, 1.0, 1.0, 1.0, 5.0, 1.0])]
    assert flags == [False, False, False, False, True, False]
    assert m.summary()["straggler_events"] == 1
    # EMA not poisoned by the straggler
    assert m.ema < 1.5


# -------------------------------------------------------------- objective
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 50), st.integers(0, 2 ** 20))
def test_hinge_objective_nonnegative(n, seed):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.normal(size=n).astype(np.float32))
    y = jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32))
    mask = jnp.asarray((rng.random(n) > 0.2).astype(np.float32))
    v = float(objective.hinge_obj_terms(m, y, mask))
    assert v >= 0.0
    # perfect margins -> zero loss
    assert float(objective.hinge_obj_terms(10 * y, y, mask)) == 0.0


def test_cs_objective_zero_iff_unit_margins():
    scores = jnp.asarray([[5.0, 0.0, 0.0], [0.0, 5.0, 0.0]])
    labels = jnp.asarray([0, 1])
    mask = jnp.ones(2)
    assert float(objective.cs_obj_terms(scores, labels, mask)) == 0.0
    bad = jnp.asarray([[0.0, 5.0, 0.0]])
    assert float(objective.cs_obj_terms(bad, jnp.asarray([0]),
                                        jnp.ones(1))) > 0.0


# ---------------------------------------------------------------- hlo_cost
def test_hlo_cost_counts_loop_bodies():
    M = 64

    def scanned(a, b):
        def body(x, _):
            return jnp.tanh(x @ b), None
        y, _ = jax.lax.scan(body, a, None, length=7)
        return y

    c = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
    r = analyze(c.as_text())
    exp = 7 * 2 * M ** 3
    assert 0.9 < r["flops"] / exp < 1.3, r["flops"] / exp


# -------------------------------------------------------------------- head
def test_pooling_helpers():
    h = jnp.arange(24.0).reshape(1, 4, 6)
    mask = jnp.asarray([[1.0, 1.0, 0.0, 0.0]])
    mp = head.mean_pool(h, mask)
    np.testing.assert_allclose(np.asarray(mp)[0], np.asarray(h[0, :2]).mean(0))
    lp = head.last_token_pool(h, jnp.asarray([2]))
    np.testing.assert_allclose(np.asarray(lp)[0], np.asarray(h[0, 1]))
