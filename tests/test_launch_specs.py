"""Regression tests for the dry-run spec builders (bugs found during the
sweep iterations are pinned here)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_config
from repro.launch import specs as sp
from repro.sharding import ShardingCtx


@pytest.fixture(scope="module")
def ctx1():
    # single-device mesh: divisibility checks still exercise the code
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                       fsdp_axis="data")


def test_cache_spec_never_shards_period_dim(ctx1):
    """REGRESSION: the stacked-periods dim (80 for qwen2: divisible by
    16!) once grabbed the model axis — the layer scan then gathered the
    whole cache slice every layer (22-49 GB/step observed)."""
    cfg = get_config("qwen2-vl-72b")
    specs, shards = sp.cache_specs(cfg, SHAPES["decode_32k"], ctx1)
    for leaf in jax.tree.leaves(
            shards, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec)):
        assert leaf[0] is None, f"period dim sharded: {leaf}"


def test_cache_spec_seq_over_model(ctx1):
    cfg = get_config("deepseek-67b")
    specs, shards = sp.cache_specs(cfg, SHAPES["decode_32k"], ctx1)
    leaf = jax.tree.leaves(
        shards, is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec))[0]
    # (periods, B, S, KVH, dh): B over dp, S over tp
    # (PartitionSpec normalizes 1-tuples to the bare axis name)
    assert leaf[1] in ("data", ("data",)) and leaf[2] == "model", leaf


def test_batch_specs_cover_modalities(ctx1):
    for arch, key in [("yi-34b", "tokens"), ("qwen2-vl-72b", "embeds"),
                      ("whisper-small", "frames")]:
        cfg = get_config(arch)
        specs, shards = sp.batch_specs(cfg, SHAPES["train_4k"], ctx1,
                                       with_labels=True)
        assert key in specs and "labels" in specs
        B = SHAPES["train_4k"].global_batch
        assert specs["labels"].shape == (B, 4096)


def test_opt_state_mirrors_params(ctx1):
    cfg = get_config("smollm-135m")
    pstructs, pspecs = sp.param_struct_specs(cfg, ctx1)
    ostructs, ospecs = sp.opt_state_specs(pstructs, pspecs)
    assert jax.tree.structure(ostructs["m"]) == jax.tree.structure(pstructs)
    assert jax.tree.structure(ospecs["v"]) == jax.tree.structure(pspecs)


def test_serve_param_dtype_override(ctx1):
    cfg = get_config("smollm-135m")
    pstructs, _ = sp.param_struct_specs(cfg, ctx1, dtype="bfloat16")
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(pstructs)
               if jnp.issubdtype(x.dtype, jnp.floating))
