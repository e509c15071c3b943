"""Unit tests: AdamW optimizer, schedules, compression, logical sharding
rules, and the roofline HLO collective parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel import sharding as shd
from repro.roofline.analysis import CollectiveStats, collective_bytes
from repro.train.optimizer import (AdamWConfig, apply_updates, clip_by_global_norm,
                                   compress_grads, compress_int8, decompress_int8,
                                   init_state, schedule)


# ---------------------------------------------------------------- optimizer
def test_adamw_moves_toward_minimum():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=100, min_lr_ratio=1.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_state(params, cfg)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}   # d/dw ||w||^2
        params, state, _ = apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_weight_decay_shrinks_weights():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.5, warmup_steps=0,
                      total_steps=10, min_lr_ratio=1.0)
    params = {"w": jnp.asarray([10.0])}
    state = init_state(params, cfg)
    params2, _, _ = apply_updates(params, {"w": jnp.zeros(1)}, state, cfg)
    assert float(params2["w"][0]) < 10.0


def test_grad_clip_global_norm():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    total = sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(clipped))
    assert abs(total - 1.0) < 1e-3
    assert float(norm) == pytest.approx(np.sqrt(800.0), rel=1e-5)


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    assert float(schedule(cfg, jnp.asarray(0))) == pytest.approx(0.1)
    assert float(schedule(cfg, jnp.asarray(9))) == pytest.approx(1.0)
    assert float(schedule(cfg, jnp.asarray(99))) == pytest.approx(0.1, abs=1e-2)


def test_int8_compression_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    q, s = compress_int8(g)
    back = decompress_int8(q, s, jnp.float32)
    assert float(jnp.abs(back - g).max()) <= float(s) * 0.51 + 1e-7


def test_compress_grads_tree_modes():
    g = {"a": jnp.ones((8,), jnp.float32), "b": jnp.ones((8,), jnp.bfloat16)}
    for mode in (None, "none", "bf16", "int8"):
        out = compress_grads(g, mode)
        assert jax.tree.structure(out) == jax.tree.structure(g)
        for x, y in zip(jax.tree.leaves(out), jax.tree.leaves(g)):
            assert x.dtype == y.dtype
    with pytest.raises(ValueError):
        compress_grads(g, "fp4")


# ---------------------------------------------------------------- sharding
def test_pspec_rules_and_divisibility():
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    # divisible dims keep their axes
    spec = shd.pspec(("embed", "ffn"), shape=(64, 128), mesh=mesh)
    assert spec == jax.sharding.PartitionSpec("data", "model")
    # non-divisible dims are dropped, not crashed (7 % 16 != 0)
    spec = shd.pspec(("vocab_out",), shape=(7,), mesh=mesh)
    assert spec == jax.sharding.PartitionSpec()
    # heads that don't divide the model axis fall back to replicated
    # ('pod' absent -> act_batch collapses to the canonical bare 'data')
    spec = shd.pspec(("act_batch", None, "act_heads", None),
                     shape=(256, 4096, 56, 128), mesh=mesh)
    assert spec == jax.sharding.PartitionSpec("data")


def test_pspec_missing_mesh_axis_filtered():
    mesh = jax.make_mesh((1,), ("data",))
    with shd.sharding_ctx(mesh):
        spec = shd.pspec(("act_batch", "act_seq", None), shape=(8, 8, 8))
        # 'pod' and 'model' absent; act_batch -> data only, act_seq -> dropped
        assert spec == jax.sharding.PartitionSpec("data")


def test_constrain_noop_outside_ctx():
    x = jnp.ones((4, 4))
    assert shd.constrain(x, "act_batch", None) is x


def test_duplicate_axis_not_reused():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with shd.sharding_ctx(mesh):
        spec = shd.pspec(("embed", "embed"), shape=(16, 16))
        assert spec == jax.sharding.PartitionSpec("data")  # second drops


# ---------------------------------------------------------------- roofline
HLO_SAMPLE = """
  %ar = f32[64,128]{1,0} all-reduce(%dot), channel_id=1, replica_groups=[4,2]<=[8], use_global_device_ids=true, to_apply=%add
  %ag = bf16[256,64]{1,0} all-gather(%x), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
  %rs = bf16[32,64]{1,0} reduce-scatter(%y), channel_id=3, replica_groups=[2,4]<=[8], to_apply=%add
  %cp = u32[16]{0} collective-permute(%z), channel_id=4, source_target_pairs={{0,1}}
  %no = f32[8]{0} add(%a, %b)
"""


def test_collective_parser_kinds_and_ring_model():
    stats = collective_bytes(HLO_SAMPLE, adjust_bf16_upcast=False)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1,
                            "reduce-scatter": 1, "collective-permute": 1}
    ar = 64 * 128 * 4
    assert stats.wire_bytes["all-reduce"] == pytest.approx(2 * ar * 1 / 2)
    ag = 256 * 64 * 2
    assert stats.wire_bytes["all-gather"] == pytest.approx(ag * 3 / 4)
    rs = 32 * 64 * 2
    assert stats.wire_bytes["reduce-scatter"] == pytest.approx(rs * 3)


def test_collective_parser_bf16_upcast_adjustment():
    stats = collective_bytes(HLO_SAMPLE, adjust_bf16_upcast=True)
    ar = 64 * 128 * 2  # f32 counted at bf16 width
    assert stats.wire_bytes["all-reduce"] == pytest.approx(2 * ar * 1 / 2)


def test_model_flops_sane():
    from repro.configs import get_config
    from repro.models.config import TRAIN_4K, DECODE_32K
    from repro.roofline.analysis import model_flops
    cfg = get_config("qwen3-8b")
    f_train = model_flops(cfg, TRAIN_4K)
    # 6*N*D within 2x of parameter-only estimate (attention adds more)
    n, d = cfg.n_params(), TRAIN_4K.seq_len * TRAIN_4K.global_batch
    assert 6 * n * d <= f_train <= 2 * 6 * n * d
    f_dec = model_flops(cfg, DECODE_32K)
    assert f_dec < f_train / 100


def test_moe_active_params():
    from repro.configs import get_config
    cfg = get_config("arctic-480b")
    assert cfg.n_params() > 400e9
    assert cfg.n_active_params() < 0.1 * cfg.n_params()
