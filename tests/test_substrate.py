"""Substrate tests: data pipeline, optimizer, compression, checkpointing,
sharding rules, HLO parsers."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # degrades to per-test skips without hypothesis

from repro.configs.base import ShapeCell
from repro.configs.registry import get_config
from repro.data.pipeline import LoaderState, PrefetchLoader, SyntheticTokens
from repro.models import api
from repro.optim import adamw, compress


SMOKE = ShapeCell("smoke", 16, 4, "train")


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_loader_deterministic_and_resumable():
    cfg = get_config("olmo-1b", reduced=True)
    l1 = SyntheticTokens(cfg, SMOKE, seed=3)
    batches = [next(iter_) for iter_ in [iter(l1)] for _ in range(5)]
    # resume from step 3
    l2 = SyntheticTokens(cfg, SMOKE, seed=3)
    l2.state = LoaderState(step=3)
    b3 = next(iter(l2))
    np.testing.assert_array_equal(b3["tokens"], batches[3]["tokens"])


def test_loader_host_sharding_partitions_batch():
    cfg = get_config("olmo-1b", reduced=True)
    full = SyntheticTokens(cfg, SMOKE, seed=1, host_id=0, n_hosts=1)
    h0 = SyntheticTokens(cfg, SMOKE, seed=1, host_id=0, n_hosts=2)
    h1 = SyntheticTokens(cfg, SMOKE, seed=1, host_id=1, n_hosts=2)
    assert h0.local_batch == full.local_batch // 2
    b0, b1 = h0.batch_at(0), h1.batch_at(0)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_prefetch_loader():
    cfg = get_config("olmo-1b", reduced=True)
    src = SyntheticTokens(cfg, SMOKE, seed=2)
    pf = PrefetchLoader(src, depth=2)
    pf.start()
    b = pf.next()
    assert b["tokens"].shape == (SMOKE.global_batch, SMOKE.seq_len)
    pf.stop()


def test_loader_tokens_in_vocab():
    cfg = get_config("olmo-1b", reduced=True)
    b = SyntheticTokens(cfg, SMOKE, seed=0).batch_at(0)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < cfg.vocab


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_descends_quadratic():
    cfg = adamw.AdamWConfig(lr_peak=0.1, warmup_steps=1, total_steps=200,
                            weight_decay=0.0, clip_norm=1e9)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw.init(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw.update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_adamw_clips_global_norm():
    cfg = adamw.AdamWConfig(clip_norm=1.0)
    params = {"w": jnp.ones((4,))}
    state = adamw.init(params)
    _, _, m = adamw.update(cfg, {"w": jnp.full((4,), 100.0)}, state, params)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_cosine_schedule_shape():
    cfg = adamw.AdamWConfig(lr_peak=1.0, warmup_steps=10, total_steps=100,
                            lr_min_ratio=0.1)
    lrs = [float(adamw.cosine_lr(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] == pytest.approx(0.0)
    assert max(lrs) == pytest.approx(1.0, rel=1e-3)
    assert lrs[-1] == pytest.approx(0.1, rel=1e-2)


# ---------------------------------------------------------------------------
# int8 error-feedback compression
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 10
    qz = compress.quantize(x)
    err = np.abs(np.asarray(compress.dequantize(qz) - x))
    assert err.max() <= float(qz.scale) * 0.5 + 1e-6


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), steps=st.integers(2, 12))
def test_error_feedback_unbiased_over_window(seed, steps):
    """Σ dequantised ≈ Σ true gradients: the residual never exceeds one
    quantisation step, so accumulated bias does not grow with steps."""
    rng = np.random.default_rng(seed)
    gs = [jnp.asarray(rng.normal(size=(64,)), jnp.float32)
          for _ in range(steps)]
    err = jnp.zeros((64,))
    total_deq = jnp.zeros((64,))
    for g in gs:
        qz, err = compress.quantize_with_feedback(g, err)
        total_deq = total_deq + compress.dequantize(qz)
    total_true = sum(gs)
    resid = np.abs(np.asarray(total_deq + err - total_true))
    assert resid.max() < 1e-4
    # carried error bounded by one quantum
    last_scale = float(compress.quantize(gs[-1] + 0).scale)
    assert np.abs(np.asarray(err)).max() <= 2.0


def test_compressed_psum_matches_plain():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.asarray(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("x",))
    x = jnp.linspace(-1, 1, 128)
    f = shard_map(
        lambda v: compress.compressed_psum(v, "x"), mesh=mesh,
        in_specs=P(), out_specs=P())
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.02)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager
    cfg = get_config("olmo-1b", reduced=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    mgr = CheckpointManager(str(tmp_path), retain=2)
    mgr.save(10, {"params": params, "opt": opt},
             extras={"loader": {"step": 10}})
    specs = {
        "params": jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        "opt": jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), opt),
    }
    restored, extras = mgr.restore(10, specs)
    assert extras["loader"]["step"] == 10
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_retention_and_latest(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), retain=2)
    tree = {"x": jnp.zeros((4,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, {"t": tree})
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async_and_atomic(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    tree = {"x": jnp.arange(8, dtype=jnp.float32)}
    mgr.save_async(5, {"t": tree})
    mgr.wait()
    assert mgr.latest_step() == 5
    # no tmp dirs left behind
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_checkpoint_shape_mismatch_raises(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"t": {"x": jnp.zeros((4,))}})
    bad = {"t": {"x": jax.ShapeDtypeStruct((5,), jnp.float32)}}
    with pytest.raises(ValueError):
        mgr.restore(1, bad)


# ---------------------------------------------------------------------------
# HLO parsers
# ---------------------------------------------------------------------------

def test_hlo_type_bytes():
    from repro.launch.hlo import _type_bytes
    assert _type_bytes("bf16[128,256]{1,0}") == 128 * 256 * 2
    assert _type_bytes("f32[8]{0}") == 32
    assert _type_bytes("(bf16[2,2]{1,0}, f32[4]{0})") == 8 + 16


def test_hlo_trip_count_and_collectives():
    from repro.launch.hlo import collective_bytes
    hlo = """
HloModule test

%body (p: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %ar = f32[64,64]{1,0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[], f32[64,64]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[64,64])) -> pred[] {
  %bound = s32[] constant(12)
  ROOT %cmp = pred[] compare(%i, %bound), direction=LT
}

ENTRY %main (a: f32[64,64]) -> f32[64,64] {
  %ag = f32[64,64]{1,0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[64,64]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""
    st = collective_bytes(hlo)
    per = 64 * 64 * 4
    assert st.bytes_by_kind["all-gather"] == per
    assert st.bytes_by_kind["all-reduce"] == per * 12


def test_hlo_dot_flops_with_loop():
    from repro.launch.hlo import hlo_dot_flops
    hlo = """
HloModule test

%body (p: (s32[], f32[32,16])) -> (s32[], f32[32,16]) {
  %w = f32[16,16]{1,0} parameter(1)
  %x = f32[32,16]{1,0} get-tuple-element(%p), index=1
  %d = f32[32,16]{1,0} dot(%x, %w), lhs_batch_dims={}, lhs_contracting_dims={1}, rhs_batch_dims={}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[32,16]) tuple(%i, %d)
}

%cond (p: (s32[], f32[32,16])) -> pred[] {
  %bound = s32[] constant(4)
  ROOT %cmp = pred[] compare(%i, %bound), direction=LT
}

ENTRY %main (a: f32[32,16]) -> f32[32,16] {
  %w = (s32[], f32[32,16]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[32,16]{1,0} get-tuple-element(%w), index=1
}
"""
    # 2*32*16*16 per iter × 4 iters
    assert hlo_dot_flops(hlo) == 2 * 32 * 16 * 16 * 4
