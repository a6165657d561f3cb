"""Tests for the §Perf hillclimb features: int8 KV cache, sorted-batched
MoE dispatch, FSDP sharding, save_residuals remat, elastic remesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_axes
from repro.configs import get_config
from repro.models import (ModelConfig, decode_step, init_cache, init_params,
                          prefill_step)
from repro.models.moe import (moe_apply_onehot, moe_apply_sorted_batched,
                              moe_init)


def test_int8_kv_cache_matches_bf16_decode():
    base = ModelConfig(name="d", family="dense", num_layers=3, d_model=64,
                       num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=256, dtype="float32", remat="none")
    q8 = dataclasses.replace(base, kv_cache_dtype="int8")
    params = init_params(base, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    S, B = 24, 2
    toks = rng.integers(0, 256, (B, S + 1))
    batch = {"tokens": jnp.asarray(toks[:, :S])}
    outs = {}
    for cfg in (base, q8):
        cache = init_cache(cfg, B, S + 1)
        _, cache = jax.jit(prefill_step(cfg))(params, batch, cache)
        logits, cache2 = jax.jit(decode_step(cfg))(
            params, cache, jnp.asarray(toks[:, S:S + 1]))
        outs[cfg.kv_cache_dtype] = np.asarray(logits)
        assert int(cache2["pos"]) == S
    rel = np.abs(outs["model"] - outs["int8"]).max() / \
        np.abs(outs["model"]).max()
    assert rel < 0.05, f"int8 KV drifted: rel={rel}"
    assert (outs["model"].argmax(-1) == outs["int8"].argmax(-1)).all()


def test_int8_kv_cache_spec_shapes():
    cfg = dataclasses.replace(get_config("smollm-135m"),
                              kv_cache_dtype="int8")
    from repro.models.serve import cache_spec
    spec = cache_spec(cfg, batch=4, max_len=128)
    assert spec["k"].dtype == jnp.int8
    assert spec["k_scale"].shape == (30, 4, 3, 128)


def test_sorted_batched_moe_equals_onehot():
    cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                      num_experts=4, top_k=2, capacity_factor=8.0,
                      dtype="float32")
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 24, 32))
    y1, a1 = jax.vmap(lambda r: moe_apply_onehot(p, r, cfg))(x)
    y2, a2 = moe_apply_sorted_batched(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)

    def loss(p, use_sorted):
        if use_sorted:
            y, aux = moe_apply_sorted_batched(p, x, cfg)
        else:
            y, a = jax.vmap(lambda r: moe_apply_onehot(p, r, cfg))(x)
            aux = a.mean()
        return jnp.sum(y ** 2) + aux

    g1 = jax.grad(loss)(p, False)
    g2 = jax.grad(loss)(p, True)
    for k in ("wi", "wo", "wg", "router"):
        assert float(jnp.abs(g1[k] - g2[k]).max()) < 1e-4, k


def test_sorted_moe_drops_overflow_tokens():
    """Tight capacity must drop tokens, not corrupt others."""
    cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                      num_experts=2, top_k=1, capacity_factor=0.5,
                      dtype="float32")
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    y, aux = moe_apply_sorted_batched(p, x, cfg)
    assert np.all(np.isfinite(np.asarray(y)))


def test_fsdp_shardings_shard_over_data():
    cfg = get_config("qwen1.5-110b")          # fsdp=True default
    assert cfg.fsdp
    from repro.distribution.sharding import param_shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=auto_axes(2))
    key = jax.random.PRNGKey(0)
    specs = jax.eval_shape(lambda k: init_params(cfg, k), key)
    sh = param_shardings(cfg, mesh, specs)
    n_data = 0
    for s in jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")):
        if any(ax == "data" for ax in jax.tree.leaves(tuple(s.spec))):
            n_data += 1
    assert n_data >= 5, "FSDP did not shard large leaves over data"


def test_save_residuals_remat_smoke():
    cfg = dataclasses.replace(get_config("smollm-135m", reduced=True),
                              remat="save_residuals")
    params = init_params(cfg, jax.random.PRNGKey(0))
    from repro.models.transformer import train_loss
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)))}
    (loss, _), grads = jax.jit(jax.value_and_grad(
        train_loss(cfg), has_aux=True))(params, batch)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree.leaves(grads))


def test_elastic_remesh_roundtrip(tmp_path):
    """Checkpoint written under one sharding restores under another."""
    from repro.train.checkpoint import CheckpointManager
    from repro.train.fault import elastic_remesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh1 = jax.make_mesh((1,), ("data",),
                          axis_types=auto_axes(1))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, tree)

    def make_shardings(like):
        return {"w": NamedSharding(mesh1, P("data", None))}

    restored, step = elastic_remesh(mgr, tree, make_shardings)
    assert step == 1
    assert np.array_equal(np.asarray(restored["w"]),
                          np.asarray(tree["w"]))


def test_ssd_chunked_matches_sequential():
    """Mamba2 SSD chunked form == token-by-token recurrence (§Perf)."""
    from repro.models.ssm import ssm_init, ssm_apply
    cfg = ModelConfig(name="s", family="hybrid", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                      ssm_state=8, attn_every=2, dtype="float32")
    p = ssm_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 50, 32)) * 0.5
    y_chunk, (st_chunk, _) = ssm_apply(p, x, cfg)
    st = jnp.zeros((2, 64, 8))
    conv = jnp.zeros((2, cfg.ssm_conv - 1, 64))
    ys = []
    for t in range(50):
        yt, (st, conv) = ssm_apply(p, x[:, t:t + 1], cfg, state=st,
                                   conv_cache=conv)
        ys.append(yt)
    y_seq = jnp.concatenate(ys, axis=1)
    assert float(jnp.abs(y_chunk - y_seq).max()) < 1e-4
    assert float(jnp.abs(st_chunk - st).max()) < 1e-4
