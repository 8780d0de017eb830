"""Per-arch REDUCED smoke tests (deliverable (f)): one forward + one train
step on CPU, asserting output shapes and no NaNs, for every assigned arch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, ParallelConfig, get_config
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.training import data as D
from repro.training.train_loop import AdamWConfig, init_opt_state, make_train_step


def _forward_once(arch, seq=16, batch=2):
    cfg = get_config(arch).reduced()
    ctx = M.ModelCtx.make(cfg, ParallelConfig(tp=1, dp=1, remat=False))
    params = M.init_params(ctx, jax.random.key(0))
    mesh = make_local_mesh(1, 1)
    tok_shape = (batch, seq) if cfg.n_codebooks == 1 else (batch, seq, cfg.n_codebooks)
    tokens = jax.random.randint(jax.random.key(1), tok_shape, 0, cfg.vocab_size)
    feats = None
    if cfg.frontend is not None:
        feats = jax.random.normal(
            jax.random.key(2),
            (batch, cfg.frontend.prefix_len, cfg.frontend.feature_dim), jnp.float32)

    def step(params, tokens, feats):
        logits, _, aux = M.forward(params, tokens, ctx, features=feats,
                                   seq_sharded=True)
        return logits, aux

    in_specs = (M.param_specs(ctx), P("data", *(None,) * (len(tok_shape) - 1)),
                P("data") if feats is not None else P())
    out_spec = (P("data", None, "model") if cfg.n_codebooks == 1
                else P("data", None, None, "model"))
    f = jax.jit(compat.shard_map(step, mesh=mesh, in_specs=in_specs,
                              out_specs=(out_spec, P()), check_vma=False))
    logits, aux = f(params, tokens, feats)
    return cfg, logits, aux


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg, logits, aux = _forward_once(arch)
    prefix = cfg.frontend.prefix_len if cfg.frontend else 0
    expect_s = 16 + prefix
    from repro.models.common import ShardPlan

    vp = ShardPlan.make(cfg, 1).vocab_p
    if cfg.n_codebooks == 1:
        assert logits.shape == (2, expect_s, vp)
    else:
        assert logits.shape == (2, expect_s, cfg.n_codebooks, vp)
    assert not bool(jnp.isnan(logits).any())
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    ctx = M.ModelCtx.make(cfg, ParallelConfig(tp=1, dp=1, remat=True))
    params = M.init_params(ctx, jax.random.key(0))
    mesh = make_local_mesh(1, 1)
    opt = init_opt_state(params)
    step_fn = make_train_step(ctx, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10))
    dc = D.DataConfig(global_batch=2, seq_len=32)
    b = D.make_batch(cfg, dc, 0)
    bspecs = {k: P("data", *(None,) * (v.ndim - 1)) for k, v in b.items()}
    pspecs = M.param_specs(ctx)
    ospecs = {"m": pspecs, "v": pspecs, "step": P()}
    f = jax.jit(compat.shard_map(step_fn, mesh=mesh,
                              in_specs=(pspecs, ospecs, bspecs),
                              out_specs=(pspecs, ospecs, P()), check_vma=False))
    new_p, new_o, metrics = f(params, opt, {k: jnp.asarray(v) for k, v in b.items()})
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually moved
    delta = max(float(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)).max())
                for a, b_ in zip(jax.tree.leaves(params), jax.tree.leaves(new_p)))
    assert delta > 0
    assert not any(bool(jnp.isnan(x).any()) for x in jax.tree.leaves(new_p))


@pytest.mark.parametrize("arch", ["yi-9b", "minicpm3-4b", "mamba2-1.3b",
                                  "recurrentgemma-9b", "musicgen-medium"])
def test_decode_matches_full_forward(arch):
    """Prefill+decode with cache == full forward on the concatenated tokens."""
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    ctx = M.ModelCtx.make(cfg, ParallelConfig(tp=1, dp=1, remat=False))
    params = M.init_params(ctx, jax.random.key(0))
    mesh = make_local_mesh(1, 1)
    S = 40  # must cover prefix + 17 prompt tokens + 1 decode slot
    tshape = (2, 17) if cfg.n_codebooks == 1 else (2, 17, cfg.n_codebooks)
    tokens = jax.random.randint(jax.random.key(1), tshape, 0, cfg.vocab_size)
    prefix = cfg.frontend.prefix_len if cfg.frontend else 0
    feats = None
    if cfg.frontend is not None:
        feats = jax.random.normal(
            jax.random.key(2), (2, prefix, cfg.frontend.feature_dim), jnp.float32)

    def full(params, tokens, feats):
        logits, _, _ = M.forward(params, tokens, ctx, features=feats)
        return logits[:, -1]

    def cached(params, tokens, feats):
        caches = M.init_caches(ctx, 2, S)
        _, caches, _ = M.forward(params, tokens[:, :16], ctx, features=feats,
                                 caches=caches, last_only=True)
        lg, _, _ = M.forward(params, tokens[:, 16:17], ctx, caches=caches,
                             cur_pos=jnp.int32(16 + prefix))
        return lg[:, -1]

    in_specs = (M.param_specs(ctx), P("data", *(None,) * (tokens.ndim - 1)),
                P("data") if feats is not None else P())
    out_spec = (P("data", "model") if cfg.n_codebooks == 1
                else P("data", None, "model"))
    run = lambda f: np.asarray(jax.jit(compat.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False))(
        params, tokens, feats), dtype=np.float32)
    a, b = run(full), run(cached)
    np.testing.assert_allclose(a, b, atol=0.08, rtol=0.05)


@pytest.mark.parametrize("arch", ["yi-9b", "qwen2.5-14b"])
def test_int8_kv_cache_close_to_bf16(arch):
    """int8 KV cache (per-head-per-slot scales) stays within ~2% of bf16 on
    dense archs (MoE archs are router-flip sensitive; documented).  The
    scale path is absmax/127 per (batch, head, slot) with an fp32
    round-trip."""
    cfg = get_config(arch).reduced()
    mesh = make_local_mesh(1, 1)
    tokens = jax.random.randint(jax.random.key(1), (2, 17), 0, cfg.vocab_size)
    outs = {}
    for quant in (False, True):
        ctx = M.ModelCtx.make(cfg, ParallelConfig(tp=1, dp=1, remat=False,
                                                  kv_quant=quant))
        params = M.init_params(ctx, jax.random.key(0))

        def pd(params, tokens, ctx=ctx):
            caches = M.init_caches(ctx, 2, 40)
            _, caches, _ = M.forward(params, tokens[:, :16], ctx, caches=caches,
                                     last_only=True)
            lg, _, _ = M.forward(params, tokens[:, 16:17], ctx, caches=caches,
                                 cur_pos=jnp.int32(16))
            return lg[:, -1]

        f = jax.jit(compat.shard_map(pd, mesh=mesh,
                                  in_specs=(M.param_specs(ctx), P("data", None)),
                                  out_specs=P("data", "model"), check_vma=False))
        outs[quant] = np.asarray(f(params, tokens), np.float32)
    rel = np.abs(outs[True] - outs[False]).max() / np.abs(outs[False]).max()
    assert rel < 0.05, rel
