"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp

from repro import compat
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("b", [1, 3, 8])
@pytest.mark.parametrize("v", [128, 500, 2048, 9504])
@pytest.mark.parametrize("k", [1, 8, 40])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_sweep(b, v, k, dtype):
    if k > v:
        pytest.skip("k>v")
    x = jax.random.normal(jax.random.key(b * v + k), (b, v)).astype(dtype)
    vals, idx = ops.topk(x, k)
    rvals, ridx = ref.topk_ref(x, k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    # indices can differ on exact ties; values picked must match exactly
    picked = np.take_along_axis(np.asarray(x, np.float32), np.asarray(idx), 1)
    np.testing.assert_allclose(picked, np.asarray(rvals), rtol=1e-6)


@pytest.mark.parametrize("t,ka,kb,d", [(1, 64, 64, 64), (100, 300, 700, 200),
                                       (128, 512, 1728, 512), (257, 129, 65, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_dual_matmul_sweep(t, ka, kb, d, dtype):
    ks = jax.random.split(jax.random.key(t), 4)
    a = jax.random.normal(ks[0], (t, ka)).astype(dtype)
    wa = jax.random.normal(ks[1], (ka, d)).astype(dtype) / np.sqrt(ka)
    b = jax.random.normal(ks[2], (t, kb)).astype(dtype)
    wb = jax.random.normal(ks[3], (kb, d)).astype(dtype) / np.sqrt(kb)
    out = ops.fused_dual_matmul(a, wa, b, wb)
    expect = ref.fused_residual_ref(a, wa, b, wb)
    tol = 1e-4 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("b,hq,hkv,S,hd", [
    (1, 4, 4, 128, 64), (2, 8, 2, 300, 64), (2, 16, 1, 1024, 128),
    (1, 2, 2, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, hq, hkv, S, hd, dtype):
    ks = jax.random.split(jax.random.key(S + hd), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, S, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, S, hd)).astype(dtype)
    valid = jnp.arange(S) < (S * 3) // 4
    scale = 1.0 / np.sqrt(hd)
    m1, l1, a1 = ops.decode_attention_partial(q, k, v, valid, scale)
    m2, l2, a2 = ref.decode_attention_ref(q, k, v, valid, scale)
    o1 = np.asarray(a1) / np.maximum(np.asarray(l1)[..., None], 1e-30)
    o2 = np.asarray(a2) / np.maximum(np.asarray(l2)[..., None], 1e-30)
    tol = 1e-5 if dtype == jnp.float32 else 0.03
    np.testing.assert_allclose(o1, o2, atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=tol, rtol=tol)


@pytest.mark.parametrize("b,hq,hkv,bs,nbps,hd,spans", [
    pytest.param(1, 4, 4, 16, 4, 64, None, id="1-4-4-16-4-64"),
    pytest.param(2, 8, 2, 8, 6, 64, None, id="2-8-2-8-6-64"),
    pytest.param(3, 4, 1, 32, 2, 128, None, id="3-4-1-32-2-128"),
    # 40 pages in steps of 32: the second step half padding; both slots
    # end mid-step
    pytest.param(2, 8, 2, 16, 40, 64, (((0, 600),), ((0, 17),)),
                 id="padded-steps"),
    # an empty slot between two live ones walks nothing (steps of 8 pages)
    pytest.param(3, 4, 1, 64, 10, 128, (((0, 300),), (), ((0, 640),)),
                 id="empty-slot"),
    # windowed masks: live ranges that start after page 0, one with a hole
    pytest.param(2, 8, 2, 64, 24, 64,
                 (((700, 1200),), ((520, 530), (1400, 1500))), id="window"),
    pytest.param(2, 10, 2, 64, 10, 64, (((0, 530),), ((100, 300),)),
                 id="g5"),
    pytest.param(2, 4, 4, 64, 10, 64, (((0, 513),), ((0, 1),)), id="g1"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_dense_kernel(b, hq, hkv, bs, nbps, hd,
                                                     spans, dtype):
    """Pool + block-table gather (a grid step per run of pages, copied by
    hand) must agree with the dense kernel run on the gathered view.  By
    default each slot is a random prefix whose last block is fully dead;
    ``spans`` gives each slot's valid [start, end) position ranges."""
    S = nbps * bs
    ks = jax.random.split(jax.random.key(b * S + hd), 3)
    nb = 1 + b * nbps
    kp = jax.random.normal(ks[0], (nb, hkv, bs, hd)).astype(dtype)
    vp = jax.random.normal(ks[1], (nb, hkv, bs, hd)).astype(dtype)
    rng = np.random.default_rng(S)
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[: b * nbps]
                     .reshape(b, nbps).astype(np.int32))
    q = jax.random.normal(ks[2], (b, hq, 1, hd)).astype(dtype)
    if spans is None:
        lens = rng.integers(1, S - bs + 1, size=b)   # last block fully dead
        valid = np.arange(S)[None, :] < lens[:, None]
    else:
        valid = np.zeros((b, S), bool)
        for bi, ranges in enumerate(spans):
            for lo, hi in ranges:
                valid[bi, lo:hi] = True
    valid = jnp.asarray(valid)
    scale = 1.0 / np.sqrt(hd)
    m1, l1, a1 = ops.paged_decode_attention(q, kp, vp, bt, valid, scale)
    view = jnp.take(kp, bt, axis=0).transpose(0, 2, 1, 3, 4).reshape(b, hkv, S, hd)
    vview = jnp.take(vp, bt, axis=0).transpose(0, 2, 1, 3, 4).reshape(b, hkv, S, hd)
    o1 = np.asarray(a1) / np.maximum(np.asarray(l1)[..., None], 1e-30)
    tol = 1e-5 if dtype == jnp.float32 else 0.03
    for bi in range(b):      # dense kernel takes a shared (S,) mask: per row
        m2, l2, a2 = ops.decode_attention_partial(
            q[bi:bi + 1], view[bi:bi + 1], vview[bi:bi + 1], valid[bi], scale)
        o2 = np.asarray(a2) / np.maximum(np.asarray(l2)[..., None], 1e-30)
        np.testing.assert_allclose(o1[bi:bi + 1], o2, atol=tol, rtol=tol)
        np.testing.assert_allclose(np.asarray(m1)[bi:bi + 1], np.asarray(m2),
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(np.asarray(l1)[bi:bi + 1], np.asarray(l2),
                                   atol=tol, rtol=tol)


def test_decode_attention_fully_masked_shard():
    """Seq-sharded decode: an all-invalid shard must contribute zero weight."""
    q = jnp.ones((1, 2, 1, 64))
    k = jnp.ones((1, 2, 64, 64))
    v = jnp.ones((1, 2, 64, 64))
    m, l, acc = ops.decode_attention_partial(q, k, v, jnp.zeros(64, bool), 0.125)
    assert not np.isfinite(np.asarray(m)).any()
    np.testing.assert_allclose(np.asarray(l), 0.0)


@pytest.mark.parametrize("b,s,w", [(1, 8, 64), (2, 37, 200), (3, 128, 256),
                                   (1, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lru_scan_sweep(b, s, w, dtype):
    ks = jax.random.split(jax.random.key(b * s + w), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, w))).astype(dtype)
    bb = jax.random.normal(ks[1], (b, s, w)).astype(dtype)
    h0 = jax.random.normal(ks[2], (b, w)).astype(jnp.float32)
    h1, hT1 = ops.lru_scan(a, bb, h0)
    h2, hT2 = ref.lru_scan_ref(a, bb, h0)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(hT1), np.asarray(hT2), atol=tol, rtol=tol)


def test_rglru_pallas_path_matches_scan():
    """Model-level: RG-LRU forward with the Pallas linear-scan kernel equals
    the associative_scan path."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import RGLRUConfig
    from repro.models import rglru as rglru_mod
    from repro.models.common import Dist, materialize, specs_of
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(
        get_config("recurrentgemma-9b").reduced(), d_model=64, n_heads=4,
        rglru=RGLRUConfig(lru_width=0, conv_width=4))
    dist = Dist(tp=1, dp=1)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    defs = rglru_mod.rglru_defs(cfg, dist)
    params = materialize(defs, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    outs = {}
    for up in (False, True):
        def f(params, x, up=up):
            out, _ = rglru_mod.rglru_forward(params, x, cfg, dist, use_pallas=up)
            return out
        outs[up] = np.asarray(jax.jit(compat.shard_map(
            f, mesh=mesh, in_specs=(specs_of(defs), P()), out_specs=P(),
            check_vma=False))(params, x))
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-3, rtol=1e-3)
