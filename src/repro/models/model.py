"""Top-level model: params, forward, and the per-shard step functions.

Everything here is per-shard code for ``jax.shard_map``; the launcher
(`repro.launch`) wraps these in shard_map + jit with the right specs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core import embedding as emb
from repro.core import wquant
from repro.core.sync_policy import SyncPolicy
from repro.models import multimodal, transformer as tfm
from repro.models.common import (
    Dist,
    ParamDef,
    ShardPlan,
    is_def,
    materialize,
    rms_norm,
    shapes_of,
    specs_of,
)

Pytree = Any


@dataclass(frozen=True)
class ModelCtx:
    """Everything static the per-shard step functions need."""

    cfg: ModelConfig
    plan: ShardPlan
    dist: Dist
    parallel: ParallelConfig

    @staticmethod
    def make(cfg: ModelConfig, parallel: ParallelConfig,
             *, pod_axis: Optional[str] = None) -> "ModelCtx":
        if parallel.use_pallas is None:
            parallel = dataclasses.replace(
                parallel, use_pallas=jax.default_backend() == "tpu")
        dist = Dist(
            model_axis="model", data_axis="data", pod_axis=pod_axis,
            tp=parallel.tp, dp=parallel.dp, pods=parallel.pods,
        )
        return ModelCtx(cfg, ShardPlan.make(cfg, parallel.tp), dist, parallel)

    def policy(self, *, seq_sharded: bool) -> SyncPolicy:
        return SyncPolicy(
            dist=self.dist,
            seq_sharded=seq_sharded and self.parallel.seq_parallel and self.dist.tp > 1,
            one_shot=self.parallel.one_shot_sync,
        )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def model_defs(ctx: ModelCtx) -> Dict[str, Any]:
    cfg, plan, dist = ctx.cfg, ctx.plan, ctx.dist
    groups = tfm.build_groups(cfg)
    defs: Dict[str, Any] = {
        "embed": emb.embed_defs(cfg, plan, dist),
        "groups": tuple(tfm.group_defs(cfg, plan, dist, g) for g in groups),
        "final_norm": ParamDef((cfg.d_model,), P(None), init="zeros"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef(
            (cfg.n_codebooks, cfg.d_model, plan.vocab_p),
            P(None, None, dist.model_axis),
            init="scaled",
            scale_dim=1,
        )
    if cfg.frontend is not None:
        defs["frontend"] = multimodal.frontend_defs(cfg, dist)
    return defs


def init_params(ctx: ModelCtx, key, mesh=None) -> Pytree:
    """Random parameters; with a ``mesh`` each leaf is created sharded."""
    return materialize(model_defs(ctx), key, mesh)


# ---------------------------------------------------------------------------
# Weight-only quantization (quantize-at-load transform)
#
# Every serving projection — attention q/k/v/o, MLP up/gate/down (incl. MoE
# shared experts), MoE expert blocks, lm_head — is replaced by a
# :class:`repro.core.wquant.QuantWeight` (packed values + scales).  Embed
# tables (row gathers, not sweeps), norms, biases, routers, and the MLA
# latent projections (absorbed-matmul reshapes; latent ranks are tiny) stay
# bf16.  The walker below is the single source of truth for WHICH leaves
# quantize, shared by the param transform, the spec tree, and the
# byte-accounting helper, so all three stay consistent.
# ---------------------------------------------------------------------------

_WQ_ATTN_KEYS = ("w_q", "w_k", "w_v", "w_o")
_WQ_FFN_KEYS = ("w_up", "w_gate", "w_down")


def _map_wq_leaves(ctx: ModelCtx, tree: Pytree, leaf_fn) -> Pytree:
    """Rebuild ``tree`` (params / specs / defs-valued) with every
    weight-quant-eligible leaf replaced by ``leaf_fn(param_def, leaf,
    site)``.  ``site`` is how the serving forward consumes the leaf:
    "matmul" (2-D projection — fused dequant kernel eligible) or "einsum"
    (batched contraction served by ``wquant.to_dense``: the attention
    out-projection and MoE expert blocks)."""
    cfg = ctx.cfg
    defs = model_defs(ctx)
    groups = tfm.build_groups(cfg)

    def map_keys(dtree, vtree, keys, einsum_keys=()):
        out = dict(vtree)
        for k in keys:
            if k in out:
                out[k] = leaf_fn(dtree[k], out[k],
                                 "einsum" if k in einsum_keys else "matmul")
        return out

    new_groups = []
    for g, gdefs, gtree in zip(groups, defs["groups"], tree["groups"]):
        gt = {}
        for i, sub in enumerate(g.subs):
            st = dict(gtree[f"sub{i}"])
            sd = gdefs[f"sub{i}"]
            if sub.kind in tfm.ATTN_KINDS and cfg.mla is None:
                st["mixer"] = map_keys(sd["mixer"], st["mixer"], _WQ_ATTN_KEYS,
                                       einsum_keys=("w_o",))
            if sub.has_ffn:
                ffn = map_keys(sd["ffn"], st["ffn"], _WQ_FFN_KEYS,
                               einsum_keys=_WQ_FFN_KEYS if sub.is_moe else ())
                if "shared" in ffn:   # shared experts run mlp_forward (2-D)
                    ffn["shared"] = map_keys(sd["ffn"]["shared"],
                                             ffn["shared"], _WQ_FFN_KEYS)
                st["ffn"] = ffn
            gt[f"sub{i}"] = st
        new_groups.append(gt)
    out = dict(tree)
    out["groups"] = tuple(new_groups)
    if "lm_head" in tree:
        # multi-codebook heads are served via dequantize+einsum even on the
        # pallas backend (_lm_head routes the kernel only when ncb == 1)
        out["lm_head"] = leaf_fn(defs["lm_head"], tree["lm_head"],
                                 "matmul" if cfg.n_codebooks == 1
                                 else "einsum")
    return out


def _wq_k_shards(ctx: ModelCtx, d: ParamDef) -> int:
    """TP shard count of the reduction dim (axis -2): the int4 group clamp
    keeps groups shard-local, so scale sharding needs no communication."""
    entries = tuple(d.spec)
    if len(entries) >= 2 and entries[-2] == ctx.dist.model_axis:
        return ctx.dist.tp
    return 1


def quantize_params(ctx: ModelCtx, params: Pytree) -> Pytree:
    """Quantize-at-load: bf16 projection weights -> QuantWeight leaves per
    ``ctx.parallel.weight_quant`` / ``wq_group_size``.  Idempotent (already-
    quantized leaves pass through); ineligible shapes stay bf16 — the spec
    tree applies the same predicate, so trees always match."""
    par = ctx.parallel
    backend = "pallas" if par.use_pallas else "ref"

    def f(d: ParamDef, leaf, site):
        if isinstance(leaf, wquant.QuantWeight):
            return leaf
        ks = _wq_k_shards(ctx, d)
        if not wquant.quantizable(d.shape, par.weight_quant,
                                  par.wq_group_size, ks):
            return leaf
        return wquant.quantize(leaf, par.weight_quant, par.wq_group_size,
                               k_shards=ks, backend=backend)

    return _map_wq_leaves(ctx, params, f)


def param_specs(ctx: ModelCtx) -> Pytree:
    specs = specs_of(model_defs(ctx))
    par = ctx.parallel
    if par.weight_quant == "none":
        return specs
    backend = "pallas" if par.use_pallas else "ref"

    def f(d: ParamDef, spec, site):
        ks = _wq_k_shards(ctx, d)
        if not wquant.quantizable(d.shape, par.weight_quant,
                                  par.wq_group_size, ks):
            return spec
        return wquant.spec_for(d.shape, spec, par.weight_quant,
                               par.wq_group_size, k_shards=ks,
                               backend=backend)

    return _map_wq_leaves(ctx, specs, f)


def decode_weight_bytes(ctx: ModelCtx) -> Dict[str, int]:
    """Bytes of weight stream a decode token sweeps, from shapes alone.

    ``swept``: all projection weights + lm_head (+ tiny norms/biases at
    their stored width) — the unique weight STORAGE decode reads every
    token.  ``quantized`` / ``dense`` split the swept set by whether the
    quantize transform covers the leaf under the current ``weight_quant``
    mode.  ``quantized_ref_einsum`` is the subset of ``quantized`` served
    through ``wquant.to_dense`` (the attention out-projection and MoE
    expert blocks): their packed stream counts as swept storage, but
    realizing it as HBM traffic needs the dequant fused into the
    contraction — XLA operand fusion or the batched kernels on the
    ROADMAP backlog; until then those leaves also materialise a bf16
    transient per step, which is activation-like traffic on top of this
    number (dominant on MoE archs — read the ratio accordingly).  Embed
    tables are excluded: a token embeds by row gather, not a full-table
    sweep."""
    import math

    par = ctx.parallel
    counted = []                             # (ParamDef, k_shards, ok, site)

    def mark(_d: ParamDef, leaf, site):
        # ``leaf`` is the ParamDef from the tree we walk below (the walker
        # rebuilds its own defs internally, so only the leaf's id is the
        # one the rest-loop can exclude against)
        ks = _wq_k_shards(ctx, leaf)
        ok = (par.weight_quant != "none"
              and wquant.quantizable(leaf.shape, par.weight_quant,
                                     par.wq_group_size, ks))
        counted.append((leaf, ks, ok, site))
        return leaf

    defs = model_defs(ctx)
    _map_wq_leaves(ctx, defs, mark)
    quantized = dense = ref_einsum = 0
    for d, ks, ok, site in counted:
        if ok:
            b = wquant.quant_bytes(d.shape, par.weight_quant,
                                   par.wq_group_size, ks)
            quantized += b
            if site == "einsum":
                ref_einsum += b
        else:
            dense += math.prod(d.shape) * jnp.dtype(d.dtype).itemsize

    # non-projection leaves swept per token (norms, biases, routers, MLA):
    # everything in the defs tree except the counted projections, embed
    # (row gather), and the frontend projector (prefill-only — decode
    # never reads it)
    counted_ids = {id(d) for d, _, _, _ in counted}
    for leaf in jax.tree.leaves({k: v for k, v in defs.items()
                                 if k not in ("embed", "frontend")},
                                is_leaf=is_def):
        if is_def(leaf) and id(leaf) not in counted_ids:
            dense += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    # tied embeddings: the head IS the table, and _lm_head einsums the whole
    # (ncb, V, d) table every token — a full sweep, not a row gather (and it
    # stays bf16: the quantize transform keeps embed tables dense)
    if ctx.cfg.tie_embeddings:
        t = defs["embed"]["table"]
        dense += math.prod(t.shape) * jnp.dtype(t.dtype).itemsize
    return {"quantized": quantized, "dense": dense,
            "quantized_ref_einsum": ref_einsum,
            "swept": quantized + dense}


def param_shapes(ctx: ModelCtx) -> Pytree:
    shapes = shapes_of(model_defs(ctx))
    par = ctx.parallel
    if par.weight_quant == "none":
        return shapes
    backend = "pallas" if par.use_pallas else "ref"

    # mirror the quantize transform so shapes/specs/params trees stay
    # structurally identical under weight_quant (tree_maps rely on it)
    def f(d: ParamDef, sds, site):
        ks = _wq_k_shards(ctx, d)
        if not wquant.quantizable(d.shape, par.weight_quant,
                                  par.wq_group_size, ks):
            return sds
        return wquant.shapes_for(d.shape, par.weight_quant,
                                 par.wq_group_size, k_shards=ks,
                                 backend=backend)

    return _map_wq_leaves(ctx, shapes, f)


# ---------------------------------------------------------------------------
# Packed-weight persistence (quantize-at-load -> disk)
#
# A 72B-scale start otherwise materializes the full bf16 tree before packing
# it down; persisting the packed QuantWeight tree lets later starts restore
# int8/int4 payloads + scales directly.  QuantWeight is a pytree whose
# children flatten under stable paths, so the flat-key npz checkpointer
# round-trips it as-is; ``param_shapes`` mirrors the quantize transform and
# supplies the ``like`` tree for the shape-checked restore.
# ---------------------------------------------------------------------------


def _wq_meta(ctx: ModelCtx) -> Dict[str, Any]:
    """Everything the packing layout depends on: mode/group decide payload
    widths, tp decides scale shapes (the int4 group clamp is shard-local),
    backend decides the packed layout."""
    par = ctx.parallel
    return {"arch": ctx.cfg.name, "weight_quant": par.weight_quant,
            "wq_group_size": par.wq_group_size, "tp": ctx.dist.tp,
            "backend": "pallas" if par.use_pallas else "ref"}


def has_quantized(path: str) -> bool:
    from repro.training import checkpoint

    return checkpoint.load_meta(path) is not None


def save_quantized(ctx: ModelCtx, params: Pytree, path: str) -> None:
    """Persist an already-quantized param tree (packed payloads + scales)."""
    from repro.training import checkpoint

    checkpoint.save(path, params, meta=_wq_meta(ctx))


def load_quantized(ctx: ModelCtx, path: str) -> Pytree:
    """Restore a packed QuantWeight tree saved by :func:`save_quantized`.
    The stored meta must match the current config — a silent layout
    mismatch would produce garbage weights, so it is an error instead."""
    from repro.training import checkpoint

    meta = checkpoint.load_meta(path)
    if meta is None:
        raise FileNotFoundError(f"no quantized checkpoint at {path}")
    want = _wq_meta(ctx)
    got = {k: meta.get("meta", {}).get(k) for k in want}
    if got != want:
        raise ValueError(
            f"quantized checkpoint {path} was packed for {got}, "
            f"engine wants {want}")
    tree, _ = checkpoint.restore(path, param_shapes(ctx))
    return tree


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _lm_head(params, x, ctx: ModelCtx) -> jax.Array:
    """x (b,s,d) -> local logits (b,s,[ncb,]V_local), fp32."""
    cfg = ctx.cfg
    if cfg.tie_embeddings:
        table = params["embed"]["table"]      # (ncb, V_local, d) vocab-sharded
        logits = jnp.einsum("bsd,cvd->bscv", x.astype(jnp.float32),
                            table.astype(jnp.float32))
        return logits[:, :, 0] if cfg.n_codebooks == 1 else logits
    head = params["lm_head"]
    if isinstance(head, wquant.QuantWeight):
        if cfg.n_codebooks == 1 and head.backend == "pallas":
            # the biggest single per-token weight sweep goes through the
            # fused dequant GEMV/GEMM (fp32 logits out of the kernel)
            flat = wquant.matmul(x, wquant.index_batch(head, 0),
                                 out_dtype=jnp.float32)
            return flat
        logits = jnp.einsum("bsd,cdv->bscv", x.astype(jnp.float32),
                            wquant.dequantize(head).astype(jnp.float32))
    else:
        logits = jnp.einsum("bsd,cdv->bscv", x.astype(jnp.float32),
                            head.astype(jnp.float32))
    return logits[:, :, 0] if cfg.n_codebooks == 1 else logits


def forward(
    params: Pytree,
    tokens: jax.Array,               # (b_local, s) or (b_local, s, ncb)
    ctx: ModelCtx,
    *,
    features: Optional[jax.Array] = None,   # (b_local, prefix, feat) stub output
    caches: Optional[Tuple] = None,
    cur_pos: Optional[jax.Array] = None,    # int32 (decode): scalar, or (b,) per-slot
    kv_seq_axis: Optional[str] = None,
    seq_sharded: bool = False,
    last_only: bool = False,
    id_broadcast: Optional[bool] = None,
    skip_head: bool = False,
    length_mask: Optional[jax.Array] = None,  # (b, s) bool, right-padded prefill
    block_tables: Optional[jax.Array] = None, # (b, nbps): paged KV addressing
    start_pos: Optional[jax.Array] = None,    # (b,): per-slot prefill offset
                                              # (cached-prefix admission)
) -> Tuple[jax.Array, Optional[Tuple], jax.Array]:
    """-> (local logits, new_caches, aux_loss). Logits are vocab-sharded.

    skip_head=True returns the final-norm hidden states instead of logits
    (the chunked vocab-parallel loss applies the head itself)."""
    cfg, plan, dist = ctx.cfg, ctx.plan, ctx.dist
    policy = ctx.policy(seq_sharded=seq_sharded)
    if id_broadcast is None:
        id_broadcast = ctx.parallel.id_broadcast
    decode = cur_pos is not None and tokens.shape[1] == 1

    x = emb.embed_lookup(params["embed"], tokens, cfg, plan, dist,
                         id_broadcast=id_broadcast)
    if cfg.frontend is not None and features is not None:
        prefix = multimodal.project_features(params["frontend"], features, cfg)
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)

    s_total = x.shape[1]
    if decode:
        # per-slot decode (continuous batching): each row rotates/masks at
        # its own position; shared decode keeps the (1,) broadcast form.
        positions = cur_pos[:, None] if cur_pos.ndim == 1 else cur_pos[None]
    elif start_pos is not None:
        # cached-prefix / chunked admission: each row's prompt suffix or
        # chunk starts at its own absolute offset (tokens 0..start-1 are
        # already resident in the row's cache or shared prefix blocks)
        positions = (start_pos[:, None]
                     + jnp.arange(s_total, dtype=jnp.int32)[None, :])
    else:
        positions = jnp.arange(s_total, dtype=jnp.int32)

    x = policy.shard_residual(x)
    groups = tfm.build_groups(cfg)
    new_caches = [] if caches is not None else None
    aux = jnp.zeros((), jnp.float32)
    for gi, g in enumerate(groups):
        c = caches[gi] if caches is not None else None
        x, c_new, a = tfm.group_forward(
            params["groups"][gi], x, positions, cfg, plan, dist, policy, g,
            caches=c, cur_pos=cur_pos, kv_seq_axis=kv_seq_axis,
            use_pallas=ctx.parallel.use_pallas, remat=ctx.parallel.remat and not decode,
            length_mask=length_mask, block_tables=block_tables,
            flash_prefill=ctx.parallel.flash_prefill,
        )
        aux = aux + a
        if new_caches is not None:
            new_caches.append(c_new)
    x = policy.unshard_residual(x)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if last_only:
        x = x[:, -1:]
    if skip_head:
        return x, (tuple(new_caches) if new_caches is not None else None), aux
    logits = _lm_head(params, x, ctx)
    return logits, (tuple(new_caches) if new_caches is not None else None), aux


def lm_head_local(params, x, ctx: ModelCtx) -> jax.Array:
    """Public head application (used by the chunked loss)."""
    return _lm_head(params, x, ctx)


def init_caches(ctx: ModelCtx, batch_local: int, cache_len: int,
                *, kv_seq_shard_dp: int = 1, batched_pos: bool = False,
                paged: Optional[Tuple[int, int]] = None,
                ring_slack: int = 0) -> Tuple:
    """``paged=(n_blocks_local, block_size)`` builds the paged layout:
    attention layers get block pools, recurrent layers keep their per-slot
    constant-size state unchanged.  ``ring_slack`` adds spare entries to
    sliding-window ring caches (spec-decode verify headroom)."""
    groups = tfm.build_groups(ctx.cfg)
    return tuple(
        tfm.group_cache(ctx.cfg, ctx.plan, ctx.dist, g, batch_local, cache_len,
                        kv_seq_shard_dp, quant=ctx.parallel.kv_quant,
                        batched_pos=batched_pos, paged=paged,
                        ring_slack=ring_slack)
        for g in groups
    )


