"""Serving engine: jitted shard_map'd prefill/decode steps + host generate loop.

The decode step is the paper's experiment unit (§3 measures ms/token of
exactly this function).  Schedule per decode round, with all paper
optimizations on:

  1 x  (token ids already replicated — §2.1a "broadcast" is free)
  L x  block reductions (1 psum per parallel-residual block, 2 per
       sequential block, or scatter/gather pairs under SP)
  1 x  k-candidate all-gather for sampling (§2.1b)

KV caches are DONATED to the decode step (§2.3): XLA aliases them in-place,
`memory_analysis().alias_size_in_bytes` is the receipt.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig, ParallelConfig, SamplingConfig
from repro.core.capabilities import ArchCapabilities
from repro.models import model as M
from repro.runtime import kvcache
from repro.runtime.sampling import sample_tokens

Pytree = Any


def make_prefill_step(ctx: M.ModelCtx, sampling: SamplingConfig):
    """Per-shard fn: (params, tokens, features, caches, rng) -> (tok, caches)."""

    def prefill_step(params, tokens, features, caches, rng):
        kv_axis = ctx.dist.data_axis if ctx.parallel.kv_seq_shard else None
        logits, caches, _ = M.forward(
            params, tokens, ctx, features=features, caches=caches,
            last_only=True, seq_sharded=True, kv_seq_axis=kv_axis,
        )
        tok = sample_tokens(
            logits[:, -1], rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        )
        return tok, caches

    return prefill_step


def make_decode_step(ctx: M.ModelCtx, sampling: SamplingConfig):
    """Per-shard fn: (params, tok, caches, cur_pos, rng) -> (tok', caches)."""

    def decode_step(params, tok, caches, cur_pos, rng):
        kv_axis = ctx.dist.data_axis if ctx.parallel.kv_seq_shard else None
        tokens = tok[:, None] if tok.ndim == 1 else tok[:, None, :]
        logits, caches, _ = M.forward(
            params, tokens, ctx, caches=caches, cur_pos=cur_pos,
            kv_seq_axis=kv_axis, last_only=True, seq_sharded=False,
        )
        nxt = sample_tokens(
            logits[:, -1], rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        )
        return nxt, caches

    return decode_step


# ---------------------------------------------------------------------------
# Continuous batching (slot engine)
#
# The wave model above decodes a whole batch at one shared ``cur_pos``.  The
# slot engine instead runs a fixed-capacity batch where every row is an
# independent *slot* at its own position ``pos[b]``: finished/empty slots are
# masked inside the jitted step, and new requests are admitted in-flight by
# prefilling into free slots of the live cache — no batch restart, no
# recompile (prompt lengths are bucketed by the scheduler).
# ---------------------------------------------------------------------------


def make_slot_prefill_step(ctx: M.ModelCtx, sampling: SamplingConfig):
    """Per-shard in-flight admission step.

    (params, tokens (b,Lp), caches, admit (b,) bool, plens (b,), rng)
      -> (tok (b,), caches)

    Runs a full-width prefill over the padded token batch, then merges ONLY
    the admitted slots back into the live cache; un-admitted rows keep their
    cache/state bit-for-bit (their forward results are discarded).  Each
    admitted slot samples its first token from its own last *real* prompt
    position (padding never conditions the sample — per-request semantics are
    identical to running the request alone)."""
    from repro.models import transformer as tfm

    groups = tfm.build_groups(ctx.cfg)

    prefix = ctx.cfg.frontend.prefix_len if ctx.cfg.frontend else 0

    def prefill_slots(params, tokens, caches, admit, plens, rng):
        # fresh requests integrate recurrent state from t=0 and must not see
        # stale positions, so their slots reset before the forward
        caches_r = kvcache.reset_slots(caches, groups, admit)
        features = None
        if prefix:
            # modality-prefix archs: the stub encoder consumes zero features
            # (as in Engine.generate) and projects a fixed-length prefix in
            # front of every row's prompt, so every prefix column is real and
            # each row's valid cache extent is prefix + plen.
            features = jnp.zeros(
                (tokens.shape[0], prefix, ctx.cfg.frontend.feature_dim),
                jnp.float32)
        ext = plens + prefix
        lmask = (jnp.arange(prefix + tokens.shape[1], dtype=jnp.int32)[None, :]
                 < ext[:, None])                         # (b, prefix + Lp)
        hidden, new_caches, _ = M.forward(
            params, tokens, ctx, features=features, caches=caches_r,
            last_only=False, skip_head=True, seq_sharded=True,
            length_mask=lmask,
        )
        idx = jnp.clip(ext - 1, 0, prefix + tokens.shape[1] - 1)
        h_last = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)
        logits = M.lm_head_local(params, h_last, ctx)
        tok = sample_tokens(
            logits[:, -1], rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        )
        new_caches = kvcache.mask_prompt_padding(new_caches, groups, ext)
        merged = kvcache.merge_slots(caches, new_caches, groups, admit)
        return tok, merged

    return prefill_slots


def make_slot_decode_step(ctx: M.ModelCtx, sampling: SamplingConfig):
    """Per-shard masked decode step with per-slot positions.

    (params, tok, caches, pos, done, remaining, eos, rng)
      -> (nxt, caches, pos', done', remaining')

    ``pos`` (b,) is the cache index the incoming token is written at (== its
    absolute position); done/remaining implement per-slot stopping (eos or
    budget) INSIDE the program, so a fused multi-step scan never overruns a
    slot: finished rows freeze their token/position and their (harmless,
    row-local) cache write lands at the frozen index."""

    def slot_decode(params, tok, caches, pos, done, remaining, eos, rng,
                    block_tables=None):
        tokens = tok[:, None] if tok.ndim == 1 else tok[:, None, :]
        logits, caches, _ = M.forward(
            params, tokens, ctx, caches=caches, cur_pos=pos,
            kv_seq_axis=None, last_only=True, seq_sharded=False,
            block_tables=block_tables,
        )
        nxt = sample_tokens(
            logits[:, -1], rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        )
        active = (~done) & (remaining > 0)
        amask = active if nxt.ndim == 1 else active[:, None]
        nxt = jnp.where(amask, nxt, tok)
        new_pos = jnp.where(active, pos + 1, pos)
        flat = nxt if nxt.ndim == 1 else nxt[..., 0]
        hit_eos = active & (eos >= 0) & (flat == eos)
        new_remaining = jnp.where(active, remaining - 1, remaining)
        new_done = done | hit_eos | (new_remaining <= 0)
        return nxt, caches, new_pos, new_done, new_remaining

    return slot_decode


def make_paged_prefill_step(ctx: M.ModelCtx, sampling: SamplingConfig,
                            *, with_prefix: bool):
    """Paged in-flight admission: like the dense slot prefill, but K/V lands
    in the block pool through a write block table and (with_prefix=True)
    each row's tokens are only its prompt SUFFIX — the shared-prefix blocks
    are already resident and are attended through the slot's view.

    (params, tokens (b,Lp), caches, admit, plens, starts, total_lens,
     block_tables, rng) -> (tok (b,), caches)

    ``plens`` are suffix lengths, ``starts`` the per-slot absolute offset of
    the suffix (0 without sharing), ``total_lens = starts + plens`` the full
    prompt length.  The un-admitted rows' table entries are the null block,
    which confines their scatter writes to a dead sink; merge_slots then
    row-selects only the per-slot leaves (pos, recurrent state)."""
    from repro.models import transformer as tfm

    groups = tfm.build_groups(ctx.cfg)

    def prefill_paged(params, tokens, caches, admit, plens, starts,
                      total_lens, bt, rng):
        caches_r = kvcache.reset_slots(caches, groups, admit, paged=True)
        lmask = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
                 < plens[:, None])                              # (b, Lp)
        hidden, new_caches, _ = M.forward(
            params, tokens, ctx, caches=caches_r, last_only=False,
            skip_head=True, seq_sharded=True, length_mask=lmask,
            block_tables=bt, start_pos=starts if with_prefix else None,
        )
        idx = jnp.clip(plens - 1, 0, tokens.shape[1] - 1)
        h_last = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)
        logits = M.lm_head_local(params, h_last, ctx)
        tok = sample_tokens(
            logits[:, -1], rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        )
        new_caches = kvcache.set_slot_positions(new_caches, groups, total_lens)
        merged = kvcache.merge_slots(caches, new_caches, groups, admit,
                                     paged=True)
        return tok, merged

    return prefill_paged


def _make_chunk_half(ctx: M.ModelCtx, sampling: SamplingConfig, groups,
                     *, paged: bool):
    """The chunk-prefill half shared by the fused mixed step and the
    chunk-only step (disaggregated prefill pool): scatter ONE chunk of up to
    C tokens for every admitting slot and sample each row's next token from
    its last real chunk position.  ``rng`` arrives pre-folded by the caller
    so both users derive ``ptok`` from the identical key stream."""

    def half(params, ctokens, caches, admit, first, clens, starts, totals,
             bt_w, rng):
        caches_r = kvcache.reset_slots(caches, groups, admit & first,
                                       paged=paged)
        lmask = (jnp.arange(ctokens.shape[1], dtype=jnp.int32)[None, :]
                 < clens[:, None])                           # (b, C)
        hidden, new_caches, _ = M.forward(
            params, ctokens, ctx, caches=caches_r, last_only=False,
            skip_head=True, seq_sharded=True, length_mask=lmask,
            start_pos=starts, block_tables=bt_w,
        )
        idx = jnp.clip(clens - 1, 0, ctokens.shape[1] - 1)
        h_last = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)
        logits = M.lm_head_local(params, h_last, ctx)
        ptok = sample_tokens(
            logits[:, -1], rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        )
        new_caches = kvcache.set_slot_positions(
            new_caches, groups, totals,
            window=0 if paged else ctx.cfg.window)
        merged = kvcache.merge_slots(caches, new_caches, groups, admit,
                                     paged=paged)
        return ptok, merged

    return half


def make_chunk_prefill_step(ctx: M.ModelCtx, sampling: SamplingConfig,
                            *, paged: bool):
    """Chunk-prefill-ONLY step — the prefill half of the mixed step with no
    decode ride-along, for the disaggregated prefill pool where decode-active
    slots live on other shards and step separately.

    (params, ctokens (b,C), caches, admit, first, clens, starts, totals,
     [bt_w,] rng) -> (ptok (b,), caches)

    Operand semantics match the mixed step's prefill half exactly (and ptok
    folds the same rng stream), so a prompt chunk-prefilled here is
    bit-identical to one admitted through the unified mixed step."""
    from repro.models import transformer as tfm

    groups = tfm.build_groups(ctx.cfg)
    half = _make_chunk_half(ctx, sampling, groups, paged=paged)

    def chunk(params, ctokens, caches, admit, first, clens, starts, totals,
              *rest):
        *bts, rng = rest
        bt_w = bts[0] if paged else None
        return half(params, ctokens, caches, admit, first, clens, starts,
                    totals, bt_w, jax.random.fold_in(rng, 0))

    return chunk


def make_mixed_step(ctx: M.ModelCtx, sampling: SamplingConfig, *, paged: bool):
    """Fused chunked-prefill + decode step — the unit of chunked admission.

    (params, ctokens (b,C), caches, admit, first, clens, starts, totals,
     tok, pos, done, remaining, eos, [bt_w, bt,] rng)
      -> (ptok (b,), nxt (b,), caches, pos', done', remaining')

    One jitted program does BOTH halves of a serving step so a long prompt
    never stalls in-flight decode for more than one chunk of compute:

    * prefill ONE chunk of up to C tokens for every admitting slot —
      ``starts`` (b,) is each row's resume offset (view position of the
      chunk's first token), ``clens`` its real token count, ``first`` marks
      a request's opening chunk (slot state resets), ``totals`` the row's
      valid cache extent after this chunk (position rows are rewritten
      whole).  ``ptok`` samples each row's next token from its last real
      chunk position — the host uses it only for rows whose chunk completed
      the prompt (their first emitted token);
    * one masked decode step for every decode-active slot (admitting slots
      ride with done=True, so the decode half freezes them).

    The chunk width C is FIXED by the scheduler, so this path compiles once
    — no pow-2 prompt buckets.  Paged variant threads two tables: ``bt_w``
    (admitting rows real, all others null — confines the chunk scatter)
    for the prefill half, ``bt`` (real) for the decode half."""
    from repro.models import transformer as tfm

    groups = tfm.build_groups(ctx.cfg)
    half = _make_chunk_half(ctx, sampling, groups, paged=paged)
    dec = make_slot_decode_step(ctx, sampling)

    def mixed(params, ctokens, caches, admit, first, clens, starts, totals,
              tok, pos, done, remaining, eos, *rest):
        *bts, rng = rest
        bt_w = bts[0] if paged else None
        bt = bts[1] if paged else None
        ptok, merged = half(params, ctokens, caches, admit, first, clens,
                            starts, totals, bt_w, jax.random.fold_in(rng, 0))
        # The decode half freezes admitting rows (done=True), but a frozen
        # row still performs its row-local cache write at its incoming
        # position — which for an admitting row is STALE and would clobber
        # the chunk just written.
        if paged:
            # Redirect those rows' write index to the last view slot: dead
            # by causality (entry value == index, never <= any earlier
            # cur_pos), confined by the nulled block table, and overwritten
            # by the real decode write before the row could ever attend it.
            sink = caches[0]["sub0"]["pos"].shape[-1] - 1
            dec_pos = jnp.where(admit, jnp.int32(sink), pos)
            nxt, merged, pos, done, remaining = dec(
                params, tok, merged, dec_pos, done, remaining, eos,
                jax.random.fold_in(rng, 1), block_tables=bt)
        else:
            # Dense caches include ring layouts, which have NO dead index to
            # redirect to (every in-window slot is live).  Let the frozen
            # write land at the stale position, then re-select the chunk
            # half's rows for admitting slots — a pure per-row merge that
            # discards the stale write entirely (and is equivalent to the
            # sink redirect for non-ring layouts).
            nxt, dec_caches, pos, done, remaining = dec(
                params, tok, merged, pos, done, remaining, eos,
                jax.random.fold_in(rng, 1), block_tables=None)
            merged = kvcache.merge_slots(dec_caches, merged, groups, admit)
        return ptok, nxt, merged, pos, done, remaining

    return mixed


def make_spec_verify_step(ctx: M.ModelCtx, sampling: SamplingConfig,
                          *, paged: bool):
    """Fused multi-token speculative-decode verify step.

    (params, vtokens (b, K+1), caches, pos, done, remaining, eos, [bt,] rng)
      -> (targets (b, K+1), n_emit (b,), nxt (b,), caches, pos', done',
          remaining')

    ``vtokens[:, 0]`` is each slot's pending token (the one plain decode
    would feed this step); columns 1..K are the host drafter's proposals.
    A verify step IS a width-(K+1) prefill chunk at the decode frontier:
    the K+1 tokens scatter into the cache at view offsets pos..pos+K via
    the batched-offset chunk writers, each row attends its stripe
    [0, pos+K] through the same flash-prefill path as chunked admission
    (view index == absolute position, causality does all the masking), and
    ALL K+1 positions sample a target token from one forward pass — one
    weight sweep scores K+1 conditionals instead of 1.

    Per slot, targets[j] is drawn from the true conditional given
    [history, vtokens[:j+1]]; draft j+1 is accepted iff it equals
    targets[j], so the emitted run targets[0..acc] (``acc`` accepted drafts
    + the bonus token at the first rejected position) is distributed
    exactly as plain autoregressive decode — and bit-identical under
    greedy.  The emit length is additionally cut at the slot's budget and
    at the first EOS among the emitted run, mirroring the masked
    slot-decode stopping rule in-program.

    KV rewind: entries pos+e..pos+K hold K/V of rejected drafts.  Dense
    slots rewind by position mask (set_slot_positions marks [0, pos+e)
    valid; the dead entries are overwritten by the next verify chunk
    before they could ever be attended, since its writes start exactly at
    pos+e).  Paged slots additionally have their block tables truncated on
    the host after the step.  Frozen rows (done / mid-admission) keep
    their cache bit-for-bit: dense rows merge from the old tree, paged
    rows write through a nulled block-table row."""
    from repro.models import transformer as tfm

    groups = tfm.build_groups(ctx.cfg)

    def verify(params, vtokens, caches, pos, done, remaining, eos, *rest):
        *bts, rng = rest
        bt = bts[0] if paged else None
        b, K1 = vtokens.shape
        active = (~done) & (remaining > 0)
        hidden, new_caches, _ = M.forward(
            params, vtokens, ctx, caches=caches, last_only=False,
            skip_head=True, seq_sharded=True, start_pos=pos,
            block_tables=bt,
        )
        logits = M.lm_head_local(params, hidden, ctx)      # (b, K+1, Vp)
        targets = sample_tokens(
            logits.reshape(b * K1, -1), rng, sampling, ctx.plan, ctx.dist,
            topk_sync_enabled=ctx.parallel.topk_sync,
            use_pallas=ctx.parallel.use_pallas,
        ).reshape(b, K1)
        # longest accepted draft prefix, then cut at EOS and budget
        match = (vtokens[:, 1:] == targets[:, :-1]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)       # (b,) in [0, K]
        idx = jnp.arange(K1, dtype=jnp.int32)
        is_eos = (eos[:, None] >= 0) & (targets == eos[:, None])
        j_eos = jnp.min(jnp.where(is_eos, idx[None, :], K1), axis=1)
        e = jnp.minimum(jnp.minimum(acc + 1, j_eos + 1), remaining)
        e = jnp.where(active, e, 0).astype(jnp.int32)
        new_pos = pos + e
        new_remaining = remaining - e
        hit_eos = active & (j_eos < e)
        new_done = done | hit_eos | (active & (new_remaining <= 0))
        last = jnp.clip(e - 1, 0, K1 - 1)
        nxt = jnp.where(
            active,
            jnp.take_along_axis(targets, last[:, None], axis=1)[:, 0],
            vtokens[:, 0])
        # rewind: exactly [0, pos+e) is valid for active rows; frozen rows
        # keep their old cache (and pos rows) through the per-row merge
        new_caches = kvcache.set_slot_positions(
            new_caches, groups, new_pos,
            window=0 if paged else ctx.cfg.window)
        merged = kvcache.merge_slots(caches, new_caches, groups, active,
                                     paged=paged)
        return targets, e, nxt, merged, new_pos, new_done, new_remaining

    return verify


def make_paged_decode_step(ctx: M.ModelCtx, sampling: SamplingConfig):
    """Masked per-slot decode over the paged pool: the dense slot-decode
    body with cache reads/writes routed through the block table.
    (params, tok, caches, pos, done, remaining, eos, bt, rng) ->
    (nxt, caches, pos', done', remaining')."""
    dec = make_slot_decode_step(ctx, sampling)

    def paged_decode(params, tok, caches, pos, done, remaining, eos, bt, rng):
        return dec(params, tok, caches, pos, done, remaining, eos, rng,
                   block_tables=bt)

    return paged_decode


def make_migrate_step(ctx: M.ModelCtx):
    """Batched cross-pool KV-block migration (disaggregated serving).

    (caches, src (m,), dst (m,), land (b,), totals (b,)) -> caches

    ``src``/``dst`` are GLOBAL block ids (shard * blocks_per_shard + local);
    every pool leaf copies row ``src[j]`` into row ``dst[j]`` in one gather +
    scatter over the block dim.  The program is jitted GLOBALLY (no
    shard_map): the pool's block dim is sharded over the data axis, so when
    src and dst fall on different shards GSPMD lowers the copy to the actual
    device-to-device transfer — which is precisely the migration traffic the
    scheduler accounts (migration_bytes = blocks x pool_block_bytes).

    ``land`` flags decode slots receiving a fully-migrated request this
    step; their position rows are rewritten to ``[0, totals[b])`` valid so
    the landed view is immediately decodable (all other rows, and all
    recurrent per-slot state, are untouched — bit-for-bit).

    Callers pad (src, dst) with null self-copies (0 -> 0) to a bucketed
    width: global block 0 is shard 0's reserved null block, and duplicate
    scatter writes of identical values are benign."""
    from repro.models import transformer as tfm

    groups = tfm.build_groups(ctx.cfg)

    def migrate(caches, src, dst, land, totals):
        def f(key, leaf, stacked):
            if key not in kvcache.POOL_KEYS:
                return leaf
            if stacked:                     # (layers, n_blocks, ...)
                return leaf.at[:, dst].set(leaf[:, src])
            return leaf.at[dst].set(leaf[src])

        out = kvcache._map_by_key(caches, groups, f)
        newpos = kvcache.set_slot_positions(out, groups, totals)
        return kvcache.merge_slots(out, newpos, groups, land, paged=True)

    return migrate


@dataclass
class Engine:
    """Host-side serving engine over a local (or production) mesh."""

    cfg: ModelConfig
    parallel: ParallelConfig
    sampling: SamplingConfig
    mesh: Any
    max_len: int
    params: Pytree = None
    seed: int = 0
    wq_cache: Optional[str] = None   # path for the packed QuantWeight tree:
                                     # load it if present (skipping bf16
                                     # materialization), else save after
                                     # quantize-at-load
    # pre-dispatch hook, called with no arguments at the top of every
    # retry-safe serving step (decode/mixed/chunk/verify) BEFORE the jitted
    # program is queued.  This is the engine's fault boundary: an exception
    # raised here leaves the donated cache chain untouched — the program
    # never dispatched, so the exact pre-step state survives and the caller
    # may re-dispatch (the schedulers' bounded-retry path).  Once a program
    # holding donated buffers HAS dispatched, a host-side replay is
    # impossible; that asymmetry is why fault injection and the watchdog
    # delay both live at this hook.  Installed by schedulers running a
    # FaultPlan (runtime/faults.py); None = zero overhead.
    dispatch_hook: Optional[Any] = None

    def __post_init__(self):
        pod = "pod" if "pod" in self.mesh.axis_names else None
        self.ctx = M.ModelCtx.make(self.cfg, self.parallel, pod_axis=pod)
        self.parallel = self.ctx.parallel      # use_pallas resolved
        # the declarative capability record every scheduler/serve entry
        # consults (the single require() choke point for path eligibility)
        self.caps = ArchCapabilities.from_config(self.cfg)
        wq = self.parallel.weight_quant != "none"
        loaded = False
        if self.params is None:
            if wq and self.wq_cache and M.has_quantized(self.wq_cache):
                self.params = M.load_quantized(self.ctx, self.wq_cache)
                loaded = True
            else:
                self.params = M.init_params(self.ctx, jax.random.key(self.seed),
                                            self.mesh)
        if wq:
            # quantize-at-load: the serving programs only ever see packed
            # weights + scales; param_specs mirrors the transform so the
            # shard_map spec trees stay structurally identical (quantize is
            # a no-op on already-packed QuantWeight leaves, so a tree
            # restored from wq_cache passes straight through)
            self.params = M.quantize_params(self.ctx, self.params)
            if self.wq_cache and not loaded:
                M.save_quantized(self.ctx, self.params, self.wq_cache)
        self._build()

    # -- sharding specs -----------------------------------------------------
    def _specs(self):
        dist = self.ctx.dist
        d = dist.data_axes if len(dist.data_axes) > 1 else dist.data_axes[0]
        batch_spec = P(None) if self.parallel.kv_seq_shard else P(d)
        tok2 = P(*batch_spec, None) if self.cfg.n_codebooks == 1 else P(*batch_spec, None, None)
        tok1 = P(*batch_spec) if self.cfg.n_codebooks == 1 else P(*batch_spec, None)
        feat = P(*batch_spec, None, None)
        cache = kvcache.cache_pspecs(self.ctx, kv_seq_shard=self.parallel.kv_seq_shard)
        return batch_spec, tok2, tok1, feat, cache

    def _build(self):
        pspecs = M.param_specs(self.ctx)
        batch_spec, tok2, tok1, feat, cache_spec = self._specs()
        sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)

        pre = make_prefill_step(self.ctx, self.sampling)
        if self.cfg.frontend is None:
            pre_nofeat = lambda p, t, c, r: pre(p, t, None, c, r)
            self._prefill_raw = jax.jit(
                sm(pre_nofeat, in_specs=(pspecs, tok2, cache_spec, P()),
                   out_specs=(tok1, cache_spec)),
                donate_argnums=(2,) if self.parallel.zero_copy else (),
            )
            self._prefill = lambda p, t, f, c, r: self._prefill_raw(p, t, c, r)
        else:
            self._prefill = jax.jit(
                sm(pre, in_specs=(pspecs, tok2, feat, cache_spec, P()),
                   out_specs=(tok1, cache_spec)),
                donate_argnums=(3,) if self.parallel.zero_copy else (),
            )
        dec = make_decode_step(self.ctx, self.sampling)
        self._decode = jax.jit(
            sm(dec, in_specs=(pspecs, tok1, cache_spec, P(), P()),
               out_specs=(tok1, cache_spec)),
            donate_argnums=(2,) if self.parallel.zero_copy else (),
        )

        # §Perf H4: fused multi-token decode — lax.scan over n steps inside
        # ONE jitted program removes the per-token dispatch + host-sync
        # overhead of the token loop (the paper's §3 metric IS this loop).
        def decode_n(params, tok, caches, cur_pos, rng, *, n):
            def body(carry, i):
                tok, caches = carry
                nxt, caches = dec(params, tok, caches,
                                  cur_pos + i, jax.random.fold_in(rng, i))
                return (nxt, caches), nxt

            (tok, caches), toks = jax.lax.scan(
                body, (tok, caches), jnp.arange(n, dtype=jnp.int32))
            return toks, caches

        tokn = P(None, *tuple(tok1))
        self._decode_n = {
            n: jax.jit(
                sm(partial(decode_n, n=n),
                   in_specs=(pspecs, tok1, cache_spec, P(), P()),
                   out_specs=(tokn, cache_spec)),
                donate_argnums=(2,) if self.parallel.zero_copy else (),
            )
            for n in (8, 16, 32)
        }

    # -- continuous batching (slot engine) --------------------------------
    def _slot_gate(self):
        if self.parallel.kv_seq_shard:
            raise ValueError("slot engine is incompatible with kv_seq_shard")

    def _slot_decode_builder(self, dec, cspec, extra_specs=()):
        """``build_decode(n)`` factory shared by the dense and paged slot
        engines: a lax.scan of ``n`` fused masked steps.  ``extra_specs``
        appends trailing sharded operands (the paged block table) that are
        threaded into every step between ``eos`` and the rng."""
        pspecs = M.param_specs(self.ctx)
        batch_spec, _, tok1, _, _ = self._specs()
        sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)
        slot = P(*batch_spec)
        donate = (2,) if self.parallel.zero_copy else ()

        def decode_n(params, tok, caches, pos, done, remaining, eos, *rest,
                     n):
            *extra, rng = rest

            def body(carry, i):
                tok, caches, pos, done, remaining = carry
                nxt, caches, pos, done, remaining = dec(
                    params, tok, caches, pos, done, remaining, eos, *extra,
                    jax.random.fold_in(rng, i))
                return (nxt, caches, pos, done, remaining), nxt

            (tok, caches, pos, done, remaining), toks = jax.lax.scan(
                body, (tok, caches, pos, done, remaining),
                jnp.arange(n, dtype=jnp.int32))
            return toks, caches, pos, done, remaining

        tokn = P(None, *tuple(tok1))

        def build_decode(n):
            return jax.jit(
                sm(partial(decode_n, n=n),
                   in_specs=(pspecs, tok1, cspec, slot, slot, slot, slot,
                             *extra_specs, P()),
                   out_specs=(tokn, cspec, slot, slot, slot)),
                donate_argnums=donate,
            )

        return build_decode

    def _cb(self):
        """Lazily-built slot-engine programs (prefill_into_slots + fused
        masked decode).  Separate from the wave programs so wave-only users
        pay no extra compile time."""
        if getattr(self, "_cb_built", None) is None:
            self._slot_gate()
            pspecs = M.param_specs(self.ctx)
            batch_spec, tok2, tok1, _, _ = self._specs()
            cspec = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                         batched_pos=True)
            sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)
            slot = P(*batch_spec)
            donate = (2,) if self.parallel.zero_copy else ()

            pre = make_slot_prefill_step(self.ctx, self.sampling)
            prefill = jax.jit(
                sm(pre, in_specs=(pspecs, tok2, cspec, slot, slot, P()),
                   out_specs=(tok1, cspec)),
                donate_argnums=donate,
            )

            dec = make_slot_decode_step(self.ctx, self.sampling)
            self._cb_built = {
                "prefill": prefill, "decode": {},
                "build_decode": self._slot_decode_builder(dec, cspec),
            }
        return self._cb_built

    def init_slot_caches(self, n_slots: int, *, ring_slack: Optional[int] = None):
        """``ring_slack`` sizes sliding-window ring caches at window + slack
        so a speculative verify chunk of K drafts never wraps onto live
        window entries; defaults to the configured spec_k."""
        dp_total = self.ctx.dist.dp * self.ctx.dist.pods
        if n_slots % dp_total:
            raise ValueError(f"n_slots {n_slots} must divide dp*pods {dp_total}")
        slack = self.parallel.spec_k if ring_slack is None else ring_slack
        return self.init_caches(n_slots, batched_pos=True, ring_slack=slack)

    def prefill_into_slots(self, caches, tokens, admit, plens, rng):
        """Admit requests in-flight: prefill ``tokens`` (B, Lp[, ncb]) into
        the slots flagged by ``admit`` (B,) of a LIVE cache; other slots are
        untouched.  Returns (first sampled token (B,[ncb]), caches).

        jit retraces per distinct Lp — callers bucket prompt lengths (the
        scheduler pads to powers of two) to bound compilation."""
        cb = self._cb()
        return cb["prefill"](
            self.params, jnp.asarray(tokens), caches,
            jnp.asarray(admit, bool), jnp.asarray(plens, jnp.int32), rng)

    @staticmethod
    def land(*arrays):
        """Materialize device futures to host numpy, blocking until the
        dispatched programs that produce them have executed.  The single
        synchronization primitive of the overlapped serving loop: every
        ``decode_slots``/``verify_slots``/``mixed_step`` output is a device
        future under JAX async dispatch, so a caller that chains outputs
        into the next dispatch and ``land``s one step late overlaps all of
        its host work with device compute.

        **Async-dispatch contract** (what makes chaining safe): jitted
        programs execute in dispatch order per device, so a program that
        consumes another's output future always reads the produced value —
        including donated cache buffers (``zero_copy``), provided the chain
        stays linear: each cache future is consumed by exactly one
        subsequent dispatch.  Host numpy arrays captured at dispatch time
        are copied by ``jnp.asarray`` during tracing/transfer, so the
        caller may mutate its host mirrors freely while blocks are in
        flight."""
        out = [np.asarray(a) for a in arrays]
        return out[0] if len(out) == 1 else out

    def _predispatch(self):
        """Run the fault/watchdog hook before a retry-safe step dispatch
        (see ``dispatch_hook``)."""
        if self.dispatch_hook is not None:
            self.dispatch_hook()

    def decode_slots(self, caches, tok, pos, done, remaining, eos, rng, *, n=1):
        """Run ``n`` fused masked decode steps over all slots.

        Outputs are device FUTURES (JAX async dispatch): callers may chain
        them into the next ``decode_slots`` call without materializing and
        ``Engine.land`` them one step late — see the overlapped scheduler
        loop.  Returns (toks (n, B[, ncb]), caches, pos, done, remaining)."""
        self._predispatch()
        cb = self._cb()
        if n not in cb["decode"]:
            cb["decode"][n] = cb["build_decode"](n)
        return cb["decode"][n](
            self.params, tok, caches, jnp.asarray(pos, jnp.int32),
            jnp.asarray(done, bool), jnp.asarray(remaining, jnp.int32),
            jnp.asarray(eos, jnp.int32), rng)

    # -- chunked prefill (fused mixed prefill/decode step) -----------------
    def _mixed(self, paged: bool):
        """Lazily-built fused mixed step (jit retraces per chunk width; the
        scheduler pins one width, so the chunked path compiles exactly one
        prefill program — no pow-2 prompt buckets)."""
        cb = self._cb_paged() if paged else self._cb()
        if "mixed" not in cb:
            pspecs = M.param_specs(self.ctx)
            batch_spec, tok2, tok1, _, _ = self._specs()
            cspec = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                         batched_pos=True)
            sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)
            slot = P(*batch_spec)
            extra = (P(*batch_spec, None),) * 2 if paged else ()
            mix = make_mixed_step(self.ctx, self.sampling, paged=paged)
            cb["mixed"] = jax.jit(
                sm(mix,
                   in_specs=(pspecs, tok2, cspec, slot, slot, slot, slot,
                             slot, tok1, slot, slot, slot, slot, *extra, P()),
                   out_specs=(tok1, tok1, cspec, slot, slot, slot)),
                donate_argnums=(2,) if self.parallel.zero_copy else (),
            )
        return cb["mixed"]

    def mixed_step(self, caches, ctokens, admit, first, clens, starts, totals,
                   tok, pos, done, remaining, eos, rng):
        """One fused chunked-admission step over the dense slot engine:
        prefill one chunk into the admitting slots AND run one masked decode
        step for the decode-active slots, in the same jitted program.
        Returns (ptok (B,), nxt (B,), caches, pos, done, remaining)."""
        self._predispatch()
        return self._mixed(False)(
            self.params, jnp.asarray(ctokens), caches,
            jnp.asarray(admit, bool), jnp.asarray(first, bool),
            jnp.asarray(clens, jnp.int32), jnp.asarray(starts, jnp.int32),
            jnp.asarray(totals, jnp.int32), jnp.asarray(tok),
            jnp.asarray(pos, jnp.int32), jnp.asarray(done, bool),
            jnp.asarray(remaining, jnp.int32), jnp.asarray(eos, jnp.int32),
            rng)

    def mixed_step_paged(self, caches, ctokens, admit, first, clens, starts,
                         totals, tok, pos, done, remaining, eos, bt_w, bt,
                         rng):
        """Paged fused mixed step: ``bt_w`` routes the chunk scatter (null
        rows for every non-admitting slot), ``bt`` serves the decode half."""
        self._predispatch()
        return self._mixed(True)(
            self.params, jnp.asarray(ctokens), caches,
            jnp.asarray(admit, bool), jnp.asarray(first, bool),
            jnp.asarray(clens, jnp.int32), jnp.asarray(starts, jnp.int32),
            jnp.asarray(totals, jnp.int32), jnp.asarray(tok),
            jnp.asarray(pos, jnp.int32), jnp.asarray(done, bool),
            jnp.asarray(remaining, jnp.int32), jnp.asarray(eos, jnp.int32),
            jnp.asarray(bt_w, jnp.int32), jnp.asarray(bt, jnp.int32), rng)

    # -- disaggregated serving (chunk-only prefill + block migration) ------
    def _chunk_only(self, paged: bool):
        """Lazily-built chunk-prefill-only program (prefill-pool step of the
        disaggregated engine; same one-width compile story as _mixed)."""
        cb = self._cb_paged() if paged else self._cb()
        if "chunk" not in cb:
            pspecs = M.param_specs(self.ctx)
            batch_spec, tok2, tok1, _, _ = self._specs()
            cspec = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                         batched_pos=True)
            sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)
            slot = P(*batch_spec)
            extra = (P(*batch_spec, None),) if paged else ()
            ch = make_chunk_prefill_step(self.ctx, self.sampling, paged=paged)
            cb["chunk"] = jax.jit(
                sm(ch, in_specs=(pspecs, tok2, cspec, slot, slot, slot, slot,
                                 slot, *extra, P()),
                   out_specs=(tok1, cspec)),
                donate_argnums=(2,) if self.parallel.zero_copy else (),
            )
        return cb["chunk"]

    def chunk_slots_paged(self, caches, ctokens, admit, first, clens, starts,
                          totals, bt_w, rng):
        """One chunk-prefill-only step over the paged pool (no decode half):
        ``bt_w`` routes the chunk scatter, with null rows for every
        non-admitting slot.  Returns (ptok (B,), caches)."""
        self._predispatch()
        return self._chunk_only(True)(
            self.params, jnp.asarray(ctokens), caches,
            jnp.asarray(admit, bool), jnp.asarray(first, bool),
            jnp.asarray(clens, jnp.int32), jnp.asarray(starts, jnp.int32),
            jnp.asarray(totals, jnp.int32), jnp.asarray(bt_w, jnp.int32), rng)

    def _migrate(self, m: int):
        """Lazily-built jitted migration program per padded batch width
        ``m`` (widths are pow-2 bucketed by migrate_blocks)."""
        cb = self._cb_paged()
        key = ("migrate", m)
        if key not in cb:
            from jax.sharding import NamedSharding
            cspecs = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                          batched_pos=True)
            shard_of = jax.tree.map(
                lambda p: NamedSharding(self.mesh, p), cspecs,
                is_leaf=lambda x: isinstance(x, P))
            cb[key] = jax.jit(
                make_migrate_step(self.ctx),
                donate_argnums=(0,) if self.parallel.zero_copy else (),
                out_shardings=shard_of,
            )
        return cb[key]

    def migrate_blocks(self, caches, src_ids, dst_ids, land, totals):
        """Copy pool blocks ``src_ids`` -> ``dst_ids`` (GLOBAL ids; cross-
        shard pairs become device-to-device traffic) and land the slots
        flagged by ``land`` at valid extent ``totals``.  Returns caches."""
        n = len(src_ids)
        m = 1 << max(0, int(n - 1).bit_length())      # pow-2 bucket, >= 1
        src = np.zeros(m, np.int32)
        dst = np.zeros(m, np.int32)
        src[:n] = src_ids
        dst[:n] = dst_ids
        return self._migrate(m)(
            caches, jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(land, bool), jnp.asarray(totals, jnp.int32))

    # -- speculative decoding (fused multi-token verify) -------------------
    def _verify(self, paged: bool, K1: int):
        """Lazily-built jitted verify program for draft width K1-1 (jit
        retraces per distinct width; the scheduler pins one ``spec_k``, so
        spec decode compiles exactly one verify program per backend)."""
        cb = self._cb_paged() if paged else self._cb()
        key = ("verify", K1)
        if key not in cb:
            pspecs = M.param_specs(self.ctx)
            batch_spec, _, tok1, _, _ = self._specs()
            cspec = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                         batched_pos=True)
            sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)
            slot = P(*batch_spec)
            tokk = P(*batch_spec, None)
            extra = (tokk,) if paged else ()
            ver = make_spec_verify_step(self.ctx, self.sampling, paged=paged)
            cb[key] = jax.jit(
                sm(ver, in_specs=(pspecs, tokk, cspec, slot, slot, slot,
                                  slot, *extra, P()),
                   out_specs=(tokk, slot, tok1, cspec, slot, slot, slot)),
                donate_argnums=(2,) if self.parallel.zero_copy else (),
            )
        return cb[key]

    def verify_slots(self, caches, vtokens, pos, done, remaining, eos, rng):
        """One fused speculative verify step over the dense slot engine:
        score ``vtokens`` (B, spec_k+1) = [pending token, drafts] at the
        decode frontier of every active slot, accept the longest matching
        draft prefix plus one bonus token, and rewind the cache past it.
        Returns (targets (B, spec_k+1), n_emit (B,), nxt (B,), caches,
        pos', done', remaining')."""
        self._predispatch()
        vtokens = jnp.asarray(vtokens, jnp.int32)
        return self._verify(False, vtokens.shape[1])(
            self.params, vtokens, caches, jnp.asarray(pos, jnp.int32),
            jnp.asarray(done, bool), jnp.asarray(remaining, jnp.int32),
            jnp.asarray(eos, jnp.int32), rng)

    def verify_slots_paged(self, caches, vtokens, pos, done, remaining, eos,
                           block_tables, rng):
        """Paged verify step: the chunk scatter and the stripe gather both
        route through ``block_tables`` (rows for frozen slots nulled by the
        caller, confining their writes to the dead sink block)."""
        self._predispatch()
        vtokens = jnp.asarray(vtokens, jnp.int32)
        return self._verify(True, vtokens.shape[1])(
            self.params, vtokens, caches, jnp.asarray(pos, jnp.int32),
            jnp.asarray(done, bool), jnp.asarray(remaining, jnp.int32),
            jnp.asarray(eos, jnp.int32), jnp.asarray(block_tables, jnp.int32),
            rng)

    # -- paged KV backend (slot engine, second storage layout) -------------
    def _cb_paged(self):
        """Lazily-built paged slot programs.  Same gating and decode
        scaffolding as the dense slot engine; the block table rides as an
        extra sharded operand."""
        if getattr(self, "_cbp_built", None) is None:
            self._slot_gate()
            pspecs = M.param_specs(self.ctx)
            batch_spec, tok2, tok1, _, _ = self._specs()
            cspec = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                         batched_pos=True)
            sm = partial(compat.shard_map, mesh=self.mesh, check_vma=False)
            slot = P(*batch_spec)
            btspec = P(*batch_spec, None)
            donate = (2,) if self.parallel.zero_copy else ()

            prefill = {
                wp: jax.jit(
                    sm(make_paged_prefill_step(self.ctx, self.sampling,
                                               with_prefix=wp),
                       in_specs=(pspecs, tok2, cspec, slot, slot, slot, slot,
                                 btspec, P()),
                       out_specs=(tok1, cspec)),
                    donate_argnums=donate,
                )
                for wp in (False, True)
            }

            dec = make_paged_decode_step(self.ctx, self.sampling)
            self._cbp_built = {
                "prefill": prefill, "decode": {},
                "build_decode": self._slot_decode_builder(
                    dec, cspec, extra_specs=(btspec,)),
            }
        return self._cbp_built

    def init_paged_caches(self, n_slots: int, n_blocks: int, block_size: int):
        """Paged cache pytree: per-shard block pools assembled into global
        arrays.  The pool's block dim shards over the data axis (each shard
        owns an independent block namespace incl. its null block 0)."""
        dp_total = self.ctx.dist.dp * self.ctx.dist.pods
        if n_slots % dp_total:
            raise ValueError(f"n_slots {n_slots} must divide dp*pods {dp_total}")
        if n_blocks % dp_total:
            raise ValueError(f"n_blocks {n_blocks} must divide dp*pods {dp_total}")
        b_local, nb_local = n_slots // dp_total, n_blocks // dp_total
        cspecs = kvcache.cache_pspecs(self.ctx, kv_seq_shard=False,
                                      batched_pos=True)
        make = jax.jit(compat.shard_map(
            lambda: M.init_caches(self.ctx, b_local, self.max_len,
                                  batched_pos=True,
                                  paged=(nb_local, block_size)),
            mesh=self.mesh, in_specs=(), out_specs=cspecs, check_vma=False,
        ))
        return make()

    def prefill_into_slots_paged(self, caches, tokens, admit, plens, starts,
                                 total_lens, block_tables, rng):
        """Paged admission.  ``tokens`` (B, Lp) hold each row's prompt
        SUFFIX (the whole prompt when nothing is cached); ``starts`` (B,)
        the absolute offset of that suffix; ``block_tables`` (B, nbps) the
        write tables (null rows for un-admitted slots).  Two jitted
        variants: the no-prefix program's attention math is identical to the
        dense slot engine; the with-prefix program attends each slot's
        gathered view."""
        cb = self._cb_paged()
        starts = jnp.asarray(starts, jnp.int32)
        with_prefix = bool(np.asarray(starts).any())
        return cb["prefill"][with_prefix](
            self.params, jnp.asarray(tokens), caches,
            jnp.asarray(admit, bool), jnp.asarray(plens, jnp.int32), starts,
            jnp.asarray(total_lens, jnp.int32),
            jnp.asarray(block_tables, jnp.int32), rng)

    def decode_slots_paged(self, caches, tok, pos, done, remaining, eos,
                           block_tables, rng, *, n=1):
        """``n`` fused masked decode steps through the block tables."""
        self._predispatch()
        cb = self._cb_paged()
        if n not in cb["decode"]:
            cb["decode"][n] = cb["build_decode"](n)
        return cb["decode"][n](
            self.params, tok, caches, jnp.asarray(pos, jnp.int32),
            jnp.asarray(done, bool), jnp.asarray(remaining, jnp.int32),
            jnp.asarray(eos, jnp.int32), jnp.asarray(block_tables, jnp.int32),
            rng)

    # -- API ------------------------------------------------------------
    def init_caches(self, batch: int, *, batched_pos: bool = False,
                    ring_slack: int = 0):
        """Create the cache pytree as properly-sharded global arrays: each
        shard builds its LOCAL buffers inside shard_map and the runtime
        assembles the global arrays per the cache specs."""
        dp_total = self.ctx.dist.dp * self.ctx.dist.pods
        if self.parallel.kv_seq_shard:
            if batched_pos:
                raise ValueError("continuous batching (batched_pos) is "
                                 "incompatible with kv_seq_shard")
            b_local, kv_dp = batch, self.ctx.dist.dp
        else:
            b_local, kv_dp = batch // dp_total, 1
        cspecs = kvcache.cache_pspecs(self.ctx,
                                      kv_seq_shard=self.parallel.kv_seq_shard,
                                      batched_pos=batched_pos)
        make = jax.jit(compat.shard_map(
            lambda: M.init_caches(self.ctx, b_local, self.max_len,
                                  kv_seq_shard_dp=kv_dp,
                                  batched_pos=batched_pos,
                                  ring_slack=ring_slack),
            mesh=self.mesh, in_specs=(), out_specs=cspecs, check_vma=False,
        ))
        return make()

    def generate(self, prompts: np.ndarray, max_new: int,
                 features: Optional[np.ndarray] = None,
                 *, multi_step: bool = True) -> np.ndarray:
        """prompts (b, prompt_len [, ncb]) -> generated tokens (b, max_new [, ncb]).

        multi_step=True uses the fused n-token decode programs (§Perf H4);
        set False to force the one-jit-call-per-token baseline loop."""
        b, plen = prompts.shape[0], prompts.shape[1]
        caches = self.init_caches(b)
        if features is None and self.cfg.frontend is not None:
            f = self.cfg.frontend
            features = np.zeros((b, f.prefix_len, f.feature_dim), np.float32)
        rng = jax.random.key(self.seed + 1)
        prefix = self.cfg.frontend.prefix_len if self.cfg.frontend else 0
        tok, caches = self._prefill(self.params, jnp.asarray(prompts),
                                    features, caches, rng)
        outs = [tok[None] if tok.ndim == 1 else tok[None, ...]]
        cur = plen + prefix  # next position to write
        remaining = max_new - 1
        while remaining > 0:
            n = next((n for n in (32, 16, 8)
                      if multi_step and remaining >= n), 0)
            rng = jax.random.fold_in(rng, cur)
            if n:
                toks, caches = self._decode_n[n](self.params, tok, caches,
                                                 jnp.int32(cur), rng)
                tok = toks[-1]
                outs.append(toks)
                cur += n
                remaining -= n
            else:
                tok, caches = self._decode(self.params, tok, caches,
                                           jnp.int32(cur), rng)
                outs.append(tok[None])
                cur += 1
                remaining -= 1
        return np.asarray(jnp.concatenate(outs, axis=0)).swapaxes(0, 1)
