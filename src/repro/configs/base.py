"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig`; the
values are exact per the assignment table and cite their source in the
per-arch module.  Configs are frozen dataclasses so they are hashable and
usable as jit static arguments.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block config (GShard-style capacity dispatch)."""

    n_experts: int
    top_k: int
    expert_d_ff: int
    n_shared: int = 0           # DeepSeekMoE shared experts (always-on)
    shared_d_ff: int = 0        # d_ff of the shared experts (total)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01  # load-balance loss weight (training)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2 / MiniCPM3 style)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD block config."""

    state_dim: int = 128        # N
    head_dim: int = 64          # P
    expand: int = 2             # d_inner = expand * d_model
    chunk: int = 256            # SSD chunk length
    conv_width: int = 4         # depthwise conv kernel size
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclass(frozen=True)
class RGLRUConfig:
    """RG-LRU recurrence config (Griffin / RecurrentGemma)."""

    lru_width: int = 0          # 0 -> d_model
    conv_width: int = 4
    c_constant: float = 8.0     # the fixed `c` in a = exp(-c * softplus(Lambda) * sigma(r))


@dataclass(frozen=True)
class FrontendStub:
    """Stub modality frontend (spec carve-out: ViT / EnCodec are NOT built).

    ``input_specs`` provides precomputed frame/patch embeddings of shape
    (batch, prefix_len, feature_dim); the (real, implemented) projector maps
    feature_dim -> d_model.
    """

    kind: str                   # "vision" | "audio"
    prefix_len: int             # number of patch/frame positions
    feature_dim: int            # raw frontend feature width


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

# Mixer kinds. The FFN kind is orthogonal: dense unless ``moe`` is set and
# the layer is not in ``dense_ffn_layers``; ``ssd`` blocks carry no FFN.
BLOCK_KINDS = ("attn", "local_attn", "ssd", "rglru")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    # block pattern, cycled over layers, e.g. ("rglru","rglru","local_attn")
    layer_pattern: Tuple[str, ...] = ("attn",)
    # layer indices whose FFN is dense even in an MoE model (deepseek layer 0)
    dense_ffn_layers: Tuple[int, ...] = ()
    qkv_bias: bool = False
    parallel_residual: bool = False   # GPT-J/Falcon style (paper §2.2)
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    window: int = 0             # sliding-window size for local_attn / SWA (0 = full)
    act: str = "silu"           # silu (gated) | gelu
    gated_mlp: bool = True      # SwiGLU vs plain 2-matmul MLP
    n_codebooks: int = 1        # musicgen: parallel codebook streams
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    frontend: Optional[FrontendStub] = None
    citation: str = ""
    # unroll all layer groups (no lax.scan) — used by the dry-run cost probes,
    # where XLA's cost_analysis counts while-loop bodies only once
    force_unroll: bool = False

    # -- derived ----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def q_group(self) -> int:
        return self.n_heads // self.n_kv_heads if self.n_kv_heads else 1

    def block_kind(self, layer: int) -> str:
        return self.layer_pattern[layer % len(self.layer_pattern)]

    @property
    def is_attention_free(self) -> bool:
        return all(k in ("ssd", "rglru") for k in self.layer_pattern)

    @property
    def supports_long_context(self) -> bool:
        """True if decode state is O(window) or O(1) per token natively."""
        return all(k in ("ssd", "rglru", "local_attn") for k in self.layer_pattern) or (
            self.window > 0
        )

    def param_count(self) -> int:
        """Approximate parameter count (used for MODEL_FLOPS = 6ND)."""
        d, hd = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.n_heads, self.n_kv_heads
        total = self.vocab_size * d * self.n_codebooks  # embed
        if not self.tie_embeddings:
            total += self.vocab_size * d * self.n_codebooks  # lm head(s)
        ffn_mats = 3 if self.gated_mlp else 2
        for layer in range(self.n_layers):
            kind = self.block_kind(layer)
            if kind in ("attn", "local_attn"):
                if self.mla is not None:
                    m = self.mla
                    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
                    total += d * m.q_lora_rank + m.q_lora_rank * n_q * qd
                    total += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                    total += m.kv_lora_rank * n_q * (m.qk_nope_head_dim + m.v_head_dim)
                    total += n_q * m.v_head_dim * d
                else:
                    total += d * (n_q + 2 * n_kv) * hd + n_q * hd * d
            elif kind == "ssd":
                s = self.ssm
                di = s.expand * d
                n_sh = di // s.head_dim
                total += d * (2 * di + 2 * s.state_dim + n_sh) + di * d
            elif kind == "rglru":
                w = self.rglru.lru_width or d
                total += d * 2 * w + 3 * w + w * d  # in-proj(x2), gates, out-proj
            # FFN (ssd blocks carry none)
            if kind != "ssd":
                if self.moe is not None and layer not in self.dense_ffn_layers:
                    m = self.moe
                    total += m.n_experts * ffn_mats * d * m.expert_d_ff
                    total += d * m.n_experts  # router
                    if m.n_shared:
                        total += ffn_mats * d * m.shared_d_ff
                else:
                    total += ffn_mats * d * self.d_ff
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k + shared experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full = self.param_count()
        per = (3 if self.gated_mlp else 2) * self.d_model * m.expert_d_ff
        n_moe_layers = sum(
            1
            for layer in range(self.n_layers)
            if layer not in self.dense_ffn_layers
        )
        inactive = n_moe_layers * (m.n_experts - m.top_k) * per
        return full - inactive

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: 2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        pat = self.layer_pattern
        n_layers = max(2, len(pat)) if len(pat) > 1 else 2
        kw = dict(
            name=self.name + "-smoke",
            n_layers=n_layers,
            d_model=d,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=64,
            d_ff=min(self.d_ff, 512) or 0,
            vocab_size=min(self.vocab_size, 512),
            window=min(self.window, 64) if self.window else 0,
            dense_ffn_layers=tuple(i for i in self.dense_ffn_layers if i < n_layers),
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                expert_d_ff=min(self.moe.expert_d_ff, 128),
                shared_d_ff=min(self.moe.shared_d_ff, 128),
            )
        if self.mla is not None:
            kw["mla"] = MLAConfig(
                q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32,
                qk_rope_head_dim=16, v_head_dim=32,
            )
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(self.ssm, state_dim=32, head_dim=32, chunk=32)
        if self.rglru is not None:
            kw["rglru"] = dataclasses.replace(self.rglru, lru_width=0)
        if self.frontend is not None:
            kw["frontend"] = dataclasses.replace(
                self.frontend, prefix_len=8, feature_dim=64
            )
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assignment table)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Parallelism / runtime configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    """How the model is laid out on the mesh."""

    tp: int = 1                 # size of the "model" axis
    dp: int = 1                 # size of the "data" axis
    pods: int = 1               # size of the "pod" axis
    seq_parallel: bool = True   # Megatron-SP residual stream (train/prefill)
    kv_seq_shard: bool = False  # shard decode KV cache sequence over data axis
    expert_parallel: bool = True  # MoE experts over model axis (vs d_ff TP)
    remat: bool = True          # activation checkpointing per layer (train)
    # paper-technique toggles (for ablation benches; all on by default)
    topk_sync: bool = True      # §2.1b local top-k before reduction
    id_broadcast: bool = True   # §2.1a broadcast token ids not embeddings
    one_shot_sync: bool = True  # §2.2 single psum for parallel-residual
    zero_copy: bool = True      # §2.3 donation + fused epilogue
    # Pallas kernels on the hot paths; None = on exactly when JAX's default
    # backend is a TPU (resolved by ModelCtx.make).  Kernels run compiled
    # on a TPU and in the Pallas interpreter on the CPU.
    use_pallas: Optional[bool] = None
    flash_prefill: bool = True  # fused Pallas flash-prefill kernel on the
                                # prefill hot path (effective with
                                # use_pallas; the pure-JAX scan remains the
                                # reference + MLA/windowed fallback)
    kv_quant: bool = False      # int8 KV cache (per-head-per-slot scales)
    # weight-only quantization (quantize-at-load transform over the param
    # tree): "int8" = per-output-channel scales, "int4" = group-wise scales
    # along the reduction dim (wq_group_size, clamped per tensor so groups
    # never straddle a TP shard).  Covers every serving projection
    # (attention q/k/v/o, MLP up/gate/down, MoE experts, lm_head); embed
    # tables, norms, biases, routers, and MLA latent projections stay bf16.
    # Routing follows use_pallas: fused dequant matmul kernels on the hot
    # 2-D projections when Pallas is on, pure-JAX dequant reference
    # otherwise (always the fallback for batched einsum sites).
    weight_quant: str = "none"  # none | int8 | int4
    wq_group_size: int = 128    # int4 group length along the reduction dim
    # chunked prefill (continuous-batching schedulers): prompts longer than
    # this many tokens are admitted chunk-by-chunk through the fused mixed
    # prefill/decode step, so a long prompt never stalls in-flight decode
    # for more than one chunk's worth of compute.  0 disables chunking
    # (whole-prompt admission only).  Eligibility is declared per arch by
    # the capability registry (core.capabilities): ineligible archs clamp
    # this config default to whole-prompt admission; an explicit scheduler
    # constructor override raises the registry error instead.
    prefill_chunk: int = 256
    # speculative decoding (continuous-batching schedulers): propose spec_k
    # draft tokens per active slot from a host-side n-gram prompt-lookup
    # drafter and score all spec_k+1 positions in ONE fused verify step (a
    # width-(k+1) chunk at the decode frontier), emitting 1..spec_k+1
    # tokens per step.  0 disables (plain one-token decode).  Greedy spec
    # decode is token-identical to plain greedy decode; eligibility comes
    # from the capability registry's "spec" path (same derivation as
    # chunked prefill — ineligible archs clamp this default to plain
    # decode, explicit constructor overrides raise).
    spec_k: int = 0
    spec_ngram: int = 3         # longest n-gram the prompt-lookup drafter
                                # matches (falls through to shorter n-grams)
    # paged KV cache (slot engine second storage backend; dense remains the
    # default and the only layout for wave mode).  PagedContinuousScheduler
    # reads these as its defaults; constructor args override.
    kv_block_size: int = 16     # tokens per KV block (paged backend)
    kv_pool_blocks: int = 0     # total pool blocks; 0 = n_slots * blocks/slot
                                # (i.e. the dense footprint — shrink to
                                # overcommit capacity vs n_slots x max_seq)
    # disaggregated prefill/decode serving (DisaggScheduler): the first
    # disagg_prefill_shards data shards form the PREFILL POOL (prompts admit
    # and chunk-prefill there), the remaining shards the DECODE POOL;
    # finished KV blocks migrate between the per-shard block namespaces via
    # a batched device-to-device copy, with refcounts handed off through
    # the allocator.  0 disables (unified serving).  Requires an arch whose
    # capability record supports "disagg" (chunked + paged with no
    # blockers) and dp * pods >= 2.
    disagg_prefill_shards: int = 0
    # overlapped host/device engine loop (continuous-batching schedulers):
    # dispatch decode step N+1 while step N's token array is still a device
    # future, running host work (drafting, admission, block allocation,
    # migration queueing) against the previous step's landed tokens and
    # materializing np.asarray one step late.  Host state advances on a
    # PREDICTION (budget decrements are deterministic; EOS is the only
    # surprise) with a one-step rollback when a landed token turns out to be
    # EOS.  Greedy token streams are bit-identical to the blocking loop —
    # overlap reorders host observation, not device math.
    overlap_decode: bool = False
    # fault tolerance (continuous-batching schedulers).  fault_plan is a
    # compact spec string (see runtime/faults.py for the grammar) injecting
    # deterministic failures — step exceptions, poisoned slot tokens,
    # allocator exhaustion, migration faults, delayed steps — at chosen
    # step indices; "" disables injection.  Kept as a str so this config
    # stays frozen/hashable.  A transient step failure is retried up to
    # max_step_retries times with exponential backoff starting at
    # retry_backoff_s (the pipeline drains to the exact pre-step state
    # before each retry); when retries exhaust, a failure attributed to one
    # slot quarantines that request (finish_reason "error") and everything
    # else keeps serving.
    fault_plan: str = ""
    max_step_retries: int = 3
    retry_backoff_s: float = 0.05
    # overload resilience (continuous-batching schedulers).  Requests carry
    # a priority class ("interactive" | "standard" | "batch"); the slo_*_s
    # fields are per-class PER-TOKEN latency targets in seconds (0 = no
    # target).  interactive_reserve_slots / _blocks hold back a quota of
    # slots (dense + paged) and free KV blocks (paged) that only
    # interactive-class admissions may consume, so a background flood can
    # never starve the latency class.  overload_degrade enables the
    # graceful-degradation controller (runtime/overload.py): it watches
    # arrived-queue depth and recently landed ITL every round and, under
    # sustained pressure, walks a ladder — shed batch at admission, disable
    # spec decode, cap the admission window — restoring in reverse as
    # pressure clears.  queue_hi/lo are the hysteresis thresholds in queued
    # requests (0 = auto from n_slots); patience/cooldown are the number of
    # consecutive pressured/clear rounds before escalating/restoring;
    # itl_hi/lo scale the interactive SLO into the ITL pressure band.
    # Every lever changes WHICH requests run and WHEN — never their tokens.
    slo_interactive_s: float = 0.0
    slo_standard_s: float = 0.0
    slo_batch_s: float = 0.0
    interactive_reserve_slots: int = 0
    interactive_reserve_blocks: int = 0
    overload_degrade: bool = False
    overload_queue_hi: int = 0
    overload_queue_lo: int = 0
    overload_patience: int = 3
    overload_cooldown: int = 6
    overload_itl_hi: float = 1.5
    overload_itl_lo: float = 1.0


@dataclass(frozen=True)
class SamplingConfig:
    top_k: int = 40
    temperature: float = 1.0
    greedy: bool = False
