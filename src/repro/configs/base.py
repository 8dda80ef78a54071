"""Config system: every architecture is a ModelConfig; shapes are ShapeConfig.

Configs are plain dataclasses (no framework deps) so the launcher, tests and
benchmarks can construct them without touching jax.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-Experts layer configuration (the paper's target module)."""

    num_experts: int
    top_k: int
    d_expert: int                     # hidden width of each expert FFN
    num_shared_experts: int = 0       # deepseek-style always-on experts
    routing: str = "token_choice"     # "token_choice" | "expert_choice"
    # token-choice scores: "softmax" keeps the top-k of softmax(x W_G);
    # "sigmoid" (DeepSeek-V3 noaux_tc) picks the top-k of sigmoid(x W_G)
    # plus a per-expert bias, weights them by the unbiased scores,
    # renormalised; both then scale the routed sum by `routed_scale`
    scoring: str = "softmax"          # "softmax" | "sigmoid"
    routed_scale: float = 1.0
    # --- paper technique knobs (C1-C3) ---
    group_size: int = 1               # crossbar-multiplexing analogue: experts per shared lane
    grouping: str = "sorted"          # "uniform" | "sorted" (load-aware, C2)
    capacity_factor: float = 1.25     # token-choice expert capacity
    balance_coef: float = 0.01        # aux balance-loss coefficient (training)
    use_grouped_gemm: bool = True     # group-multiplexed execution path (C1)
    # --- execution backend ---
    # "pallas" streams every path through the tile-dispatch grouped GEMM
    # (kernels/moe_gmm.py): zero-redundancy C1 multiplexing, dropless.
    # "xla" is the masked einsum realization (validation + CPU production).
    # "auto" resolves per host: pallas on TPU (Mosaic), xla elsewhere —
    # except under training (loss_fn), which pins "auto" to xla until the
    # pallas kernels grow a VJP (see ROADMAP).
    backend: str = "auto"             # "auto" | "xla" | "pallas"
    gmm_block_rows: int = 0           # pallas row-tile height (0 = auto)
    # --- C4 ---
    go_cache: bool = True             # gate-output cache for expert-choice decode


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture. All assigned archs reduce to this one schema."""

    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // num_heads
    block: str = "attn"               # attn | xlstm | mamba2
    moe: Optional[MoEConfig] = None

    # attention details
    qkv_bias: bool = False            # qwen2
    # multi-head latent attention (DeepSeek-V2/V3): kv_lora_rank > 0 caches
    # one normalised latent of this width and one shared rotary key of
    # qk_rope_dim per token; head_dim is then the query/key width per head
    # (its last qk_rope_dim dims rotary), v_head_dim the value width
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    first_dense_layers: int = 0       # leading SwiGLU layers (width d_ff)
                                      # before the MoE stack
    rope_theta: float = 10000.0
    sliding_window: int = 0           # >0 enables local attention layers
    local_global_ratio: int = 0       # gemma3: N local layers per 1 global
    logit_softcap: float = 0.0

    # --- serving: paged-attention realization ---
    # "kernel" walks the block table in Pallas (kernels/paged_attn.py):
    # per-tick HBM traffic scales with live tokens. "gather" re-materializes
    # the dense [B, max_tokens] layout per layer per tick (bit-exact vs the
    # dense pool, the escape hatch). "auto" resolves per lowering platform:
    # kernel on TPU (Mosaic), gather elsewhere — CPU CI opts into the kernel
    # explicitly (REPRO_FORCE_PAGED_KERNEL / with_overrides).
    paged_attn: str = "auto"          # "auto" | "kernel" | "gather"

    # --- serving: quantized decode state (paged pools only) ---
    # "int8" stores KV pages as int8 with per-page, per-kv-head amax scales
    # (f32 [L, NP, Hkv]) and GO rows as int8 with per-row scales — bytes per
    # resident token drop ~4x vs the fp32 smoke dtype (~2x vs bf16) while
    # attention compute stays fp32 (dequantized in-kernel / at the gather).
    # The enum leaves room for fp8 once hardware dtypes land. "none" keeps
    # the full-precision pages. Quantized mode REQUIRES a paged pool — scale
    # granularity is page granularity (core/quant.py).
    kv_quant: str = "none"            # "none" | "int8"

    # ssm / hybrid details
    ssm_state: int = 0                # mamba2 state size (zamba2: 64)
    ssm_chunk: int = 128              # SSD chunk length
    attn_every: int = 0               # zamba2: shared attention block every N layers
    slstm_every: int = 0              # xlstm: one sLSTM block every N layers
    conv_width: int = 4               # mamba2 short conv

    # multimodal / enc-dec details
    cross_attn_every: int = 0         # llama-vision: cross-attn layer cadence
    num_image_tokens: int = 0         # stub patch-embedding count
    encoder_layers: int = 0           # whisper: >0 -> encoder-decoder
    num_audio_frames: int = 0         # stub frame-embedding count

    # numerics
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    tie_embeddings: bool = True

    # training-time knobs (used by launch/train.py and the dry-run)
    remat: bool = True
    scan_layers: bool = True
    seq_shard_activations: bool = True   # SP on the residual stream
    sp_attn: bool = False    # sequence-parallel attention fallback (forward-
                             # only paths; for head counts that don't divide
                             # the model axis — a §Perf hillclimb knob)

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    def latent_width(self) -> int:
        """Values cached per token and layer by latent attention: the
        latent and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_dim

    def latent_row_width(self) -> int:
        """A latent cache row as stored: `latent_width` values, then zeros
        up to the next 128 lanes. A page of rows is then a whole number of
        TPU lane tiles, as the page must be for the kernel to stage it in
        one copy: a row of 576 would leave the device free to store the
        pool with the page index innermost, and every kernel call would
        first copy the whole pool back into rows."""
        return -(-self.latent_width() // 128) * 128

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS in the roofline)."""
        d, hd = self.d_model, self.resolved_head_dim()
        nq, nkv = self.num_heads, self.num_kv_heads
        if self.mla:
            # W_q, W_kva (latent + rotary key), W_kvb (keys, values), W_o
            r, nope = self.kv_lora_rank, hd - self.qk_rope_dim
            attn = (d * nq * hd + d * self.latent_width()
                    + r * nq * (nope + self.v_head_dim)
                    + nq * self.v_head_dim * d)
        else:
            attn = d * hd * nq + 2 * d * hd * nkv + hd * nq * d
        if self.block == "attn":
            mix = attn
        elif self.block == "xlstm":
            # mLSTM block: up(2x) + q/k/v (on 2d inner) + gates + down
            di = 2 * d
            mix = 2 * d * di + 3 * di * di + di * d
        elif self.block == "mamba2":
            di = 2 * d
            mix = d * (2 * di + 2 * self.ssm_state) + di * d
        else:
            raise ValueError(self.block)
        nd = self.first_dense_layers
        dense_ffn = 3 * d * self.d_ff
        if self.moe is not None:
            e = self.moe
            ffn = (e.num_experts + e.num_shared_experts) * 3 * d * e.d_expert
            ffn += d * e.num_experts  # gate
        elif self.d_ff > 0:
            ffn = dense_ffn  # gated SwiGLU
        else:
            ffn = 0
        layers = (self.num_layers - nd) * (mix + ffn) + nd * (mix + dense_ffn)
        if self.attn_every:
            layers += attn  # zamba2 shared attention block params
        if self.cross_attn_every:
            n_x = self.num_layers // self.cross_attn_every
            layers += n_x * (attn + 3 * d * self.d_ff)
        if self.encoder_layers:
            layers += self.encoder_layers * (attn + 2 * d * self.d_ff)
            layers += self.num_layers * attn  # decoder cross-attn
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return layers + emb

    def active_param_count(self) -> int:
        """Params touched per token (MoE uses top-k + shared experts only)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        d = self.d_model
        moe_layers = self.num_layers - self.first_dense_layers
        idle = moe_layers * (e.num_experts - e.top_k) * 3 * d * e.d_expert
        return self.param_count() - idle


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (assigned per architecture)."""

    name: str                         # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                         # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class TrainConfig:
    """End-to-end training-run knobs for launch/train.py."""

    steps: int = 100
    seq_len: int = 512
    global_batch: int = 8
    lr: float = 3e-4
    warmup_steps: int = 20
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    seed: int = 0
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    log_every: int = 10
    microbatch: int = 0               # >0 enables gradient accumulation
