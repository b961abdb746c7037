"""Core configuration types shared by every layer of the framework.

The paper's three-layer paradigm (Parallelization Strategy / CCL / Network)
is wired together through the types in this module: a ``ModelConfig``
describes the DNN at the top of the stack, a ``ShapeConfig`` describes the
workload, and ``MeshConfig`` describes how the parallelization strategy maps
onto hardware axes.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Literal, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------

LayerKind = Literal["attn", "mamba", "cross_attn"]
FFNKind = Literal["dense", "moe", "none"]


@dataclass(frozen=True)
class LayerSpec:
    """One decoder layer: its mixer (attention / mamba) and its FFN."""

    mixer: LayerKind = "attn"
    ffn: FFNKind = "dense"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description. One instance per ``src/repro/configs/<id>.py``.

    All 10 assigned architectures are expressible with this single config:
    dense GQA, MLA, sliding-window, MoE (shared + routed experts), Mamba2/SSD,
    hybrid interleaves, encoder-decoder and VLM cross-attention interleaves.
    """

    name: str
    family: Literal["dense", "ssm", "moe", "audio", "vlm", "hybrid"]
    source: str  # citation bracket from the assignment

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- attention flavour ---
    attention: Literal["gqa", "mla", "none"] = "gqa"
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    sliding_window: Optional[int] = None  # tokens; None = full attention

    # --- MLA (DeepSeek-V2) ---
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0  # 0 -> head_dim

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0  # per-expert hidden dim; 0 -> d_ff
    moe_layer_period: int = 1  # MoE FFN every k-th layer (Jamba: 2)
    moe_first_dense: int = 0  # first N layers use dense FFN (DeepSeek-V2: 1)
    router_aux_loss: float = 0.01

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_kernel: int = 4
    attn_period: int = 0  # hybrid: one attn layer every k layers (Jamba: 8)

    # --- encoder-decoder (audio) ---
    encoder_layers: int = 0

    # --- VLM cross-attention interleave ---
    cross_attn_period: int = 0  # one cross-attn layer every k layers
    num_vision_tokens: int = 0  # patch embeddings per image (stub frontend)
    num_audio_frames: int = 0  # frame embeddings (stub frontend)

    # --- misc ---
    ffn_act: Literal["swiglu", "gelu", "geglu"] = "swiglu"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    max_seq_len: int = 524_288

    # ------------------------------------------------------------------
    # Derived properties
    # ------------------------------------------------------------------

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def resolved_v_head_dim(self) -> int:
        return self.v_head_dim or self.resolved_head_dim

    @property
    def padded_vocab(self) -> int:
        """Vocab padded so the embedding/LM-head shard cleanly over TP=16."""
        return _round_up(self.vocab_size, 256)

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    # ------------------------------------------------------------------
    # Layer pattern
    # ------------------------------------------------------------------

    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """Per-layer (mixer, ffn) pattern for the decoder stack."""
        specs = []
        for i in range(self.num_layers):
            # mixer
            if self.attention == "none":
                mixer = "mamba"
            elif self.attn_period > 0:
                # hybrid: one attention layer per period, rest mamba
                mixer = "attn" if i % self.attn_period == 0 else "mamba"
            elif self.cross_attn_period > 0 and (i % self.cross_attn_period
                                                 == self.cross_attn_period - 1):
                mixer = "cross_attn"
            else:
                mixer = "attn"
            # ffn
            if self.ssm_state > 0 and self.attn_period == 0:
                ffn = "none" if self.d_ff == 0 else "dense"
            elif self.is_moe and i >= self.moe_first_dense and (
                    i % self.moe_layer_period == self.moe_layer_period - 1
                    or self.moe_layer_period == 1):
                ffn = "moe"
            else:
                ffn = "dense"
            specs.append(LayerSpec(mixer=mixer, ffn=ffn))
        return tuple(specs)

    def layer_groups(self) -> Tuple[Tuple[Tuple[LayerSpec, ...], int], ...]:
        """Group the layer pattern into (period, repeats) so the stack can be
        built as ``scan`` over stacked params — keeps HLO size O(period), not
        O(num_layers), which is what makes 100-layer configs compile fast.
        """
        specs = self.layer_specs()
        # find, over small prefixes, the smallest period that tiles the rest;
        # prefer the decomposition with the shortest period (most repeats).
        best = ((specs, 1),)
        best_period = len(specs)
        for prefix in range(0, 3):
            body = specs[prefix:]
            m = len(body)
            if not m:
                continue
            for period in range(1, m + 1):
                if m % period:
                    continue
                pat = body[:period]
                if all(body[j] == pat[j % period] for j in range(m)):
                    if period < best_period:
                        groups = []
                        if prefix:
                            groups.append((specs[:prefix], 1))
                        groups.append((pat, m // period))
                        best = tuple(groups)
                        best_period = period
                    break  # smallest period for this prefix found
        return best

    # ------------------------------------------------------------------
    # Parameter counting (used by roofline MODEL_FLOPS = 6*N*D)
    # ------------------------------------------------------------------

    def param_counts(self) -> dict:
        """Returns dict with total and active (per-token) parameter counts."""
        d = self.d_model
        hd = self.resolved_head_dim
        vhd = self.resolved_v_head_dim
        total = 0
        active = 0
        # embeddings (+ untied head)
        emb = self.padded_vocab * d
        total += emb * (1 if self.tie_embeddings else 2)
        active += emb * (1 if self.tie_embeddings else 2)

        def attn_params() -> int:
            if self.attention == "mla":
                p = d * self.q_lora_rank if self.q_lora_rank else 0
                qin = self.q_lora_rank or d
                p += qin * self.num_heads * (hd + self.qk_rope_head_dim)
                p += d * (self.kv_lora_rank + self.qk_rope_head_dim)
                p += self.kv_lora_rank * self.num_heads * (hd + vhd)
                p += self.num_heads * vhd * d
                return p
            q = d * self.num_heads * hd
            kv = 2 * d * self.num_kv_heads * hd
            o = self.num_heads * hd * d
            return q + kv + o

        def mamba_params() -> int:
            din = self.ssm_d_inner
            nh = self.ssm_num_heads
            # in_proj: z, x, B, C, dt ; out_proj
            p = d * (2 * din + 2 * self.ssm_state + nh)
            p += self.ssm_conv_kernel * (din + 2 * self.ssm_state)
            p += nh * 2  # A_log, D
            p += din * d
            return p

        def ffn_params(dff: int) -> int:
            if self.ffn_act in ("swiglu", "geglu"):
                return 3 * d * dff
            return 2 * d * dff

        moe_dff = self.moe_d_ff or self.d_ff
        for spec in self.layer_specs():
            if spec.mixer in ("attn", "cross_attn"):
                a = attn_params()
                total += a
                active += a
            else:
                m = mamba_params()
                total += m
                active += m
            if spec.ffn == "dense":
                f = ffn_params(self.d_ff)
                total += f
                active += f
            elif spec.ffn == "moe":
                routed = self.num_experts * ffn_params(moe_dff)
                shared = self.num_shared_experts * ffn_params(moe_dff)
                total += routed + shared + d * self.num_experts
                active += (self.top_k * ffn_params(moe_dff) + shared
                           + d * self.num_experts)
        if self.encoder_layers:
            # encoder: self-attn + dense ffn per layer, plus decoder gains
            # cross-attn (already counted via cross_attn_period==0 here we add)
            enc = self.encoder_layers * (attn_params() + ffn_params(self.d_ff))
            total += enc
            active += enc
            # decoder cross-attention blocks (one per decoder layer)
            ca = self.num_layers * attn_params()
            total += ca
            active += ca
        return {"total": total, "active": active}


# ---------------------------------------------------------------------------
# Workload shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]
    # decode shapes attend against a cache of ``seq_len`` and produce 1 token.


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

INPUT_SHAPES: Tuple[ShapeConfig, ...] = (
    TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)

SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}


# ---------------------------------------------------------------------------
# Mesh / parallelization strategy config (the paper's "Para." layer knob)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshConfig:
    """How logical parallelism axes map onto the device mesh."""

    shape: Tuple[int, ...] = (16, 16)
    axis_names: Tuple[str, ...] = ("data", "model")
    # which mesh axes carry each parallel dimension
    data_axes: Tuple[str, ...] = ("data",)
    model_axes: Tuple[str, ...] = ("model",)
    pipeline_axis: Optional[str] = None

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    def axis_size(self, name: str) -> int:
        return self.shape[self.axis_names.index(name)]

    @property
    def tp(self) -> int:
        return math.prod(self.axis_size(a) for a in self.model_axes)

    @property
    def dp(self) -> int:
        return math.prod(self.axis_size(a) for a in self.data_axes)


SINGLE_POD_MESH = MeshConfig()
MULTI_POD_MESH = MeshConfig(
    shape=(2, 16, 16), axis_names=("pod", "data", "model"),
    data_axes=("pod", "data"), model_axes=("model",))


# ---------------------------------------------------------------------------
# Training hyper-parameters
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    zero1: bool = True  # shard optimizer state over the data axis
    remat: bool = True  # activation checkpointing per layer
    grad_sync: Literal["all_reduce", "reduce_scatter"] = "reduce_scatter"
    microbatches: int = 1  # grad-accumulation steps (activation memory / K)
    grad_dtype: Literal["f32", "bf16"] = "f32"  # sync precision (§Perf)
    seed: int = 0
