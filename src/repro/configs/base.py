"""Config system for the VRGD framework.

A :class:`Config` fully describes (model, optimizer, parallelism) and is what
every entry point (trainer, server, dry-run, benchmarks) consumes.  Configs are
frozen dataclasses so they hash and are safe as jit static args.

Block kinds understood by ``models/transformer.py``:

  "attn"    full (causal) self-attention + MLP; YaRN RoPE where
            ``ModelConfig.rope_yarn`` is set
  "swa"     sliding-window self-attention + MLP (plain RoPE)
  "local"   sliding-window self-attention + MLP (recurrentgemma naming)
  "xattn"   self-attention + cross-attention (to image/audio memory) + MLP
  "rec"     RG-LRU recurrent block + MLP                     [arXiv:2402.19427]
  "mlstm"   mLSTM block (matrix memory, chunkwise parallel)  [arXiv:2405.04517]
  "slstm"   sLSTM block (scalar memory, sequential scan)     [arXiv:2405.04517]

A layer stack is ``block_pattern`` repeated; remainders are appended by
truncating the pattern (``pattern_layers()``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from repro.backend import Backend

# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8  # the router's width: experts routed over
    top_k: int = 2
    # None: the dropless layer (every routed row computed, grouped matmuls
    # over rows sorted by expert); a number: capacity-bounded dispatch
    capacity_factor: Optional[float] = 1.25
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3
    n_shared_experts: int = 0  # llama4-style always-on shared expert
    # the share of the experts this layer holds (dropless only): experts
    # first_held .. first_held + n_held - 1; 0 holds all n_experts
    n_held: int = 0
    first_held: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts

    @property
    def dropless(self) -> bool:
        return self.capacity_factor is None


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN RoPE scaling (arXiv:2309.00071), as HF ``rope_type: yarn``."""

    factor: float
    original_max_positions: int
    beta_fast: float
    beta_slow: float
    attention_factor: float  # cos and sin are multiplied by it


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for enc-dec models (whisper). Frontend is a stub: the
    pipeline provides precomputed frame embeddings of shape (B, n_frames, d)."""

    n_layers: int = 12
    n_frames: int = 1500  # whisper-small: 30s audio -> 1500 frames after conv


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | vlm | hybrid | ssm | audio | dlrm
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    n_kv_heads: int = 12
    d_ff: int = 3072
    vocab_size: int = 32000
    head_dim: int = 0  # 0 -> d_model // n_heads
    block_pattern: Tuple[str, ...] = ("attn",)
    sliding_window: int = 0  # 0 -> full attention for "attn"; "swa"/"local" need >0
    rope_theta: float = 10000.0
    rope_yarn: Optional[YarnConfig] = None  # "attn" layers only; None: plain RoPE
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    act: str = "swiglu"  # swiglu | gelu
    moe: Optional[MoEConfig] = None
    encoder: Optional[EncoderConfig] = None
    n_image_tokens: int = 0  # vlm: stubbed vision-encoder output length
    causal: bool = True
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    # xLSTM specifics
    qk_dim_factor: float = 0.5
    v_dim_factor: float = 1.0
    # max positions for caches / abs-pos models
    max_seq_len: int = 1 << 20
    citation: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def pattern_layers(self) -> Tuple[str, ...]:
        """The full per-layer kind list, pattern repeated/truncated to n_layers."""
        p = self.block_pattern
        reps = math.ceil(self.n_layers / len(p))
        return tuple((p * reps)[: self.n_layers])

    def n_groups(self) -> int:
        """Number of full pattern groups (scanned); remainder is unrolled."""
        return self.n_layers // len(self.block_pattern)

    def tail_kinds(self) -> Tuple[str, ...]:
        return tuple(self.block_pattern[: self.n_layers % len(self.block_pattern)])

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head), for rooflines."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        q = self.n_heads * hd
        kv = self.n_kv_heads * hd
        attn = d * q + 2 * d * kv + q * d  # wq wk wv wo
        mlp = 3 * d * f if self.act == "swiglu" else 2 * d * f
        total = 0
        for kind in self.pattern_layers():
            if kind in ("attn", "swa", "local"):
                body = attn + self._mlp_or_moe(mlp)
            elif kind == "xattn":
                body = 2 * attn + self._mlp_or_moe(mlp)
            elif kind == "rec":
                # RG-LRU block: in/out proj + gates (see models/recurrent.py)
                rnn_width = d
                body = 2 * d * rnn_width + 2 * rnn_width * rnn_width // 8 + 3 * rnn_width
                body += self._mlp_or_moe(mlp)
            elif kind == "mlstm":
                qk = int(d * self.qk_dim_factor)
                vd = int(d * self.v_dim_factor)
                body = d * (2 * qk + 3 * vd) + vd * d + 2 * d * 2 * d  # proj + gates approx
            elif kind == "slstm":
                body = 4 * d * d + 2 * d * 4 * d
            else:
                raise ValueError(kind)
            total += body + 2 * d  # norms
        total += v * d  # embed
        if not self.tie_embeddings:
            total += v * d
        if self.encoder is not None:
            total += self.encoder.n_layers * (attn + mlp + 2 * d)
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k + shared experts count;
        of a held share, its nominal part: top_k * held / n_experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        full = self.param_count()
        mlp = 3 * self.d_model * self.d_ff if self.act == "swiglu" else 2 * self.d_model * self.d_ff
        n_moe_layers = sum(1 for k in self.pattern_layers() if k in ("attn", "swa", "local", "xattn"))
        inactive = n_moe_layers * mlp * (m.held * (m.n_experts - m.top_k) // m.n_experts)
        return full - inactive

    def _mlp_or_moe(self, mlp: int) -> int:
        if self.moe is None:
            return mlp
        m = self.moe
        return mlp * (m.held + m.n_shared_experts) + self.d_model * m.n_experts


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "vr_lamb"  # {sgd,momentum,adam,lars,lamb} or vr_ prefixed
    lr: float = 1e-3
    warmup_steps: int = 0  # 0 = no warm-up (explicit opt-in)
    total_steps: int = 1000
    schedule: str = "cosine"  # cosine | poly | linear | constant
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.999
    b3: float = 0.9  # GSNR momentum decay (paper beta_3)
    eps: float = 1e-6
    momentum: float = 0.9
    grad_clip: float = 1.0
    # --- VRGD hyper-parameters (paper defaults) ---
    gamma: float = 0.1  # GSNR clip floor, paper sec. 4.1 (never tuned in paper)
    k: int = 8  # statistic groups; paper: min devices holding LB, >= 8
    gsnr_source: str = "microbatch"  # microbatch | data_axis
    gsnr_eps: float = 1e-12
    stats_method: str = "scan"  # scan (paper) | vmap (shared FSDP gathers)
    gsnr_refresh: int = 1  # recompute GradStats every R steps (1 = paper)
    state_dtype: str = "float32"  # storage dtype for m/v/p moments (math in f32)
    # --- batch-size LR scaling (paper §6; live rescale via train/autoscale) ---
    base_batch: int = 0  # reference batch cfg.lr was tuned at; 0 = no rescale
    lr_scale_rule: str = "sqrt"  # sqrt (paper's choice) | linear | none
    noise_beta: float = 0.9  # EMA decay for tr(Σ)/|G|² noise-scale smoothing

    @property
    def is_vr(self) -> bool:
        return self.name.startswith("vr_")


# ---------------------------------------------------------------------------
# Parallelism / runtime
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParallelismConfig:
    dp_axis: str = "data"
    tp_axis: str = "model"
    pod_axis: str = "pod"
    fsdp: bool = True  # shard params/opt-state over the data axis too
    remat: bool = True
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Execution plan: per-subsystem fused/reference/auto selection plus the
    # interpret-mode override (repro.backend.Backend).  Consumers resolve it
    # ONCE via repro.backend.resolve_backend(cfg.parallel) and pass it down.
    backend: Backend = Backend()
    # DEPRECATED (one release): the legacy all-or-nothing boolean.  None =
    # unset; a set value takes precedence over `backend` and maps through
    # Backend.from_flag in resolve_backend (which warns once per process).
    use_pallas: Optional[bool] = None
    attn_chunk: int = 1024  # q-chunk for online-softmax attention (0 = naive)
    scan_layers: bool = True


@dataclasses.dataclass(frozen=True)
class Config:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    parallel: ParallelismConfig = dataclasses.field(default_factory=ParallelismConfig)
    seed: int = 0
    global_batch: int = 32
    seq_len: int = 512
    # Cross-entropy normalization for packed batches: "token" = mean over
    # live tokens (default); "document" = every packed document contributes
    # its own token-mean NLL with equal weight (BERT-pretraining style) —
    # long documents can't drown short ones.  Ignored for unpacked batches.
    loss_norm: str = "token"

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def smoke_variant(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Reduced same-family variant: <=2 pattern groups, d_model<=512, <=4 experts."""
    pattern = cfg.block_pattern
    if len(pattern) > 4:
        # keep one of each distinct kind, order-preserving
        seen, small = set(), []
        for k in pattern:
            if k not in seen:
                seen.add(k)
                small.append(k)
        pattern = tuple(small)
    n_layers = len(pattern) if len(pattern) >= 2 else 2
    d_model = min(cfg.d_model, 256)
    n_heads = min(cfg.n_heads, 4)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    while n_heads % n_kv:
        n_kv -= 1
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, n_experts=min(4, cfg.moe.n_experts))
    enc = None
    if cfg.encoder is not None:
        enc = dataclasses.replace(cfg.encoder, n_layers=2, n_frames=16)
    kw = dict(
        n_layers=n_layers,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        d_ff=0 if cfg.d_ff == 0 else min(cfg.d_ff, 512),
        vocab_size=min(cfg.vocab_size, 512),
        head_dim=0,
        block_pattern=pattern,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        moe=moe,
        encoder=enc,
        n_image_tokens=min(cfg.n_image_tokens, 16) if cfg.n_image_tokens else 0,
        name=cfg.name + "-smoke",
    )
    kw.update(overrides)
    return dataclasses.replace(cfg, **kw)
