"""Config system: model configs, input shapes, federation configs.

Every assigned architecture gets a ``ModelConfig`` in its own module under
``repro/configs/`` citing the source paper/model card. The model builder
(`repro.models.model.build_model`) consumes only this dataclass.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# Block types understood by the model builder. A layer is one block.
#   attn        : self-attention (GQA/MQA/MLA per config) + dense MLP
#   moe         : self-attention + MoE MLP (top-k routed + shared experts)
#   mamba2      : Mamba2 SSD mixer block (norm + mixer; no separate MLP)
#   mlstm       : xLSTM matrix-LSTM block
#   slstm       : xLSTM scalar-LSTM block
#   shared_attn : attention+MLP block whose params are SHARED across all
#                 occurrences (Zamba2-style global shared block)
BLOCK_TYPES = ("attn", "moe", "mamba2", "mlstm", "slstm", "shared_attn")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    block_pattern: Tuple[str, ...] = ("attn",)   # cycled to num_layers
    head_dim: int = 0                # 0 -> d_model // num_heads
    # --- attention options ---
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    sliding_window: Optional[int] = None   # used by long-context decode path
    tie_embeddings: bool = False
    # --- MLA (DeepSeek-V3) ---
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False    # rotary pairs (2i, 2i+1), not (i, i+d/2)
    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim (d_ff used if 0)
    router_aux_coef: float = 0.01    # softmax router's load-balance loss
    # router scores: "softmax" (top-k of the softmax, Switch aux loss) or
    # "sigmoid" (DeepSeek-V3 noaux_tc: top-k of sigmoid + a per-expert
    # correction bias that only selects; no aux loss)
    router_scoring: str = "softmax"
    router_groups: int = 1           # n_group of group-limited routing
    router_topk_groups: int = 1      # topk_group: groups a token may use
    norm_topk_prob: bool = True      # renormalise the selected scores
    routed_scaling_factor: float = 1.0
    # expert share (expert parallelism): the layer routes over all
    # num_experts and holds/computes experts [first_expert,
    # first_expert + experts_held); 0 holds them all
    experts_held: int = 0
    first_expert: int = 0
    # --- SSM (Mamba2 / xLSTM) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1
    # --- encoder-decoder (Whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0             # frames produced by the (stub) frontend
    cross_attention: bool = False
    # --- VLM ---
    num_image_tokens: int = 0        # stub-frontend patch embeddings prepended
    # --- multi-token prediction (DeepSeek-V3) ---
    mtp_depth: int = 0
    # --- activation / norm flavour ---
    mlp_variant: str = "swiglu"      # swiglu | gelu
    norm_variant: str = "rmsnorm"    # rmsnorm | layernorm
    # Model.loss runs under models.common.remat_blocks (per-block remat,
    # MLA's query chunks recomputed), as a remat_blocks caller can ask
    # for any config
    remat: bool = False
    citation: str = ""

    def __post_init__(self):
        for b in self.block_pattern:
            if b not in BLOCK_TYPES:
                raise ValueError(f"unknown block type {b!r}")
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {self.router_scoring!r}")
        if self.num_experts and not (
                0 <= self.first_expert
                and self.first_expert + self.held_experts <= self.num_experts):
            raise ValueError(f"expert share [{self.first_expert}, "
                             f"{self.first_expert + self.held_experts}) is "
                             f"outside the {self.num_experts} experts")

    # ---- derived ----
    @property
    def layer_types(self) -> Tuple[str, ...]:
        """Per-layer block types, pattern cycled to num_layers."""
        p = self.block_pattern
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    @property
    def padded_vocab(self) -> int:
        """Embedding/head tables are padded to a multiple of 128 so the
        vocab dim tiles TPU lanes and shards over the model axis (16).
        Logits beyond vocab_size are masked to -inf (whisper's 51865 and
        internvl2's 151655 are otherwise unshardable)."""
        return -(-self.vocab_size // 128) * 128

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> int:
        """Experts this layer holds and computes (its share)."""
        return self.experts_held or self.num_experts

    @property
    def is_decoder_only(self) -> bool:
        return not self.cross_attention

    @property
    def supports_long_context(self) -> bool:
        """True if the arch has a sub-quadratic path for 500k decode."""
        has_recurrent = any(t in ("mamba2", "mlstm", "slstm")
                            for t in self.layer_types)
        return has_recurrent or self.sliding_window is not None

    def param_count(self) -> int:
        """Analytic parameter count (matches init exactly; used for roofline
        MODEL_FLOPS and memory napkin math)."""
        from repro.models.model import count_params_analytic
        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params_analytic
        return count_params_analytic(self, active_only=True)

    def reduced(self, num_layers: int = 2, d_model: int = 256,
                vocab: int = 512) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        nh = max(2, min(4, self.num_heads))
        kv = max(1, min(nh, self.num_kv_heads if self.num_kv_heads < self.num_heads else nh))
        if self.num_kv_heads == self.num_heads:
            kv = nh
        upd = dict(
            name=self.name + "-smoke",
            num_layers=num_layers,
            d_model=d_model,
            num_heads=nh,
            num_kv_heads=kv,
            head_dim=d_model // nh,
            d_ff=2 * d_model,
            vocab_size=vocab,
        )
        if self.num_experts:
            upd.update(num_experts=4, num_experts_per_tok=2,
                       moe_d_ff=d_model, num_shared_experts=min(1, self.num_shared_experts),
                       experts_held=0, first_expert=0, router_groups=1,
                       router_topk_groups=1)
        if self.use_mla:
            upd.update(q_lora_rank=64 if self.q_lora_rank else 0,
                       kv_lora_rank=32, qk_rope_head_dim=16,
                       qk_nope_head_dim=16, v_head_dim=d_model // nh)
        if self.ssm_state:
            upd.update(ssm_state=16, ssm_head_dim=32)
        if self.encoder_layers:
            upd.update(encoder_layers=2, encoder_seq=64)
        if self.num_image_tokens:
            upd.update(num_image_tokens=16)
        if self.mtp_depth:
            upd.update(mtp_depth=1)
        if self.sliding_window:
            upd.update(sliding_window=64)
        return dataclasses.replace(self, **upd)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


INPUT_SHAPES = {
    "train_4k":    ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k":  ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k":   ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclass(frozen=True)
class FLConfig:
    """Federated-learning round configuration (paper §4 defaults)."""
    num_clients: int = 100           # m
    participation: float = 0.1       # p  -> |S_t| = p*m
    local_steps: int = 2             # K (for the jitted round; paper uses E=1
                                     # epoch ≈ 7 steps at b=64,n=500)
    client_opt: str = "delta_sgd"
    server_opt: str = "fedavg"
    loss: str = "ce"
    fedprox_mu: float = 0.0
    moon_mu: float = 0.0
    moon_tau: float = 0.5
    # Δ-SGD defaults (paper footnotes 2-3: γ=2, η0=0.2, θ0=1, δ=0.1)
    gamma: float = 2.0
    eta0: float = 0.2
    theta0: float = 1.0
    delta: float = 0.1
    # generic client-opt hparams
    lr: float = 0.01
    momentum: float = 0.9
    weighted_agg: bool = False
    # flat-parameter Δ-SGD engine: pack the param pytree + client axis
    # into one client slab for the whole local scan (core/fed_round)
    flat_engine: bool = False
    # federation scenario preset name (repro.federation.scenarios): adds
    # participation scheduling, compute heterogeneity, and/or async
    # buffered aggregation to the round. None = the plain sync round.
    scenario: Optional[str] = None
    # client->server delta compression (repro.compression, flat engine):
    # kind over the LEVELS ladder ("none"|"int8"|"topk"), the top-k keep
    # fraction per LANES-chunk, and EF21 error feedback (FLState.ef).
    # "none" without error feedback is inert — bit-exact seed behavior.
    compression: str = "none"
    compression_k_frac: float = 0.25
    error_feedback: bool = False
    # robust server aggregation + quorum degradation (repro.federation
    # .faults, flat engine): overrides applied onto the scenario —
    # "mean"/0 are inert and keep the exact legacy round tail.
    robust_agg: str = "mean"         # mean|clip|trimmed|median
    quorum: int = 0                  # skip round when < Q valid clients
    # fleet scale (repro.federation.arena): C_registered clients known
    # to the server, of which only |S_t| = p·m are sampled per round.
    # None keeps the legacy regime (registered == num_clients); setting
    # it routes training through make_fleet_loop — per-registered-client
    # state lives in the sharded ClientArena and cohort draws run over
    # all C_registered candidates. num_clients then bounds the DATA
    # partitions: registered client i trains on partition i % m
    # (virtual clients), so fleet scale never multiplies dataset memory.
    num_registered_clients: Optional[int] = None
    # device-native telemetry plane (repro.telemetry): in-scan η
    # histogram / loss deciles / guard counts. Read-only over round-end
    # values — the trained trajectory is bit-exact on or off.
    telemetry: bool = False

    @property
    def telemetry_spec(self):
        from repro.telemetry import resolve_telemetry
        return resolve_telemetry(self.telemetry)

    @property
    def compression_spec(self):
        from repro.compression import CompressionSpec
        return CompressionSpec(kind=self.compression,
                               k_frac=self.compression_k_frac,
                               error_feedback=self.error_feedback)

    @property
    def registered_clients(self) -> int:
        """C_registered: the fleet size the schedulers draw over.
        Defaults to ``num_clients`` (legacy regime, every registered
        client has its own data partition)."""
        m = self.num_registered_clients
        if m is not None and m < self.num_clients:
            raise ValueError(f"num_registered_clients={m} must be >= "
                             f"num_clients={self.num_clients}")
        return self.num_clients if m is None else m

    @property
    def fleet(self) -> bool:
        return self.num_registered_clients is not None

    @property
    def clients_per_round(self) -> int:
        # shared helper (repro.federation.schedulers.cohort_size): the
        # data pipeline computes |S_t| with the SAME rounding, so config
        # and sampled batches can never disagree on the cohort shape.
        # Fleet regime: participation applies to the REGISTERED fleet
        # (|S_t| = p·C_registered), same as the cross-device deployments
        # the schedulers model.
        from repro.federation.schedulers import cohort_size
        return cohort_size(self.participation, self.registered_clients)
