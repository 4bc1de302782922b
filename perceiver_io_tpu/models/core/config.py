"""Config dataclasses — the single source of truth for model hyperparameters.

Parity targets (reference: /root/reference/perceiver/model/core/config.py:5-100):
same field names and defaults so recipes and converted checkpoints line up, plus
TPU-specific extensions (``dtype`` compute precision, remat) that the torch
reference expressed through Lightning flags / fairscale wrappers instead.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass, field, fields
from typing import Generic, Optional, Tuple, TypeVar


@dataclass(frozen=True)
class EncoderConfig:
    num_cross_attention_heads: int = 8
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    num_cross_attention_layers: int = 1
    first_cross_attention_layer_shared: bool = False
    cross_attention_widening_factor: int = 1
    num_self_attention_heads: int = 8
    num_self_attention_qk_channels: Optional[int] = None
    num_self_attention_v_channels: Optional[int] = None
    num_self_attention_layers_per_block: int = 8
    num_self_attention_blocks: int = 1
    first_self_attention_block_shared: bool = True
    self_attention_widening_factor: int = 1
    dropout: float = 0.0
    init_scale: float = 0.02
    freeze: bool = False
    # lax.scan unroll factor for the SA-block layer loop — the same TPU
    # execution knob CausalSequenceModelConfig.scan_unroll exposes (NOTES.md:
    # full unroll is +2.9 MFU points on the 455M CLM; rolled wins at small op
    # sizes). Also required for exact XLA cost accounting: cost_analysis counts
    # a rolled scan body ONCE (scripts/xla_cost_proxy.py).
    scan_unroll: int = 1

    def base_kwargs(self, exclude=("freeze",)):
        return _base_kwargs(self, EncoderConfig, exclude)


@dataclass(frozen=True)
class DecoderConfig:
    num_cross_attention_heads: int = 8
    num_cross_attention_qk_channels: Optional[int] = None
    num_cross_attention_v_channels: Optional[int] = None
    cross_attention_widening_factor: int = 1
    cross_attention_residual: bool = True
    dropout: float = 0.0
    init_scale: float = 0.02
    freeze: bool = False

    def base_kwargs(self, exclude=("freeze",)):
        return _base_kwargs(self, DecoderConfig, exclude)


@dataclass(frozen=True)
class ClassificationDecoderConfig(DecoderConfig):
    num_output_queries: int = 1
    num_output_query_channels: int = 256
    num_classes: int = 100

    def base_kwargs(self, exclude=("freeze", "num_output_queries", "num_output_query_channels", "num_classes")):
        return super().base_kwargs(exclude=exclude)


E = TypeVar("E", bound=EncoderConfig)
D = TypeVar("D", bound=DecoderConfig)


@dataclass(frozen=True)
class PerceiverIOConfig(Generic[E, D]):
    encoder: E
    decoder: D
    num_latents: int
    num_latent_channels: int
    activation_checkpointing: bool = False
    remat_policy: Optional[str] = None  # jax.checkpoint_policies name (None = full remat)
    activation_offloading: bool = False  # stage checkpointed dots to pinned host (modules._remat_policy)


@dataclass(frozen=True)
class PerceiverARConfig:
    num_heads: int = 8
    max_heads_parallel: Optional[int] = None
    num_self_attention_layers: int = 8
    num_self_attention_rotary_layers: int = 1
    self_attention_widening_factor: int = 4
    cross_attention_widening_factor: int = 4
    cross_attention_dropout: float = 0.5
    # "gather" (default): the reference's exact fixed-size random-subset gather
    #   (modules.py:814-826) — also the fastest on TPU, since halving the prefix
    #   halves the cross-attention kv projections and scores (measured 176.6k
    #   vs 140.4k tok/s at p=0.5 on v5e).
    # "mask": Bernoulli drop via the attention mask — no sort/gather; useful when
    #   the kept count must stay shape-static across dropout rates.
    cross_attention_dropout_mode: str = "gather"
    post_attention_dropout: float = 0.0
    residual_dropout: float = 0.0
    activation_checkpointing: bool = False
    remat_policy: Optional[str] = None  # jax.checkpoint_policies name (None = full remat)
    activation_offloading: bool = False
    # lax.scan unroll factor for the self-attention layer loop. 1 (default) =
    # rolled scan, best for small configs; num_self_attention_layers = full
    # unroll, measured +2.9 MFU points on the 455M flagship where the scan's
    # carry writes cost real bandwidth (NOTES.md)
    scan_unroll: int = 1
    # single-GEMM q/k/v projections: kernels concatenated at APPLY time, so the
    # param tree and checkpoints are unchanged — a pure execution knob for
    # on-chip ablation (NOTES.md §1)
    fused_qkv: bool = False
    # mesh axis name for sequence-parallel ring attention over the prefix/latent
    # sequences (long-context training beyond one chip's memory); None = off
    sequence_parallel_axis: Optional[str] = None
    # mesh axis name for GPipe pipeline parallelism over the self-attention
    # stack (layer-sharded params + microbatched shard_map schedule,
    # parallel/pipeline.py); None = off. Pure execution knob like fused_qkv.
    pipeline_axis: Optional[str] = None
    pipeline_microbatches: Optional[int] = None  # default = stage count

    def base_kwargs(self, exclude=()):
        return _base_kwargs(self, PerceiverARConfig, exclude)


def _base_kwargs(config, base_class, exclude):
    base_field_names = [f.name for f in fields(base_class) if f.name not in exclude]
    return {k: v for k, v in asdict(config).items() if k in base_field_names}


@dataclass(frozen=True)
class CausalSequenceModelConfig(PerceiverARConfig):
    vocab_size: int = 262
    max_seq_len: int = 4096
    max_latents: int = 512
    num_channels: int = 512
    output_norm: bool = False
    output_bias: bool = True
    abs_pos_emb: bool = True
    init_scale: float = 0.02

    @classmethod
    def create(cls, **kwargs):
        return cls(**{f.name: kwargs[f.name] for f in fields(cls) if f.name in kwargs})


def flagship_455m_config() -> "CausalSequenceModelConfig":
    """The reference's published flagship training recipe (455M C4 Perceiver AR,
    reference examples/training/clm/train_fsdp.sh: 20 layers x 1280, heads 10,
    seq 1024, latents 512, xlnet 32k vocab) with this framework's measured-best
    single-chip execution knobs (NOTES.md: dots-saveable remat, full layer-loop
    unroll). Shared by bench.py and __graft_entry__ so the two cannot drift."""
    return CausalSequenceModelConfig(
        vocab_size=32000,
        max_seq_len=1024,
        max_latents=512,
        num_channels=1280,
        num_heads=10,
        num_self_attention_layers=20,
        cross_attention_dropout=0.0,
        abs_pos_emb=False,
        output_norm=True,
        output_bias=False,
        activation_checkpointing=True,
        remat_policy="dots_with_no_batch_dims_saveable",
        scan_unroll=20,
    )


@dataclass(frozen=True)
class FalconH1Config:
    """A Falcon-H1 decoder (``models/core/falcon_h1.py``) under the keys of its
    published ``config.json``: every block runs a Mamba-2 mixer and grouped-
    query attention in parallel on one normed input, then a gated MLP, with the
    muP multipliers of the release. ``max_seq_len`` is this program's own: the
    most tokens (prompt plus answer) a serving slot can hold, which sizes a
    slot's page-table row; the published ``max_position_embeddings`` only
    bounds it."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    max_position_embeddings: int = 262144
    max_seq_len: int = 2048
    init_scale: float = 0.02

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head ({self.mamba_n_heads} x {self.mamba_d_head}) "
                f"must equal mamba_d_ssm ({self.mamba_d_ssm})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be a multiple of "
                f"num_key_value_heads ({self.num_key_value_heads})")
        if self.mamba_n_heads % self.mamba_n_groups or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError(f"mamba_n_groups ({self.mamba_n_groups}) must divide the mixer's heads and width")
        if not 1 <= self.max_seq_len <= self.max_position_embeddings:
            raise ValueError(
                f"max_seq_len ({self.max_seq_len}) must lie in [1..max_position_embeddings="
                f"{self.max_position_embeddings}]")

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def create(cls, **kwargs):
        known = {f.name for f in fields(cls)}
        picked = {k: (tuple(v) if isinstance(v, list) else v) for k, v in kwargs.items() if k in known}
        return cls(**picked)


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """An LFM2 mixture-of-experts decoder (``models/core/lfm2_moe.py``) under
    the keys of its published ``config.json`` (``model_type`` ``lfm2_moe``):
    layer ``l`` mixes tokens by a gated short convolution (``layer_types[l]``
    ``"conv"``) or by grouped-query attention (``"full_attention"``); the first
    ``num_dense_layers`` layers have a dense gated MLP of
    ``intermediate_size``, the others ``num_experts`` routed experts of
    ``moe_intermediate_size``, ``num_experts_per_tok`` a token. Every expert
    of a layer lies here (``ops/moe.py``'s layer takes the range it holds as an
    argument; a model cut across chips waits for the exchange that completes
    its sum).

    This program's own: ``max_seq_len``, the most tokens a serving slot can
    hold."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = (
        "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "full_attention", "conv", "conv")
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    max_seq_len: int = 2048
    init_scale: float = 0.02

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, num_hidden_layers is "
                             f"{self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: a layer is 'conv' or 'full_attention'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be a multiple of "
                f"num_key_value_heads ({self.num_key_value_heads})")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if not 1 <= self.max_seq_len <= self.max_position_embeddings:
            raise ValueError(
                f"max_seq_len ({self.max_seq_len}) must lie in [1..max_position_embeddings="
                f"{self.max_position_embeddings}]")

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, kind in enumerate(self.layer_types) if kind == "full_attention")

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, kind in enumerate(self.layer_types) if kind == "conv")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.num_dense_layers, self.num_hidden_layers))

    @classmethod
    def create(cls, **kwargs):
        known = {f.name for f in fields(cls)}
        picked = {k: (tuple(v) if isinstance(v, list) else v) for k, v in kwargs.items() if k in known}
        return cls(**picked)


@dataclass(frozen=True)
class NemotronHConfig:
    """A Nemotron-H decoder (``models/core/nemotron_h.py``) under the keys of
    its published ``config.json`` (``model_type`` ``nemotron_h``): layer ``i``
    is ONE mixer under one norm and one residual, by
    ``hybrid_override_pattern[i]``: ``M`` a Mamba-2 mixer (``mamba_num_heads``
    heads of ``mamba_head_dim``, so its inner width is their product and NOT
    ``expand`` x hidden; ``n_groups`` groups of state ``ssm_state_size``), ``E``
    ``n_routed_experts`` routed experts of ``moe_intermediate_size`` in the
    ungated ``relu(x W_up)^2 W_down`` form, ``num_experts_per_tok`` a token,
    beside one shared expert of ``moe_shared_expert_intermediate_size``; ``*``
    grouped-query attention with NO position signal.

    This program's own: ``experts_held`` = (first, count), the experts of the
    router's ``n_routed_experts`` that lie here (the chip's share of an
    expert-parallel layer; the router keeps its width); ``max_seq_len``, the
    most tokens a serving slot can hold."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    experts_held: Tuple[int, int] = (0, 128)
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    max_seq_len: int = 2048
    init_scale: float = 0.02

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers:
            raise ValueError(f"hybrid_override_pattern names {len(self.hybrid_override_pattern)} layers, "
                             f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.hybrid_override_pattern) - set("ME*")
        if unknown:
            raise ValueError(f"hybrid_override_pattern holds {sorted(unknown)}: a layer is 'M', 'E' or '*'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be a multiple of "
                f"num_key_value_heads ({self.num_key_value_heads})")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"n_groups ({self.n_groups}) must divide mamba_num_heads ({self.mamba_num_heads})")
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} is no range of the router's "
                             f"{self.n_routed_experts} experts")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        if not 1 <= self.max_seq_len <= self.max_position_embeddings:
            raise ValueError(
                f"max_seq_len ({self.max_seq_len}) must lie in [1..max_position_embeddings="
                f"{self.max_position_embeddings}]")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers of one kind (``M``, ``E`` or ``*``), in order."""
        return tuple(i for i, k in enumerate(self.hybrid_override_pattern) if k == kind)

    @classmethod
    def create(cls, **kwargs):
        known = {f.name for f in fields(cls)}
        picked = {k: (tuple(v) if isinstance(v, list) else v) for k, v in kwargs.items() if k in known}
        return cls(**picked)
