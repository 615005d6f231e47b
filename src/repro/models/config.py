"""Model configuration: a composable block-group description.

A model is an embedding, an ordered tuple of *block groups*, a final norm,
and an LM head. Each group is executed as a ``lax.scan`` over its stacked
per-layer parameters (keeping HLO size O(groups), not O(layers) — essential
for compiling 48-81-layer architectures in the multi-pod dry-run).

Heterogeneous layer patterns are expressed as structured groups:

* ``AttnGroup``     — n identical GQA decoder blocks; per-layer sliding
                      windows / rope thetas are *traced scan inputs*, so
                      gemma3's 5-local:1-global pattern is one scan.
* ``MoEGroup``      — GQA attention + top-1 routed experts (GShard-style
                      scatter dispatch, optional shared expert).
* ``XLSTMGroup``    — repeating [m x mLSTM, 1 x sLSTM] units (xLSTM).
* ``ZambaGroup``    — Zamba2's hybrid stack: a Mamba2 mixer in every
                      layer, and in each "hybrid" layer one of a few
                      weight-tied transformer blocks in front of it.
* ``CrossSelfGroup``— repeating [1 x cross-attention, m x self-attention]
                      units consuming stub image embeddings (Llama-3.2-V).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = [
    "AttnGroup",
    "MoEGroup",
    "XLSTMGroup",
    "ZambaGroup",
    "CrossSelfGroup",
    "ModelConfig",
]


@dataclasses.dataclass(frozen=True)
class AttnGroup:
    n_layers: int
    # Per-layer sliding window; None = full/global attention. A single value
    # broadcasts. gemma3: (w, w, w, w, w, None) * k.
    windows: Optional[Tuple[Optional[int], ...]] = None
    # Per-layer rope theta override (gemma3 uses 10k local / 1M global).
    thetas: Optional[Tuple[float, ...]] = None

    kind: str = dataclasses.field(default="attn", init=False)

    def layer_windows(self) -> Tuple[Optional[int], ...]:
        if self.windows is None:
            return (None,) * self.n_layers
        if len(self.windows) == self.n_layers:
            return self.windows
        # repeat pattern
        reps = -(-self.n_layers // len(self.windows))
        return (self.windows * reps)[: self.n_layers]

    def layer_thetas(self, default: float) -> Tuple[float, ...]:
        if self.thetas is None:
            return (default,) * self.n_layers
        if len(self.thetas) == self.n_layers:
            return self.thetas
        reps = -(-self.n_layers // len(self.thetas))
        return (self.thetas * reps)[: self.n_layers]

    @property
    def total_layers(self) -> int:
        return self.n_layers

    @property
    def min_window(self) -> Optional[int]:
        ws = [w for w in self.layer_windows()]
        return None if any(w is None for w in ws) else max(ws)


@dataclasses.dataclass(frozen=True)
class MoEGroup:
    n_layers: int
    n_experts: int
    top_k: int = 1                 # paper-assigned archs use top-1
    capacity_factor: float = 1.25
    shared_expert: bool = True     # llama4-style always-on shared expert
    router_aux_weight: float = 0.01
    # Interleave: every moe_every-th layer is MoE, the rest are dense MLP
    # (llama4-maverick alternates dense/MoE; scout is all-MoE).
    moe_every: int = 1

    kind: str = dataclasses.field(default="moe", init=False)

    def __post_init__(self):
        if self.moe_every < 1 or self.n_layers % self.moe_every:
            raise ValueError("n_layers must be divisible by moe_every >= 1")

    @property
    def n_units(self) -> int:
        return self.n_layers // self.moe_every

    @property
    def total_layers(self) -> int:
        return self.n_layers


@dataclasses.dataclass(frozen=True)
class XLSTMGroup:
    n_units: int                   # each unit = mlstm_per_unit mLSTM + 1 sLSTM
    mlstm_per_unit: int = 3
    proj_factor: float = 2.0       # mLSTM up-projection factor
    conv_kernel: int = 0           # 0 disables the causal conv (kept simple)

    kind: str = dataclasses.field(default="xlstm", init=False)

    @property
    def total_layers(self) -> int:
        return self.n_units * (self.mlstm_per_unit + 1)


@dataclasses.dataclass(frozen=True)
class ZambaGroup:
    """Zamba2 (arXiv:2411.15242; ``transformers`` ``Zamba2Config``).

    Every layer holds a Mamba2 mixer of ``expand * d_model /
    mamba_headdim`` heads in ``mamba_ngroups`` B/C groups. A "hybrid"
    layer first applies transformer block ``k mod num_mem_blocks`` (its
    k-th application; the blocks' weights are tied across applications)
    to concat(h, token embedding), with a rank-``adapter_rank`` adapter on
    the MLP's gate_up and a d x d ``linear`` of its own, and adds the result
    to its Mamba2 layer's input. Attention widths come from the model
    (``n_heads`` of ``head_dim`` over 2 * d_model in), the MLP's from
    ``d_ff``.
    """

    layers_block_type: Tuple[str, ...]   # "mamba" | "hybrid", one per layer
    num_mem_blocks: int = 2
    adapter_rank: int = 128
    mamba_headdim: int = 64
    mamba_ngroups: int = 2
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    chunk_size: int = 256
    time_step_min: float = 1e-3

    kind: str = dataclasses.field(default="zamba", init=False)

    def __post_init__(self):
        if set(self.layers_block_type) - {"mamba", "hybrid"}:
            raise ValueError("layers_block_type holds 'mamba' or 'hybrid'")

    @property
    def hybrid_layer_ids(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layers_block_type)
                     if t == "hybrid")

    @property
    def total_layers(self) -> int:
        return len(self.layers_block_type)


@dataclasses.dataclass(frozen=True)
class CrossSelfGroup:
    n_units: int                   # each unit = 1 cross-attn + self_per_unit self-attn
    self_per_unit: int = 4
    n_image_tokens: int = 1600

    kind: str = dataclasses.field(default="cross_self", init=False)

    @property
    def total_layers(self) -> int:
        return self.n_units * (self.self_per_unit + 1)


GroupSpec = object  # union of the dataclasses above


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    vocab_size: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    groups: Tuple[GroupSpec, ...]
    norm_eps: float = 1e-6
    activation: str = "silu"       # silu | geglu | gelu
    rope_theta: float = 10_000.0
    tie_embedding: bool = True
    embed_scale: bool = False      # gemma: scale embeddings by sqrt(d_model)
    logit_softcap: float = 0.0     # 0 disables
    input_mode: str = "tokens"     # tokens | embeddings (modality stub)
    param_dtype: str = "float32"
    # Eligible for the long_500k decode shape (SSM/hybrid state, or a mostly
    # sliding-window dense stack). Pure full-attention archs keep False and
    # skip long_500k.
    long_context_ok: bool = False
    # SPerf optimization: keep the layer-stacked KV cache in the decode
    # scan *carry* and update it in place (one token-slot write + one
    # layer-slice read per layer) instead of streaming the full stack
    # through scan xs/ys (full read + full write per step).
    decode_cache_in_carry: bool = False
    # SPerf optimization: route prefill self-attention through the Pallas
    # flash-attention kernel (O(S*D) HBM traffic instead of materialized
    # (S, S) scores). Forward-only — applies to prefill, not training.
    flash_prefill: bool = False
    # The tensor-parallel share this model holds, (rank, size): each layer
    # keeps 1/size of its heads, B/C groups and MLP columns, and the
    # vocabulary keeps rows [rank, rank + 1) * vocab_size / size. Size 1 is
    # the whole model. Only the Zamba group divides its layers.
    share: Tuple[int, int] = (0, 1)
    # citation for the assigned-architecture table
    source: str = ""

    def __post_init__(self):
        if self.activation not in ("silu", "geglu", "gelu"):
            raise ValueError(f"unknown activation {self.activation!r}")
        if self.input_mode not in ("tokens", "embeddings"):
            raise ValueError(f"unknown input_mode {self.input_mode!r}")
        if self.n_heads % max(self.n_kv_heads, 1) != 0:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        rank, size = self.share
        if not 0 <= rank < size or self.vocab_size % size:
            raise ValueError(f"bad share {self.share} of vocab "
                             f"{self.vocab_size}")
        if size > 1 and any(g.kind != "zamba" for g in self.groups):
            raise ValueError("only Zamba layers divide into shares")

    @property
    def total_layers(self) -> int:
        return sum(g.total_layers for g in self.groups)

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs are decoders
