"""Model zoo substrate: config-driven decoder transformers covering the ten
assigned architectures (dense GQA, sliding-window, GeGLU, MoE top-1,
mLSTM/sLSTM, Mamba2 hybrid, cross-attention VLM, audio-token decoders)."""
from repro.models.config import (
    AttnGroup,
    CrossSelfGroup,
    ModelConfig,
    MoEGroup,
    XLSTMGroup,
    ZambaGroup,
)
from repro.models.transformer import Transformer

__all__ = [
    "ModelConfig",
    "AttnGroup",
    "MoEGroup",
    "XLSTMGroup",
    "ZambaGroup",
    "CrossSelfGroup",
    "Transformer",
]
