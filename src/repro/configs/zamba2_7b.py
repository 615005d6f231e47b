"""zamba2-7b [hybrid] — Zyphra's Zamba2-7B: 81 layers at d_model 3584, each
with a Mamba2 mixer (112 heads of 64, state 64, 2 B/C groups, conv 4,
chunk 256); the 13 hybrid layers (ids 6, 11, 17, ..., 77) first apply one
of 2 weight-tied transformer blocks (32 heads of 224 over the 7168-wide
concat(h, embedding), GELU-gated MLP 14336 with a rank-128 adapter per
application) through a 3584 x 3584 linear of their own. vocab 32000, head
tied to the embedding, 7,356,749,648 parameters.

Source: https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json
and arXiv:2411.15242; the equations follow ``transformers``'
``modeling_zamba2`` (see ``repro.models.transformer._ZambaGroupImpl``).
"""
from repro.configs.base import ArchSpec
from repro.models.config import ModelConfig, ZambaGroup

_M, _H = "mamba", "hybrid"
# layers_block_type of the published config.json
LAYERS = (_M,) * 6 + (_H,) + (_M,) * 4 + ((_H,) + (_M,) * 5) * 11 + (_H,) + (_M,) * 3

MODEL = ModelConfig(
    name="zamba2-7b",
    d_model=3584,
    vocab_size=32_000,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14_336,
    activation="gelu",
    norm_eps=1e-5,
    rope_theta=10_000.0,
    tie_embedding=True,
    groups=(ZambaGroup(layers_block_type=LAYERS, num_mem_blocks=2,
                       adapter_rank=128, mamba_headdim=64, mamba_ngroups=2,
                       d_state=64, d_conv=4, expand=2, chunk_size=256),),
    long_context_ok=True,   # Mamba2 state is O(1); 13 attention caches
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct; arXiv:2411.15242",
)

# Two hybrid layers, so both tied blocks and two adapters are exercised.
SMOKE = ModelConfig(
    name="zamba2-7b-smoke",
    d_model=64,
    vocab_size=512,
    n_heads=4,
    n_kv_heads=4,
    head_dim=32,
    d_ff=128,
    activation="gelu",
    norm_eps=1e-5,
    tie_embedding=True,
    groups=(ZambaGroup(layers_block_type=(_M, _H, _M, _H), num_mem_blocks=2,
                       adapter_rank=4, mamba_headdim=16, mamba_ngroups=2,
                       d_state=16, d_conv=4, expand=2, chunk_size=8),),
    long_context_ok=True,
)

SPEC = ArchSpec(
    name="zamba2-7b",
    family="hybrid",
    model=MODEL,
    smoke=SMOKE,
    # The tied transformer blocks are the globally coupled component: share
    # them; the Mamba2 layers, adapters and linears stay local (paper SIII.C).
    shared_rules=(("group_0/shared_blocks/.*", "shared"),),
    notes="SPerf hillclimb pair #3 (long_500k decode memory)",
)
