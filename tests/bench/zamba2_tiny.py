"""The train-zamba2-7b-tp2 cell's configuration cut to a size a CPU test
holds: the same keys, share and protocol, smaller counts."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import manifest  # noqa: E402

CFG = json.loads((ROOT / "bench/configs/zamba2-7b-tp2.json").read_text())
KIND = manifest.load_module(ROOT / "bench", "kinds", "partpsp_train_zamba2")


def config(share_size=2, layers=("mamba", "mamba", "hybrid")):
    """d_model 64, 16 tokens, ``layers`` as given, 1/``share_size`` of
    each layer held."""
    c = json.loads(json.dumps(CFG))
    c.update(hidden_size=64, layers_block_type=list(layers),
             num_hidden_layers=len(layers), mamba_headdim=16,
             n_mamba_heads=8 // share_size, mamba_ngroups=2 // share_size,
             mamba_d_state=8, chunk_size=4, adapter_rank=4,
             num_attention_heads=4 // share_size, attention_head_dim=32,
             intermediate_size=64, vocab_size=128 // share_size,
             context_length=16)
    c["share"] = dict(c["share"], size=share_size)
    return c
