"""The plain Zamba2 reference of the train-zamba2-7b-tp2 cell against
``transformers``' Zamba2ForCausalLM and against the program's model."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src"), str(ROOT / "tests/bench")]

from benchlib import zamba2_params  # noqa: E402
from benchlib.weights import nest  # noqa: E402
from refs import zamba2 as ref_zamba2  # noqa: E402
import zamba2_tiny  # noqa: E402

LAYERS = ["mamba", "mamba", "hybrid", "mamba", "hybrid"]


@pytest.mark.parametrize("share_size", [1, 2])
def test_program_matches_the_reference(share_size):
    """Loss and every leaf's gradient of the program's Transformer against
    the plain reference, on the same seeded weights and tokens, whole and
    as one of two shares. rtol 1e-4: float32 summation order (the CPU runs
    both at full float32)."""
    cfg = zamba2_tiny.config(share_size, LAYERS)
    m = zamba2_params.held(cfg)
    params = jax.tree_util.tree_map(
        lambda x: x[0], zamba2_params.params(m, jax.random.PRNGKey(3), 1))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 21), 0, m["vocab"])
    model = zamba2_tiny.KIND.zamba2_model(cfg)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss_fn)(params, {"tokens": tokens})
    paths = tuple(zamba2_params.leaf_shapes(m))
    lr, gr = ref_zamba2.value_and_grad(params, tokens, model=m, keep=paths)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    flat = {p: g for p, g in zip(paths, jax.tree_util.tree_leaves(gp))}
    for p in paths:
        np.testing.assert_allclose(np.asarray(flat[p]), np.asarray(gr[p]),
                                   rtol=1e-4, atol=1e-4 * float(
                                       jnp.max(jnp.abs(gr[p]))), err_msg=p)


def _hf_model():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = transformers.Zamba2Config(
        vocab_size=100, hidden_size=64, num_hidden_layers=len(LAYERS),
        layers_block_type=LAYERS, mamba_d_state=16, mamba_d_conv=4,
        mamba_expand=2, mamba_ngroups=2, n_mamba_heads=16, chunk_size=8,
        intermediate_size=128, num_attention_heads=4, num_mem_blocks=2,
        adapter_rank=4, use_mem_rope=True, use_shared_attention_adapter=False,
        rms_norm_eps=1e-5, max_position_embeddings=64,
        attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.Zamba2ForCausalLM(cfg).eval()
    with torch.no_grad():
        # Weights far from the initializer's small ones, so that every
        # term moves the logits.
        for name, prm in model.named_parameters():
            if name.endswith(("A_log", "dt_bias")):
                continue
            if "norm" in name:
                prm.copy_(1 + 0.1 * torch.randn_like(prm))
            else:
                prm.copy_(0.15 * torch.randn_like(prm))
    m = {"d_model": 64, "layers": tuple(LAYERS), "num_mem_blocks": 2,
         "adapter_rank": 4, "mamba_heads": 16, "mamba_headdim": 8,
         "mamba_groups": 2, "d_state": 16, "d_conv": 4, "chunk_size": 8,
         "attn_heads": 4, "attn_head_dim": 32, "ffn": 128, "vocab": 100,
         "eps": 1e-5, "rope_theta": 10000.0, "dt_min": 1e-3, "rank": 0}
    return torch, cfg, model, m


def _from_hf(sd, cfg):
    """transformers' state dict -> the reference's (and program's) tree."""
    sd = {k: v.detach().numpy() for k, v in sd.items()}
    flat = {"embed": sd["model.embed_tokens.weight"],
            "final_ln/scale": sd["model.final_layernorm.weight"]}
    mixer = ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
             "norm", "out_proj")
    cols = {k: [] for k in ("ln/scale",) + tuple("mixer/" + k for k in mixer)}
    for i, t in enumerate(cfg.layers_block_type):
        pre = f"model.layers.{i}." + ("mamba_decoder." if t == "hybrid" else "")
        mx = pre + "mamba."
        cols["ln/scale"].append(sd[pre + "input_layernorm.weight"])
        cols["mixer/in_proj"].append(sd[mx + "in_proj.weight"].T)
        cols["mixer/conv_w"].append(sd[mx + "conv1d.weight"][:, 0, :].T)
        cols["mixer/conv_b"].append(sd[mx + "conv1d.bias"])
        cols["mixer/dt_bias"].append(sd[mx + "dt_bias"])
        cols["mixer/a_log"].append(sd[mx + "A_log"])
        cols["mixer/d_skip"].append(sd[mx + "D"])
        cols["mixer/norm"].append(sd[mx + "norm.weight"])
        cols["mixer/out_proj"].append(sd[mx + "out_proj.weight"].T)
    hyb = {"adapter_a": [], "adapter_b": [], "linear": []}
    blocks = {}
    for k, i in enumerate(cfg.hybrid_layer_ids):
        st = f"model.layers.{i}.shared_transformer."
        ad = st + f"feed_forward.gate_up_proj_adapter_list.{k}."
        hyb["adapter_a"].append(sd[ad + "0.weight"].T)
        hyb["adapter_b"].append(sd[ad + "1.weight"].T)
        hyb["linear"].append(sd[f"model.layers.{i}.linear.weight"].T)
        blocks.setdefault(k % cfg.num_mem_blocks, {
            "ln_in/scale": sd[st + "input_layernorm.weight"],
            "ln_ff/scale": sd[st + "pre_ff_layernorm.weight"],
            "wq": sd[st + "self_attn.q_proj.weight"].T,
            "wk": sd[st + "self_attn.k_proj.weight"].T,
            "wv": sd[st + "self_attn.v_proj.weight"].T,
            "wo": sd[st + "self_attn.o_proj.weight"].T,
            "gate_up": sd[st + "feed_forward.gate_up_proj.weight"].T,
            "down": sd[st + "feed_forward.down_proj.weight"].T})
    flat.update({"group_0/mamba/" + k: np.stack(v) for k, v in cols.items()})
    flat.update({"group_0/hybrid/" + k: np.stack(v) for k, v in hyb.items()})
    flat.update({"group_0/shared_blocks/" + k: np.stack(
        [blocks[j][k] for j in sorted(blocks)]) for k in blocks[0]})
    return nest({k: jnp.asarray(v) for k, v in flat.items()})


@pytest.mark.parametrize("path", ["forward", "cached_decode"])
def test_reference_matches_transformers(path):
    """The reference's logits against transformers' Zamba2ForCausalLM on
    the same weights, to 1e-5 of the largest logit (float32 summation
    order). ``forward``: the whole sequence, 8 tokens, one SSD chunk.
    ``cached_decode``: 21 tokens fed one at a time through transformers'
    cache, which runs the recurrence and attends to the cached keys: its
    full-sequence slow path is only right within one chunk (4.57.6 sums
    the inter-chunk decay over the wrong chunk index), so longer sequences
    are read through the cache."""
    torch, cfg, model, m = _hf_model()
    params = _from_hf(model.state_dict(), cfg)
    toks = np.random.default_rng(0).integers(0, 100, (2, 21))
    with torch.no_grad():
        if path == "forward":
            toks = toks[:, :8]
            want = model(torch.tensor(toks)).logits.numpy()
        else:
            from transformers.models.zamba2.modeling_zamba2 import \
                Zamba2HybridDynamicCache

            cache = Zamba2HybridDynamicCache(cfg, 2, dtype=torch.float32)
            want = np.stack([model(
                torch.tensor(toks[:, t:t + 1]), past_key_values=cache,
                use_cache=True, cache_position=torch.tensor([t])
            ).logits.numpy()[:, 0] for t in range(toks.shape[1])], 1)
    got = np.stack([np.asarray(ref_zamba2.logits(params, jnp.asarray(t),
                                                 model=m)) for t in toks])
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


