"""Work counts and the peak table, on known shapes."""
import json
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import counts, device  # noqa: E402

XLSTM = json.loads((ROOT / "bench/configs/xlstm-148m.json").read_text())
MLP = json.loads((ROOT / "bench/configs/paper-mlp-n16.json").read_text())


def test_xlstm_sizes_match_the_configuration():
    shapes = counts.xlstm_leaf_shapes(XLSTM["model"])
    shared = sum(counts.size(s) for p, s in shapes.items()
                 if p.startswith("group_0/mlstm/"))
    total = sum(counts.size(s) for s in shapes.values())
    assert shared == XLSTM["d_s"] == 95_669_064
    assert total - shared == XLSTM["d_l"] == 52_801_536
    assert total == XLSTM["n_params"] == 148_470_600
    assert XLSTM["d_pad"] == -(-XLSTM["d_s"] // 128) * 128


def test_xlstm_shapes_match_the_program():
    from repro.configs import get_config
    from repro.models import Transformer
    from repro.core.partition import _path_str

    model = Transformer(get_config("xlstm-125m").model)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prog = {_path_str(p): tuple(x.shape)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    ours = counts.xlstm_leaf_shapes(XLSTM["model"])
    assert list(prog) == list(ours)
    assert prog == ours


def test_mlp_sizes():
    assert sum(counts.size(tuple(s)) for s in MLP["values"]["leaves"]) \
        == MLP["d_s"] == 7850
    assert MLP["d_pad"] == 7936


def test_train_flops_are_ten_p_t_per_node():
    # 148.5M parameters, 256 tokens per node, 4 nodes.
    assert counts.train_flops_per_step(148_470_600, 256, 4) == \
        10 * 148_470_600 * 256 * 4
    assert counts.train_flops_per_step(1, 1, 1) == 10.0


@pytest.mark.parametrize("perturbs,per_elem", [(True, 12), (False, 8)])
def test_perturb_bytes(perturbs, per_elem):
    assert counts.perturb_bytes_per_round(16, 7850, perturbs) == \
        per_elem * 16 * 7850


def test_round_bytes():
    assert counts.round_bytes_per_round(4, 95_669_120) == 8 * 4 * 95_669_120


def test_peaks_table_has_the_v5e():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_no_tpu_is_refused():
    with pytest.raises(device.NoChip):
        device.chips(1)
    with pytest.raises(device.NoChip):
        device.chips(len(jax.devices()) + 1, require_tpu=False)
