"""The FLOP counts follow the parameter shapes."""
import json

import jax
import numpy as np
import pytest

from bench_tiny import REPO

from bench import harness
from bench.reference import ssm as ref_ssm


def matmul_params_per_layer(conf):
    """Weights of one layer that a token multiplies (2-D, per layer)."""
    ref = harness.family("reference", conf)
    shapes = jax.eval_shape(lambda k: ref.init(k, conf),
                            np.zeros(2, np.uint32))
    n = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes["layers"])[0]:
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 3 and "conv_w" not in name:
            n += leaf.shape[1] * leaf.shape[2]
    return n


@pytest.mark.parametrize("name", ["mamba2-130m", "hymba-1.5b-L8"])
def test_layer_matmuls_match_the_weights(name):
    conf = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    fl = harness.family("flops", conf)
    assert fl.layer_matmul_params(conf) == matmul_params_per_layer(conf)
    per_token = fl.token_flops(conf, 100) + fl.head_flops(conf)
    assert per_token > 2 * conf["num_layers"] * matmul_params_per_layer(conf)
    assert fl.head_flops(conf) == 2 * conf["d_model"] * conf["vocab_size"]


def test_attention_work_stops_growing_at_the_window():
    conf = json.loads((REPO / "bench/configs/hymba-1.5b-L8.json").read_text())
    fl = harness.family("flops", conf)
    w = conf["sliding_window"]
    assert fl.layer_flops(conf, w - 1) == fl.layer_flops(conf, 5 * w)
    assert fl.layer_flops(conf, 10) < fl.layer_flops(conf, w - 1)


def test_weight_bytes_of_the_configurations():
    for name, want in (("mamba2-130m", 258_316_032),
                       ("hymba-1.5b-L8", 975_885_760)):
        conf = json.loads((REPO / f"bench/configs/{name}.json").read_text())
        assert harness.weight_bytes(conf) == want
    assert ref_ssm.ROWS >= 1
