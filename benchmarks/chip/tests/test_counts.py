"""The count functions against hand-worked values, and the benchmark's
weight layout against the program's, for both configurations (CPU).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import counts  # noqa: E402
import harness  # noqa: E402


def cfg(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# By hand.  qwen2-0.5b, 4 layers, d 896, 14/2 heads of 64, d_ff 4864:
#   per layer  q 896*896 + k, v 2 * 896*128 + o 896*896 + MLP 3*896*4864
#              = 802816 + 229376 + 802816 + 13074432 = 14909440
#   head       896 * 151936 = 136134656
#   matmul     4 * 14909440 + 136134656 = 195772416
#   the rest   4 * (2 * 896 norms + 896 + 2 * 128 biases) + 896 = 12672
# qwen3-1.7b, 3 layers, d 2048, 16/8 heads of 128, d_ff 6144:
#   per layer  2048*2048 + 2 * 2048*1024 + 2048*2048 + 3 * 2048*6144
#              = 4194304 + 4194304 + 4194304 + 37748736 = 50331648
#   head       2048 * 151936 = 311164928
#   matmul     3 * 50331648 + 311164928 = 462159872
#   the rest   3 * (2 * 2048 norms + 2 * 128 q/k norms) + 2048 = 15104
HAND = {
    "qwen2-0.5b": {"matmul": 195_772_416, "params": 195_785_088},
    "qwen3-1.7b": {"matmul": 462_159_872, "params": 462_174_976},
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_param_counts(name):
    c = cfg(name)
    assert counts.matmul_params(c) == HAND[name]["matmul"]
    assert counts.param_count(c) == HAND[name]["params"]


@pytest.mark.parametrize("name,per_token", [
    # 3 * (2 * matmul + L * 2 * H * hd * S): attention 4*2*14*64*1024,
    # 3*2*16*128*1024
    ("qwen2-0.5b", 3 * (2 * 195_772_416 + 4 * 2 * 14 * 64 * 1024)),
    ("qwen3-1.7b", 3 * (2 * 462_159_872 + 3 * 2 * 16 * 128 * 1024)),
])
def test_flops_per_token(name, per_token):
    assert counts.train_flops_per_token(cfg(name), 1024) == per_token


def test_round_bytes():
    # qwen2-0.5b at n = 8, bf16 fresh and slabs, SGD: 16 + 64 + 16 bytes
    # per parameter
    P = HAND["qwen2-0.5b"]["params"]
    assert counts.round_bytes(8, P, 2, 2) == 96 * P
    # qwen3-1.7b at n = 4: 8 + 32 + 16
    P = HAND["qwen3-1.7b"]["params"]
    assert counts.round_bytes(4, P, 2, 2) == 56 * P


def test_peaks_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(LookupError):
        counts.peaks("cpu")


@pytest.mark.parametrize("name", sorted(HAND))
def test_layout_matches_program(name):
    """The benchmark's weights, laid out for the program, have the
    program's tree and leaf shapes, and as many values as the hand count."""
    import jax
    from repro.launch.steps import abstract_params
    c = cfg(name)
    ref, prog = harness.load_family(c)
    mine = jax.eval_shape(lambda k: prog.to_program(ref.init(k, c)),
                          jax.random.PRNGKey(0))
    theirs = abstract_params(prog.model_config(c, 4))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(mine)] == \
        [x.shape for x in jax.tree.leaves(theirs)]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(mine)) == \
        HAND[name]["params"]
