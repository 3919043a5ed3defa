"""Operation, byte and peak tables against hand counts, and the seeded
weights against sums taken before the family modules existed."""
import jax
import numpy as np
import pytest

import families
import models
import peaks
from families import cnn, dense_lm


def test_caffenet_counts_from_its_shapes():
    cfg = models.load_config("caffenet")
    layers = cnn.layer_macs(cfg)
    # conv1 55x55 outputs of 11x11x3 -> 96; fc6 sees 2x2x256 = 1,024
    assert layers[0]["macs"] == 55 * 55 * 11 * 11 * 3 * 96
    assert layers[5]["macs"] == 1024 * 4096
    assert sum(x["macs"] for x in layers) == 614_310_432
    assert cnn.forward_flops(cfg) == 2 * 614_310_432
    # backward: weight gradients everywhere, input gradients but conv1
    fwd = 614_310_432
    assert cnn.train_flops(cfg) == 2 * (3 * fwd - layers[0]["macs"])
    assert cnn.params(cfg) == 28_823_912
    assert families.of(cfg) is cnn


def test_phi4_mini_tied_parameters_and_decode_bytes():
    cfg = models.load_config("phi4-mini-3.8b")
    assert families.of(cfg) is dense_lm
    assert dense_lm.params(cfg) == 3_836_021_760
    untied = dict(cfg, tie_word_embeddings=False)
    assert dense_lm.params(untied) - dense_lm.params(cfg) == 200064 * 3072
    w = dense_lm.decode_bytes(cfg, 0)
    assert w == 2 * (32 * dense_lm.layer_params(cfg) + 200064 * 3072)
    # one live token adds K and V rows of 8 heads x 128 in bf16, 32 layers
    assert dense_lm.decode_bytes(cfg, 1) - w == 32 * 2 * 8 * 128 * 2
    # 2 FLOPs per matmul parameter: 32 layers of 100,663,296 (attention
    # 25,165,824, SwiGLU 75,497,472) and the tied head once
    assert dense_lm.forward_flops_per_token(cfg) == 2 * 3_835_822_080


def test_weights_tree_matches_the_program():
    from repro.models import transformer as Tm
    cfg = models.load_config("tiny-lm")
    ours = jax.eval_shape(dense_lm.weights_fn(cfg), jax.random.PRNGKey(0))
    arch = dense_lm.program_config(cfg)
    theirs = jax.eval_shape(lambda k: Tm.init_params(k, arch),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    n = sum(x.size for x in jax.tree.leaves(ours))
    assert n == dense_lm.params(cfg)


def test_peak_table():
    p = peaks.peak("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bw == 819e9
    assert p.ici_bw == 200e9 and "v5e" in p.source
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_seeds_beyond_32_bits_give_distinct_keys():
    a = jax.random.key_data(models.key_from_seed(2**31 + 5))
    b = jax.random.key_data(models.key_from_seed(5))
    c = jax.random.key_data(models.key_from_seed(2**31 + 5))
    assert not np.array_equal(a, b) and np.array_equal(a, c)


#: sum of |x| of every leaf, in the tree's flattened order, of each
#: stand-in's weights at seed 2**31 + 29, as the harness made them before
#: the family modules (JAX 0.9.0 on the CPU)
WEIGHT_SUMS = {
    "tiny-lm": [6492.7146072387695, 13078.87638092041, 13043.1279296875,
                6520.100204467773, 80.26365661621094, 80.20024108886719,
                18452.81329345703, 26131.003189086914, 26122.4994430542,
                26048.831970214844, 18.898696899414062],
    "tiny-cnn": [0.08682420721743256, 39.17595574302322,
                 0.17276536137796938, 53.249709306444856,
                 0.10130985209252685, 78.10241253830623,
                 0.23557696610805579, 49.99294526557787,
                 0.11165811261162162, 22.04393059751601],
}


@pytest.mark.parametrize("name", sorted(WEIGHT_SUMS))
def test_seeded_weights_are_those_made_before_the_families(name):
    """Leaf i is drawn from fold_in(key, i): a reordered tree, another
    scale or another type changes these sums, and every reading with
    them."""
    w = models.weights(models.load_config(name),
                       models.key_from_seed(2**31 + 29))
    sums = [float(np.abs(np.asarray(x, np.float64)).sum())
            for x in jax.tree.leaves(w)]
    assert sums == pytest.approx(WEIGHT_SUMS[name], rel=1e-9)
