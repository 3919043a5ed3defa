"""Operation, byte and peak tables against hand counts."""
import pytest

import counts
import models
import peaks


def test_caffenet_counts_from_its_shapes():
    cfg = models.load_config("caffenet")
    layers = counts.cnn_layer_macs(cfg)
    # conv1 55x55 outputs of 11x11x3 -> 96; fc6 sees 2x2x256 = 1,024
    assert layers[0]["macs"] == 55 * 55 * 11 * 11 * 3 * 96
    assert layers[5]["macs"] == 1024 * 4096
    assert sum(x["macs"] for x in layers) == 614_310_432
    assert counts.cnn_forward_flops(cfg) == 2 * 614_310_432
    # backward: weight gradients everywhere, input gradients but conv1
    fwd = 614_310_432
    assert counts.cnn_train_flops(cfg) == 2 * (3 * fwd - layers[0]["macs"])
    assert counts.cnn_params(cfg) == 28_823_912


def test_phi4_mini_tied_parameters_and_decode_bytes():
    cfg = models.load_config("phi4-mini-3.8b")
    assert counts.lm_params(cfg) == 3_836_021_760
    untied = dict(cfg, tie_word_embeddings=False)
    assert counts.lm_params(untied) - counts.lm_params(cfg) == 200064 * 3072
    w = counts.lm_decode_bytes(cfg, 0)
    assert w == 2 * (32 * counts.lm_layer_params(cfg) + 200064 * 3072)
    # one live token adds K and V rows of 8 heads x 128 in bf16, 32 layers
    assert counts.lm_decode_bytes(cfg, 1) - w == 32 * 2 * 8 * 128 * 2


def test_weights_tree_matches_the_program():
    import jax
    from repro.models import transformer as Tm
    cfg = models.load_config("tiny-lm")
    ours = jax.eval_shape(models.lm_weights_fn(cfg), jax.random.PRNGKey(0))
    arch = models.lm_program_config(cfg)
    theirs = jax.eval_shape(lambda k: Tm.init_params(k, arch),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    n = sum(x.size for x in jax.tree.leaves(ours))
    assert n == counts.lm_params(cfg)


def test_peak_table():
    p = peaks.peak("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bw == 819e9
    assert p.ici_bw == 200e9 and "v5e" in p.source
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_seeds_beyond_32_bits_give_distinct_keys():
    import jax
    import numpy as np
    a = jax.random.key_data(models.key_from_seed(2**31 + 5))
    b = jax.random.key_data(models.key_from_seed(5))
    c = jax.random.key_data(models.key_from_seed(2**31 + 5))
    assert not np.array_equal(a, b) and np.array_equal(a, c)
