"""The plain reference against the program's model at a tiny size, and
the seeded weights layer by layer against the stacked tree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, weights
from benchmark.reference import mistral as ref

CFG = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           vocab_size=256, rope_theta=1e6, rms_norm_eps=1e-5)
SEED = 2**31 + 77   # past 32 signed bits, as the driver's seeds are


def test_a_layer_made_alone_equals_its_slice_of_the_stacked_tree():
    tree = weights.init_all(CFG, SEED)
    key = weights.root_key(SEED)
    one = jax.jit(lambda k, i: weights.layer(CFG, k, i))(key, jnp.int32(2))
    for name in weights.MATMUL_LEAVES:
        q, scale = tree['layers'][name]
        assert q.dtype == jnp.int8 and scale.dtype == jnp.bfloat16
        assert bool((q[2] == one[name][0]).all())
        assert bool((scale[2] == one[name][1]).all())
        assert int(jnp.abs(q).max()) == 127
    for name in weights.NORM_LEAVES:
        assert bool((tree['layers'][name][2] == one[name]).all())
    other = weights.init_all(CFG, SEED + 1)
    assert not bool((other['lm_head'][0] == tree['lm_head'][0]).all())


def test_reference_matches_the_programs_forward_pass():
    from skypilot_tpu.models import llama
    from skypilot_tpu.ops.quant import QuantArray
    tree = weights.init_all(CFG, SEED)

    def leaf(v):
        if isinstance(v, tuple):
            return QuantArray(q=v[0], scale=v[1].astype(jnp.float32))
        return v.astype(jnp.float32)
    params = {'embed': weights.dequantize(*tree['embed'], axis=1),
              'layers': {k: leaf(v) for k, v in tree['layers'].items()},
              'final_norm': leaf(tree['final_norm']),
              'lm_head': leaf(tree['lm_head'])}
    lcfg = llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq_len=128, rope_theta=1e6, norm_eps=1e-5,
        dtype='float32', attention_impl='dense')
    tokens = np.random.default_rng(0).integers(0, 256, (1, 48))
    got = llama.forward(lcfg, params, jnp.asarray(tokens))[0]
    want = ref.forward(CFG, check.reference_weights(CFG, SEED),
                       jnp.asarray(tokens[0]))
    # float32 on both sides: rounding of a few ulps through three layers
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_rope_is_the_rotate_half_convention_at_theta_1e6():
    x = jnp.ones((3, 1, 4))
    out = ref.rope(x, jnp.arange(3), 1e6)
    # pair (x0, x2) turns by pos * 1, pair (x1, x3) by pos * 1e6**-0.5
    for pos in range(3):
        a, b = pos * 1.0, pos * 1e-3
        want = [np.cos(a) - np.sin(a), np.cos(b) - np.sin(b),
                np.cos(a) + np.sin(a), np.cos(b) + np.sin(b)]
        np.testing.assert_allclose(out[pos, 0], want, rtol=1e-6)


@pytest.mark.parametrize('act', ['int8', 'fp8', 'bf16'])
def test_lower_precisions_round_and_the_reference_does_not(act):
    x = jnp.asarray([[0.1234567, -3.3, 100.0, 1e-3]])
    assert bool((ref.lower_precision(x, None) == x).all())
    assert not bool((ref.lower_precision(x, act) == x).all())


def test_every_norm_has_the_same_hot_channels():
    tree = weights.init_all(CFG, SEED)
    hot = np.asarray(weights.hot_channels(CFG, weights.root_key(SEED)))
    assert len(hot) == 1     # one to 1024 channels, and at least one
    norms = [tree['final_norm'], *tree['layers']['attn_norm'],
             *tree['layers']['mlp_norm']]
    for w in norms:
        w = np.asarray(w.astype(jnp.float32))
        cold = np.delete(w, hot)
        assert 0.5 < cold.min() and cold.max() < 1.5
        assert (w[hot] > 0.5 * weights.HOT_GAIN).all()
    other = weights.hot_channels(CFG, weights.root_key(SEED + 1))
    assert weights.hot_channels(dict(CFG, hidden_size=4096),
                                weights.root_key(SEED)).shape == (4,)
    assert other.shape == (1,)


def test_w8a8_lowers_the_weight_products_inputs_and_nothing_else():
    assert ref.precisions('w8a8') == (None, 'int8')
    assert ref.precisions('int8') == ('int8', 'int8')
    assert ref.precisions(None) == (None, None)
    w = check.reference_weights(CFG, SEED)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40))
    exact = ref.forward(CFG, w, tokens)
    low = ref.forward(CFG, w, tokens, act='w8a8')
    all_int8 = ref.forward(CFG, w, tokens, act='int8')
    err = float(jnp.abs(low - exact).max())
    assert 0 < err < float(jnp.abs(all_int8 - exact).max())
