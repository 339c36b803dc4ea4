"""The on-device packed-weight construction makes the tree a deployment
serves: the same structure, shapes, dtypes and QuantizedTensor statics
as GPTQ + norm tweaking (`norm_tweak_ptq`) give, at smoke size; and its
weights are the reference's draws on the reference's grid."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_smoke import SMOKE, smoke
import weights
from references import dense

def _describe(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [(x.shape, x.dtype) for x in leaves]


@pytest.mark.parametrize("name", sorted(SMOKE))
def test_built_tree_is_the_norm_tweaked_tree(name):
    from repro.core.normtweak.pipeline import NTConfig, norm_tweak_ptq
    from repro.models.transformer import init_lm

    _, a, cfg = smoke(name)
    built = weights.build_served(a, cfg, seed=2**33 + 1)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    calib = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, a.vocab)
    nt = NTConfig(method="gptq", bits=a.bits, group_size=a.group_size,
                  iters=1, sample_batch=2)
    tweaked, _ = norm_tweak_ptq(cfg, params, calib, nt)
    assert _describe(built) == _describe(tweaked)


@pytest.mark.parametrize("name", sorted(SMOKE))
def test_built_weights_are_the_references_draws(name):
    from repro.core.quant.types import dequantize

    _, a, cfg = smoke(name)
    seed = 2**40 + 5
    built = weights.build_served(a, cfg, seed)
    ref = dense.block_weights(a, dense.base_key(seed), 1)
    blk = jax.tree.map(lambda x: x[1], built["stack"]["p0"])
    for path, (_, kind) in dense.layer_leaves(a).items():
        node = blk
        for k in path.split("/"):
            node = node[k]
        got = (dequantize(node) if kind == "linear"
               else node.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref[path]),
                                   rtol=1e-6, atol=1e-7, err_msg=path)
    g = dense.global_weights(a, dense.base_key(seed))
    np.testing.assert_array_equal(
        np.asarray(built["embed"]["w"].astype(jnp.float32)),
        np.asarray(g["embed/w"]))


def test_program_config_must_match_the_file():
    _, a, cfg = smoke("chatglm3-6b-w4g128")
    with pytest.raises(ValueError, match="departs"):
        weights.check_widths(a, cfg.replace(d_ff=256))
