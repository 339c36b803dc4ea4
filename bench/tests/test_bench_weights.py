"""The on-device packed-weight construction makes the tree a deployment
serves: the same structure, shapes, dtypes and QuantizedTensor statics
as GPTQ + norm tweaking (`norm_tweak_ptq`) give, at smoke size; its
weights are the reference's draws on the round-to-nearest grid; and the
builder takes any reference module's block groups, an MoE model's
dense prefix block and (E, K, N) expert stacks included."""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_smoke import CONFIGS, smoke
import weights


def _describe(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [(x.shape, x.dtype) for x in leaves]


def _leaf(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def _rtn(w, bits, group_size):
    """Symmetric round-to-nearest of w (K, N), one scale per group_size
    rows of K and per column: scale = max|w| / (2^(bits-1) - 1)."""
    k, n = w.shape
    qmax = 2 ** (bits - 1) - 1
    wg = w.reshape(k // group_size, group_size, n)
    scale = np.maximum(np.abs(wg).max(axis=1) / np.float32(qmax),
                       np.float32(1e-10))
    q = np.clip(np.round(wg / scale[:, None, :]), -qmax, qmax)
    return (q * scale[:, None, :]).reshape(k, n)


def _assert_draws(ref, a, built, seed, pdtype):
    """Every leaf of one block of each group, and every global leaf, is
    the reference's draw: packed on the grid, or cast to `pdtype`."""
    from repro.core.quant.types import dequantize

    key = ref.base_key(seed)
    draw = jax.jit(ref.draw, static_argnums=(2, 3, 4))
    for gpath, layers, leaves in ref.groups(a):
        stacked = gpath.startswith("stack/")
        i = min(1, len(layers) - 1)
        blk = _leaf(built, gpath)
        if stacked:
            blk = jax.tree.map(lambda x: x[i], blk)
        for path, (shape, kind) in leaves.items():
            w = np.asarray(draw(key, layers[i], path, shape, kind))
            node = _leaf(blk, path)
            if kind == "linear":
                want = _rtn(w, a.bits, a.group_size)
            elif kind == "experts":
                want = np.stack([_rtn(we, a.bits, a.group_size) for we in w])
            else:
                want = np.asarray(jnp.asarray(w).astype(pdtype)
                                  .astype(jnp.float32))
            got = (dequantize(node) if kind in weights.PACKED
                   else node.astype(jnp.float32))
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                       atol=1e-7, err_msg=f"{gpath}/{path}")
    for path, (shape, kind) in ref.global_leaves(a).items():
        w = draw(key, -1, path, shape, kind)
        np.testing.assert_array_equal(
            np.asarray(_leaf(built, path).astype(jnp.float32)),
            np.asarray(w.astype(pdtype).astype(jnp.float32)), err_msg=path)


@pytest.mark.parametrize("name", CONFIGS)
def test_built_tree_is_the_norm_tweaked_tree(name):
    from repro.core.normtweak.pipeline import NTConfig, norm_tweak_ptq
    from repro.models.transformer import init_lm

    _, ref, a, cfg = smoke(name)
    built = weights.build_served(ref, a, cfg, seed=2**33 + 1)
    params = init_lm(cfg, jax.random.PRNGKey(0))
    calib = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, a.vocab)
    nt = NTConfig(method="gptq", bits=a.bits, group_size=a.group_size,
                  iters=1, sample_batch=2)
    tweaked, _ = norm_tweak_ptq(cfg, params, calib, nt)
    assert _describe(built) == _describe(tweaked)


@pytest.mark.parametrize("name", CONFIGS)
def test_built_weights_are_the_references_draws(name):
    _, ref, a, cfg = smoke(name)
    seed = 2**40 + 5
    _assert_draws(ref, a, weights.build_served(ref, a, cfg, seed), seed,
                  jnp.dtype(cfg.param_dtype))


def test_program_config_must_match_the_file():
    _, ref, a, cfg = smoke(CONFIGS[0])
    with pytest.raises(ValueError, match="departs"):
        weights.build_served(ref, a, cfg.replace(d_ff=256), seed=1)


# ------------------------------------------------ an MoE model's layout

@dataclasses.dataclass(frozen=True)
class _MoEArch:
    vocab: int
    bits: int
    group_size: int
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    ff: int
    experts: int
    expert_ff: int
    shared: int
    blocks: int           # MoE blocks after the one dense prefix block


class _DeepSeekLayout:
    """The weight layout a DeepSeek-V2 reference module would give, written
    out leaf by leaf (MLA without q-LoRA, a dense first block, MoE blocks
    of routed and shared experts), with a plain seeded law."""

    @staticmethod
    def check_widths(a, cfg):
        assert (cfg.d_model, cfg.vocab_size, cfg.moe.n_experts) == (
            a.d, a.vocab, a.experts)

    @staticmethod
    def base_key(seed):
        return jax.random.PRNGKey(seed % 2**31)

    @staticmethod
    def _attn(a):
        qk = a.nope + a.rope
        return {"ln1/scale": ((a.d,), "norm_scale"),
                "ln2/scale": ((a.d,), "norm_scale"),
                "attn/wq/w": ((a.d, a.heads * qk), "linear"),
                "attn/wdkv/w": ((a.d, a.kv_lora + a.rope), "linear"),
                "attn/kvnorm/scale": ((a.kv_lora,), "norm_scale"),
                "attn/wukv/w": ((a.kv_lora, a.heads * (a.nope + a.v)),
                                "linear"),
                "attn/wo/w": ((a.heads * a.v, a.d), "linear")}

    @staticmethod
    def groups(a):
        dense = dict(_DeepSeekLayout._attn(a), **{
            "mlp/wi/w": ((a.d, a.ff), "linear"),
            "mlp/wg/w": ((a.d, a.ff), "linear"),
            "mlp/wo/w": ((a.ff, a.d), "linear")})
        sh = a.shared * a.expert_ff
        moe = dict(_DeepSeekLayout._attn(a), **{
            "moe/router/w": ((a.d, a.experts), "router"),
            "moe/experts/wi/w": ((a.experts, a.d, a.expert_ff), "experts"),
            "moe/experts/wg/w": ((a.experts, a.d, a.expert_ff), "experts"),
            "moe/experts/wo/w": ((a.experts, a.expert_ff, a.d), "experts"),
            "moe/shared/wi/w": ((a.d, sh), "linear"),
            "moe/shared/wg/w": ((a.d, sh), "linear"),
            "moe/shared/wo/w": ((sh, a.d), "linear")})
        return [("prefix/0", (0,), dense),
                ("stack/p0", tuple(range(1, 1 + a.blocks)), moe)]

    @staticmethod
    def global_leaves(a):
        return {"embed/w": ((a.vocab, a.d), "embed"),
                "final_norm/scale": ((a.d,), "norm_scale"),
                "lm_head/w": ((a.d, a.vocab), "head")}

    @staticmethod
    def draw(key, layer, path, shape, kind):
        k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                               zlib.crc32(path.encode()) & 0x7FFFFFFF)
        z = jax.random.normal(k, shape, jnp.float32)
        return 1.0 + 0.05 * z if kind == "norm_scale" else 0.05 * z


def test_moe_tree_is_the_serving_quantizers_tree():
    """DeepSeek-V2-Lite at the program's smoke widths with its two shared
    experts: the built tree has the leaves, shapes, dtypes and packing
    statics that the program's serving quantizer gives its own params. A
    packed leaf records its block's shape (K, N) or (E, K, N), as norm
    tweaking does; the serving quantizer records a stacked leaf's whole
    shape, blocks first."""
    from repro.configs.deepseek_v2_lite_16b import SMOKE
    from repro.core.quant.deploy import quantize_params_for_serving
    from repro.core.quant.types import QuantizedTensor
    from repro.models.transformer import init_lm

    cfg = SMOKE.replace(moe=dataclasses.replace(SMOKE.moe, n_shared=2),
                        serve_quant_group=32)
    m, moe = cfg.mla, cfg.moe
    a = _MoEArch(vocab=cfg.vocab_size, bits=cfg.serve_quant_bits,
                 group_size=32, d=cfg.d_model, heads=cfg.n_heads,
                 nope=m.qk_nope_head_dim, rope=m.qk_rope_head_dim,
                 v=m.v_head_dim, kv_lora=m.kv_lora_rank, ff=cfg.d_ff,
                 experts=moe.n_experts, expert_ff=moe.d_ff_expert,
                 shared=moe.n_shared, blocks=cfg.n_repeats)
    seed = 2**36 + 3
    built = weights.build_served(_DeepSeekLayout, a, cfg, seed)
    served = quantize_params_for_serving(
        cfg, init_lm(cfg, jax.random.PRNGKey(0)))
    is_qt = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    got = jax.tree_util.tree_flatten_with_path(built, is_leaf=is_qt)[0]
    want = jax.tree_util.tree_flatten_with_path(served, is_leaf=is_qt)[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    n_experts = 0
    for (path, g), (_, w) in zip(got, want):
        name = jax.tree_util.keystr(path)
        assert is_qt(g) == is_qt(w), name
        if not is_qt(g):
            assert (g.shape, g.dtype) == (w.shape, w.dtype), name
            continue
        for part in ("qw", "scale"):
            x, y = getattr(g, part), getattr(w, part)
            assert (x.shape, x.dtype) == (y.shape, y.dtype), (name, part)
        assert (g.bits, g.group_size, g.act_bits) == (
            w.bits, w.group_size, w.act_bits), name
        stacked = "'stack'" in name
        assert w.shape == (g.qw.shape[:1] if stacked else ()) + g.shape, name
        n_experts += len(g.shape) == 3
    assert n_experts == 3
    _assert_draws(_DeepSeekLayout, a, built, seed, jnp.dtype(cfg.param_dtype))
