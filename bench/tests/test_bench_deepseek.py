"""DeepSeek-V2-Lite's program against its plain reference at smoke size:
the engine's own prefill and decode programs, through the latent page
pool, give the reference's logits (bench/references/deepseek_v2.py) on
the tokens they chose, within a tolerance that the reference without
YaRN's mscale^2, or with renormalised gates, exceeds."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_smoke import smoke
import weights

CONFIG = "deepseek-v2-lite-w4g128"
SEED = 2**35 + 7
# largest |program - reference| logit over the positions, as a share of
# the reference logits' standard deviation. The program computes in
# bfloat16 (activations, the latent pool, the residual stream) on the
# reference's own weight grid; the reference in float32: 0.067 at this
# seed. The two departures below read 0.69 (renormalised gates) and 1.57
# (no mscale^2), so the limit sits at three times the program's reading
# and under a third of the nearer departure.
TOL = 0.2


def _program_logits(cfg, params, prompt, k):
    """Logits of the engine's prefill program at the prompt's last token,
    then of k greedy steps of its decode program (the absorbed latent
    read of the paged pool), and the tokens it chose."""
    from repro.serve import engine as E

    eng = E.ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                             page_size=8, prefill_bucket=8)
    eng.submit(prompt, max_new=k + 1)
    eng._admit(0, 0.0)                       # slot 0 and its pages
    n, pad = len(prompt), -(-len(prompt) // 8) * 8
    toks = np.zeros((1, pad), np.int32)
    pos = np.full((1, pad), -1, np.int32)
    toks[0, pad - n:], pos[0, pad - n:] = prompt, np.arange(n)
    paged = {"bt_rows": jnp.asarray(eng.pool.tables[[0]]),
             "slots": jnp.asarray([0], jnp.int32)}
    first, cache, _ = E._paged_prefill_jit(eng.cfg, eng.params,
                                           jnp.asarray(toks), eng.cache,
                                           jnp.asarray(pos), paged)
    scan = jax.jit(functools.partial(
        E._decode_scan, k_steps=k, page_size=8, temperature=0.0, top_k=0,
        with_logits=True), static_argnums=(0,))
    tok0 = int(jnp.argmax(first[0]))
    toks_k, logits_k, _, _, _ = scan(
        eng.cfg, eng.params, cache, jnp.asarray([tok0, 0], jnp.int32),
        jnp.asarray([n, 0], jnp.int32), jnp.asarray([True, False]),
        jnp.asarray(eng.pool.tables), jax.random.PRNGKey(0))
    logits = np.concatenate([np.asarray(first[:1]),
                             np.asarray(logits_k[:, 0])])
    return logits, [tok0] + [int(t) for t in np.asarray(toks_k[:, 0])]


@pytest.fixture(scope="module")
def served():
    _, ref, a, cfg = smoke(CONFIG)
    prompt = np.random.default_rng(1).integers(0, a.vocab, 13)
    logits, tokens = _program_logits(
        cfg, weights.build_served(ref, a, cfg, SEED), prompt, 8)
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)[None]
    cols = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return ref, a, logits, (seq, np.zeros_like(cols), cols)


@pytest.mark.parametrize("reference,within", [
    ("published", True),
    ("without mscale^2", False),
    ("renormalised gates", False),
])
def test_engine_logits_agree_with_the_reference(served, monkeypatch,
                                                reference, within):
    ref, a, logits, at = served
    want = np.asarray(ref.logits(a, SEED, *at))
    if reference == "without mscale^2":
        monkeypatch.setattr(ref, "softmax_scale",
                            lambda a_: 1.0 / math.sqrt(a_.nope + a_.rope))
    elif reference == "renormalised gates":
        a = dataclasses.replace(a, norm_topk=True)
    ref.block_apply.clear_cache()            # retrace with the departure
    try:
        got = np.asarray(ref.logits(a, SEED, *at))
    finally:
        monkeypatch.undo()
        ref.block_apply.clear_cache()
    assert (np.abs(logits - got).max() / want.std() <= TOL) == within
