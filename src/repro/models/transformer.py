"""Decoder-only LM (covers dense/MoE/SSM/hybrid/VLM archs).

Layer stack = unstacked `prefix` blocks + `stack` of the repeating pattern,
executed with `lax.scan` over repeats (compile-time O(|pattern|), not
O(depth)). Per-layer access for the PTQ/norm-tweak pipeline goes through
`get_block` / `set_block`, which view into the stacked arrays.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lc
from repro.models.blocks import apply_block, init_block, init_block_cache
from repro.models.config import ModelConfig
from repro.models.norms import apply_norm, init_norm
from repro.utils.tree import tree_index, tree_stack


# ----------------------------------------------------------------- init

def init_lm(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 6 + len(cfg.prefix_pattern)
                          + len(cfg.pattern) * cfg.n_repeats)
    ki = iter(range(len(ks)))
    params: dict = {
        "embed": {"w": (jax.random.normal(ks[next(ki)],
                                          (cfg.vocab_size, cfg.d_model)) * 0.02
                        ).astype(cfg.pdtype)},
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if cfg.pos_emb == "learned":
        params["pos"] = {"w": (jax.random.normal(
            ks[next(ki)], (cfg.max_position, cfg.d_model)) * 0.02
        ).astype(cfg.pdtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": (jax.random.normal(
            ks[next(ki)], (cfg.d_model, cfg.vocab_size)) * 0.02
        ).astype(cfg.pdtype)}
    prefix = {}
    for i, spec in enumerate(cfg.prefix_pattern):
        prefix[str(i)] = init_block(cfg, spec, ks[next(ki)])
    if prefix:
        params["prefix"] = prefix
    stack = {}
    for j, spec in enumerate(cfg.pattern):
        reps = [init_block(cfg, spec, ks[next(ki)]) for _ in range(cfg.n_repeats)]
        stack[f"p{j}"] = tree_stack(reps)
    params["stack"] = stack
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0, paged=None) -> dict:
    """`paged`: a serve.kvcache.PageSpec — build page-pool caches for the
    continuous-batching engine (`batch` is then the slot count)."""
    cache: dict = {}
    if cfg.prefix_pattern:
        cache["prefix"] = {
            str(i): init_block_cache(cfg, spec, batch, max_len, enc_len,
                                     paged=paged)
            for i, spec in enumerate(cfg.prefix_pattern)}
    cache["stack"] = {}
    for j, spec in enumerate(cfg.pattern):
        one = init_block_cache(cfg, spec, batch, max_len, enc_len,
                               paged=paged)
        cache["stack"][f"p{j}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.n_repeats,) + x.shape).copy() if hasattr(
                    x, "shape") else x, one)
    return cache


# ----------------------------------------------------------------- blocks

def num_blocks(cfg: ModelConfig) -> int:
    return cfg.n_layers


def has_moe(cfg: ModelConfig) -> bool:
    return any(s.mlp == "moe" for s in cfg.all_layer_specs())


def block_spec(cfg: ModelConfig, i: int):
    np_ = len(cfg.prefix_pattern)
    if i < np_:
        return cfg.prefix_pattern[i]
    return cfg.pattern[(i - np_) % len(cfg.pattern)]


def get_block(cfg: ModelConfig, params: dict, i: int) -> dict:
    np_ = len(cfg.prefix_pattern)
    if i < np_:
        return params["prefix"][str(i)]
    j = (i - np_) % len(cfg.pattern)
    r = (i - np_) // len(cfg.pattern)
    return tree_index(params["stack"][f"p{j}"], r)


def set_block(cfg: ModelConfig, params: dict, i: int, new_block: dict) -> dict:
    np_ = len(cfg.prefix_pattern)
    out = dict(params)
    if i < np_:
        out["prefix"] = dict(out["prefix"])
        out["prefix"][str(i)] = new_block
        return out
    j = (i - np_) % len(cfg.pattern)
    r = (i - np_) // len(cfg.pattern)
    key = f"p{j}"
    out["stack"] = dict(out["stack"])
    out["stack"][key] = jax.tree.map(
        lambda stacked, nb: stacked.at[r].set(nb.astype(stacked.dtype))
        if hasattr(stacked, "at") else stacked,
        out["stack"][key], new_block)
    return out


# ----------------------------------------------------------------- forward

def _embed(cfg: ModelConfig, params: dict, tokens: jax.Array,
           ext_embeds: Optional[jax.Array], positions: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"]["w"], tokens, axis=0).astype(cfg.adtype)
    if ext_embeds is not None:  # VLM: patch embeds prepended to text tokens
        x = jnp.concatenate([ext_embeds.astype(cfg.adtype), x], axis=1)
    if cfg.pos_emb == "learned":
        pe = jnp.take(params["pos"]["w"],
                      jnp.clip(positions, 0, cfg.max_position - 1), axis=0)
        x = x + pe.astype(cfg.adtype)
    return lc(x, "batch", "seq", "embed")


def _run_stack(cfg: ModelConfig, params: dict, x: jax.Array, *,
               positions: jax.Array, mode: str, cache: Optional[dict],
               enc_out: Optional[jax.Array] = None,
               paged: Optional[dict] = None):
    """Prefix blocks then scanned pattern repeats. Returns (x, new_cache,
    aux, load): aux the summed auxiliary loss, load (L_moe, 3) int32 the
    routing counts of every MoE block (see `apply_moe`).
    `paged` (block-table indices) is loop-invariant across layers — each
    block's page pool is indexed by the same per-slot tables."""
    aux_total = jnp.zeros((), jnp.float32)
    loads = []
    new_cache: dict = {} if cache is not None else None

    for i, spec in enumerate(cfg.prefix_pattern):
        c = cache["prefix"][str(i)] if cache is not None else None
        x, nc, aux = apply_block(cfg, spec, params["prefix"][str(i)], x,
                                 positions=positions, mode=mode, cache=c,
                                 enc_out=enc_out, paged=paged)
        aux_total += aux["loss"]
        loads.append(aux["load"])
        if cache is not None:
            new_cache.setdefault("prefix", {})[str(i)] = nc

    pat = cfg.pattern
    stacks = tuple(params["stack"][f"p{j}"] for j in range(len(pat)))
    cstacks = tuple(cache["stack"][f"p{j}"] if cache is not None else None
                    for j in range(len(pat)))

    def one_repeat(x, slices, cslices):
        aux_sum = jnp.zeros((), jnp.float32)
        ncs, lds = [], []
        for j, spec in enumerate(pat):
            x, nc, aux = apply_block(
                cfg, spec, slices[j], x, positions=positions, mode=mode,
                cache=cslices[j] if cslices is not None else None,
                enc_out=enc_out, paged=paged)
            aux_sum += aux["loss"]
            ncs.append(nc)
            lds.append(aux["load"])
        return x, tuple(ncs), aux_sum, jnp.concatenate(lds)

    if cfg.remat and mode == "train":
        policy = {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        }[cfg.remat_policy]
        one_repeat = jax.checkpoint(one_repeat, policy=policy)

    if cfg.scan_layers:
        def body(carry, xs):
            x, aux_sum = carry
            slices = xs[0]
            cslices = xs[1] if cache is not None else None
            x, ncs, aux, ld = one_repeat(x, slices, cslices)
            return (x, aux_sum + aux), (ncs, ld)

        (x, aux_scan), (ncs_stacked, ld_stacked) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (stacks, cstacks if cache is not None else None))
        aux_total += aux_scan
        loads.append(ld_stacked.reshape(-1, 3))
        if cache is not None:
            new_cache["stack"] = {f"p{j}": ncs_stacked[j]
                                  for j in range(len(pat))}
    else:
        for r in range(cfg.n_repeats):
            slices = tuple(tree_index(s, r) for s in stacks)
            cslices = (tuple(tree_index(c, r) for c in cstacks)
                       if cache is not None else None)
            x, ncs, aux, ld = one_repeat(x, slices, cslices)
            aux_total += aux
            loads.append(ld)
            if cache is not None:
                for j in range(len(pat)):
                    new_cache.setdefault("stack", {}).setdefault(
                        f"p{j}", []).append(ncs[j])
        if cache is not None and "stack" in new_cache:
            new_cache["stack"] = {k: tree_stack(v)
                                  for k, v in new_cache["stack"].items()}
    return x, new_cache, aux_total, jnp.concatenate(loads)


def _head(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"]["w"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
    else:
        from repro.models.linear import dense
        logits = dense(params["lm_head"], x, dtype=x.dtype).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return lc(logits.astype(jnp.float32), "batch", "seq", "vocab")


def lm_forward(cfg: ModelConfig, params: dict, tokens: jax.Array,
               ext_embeds: Optional[jax.Array] = None):
    """Full-sequence causal forward. Returns (logits f32, aux)."""
    b = tokens.shape[0]
    s = tokens.shape[1] + (ext_embeds.shape[1] if ext_embeds is not None else 0)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed(cfg, params, tokens, ext_embeds, positions)
    x, _, aux, _ = _run_stack(cfg, params, x, positions=positions,
                              mode="train", cache=None)
    return _head(cfg, params, x), aux


def lm_prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, cache: dict,
               ext_embeds: Optional[jax.Array] = None,
               positions: Optional[jax.Array] = None,
               paged: Optional[dict] = None, with_load: bool = False):
    """Prompt ingestion. Returns (last-token logits (B, V), new_cache), and
    with `with_load` the MoE routing counts (L_moe, 3) (`_run_stack`).

    `positions` (B, S) overrides the default arange for continuous batching:
    left-padded prompts mark pads with -1 (masked everywhere, routed to the
    scratch page) so the real last token stays at index -1. `paged` carries
    the target slot's block-table row (serve/kvcache.py).
    """
    b = tokens.shape[0]
    s = tokens.shape[1] + (ext_embeds.shape[1] if ext_embeds is not None else 0)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
    x = _embed(cfg, params, tokens, ext_embeds, positions)
    x, new_cache, _, load = _run_stack(cfg, params, x, positions=positions,
                                       mode="prefill", cache=cache,
                                       paged=paged)
    logits = _head(cfg, params, x[:, -1:, :])
    if with_load:
        return logits[:, 0, :], new_cache, load
    return logits[:, 0, :], new_cache


def lm_decode(cfg: ModelConfig, params: dict, tokens: jax.Array,
              cache: dict, positions: jax.Array,
              paged: Optional[dict] = None, with_load: bool = False):
    """One decode step. tokens: (B, 1); positions: (B, 1) absolute. With a
    paged cache, B is the slot count and `paged` holds per-slot write
    targets plus the block tables for gather-based reads. With
    `with_load`, also the MoE routing counts (L_moe, 3) (`_run_stack`)."""
    x = _embed(cfg, params, tokens, None, positions)
    x, new_cache, _, load = _run_stack(cfg, params, x, positions=positions,
                                       mode="decode", cache=cache,
                                       paged=paged)
    logits = _head(cfg, params, x)
    if with_load:
        return logits[:, 0, :], new_cache, load
    return logits[:, 0, :], new_cache


def lm_verify(cfg: ModelConfig, params: dict, tokens: jax.Array,
              cache: dict, positions: jax.Array, paged: dict):
    """Spec-decode verify forward: score M draft tokens per slot in one
    batched pass. tokens: (B, M) = [last emitted token, d_1..d_{M-1}];
    positions: (B, M) absolute (inactive slots -1). Runs the prefill-shaped
    stack — `paged` carries bt_rows + kv_len (fill *including* the M
    tokens) plus a "verify" marker that routes the fused small-M
    paged-attention read (gather impl needs no marker: its prefill path
    already reads the whole context). Returns (logits (B, M, V) f32,
    new_cache); row m is the next-token distribution after the prefix plus
    tokens[:, :m+1]."""
    x = _embed(cfg, params, tokens, None, positions)
    x, new_cache, _, _ = _run_stack(cfg, params, x, positions=positions,
                                    mode="prefill", cache=cache, paged=paged)
    return _head(cfg, params, x), new_cache


def lm_loss(cfg: ModelConfig, params: dict, batch: dict):
    """Next-token cross-entropy (+ MoE aux). batch: tokens, labels, [mask]."""
    logits, aux = lm_forward(cfg, params, batch["tokens"],
                             batch.get("ext_embeds"))
    labels = batch["labels"]
    # align: ext embeds (if any) prepended -> score only the token positions
    if batch.get("ext_embeds") is not None:
        logits = logits[:, batch["ext_embeds"].shape[1]:, :]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux, {"nll": loss, "aux": aux}
