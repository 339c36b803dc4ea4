"""Dense MLP (SwiGLU / GELU) and dropless top-k MoE.

Routing is dropless: every (token, expert) pair the router picks is
computed. The pairs are sorted by expert and each expert's group is
padded to whole row tiles of the grouped expert kernel, inside a static
bound of T·k + E·(bm − 1) rows, so shapes never depend on the routing and
a token's result never depends on which other tokens share its call (no
capacity, no cross-token dropping). The experts then run as one grouped
product per projection (`linear.grouped_experts`): quantized stacks on the
chip through kernels/grouped_expert_matmul, which reads only the packed
slabs of experts that received rows; float stacks (calibration) and the
CPU through `jax.lax.ragged_dot`. Results are combined with the router's
gates, renormalised over the top k unless the config says otherwise
(DeepSeek-V2 keeps the softmax probabilities), and times the config's
routed scaling factor.

The shard_map expert-parallel training dispatch (models/moe_shardmap.py)
keeps its own capacity rule; it serves float experts under a mesh only.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import TP_AXIS, lc
from repro.models.config import ModelConfig
from repro.kernels.autotune import grouped_row_tile
from repro.models.linear import (ExpertGroups, dense, grouped_experts,
                                 init_dense, init_dense_experts)


def init_mlp(cfg: ModelConfig, key, d_ff: int, d_in: int = 0) -> dict:
    d = d_in or cfg.d_model
    ks = jax.random.split(key, 3)
    p = {"wi": init_dense(ks[0], d, d_ff, bias=cfg.mlp_bias, dtype=cfg.pdtype),
         "wo": init_dense(ks[1], d_ff, d, bias=cfg.mlp_bias, dtype=cfg.pdtype)}
    if cfg.act == "silu":
        p["wg"] = init_dense(ks[2], d, d_ff, bias=cfg.mlp_bias, dtype=cfg.pdtype)
    return p


def apply_mlp(cfg: ModelConfig, p: dict, x: jax.Array,
              taps: Optional[dict] = None, tap_prefix: str = "") -> jax.Array:
    if taps is not None:
        taps[tap_prefix + "wi"] = x
        if "wg" in p:
            taps[tap_prefix + "wg"] = x
    if cfg.act == "silu":
        h = jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x)
    else:
        h = jax.nn.gelu(dense(p["wi"], x), approximate=True)
    h = lc(h, "batch", "seq", "mlp")
    if taps is not None:
        taps[tap_prefix + "wo"] = h
    # serving TP: wi/wg are column-parallel (local d_ff slice), the down
    # projection is row-parallel and reduces over the model axis
    return dense(p["wo"], h, reduce_axis=TP_AXIS if cfg.tp > 1 else None)


# ------------------------------------------------------------------- MoE

def init_moe(cfg: ModelConfig, key) -> dict:
    m = cfg.moe
    d, ff = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": init_dense(ks[0], d, m.n_experts, dtype=cfg.pdtype),
        "experts": {
            "wi": init_dense_experts(ks[1], m.n_experts, d, ff, dtype=cfg.pdtype),
            "wg": init_dense_experts(ks[2], m.n_experts, d, ff, dtype=cfg.pdtype),
            "wo": init_dense_experts(ks[3], m.n_experts, ff, d, dtype=cfg.pdtype),
        },
    }
    if m.n_shared:
        p["shared"] = init_mlp(cfg, ks[4], m.n_shared * ff)
    return p


def expert_groups(flat_e: jax.Array, n_experts: int, bm: int):
    """Lay out (token, expert) pairs in expert groups of whole `bm`-row
    tiles. flat_e: (P,) expert of each pair, `n_experts` for a pair that
    routes nowhere. Returns (ExpertGroups, dest (P,) row of each pair —
    past the buffer for the unrouted, counts (E,) real rows an expert
    got). The buffer has the static T·k + E·(bm − 1) rows, in tiles."""
    e = n_experts
    n_pairs = flat_e.shape[0]
    n_rows = -(-(n_pairs + e * (bm - 1)) // bm) * bm
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
    sizes = -(-counts // bm) * bm
    ends = jnp.cumsum(sizes)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    ex = jnp.minimum(sorted_e, e - 1)
    first = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = jnp.arange(n_pairs, dtype=jnp.int32) - first[ex].astype(jnp.int32)
    dest_sorted = jnp.where(sorted_e < e, ends[ex] - sizes[ex] + rank,
                            n_rows)
    dest = jnp.zeros((n_pairs,), jnp.int32).at[order].set(dest_sorted)
    n_tiles = n_rows // bm
    n_live = ends[-1] // bm
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * bm
    tile_e = jnp.searchsorted(ends, starts, side="right")
    # dead tiles repeat the last live tile's expert: the kernel's index
    # maps then stay on blocks it already holds
    last = tile_e[jnp.maximum(n_live - 1, 0)]
    tile_e = jnp.where(jnp.arange(n_tiles) < n_live, tile_e, last)
    groups = ExpertGroups(sizes.astype(jnp.int32),
                          jnp.minimum(tile_e, e - 1).astype(jnp.int32),
                          n_live.reshape(1).astype(jnp.int32), bm)
    return groups, dest, counts


def apply_moe(cfg: ModelConfig, p: dict, x: jax.Array,
              taps: Optional[dict] = None, tap_prefix: str = "",
              valid: Optional[jax.Array] = None):
    """x: (B, S, d). Returns (y, aux): aux["loss"] is the router's
    load-balancing loss, aux["load"] (3,) int32 the call's routing counts
    [rows routed, experts that got rows, the busiest expert's rows].

    `valid` (B, S) marks real tokens; False = padding (continuous-batching
    prefill pads, idle decode slots), which routes to no expert.
    """
    m = cfg.moe
    b, s, d = x.shape

    if cfg.moe_impl == "shard_map" and taps is None and valid is None:
        from repro.core.quant.types import QuantizedTensor
        from repro.distributed.sharding import active_mesh
        mesh = active_mesh()
        float_experts = not isinstance(p["experts"]["wi"]["w"],
                                       QuantizedTensor)
        if (mesh is not None and "model" in mesh.shape and float_experts
                and m.n_experts % mesh.shape["model"] == 0):
            dp = 1
            for a in ("pod", "data"):
                dp *= mesh.shape.get(a, 1)
            if b % dp == 0 and (b // dp) * s % mesh.shape["model"] == 0:
                from repro.models.moe_shardmap import moe_ep_shardmap
                y, loss = moe_ep_shardmap(cfg, p, x, mesh)
                return y, {"loss": loss, "load": jnp.zeros(3, jnp.int32)}
    t = b * s
    e, k = m.n_experts, m.top_k
    xf = x.reshape(t, d)

    if taps is not None:
        taps[tap_prefix + "router"] = xf

    logits = dense(p["router"], xf.astype(jnp.float32))          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                          # (T, k)
    if m.norm_topk_prob:
        gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True),
                                  1e-9)

    flat_e = idx.reshape(t * k)
    if valid is not None:
        flat_e = jnp.where(jnp.repeat(valid.reshape(t), k), flat_e, e)
    groups, dest, counts = expert_groups(flat_e, e,
                                         grouped_row_tile(t * k, e))

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    aux = m.router_aux_weight * e * jnp.sum(me * counts / t)
    load = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                      jnp.max(counts)]).astype(jnp.int32)

    # dispatch: each pair's token row at its place in its expert's group
    rows = jnp.zeros((groups.tile_expert.shape[0] * groups.bm, d), x.dtype)
    rows = rows.at[dest].set(xf[jnp.arange(t * k) // k], mode="drop")
    if taps is not None:
        # per-expert calibration inputs (E, T, d): a token's row where it
        # routes to the expert, zero elsewhere (zero rows add nothing to a
        # GPTQ Hessian)
        hit = (flat_e[None, :] == jnp.arange(e)[:, None]).reshape(e, t, k)
        taps[tap_prefix + "experts"] = jnp.where(
            hit.any(-1)[..., None], xf[None], 0).astype(x.dtype)

    ex = p["experts"]
    h = jax.nn.silu(grouped_experts(ex["wg"], rows, groups)) * \
        grouped_experts(ex["wi"], rows, groups)
    if taps is not None:
        hp = jnp.take(h, dest, axis=0, mode="fill", fill_value=0)
        taps[tap_prefix + "experts_out"] = jnp.zeros(
            (e + 1, t, h.shape[-1]), h.dtype).at[
                flat_e, jnp.arange(t * k) // k].set(hp)[:e]
    out = grouped_experts(ex["wo"], h, groups)                   # (R, d)

    # combine: each pair's expert row times its gate (unrouted pairs read
    # zero), summed over the token's k pairs
    pairs = jnp.take(out, dest, axis=0, mode="fill", fill_value=0)
    y = jnp.sum((pairs.astype(jnp.float32)
                 * gate.reshape(t * k, 1)).reshape(t, k, d), axis=1)
    y = y.astype(x.dtype).reshape(b, s, d)

    if "shared" in p:
        if taps is not None:
            sh_taps = {}
            ysh = apply_mlp(cfg, p["shared"], x, sh_taps, "")
            for kk, vv in sh_taps.items():
                taps[tap_prefix + "shared/" + kk] = vv
        else:
            ysh = apply_mlp(cfg, p["shared"], x)
        y = y + ysh
    return lc(y, "batch", "seq", "embed"), {"loss": aux, "load": load}
