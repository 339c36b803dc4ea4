"""Serving engines for (quantized) LMs.

Weights may be float or packed QuantizedTensor (the paper's deployment
format — dequant happens inside the fused Pallas matmul on TPU; both
engines accept `quant_bits=...` to pack a float tree in place via
`quantize_params_for_serving`). Decode steps present M = n_slots (or
batch) token rows per linear, which rides the decode-shaped skinny-M
kernel tiles picked by kernels/ops.py; quantized MoE experts run the
grouped expert kernel without materializing float expert stacks. Two
engines share the model code:

  * ServeEngine        — static batch: one prompt length, lockstep decode to
                         max_new. Kept as the baseline and for scoring.
  * ContinuousEngine   — continuous batching over a fixed slot pool with a
                         paged KV cache (serve/kvcache.py): requests are
                         admitted into free slots as others retire, each
                         slot decodes at its own depth, and finished
                         requests stop burning decode FLOPs. All jitted
                         shapes are static (slot count, page pool, bucketed
                         prefill lengths), so steady-state serving never
                         recompiles. Decode attention runs the fused
                         paged-attention kernel by default (block-table walk
                         + inline int8-KV dequant inside the kernel); pass
                         paged_attn="gather" for the gather->dequant->einsum
                         oracle path (see DESIGN.md "Paged-attention decode
                         kernel").

`ContinuousEngine(tp=N)` runs the whole serving step tensor-parallel over
an N-way "model" mesh: packed weights and scales are placed per the
serving TP contract (distributed/partitioning.py), the paged KV pools
shard along their kv-head dim (each device holds its head slice of every
page), and the prefill/decode jits run the model per-shard under
`shard_map` with psums at the attention/MLP output projections. Logits
come out identical on every shard (replicated lm_head), so sampling and
all host-side bookkeeping — scheduler, page budget, block tables — are
TP-invariant. See DESIGN.md "Tensor-parallel serving".

The traffic driver (Poisson arrivals, latency percentiles) lives in
launch/serve.py; admission policy lives in serve/scheduler.py.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import zlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from repro import spans
from repro.analysis.sanitize import note_trace
from repro.core.quant.types import QuantizedTensor, localize_quantized
from repro.debug_flags import debug_enabled
from repro.distributed.partitioning import (paged_pool_pspecs,
                                            serve_param_shardings,
                                            serve_tp_widths, tp_local_cfg)
from repro.distributed.sharding import TP_AXIS, sharding_ctx
from repro.models.config import ModelConfig
from repro.models.transformer import (has_moe, init_cache, lm_decode,
                                      lm_forward, lm_prefill, lm_verify)
from repro.serve.faults import FaultInjected, FaultPlan, KernelFaultInjected
from repro.serve.kvcache import (POOL_KEYS, PagePool, PageSpec,
                                 default_page_spec, pool_head_dim)
from repro.serve.sampling import (sample, spec_accept_greedy,
                                  spec_accept_sample)
from repro.serve.scheduler import Request, Scheduler


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray          # (B, max_new)
    n_prompt: int
    steps: int


@functools.partial(jax.jit, static_argnames=("cfg", "max_new", "temperature",
                                             "top_k", "eos_id"))
def _generate_jit(cfg, params, prompts, key, max_new, temperature, top_k,
                  eos_id):
    b, s = prompts.shape
    # note_trace calls sit inside jit bodies on purpose: the Python side
    # effect runs once per compilation and never on cache hits, so under
    # REPRO_SANITIZE=1 they count compiled variants (repro.analysis.sanitize)
    note_trace("generate", batch=b, prompt=s, max_new=max_new,
               temperature=temperature, top_k=top_k)
    cache = init_cache(cfg, b, s + max_new)
    logits, cache = lm_prefill(cfg, params, prompts, cache)

    def step(carry, t):
        cache, logits, key, done = carry
        key, sk = jax.random.split(key)
        tok = sample(logits, sk, temperature=temperature, top_k=top_k)
        tok = jnp.where(done, eos_id, tok)
        done = done | (tok == eos_id) if eos_id >= 0 else done
        pos = jnp.full((b, 1), s + t, jnp.int32)
        logits, cache = lm_decode(cfg, params, tok[:, None], cache, pos)
        return (cache, logits, key, done), tok

    (_, _, _, _), toks = jax.lax.scan(
        step, (cache, logits, key, jnp.zeros((b,), bool)),
        jnp.arange(max_new, dtype=jnp.int32))
    return toks.T                                              # (B, max_new)


def _maybe_quantize(cfg, params, quant_bits, quant_group, act_bits,
                    mesh=None):
    """Pack a float param tree for serving when quant_bits is set (no-op on
    already-packed trees: QuantizedTensor leaves are left untouched).
    quant_group follows the deploy convention: 0 = cfg.serve_quant_group,
    -1 = per-channel. With a mesh, packing is followed by the TP placement
    step — packed and float leaves alike are device_put per the serving
    contract instead of staying replicated."""
    from repro.core.quant.deploy import (place_params_for_serving,
                                         quantize_params_for_serving)

    if not quant_bits:
        if act_bits:
            raise ValueError("act_bits requires quant_bits (A8 tags live on "
                             "packed QuantizedTensors)")
        return place_params_for_serving(cfg, params, mesh)
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    if any(isinstance(x, QuantizedTensor) for x in leaves):
        raise ValueError("params already hold packed QuantizedTensors; "
                         "pass quant_bits=0 (re-packing is a silent no-op "
                         "and would drop the requested act_bits/group)")
    return quantize_params_for_serving(cfg, params, bits=quant_bits,
                                       group_size=quant_group,
                                       act_bits=act_bits, mesh=mesh)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, eos_id: int = -1,
                 quant_bits: int = 0, quant_group: int = 0,
                 act_bits: int = 0):
        self.cfg = cfg
        self.params = _maybe_quantize(cfg, params, quant_bits, quant_group,
                                      act_bits)
        self.eos_id = eos_id

    def generate(self, prompts: np.ndarray, *, max_new: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 key: Optional[jax.Array] = None) -> GenerateResult:
        key = key if key is not None else jax.random.PRNGKey(0)
        toks = _generate_jit(self.cfg, self.params,
                             jnp.asarray(prompts, jnp.int32), key, max_new,
                             temperature, top_k, self.eos_id)
        return GenerateResult(np.asarray(toks), prompts.shape[1], max_new)

    def score(self, tokens: np.ndarray) -> np.ndarray:
        """Per-token log-likelihoods (B, S-1)."""
        toks = jnp.asarray(tokens, jnp.int32)
        logits, _ = lm_forward(self.cfg, self.params, toks[:, :-1])
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, toks[:, 1:][..., None],
                                 axis=-1)[..., 0]
        return np.asarray(ll - lse)


# ------------------------------------------------------- continuous batching

# The MoE routing counts of every step of every ContinuousEngine in this
# process, newest last, one entry per prefill call and per decode block:
# (engine serial, the step's `now`, "prefill" | "decode", MoE layer calls,
# rows routed, experts that got rows, the busiest expert's rows summed
# over the calls). It outlives the engine, so a benchmark can read the
# counts of a window of steps after it has freed the engine. It goes, with
# `_ENGINE_SERIAL`, once the serving benchmark takes the window's deltas
# of the `n_moe_*` counters at the window's edges, as it already does for
# `n_decode_steps`: they hold the same counts.
MOE_LOG: collections.deque = collections.deque(maxlen=1 << 16)
_ENGINE_SERIAL = itertools.count()


def _params_sig(params) -> str:
    """Coarse weight signature for sanitizer trace keys: the quantized
    bit-widths present in the tree ("w2", "w4/8"), or "f32". Target and
    draft params reach the same jits with different leaf shapes — without
    this in the key, their two legitimate compilations would read as one
    variant traced twice (a false budget violation)."""
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    bits = sorted({x.bits for x in leaves if isinstance(x, QuantizedTensor)})
    return "w" + "/".join(map(str, bits)) if bits else "f32"


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def _paged_prefill_jit(cfg, params, tokens, cache, positions, paged):
    note_trace("paged_prefill", batch=tokens.shape[0],
               bucket=tokens.shape[1], impl=cfg.paged_attn_impl,
               w=_params_sig(params))
    return lm_prefill(cfg, params, tokens, cache, positions=positions,
                      paged=paged, with_load=True)


@functools.partial(jax.jit, static_argnames=("temperature", "top_k"))
def _sample_first_jit(logits, first_key, rids, *, temperature, top_k):
    """Per-request first-token sampling: logits (B, V), request ids (B,).

    Each row draws from its own key, `first_key` folded with the row's
    request id, so the result does not depend on how admitted requests
    were grouped into prefill batches — the same seed gives the same
    tokens at prefill_batch=1 and prefill_batch=8. Also returns the
    per-row isfinite sentinel so a prompt whose prefill produced
    non-finite logits is quarantined before it ever enters the decode
    set."""
    note_trace("sample_first", batch=logits.shape[0],
               temperature=temperature, top_k=top_k)
    keys = jax.vmap(lambda r: jax.random.fold_in(first_key, r))(rids)
    toks = jax.vmap(lambda l, k: sample(l[None], k, temperature=temperature,
                                        top_k=top_k)[0])(logits, keys)
    return toks, jnp.all(jnp.isfinite(logits), axis=-1)


# ------------------------------------------------------ KV spill / restore
#
# Preemption moves a victim slot's exclusively-owned live pages to host RAM
# and back. Both directions walk the whole cache tree and touch only the
# paged pool leaves (k/v pools + their int8 scale pools), indexing each
# along its page axis — (P, ps, KVH[, hd]) unstacked, (L, P, ...) for the
# scan-stacked layer dim — so one call moves every layer's slice of the
# spilled pages at once. Page-count shapes are pow2-padded by the engine
# (pad entries target the scratch page, which is never read) to bound the
# number of compiled shapes.

def _pool_page_axis(key: str, ndim: int) -> int:
    """Page axis of a paged pool leaf: two dims left of the kv-head dim
    (pool layout ... P, page_size, KVH[, hd])."""
    return pool_head_dim(key, ndim) - 2


def _tree_checksum(tree) -> int:
    """crc32 over every array leaf of a (nested-dict) host tree, walked in
    sorted-key order so the digest is layout-stable. Cheap enough to run on
    every spill (host RAM bandwidth, no device sync) and catches the
    corruption class that matters: bytes flipped while a snapshot sits in
    host memory awaiting restore."""
    crc = 0
    if isinstance(tree, dict):
        for k in sorted(tree):
            crc = zlib.crc32(str(k).encode(), crc)
            crc = zlib.crc32(_tree_checksum(tree[k]).to_bytes(4, "little"),
                             crc)
        return crc
    if tree is None:
        return 0
    arr = np.ascontiguousarray(np.asarray(tree))
    return zlib.crc32(arr.tobytes(), zlib.crc32(str(arr.dtype).encode()))


def _corrupt_first_leaf(tree):
    """Flip one byte of the first array leaf (sorted-key walk) — the
    spill_corrupt fault's payload damage. Returns (new_tree, corrupted)."""
    if isinstance(tree, dict):
        out, hit = {}, False
        for k in sorted(tree):
            if hit:
                out[k] = tree[k]
            else:
                out[k], hit = _corrupt_first_leaf(tree[k])
        # preserve original (insertion) key order of the input dict
        return {k: out[k] for k in tree}, hit
    if tree is None:
        return tree, False
    arr = np.asarray(tree).copy()
    flat = arr.view(np.uint8).reshape(-1)
    flat[0] ^= 0xFF
    return arr, True


@jax.jit
def _spill_gather_jit(cache, idx):
    """Gather pages `idx` (P,) from every pool leaf -> host-bound tree
    with a leading/inner page dim of len(idx); non-pool leaves drop."""
    note_trace("spill_gather", pages=idx.shape[0])

    def walk(tree, key=None):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if key in POOL_KEYS:
            return jnp.take(tree, idx, axis=_pool_page_axis(key, tree.ndim))
        return None
    return walk(cache)


@functools.partial(jax.jit, donate_argnames=("cache",))
def _spill_scatter_jit(cache, idx, host):
    """Scatter a spill snapshot back: write host[...] into pages `idx` of
    every pool leaf (inverse of _spill_gather_jit)."""
    note_trace("spill_scatter", pages=idx.shape[0])

    def walk(tree, htree, key=None):
        if isinstance(tree, dict):
            return {k: walk(v, htree[k], k) for k, v in tree.items()}
        if key in POOL_KEYS and htree is not None:
            ax = _pool_page_axis(key, tree.ndim)
            loc = (slice(None),) * ax + (idx,)
            return tree.at[loc].set(htree.astype(tree.dtype))
        return tree
    return walk(cache, host)


def _decode_scan(cfg, params, cache, last_tok, cur_len, active,
                 block_table, key, *, k_steps, page_size,
                 temperature, top_k, with_logits=False, poison=None):
    """K fused decode steps over all slots with on-device sampling.

    One dispatch and one host sync per K tokens — the per-step Python/
    transfer overhead of a step-at-a-time loop would otherwise rival the
    model compute. Slots whose request finishes mid-block keep stepping;
    their extra writes fall off the block table onto the scratch page and
    the host drops the surplus tokens. Returns ((K, S) tokens, (K, S)
    alive, cache) — or ((K, S) tokens, (K, S, V) logits, (K, S) alive,
    cache) under `with_logits`, for the speculative draft whose
    temperature>0 acceptance rule needs the distribution each proposal was
    sampled from.

    The alive mask is the graceful-degradation sentinel: one cheap (S,)
    isfinite reduction over each step's logits. A slot whose logits go
    non-finite is *deactivated inside the scan* — its token freezes, its
    fill count stops, and its rows stop feeding the model on later steps
    of the same block. The host reads alive, drops the garbage token, and
    quarantines the request.
    `poison` (S,) bool is the fault-injection hook: marked slots get NaN
    logits on the first step, exercising exactly the real failure path.

    Shared by the single-device jit and the shard_map TP jit below — under
    TP, `cfg` is the head-localized per-shard view and `params`/`cache`
    are the shard-local slices (tokens, lengths, tables, key replicated).
    An MoE model's scan also returns each step's routing counts, (K,
    L_moe, 3) (`apply_moe`), ahead of the cache; other models return None
    there.
    """
    moe = has_moe(cfg)
    n_slots, max_pages = block_table.shape
    sl = jnp.arange(n_slots)
    if poison is None:
        poison = jnp.zeros(n_slots, bool)

    def body(carry, first):
        cache, tok, clen, key, alive = carry
        act = active & alive
        key, sk = jax.random.split(key)
        page_idx = jnp.clip(clen // page_size, 0, max_pages - 1)
        paged = {
            "block_table": block_table,
            "write_page": jnp.where(
                act, jnp.maximum(block_table[sl, page_idx], 0), 0),
            "write_off": jnp.where(act, clen % page_size, 0),
            "kv_len": jnp.where(act, clen + 1, 0),
        }
        pos = jnp.where(act, clen, 0)[:, None]
        out = lm_decode(cfg, params, tok[:, None], cache, pos, paged=paged,
                        with_load=moe)
        logits, cache = out[:2]
        load = out[2] if moe else None
        logits = jnp.where((first & poison)[:, None],
                           jnp.float32(jnp.nan).astype(logits.dtype), logits)
        # sentinel: a slot dies the step its logits stop being finite
        # (inactive slots read garbage rows — only active ones can die)
        alive = alive & (jnp.all(jnp.isfinite(logits), axis=-1) | ~act)
        nxt = sample(logits, sk, temperature=temperature, top_k=top_k)
        keep = act & alive
        tok = jnp.where(keep, nxt, tok)
        clen = clen + keep.astype(clen.dtype)
        return (cache, tok, clen, key, alive), (
            (nxt, logits, alive, load) if with_logits
            else (nxt, alive, load))

    first = jnp.zeros(k_steps, bool).at[0].set(True)
    (cache, _, _, _, _), ys = jax.lax.scan(
        body, (cache, last_tok, cur_len, key, jnp.ones(n_slots, bool)),
        first, length=k_steps)
    return ys + (cache,)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "k_steps", "page_size",
                                    "temperature", "top_k"),
                   donate_argnames=("cache",))
def _paged_decode_scan_jit(cfg, params, cache, last_tok, cur_len, active,
                           block_table, key, poison, *, k_steps, page_size,
                           temperature, top_k):
    note_trace("paged_decode_scan", k=k_steps, slots=block_table.shape[0],
               width=block_table.shape[1], temperature=temperature,
               top_k=top_k, impl=cfg.paged_attn_impl, w=_params_sig(params))
    return _decode_scan(cfg, params, cache, last_tok, cur_len, active,
                        block_table, key, k_steps=k_steps,
                        page_size=page_size, temperature=temperature,
                        top_k=top_k, poison=poison)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "k_steps", "page_size",
                                    "temperature", "top_k"),
                   donate_argnames=("cache", "draft_cache"))
def _spec_block_jit(cfg, params, draft_params, cache, draft_cache, last_tok,
                    cur_len, active, block_table, key, *, k_steps, page_size,
                    temperature, top_k):
    """One fused speculative round: draft-propose, target-verify, accept.

    The low-bit draft runs k_steps+1 autoregressive decode steps from the
    shared `last_tok`/`cur_len` state — step i writes the K/V of the token
    it was fed, so after the extra step the draft cache is complete through
    position cur_len + k_steps whatever prefix the target accepts (rollback
    is then free: rejected-tail entries sit beyond the advanced fill count
    and are masked by construction until overwritten). The last proposal is
    discarded; d_1..d_k plus the pending last token form the (S, k+1)
    verify batch the target scores in a single prefill-shaped forward
    (fused small-M page walk — see kernels/paged_attention.py). Greedy
    acceptance emits only target argmaxes, so the stream is bit-identical
    to target-only decode; temperature>0 uses residual resampling.

    Returns (out (S, M) tokens, n_emit (S,), cache, draft_cache) — slot s
    emits out[s, :n_emit[s]].
    """
    n_slots = block_table.shape[0]
    note_trace("spec_block", k=k_steps, slots=n_slots,
               width=block_table.shape[1], temperature=temperature,
               top_k=top_k, impl=cfg.paged_attn_impl,
               w=_params_sig(params), dw=_params_sig(draft_params))
    kd, kv = jax.random.split(key)
    m = k_steps + 1
    draft = _decode_scan(cfg, draft_params, draft_cache, last_tok, cur_len,
                         active, block_table, kd, k_steps=m,
                         page_size=page_size, temperature=temperature,
                         top_k=top_k, with_logits=(temperature > 0.0))
    if temperature > 0.0:
        draft_toks, draft_logits, _, _, draft_cache = draft
    else:
        (draft_toks, _, _, draft_cache), draft_logits = draft, None
    # verify rows: [last_tok, d_1..d_k] at absolute positions cur_len..
    # cur_len+k (inactive slots parked at -1 / kv_len 0 — their writes land
    # on the scratch page and their rows read as garbage we never emit)
    x = jnp.concatenate([last_tok[:, None], draft_toks[:m - 1].T], axis=1)
    positions = jnp.where(
        active[:, None],
        cur_len[:, None] + jnp.arange(m, dtype=cur_len.dtype)[None, :], -1)
    paged = {"bt_rows": block_table,
             "slots": jnp.arange(n_slots, dtype=jnp.int32),
             "kv_len": jnp.where(active, cur_len + m, 0),
             "verify": jnp.int32(1)}
    logits, cache = lm_verify(cfg, params, x, cache, positions, paged)
    if temperature > 0.0:
        out, n_emit = spec_accept_sample(
            logits, draft_logits[:m - 1].transpose(1, 0, 2), x[:, 1:], kv,
            temperature=temperature, top_k=top_k)
    else:
        out, n_emit = spec_accept_greedy(logits, x[:, 1:])
    return out, jnp.where(active, n_emit, 0), cache, draft_cache


# ------------------------------------------------- tensor-parallel variants
#
# The TP jits wrap the same model code in a shard_map over the serving
# mesh: params/caches enter with their placement specs (shard-local heads
# and mlp slices inside), everything host-shaped — tokens, positions,
# lengths, block tables, RNG keys — is replicated, and the outputs are
# replicated logits/tokens plus the re-sharded cache. Row-parallel psums
# inside the model (cfg.tp > 1) make per-shard activations exact, so every
# shard samples the same token from the same key — no token collective.
# QuantizedTensor statics are re-localized at body entry because shard_map
# splits the qw/scale children but not the recorded (K, N).

def _tp_in_specs(cfg, mesh, params, cache, paged):
    rep = PartitionSpec()
    pspecs = serve_param_shardings(mesh, cfg, params, specs_only=True)
    cspecs = paged_pool_pspecs(cache, mesh, axis=TP_AXIS)
    paged_specs = jax.tree.map(lambda _: rep, paged)
    return pspecs, cspecs, paged_specs


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnames=("cache",))
def _paged_prefill_tp_jit(cfg, mesh, params, tokens, cache, positions, paged):
    note_trace("paged_prefill_tp", batch=tokens.shape[0],
               bucket=tokens.shape[1], tp=cfg.tp, impl=cfg.paged_attn_impl,
               w=_params_sig(params))
    lcfg = tp_local_cfg(cfg)
    rep = PartitionSpec()
    pspecs, cspecs, paged_specs = _tp_in_specs(cfg, mesh, params, cache, paged)

    def body(params, tokens, cache, positions, paged):
        params = localize_quantized(params)
        with sharding_ctx(None):   # no nested GSPMD constraints under shard_map
            return lm_prefill(lcfg, params, tokens, cache,
                              positions=positions, paged=paged)

    return shard_map(body, mesh=mesh,
                     in_specs=(pspecs, rep, cspecs, rep, paged_specs),
                     out_specs=(rep, cspecs), check_rep=False)(
        params, tokens, cache, positions, paged)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "mesh", "k_steps", "page_size",
                                    "temperature", "top_k"),
                   donate_argnames=("cache",))
def _paged_decode_scan_tp_jit(cfg, mesh, params, cache, last_tok, cur_len,
                              active, block_table, key, poison, *, k_steps,
                              page_size, temperature, top_k):
    note_trace("paged_decode_scan_tp", k=k_steps,
               slots=block_table.shape[0], width=block_table.shape[1],
               tp=cfg.tp, temperature=temperature, top_k=top_k,
               impl=cfg.paged_attn_impl, w=_params_sig(params))
    lcfg = tp_local_cfg(cfg)
    rep = PartitionSpec()
    pspecs, cspecs, _ = _tp_in_specs(cfg, mesh, params, cache, {})

    def body(params, cache, last_tok, cur_len, active, block_table, key,
             poison):
        params = localize_quantized(params)
        with sharding_ctx(None):
            toks, alive, _, cache = _decode_scan(
                lcfg, params, cache, last_tok, cur_len, active, block_table,
                key, k_steps=k_steps, page_size=page_size,
                temperature=temperature, top_k=top_k, poison=poison)
            return toks, alive, cache

    return shard_map(body, mesh=mesh,
                     in_specs=(pspecs, cspecs, rep, rep, rep, rep, rep, rep),
                     out_specs=(rep, rep, cspecs), check_rep=False)(
        params, cache, last_tok, cur_len, active, block_table, key, poison)


class ContinuousEngine:
    """Slot-stepping execution core for continuous batching.

    Holds the paged cache, the per-slot host state (fill depth, last token),
    and the jitted prefill/decode steps. Admission policy and request
    bookkeeping are delegated to serve/scheduler.py. One `step()`:

      1. retire-then-admit: the scheduler maps queued requests onto free
         slots (FIFO; whole-budget page allocation minus any prefix-cache
         hit — see below);
      2. slots still ingesting their prompt advance by one prefill chunk —
         jitted calls batched per chunk-length bucket (pow2 batch sizes,
         capped at `prefill_batch`) that scatter K/V into the admitted
         slots' pages while every other slot's cache state is untouched;
         a slot whose prompt completes samples its first token and joins
         the decode set;
      3. one fused block of `decode_block` lockstep decode steps over all
         slots (a device-side lax.scan with on-device sampling — one
         dispatch and one host sync per K tokens). Idle and mid-prefill
         slots write to the scratch page and are masked; slots finishing
         mid-block overshoot onto the scratch page and the surplus tokens
         are dropped.

    `precompile_prefill=True` compiles, at the first prefill of each
    (bucket, has-context, width) key, the programs of every pow2 batch
    size up to `prefill_batch`: requests warmed one at a time then warm
    every co-batched shape, and no prefill compiles mid-serve once each
    key has been seen (at up to log2(prefill_batch) + 1 times the prefill
    compile time).

    `prefix_share=True` turns on the pool's prefix cache: a prompt whose
    full-page prefix was already prefilled by an earlier request reuses
    those pages by reference and prefills only the unshared suffix.
    `chunked_prefill=N` caps each prefill call at N tokens (rounded to a
    page multiple), spreading a long prompt across `step()` ticks so
    decode slots keep stepping instead of stalling behind it. Both
    features need the gathered-context prefill read path and per-page
    prompt state, so they cover attention decoders, latent (MLA) ones
    included, and no SSM state. See DESIGN.md "Prefix cache & chunked
    prefill".

    `spec_decode=True` swaps the decode block for self-speculative
    rounds: a W2/W3 draft packed from the *same* params proposes up to
    `spec_k` tokens per slot, the target verifies them in one fused
    (S, k+1)-row forward, and each slot emits its accepted prefix — the
    greedy stream is bit-identical to target-only decode, only the number
    of target forwards changes. The draft KV rides a second cache over
    the same PageSpec/block tables. See DESIGN.md "Self-speculative
    decoding".

    `preempt=True` arms overload discipline: `submit(..., priority=1)`
    marks batch-class work, and when an interactive request cannot be
    admitted the scheduler evicts a batch victim — the engine spills the
    victim's exclusively-owned live KV pages to host RAM (shared prefix
    pages stay resident by reference), frees its slot, and restores it
    later by re-stitching the block table and scattering the spilled
    pages back, resuming the token stream exactly where it stopped (a
    new `preempted` lifecycle state beside prefilling/decoding;
    `age_promote` bounds batch starvation). See DESIGN.md "Overload &
    preemption".

    `prefill_bucket` trades compile count for pad waste: prompts are
    left-padded (pos = -1, masked everywhere) up to the next multiple.
    Bucket 1 reproduces the static engine's unpadded prefill bit-for-bit.
    `decode_block` trades admission latency (new arrivals wait for the
    current block) against per-token dispatch overhead.
    """

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 max_len: int = 512, page_size: int = 16,
                 n_pages: Optional[int] = None, eos_id: int = -1,
                 prefill_bucket: int = 16, prefill_batch: int = 8,
                 precompile_prefill: bool = False, decode_block: int = 8,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 quant_bits: int = 0, quant_group: int = 0,
                 act_bits: int = 0, paged_attn: Optional[str] = None,
                 prefix_share: bool = False, chunked_prefill: int = 0,
                 tp: int = 1, mesh=None, spec_decode: bool = False,
                 draft_bits: int = 2, spec_k: int = 4,
                 preempt: bool = False,
                 age_promote: Optional[float] = None,
                 faults: Optional[FaultPlan] = None):
        if cfg.enc_dec:
            raise NotImplementedError("paged serving covers decoder-only LMs")
        if mesh is not None and tp == 1:
            tp = int(mesh.shape.get(TP_AXIS, 1))
        if tp > 1:
            specs = cfg.all_layer_specs()
            if any(s.kind != "attn" for s in specs) or \
                    any(s.mlp == "moe" for s in specs):
                # EP-sharded MoE serving and SSM-state sharding are open
                # items (ROADMAP) — the placement contract below only
                # covers dense attention decoders
                raise NotImplementedError(
                    "tensor-parallel serving covers dense attention "
                    "decoders (no MoE, no SSM blocks)")
            if tp not in serve_tp_widths(cfg):
                raise ValueError(
                    f"tp={tp} is illegal for {cfg.name}: GQA head groups "
                    f"must stay whole per shard and d_ff must split evenly "
                    f"— legal widths {serve_tp_widths(cfg)}")
            if mesh is None:
                devs = jax.devices()
                if len(devs) < tp:
                    raise ValueError(f"tp={tp} needs {tp} devices, have "
                                     f"{len(devs)} (on CPU force more with "
                                     f"XLA_FLAGS=--xla_force_host_platform_"
                                     f"device_count=N)")
                mesh = jax.sharding.Mesh(np.asarray(devs[:tp]), (TP_AXIS,))
            if int(mesh.shape.get(TP_AXIS, 1)) != tp:
                raise ValueError(f"mesh axis {TP_AXIS!r} has size "
                                 f"{mesh.shape.get(TP_AXIS)} != tp={tp}")
            cfg = cfg.replace(tp=tp)
        self.tp = tp
        self.mesh = mesh if tp > 1 else None
        self.preempt = bool(preempt)
        if self.preempt:
            has_ssm = any(spec.kind != "attn"
                          for spec in cfg.all_layer_specs())
            if has_ssm or cfg.attention == "mla":
                # SSM recurrence state is slot-indexed, not page-addressed
                # (a spill snapshot of pages misses it); spilling and
                # restoring MLA's latent pools has no test yet
                raise NotImplementedError(
                    "preempt covers attention-only decoders "
                    "(no SSM blocks, no MLA)")
            if tp > 1:
                raise NotImplementedError(
                    "preempt + tensor-parallel serving is an open item "
                    "(spill must gather per-shard kv-head slices)")
            if spec_decode:
                raise NotImplementedError(
                    "preempt + spec_decode is an open item (the draft "
                    "cache would need spilling in lockstep)")
        if prefix_share or chunked_prefill:
            if any(spec.kind != "attn" for spec in cfg.all_layer_specs()):
                # SSM state is not page-addressed (a shared page carries no
                # recurrence state) — silently wrong, so refuse up front
                raise NotImplementedError(
                    "prefix_share/chunked_prefill cover attention decoders "
                    "(no SSM blocks)")
        if paged_attn is not None:
            # per-engine override of the decode attention path: "fused"
            # (paged-attention kernel) or "gather" (oracle). Threaded via
            # the config because the dispatch lives in models/attention.py.
            if paged_attn not in ("fused", "gather"):
                raise ValueError(f"paged_attn must be 'fused' or 'gather', "
                                 f"got {paged_attn!r}")
            cfg = cfg.replace(paged_attn_impl=paged_attn)
        self.spec_decode = bool(spec_decode)
        self.spec_k = spec_k
        self.draft_bits = draft_bits
        if self.spec_decode:
            specs = cfg.all_layer_specs()
            if (any(s.kind != "attn" for s in specs)
                    or cfg.attention == "mla"):
                raise NotImplementedError(
                    "spec_decode covers attention-only decoders: the "
                    "verify forward rides the paged gathered/fused read "
                    "(no SSM recurrence rewind, no MLA latent prefill)")
            if any(s.mlp == "moe" for s in specs):
                # routing is per token (dropless), but the grouped expert
                # kernel's row tile, and with it its K blocking and f32
                # summation order, follows a call's row count: an (S, M)
                # verify batch can round an expert product differently
                # from M single-token decode steps, and greedy speculation
                # would no longer be bit-identical to plain decode. No
                # verify-against-decode parity test covers MoE yet
                raise NotImplementedError(
                    "spec_decode does not cover MoE")
            if tp > 1 or prefix_share:
                raise NotImplementedError(
                    "spec_decode is single-device and unshared for now "
                    "(no tp>1, no prefix_share)")
            if draft_bits not in (2, 3):
                raise ValueError(f"draft_bits must be 2 or 3 (a draft at "
                                 f"the target's own width buys nothing), "
                                 f"got {draft_bits}")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.cfg = cfg
        self.params = _maybe_quantize(cfg, params, quant_bits, quant_group,
                                      act_bits, mesh=self.mesh)
        if self.spec_decode:
            # the draft is the *same* params requantized harder — W2/W3
            # packed sub-byte (kernels/dequant_matmul.py unpacks inline),
            # so it adds ~bits/16 of the bf16 footprint, no second model
            from repro.core.quant.deploy import quantize_params_for_serving
            leaves = jax.tree_util.tree_leaves(
                params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
            if any(isinstance(x, QuantizedTensor) for x in leaves):
                raise ValueError(
                    "spec_decode requantizes the float params into the "
                    "draft; pass float params (+ quant_bits for the "
                    "target), not a pre-packed tree")
            self.draft_params = quantize_params_for_serving(
                cfg, params, bits=draft_bits, group_size=quant_group)
        self.n_slots = n_slots
        self.eos_id = eos_id
        self.prefill_bucket = max(1, prefill_bucket)
        # co-batched prefills are exact for every architecture: MoE
        # routing is dropless and per token (see DESIGN.md), so a request's
        # tokens do not depend on which requests share its prefill call
        self.prefill_batch = max(1, prefill_batch)
        self.precompile_prefill = bool(precompile_prefill)
        self._prefill_keys: set = set()   # (bucket, has_ctx, width) compiled
        self.decode_block = max(1, decode_block)
        self.temperature = temperature
        self.top_k = top_k
        self.prefix_share = bool(prefix_share)
        # chunk sizes are page-aligned so every chunk boundary (and every
        # shared-prefix handoff) starts exactly at a page start
        self.chunk_tokens = (max(1, chunked_prefill // page_size) * page_size
                             if chunked_prefill else 0)
        if n_pages is None:
            self.spec = default_page_spec(n_slots, max_len, page_size)
        else:
            self.spec = PageSpec(n_pages=n_pages, page_size=page_size,
                                 max_pages=-(-max_len // page_size))
        self.pool = PagePool(self.spec, n_slots,
                             prefix_cache=self.prefix_share)
        self.sched = Scheduler(n_slots, self.pool,
                               prefix_share=self.prefix_share, tp=self.tp,
                               age_promote=age_promote,
                               preempt_hook=(self._spill_slot
                                             if self.preempt else None))
        self.cache = init_cache(cfg, n_slots, self.spec.max_len,
                                paged=self.spec)
        if self.spec_decode:
            # draft KV rides the same PagePool geometry (identical block
            # tables / scratch page / kv_cache_bits) in its own pools —
            # one allocator decision covers both caches, and the fused
            # verify read sees the same page walk either way
            self.draft_cache = init_cache(cfg, n_slots, self.spec.max_len,
                                          paged=self.spec)
        if self.tp > 1:
            # shard every paged pool along its kv-head dim; page axes stay
            # whole on purpose (the scheduler's page budget must be
            # shard-invariant — asserted below)
            self.cache = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                self.cache, paged_pool_pspecs(self.cache, self.mesh,
                                              axis=TP_AXIS))
            self._assert_tp_placement()
        # host mirrors are int32 end-to-end: every jit consumes int32, so an
        # int64 mirror would silently truncate at the cast boundary — keep
        # the dtypes aligned and the geometry provably in range
        assert self.spec.max_len < np.iinfo(np.int32).max, \
            "per-slot capacity overflows the int32 host/jit length contract"
        self.cur_len = np.zeros(n_slots, np.int32)   # tokens in cache per slot
        self.last_tok = np.zeros(n_slots, np.int32)  # next token to feed
        self.active = np.zeros(n_slots, bool)
        self._prefilling: dict[int, Request] = {}    # slot -> mid-prompt req
        self._key, self._first_key = jax.random.split(jax.random.PRNGKey(seed))
        self._next_rid = 0
        # the virtual clock is engine state (not a run()-local counter) so
        # a snapshot/restore resumes arrival gating mid-trace; run() keeps
        # ticking it from wherever the restore left it
        self.t = 0
        self.n_steps_total = 0       # step() call count — fault step index
        # ------------------------------------------------ fault tolerance
        self.faults = faults         # FaultPlan consumed by _apply_faults
        # snapshot at construction (tests toggle it per-instance); the
        # env is read through the debug_flags funnel, never directly
        self.debug = debug_enabled()
        self.n_kernel_fallbacks = 0  # fused -> gather decode retries
        self.n_spill_corruptions = 0     # corruption faults injected
        self.n_spill_checksum_fails = 0  # ... caught at restore time
        self.n_nonfinite = 0         # slots the isfinite sentinel killed
        self.n_faults_applied = 0    # total injected-fault firings
        self._poison_slots: set[int] = set()  # NaN-inject at next decode
        self._kernel_fault = False   # fail the next fused decode dispatch
        self._spill_corrupt = False  # corrupt the next spill payload
        # pages pinned by pool_exhaust faults: [release_step, [pages]]
        self._fault_holds: list[list] = []
        self.n_decode_steps = 0
        self.n_decode_tokens = 0     # tokens emitted by decode blocks/rounds
        # KV pages the occupied slots hold, and those holding live tokens,
        # summed at the end of every step: their ratio is the share of the
        # reserved pool that holds data
        self.n_pages_held = 0
        self.n_pages_live = 0
        self.n_prefills = 0
        self.n_prefill_tokens = 0    # real prompt tokens actually prefilled
        # MoE routing, summed over every MoE layer call of every prefill
        # and decode step: calls, (token, expert) rows routed, experts
        # that received rows, and the busiest expert's rows
        self.n_moe_calls = 0
        self.n_moe_rows = 0
        self.n_moe_experts_hit = 0
        self.n_moe_busiest_rows = 0
        self._moe = has_moe(cfg)
        self._moe_pending: list = []   # (phase, now, device counts)
        self.serial = next(_ENGINE_SERIAL)
        self.n_shared_tokens = 0     # prompt tokens served from the prefix cache
        # preemption accounting: pages actually moved (kept-by-reference
        # shared pages never count — spill must not duplicate them)
        self.n_spilled_pages = 0     # owned live pages copied to host RAM
        self.n_restored_pages = 0    # pages scattered back on re-admission
        # speculative-decoding acceptance accounting (spec_stats())
        self.n_spec_rounds = 0       # fused draft+verify dispatches
        self.n_draft_tokens = 0      # draft proposals across active slots
        self.n_spec_emitted = 0      # tokens emitted by spec rounds
        self.spec_accept_sum = np.zeros(n_slots, np.int64)   # per-slot n_emit
        self.spec_round_count = np.zeros(n_slots, np.int64)  # per-slot rounds
        # requests _emit stamped during the current step (run() restamps
        # them with its clock once the step has returned)
        self._stamped: list[Request] = []

    # -------------------------------------------------------- TP placement
    _TP_COL = ("attn/wq/w", "attn/wk/w", "attn/wv/w", "attn/wukv/w",
               "mlp/wi/w", "mlp/wg/w")
    _TP_ROW = ("attn/wo/w", "mlp/wo/w")

    def _iter_param_leaves(self):
        def walk(tree, prefix):
            if isinstance(tree, QuantizedTensor):
                yield prefix + "#qw", tree.qw
                yield prefix + "#scale", tree.scale
            elif isinstance(tree, dict):
                for k, v in tree.items():
                    yield from walk(v, f"{prefix}/{k}" if prefix else k)
            else:
                yield prefix, tree

        yield from walk(self.params, "")

    def _iter_cache_leaves(self):
        def walk(tree, key=None):
            if isinstance(tree, dict):
                for k, v in tree.items():
                    yield from walk(v, k)
            else:
                yield key, tree

        yield from walk(self.cache)

    @staticmethod
    def _shard_shape(leaf):
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            return tuple(leaf.shape)
        return tuple(sh.shard_shape(leaf.shape))

    def _tp_exempt_replicated(self, path, leaf) -> bool:
        """The one projection leaf legitimately replicated under TP: a
        per-channel (1, N) scale of a row-parallel weight — every K shard
        needs the whole output-channel row. Shared by the placement assert
        and the report so they can never disagree."""
        base = path.rsplit("#", 1)[0]
        return (path.endswith("#scale") and leaf.shape[-2] == 1
                and any(base.endswith(t) for t in self._TP_ROW))

    def _assert_tp_placement(self) -> None:
        """Verify the placement contract on the live buffers, not on specs:
        every attention/MLP projection leaf — packed qw AND scale included —
        is sharded over the model axis, and every paged pool leaf holds only
        its kv-head slice per shard while the page geometry stays global
        (the scheduler's whole-budget page gating is therefore TP-invariant
        by construction). Raises with an actionable message instead of
        serving silently replicated weights."""
        bad = []
        for path, leaf in self._iter_param_leaves():
            base = path.rsplit("#", 1)[0]
            if not any(base.endswith(t) for t in self._TP_COL + self._TP_ROW):
                continue
            if self._tp_exempt_replicated(path, leaf):
                continue
            if self._shard_shape(leaf) == tuple(leaf.shape):
                bad.append(path)
        if bad:
            raise ValueError(
                f"tp={self.tp}: projection leaves stayed replicated: {bad}. "
                f"For grouped quantization every shard must hold whole scale "
                f"groups — pick a group_size dividing K/tp, or per-channel "
                f"(group_size=-1)")
        for key, leaf in self._iter_cache_leaves():
            if key not in POOL_KEYS:
                continue
            hdim = pool_head_dim(key, leaf.ndim)
            shard = self._shard_shape(leaf)
            assert (shard[:hdim] == tuple(leaf.shape[:hdim])
                    and shard[hdim + 1:] == tuple(leaf.shape[hdim + 1:])), \
                f"{key}: page geometry must be identical on every shard"
            if leaf.shape[hdim] % self.tp == 0:
                assert shard[hdim] * self.tp == leaf.shape[hdim], \
                    f"{key}: kv-head dim left replicated under tp={self.tp}"

    def tp_placement_report(self) -> dict:
        """Per-device placement summary: bytes each device holds for params
        and paged KV pools, plus any quantized/pool leaves left replicated.
        Drives benchmarks/tp_serve_bench.py's modeled per-device HBM and the
        TP test suite's no-replicated-leaves assertion."""
        def nbytes(shape, dtype):
            return int(np.prod(shape)) * np.dtype(dtype).itemsize

        rep = {"tp": self.tp,
               "params": {"global_bytes": 0, "per_device_bytes": 0},
               "kv": {"global_bytes": 0, "per_device_bytes": 0},
               "replicated_quant_leaves": [],
               "replicated_pool_leaves": []}
        for path, leaf in self._iter_param_leaves():
            shard = self._shard_shape(leaf)
            rep["params"]["global_bytes"] += nbytes(leaf.shape, leaf.dtype)
            rep["params"]["per_device_bytes"] += nbytes(shard, leaf.dtype)
            # same classification as _assert_tp_placement: only projection
            # leaves the contract says to shard count as violations (e.g.
            # quantized MLA wdkv is replicated *by design* — per-token
            # latent, no head dim — and must not be reported)
            base = path.rsplit("#", 1)[0]
            is_proj = any(base.endswith(t)
                          for t in self._TP_COL + self._TP_ROW)
            if ("#" in path and is_proj and self.tp > 1
                    and shard == tuple(leaf.shape)
                    and not self._tp_exempt_replicated(path, leaf)):
                rep["replicated_quant_leaves"].append(path)
        for key, leaf in self._iter_cache_leaves():
            rep["kv"]["global_bytes"] += nbytes(leaf.shape, leaf.dtype)
            shard = self._shard_shape(leaf)
            rep["kv"]["per_device_bytes"] += nbytes(shard, leaf.dtype)
            if key in POOL_KEYS:
                hdim = pool_head_dim(key, leaf.ndim)
                if (self.tp > 1 and shard == tuple(leaf.shape)
                        and leaf.shape[hdim] % self.tp == 0):
                    rep["replicated_pool_leaves"].append(key)
        return rep

    # ------------------------------------------------------------- intake
    def submit(self, prompt: np.ndarray, *, max_new: int = 32,
               arrival: float = 0.0, priority: int = 0,
               deadline: Optional[float] = None) -> Request:
        """`priority`: SLO class — 0 interactive (may preempt batch work
        when `preempt=True`), 1 batch (admitted when interactive traffic
        leaves room; aging keeps it starvation-free).
        `deadline`: absolute time past which the answer is worthless — the
        scheduler sheds the request from the queue (never admitted) or the
        engine cancels it mid-run, freeing slot and pages either way."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + max_new > self.spec.max_len:
            raise ValueError(
                f"request budget {prompt.size + max_new} exceeds per-slot "
                f"capacity {self.spec.max_len}")
        need = self.spec.pages_for(prompt.size + max_new)
        if need > self.spec.n_pages - 1:
            # an under-provisioned pool could otherwise head-of-line block
            # this request forever (admission waits for pages that can
            # never all be free at once)
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.spec.n_pages - 1} allocatable pages")
        req = Request(rid=self._next_rid, prompt=prompt, max_new=max_new,
                      arrival=arrival, priority=priority, deadline=deadline)
        self._next_rid += 1
        self.sched.submit(req)
        return req

    # -------------------------------------------------- preemption support
    def _pad_pages(self, pages: list[int]) -> np.ndarray:
        """Pow2-pad a page-id list with scratch-page entries so the spill
        gather/scatter jits compile O(log max_pages) shapes, not one per
        distinct spill size. Scratch writes/reads are dead by construction."""
        n = max(1, len(pages))
        padded = 1 << (n - 1).bit_length()
        from repro.serve.kvcache import SCRATCH_PAGE
        return np.asarray(pages + [SCRATCH_PAGE] * (padded - len(pages)),
                          np.int32)

    def _spill_slot(self, slot: int, req: Request, now: float):
        """Scheduler preempt hook: checkpoint `slot`'s KV and host state so
        the request can resume later exactly where it stopped.

        Pool bookkeeping (which pages spill by copy vs stay resident by
        reference) lives in PagePool.spill; this hook supplies the data
        movement — a jitted whole-tree page gather, synced to numpy so the
        snapshot really lives in host RAM — and clears the engine's slot
        mirrors. Owned live pages only: shared prefix pages never move."""
        n_live = int(self.cur_len[slot])

        def copy_out(pages):
            host = _spill_gather_jit(self.cache, self._pad_pages(pages))
            host = jax.tree.map(np.asarray, host)   # force sync, host RAM
            self.n_spilled_pages += len(pages)
            return host

        req.prefill_done = slot not in self._prefilling
        snap = self.pool.spill(slot, n_live, copy_out)
        if snap.host is not None:
            # checksum BEFORE any injected corruption: restore re-verifies
            # against what the data looked like when it really left device
            snap.checksum = _tree_checksum(snap.host)
            if self._spill_corrupt:
                self._spill_corrupt = False
                snap.host, hit = _corrupt_first_leaf(snap.host)
                if hit:
                    self.n_spill_corruptions += 1
        self._prefilling.pop(slot, None)
        self.active[slot] = False
        self.cur_len[slot] = 0
        self.last_tok[slot] = 0
        return snap

    def _restore_slot(self, slot: int, req: Request, now: float) -> None:
        """Finish a scheduler restore: scatter the spilled KV back into the
        fresh pages the pool picked, rebuild the slot's host mirrors, and
        re-enter the request where it left off — decoding slots resume with
        their last emitted token pending, mid-prefill slots rejoin the
        chunked-prefill set at their old progress (only tokens that were
        never prefilled get prefilled; nothing is recomputed).

        The host payload is checksum-verified first: scattering a corrupted
        snapshot would resume the stream on garbage KV (and a shared page's
        neighbors would read it too), so a mismatch quarantines the request
        instead — pages freed, error recorded, co-batched slots untouched."""
        snap = req.spill
        assert snap is not None and snap.restored is not None
        if (snap.copied and snap.checksum is not None
                and _tree_checksum(snap.host) != snap.checksum):
            self.n_spill_checksum_fails += 1
            req.spill = None
            # the pool already converted the snapshot's kept references
            # into slot references in restore(); quarantine releases them
            # all along with the fresh pages
            self.sched.quarantine(slot, now, "spill_corrupt")
            return
        if snap.copied:
            idx = self._pad_pages(snap.restored)
            self.cache = _spill_scatter_jit(self.cache, jnp.asarray(idx),
                                            snap.host)
            self.n_restored_pages += len(snap.copied)
        req.spill = None
        self.cur_len[slot] = snap.n_live
        if req.prefill_done:
            self.last_tok[slot] = req.tokens[-1]
            self.active[slot] = True
        else:
            self._prefilling[slot] = req

    # -------------------------------------------- engine snapshot / restore
    _SNAP_COUNTERS = ("n_decode_steps", "n_decode_tokens", "n_pages_held",
                      "n_pages_live", "n_prefills", "n_prefill_tokens",
                      "n_moe_calls", "n_moe_rows", "n_moe_experts_hit",
                      "n_moe_busiest_rows",
                      "n_shared_tokens", "n_spilled_pages",
                      "n_restored_pages", "n_spec_rounds", "n_draft_tokens",
                      "n_spec_emitted", "n_kernel_fallbacks",
                      "n_spill_corruptions", "n_spill_checksum_fails",
                      "n_nonfinite", "n_faults_applied")

    def _fingerprint(self) -> dict:
        """Identity of the serving configuration a snapshot belongs to.
        Restore refuses a snapshot from a different config/geometry — the
        cache tree shapes, RNG stream, and scheduler semantics would all
        silently diverge. `paged_attn_impl` is excluded on purpose: the
        fused and gather paths are bitwise-identical, and a kernel-fault
        fallback mid-trace must not orphan earlier snapshots (the live
        impl is carried in the snapshot body instead)."""
        return {
            "cfg": repr(self.cfg.replace(paged_attn_impl="fused")),
            "n_slots": self.n_slots,
            "spec": (self.spec.n_pages, self.spec.page_size,
                     self.spec.max_pages),
            "eos_id": self.eos_id,
            "prefill_bucket": self.prefill_bucket,
            "prefill_batch": self.prefill_batch,
            "decode_block": self.decode_block,
            "temperature": self.temperature, "top_k": self.top_k,
            "prefix_share": self.prefix_share,
            "chunk_tokens": self.chunk_tokens,
            "tp": self.tp,
            "spec_decode": self.spec_decode,
            "draft_bits": self.draft_bits, "spec_k": self.spec_k,
            "preempt": self.preempt,
            "age_promote": self.sched.age_promote,
        }

    def snapshot(self) -> dict:
        """Capture the full serving state as a plain nested dict of host
        values: every cache pool leaf, the allocator (free-list order
        included — allocation determinism), the scheduler (requests, queue
        order, event log, counters), the slot host mirrors, the RNG keys,
        the virtual clock, and the in-flight fault one-shots. The result
        is self-contained (no live object references), serializable via
        ``checkpoint.store.save_snapshot``, and consumable by ``restore``
        on a freshly built identical engine — which then resumes the trace
        with bit-identical greedy tokens. The FaultPlan itself is *not*
        captured: the crash driver owns it (see serve/faults.py)."""
        snap = {
            "fingerprint": self._fingerprint(),
            "t": self.t,
            "n_steps_total": self.n_steps_total,
            "next_rid": self._next_rid,
            "paged_attn_impl": self.cfg.paged_attn_impl,
            "rng": {"key": np.asarray(self._key),
                    "first_key": np.asarray(self._first_key)},
            "mirrors": {"cur_len": self.cur_len.copy(),
                        "last_tok": self.last_tok.copy(),
                        "active": self.active.copy()},
            "prefilling": {int(s): r.rid
                           for s, r in self._prefilling.items()},
            # np.asarray forces the device sync leaf-by-leaf: after this,
            # the snapshot is consistent even if the process dies mid-write
            "cache": jax.tree.map(np.asarray, self.cache),
            "pool": self.pool.state_dict(),
            "sched": self.sched.state_dict(),
            "counters": {k: getattr(self, k) for k in self._SNAP_COUNTERS},
            "spec_accept_sum": self.spec_accept_sum.copy(),
            "spec_round_count": self.spec_round_count.copy(),
            "fault_state": {
                "poison_slots": sorted(self._poison_slots),
                "kernel_fault": self._kernel_fault,
                "spill_corrupt": self._spill_corrupt,
                "fault_holds": [[int(s), [int(p) for p in pages]]
                                for s, pages in self._fault_holds],
            },
        }
        if self.spec_decode:
            snap["draft_cache"] = jax.tree.map(np.asarray, self.draft_cache)
        return snap

    def restore(self, snap: dict) -> None:
        """Load a ``snapshot()`` into this engine (built with the same
        config/geometry — validated against the fingerprint) and resume:
        the next ``step()``/``run()`` continues the interrupted trace with
        bit-identical greedy tokens. The scheduler's requests are rebuilt
        by value and re-linked into every membership structure by rid, so
        object identity (slot <-> prefilling <-> queue) holds again."""
        fp, got = self._fingerprint(), dict(snap["fingerprint"])
        if got != fp:
            bad = sorted(k for k in set(fp) | set(got)
                         if fp.get(k) != got.get(k))
            raise ValueError(f"snapshot fingerprint mismatch on {bad}: "
                             f"snapshot from a different engine config")
        impl = str(snap["paged_attn_impl"])
        if impl != self.cfg.paged_attn_impl:
            self.cfg = self.cfg.replace(paged_attn_impl=impl)
        self.t = int(snap["t"])
        self.n_steps_total = int(snap["n_steps_total"])
        self._next_rid = int(snap["next_rid"])
        self._key = jnp.asarray(np.asarray(snap["rng"]["key"]))
        self._first_key = jnp.asarray(np.asarray(snap["rng"]["first_key"]))
        self.cur_len = np.asarray(snap["mirrors"]["cur_len"],
                                  np.int32).copy()
        self.last_tok = np.asarray(snap["mirrors"]["last_tok"],
                                   np.int32).copy()
        self.active = np.asarray(snap["mirrors"]["active"], bool).copy()
        cache = jax.tree.map(jnp.asarray, snap["cache"])
        if self.tp > 1:
            cache = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                cache, paged_pool_pspecs(cache, self.mesh, axis=TP_AXIS))
        self.cache = cache
        if self.spec_decode:
            self.draft_cache = jax.tree.map(jnp.asarray,
                                            snap["draft_cache"])
        self.pool.load_state_dict(snap["pool"])
        by_rid = self.sched.load_state_dict(snap["sched"])
        self._prefilling = {int(s): by_rid[int(r)]
                            for s, r in snap["prefilling"].items()}
        for k in self._SNAP_COUNTERS:
            setattr(self, k, int(snap["counters"][k]))
        self.spec_accept_sum = np.asarray(snap["spec_accept_sum"],
                                          np.int64).copy()
        self.spec_round_count = np.asarray(snap["spec_round_count"],
                                           np.int64).copy()
        fs = snap["fault_state"]
        self._poison_slots = {int(s) for s in fs["poison_slots"]}
        self._kernel_fault = bool(fs["kernel_fault"])
        self._spill_corrupt = bool(fs["spill_corrupt"])
        self._fault_holds = [[int(s), [int(p) for p in pages]]
                             for s, pages in fs["fault_holds"]]
        if self.debug:
            self._debug_check()

    def fault_stats(self) -> dict:
        """Fault-tolerance accounting: injections applied, sentinel and
        checksum catches, kernel fallbacks, and the scheduler's
        deadline/quarantine counters — everything the chaos suite and the
        launcher report assert on."""
        return {
            "n_steps": self.n_steps_total,
            "n_faults_applied": self.n_faults_applied,
            "n_nonfinite": self.n_nonfinite,
            "n_kernel_fallbacks": self.n_kernel_fallbacks,
            "n_spill_corruptions": self.n_spill_corruptions,
            "n_spill_checksum_fails": self.n_spill_checksum_fails,
            "n_quarantined": self.sched.n_quarantined,
            "n_shed": self.sched.n_shed,
            "n_cancelled": self.sched.n_cancelled,
            "held_pages": sum(len(h[1]) for h in self._fault_holds),
            "paged_attn_impl": self.cfg.paged_attn_impl,
        }

    def _debug_check(self) -> None:
        """REPRO_DEBUG=1 per-step validation: pool invariants plus
        slot-mirror/scheduler-state agreement, so chaos and fuzz runs fail
        at the step corruption happens instead of at drain. Cheap (host
        arithmetic only, no device sync) but O(slots + pages) per step —
        opt-in via the env var, not default-on."""
        self.pool.check_invariants()
        for slot in range(self.n_slots):
            req = self.sched.slots[slot]
            if req is None:
                assert not self.active[slot], \
                    f"slot {slot}: active with no request"
                assert slot not in self._prefilling, \
                    f"slot {slot}: prefilling with no request"
                assert np.all(self.pool.tables[slot] == -1), \
                    f"slot {slot}: pages mapped with no request"
                continue
            assert req.slot == slot, \
                f"slot {slot}: request {req.rid} thinks it is in {req.slot}"
            mapped = int(np.sum(self.pool.tables[slot] >= 0))
            if slot in self._prefilling:
                assert not self.active[slot], \
                    f"slot {slot}: both prefilling and decoding"
                assert int(self.cur_len[slot]) <= req.n_prompt, \
                    f"slot {slot}: prefill fill beyond the prompt"
            else:
                assert self.active[slot], \
                    f"slot {slot}: occupied but neither prefilling nor " \
                    f"decoding"
                assert (int(self.cur_len[slot])
                        == req.n_prompt + len(req.tokens) - 1), \
                    f"slot {slot}: fill count disagrees with the token " \
                    f"stream ({int(self.cur_len[slot])} vs " \
                    f"{req.n_prompt}+{len(req.tokens)}-1)"
            assert mapped >= self.spec.pages_for(int(self.cur_len[slot])), \
                f"slot {slot}: fill {int(self.cur_len[slot])} exceeds its " \
                f"{mapped} mapped pages"

    # ------------------------------------------------- fault-plan plumbing
    def _apply_faults(self, step_idx: int, now: float) -> None:
        """Fire every fault scheduled for this step (see serve/faults.py).
        Holds from expired pool_exhaust faults release first so a fault
        plan can never permanently shrink the pool."""
        due = [h for h in self._fault_holds if h[0] <= step_idx]
        for h in due:
            self.pool.release_hold(h[1])
            self._fault_holds.remove(h)
        if self.faults is None:
            return
        for f in self.faults.at(step_idx):
            self.n_faults_applied += 1
            if f.kind == "step_exception":
                raise FaultInjected(f)
            elif f.kind == "nan_logits":
                self._poison_slots.add(max(0, f.slot) % self.n_slots)
            elif f.kind == "pool_exhaust":
                pages = self.pool.hold(f.pages)
                if pages:
                    self._fault_holds.append(
                        [step_idx + max(1, f.duration), pages])
            elif f.kind == "latency_spike":
                # virtual time jumps; run() passes `now` from self.t, so
                # the spike ages queues/deadlines from the next tick on
                self.t += max(1, f.duration)
            elif f.kind == "kernel_fault":
                self._kernel_fault = True
            elif f.kind == "spill_corrupt":
                self._spill_corrupt = True

    def _enforce_deadlines(self, now: float) -> bool:
        """Cancel running/prefilling requests whose deadline has passed
        (queued ones are shed inside scheduler.admit). Clearing the slot
        mirrors here is what _spill_slot does on eviction — the slot is
        immediately reusable."""
        did = False
        for slot, req in enumerate(self.sched.slots):
            if (req is not None and req.deadline is not None
                    and now > req.deadline):
                self._prefilling.pop(slot, None)
                self.active[slot] = False
                self.cur_len[slot] = 0
                self.last_tok[slot] = 0
                self.sched.cancel(slot, now)
                did = True
        return did

    def _quarantine(self, slot: int, req: Request, reason: str,
                    now: float) -> None:
        """Retire a slot the sentinel flagged: clear the engine mirrors and
        let the scheduler free its pages + record the error status. The
        other slots' state is untouched — their tokens this block came out
        of the same scan, already shielded by the in-scan deactivation."""
        self.n_nonfinite += reason == "nonfinite_logits"
        self._prefilling.pop(slot, None)
        self.active[slot] = False
        self.cur_len[slot] = 0
        self.last_tok[slot] = 0
        self.sched.quarantine(slot, now, reason)

    # ------------------------------------------------------------ serving
    def step(self, now: float = 0.0) -> bool:
        """One scheduler tick: fire scheduled faults and shed/cancel
        expired deadlines, admit new requests, advance every mid-prefill
        slot by one chunk (batched by chunk bucket), then run one fused
        block of decode steps over all decoding slots. Returns False when
        there was nothing to do. Its phases are host spans (repro.spans)."""
        step_idx = self.n_steps_total
        self.n_steps_total += 1
        self._stamped = []
        with spans.span(spans.STEP, step=step_idx):
            did = self._admit(step_idx, now)
            if self._prefilling:
                did = True
                self._prefill_tick(now)
            act = np.nonzero(self.active)[0]
            if act.size:
                did = True
                if self.spec_decode:
                    self._spec_block(self.active.copy(), now)
                else:
                    toks, alive = self._decode_block(now)     # (K, n_slots)
                    self._emit_block(toks, alive, act, now)
            occupied = np.asarray([r is not None for r in self.sched.slots])
            self.n_pages_held += int((self.pool.tables[occupied] >= 0).sum())
            self.n_pages_live += int(
                (-(-self.cur_len[occupied] // self.spec.page_size)).sum())
            if self.debug:
                self._debug_check()
        return did

    def _admit(self, step_idx: int, now: float) -> bool:
        """Fire this step's faults, shed/cancel expired deadlines, and map
        queued requests onto free slots; returns whether a deadline or a
        restore changed anything."""
        with spans.span(spans.ADMIT) as sp:
            self._apply_faults(step_idx, now)    # may raise FaultInjected
            did = self._enforce_deadlines(now)
            fresh = []
            for slot, req in self.sched.admit(now):
                if req.spill is not None:
                    # re-admission of a preempted request: scatter its
                    # spilled KV back and resume (decode or mid-prompt
                    # prefill) — no token is ever re-prefilled, the stream
                    # picks up exactly where the eviction cut it
                    did = True
                    self._restore_slot(slot, req, now)
                    continue
                # a prefix hit starts the prefill past the shared pages —
                # the cache already holds positions 0..n_shared-1
                self.cur_len[slot] = req.n_shared
                self.n_shared_tokens += req.n_shared
                self._prefilling[slot] = req
                fresh.append(req.rid)
            if fresh:
                sp.set_metadata(rids=spans.ids(fresh))
        return did

    def _emit_block(self, toks: np.ndarray, alive: np.ndarray,
                    act: np.ndarray, now: float) -> None:
        """Hand a decode block's (K, n_slots) tokens to the slots `act`
        that decoded it, in step order: emit, retire at max_new/EOS, or
        quarantine where the sentinel fired."""
        with spans.span(spans.EMIT) as sp:
            n = 0
            for t in range(toks.shape[0]):
                for slot in act:
                    req = self.sched.slots[slot]
                    if req is None:                           # retired
                        continue
                    if not alive[t, slot]:
                        # sentinel fired: the token at (and after) this
                        # step is garbage; the scan already froze the
                        # slot, so only this retire remains
                        self._quarantine(slot, req, "nonfinite_logits", now)
                        continue
                    self._emit(slot, req, int(toks[t, slot]), now)
                    n += 1
            self.n_decode_tokens += n
            sp.set_metadata(tokens=n)

    def run(self, *, clock=None, max_steps: Optional[int] = None):
        """Drain every submitted request; returns the requests that finished
        during this call, in submit order.

        `clock`: callable giving the current time for arrival gating and
        latency stamps (wall-clock driver); default is a virtual step
        counter, so `arrival` is then measured in scheduler steps. The
        virtual clock is the persistent ``self.t`` — a restored engine
        resumes mid-trace with arrival gating intact, and back-to-back
        run() calls keep monotonic time (latency_spike faults advance it
        too; reset ``engine.t = 0`` to re-zero between measured runs).
        Under a wall clock a token reaches the caller when the step that
        made it returns, after the rest of that step's decode block, so
        `first_token_at` / `finished_at` stamped in a step are set to a
        clock reading taken then.
        """
        import time as _time

        steps = 0
        while not self.sched.all_done():
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(f"serve loop exceeded {max_steps} steps")
            now = clock() if clock is not None else float(self.t)
            did = self.step(now)
            if clock is not None and self._stamped:
                done_at = clock()
                for req in self._stamped:
                    if req.first_token_at == now:
                        req.first_token_at = done_at
                    if req.finished_at == now:
                        req.finished_at = done_at
            if did or clock is None:
                steps += 1
                # virtual time must tick even when idle (arrival gating),
                # but under a wall clock an idle spin would burn CPU and
                # exhaust max_steps between sparse arrivals — sleep instead
                self.t += 1
            else:
                _time.sleep(1e-3)
        return sorted(self.sched.drain_finished(), key=lambda r: r.rid)

    # ----------------------------------------------------------- internals
    def _bucket(self, n: int) -> int:
        b = self.prefill_bucket
        return -(-n // b) * b

    def _read_width(self, n_tokens: int) -> int:
        """Pow2 page count covering n_tokens, capped at the table width —
        the read-width bucketing shared by decode and chunked prefill."""
        need = self.spec.pages_for(n_tokens)
        width = 1
        while width < need:
            width *= 2
        return min(width, self.spec.max_pages)

    def _prefill_tick(self, now: float) -> None:
        """Advance every mid-prefill slot by one (page-aligned) chunk.

        Chunks are batched per (length bucket, has-context) pair: rows
        whose chunk starts at position 0 keep the original self-attending
        prefill read path (bit-identical to the monolithic engine), while
        suffix/later chunks need the gathered-context path because their
        earlier tokens live in pages — their own prior chunks, or shared
        prefix pages written by another request."""
        work = []
        for slot in sorted(self._prefilling):
            req = self._prefilling[slot]
            start = int(self.cur_len[slot])
            end = req.n_prompt
            if self.chunk_tokens and end - start > self.chunk_tokens:
                end = start + self.chunk_tokens
            work.append((slot, req, start, end))
        groups: dict[tuple[int, bool], list] = {}
        for item in work:
            slot, req, start, end = item
            groups.setdefault((self._bucket(end - start), start > 0),
                              []).append(item)
        for (padded, has_ctx), items in sorted(groups.items()):
            i = 0
            while i < len(items):
                # pow2 chunk sizes bound the number of compiled shapes
                size = min(1 << ((len(items) - i).bit_length() - 1),
                           self.prefill_batch)
                self._prefill_chunk(items[i:i + size], padded, has_ctx, now)
                i += size

    def _prefill_chunk(self, items: Sequence[tuple], padded: int,
                       has_ctx: bool, now: float) -> None:
        """Prefill one same-bucket batch of (slot, req, start, end) chunks;
        rows that complete their prompt sample a first token and switch
        the slot to decoding."""
        batch = len(items)
        kv_end = np.asarray([end for _, _, _, end in items], np.int32)
        # pow2-bucketed read width over the deepest chunk end, so the
        # gathered context scales with fill, not provisioned max_len
        width = (self._read_width(int(kv_end.max())) if has_ctx
                 else self.spec.max_pages)
        with spans.span(spans.PREFILL, rows=batch, bucket=padded,
                        ctx=int(has_ctx), width=width):
            if (self.precompile_prefill
                    and (padded, has_ctx, width) not in self._prefill_keys):
                self._prefill_keys.add((padded, has_ctx, width))
                self._compile_prefill(padded, has_ctx, width, skip=batch)
            toks = np.zeros((batch, padded), np.int32)
            pos = np.full((batch, padded), -1, np.int32)
            for row, (slot, req, start, end) in enumerate(items):
                n = end - start
                toks[row, padded - n:] = req.prompt[start:end]
                pos[row, padded - n:] = np.arange(start, end, dtype=np.int32)
            slots = np.asarray([slot for slot, _, _, _ in items], np.int32)
            if has_ctx:
                paged = {"bt_rows": jnp.asarray(np.ascontiguousarray(
                             self.pool.tables[slots][:, :width])),
                         "slots": jnp.asarray(slots),
                         "kv_len": jnp.asarray(kv_end)}
            else:
                paged = {"bt_rows": jnp.asarray(self.pool.tables[slots]),
                         "slots": jnp.asarray(slots)}
            if self.tp > 1:
                logits, self.cache = _paged_prefill_tp_jit(
                    self.cfg, self.mesh, self.params, jnp.asarray(toks),
                    self.cache, jnp.asarray(pos), paged)
            else:
                logits, self.cache, load = _paged_prefill_jit(
                    self.cfg, self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(pos), paged)
                if self._moe:
                    self._moe_pending.append(("prefill", now, load))
            if self.spec_decode:
                # mirror the chunk into the draft cache so draft decode
                # starts from the same fill state (its logits are discarded
                # — the first token is always the target's)
                _, self.draft_cache, _ = _paged_prefill_jit(
                    self.cfg, self.draft_params, jnp.asarray(toks),
                    self.draft_cache, jnp.asarray(pos), paged)
            self.n_prefills += 1
            self.n_prefill_tokens += sum(end - start
                                         for _, _, start, end in items)
            finish = []
            for row, (slot, req, start, end) in enumerate(items):
                self.cur_len[slot] = end
                if end >= req.n_prompt:
                    finish.append(row)
            if not finish:
                return
            # every row is sampled, so the program's shape is the batch's;
            # rows whose prompt goes on are dropped on the host
            rids = np.asarray([req.rid for _, req, _, _ in items], np.int32)
            first, fin_ok = _sample_first_jit(
                logits, self._first_key, jnp.asarray(rids),
                temperature=self.temperature, top_k=self.top_k)
        with spans.span(spans.PREFILL_WAIT,
                        rids=spans.ids(items[row][1].rid for row in finish)):
            first, fin_ok = self._fetch(first, fin_ok)
        for row in finish:
            tok, okf = first[row], fin_ok[row]
            slot, req, _, _ = items[row]
            del self._prefilling[slot]
            if not okf:
                # non-finite prefill logits: quarantine before the slot
                # ever joins the decode set (and never publish its pages
                # into the prefix index)
                self._quarantine(slot, req, "nonfinite_logits", now)
                continue
            self.active[slot] = True
            if self.prefix_share:
                # publish this prompt's full pages before _emit can retire
                # the slot (an immediate EOS/max_new=1 would unmap it)
                self.pool.register_prefix(req.prompt, slot)
            self._emit(slot, req, int(tok), now)

    def _compile_prefill(self, padded: int, has_ctx: bool, width: int,
                         skip: int) -> None:
        """Compile the prefill and first-token programs of every pow2 batch
        size up to `prefill_batch` but `skip` (the call about to run) at
        one (bucket, has-context, width) key, the first time a prefill
        reaches that key. Rows co-batched later then compile nothing
        mid-serve, and requests warmed one at a time warm every batch."""
        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        b = 1
        while b <= self.prefill_batch:
            if b != skip:
                paged = {"bt_rows": i32(b, width), "slots": i32(b)}
                if has_ctx:
                    paged["kv_len"] = i32(b)
                args = (i32(b, padded), self.cache, i32(b, padded), paged)
                if self.tp > 1:
                    low = _paged_prefill_tp_jit.lower(
                        self.cfg, self.mesh, self.params, *args)
                else:
                    low = _paged_prefill_jit.lower(self.cfg, self.params,
                                                   *args)
                    if self.spec_decode:
                        _paged_prefill_jit.lower(
                            self.cfg, self.draft_params, args[0],
                            self.draft_cache, *args[2:]).compile()
                comp = low.compile()
                # a TP program's logits are committed to its mesh; a
                # single-device program's stay uncommitted
                out = low.out_info[0]
                logits = jax.ShapeDtypeStruct(
                    out.shape, out.dtype, sharding=(
                        comp.output_shardings[0] if self.tp > 1 else None))
                _sample_first_jit.lower(
                    logits, self._first_key, i32(b),
                    temperature=self.temperature, top_k=self.top_k).compile()
            b *= 2

    def _decode_block(self, now: float) -> tuple[np.ndarray, np.ndarray]:
        """One fused block of decode steps; returns ((K, n_slots) tokens,
        (K, n_slots) alive) — alive[t, s] False marks s's tokens from step
        t on as garbage (non-finite logits; the caller quarantines).

        K adapts to the smallest remaining budget among active requests
        (pow2-capped at decode_block) so slots retire exactly at a block
        boundary instead of idling through overshoot steps."""
        with spans.span(spans.DECODE) as sp:
            act = self.active.copy()
            self._key, sk = jax.random.split(self._key)
            # min over *decoding* slots only: a mid-prefill request has
            # its whole max_new outstanding and must not shrink everyone's
            # block
            remaining = min(req.max_new - len(req.tokens)
                            for slot, req in enumerate(self.sched.slots)
                            if req is not None and act[slot])
            k_steps = min(self.decode_block,
                          1 << (max(remaining, 1).bit_length() - 1))
            # bucket the attention read width (pow2 pages over the deepest
            # slot at block end) so shallow traffic doesn't pay
            # max_len-wide gathers
            width = self._read_width(int(self.cur_len[act].max()) + k_steps)
            # host mirrors feed the jit directly — int32 end-to-end, no cast
            # boundary where an int64 length could silently truncate
            assert (self.cur_len.dtype == np.int32
                    and self.last_tok.dtype == np.int32), \
                "engine host state drifted off the int32 jit contract"
            poison = np.zeros(self.n_slots, bool)
            if self._poison_slots:
                for s in self._poison_slots:
                    poison[s] = True
                self._poison_slots.clear()
            kw = dict(k_steps=k_steps, page_size=self.spec.page_size,
                      temperature=self.temperature, top_k=self.top_k)

            def dispatch():
                # .copy(): the transfer of a host buffer may be deferred
                # past this call's (async) dispatch, and the engine mutates
                # these mirrors right after — handing jax the live array is
                # a data race (the old .astype(int32) made an incidental
                # copy; keep an explicit one). Rebuilt per attempt: donated
                # buffers must not be reused by the fallback retry.
                args = (self.params, self.cache,
                        jnp.asarray(self.last_tok.copy()),
                        jnp.asarray(self.cur_len.copy()), jnp.asarray(act),
                        jnp.asarray(self.pool.tables[:, :width].copy()), sk,
                        jnp.asarray(poison))
                if self._kernel_fault:
                    # simulates the *fused* kernel failing to dispatch;
                    # once the engine has already degraded to the gather
                    # oracle there is no fused path left to fail, so the
                    # injection is consumed as a no-op
                    self._kernel_fault = False
                    if self.cfg.paged_attn_impl != "gather":
                        raise KernelFaultInjected(
                            "injected kernel dispatch failure")
                if self.tp > 1:
                    toks, alive, cache = _paged_decode_scan_tp_jit(
                        self.cfg, self.mesh, *args, **kw)
                    return toks, alive, None, cache
                return _paged_decode_scan_jit(self.cfg, *args, **kw)

            try:
                toks, alive, load, self.cache = dispatch()
            except KernelFaultInjected:
                # the injected kernel_fault (raised before the donated cache
                # is consumed, so the retry is safe): permanently fall back
                # to the gather oracle paged-attention path and retry once.
                # Tokens are bitwise-identical (gather is the fused kernel's
                # oracle); only bandwidth is lost, and the counter makes it
                # visible. A real lowering error is not caught: it
                # propagates.
                self.cfg = self.cfg.replace(paged_attn_impl="gather")
                self.n_kernel_fallbacks += 1
                toks, alive, load, self.cache = dispatch()
            if self._moe:
                self._moe_pending.append(("decode", now, load))
            self.cur_len[act] += k_steps
            self.n_decode_steps += k_steps
            sp.set_metadata(k=k_steps, slots=int(act.sum()), width=width)
        with spans.span(spans.DECODE_WAIT):
            return self._fetch(toks, alive)

    def _fetch(self, *arrays) -> list:
        """The host copies of `arrays`, fetched in one transfer with the
        routing counts of the MoE calls dispatched since the last fetch,
        which the MoE counters and MOE_LOG then take up."""
        pending, self._moe_pending = self._moe_pending, []
        host = jax.device_get((arrays, [c for _, _, c in pending]))
        for (phase, now, _), counts in zip(pending, host[1]):
            counts = np.asarray(counts).reshape(-1, 3)
            n, (rows, hit, _) = len(counts), counts.sum(axis=0)
            busiest = int(counts[:, 2].sum())
            self.n_moe_calls += n
            self.n_moe_rows += int(rows)
            self.n_moe_experts_hit += int(hit)
            self.n_moe_busiest_rows += busiest
            MOE_LOG.append((self.serial, now, phase, n, int(rows), int(hit),
                            busiest))
        return [np.asarray(a) for a in host[0]]

    def _spec_block(self, act: np.ndarray, now: float) -> None:
        """One speculative round over all decoding slots.

        The draft proposes up to `spec_k` tokens, the target scores them in
        a single (S, k+1)-row verify forward, and each slot emits its
        accepted prefix plus the target's own token for the first divergent
        row (so even a useless draft makes one token of progress — k_eff=0
        degenerates to a single-row verify, i.e. plain decode). k adapts to
        the smallest remaining budget among active slots (pow2-bucketed
        like _decode_block to bound the compiled-shape count)."""
        with spans.span(spans.DECODE) as sp:
            self._key, sk = jax.random.split(self._key)
            remaining = min(req.max_new - len(req.tokens)
                            for slot, req in enumerate(self.sched.slots)
                            if req is not None and act[slot])
            k_eff = min(self.spec_k, max(remaining - 1, 0))
            if k_eff:
                k_eff = 1 << (k_eff.bit_length() - 1)
            m = k_eff + 1
            width = self._read_width(int(self.cur_len[act].max()) + m)
            assert (self.cur_len.dtype == np.int32
                    and self.last_tok.dtype == np.int32), \
                "engine host state drifted off the int32 jit contract"
            out, n_emit, self.cache, self.draft_cache = _spec_block_jit(
                self.cfg, self.params, self.draft_params, self.cache,
                self.draft_cache, jnp.asarray(self.last_tok.copy()),
                jnp.asarray(self.cur_len.copy()), jnp.asarray(act),
                jnp.asarray(self.pool.tables[:, :width].copy()), sk,
                k_steps=k_eff, page_size=self.spec.page_size,
                temperature=self.temperature, top_k=self.top_k)
            sp.set_metadata(k=k_eff, slots=int(act.sum()), width=width)
        with spans.span(spans.DECODE_WAIT):
            out = np.asarray(out)
            n_emit = np.asarray(n_emit)
        act_idx = np.nonzero(act)[0]
        self.n_spec_rounds += 1
        self.n_decode_steps += 1         # one target forward per round
        self.n_draft_tokens += k_eff * act_idx.size
        with spans.span(spans.EMIT) as sp:
            emitted = 0
            for slot in act_idx:
                n = int(n_emit[slot])
                self.spec_accept_sum[slot] += n
                self.spec_round_count[slot] += 1
                self.n_spec_emitted += n
                # the cache holds positions 0..cur_len+n-1 = the old pending
                # token plus the accepted drafts; the final emitted token
                # stays unwritten (it is next round's last_tok), and the
                # rejected tail beyond the new fill is dead by masking
                self.cur_len[slot] += n
                for t in range(n):
                    req = self.sched.slots[slot]
                    if req is None:      # retired mid-round (EOS/max_new)
                        break
                    self._emit(slot, req, int(out[slot, t]), now)
                    emitted += 1
            self.n_decode_tokens += emitted
            sp.set_metadata(tokens=emitted)

    def spec_stats(self) -> dict:
        """Acceptance accounting for speculative decoding: overall rate,
        mean accepted length per slot-round, and the per-slot means."""
        slot_rounds = int(self.spec_round_count.sum())
        accepted = int(self.n_spec_emitted) - slot_rounds
        per_slot = np.where(
            self.spec_round_count > 0,
            self.spec_accept_sum / np.maximum(self.spec_round_count, 1), 0.0)
        return {
            "rounds": int(self.n_spec_rounds),
            "slot_rounds": slot_rounds,
            "draft_tokens": int(self.n_draft_tokens),
            "emitted_tokens": int(self.n_spec_emitted),
            "accepted_draft_tokens": accepted,
            "acceptance_rate": (accepted / self.n_draft_tokens
                                if self.n_draft_tokens else 0.0),
            "mean_accepted_len": (self.n_spec_emitted / slot_rounds
                                  if slot_rounds else 0.0),
            "per_slot_mean_accepted_len": [round(float(x), 4)
                                           for x in per_slot],
        }

    def _emit(self, slot: int, req: Request, tok: int, now: float) -> None:
        if req.first_token_at is None:
            req.first_token_at = now
            self._stamped.append(req)
        req.tokens.append(tok)
        self.last_tok[slot] = tok
        if len(req.tokens) >= req.max_new or tok == self.eos_id:
            self.active[slot] = False
            self.sched.retire(slot, now)
            self._stamped.append(req)
