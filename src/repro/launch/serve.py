"""Serving launcher: quantize (GPTQ/RTN/SmoothQuant ± Norm-Tweaking) and
drive the continuous-batching engine with Poisson traffic, reporting
throughput and per-request latency percentiles.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --bits 4 --method gptq --requests 16 --rate 8.0

`--no-smoke` runs the full-size config. `--engine static` runs the old
static-batch engine on the same workload for comparison. `--spec-decode`
(float checkpoints, `--method none`) turns on self-speculative decoding:
a packed W2/W3 draft of the same params proposes `--spec-k` tokens per
round, the target verifies in one forward — greedy output stays
bit-identical to target-only decode, and the summary reports the
acceptance counters.

Traffic shapes come from serve/traffic.py: `--trace poisson` (default
trickle), `--trace bursty` (on/off overload), or `--trace uniform`;
`--batch-frac` marks that fraction of requests batch-class. `--preempt`
arms priority scheduling with KV spill — interactive requests evict
batch victims under pressure (`--age-promote` bounds batch starvation) —
and the summary reports per-class TTFT/TPOT percentiles, goodput, and
the preemption/spill counters. `--virtual-clock` drives the run on the
deterministic step clock instead of wall time (same seed, same numbers,
every machine).

Fault tolerance (serve/faults.py): `--deadline T` gives every request an
SLO of T time units after arrival (missed = shed from the queue or
cancelled mid-run), `--faults SEED` injects a seeded chaos schedule —
NaN logits, pool exhaustion, kernel faults, corrupt spills, latency
spikes, plus one crash recovered from the latest snapshot — and
`--snapshot-every N` checkpoints the full engine state every N steps.
The report then carries shed/cancelled/quarantined columns and the
fault counters.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.analysis import sanitize
from repro.configs import get_config, get_smoke_config, list_archs
from repro.core.calibration.generator import generate_calibration
from repro.core.normtweak.pipeline import NTConfig, norm_tweak_ptq
from repro.debug_flags import sanitize_enabled
from repro.distributed.partitioning import rules_for_config
from repro.distributed.sharding import sharding_ctx
from repro.models.transformer import init_lm
from repro.serve import traffic
from repro.serve.engine import ContinuousEngine, ServeEngine
from repro.serve.faults import FaultPlan, run_resilient
from repro.utils.tree import tree_size_bytes


def build_params(cfg, args):
    params = init_lm(cfg, jax.random.PRNGKey(0))
    print(f"{cfg.name}: float {tree_size_bytes(params) / 1e6:.1f} MB")
    if args.method != "none":
        calib = generate_calibration(cfg, params, jax.random.PRNGKey(1),
                                     n_samples=8, token_length=32)
        nt = NTConfig(method=args.method, bits=args.bits,
                      group_size=args.group_size,
                      tweak=not args.no_tweak, lr0=1e-3, iters=1,
                      sample_batch=4,
                      act_bits=8 if args.method == "smoothquant" else 0)
        params, _ = norm_tweak_ptq(cfg, params, calib, nt,
                                   log=lambda s: print("  " + s))
        print(f"quantized: {tree_size_bytes(params) / 1e6:.1f} MB "
              f"(W{args.bits}{'+NT' if not args.no_tweak else ''})")
    return params


def make_workload(cfg, args):
    """Seeded trace from the traffic harness (serve/traffic.py): Poisson
    trickle, bursty on/off overload, or uniform arrivals, with a
    deterministic interactive/batch class mix.

    `--shared-prefix N` models system-prompt traffic: every request's
    prompt starts with the same N tokens (the prefix cache's target
    workload) followed by a unique tail."""
    return traffic.make_trace(
        kind=args.trace, n=args.requests,
        rate=args.rate if args.rate > 0 else 1e9,
        seed=args.seed, vocab_size=cfg.vocab_size,
        prompt_len=(args.prompt_len_min, args.prompt_len_max),
        max_new=(args.max_new_min, args.max_new_max),
        batch_frac=args.batch_frac,
        burst_len=args.burst_len, idle_len=args.idle_len,
        burst_rate_mult=args.burst_rate_mult,
        shared_prefix=args.shared_prefix,
        deadline=args.deadline)


def run_continuous(cfg, params, work, args):
    """Warm, replay `work` and print the report; returns (engine, report)."""
    # per-slot capacity must cover a bucket-padded prompt plus max decode,
    # or the bucket-length warm-up requests below would overflow it
    plen_max = max(len(it.prompt) for it in work)
    bucket_up = -(-plen_max // args.prefill_bucket) * args.prefill_bucket
    max_len = bucket_up + args.max_new_max

    def build():
        # also run_resilient's crash-recovery constructor: a rebuilt
        # engine must warm and reset identically to the first one (the
        # jit caches themselves are process-global, so only the first
        # build pays the compiles)
        eng = ContinuousEngine(cfg, params, n_slots=args.slots,
                               max_len=max_len, page_size=args.page_size,
                               prefill_bucket=args.prefill_bucket,
                               paged_attn=args.paged_attn,
                               prefix_share=args.prefix_share,
                               chunked_prefill=args.chunked_prefill,
                               tp=args.tp, spec_decode=args.spec_decode,
                               draft_bits=args.draft_bits,
                               spec_k=args.spec_k,
                               preempt=args.preempt,
                               age_promote=args.age_promote)
        if args.tp > 1:
            rep = eng.tp_placement_report()
            print(f"tensor-parallel x{args.tp}: params "
                  f"{rep['params']['per_device_bytes'] / 1e6:.1f} MB/device "
                  f"(global {rep['params']['global_bytes'] / 1e6:.1f} MB), "
                  f"KV pools "
                  f"{rep['kv']['per_device_bytes'] / 1e6:.1f} MB/device")
            assert not rep["replicated_quant_leaves"], \
                rep["replicated_quant_leaves"]
        # warm the jit caches — every prefill bucket in the workload,
        # decoded both shallow and to full depth so the common (k, width)
        # decode-scan shapes compile before timing (odd depth/remaining
        # combos in the real traffic can still hit a fresh shape mid-run)
        buckets = sorted({eng._bucket(len(it.prompt)) for it in work})
        waves = 2 if args.prefix_share else 1
        shared_floor = ((args.shared_prefix // args.page_size)
                        * args.page_size if args.prefix_share else 0)
        for wave in range(waves):
            # with prefix sharing, the first wave registers its prompts
            # and a second wave prefix-hits exactly the system-prefix
            # floor (its tails differ, like real traffic), compiling the
            # gathered-context suffix-prefill shapes the timed run takes
            for b in buckets:
                for mn in {2, args.max_new_max}:
                    p = np.zeros(b, np.int64)
                    if wave > 0 and 0 < shared_floor < b:
                        p[shared_floor:] = 1
                    eng.submit(p, max_new=mn)
            eng.run(max_steps=10_000)
        print(f"warmed {len(buckets)} prefill buckets "
              f"({waves} wave{'s' if waves > 1 else ''}): {buckets}")
        # report the timed run only: reset the counters, the virtual
        # clock, and the step index (fault plans are step-indexed), and
        # drop the warm-up prompts' cache registrations, so stats and
        # injected faults reflect measured traffic alone
        eng.t = 0
        eng.n_steps_total = 0
        eng.n_decode_steps = eng.n_prefills = 0
        eng.n_prefill_tokens = eng.n_shared_tokens = 0
        eng.n_spilled_pages = eng.n_restored_pages = 0
        eng.sched.events.clear()
        eng.sched.n_preemptions = eng.sched.n_restored = 0
        eng.sched.n_rejected = 0
        eng.sched.n_finished_ok = eng.sched.n_finished_preempted = 0
        eng.sched.n_shed = eng.sched.n_cancelled = 0
        eng.sched.n_quarantined = 0
        if args.spec_decode:
            eng.n_spec_rounds = eng.n_draft_tokens = eng.n_spec_emitted = 0
            eng.spec_accept_sum[:] = 0
            eng.spec_round_count[:] = 0
        eng.pool.clear_prefix_cache()
        return eng

    if args.faults is not None or args.snapshot_every > 0:
        # fault injection and periodic snapshotting run under the
        # deterministic step clock (fault plans are step-indexed and a
        # crash-restored engine replays virtual time, not wall time);
        # dt includes the (first) warm-up — run_resilient owns building
        plan = (FaultPlan.seeded(args.faults, n_steps=max(64, 4 * len(work)),
                                 n_slots=args.slots, crashes=1)
                if args.faults is not None else None)
        t0 = time.time()
        res = run_resilient(build, work, faults=plan,
                            snapshot_every=args.snapshot_every,
                            max_steps=1_000_000)
        dt = time.time() - t0
        eng, report = res["engine"], res["report"]
        print(f"resilient: {res['n_crashes']} crash(es) recovered from "
              f"snapshot, {res['n_snapshots']} periodic snapshots"
              + (f", fault plan {plan!r}" if plan is not None else ""))
    else:
        eng = build()
        t0 = time.time()
        clock = None if args.virtual_clock else (lambda: time.time() - t0)
        report = traffic.replay(eng, work, clock=clock,
                                max_steps=1_000_000)
        dt = time.time() - t0
    done = report["requests"]
    total_tok = sum(len(r.tokens) for r in done)
    print(f"continuous: {len(done)} requests, {total_tok} tokens in {dt:.2f}s "
          f"({total_tok / dt:.1f} tok/s; {eng.n_decode_steps} decode steps, "
          f"{eng.n_prefills} prefills)")
    print(f"  prefilled {eng.n_prefill_tokens} prompt tokens, "
          f"{eng.n_shared_tokens} reused from the prefix cache "
          f"({eng.pool.n_cached} pages cached)")
    if args.preempt:
        sc = report["scheduler"]
        sp = report["spill"]
        print(f"  overload {sc['n_preemptions']} preemptions "
              f"({sp['spilled_pages']} pages spilled, "
              f"{sp['restored_pages']} restored), "
              f"{sc['n_rejected']} rejected, "
              f"{sc['n_finished_preempted']} finished after preemption")
    virtual = (args.virtual_clock or args.faults is not None
               or args.snapshot_every > 0)
    print(traffic.format_report(report, unit="steps" if virtual else "s"))
    if args.spec_decode:
        st = eng.spec_stats()
        print(f"  spec     {st['rounds']} rounds, {st['draft_tokens']} draft "
              f"tokens proposed, {st['accepted_draft_tokens']} accepted "
              f"(rate {st['acceptance_rate']:.3f})")
        print(f"  accepted len  mean {st['mean_accepted_len']:.2f} "
              f"tokens/slot-round, per slot "
              f"{st['per_slot_mean_accepted_len']}")
    if sanitize_enabled():
        # REPRO_SANITIZE=1: show which jit variants this run compiled and
        # whether any cache-key leak forced a variant to retrace
        print(sanitize.format_report())
        over = sanitize.budget_violations(max_per_key=1)
        if over:
            print(f"  WARNING: {len(over)} variant(s) exceeded the "
                  "per-variant compile budget (see repro.analysis.sanitize)")
    print("request 0:", done[0].tokens)
    return eng, report


def run_static(cfg, params, work, args):
    """Static-batch baseline: uniform-length groups decoded in lockstep."""
    eng = ServeEngine(cfg, params)
    groups: dict[int, list] = {}
    for it in work:
        groups.setdefault(len(it.prompt), []).append((it.prompt, it.max_new))
    t0 = time.time()
    total = 0
    for plen, items in sorted(groups.items()):
        for i in range(0, len(items), args.slots):
            chunk = items[i:i + args.slots]
            prompts = np.stack([p for p, _ in chunk])
            mnew = max(m for _, m in chunk)
            eng.generate(prompts, max_new=mnew, temperature=0.0)
            total += sum(m for _, m in chunk)
    dt = time.time() - t0
    print(f"static: {len(work)} requests, {total} useful tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, incl. compile)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=["tiny"] + list_archs())
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="use the reduced config of --arch (--no-smoke for "
                         "full size)")
    ap.add_argument("--method", default="gptq",
                    choices=["gptq", "rtn", "smoothquant", "none"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=-1)
    ap.add_argument("--no-tweak", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "static", "both"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, req/s (0 = all at t=0)")
    ap.add_argument("--trace", default="poisson",
                    choices=["poisson", "bursty", "uniform"],
                    help="arrival shape (bursty = on/off overload)")
    ap.add_argument("--batch-frac", type=float, default=0.5,
                    help="fraction of requests in the batch SLO class "
                         "(deterministic stride, not sampled)")
    ap.add_argument("--burst-len", type=float, default=4.0,
                    help="bursty trace: on-phase length, time units")
    ap.add_argument("--idle-len", type=float, default=8.0,
                    help="bursty trace: off-phase length, time units")
    ap.add_argument("--burst-rate-mult", type=float, default=8.0,
                    help="bursty trace: rate multiplier during a burst")
    ap.add_argument("--preempt", action="store_true",
                    help="priority scheduling with preemptive KV spill: "
                         "interactive arrivals evict batch victims to host "
                         "RAM under slot/page pressure")
    ap.add_argument("--age-promote", type=float, default=None,
                    help="promote a batch request to interactive priority "
                         "after waiting this long (starvation bound)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request SLO: shed from the queue or cancel "
                         "mid-run any request still unfinished this many "
                         "time units after its arrival")
    ap.add_argument("--faults", type=int, default=None, metavar="SEED",
                    help="inject a seeded chaos schedule (nan logits, pool "
                         "exhaustion, kernel faults, corrupt spills, "
                         "latency spikes, one crash) and serve through it "
                         "via the crash-recovery driver; implies the "
                         "virtual clock")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot the full engine state every N steps "
                         "(0 = off); with --faults the crash recovers "
                         "from the latest snapshot")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="drive the run on the deterministic step clock "
                         "instead of wall time")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width for the continuous engine "
                         "(shards heads/mlp/KV pools over a 'model' mesh; "
                         "on CPU force devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--paged-attn", default=None,
                    choices=["fused", "gather"],
                    help="decode attention path: fused paged-attention "
                         "kernel (config default) or the gather oracle")
    ap.add_argument("--prefill-bucket", type=int, default=16)
    ap.add_argument("--prefix-share", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="reuse full prompt-prefix pages across requests "
                         "(attention-only archs)")
    ap.add_argument("--chunked-prefill", type=int, default=0,
                    help="max tokens per prefill chunk, page-aligned "
                         "(0 = whole prompt in one call)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common system prompt of this many "
                         "tokens to every request")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decoding: a truly-packed W2/W3 "
                         "draft of the same checkpoint proposes, the target "
                         "verifies (greedy output bit-identical to "
                         "target-only decode)")
    ap.add_argument("--draft-bits", type=int, default=2, choices=(2, 3),
                    help="draft weight width (packed sub-byte)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft proposals per verify round")
    ap.add_argument("--prompt-len-min", type=int, default=8)
    ap.add_argument("--prompt-len-max", type=int, default=64)
    ap.add_argument("--max-new-min", type=int, default=8)
    ap.add_argument("--max-new-max", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    args = build_parser().parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.enc_dec:
        raise SystemExit("whisper serving demo lives in tests/test_system.py")
    if args.tp > 1 and args.engine != "continuous":
        # the static baseline has no TP path — refusing beats silently
        # timing a differently-configured engine in a "comparison"
        raise SystemExit("--tp applies to the continuous engine only "
                         "(use --engine continuous)")
    if args.spec_decode and args.method != "none":
        # the normtweak pipeline hands back pre-packed QuantizedTensor
        # leaves; the engine quantizes its own draft from the float
        # checkpoint and refuses packed trees
        raise SystemExit("--spec-decode quantizes its own low-bit draft "
                         "from the float checkpoint; use --method none")
    if args.spec_decode and args.engine != "continuous":
        raise SystemExit("--spec-decode applies to the continuous engine "
                         "only (use --engine continuous)")
    n_dev = len(jax.devices())
    # with --tp the continuous engine owns placement (it builds a 1-D
    # ("model",) mesh and device_puts weights + KV pools itself), so the
    # GSPMD data-parallel ctx below stays out of its way
    if args.tp > 1:
        mesh, rules = None, None
    else:
        mesh = (jax.make_mesh((n_dev, 1), ("data", "model"))
                if n_dev > 1 else None)
        rules = rules_for_config(cfg, mesh) if mesh else None

    with sharding_ctx(mesh, rules):
        params = build_params(cfg, args)
        work = make_workload(cfg, args)
        if args.engine in ("continuous", "both"):
            run_continuous(cfg, params, work, args)
        if args.engine in ("static", "both"):
            run_static(cfg, params, work, args)


if __name__ == "__main__":
    main()
