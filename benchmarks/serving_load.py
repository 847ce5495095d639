"""Serving-load benchmark: the deadline-aware scheduler under mixed XR
traffic, with live paged-weight streaming — single-model AND
multi-tenant.

Three request streams model the paper's concurrent XR workload (§V):
a high-priority hand-tracking stream on a 15 ms deadline, a gaze stream
on 10 ms, and a best-effort background assistant.  The packed store is
split by ``plan_for_budget`` so the cold half pages through the
double-buffered HostPagedStore every tick.

The multi-tenant section then serves TWO models (``--arch`` plus
``--arch2``, a dense LM and an SSM by default) through one
``MultiScheduler`` with all cold pages contending for one
``SharedPagePool`` budget (``--shared-budget-frac`` of the combined cold
bytes), asserts the pool counters against the static
``shared_pass_counters`` prediction and — under ``--smoke`` — each
tenant's tokens bit-exact versus serving that model alone on a private
pager.

Paged weights stream through the **async overlapped pipeline** by
default: tick t+1's host->device pass is begun while tick t computes and
fenced at first use, so the metrics split paging stall into *exposed*
(blocked the tick) and *hidden* (rode behind compute).  ``--sync-io``
runs the pre-overlap blocking schedule instead — CI runs the smoke bench
both ways and asserts the async run hides a nonzero fraction
(``overlap_frac > 0``) while tokens and swap/miss counters stay
identical.  A micro-bench section times the cached thread-template tick
threading against the old full-tree rebuild.

``--kv-paged`` additionally pages every tenant's per-slot KV cache
through the SAME budgeted stream (single model: a private
``KVPageTable``; tenants: ``<name>/kv`` members of the shared pool) and
asserts the generations bit-exact versus the resident-KV engine.

The **XR deadline gate** section then replays the same open-loop XR
traffic (periodic hand/gaze tracker invocations against a backlog of
long assistant requests) twice on a deterministic virtual clock — once
with the PR 5 run-to-completion scheduler, once with continuous
batching (per-tick token budget + mid-request preemption + admission
control) — and asserts the headline claim: the tracker streams'
deadline ``miss_rate <= 0.05`` under continuous batching while the
assistant's throughput stays within 10% of the run-to-completion
baseline, every request's tokens bit-exact across the two policies
(preempt/restore must not change a single token), and the weight-paging
counters still on the static ``ticks x pass_counters`` prediction under
preemption.  The virtual clock advances a fixed ``--tick-ms`` per tick
(plus 1 µs per read, keeping intra-tick stamps ordered), so the gate
measures SCHEDULING — not the host machine.

``--page-bits N`` streams every cold page *encoded*: blockwise-quantized
intN payload + scales over the wire, dequantized into the packed device
format at fetch.  The bench then asserts the compression is real —
int8 cold pages must move <= 0.3 wire bytes per fp32-dense raw byte
(>= 3.5x compression) — that the pool counters INCLUDING the wire/raw
byte ledgers still sit on the static ``kv_pass_counters`` prediction,
and times the fetch-side decode as the ``serving_page_decode``
micro-line.

Emits the ``repro.serving.metrics/v8`` multi document (default
``BENCH_serving.json``; the single-model summary rides along under
``single_model``, the deadline gate under ``xr_gate``) — tok/s, p99
tick latency, TTFT, deadline-miss rate, exposed/hidden paging stalls,
wire-vs-raw streamed bytes, shared-pool contention, preemption/
admission counters — the bench-trajectory artefact for serving PRs.

``--trace-json PATH`` additionally records the whole bench — the solo
leg, both tenants, and the continuous XR-gate leg — as one Chrome Trace
Event JSON (per-tenant ``sched.*``/``engine.*`` spans, per-page
``paging.fetch`` spans, preempt/restore/reject instants, and the
measured stall split); a disabled-``Tracer`` micro-gate holds the
untraced span hook under 5 us/call either way.

Run:  PYTHONPATH=src python benchmarks/serving_load.py --smoke
"""

from __future__ import annotations

import argparse
from collections import deque

import jax
import numpy as np

from repro.configs import get_config
from repro.core.paging import (SharedPagePool, kv_pass_counters,
                               page_sizes, pass_counters)
from repro.core.faults import FaultPlan
from repro.core.placement import Placement, packed_sizes, plan_for_budget
from repro.models import transformer as tfm
from repro.parallel.sharding import freeze_for_serving
from repro.serving import (MultiScheduler, Request, Scheduler,
                           ServingEngine, Stopwatch, Tracer, validate)
from repro.serving.trace import validate as validate_trace

STREAMS = (
    ("hand_tracking", dict(priority=2, deadline_ms=15.0)),
    ("gaze", dict(priority=1, deadline_ms=10.0)),
    ("assistant", dict(priority=0, deadline_ms=None)),
)


def _build(arch, smoke, budget_frac, seed, page_bits=None, wire_serve=False):
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    if wire_serve:
        # wire-serve wants re-encoded int8 pages (page_bits != weight
        # bits): an int4 device store whose cold pages stay blockwise
        # int8 on the wire and skip the fetch decode entirely
        packed = freeze_for_serving(params, bits=4)
        sizes = packed_sizes(packed)
        plan = plan_for_budget(sizes,
                               int(sum(sizes.values()) * budget_frac),
                               hot=Placement("l1mram", 4, "resident"),
                               cold=Placement("l1mram", 4, "paged", 8),
                               sizes_bits=4)
        return cfg, packed, plan
    packed = freeze_for_serving(params, bits=8)
    sizes = packed_sizes(packed)
    plan = plan_for_budget(sizes, int(sum(sizes.values()) * budget_frac))
    if page_bits is not None:
        plan = plan.with_page_bits(page_bits)
    return cfg, packed, plan


def _tenant_reqs(cfg, args, salt):
    rng = np.random.default_rng(args.seed + salt)
    out = []
    for uid in range(args.requests):
        hi = max(3, min(48, args.max_len - args.max_new - 2))
        prompt_len = int(rng.integers(2, hi))
        out.append(Request(uid=uid,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               prompt_len).astype(np.int32),
                           max_new_tokens=args.max_new))
    return out


def _bench_multi(args, tracer=None):
    """Two tenants, one MultiScheduler, one SharedPagePool budget."""
    tenants = {args.arch: _build(args.arch, args.smoke,
                                 args.budget_frac, seed=0,
                                 page_bits=args.page_bits)}
    name2 = args.arch2 if args.arch2 != args.arch else args.arch2 + "#2"
    tenants[name2] = _build(args.arch2, args.smoke, args.budget_frac,
                            seed=1, page_bits=args.page_bits)
    cold = sum(plan.paged_bytes(packed_sizes(packed))
               for _c, packed, plan in tenants.values())
    budget = max(int(cold * args.shared_budget_frac), 1)
    ms = MultiScheduler(pool=SharedPagePool(budget) if cold else None,
                        async_io=args.async_io, tracer=tracer)
    for name, (cfg, packed, plan) in tenants.items():
        eng = ServingEngine(cfg, packed, batch_slots=args.slots,
                            max_len=args.max_len, plan=plan,
                            seed=args.seed)
        ms.add_model(name, eng, prefill_chunk=args.prefill_chunk,
                     kv_paged=args.kv_paged and "kv" in eng.cache,
                     kv_block_rows=args.kv_block)
        for sname, kw in STREAMS:
            ms.add_stream(name, sname, **kw)
    names = [s[0] for s in STREAMS]
    for salt, (name, (cfg, _p, _pl)) in enumerate(tenants.items()):
        for req in _tenant_reqs(cfg, args, salt):
            ms.submit(name, req, stream=names[req.uid % len(names)])
    done = ms.run_until_done()
    doc = validate(ms.summary())

    pred_ok = True
    if ms.pool is not None:
        # the unified replay covers weight members AND (under --kv-paged)
        # the <name>/kv page tables contending for the same budget
        pred = kv_pass_counters(
            {name: page_sizes(ms.model(name).engine.pager.pages)
             for name in tenants
             if ms.model(name).engine.pager is not None},
            ms.pool.budget_bytes, events=ms.pool.events)
        pool_models = doc["shared_pool"]["models"]
        pred_ok = all(
            all(pool_models[m][k] == pred[m][k]
                for k in ("swaps", "misses", "pool_hits", "evicted"))
            and pool_models[m]["bytes_streamed_wire"] == pred[m]["bytes_wire"]
            and pool_models[m]["bytes_streamed_raw"] == pred[m]["bytes_raw"]
            for m in pred)

    exact_ok = True
    if args.smoke:
        # bit-exactness vs solo private pagers (smoke only: 2 extra runs)
        for salt, (name, (cfg, packed, plan)) in enumerate(tenants.items()):
            eng = ServingEngine(cfg, packed, batch_slots=args.slots,
                                max_len=args.max_len, plan=plan,
                                seed=args.seed)
            if plan.paged_bytes(packed_sizes(packed)) > 0:
                eng.attach_paging()
            if args.kv_paged and "kv" in eng.cache:
                eng.attach_kv_paging(args.kv_block)
            solo = Scheduler(eng, prefill_chunk=args.prefill_chunk,
                             async_io=args.async_io)
            for sname, kw in STREAMS:
                solo.add_stream(sname, **kw)
            for req in _tenant_reqs(cfg, args, salt):
                solo.submit(req, stream=names[req.uid % len(names)])
            want = {r.uid: r.generated for r in solo.run_until_done()}
            got = {r.uid: r.generated for r in done.get(name, [])}
            exact_ok = exact_ok and (got == want)
            if eng.pager is not None:
                eng.pager.close()
            if eng.kv_table is not None:
                eng.kv_table.close()

    ms.close()
    if not (pred_ok and exact_ok):
        raise SystemExit(
            f"multi-tenant bench invariants violated: "
            f"counters_match={pred_ok} bit_exact={exact_ok}")
    return doc, dict(tenants=list(tenants), shared_budget_bytes=budget,
                     counters_match=pred_ok,
                     bit_exact_vs_solo=exact_ok if args.smoke else None)


def _bench_chaos(args):
    """Chaos leg (``--fault-seed``): the SAME two-tenant pooled run twice
    — fault-free, then under a seeded :class:`FaultPlan` — asserting the
    headline robustness guarantee end to end: bit-exact tokens, retries
    actually absorbed faults, and no corrupted page ever reached compute
    (every checksum failure was caught pre-install and re-fetched)."""

    def run(faults):
        tenants = {args.arch: _build(args.arch, args.smoke,
                                     args.budget_frac, seed=0,
                                     page_bits=args.page_bits)}
        name2 = args.arch2 if args.arch2 != args.arch else args.arch2 + "#2"
        tenants[name2] = _build(args.arch2, args.smoke, args.budget_frac,
                                seed=1, page_bits=args.page_bits)
        cold = sum(plan.paged_bytes(packed_sizes(packed))
                   for _c, packed, plan in tenants.values())
        budget = max(int(cold * args.shared_budget_frac), 1)
        ms = MultiScheduler(pool=SharedPagePool(budget) if cold else None,
                            async_io=args.async_io, faults=faults)
        for name, (cfg, packed, plan) in tenants.items():
            eng = ServingEngine(cfg, packed, batch_slots=args.slots,
                                max_len=args.max_len, plan=plan,
                                seed=args.seed)
            ms.add_model(name, eng, prefill_chunk=args.prefill_chunk,
                         kv_paged=args.kv_paged and "kv" in eng.cache,
                         kv_block_rows=args.kv_block)
        for salt, (name, (cfg, _p, _pl)) in enumerate(tenants.items()):
            for req in _tenant_reqs(cfg, args, salt):
                ms.submit(name, req)
        done = ms.run_until_done()
        doc = validate(ms.summary())
        ms.close()
        toks = {name: {r.uid: r.generated for r in rs}
                for name, rs in done.items()}
        return toks, doc

    base_toks, base_doc = run(None)
    assert all(v == 0 for v in base_doc["totals"]["faults"].values()), \
        "fault-free leg reported nonzero fault counters"
    plan = FaultPlan(seed=args.fault_seed, fail_rate=args.fault_rate,
                     bitflip_rate=args.fault_bitflip, spike_rate=0.05,
                     spike_s=0.0005)
    chaos_toks, doc = run(plan)
    ft = doc["totals"]["faults"]
    bit_exact = chaos_toks == base_toks
    if not bit_exact:
        raise SystemExit("chaos leg: tokens diverged from the fault-free "
                         "run under seeded faults")
    if ft["retries"] <= 0 or ft["checksum_failures"] <= 0:
        raise SystemExit(f"chaos leg exercised too little ({ft}) — it "
                         f"must see at least one retried transient AND "
                         f"one CRC-caught bit-flip; raise the rates or "
                         f"pick a seed that hits the tenants' pages")
    # every corrupted wire payload must have been caught by the page CRC
    # and re-fetched; none may survive to an install (bit-exact tokens
    # above are the end-to-end evidence, this is the ledger-level check)
    if ft["checksum_failures"] != ft["refetches"]:
        raise SystemExit(f"chaos leg: {ft['checksum_failures']} checksum "
                         f"failures but {ft['refetches']} refetches")
    doc["chaos"] = dict(fault_plan=dict(seed=args.fault_seed,
                                        fail_rate=args.fault_rate,
                                        bitflip_rate=args.fault_bitflip,
                                        spike_rate=0.05),
                        bit_exact_vs_fault_free=bit_exact)
    return doc


class _VirtualClock:
    """Deterministic bench time: the drive loop advances a fixed
    ``--tick-ms`` per scheduler tick and every read adds 1 µs so
    intra-tick timestamps stay strictly ordered (and the admission EMAs
    stay nonzero).  Deadline math then measures SCHEDULING decisions —
    who waited how many ticks — not the host machine's jit latency."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1e-6
        return self.now

    def advance(self, seconds):
        self.now += seconds


def _xr_traffic(cfg, args):
    """Open-loop XR trace: a t=0 backlog of long best-effort assistant
    requests plus periodic short hand/gaze tracker invocations.  Returns
    submission events sorted by virtual arrival time."""
    rng = np.random.default_rng(args.seed + 7)
    events, uid = [], 0
    n_per_stream = max(args.xr_requests // 3, 2)
    for _ in range(n_per_stream):
        n = int(rng.integers(16, 48))
        events.append((0.0, "assistant", Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=args.xr_assist_new)))
        uid += 1
    period = args.xr_period_ms / 1e3
    for k in range(n_per_stream):
        for off, stream, lo, hi in ((0.004, "hand_tracking", 4, 9),
                                    (0.006, "gaze", 2, 7)):
            n = int(rng.integers(lo, hi))
            events.append((off + k * period, stream, Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=2)))
            uid += 1
    return sorted(events, key=lambda e: (e[0], e[2].uid))


def _run_xr(cfg, packed, plan, args, continuous, tracer=None):
    """Serve the XR trace under one scheduling policy on the virtual
    clock.  ``continuous=False`` is the PR 5 run-to-completion baseline;
    ``continuous=True`` turns on the per-tick token budget, preemption
    and reject-mode admission control."""
    clock = _VirtualClock()
    eng = ServingEngine(cfg, packed, batch_slots=args.slots,
                        max_len=args.max_len, plan=plan, seed=args.seed)
    if plan.paged_bytes(packed_sizes(packed)) > 0:
        eng.attach_paging()
    sched = Scheduler(eng, prefill_chunk=args.prefill_chunk,
                      async_io=args.async_io, clock=clock,
                      token_budget=args.token_budget if continuous else None,
                      preemptive=continuous,
                      admission="reject" if continuous else None,
                      # pin the admission cost model to the virtual tick
                      # (measured EMAs would mix the engine's REAL stall
                      # seconds into virtual-clock deadline math and
                      # reject nondeterministically under host load)
                      est_tick_s=args.tick_ms / 1e3 if continuous else None,
                      # span timestamps stay on the tracer's wall clock:
                      # the virtual clock only drives deadline math
                      tracer=tracer, trace_track="xr")
    for name, kw in STREAMS:
        sched.add_stream(name, **kw)
    arrivals = deque(_xr_traffic(cfg, args))
    done = []
    while arrivals or sched.pending:
        if not sched.pending and arrivals and arrivals[0][0] > clock.now:
            clock.advance(arrivals[0][0] - clock.now)  # idle gap: jump
        while arrivals and arrivals[0][0] <= clock.now:
            _t, stream, req = arrivals.popleft()
            sched.submit(req, stream=stream)
        done += sched.tick()
        clock.advance(args.tick_ms / 1e3)
    summary = validate(sched.metrics.summary(paging=eng.paging_summary()))
    counters_ok = True
    if eng.pager is not None:
        # preemption must not bend the weight-streaming structure: the
        # runtime counters stay on the static ticks x pass_counters line
        per_pass = pass_counters(len(eng.pager.pages),
                                 eng.page_resident_slots)
        counters_ok = (eng.swap_count == sched.ticks * per_pass["swaps"]
                       and eng.miss_count == sched.ticks * per_pass["misses"])
        eng.pager.close()
    wall = max(summary["throughput"]["wall_s"], 1e-9)
    assist_tok_s = sum(r.n_generated for r in sched.metrics.records
                       if r.stream == "assistant") / wall
    toks = {r.uid: r.generated for r in done}
    return toks, summary, assist_tok_s, counters_ok


def _bench_xr_gate(cfg, packed, plan, args, tracer=None):
    """The headline acceptance gate: continuous batching makes the
    tracker deadlines real (miss_rate <= 0.05) without costing the
    assistant more than 10% throughput, changing a single token, or
    bending the paging counters off their static prediction."""
    base_toks, base, base_assist, base_ok = _run_xr(
        cfg, packed, plan, args, continuous=False)
    # only the continuous leg is traced: it is the run with preempt /
    # restore / reject traffic worth looking at on a timeline
    cont_toks, cont, cont_assist, cont_ok = _run_xr(
        cfg, packed, plan, args, continuous=True, tracer=tracer)
    trackers = ("hand_tracking", "gaze")
    miss = max(cont["streams"][s]["miss_rate"] for s in trackers
               if s in cont["streams"])
    base_miss = max(base["streams"][s]["miss_rate"] for s in trackers
                    if s in base["streams"])
    tok_ratio = cont_assist / max(base_assist, 1e-9)
    bit_exact = (base_toks.keys() == cont_toks.keys()
                 and all(base_toks[u] == cont_toks[u] for u in base_toks))
    gate = dict(deadline_miss_rate=miss,
                baseline_miss_rate=base_miss,
                assistant_tok_ratio=tok_ratio,
                preemptions=cont["scheduler"]["preemptions"],
                restores=cont["scheduler"]["restores"],
                rejected=cont["scheduler"]["rejected"],
                bit_exact=bit_exact,
                counters_match=base_ok and cont_ok)
    ok = (miss <= 0.05 and tok_ratio >= 0.90 and bit_exact
          and gate["counters_match"] and gate["preemptions"] > 0)
    if not ok:
        raise SystemExit(f"XR deadline gate failed: {gate}")
    return dict(baseline=base, continuous=cont, gate=gate)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--arch2", default="falcon-mamba-7b",
                    help="second tenant for the multi-model section "
                         "(dense LM + SSM tracker by default)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--budget-frac", type=float, default=0.5,
                    help="resident budget as a fraction of the packed "
                         "store (the §II-B2 pressure knob)")
    ap.add_argument("--page-bits", type=int, default=None,
                    choices=(2, 4, 8),
                    help="stream cold pages ENCODED (blockwise intN "
                         "payload + scales, dequantized at fetch) instead "
                         "of the packed device format; with the bench's "
                         "int8 store, --page-bits 8 is the zero-decode "
                         "identity whose wire/raw ratio the bench gates "
                         "at <= 0.3 (>= 3.5x vs fp32 dense)")
    ap.add_argument("--shared-budget-frac", type=float, default=0.6,
                    help="SharedPagePool budget as a fraction of the "
                         "tenants' combined cold bytes (the cross-model "
                         "contention knob)")
    ap.add_argument("--kv-paged", action="store_true",
                    help="page the per-slot KV cache through the same "
                         "budgeted stream as the weights (single model: "
                         "private table; tenants: <name>/kv pool members)")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="KV page size in cache rows")
    ap.add_argument("--token-budget", type=int, default=96,
                    help="per-tick token budget for the continuous-"
                         "batching leg of the XR deadline gate")
    ap.add_argument("--xr-requests", type=int, default=60,
                    help="XR-gate trace length (requests across the 3 "
                         "streams); long enough that the preemption "
                         "tail raggedness amortizes out of the "
                         "assistant-throughput ratio")
    ap.add_argument("--tick-ms", type=float, default=1.0,
                    help="virtual-clock advance per tick in the XR gate")
    ap.add_argument("--xr-period-ms", type=float, default=6.0,
                    help="tracker invocation period in the XR trace")
    ap.add_argument("--xr-assist-new", type=int, default=24,
                    help="assistant decode length in the XR trace (long "
                         "enough that run-to-completion blows the "
                         "tracker deadlines)")
    ap.add_argument("--no-xr-gate", action="store_true",
                    help="skip the XR deadline-gate section")
    io = ap.add_mutually_exclusive_group()
    io.add_argument("--async-io", dest="async_io", action="store_true",
                    default=True,
                    help="overlapped page streaming (default)")
    io.add_argument("--sync-io", dest="async_io", action="store_false",
                    help="blocking stream-then-step ticks (the overlap "
                         "baseline CI compares against)")
    ap.add_argument("--trace-json", default=None,
                    help="record the whole bench (solo leg, tenants, "
                         "continuous XR-gate leg) as ONE Chrome Trace "
                         "Event JSON at this path; open in "
                         "chrome://tracing or ui.perfetto.dev")
    ap.add_argument("--wire-serve", action="store_true",
                    help="solo leg: int4 device store whose cold pages "
                         "are re-encoded int8 and served straight from "
                         "the wire form by the blockscale matmul (no "
                         "fetch decode); incompatible with --page-bits")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="run the chaos leg: repeat the two-tenant run "
                         "under a FaultPlan with this seed and assert "
                         "bit-exact tokens vs the fault-free leg "
                         "(writes BENCH_serving_chaos.json)")
    ap.add_argument("--fault-rate", type=float, default=0.15,
                    help="chaos leg transient fetch-failure probability")
    ap.add_argument("--fault-bitflip", type=float, default=0.15,
                    help="chaos leg wire bit-flip probability")
    ap.add_argument("--chaos-out", default="BENCH_serving_chaos.json")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.wire_serve and args.page_bits is not None:
        ap.error("--wire-serve fixes the page encoding (int8 over an "
                 "int4 store); drop --page-bits")

    cfg, packed, plan = _build(args.arch, args.smoke, args.budget_frac,
                               seed=0, page_bits=args.page_bits,
                               wire_serve=args.wire_serve)
    sizes = packed_sizes(packed)
    budget = int(sum(sizes.values()) * args.budget_frac)
    print(plan.summary(sizes))

    tracer = Tracer() if args.trace_json else None
    eng = ServingEngine(cfg, packed, batch_slots=args.slots,
                        max_len=args.max_len, plan=plan, seed=args.seed)
    if plan.paged_bytes(sizes) > 0:
        eng.attach_paging(wire_serve=args.wire_serve)
    if args.kv_paged:
        eng.attach_kv_paging(args.kv_block)
    # the solo leg runs under the SAME continuous-batching token budget
    # as the XR gate — without it the wall-clock deadline numbers here
    # are run-to-completion artifacts (miss_rate 1.0, TTFTs dominated by
    # jit compile) that read like regressions next to the gate's
    sched = Scheduler(eng, prefill_chunk=args.prefill_chunk,
                      async_io=args.async_io,
                      token_budget=args.token_budget,
                      tracer=tracer, trace_track=f"solo:{args.arch}")
    for name, kw in STREAMS:
        sched.add_stream(name, **kw)

    names = [s[0] for s in STREAMS]
    for req in _tenant_reqs(cfg, args, 0):
        sched.submit(req, stream=names[req.uid % len(names)])

    done = sched.run_until_done()
    summary = validate(sched.metrics.summary(paging=eng.paging_summary(),
                                             trace=sched.trace_summary()))
    if args.async_io and eng.pager is not None:
        # the overlapped pipeline must actually hide stream time behind
        # compute (the first tick's demand fence is the only fully
        # exposed pass) — the CI acceptance gate for the async path
        assert summary["paging"]["overlap_frac"] > 0.0, \
            "async run hid no paging stall (overlap_frac == 0)"
        assert summary["paging"]["hidden_s"] > 0.0
    if args.kv_paged:
        assert summary["paging"]["kv_swaps"] > 0, "no KV blocks streamed"
        assert summary["paging"]["kv_writebacks"] > 0
    if args.page_bits is not None and eng.pager is not None:
        # the compression acceptance gate: encoded cold pages must
        # actually shrink the link traffic relative to fp32 dense
        wire = summary["paging"]["bytes_streamed_wire"]
        raw = summary["paging"]["bytes_streamed_raw"]
        assert wire > 0 and raw > 0, "encoded paging streamed no bytes"
        if args.page_bits == 8:
            assert wire / raw <= 0.3, \
                f"int8 pages wire/raw {wire / raw:.3f} exceeds 0.3"
            assert raw / wire >= 3.5, \
                f"int8 pages compress only {raw / wire:.2f}x (< 3.5x)"
    if args.kv_paged and args.smoke:
        # KV paging must change WHERE cache rows live, never the tokens:
        # re-serve the same traffic on the resident-KV engine and compare
        ref_eng = ServingEngine(cfg, packed, batch_slots=args.slots,
                                max_len=args.max_len, plan=plan,
                                seed=args.seed)
        if plan.paged_bytes(sizes) > 0:
            ref_eng.attach_paging()
        # same token budget as the paged run so the schedules line up
        # tick for tick, not just token for token
        ref_sched = Scheduler(ref_eng, prefill_chunk=args.prefill_chunk,
                              async_io=args.async_io,
                              token_budget=args.token_budget)
        for name, kw in STREAMS:
            ref_sched.add_stream(name, **kw)
        for req in _tenant_reqs(cfg, args, 0):
            ref_sched.submit(req, stream=names[req.uid % len(names)])
        ref_done = ref_sched.run_until_done()
        assert ({r.uid: r.generated for r in done}
                == {r.uid: r.generated for r in ref_done}), \
            "kv-paged tokens diverged from the resident-KV engine"
        if ref_eng.pager is not None:
            ref_eng.pager.close()

    tick_overhead = None
    if eng.pager is not None:
        # satellite micro-bench: cached thread-template threading vs the
        # old per-tick full-tree rebuild (one extra pass is streamed for
        # the probe, AFTER the counters above were recorded)
        import time as _time
        from repro.core.paging import thread_packed
        dev = eng.pager.begin_pass(eng.page_resident_slots).fence()
        reps = 20
        t0 = _time.perf_counter()
        for _ in range(reps):
            eng._thread_tick(dev)
        cached_us = (_time.perf_counter() - t0) / reps * 1e6
        t0 = _time.perf_counter()
        for _ in range(reps):
            thread_packed(eng.params, dev)
        rebuild_us = (_time.perf_counter() - t0) / reps * 1e6
        tick_overhead = dict(thread_cached_us=cached_us,
                             thread_rebuild_us=rebuild_us,
                             speedup=rebuild_us / max(cached_us, 1e-9))
    page_decode = None
    if eng.pager is not None:
        # satellite micro-bench: fetch-side page decode (unpack intN ->
        # blockwise dequant -> requantize -> repack for re-encoded pages;
        # a passthrough for fp/identity encodings).  Host-side numpy only,
        # the cost the streaming pipeline pays per parameter per swap.
        import time as _time
        host = list(eng.pager._host.items())
        reps = 5
        t0 = _time.perf_counter()
        for _ in range(reps):
            for _name, hp in host:
                hp.decode()
        decode_us = ((_time.perf_counter() - t0)
                     / max(reps * len(host), 1) * 1e6)
        page_decode = dict(
            decode_us_per_param=decode_us, params=len(host),
            encoding=("int8" if args.wire_serve
                      else "fp" if args.page_bits is None
                      else f"int{args.page_bits}"),
            decode_s_in_run=eng.pager.decode_s,
            # wire-serve: wire bytes that never paid the decode above
            # (served straight to the blockscale matmul)
            decode_skipped_bytes=eng.pager.decode_skipped_bytes,
            bytes_streamed_wire=eng.pager.bytes_streamed_wire,
            bytes_streamed_raw=eng.pager.bytes_streamed_raw)
        if args.wire_serve:
            assert eng.pager.decode_skipped_bytes > 0, \
                "--wire-serve streamed every page through the decode path"
            assert eng.pager.decode_s == 0.0, \
                "--wire-serve still paid fetch decode time"
    if eng.pager is not None:
        eng.pager.close()
    if eng.kv_table is not None:
        eng.kv_table.close()

    # disabled-tracer overhead gate: the tracer= hook must cost nothing
    # when tracing is off — time the enabled=False path (the profiler-
    # only span every untraced tick takes) and hold it under 5 us/call
    off = Tracer(enabled=False)
    reps = 10_000
    with Stopwatch() as sw:
        for i in range(reps):
            with off.span("tick", track="bench", i=i):
                pass
            off.instant("mark", track="bench")
    tracer_disabled_us = sw.elapsed_s / (2 * reps) * 1e6
    assert tracer_disabled_us < 5.0, \
        f"disabled tracer costs {tracer_disabled_us:.2f} us/call on the " \
        f"tick path (no-op budget is 5 us)"
    assert off.event_count == 0, "disabled tracer recorded events"
    tick_overhead = dict(tick_overhead or {},
                         tracer_disabled_us=tracer_disabled_us)

    multi_doc, multi_cfg = _bench_multi(args, tracer=tracer)
    multi_doc["single_model"] = summary
    multi_doc["tick_overhead"] = tick_overhead
    multi_doc["page_decode"] = page_decode
    xr = (None if args.no_xr_gate
          else _bench_xr_gate(cfg, packed, plan, args, tracer=tracer))
    multi_doc["xr_gate"] = xr
    multi_doc["config"] = dict(arch=cfg.name, smoke=args.smoke,
                               requests=args.requests, slots=args.slots,
                               budget_bytes=budget,
                               prefill_chunk=sched.prefill_chunk,
                               async_io=args.async_io,
                               kv_paged=args.kv_paged,
                               kv_block=args.kv_block,
                               token_budget=args.token_budget,
                               page_bits=args.page_bits,
                               tick_ms=args.tick_ms,
                               xr_requests=args.xr_requests,
                               # the solo leg serves on the WALL clock, so
                               # its deadline/TTFT numbers absorb jit
                               # compile; the virtual-clock xr_gate is the
                               # deadline-meaningful section
                               solo=dict(clock="wall",
                                         token_budget=args.token_budget,
                                         admission=None, preemptive=False),
                               traced=tracer is not None,
                               multi=multi_cfg)
    validate(multi_doc)
    import json
    with open(args.out, "w") as fh:
        json.dump(multi_doc, fh, indent=2)
        fh.write("\n")
    if tracer is not None:
        validate_trace(tracer.to_dict())
        tracer.write(args.trace_json)

    thr, dl, ticks = (summary["throughput"], summary["deadlines"],
                      summary["ticks"])
    # harness contract: name,us_per_call,derived
    print(f"serving_tick,{ticks['latency_ms']['p50'] * 1e3:.2f},"
          f"p99_ms={ticks['latency_ms']['p99']:.2f}")
    pg = summary["paging"]
    print(f"serving_load,{1e6 / max(thr['tok_per_s'], 1e-9):.2f},"
          f"tok_per_s={thr['tok_per_s']:.1f}"
          f";miss_rate={dl['miss_rate']:.3f}"
          f";swaps={pg['swap_count']}"
          f";exposed_ms={pg['exposed_s'] * 1e3:.2f}"
          f";hidden_ms={pg['hidden_s'] * 1e3:.2f}"
          f";overlap={pg['overlap_frac']:.3f}")
    if args.kv_paged:
        print(f"serving_kv_paging,{pg['kv_swaps']},"
              f"kv_pool_hits={pg['kv_pool_hits']}"
              f";kv_writebacks={pg['kv_writebacks']}"
              f";kv_dropped={pg['kv_dropped']}"
              f";kv_exposed_ms={pg['kv_exposed_s'] * 1e3:.2f}"
              f";kv_hidden_ms={pg['kv_hidden_s'] * 1e3:.2f}")
    if page_decode is not None:
        pd = page_decode
        ratio = (pd["bytes_streamed_raw"] / pd["bytes_streamed_wire"]
                 if pd["bytes_streamed_wire"] else 1.0)
        print(f"serving_page_decode,{pd['decode_us_per_param']:.2f},"
              f"encoding={pd['encoding']}"
              f";params={pd['params']}"
              f";decode_ms_in_run={pd['decode_s_in_run'] * 1e3:.2f}"
              f";decode_skipped_bytes={pd['decode_skipped_bytes']}"
              f";wire_bytes={pd['bytes_streamed_wire']}"
              f";raw_bytes={pd['bytes_streamed_raw']}"
              f";compression={ratio:.2f}x")
    if "thread_cached_us" in tick_overhead:
        print(f"serving_thread_cache,{tick_overhead['thread_cached_us']:.2f},"
              f"rebuild_us={tick_overhead['thread_rebuild_us']:.2f}"
              f";speedup={tick_overhead['speedup']:.1f}x")
    print(f"serving_tracer_off,{tick_overhead['tracer_disabled_us']:.3f},"
          f"budget_us=5.0")
    if tracer is not None:
        tr = summary["trace"]
        print(f"serving_trace,{tracer.event_count},"
              f"tracks={len(tracer.track_names)}"
              f";pred_vs_meas={tr['predicted_vs_measured_stall_ratio']:.3f}"
              f";path={args.trace_json}")
    if xr is not None:
        g = xr["gate"]
        print(f"serving_xr_gate,{g['deadline_miss_rate']:.3f},"
              f"baseline_miss={g['baseline_miss_rate']:.3f}"
              f";assistant_tok_ratio={g['assistant_tok_ratio']:.3f}"
              f";preemptions={g['preemptions']}"
              f";restores={g['restores']}"
              f";rejected={g['rejected']}"
              f";bit_exact={g['bit_exact']}"
              f";counters_match={g['counters_match']}")
    tot = multi_doc["totals"]
    pool = multi_doc["shared_pool"]
    print(f"serving_tenancy,{1e6 / max(tot['tok_per_s'], 1e-9):.2f},"
          f"tok_per_s={tot['tok_per_s']:.1f}"
          f";models={len(multi_doc['models'])}"
          f";evictions={pool.get('evictions', 0)}"
          f";counters_match={multi_cfg['counters_match']}"
          f";bit_exact={multi_cfg['bit_exact_vs_solo']}")
    if args.fault_seed is not None:
        chaos_doc = _bench_chaos(args)
        with open(args.chaos_out, "w") as fh:
            json.dump(chaos_doc, fh, indent=2)
            fh.write("\n")
        cf = chaos_doc["totals"]["faults"]
        print(f"serving_chaos,{cf['injected']},"
              f"retries={cf['retries']}"
              f";checksum_failures={cf['checksum_failures']}"
              f";refetches={cf['refetches']}"
              f";fetch_timeouts={cf['fetch_timeouts']}"
              f";deferred_ticks={cf['deferred_ticks']}"
              f";bit_exact={chaos_doc['chaos']['bit_exact_vs_fault_free']}"
              f";out={args.chaos_out}")
    print(f"served {len(done)} single-model + {tot['requests']} tenant "
          f"requests over {sched.ticks} ticks; metrics -> {args.out}")
    return multi_doc


if __name__ == "__main__":
    main()
