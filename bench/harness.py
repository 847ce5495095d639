"""Build one cell's serving objects, warm them up, and drive the window.

The cell is served the way ``launch/serve.py`` serves a model: the packed
int8 tree, a placement plan (all resident, or ``plan_for_budget`` at the
config's resident share with the launcher's hot and cold placements and
the cold weights streamed from the host every tick), a ``ServingEngine``
and a ``Scheduler`` with the mix's streams.  The window drives
``Scheduler.tick()``; the harness only submits requests between ticks and
reads back what each tick produced.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import traffic
from bench.model import Dims
from bench.weights import make_weights

clock = time.perf_counter


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


@dataclasses.dataclass
class ReqRec:
    """One request as the harness saw it.  Times are seconds from the
    window's start (negative before it)."""
    item: traffic.Item
    req: object
    stream: Dict
    due: float
    sent: float
    assigned: Optional[float] = None
    tokens: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None

    @property
    def xr(self) -> bool:
        return bool(self.stream.get("xr"))

    @property
    def deadline_ms(self) -> Optional[float]:
        return self.stream.get("deadline_ms")


@dataclasses.dataclass
class Window:
    """What a window left behind for the metric readers."""
    dims: Dims
    peaks: Dict
    window_s: float
    requests: List[ReqRec]
    ticks: int
    counters: Dict[str, float]
    compiles: int
    compile_s: float
    lateness_s: List[float]
    decode_calls: List[List[int]]
    prefill_calls: List[tuple]
    # (exposed paging seconds, wire bytes) after each fence of a traced
    # window: a fence is where one tick's pass ends and the next has not
    # begun, so differences between fences count whole passes
    fences: List[tuple] = dataclasses.field(default_factory=list)
    trace: Optional[Dict] = None
    opened_at: float = 0.0      # clock() when the window opened
    setup_s: float = 0.0


class Compiles:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def prefill_shapes(mix: Dict, d: Dims) -> List[tuple]:
    """Every (bucket, kv_span) prefill program the mix can reach: chunks
    of n tokens run in the bucket next_pow2(n) and attend the cache up to
    next_pow2(insert position + bucket), capped at ``max_len``."""
    sc = mix["scheduler"]
    chunk = next_pow2(sc["prefill_chunk"])
    pmin, pmax = traffic.length_range(mix, "prompt")
    budgeted = sc.get("token_budget") is not None
    shapes = set()
    for n in range(1, min(chunk, pmax) + 1):
        b = next_pow2(n)
        if budgeted:
            positions = range(0, pmax - n + 1)
        else:
            positions = range(0, pmax - n + 1, chunk)
        for pos in positions:
            if pos == 0 and not budgeted and n < min(pmin, chunk):
                continue
            shapes.add((b, min(d.max_len, next_pow2(pos + b))))
    return sorted(shapes)


def to_model_config(d: Dims):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=d.name, family="dense", n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        head_dim=d.head_dim, d_ff=d.d_ff, vocab_size=d.vocab,
        qk_norm=d.qk_norm, qkv_bias=False, rope_theta=d.rope_theta,
        mlp_act="swiglu", norm_type=d.norm, tie_embeddings=True,
        weight_bits=d.weight_bits)


class Cell:
    """One configuration served under one traffic mix."""

    def __init__(self, d: Dims, conf: Dict, mix: Dict, peaks: Dict,
                 seed: int, rate_hz: Optional[float] = None):
        self.d, self.conf, self.mix, self.peaks = d, conf, mix, peaks
        self.seed = int(seed)
        self.rate_hz = rate_hz
        self.compiles = Compiles()
        self.engine = None
        self.sched = None

    # -- set-up ---------------------------------------------------------------
    def build(self) -> None:
        from repro.core.placement import (Placement, PlacementPlan,
                                          packed_sizes, plan_for_budget)
        from repro.serving import ServingEngine

        d = self.d
        packed = make_weights(d, self.seed)
        share = float(self.conf["serving"]["resident_share"])
        if share >= 1.0:
            plan = PlacementPlan.uniform("l1mram", bits=d.weight_bits)
        else:
            sizes = packed_sizes(packed)
            plan = plan_for_budget(
                sizes, int(sum(sizes.values()) * share),
                hot=Placement("l1mram", d.weight_bits, "resident"),
                cold=Placement("l3flash", d.weight_bits, "paged"),
                sizes_bits=d.weight_bits)
        self.plan = plan
        self.engine = ServingEngine(
            to_model_config(d), packed, batch_slots=d.slots,
            max_len=d.max_len, plan=plan, seed=self.seed % (1 << 31))
        del packed
        if share < 1.0:
            self.engine.attach_paging()

    def new_scheduler(self):
        from repro.serving import Scheduler
        sc = self.mix["scheduler"]
        sched = Scheduler(self.engine, prefill_chunk=sc["prefill_chunk"],
                          async_io=True,
                          token_budget=sc.get("token_budget"),
                          preemptive=bool(sc.get("preemptive")),
                          admission=sc.get("admission"))
        for s in self.mix["streams"]:
            sched.add_stream(s["name"], priority=s["priority"],
                             deadline_ms=s["deadline_ms"])
        return sched

    def warm(self) -> None:
        """Compile and run once every program the window can reach.  A few
        requests go through a scheduler first (the decode step and the
        first-token sampling, and they leave the cache as a program output
        placed on the device, as every later call sees it); then each
        prefill (bucket, kv_span) program the mix's lengths reach runs
        once on that cache."""
        import jax.numpy as jnp
        from repro.serving import Request

        eng = self.engine
        sched = self.new_scheduler()
        pmin, pmax = traffic.length_range(self.mix, "prompt")
        stream = self.mix["streams"][-1]["name"]
        rng = np.random.default_rng(0)
        for uid, n in enumerate(sorted({pmin, pmax})):
            sched.submit(Request(uid=-1 - uid, prompt=rng.integers(
                0, self.d.vocab, n).astype(np.int32), max_new_tokens=3),
                stream=stream)
        sched.run_until_done()
        sched.close()
        params = eng.fence_tick_params()
        k = eng.slots
        for bucket, span in prefill_shapes(self.mix, self.d):
            fn = eng._prefill_for_bucket(bucket, True, span)
            logits, cache = fn(params, jnp.zeros((k, bucket), jnp.int32),
                               eng.cache, jnp.arange(k, dtype=jnp.int32),
                               jnp.zeros((k,), jnp.int32))
            del cache      # free the copy before the next program runs
            # the engine reads each finished row's last logits eagerly
            int(jnp.argmax(logits[k - 1, bucket - 1]))
            del logits

    # -- the window -----------------------------------------------------------
    def _instrument(self, rec_decode, rec_prefill, rec_fence):
        """Wrap the engine's calls in host spans on the profiler's clock,
        and count the real rows and positions of each call."""
        from jax.profiler import TraceAnnotation

        eng = self.engine
        orig = {n: getattr(eng, n) for n in (
            "decode_tick", "prefill_tick", "fence_tick_params",
            "begin_tick_params", "preempt", "restore")}

        def decode_tick(params):
            keys = [int(eng.slot_pos[i]) + 1
                    for i, r in enumerate(eng.slot_req)
                    if r is not None and r.prefill_pos >= len(r.prompt)]
            with TraceAnnotation("bench.engine.decode"):
                out = orig["decode_tick"](params)
            if keys:
                rec_decode.append(keys)
            return out

        def prefill_tick(params, *a, **kw):
            before = [(r, r.prefill_pos) for r in eng.slot_req
                      if r is not None]
            with TraceAnnotation("bench.engine.prefill"):
                out = orig["prefill_tick"](params, *a, **kw)
            toks = keys = 0
            for r, p0 in before:
                n = r.prefill_pos - p0
                toks += n
                keys += n * p0 + n * (n + 1) // 2
            if toks:
                rec_prefill.append((toks, keys))
            return out

        def span(name, fn):
            def wrapped(*a, **kw):
                with TraceAnnotation(name):
                    return fn(*a, **kw)
            return wrapped

        eng.decode_tick = decode_tick
        eng.prefill_tick = prefill_tick
        def fence_tick_params(*a, **kw):
            with TraceAnnotation("bench.paging.fence"):
                out = orig["fence_tick_params"](*a, **kw)
            if eng.pager is not None:
                rec_fence.append((eng.paging_stall_s,
                                  eng.pager.bytes_streamed_wire))
            return out

        eng.fence_tick_params = fence_tick_params
        eng.begin_tick_params = span("bench.paging.begin",
                                     orig["begin_tick_params"])
        eng.preempt = span("bench.engine.preempt", orig["preempt"])
        eng.restore = span("bench.engine.restore", orig["restore"])
        return orig

    def _counters(self) -> Dict[str, int]:
        eng = self.engine
        return dict(ticks=self.sched.ticks, preemptions=eng.preempt_count,
                    restores=eng.restore_count)

    def run_window(self, seconds: float, trace_dir: Optional[str] = None
                   ) -> Window:
        from jax.profiler import TraceAnnotation
        from repro.serving import Request

        self.sched = sched = self.new_scheduler()
        eng = self.engine
        rec_decode: List[List[int]] = []
        rec_prefill: List[tuple] = []
        rec_fence: List[tuple] = []
        orig = None
        streams = {s["name"]: s for s in self.mix["streams"]}
        recs: List[ReqRec] = []
        live: List[ReqRec] = []
        lateness: List[float] = []

        def submit(item: traffic.Item, due_abs: float) -> ReqRec:
            req = Request(uid=item.uid, prompt=item.prompt,
                          max_new_tokens=item.new_tokens, temperature=0.0)
            req.arrival_s = due_abs
            now = clock()
            sched.submit(req, stream=item.stream)
            r = ReqRec(item=item, req=req, stream=streams[item.stream],
                       due=due_abs, sent=now)
            recs.append(r)
            live.append(r)
            return r

        def after_tick(t_start: float, t_end: float) -> List[ReqRec]:
            """Stamp the tokens each live request got back from the tick;
            returns those that finished."""
            slot_ids = {id(r) for r in eng.slot_req if r is not None}
            done = []
            for r in live:
                if r.assigned is None and (id(r.req) in slot_ids
                                           or r.req.generated):
                    r.assigned = t_start
                n = len(r.req.generated)
                while len(r.tokens) < n:
                    r.tokens.append(t_end)
                if r.req.done:
                    r.finished = t_end
                    done.append(r)
            if done:
                gone = {id(r) for r in done}
                live[:] = [r for r in live if id(r) not in gone]
            return done

        def tick() -> List[ReqRec]:
            t_start = clock()
            sched.tick()
            return after_tick(t_start, clock())

        closed = self.mix["loop"] == "closed"
        if closed:
            queues = traffic.closed_loop(self.mix, self.seed, self.d.vocab)
            nexts = [iter(q) for q in queues]
            first = [submit(next(it), clock()) for it in nexts]
            # the loop runs until the first wave of prefills is done
            while not all(r.tokens for r in first):
                for r in tick():
                    item = next(nexts[r.item.client], None)
                    if item is not None:
                        submit(item, clock())
            events = []
        else:
            events = traffic.open_loop(self.mix, self.seed, seconds,
                                       self.d.vocab, rate_hz=self.rate_hz)
        if trace_dir is not None:
            # counted from here on, so that every count covers the traced
            # window and nothing of the closed loop's first wave
            orig = self._instrument(rec_decode, rec_prefill, rec_fence)
            jax.profiler.start_trace(
                trace_dir, profiler_options=_profile_options())
        window_span = TraceAnnotation("bench.window")
        window_span.__enter__()
        c0 = self._counters()
        n0, s0 = self.compiles.n, self.compiles.seconds
        t0 = clock()
        end = t0 + seconds
        i = 0
        while True:
            now = clock()
            if now >= end:
                break
            while i < len(events) and t0 + events[i].due_s <= now:
                submit(events[i], t0 + events[i].due_s)
                lateness.append(now - (t0 + events[i].due_s))
                i += 1
            if sched.pending:
                with TraceAnnotation("bench.sched.tick"):
                    finished = tick()
                if closed:
                    for r in finished:
                        item = next(nexts[r.item.client], None)
                        if item is not None:
                            submit(item, clock())
            else:
                nxt = t0 + events[i].due_s if i < len(events) else end
                with TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, end) - clock()))
        t_close = clock()
        c1 = self._counters()
        compiles = self.compiles.n - n0
        compile_s = self.compiles.seconds - s0
        window_span.__exit__(None, None, None)
        trace = None
        if trace_dir is not None:
            jax.effects_barrier()
            jax.tree_util.tree_map(lambda a: a.block_until_ready(),
                                   eng.cache)
            jax.profiler.stop_trace()
            for n, fn in orig.items():
                setattr(eng, n, fn)
            from bench.xplane import find_trace, reduce_trace
            trace = reduce_trace(find_trace(trace_dir))
        sched.close()
        for r in recs:
            r.due -= t0
            r.sent -= t0
            if r.assigned is not None:
                r.assigned -= t0
            r.tokens = [t - t0 for t in r.tokens]
            if r.finished is not None:
                r.finished -= t0
        counters = {k: c1[k] - c0[k] for k in c0}
        return Window(
            dims=self.d, peaks=self.peaks, window_s=t_close - t0,
            requests=recs,
            ticks=int(counters["ticks"]), counters=counters,
            compiles=compiles, compile_s=compile_s, lateness_s=lateness,
            decode_calls=rec_decode, prefill_calls=rec_prefill,
            fences=rec_fence, trace=trace, opened_at=t0)

    def free(self) -> None:
        """Stop the page worker and drop every device buffer the program
        holds, so the reference runs on a clear device."""
        if self.sched is not None:
            self.sched.close()
        if self.engine is not None and self.engine.pager is not None:
            self.engine.pager.close()
        self.engine = self.sched = None
        import gc
        gc.collect()


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
