"""Batched serving engine over the packed At-MRAM weight store.

The paper's deployment story, at LM scale: weights live packed (WeightStore
= the MRAM), the fused dequant path computes, and when the packed model
exceeds the resident budget the layer pages stream host->HBM double-
buffered (core/paging.HostPagedStore) — §II-B2's software-assisted
virtual paging, proactive swaps included.

The engine is a continuous-batching loop:
  * requests join a waiting queue and are admitted into free batch slots;
  * prompts prefill in power-of-two **buckets** (left-aligned, padded on
    the right so the causal mask keeps the pads invisible to real tokens)
    — the jit cache stays <= log2(max_len) programs instead of one per
    exact prompt length — and all fresh slots of a tick prefill in ONE
    batched call (gather slots -> batch-k step -> scatter rows back);
  * one jitted ``step`` serves the whole batch each tick (decode for the
    active slots, per-slot sampling at each request's own temperature);
  * finished sequences free their slot immediately (no drain barrier);
  * with :meth:`attach_paging`, the plan's cold parameters live on the
    host and stream device-ward between ticks through the double-buffered
    ``HostPagedStore`` page cache, so a mixed ``plan_for_budget`` plan is
    exercised end-to-end at serve time (swap/miss/stall counters kept).
    The stream can run *overlapped*: :meth:`begin_tick_params` kicks the
    next tick's pass while this tick computes and
    :meth:`fence_tick_params` joins at first use, recording only the
    exposed wait on the critical path (the scheduler's async pipeline);
    :meth:`tick_params` remains the blocking begin+fence wrapper.

The engine owns *mechanism* only.  Policy — deadlines, priorities,
chunked prefill pacing, metrics — lives in
:class:`repro.serving.sched.Scheduler`, which drives the same tick
primitives (``tick_params`` / ``prefill_tick`` / ``decode_tick``).

Bucketed prefill is enabled for the attention families ("dense", "vlm").
SSM state and MoE capacity routing are position-history-dependent, so pad
tokens would perturb real activations there; those families keep the
exact-length single-shot prefill.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import PlacementPlan, as_plan
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.serving.trace import now as _now, span


def sample_token(logits: jax.Array, key: jax.Array, temperature: float = 1.0,
                 top_k: int = 0) -> jax.Array:
    """logits (..., V) -> token ids (...,)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[..., -1:]
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1)


def sample_token_batch(logits: jax.Array, key: jax.Array,
                       temperatures: jax.Array) -> jax.Array:
    """Per-row sampling: logits (B, V) with temperatures (B,).

    Row b is greedy when ``temperatures[b] <= 0`` and otherwise sampled at
    its OWN temperature.  (The old engine computed one greedy and one
    temperature-1.0 draw for the whole batch, silently serving every
    stochastic request at temperature 1.0.)"""
    temps = jnp.asarray(temperatures, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    safe = jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / safe, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def _pow2_floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    # deadline-aware scheduling (serving.sched): latency bound in ms from
    # arrival to the last generated token; None = best effort.  priority
    # None defers to the stream's default.
    deadline_ms: Optional[float] = None
    priority: Optional[int] = None
    stream: str = "default"
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # retired because the KV cache ran out (slot_pos hit max_len - 1)
    # before max_new_tokens was reached — such a request got *partial*
    # service, so deadline accounting must not conflate it with natural
    # completion
    truncated: bool = False
    # runtime bookkeeping (stamped by the engine / scheduler)
    prefill_pos: int = 0               # prompt tokens already prefilled
    arrival_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None
    # continuous batching (serving.sched): monotonic submission sequence
    # (the deterministic admission tie-break), admission-control outcome
    # flags, and how many times this request was preempted mid-service
    seq: Optional[int] = None
    rejected: bool = False             # admission control refused to queue
    degraded: bool = False             # deadline stripped at admission
    preemptions: int = 0


@dataclasses.dataclass
class SlotCheckpoint:
    """Bit-exact resumable snapshot of one preempted batch slot.

    ``kv`` holds the slot's valid cache rows ``[0, valid)`` (host copies;
    the dtype round-trips exactly), ``ssm`` the recurrent state, and the
    request itself carries its chunk frontier (``prefill_pos``) and the
    tokens generated so far.  Restoring scatters these back into any free
    slot; completed KV blocks re-writeback through the normal
    ``sync_kv_tick`` path, so the page-pool event log stays a faithful
    replay input for ``kv_pass_counters``."""
    req: Request
    slot_pos: int
    valid: int                          # valid KV rows at preemption
    kv: Optional[Dict[str, np.ndarray]] = None
    ssm: Optional[Dict[str, np.ndarray]] = None


class ServingEngine:
    """``plan`` is the per-parameter weight placement
    (:class:`~repro.core.placement.PlacementPlan`); the legacy ``engine``
    dict ({"scenario", "mode", "bits"}) is still accepted and is converted
    to a uniform plan.  A mixed plan serves hot parameters over the fused
    At-MRAM path and cold parameters through the background scenarios in
    the SAME jitted step."""

    def __init__(self, cfg: ModelConfig, params: Any, *, batch_slots: int = 4,
                 max_len: int = 512, engine: Optional[Dict] = None,
                 plan: Optional[PlacementPlan] = None, seed: int = 0,
                 prefill_chunk: int = 64):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        if plan is not None and engine is not None:
            raise ValueError("pass either plan= or the legacy engine=, "
                             "not both")
        self.plan = plan if plan is not None else as_plan(engine)
        # kept for backward compatibility with callers poking .engine
        self.engine = self.plan
        self.key = jax.random.PRNGKey(seed)
        # pad-safe bucketing needs pads to be invisible to real tokens:
        # attention families hide them behind the causal mask, and the
        # pure-SSM family masks them into exact state no-ops (dt = 0 at
        # pads — see models/ssm.mamba_mixer).  MoE capacity routing is
        # contended across the flattened batch and hybrid's parallel
        # attn+SSM heads are untested under masking, so those families
        # keep exact-length prefill.
        self._bucketed = cfg.family in ("dense", "vlm", "ssm")
        if prefill_chunk < 1:
            # _next_pow2 maps 0/negative to 1, which would silently serve
            # chunk=1 pacing the caller never asked for
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        self.prefill_chunk = _next_pow2(prefill_chunk)

        self.cache = tfm.init_serve_cache(cfg, batch_slots, max_len)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_pos = np.zeros(batch_slots, np.int32)
        self.waiting: List[Request] = []
        self.finished: List[Request] = []
        # mid-request preemption (serving.sched): every slot handover
        # bumps the slot's generation; a KV streaming pass begun under an
        # older generation must not scatter its (stale) rows over the new
        # occupant — the guard that makes preempt/restore safe while a
        # pass is in flight
        self._slot_gen = np.zeros(batch_slots, np.int64)
        self._kv_begun_gen: Optional[np.ndarray] = None
        self.preempt_count = 0
        self.restore_count = 0

        self._decode = jax.jit(self._decode_impl)
        # keyed by (bucket, add_prefix, kv_span): pow2 buckets x pow2 KV
        # spans = O(log^2 max_len) compiled prefill programs (the ROADMAP
        # KV-span-slicing note — chunks no longer attend the full max_len
        # cache, only the next pow2 >= insert_at + bucket)
        self._prefill_cache: Dict[Tuple[int, bool, Optional[int]],
                                  Callable] = {}

        # §II-B2 live paging (attach_paging).  Stall accounting is split
        # the way the paper's At-MRAM story demands: `exposed` is paging
        # wait that actually blocked a tick, `hidden` is stream time the
        # async pipeline absorbed behind compute.  paging_stall_s keeps
        # its historical name but holds the EXPOSED total (a synchronous
        # run hides nothing, so its numbers read exactly as before).
        self.pager = None
        self.page_resident_slots = 2
        self.paging_stall_s = 0.0
        self.paging_hidden_s = 0.0
        self.last_stall_s = 0.0
        self.last_hidden_s = 0.0
        # measured split of the LAST fenced pass — swap_s (stream wall),
        # window_s (begin->fence compute window), exposed_s, hidden_s —
        # which tests assert against memsys.overlap_stall's closed form
        self.last_overlap: Optional[Dict[str, float]] = None
        self._inflight_pass = None        # AsyncPageStream begun, unfenced
        self._thread_template = None      # (treedef, slots) cache

        # KV-cache paging (attach_kv_paging): the per-slot KV cache flows
        # through the SAME pool budget and the SAME begin/fence overlap
        # as the weight pages — one memory hierarchy, the paper's actual
        # constraint.  kv_stall_s / kv_hidden_s are the KV share of the
        # combined paging_stall_s / paging_hidden_s totals.
        self.kv_table = None
        self._inflight_kv = None          # KVPageStream begun, unfenced
        self.kv_stall_s = 0.0
        self.kv_hidden_s = 0.0
        self.last_kv_overlap: Optional[Dict[str, float]] = None
        self._kv_synced = np.zeros(batch_slots, np.int64)  # blocks on host

        # opt-in Chrome-trace sink (set_tracer): None by default; the
        # engine's spans also reach any recording profiler session
        self.tracer = None
        self.trace_track = "serve"
        # id of the last page pass begun: each fetch span carries its
        # pass's id, linking it to the begin that caused it
        self.pass_id = 0

    # -- jitted bodies --------------------------------------------------------
    def _decode_impl(self, params, tokens, cache, pos_vec):
        # batched decode with PER-SLOT positions (continuous batching):
        # rope, cache insert and attention masks all take the (B,) vector.
        logits, cache = tfm.step(params, tokens, cache, pos_vec, self.cfg,
                                 engine=self.plan)
        return logits, cache

    def _prefill_for_bucket(self, bucket: int, add_prefix: bool,
                            kv_span: Optional[int] = None) -> Callable:
        """Batched multi-slot prefill for one (bucket, prefix, kv_span)
        shape: gather the k slot cache rows, slice the KV cache to the
        ``kv_span`` prefix (masked-out keys beyond the span are exact
        no-ops, so attending only the live rows changes FLOPs, never
        values), run a batch-k step at per-slot cache offsets, scatter
        the rows back.  The batch is always padded to the full slot
        count, so the jit cache is keyed by the power-of-two bucket, the
        power-of-two kv span, and (for meta-token models) whether the
        prefix is built — O(log^2 max_len) programs in place of the old
        full-cache O(log)."""
        key = (int(bucket), bool(add_prefix),
               None if kv_span is None else int(kv_span))
        if key not in self._prefill_cache:
            # SSM rows need each row's real-token count so the masked
            # scan treats the bucket pads as state no-ops; attention-only
            # families get pad safety from the causal mask alone and keep
            # the narrower signature
            needs_len = self._bucketed and "ssm" in self.cache

            def impl(params, tokens, cache, slot_idx, pos_vec,
                     lengths=None):
                sub = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, slot_idx, axis=1), cache)
                if kv_span is not None:
                    sub = dict(sub, kv=dict(
                        k=sub["kv"]["k"][:, :, :, :kv_span],
                        v=sub["kv"]["v"][:, :, :, :kv_span]))
                logits, sub = tfm.step(params, tokens, sub, pos_vec,
                                       self.cfg, engine=self.plan,
                                       add_prefix=add_prefix,
                                       lengths=lengths if needs_len
                                       else None)
                out = {}
                for part, c in cache.items():
                    s_part = sub[part]
                    if part == "kv" and kv_span is not None:
                        out[part] = {
                            n: c[n].at[:, slot_idx, :, :kv_span].set(
                                s_part[n].astype(c[n].dtype))
                            for n in ("k", "v")}
                    else:
                        out[part] = jax.tree_util.tree_map(
                            lambda cc, ss: cc.at[:, slot_idx].set(
                                ss.astype(cc.dtype)),
                            c, s_part)
                return logits, out
            self._prefill_cache[key] = jax.jit(impl)
        return self._prefill_cache[key]

    # -- §II-B2: live paged-weight streaming ---------------------------------
    def attach_paging(self, page_bytes: Optional[int] = None,
                      resident_slots: int = 2, *,
                      pool: Optional[Any] = None,
                      name: Optional[str] = None,
                      faults: Optional[Any] = None,
                      wire_serve: bool = False,
                      mesh: Optional[Any] = None,
                      shard_budget_bytes: Optional[int] = None
                      ) -> "ServingEngine":
        """Put the plan's paged parameters behind a
        :class:`~repro.core.paging.HostPagedStore`.

        The plan's resident set is pinned on device once; every cold
        parameter group is evacuated to the host image and re-streamed
        device-ward each tick through the double-buffered page cache
        (``tick_params``).  ``page_bytes`` defaults to the largest cold
        group (page == parameter-group granularity).

        With ``pool`` (a :class:`~repro.core.paging.SharedPagePool`), the
        store JOINS the pool's shared device-bytes budget under ``name``
        instead of assuming a private cache — the multi-model tenancy
        path, where every tenant's cold pages contend for one budget and
        cross-model eviction is the pool's call.

        ``faults`` (a :class:`~repro.core.faults.FaultPlan` or shared
        :class:`~repro.core.faults.FaultInjector`) puts every page fetch
        under seeded fault injection with CRC-verified retry — see
        :mod:`repro.core.faults`.

        ``wire_serve=True`` serves int8-re-encoded cold pages straight
        from their wire form: the fetch skips the host decode, the device
        holds the packed blockwise levels + per-block scales, and
        ``linear`` dispatches those params to the blockscale matmul
        (:func:`repro.core.placement.wire_served_bits`).  Params the
        predicate excludes (fp/identity pages, non-int8 encodings, other
        scenarios) keep the host-decode path unchanged.

        ``mesh`` (a jax Mesh with a "model" axis of size > 1) shards the
        paged store across the mesh's model devices instead: each device
        streams only its shard's pages through its own per-device link
        (:class:`~repro.core.paging.ShardedPagedStore`), the tick's fence
        joins all the per-device streams, and ``shard_budget_bytes`` — if
        given — splits one global byte budget into per-device page pools
        under a :class:`~repro.core.paging.ShardedPoolLedger`.  A mesh
        whose model axis has size 1 falls back to the single-device path
        unchanged.  Mutually exclusive with ``pool`` (the ledger owns the
        per-device pools)."""
        from repro.core.paging import HostPagedStore, ShardedPagedStore, \
            packed_tree_store, thread_packed

        if resident_slots < 1:
            raise ValueError(f"resident_slots must be >= 1, got "
                             f"{resident_slots}")
        if wire_serve:
            # flip the plan BEFORE building the store and template so the
            # jitted model (which reads self.plan at trace time) and the
            # fetch path agree on which params arrive in wire form
            self.plan = self.plan.replace(wire_serve=True)
            self.engine = self.plan
        store = packed_tree_store(self.params, self.plan)
        paged = [n for n in store.params
                 if self.plan.placement_for(n).paged]
        if not paged:
            raise ValueError("plan has no paged parameters; nothing to "
                             "stream — use the engine without paging")
        if page_bytes is None:
            page_bytes = max(store.params[n].nbytes_packed for n in paged)
        mesh_wide = (mesh is not None
                     and "model" in tuple(getattr(mesh, "axis_names", ()))
                     and int(mesh.shape["model"]) > 1)
        if mesh_wide:
            if pool is not None:
                raise ValueError("mesh= and pool= are mutually exclusive: "
                                 "the sharded ledger owns its per-device "
                                 "pools")
            self.pager = ShardedPagedStore(
                store, page_bytes, mesh, plan=self.plan,
                budget_bytes=shard_budget_bytes,
                name=name if name is not None else "default",
                faults=faults)
        else:
            self.pager = HostPagedStore(store, page_bytes, plan=self.plan,
                                        pool=pool,
                                        name=name if name is not None
                                        else "default",
                                        faults=faults)
        self.page_resident_slots = resident_slots
        # repoint the template tree: resident groups at the pager's pinned
        # device copies, cold groups at the HOST image — nothing stays
        # device-resident behind the pager's back.  The template only
        # fixes shapes/dtypes; template_view() presents exactly the
        # leaves a streamed (and, on a mesh, joined) page will fill.
        host_view = self.pager.template_view()
        self.params = thread_packed(self.params,
                                    {**self.pager.resident, **host_view})
        self._build_thread_template(set(host_view))
        if self.tracer is not None:
            self.set_tracer(self.tracer)   # reach the new store/pool
        return self

    def _build_thread_template(self, paged_names) -> None:
        """Pre-flatten the repointed template ONCE: each leaf slot either
        passes through verbatim (resident/pinned) or names the paged
        group + half ("packed"/"scale") a streamed page must fill.  Ticks
        then thread fresh pages by list substitution + unflatten instead
        of re-walking the whole tree with path matching every tick."""
        from repro.core.placement import path_key
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        slots = []
        for path, leaf in flat:
            key = path_key(path)
            if key.endswith("/packed") and key[:-len("/packed")] in paged_names:
                slots.append(("packed", key[:-len("/packed")]))
            elif key.endswith("/scale") and key[:-len("/scale")] in paged_names:
                slots.append(("scale", key[:-len("/scale")]))
            else:
                slots.append((None, leaf))
        self._thread_template = (treedef, slots)

    def _thread_tick(self, dev: Dict[str, Any]) -> Any:
        """Streamed device pages -> the params tree the jitted step
        consumes, via the cached template (same result as
        ``paging.thread_packed(self.params, dev)``, without the per-tick
        tree rebuild)."""
        treedef, slots = self._thread_template
        leaves = [leaf if kind is None
                  else (dev[leaf].packed if kind == "packed"
                        else dev[leaf].scale)
                  for kind, leaf in slots]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # -- KV-cache paging through the same pool --------------------------------
    def attach_kv_paging(self, block_rows: int = 16, *,
                         pool: Optional[Any] = None,
                         name: Optional[str] = None,
                         faults: Optional[Any] = None) -> "ServingEngine":
        """Page the per-slot KV cache through the SAME device-bytes
        budget (and the same begin/fence overlap) the weight pages use.

        The preallocated device cache stays the compute buffer — jit
        shapes never change — but the authoritative copy of every
        *completed* ``block_rows``-row block lives in a
        :class:`~repro.core.paging.KVPageTable` host image: blocks are
        written back once when the append-only frontier crosses them,
        and each tick the admitted slots' ``[0, valid)`` spans stream
        host->device through the pool alongside the weight pages (one
        unified eviction domain; pooled blocks re-fetch swap-free).
        With ``pool``, the table JOINS the shared budget under ``name``
        (default ``<weights-name>/kv``); without one it keeps a private
        no-cache stream, re-swapping every block every pass — exactly
        the private ``HostPagedStore`` discipline.

        Attach before serving: the table snapshots the (empty) cache."""
        from repro.core.paging import KVPageTable

        if "kv" not in self.cache:
            raise ValueError(f"family {self.cfg.family!r} has no KV cache "
                             "to page (recurrent state is not paged)")
        if self.kv_table is not None:
            raise ValueError("KV paging already attached")
        if self.waiting or any(r is not None for r in self.slot_req):
            raise ValueError("attach_kv_paging before submitting work: "
                             "the host image snapshots an idle cache")
        if name is None:
            name = (self.pager.name if self.pager is not None
                    else "default") + "/kv"
        self.kv_table = KVPageTable(self.cache["kv"], block_rows=block_rows,
                                    pool=pool, name=name, faults=faults)
        self._kv_synced[:] = 0
        if self.tracer is not None:
            self.set_tracer(self.tracer)   # reach the new table/pool
        return self

    def set_tracer(self, tracer, track: Optional[str] = None
                   ) -> "ServingEngine":
        """Attach (or, with None, detach) a
        :class:`~repro.serving.trace.Tracer` to the engine and every
        paging component it owns — the paged weight store, the KV page
        table, and their shared pool all emit onto the same tracer so
        one trace shows scheduler phases, fence stalls, per-page I/O,
        evictions and pool occupancy together.  ``track`` names this
        engine's rows (the tenancy loop passes the tenant name).
        Re-invoked automatically when paging attaches later."""
        self.tracer = tracer
        if track is not None:
            self.trace_track = track
        if self.pager is not None:
            self.pager.tracer = tracer
            if self.pager.pool is not None:
                self.pager.pool.tracer = tracer
        if self.kv_table is not None:
            self.kv_table.tracer = tracer
            if self.kv_table.pool is not None:
                self.kv_table.pool.tracer = tracer
        return self

    def _kv_valid(self, i: int) -> int:
        """Valid KV rows of slot ``i`` — the admitted request's
        ``[0, slot_pos)`` prefix (during chunked prefill: the prefix plus
        the tokens absorbed so far)."""
        r = self.slot_req[i]
        if r is None or r.prefill_pos == 0:
            return 0
        if r.prefill_pos < len(r.prompt):
            return self.cfg.n_meta_tokens + r.prefill_pos
        return int(self.slot_pos[i])

    def _kv_full_blocks(self) -> Dict[int, int]:
        """{slot: host-synced completed-block count} over the occupied
        slots — the span map one KV streaming pass fetches.  Advertising
        the *synced* count (not the raw frontier) is what keeps a
        just-restored preemption victim safe: its completed blocks live
        only in the device cache until ``sync_kv_tick`` re-writes them
        back, and a fetch of an unsynced block would stream stale host
        rows.  At every begin/fence point of an unpreempted slot the two
        counts are equal (writeback runs at end of tick, before the next
        begin), so this is the same map the frontier would give."""
        out = {}
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            full = int(self._kv_synced[i])
            if full > 0:
                out[i] = full
        return out

    def _scatter_kv(self, blocks: Dict[int, Any]) -> None:
        """Fetched KV pages -> the device cache buffer (rows beyond the
        spans keep whatever was there; the causal/cache-length masks make
        them exact no-ops).  A slot's fetched blocks are always the
        contiguous ``[0, full*block_rows)`` prefix, so they scatter as
        ONE update per slot — each un-jitted ``.at[].set`` copies the
        whole cache buffer, so this is O(slots), not O(pages)."""
        if not blocks:
            return
        k, v = self.cache["kv"]["k"], self.cache["kv"]["v"]
        nb = self.kv_table.n_blocks
        by_slot: Dict[int, List[Any]] = {}
        for page in sorted(blocks):        # slot-major, block-ascending
            slot, _blk = divmod(page, nb)
            by_slot.setdefault(slot, []).append(blocks[page])
        for slot, rows in by_slot.items():
            if self.slot_req[slot] is None:
                continue        # retired mid-pass: rows are dead anyway
            if (self._kv_begun_gen is not None
                    and self._kv_begun_gen[slot] != self._slot_gen[slot]):
                # the slot changed hands (preempt/restore/assign) after
                # the pass was begun: these rows belong to the previous
                # occupant and must not clobber the new one's restored
                # or freshly prefilled cache rows
                continue
            ks = (rows[0]["k"] if len(rows) == 1
                  else jnp.concatenate([r["k"] for r in rows], axis=2))
            vs = (rows[0]["v"] if len(rows) == 1
                  else jnp.concatenate([r["v"] for r in rows], axis=2))
            hi = ks.shape[2]
            k = k.at[:, slot, :, :hi].set(ks.astype(k.dtype))
            v = v.at[:, slot, :, :hi].set(vs.astype(v.dtype))
        self.cache["kv"] = dict(k=k, v=v)

    def sync_kv_tick(self) -> None:
        """End-of-tick writeback: blocks the append-only frontier
        completed this tick move device->host exactly once, making them
        fetchable (and poolable) from the next pass on.  Driven by the
        Scheduler's tick_compute and the legacy step() loop."""
        if self.kv_table is None:
            return
        block = self.kv_table.block_rows
        with span("engine.kv_sync", self.tracer, self.trace_track):
            for i, r in enumerate(self.slot_req):
                if r is None:
                    continue
                full = self._kv_valid(i) // block
                if full > self._kv_synced[i]:
                    self.kv_table.writeback(i, int(self._kv_synced[i]),
                                            full, self.cache["kv"])
                    self._kv_synced[i] = full

    def begin_tick_params(self) -> None:
        """Kick the overlapped host->device page stream for the NEXT
        fence and return immediately (no-op without paging, or when a
        pass is already in flight).  The fetch loop runs on the pager's
        worker while the caller keeps computing — the §II-B2 proactive
        swap, realized across ticks: tick t's compute hides tick t+1's
        page traffic.  With KV paging attached, the tick's live KV spans
        ride the same overlapped stream (blocks completed after this
        begin are demand-fetched at the fence)."""
        kicked = []
        if self.pager is not None and self._inflight_pass is None:
            kicked.append("weights")
        if self.kv_table is not None and self._inflight_kv is None:
            kicked.append("kv")
        if not kicked:
            return
        self.pass_id += 1
        if "weights" in kicked:
            self._inflight_pass = self.pager.begin_pass(
                self.page_resident_slots, pass_id=self.pass_id)
        if "kv" in kicked:
            self._kv_begun_gen = self._slot_gen.copy()
            self._inflight_kv = self.kv_table.begin_pass(
                self._kv_full_blocks())
        if self.tracer is not None:
            self.tracer.instant("begin_pass", track=self.trace_track,
                                streams="+".join(kicked),
                                pass_id=self.pass_id)

    def fence_tick_params(self, timeout_s: Optional[float] = None) -> Any:
        """The params tree for this tick, fencing at first use.

        Without paging this is just the packed store.  With paging, the
        in-flight pass (begun by :meth:`begin_tick_params`; demand-begun
        here if nothing is in flight — the sync fallback and the cold
        first tick) is joined, the arrived pages are threaded through the
        cached template, and the stall is split into the *exposed* wait
        (time this call actually blocked) and the *hidden* overlap.  The
        fused step needs every layer resident at once (the stacked scan),
        so the page cache models the *traffic* (swap/miss counters, stall
        time) while the tick's working set is materialized in full — the
        TPU-native reading of the two live MRAM pages.

        ``timeout_s`` bounds the tick's I/O wait: on expiry the fence
        raises :class:`~repro.core.faults.PageFetchTimeout` and the
        in-flight streams stay owned by the engine, untouched — no page
        is threaded, no stall is accounted, and the next call resumes
        the SAME passes (stream fences are idempotent), so a scheduler
        can defer the tick instead of stalling the world."""
        self.last_stall_s = 0.0
        self.last_hidden_s = 0.0
        if self.pager is None and self.kv_table is None:
            return self.params
        demand = (self._inflight_pass is None
                  and self._inflight_kv is None)
        if demand:
            self.begin_tick_params()
        ps = self._inflight_pass
        ks = self._inflight_kv
        # fence BOTH streams before consuming either: a timeout raises
        # with the passes still in flight (a fenced stream's result is
        # cached, so the retry re-joins it for free), and the accounting
        # below runs exactly once, on the tick that actually consumes
        with span("paging.wait", self.tracer, self.trace_track,
                  pass_id=self.pass_id, demand=demand):
            dev = ps.fence(timeout_s=timeout_s) if ps is not None else None
            blocks = (ks.fence(self._kv_full_blocks(), timeout_s=timeout_s)
                      if ks is not None else None)
        self._inflight_pass = None
        self._inflight_kv = None
        params = self.params
        if ps is not None:
            self.last_overlap = self._account_fence(
                ps, demand, self.pager.pool, self.pager.name)
            params = self._thread_tick(dev)
        if ks is not None:
            self.last_kv_overlap = self._account_fence(
                ks, demand, self.kv_table.pool, self.kv_table.name,
                kv=True)
            self._scatter_kv(blocks)
            # every in-flight fetch has settled: retired slots' stale
            # pooled blocks can now be dropped without a late fetch
            # resurrecting them
            self.kv_table.flush_drops()
        return params

    def _account_fence(self, ps, demand: bool, pool, name: str,
                       kv: bool = False) -> Dict[str, float]:
        """Book one fenced pass's stall split — ONE copy of the rule for
        both the weight stream and the KV stream (the PR 4
        double-attribution bug class lived in exactly this kind of
        duplicated accounting).  When the pass was demand-begun INSIDE
        this fence (sync tick_params, or the cold first tick), its whole
        begin->fence window was spent blocked here, not in caller
        compute: the full stream wall lands exposed, nothing was
        hidden."""
        exposed, hidden, window = ps.exposed_s, ps.hidden_s, ps.window_s
        if demand:
            exposed, hidden, window = exposed + hidden, 0.0, 0.0
        self.last_stall_s += exposed
        self.last_hidden_s += hidden
        self.paging_stall_s += exposed
        self.paging_hidden_s += hidden
        if kv:
            self.kv_stall_s += exposed
            self.kv_hidden_s += hidden
        if pool is not None:
            pool.add_stall(name, exposed, hidden)
        tr = self.tracer
        if tr is not None:
            # the measured stall split, retro-dated so [hidden][exposed]
            # render as one contiguous swap bar ending at the fence —
            # the spans the reconciliation tests sum against metrics/v8
            stream = "kv" if kv else "weights"
            track = f"{self.trace_track}:stall"
            if hidden > 0.0:
                tr.complete(f"hidden:{stream}", hidden, track=track,
                            end_offset_s=exposed, swap_ms=ps.swap_s * 1e3)
            tr.complete(f"exposed:{stream}", exposed, track=track,
                        demand=demand, window_ms=window * 1e3)
        return dict(swap_s=ps.swap_s, window_s=window,
                    exposed_s=exposed, hidden_s=hidden)

    def cancel_tick_params(self) -> None:
        """Cancel/drain an in-flight pass that will never be fenced
        (early scheduler exit, teardown) without leaking worker fetches
        or the shared pool's eviction guard."""
        if self._inflight_pass is not None:
            self._inflight_pass.close()
            self._inflight_pass = None
        if self._inflight_kv is not None:
            self._inflight_kv.close()
            self._inflight_kv = None

    def tick_params(self) -> Any:
        """Legacy blocking API: begin + fence back to back (the stream's
        full wall time lands exposed, hidden ~ 0 — exactly the old
        synchronous accounting).  Kept as the sync path the async
        pipeline is verified bit-exact against."""
        self.begin_tick_params()
        return self.fence_tick_params()

    def has_tick_after(self, chunk: Optional[int] = None,
                       plan: Optional[Dict[int, int]] = None) -> bool:
        """Will the engine still hold work after ONE more scheduler-paced
        tick (``complete=False`` prefill at ``chunk`` pacing, or at the
        per-slot ``plan`` allocations of the budgeted tick)?

        Drives the pipeline's begin decision: a pass begun with no tick
        left to consume it would stream a whole extra pass and skew the
        swap counters away from ``ticks * pass_counters``.  The predicate
        mirrors the tick's own retirement rules exactly; when in doubt it
        must answer False (a missed overlap costs latency, a phantom
        pass costs determinism)."""
        if self.waiting:
            return True
        prefix = self.cfg.n_meta_tokens
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            remaining = len(r.prompt) - r.prefill_pos
            if remaining > 0:
                if plan is not None:
                    if plan.get(i, 0) <= 0:
                        return True      # unscheduled this tick: the
                                         # frontier survives untouched
                    n, _b, _p, _q = self._chunk_shape(r, plan[i])
                else:
                    n, _bucket, _pfx, _pos = self._chunk_shape(r, chunk)
                if n < remaining:
                    return True          # more prefill chunks after this
                # prefill completes THIS tick — and the same tick's
                # decode_tick already sees it (prefill_pos is bumped
                # before decode runs), so the slot leaves this tick with
                # TWO tokens unless max_new retires it at one
                if (r.max_new_tokens > 2
                        and prefix + len(r.prompt) + 1 < self.max_len - 1):
                    return True
            elif (len(r.generated) + 1 < r.max_new_tokens
                    and self.slot_pos[i] + 1 < self.max_len - 1):
                return True              # survives this decode tick
        return False

    @property
    def swap_count(self) -> int:
        return 0 if self.pager is None else self.pager.swap_count

    @property
    def miss_count(self) -> int:
        return 0 if self.pager is None else self.pager.miss_count

    def paging_summary(self) -> Dict[str, Any]:
        total = self.paging_stall_s + self.paging_hidden_s
        kv = self.kv_table
        return dict(
            swap_count=self.swap_count, miss_count=self.miss_count,
            exposed_s=self.paging_stall_s, hidden_s=self.paging_hidden_s,
            overlap_frac=(self.paging_hidden_s / total) if total > 0 else 0.0,
            stall_s=self.paging_stall_s,       # v2 alias: exposed wait
            n_pages=0 if self.pager is None else len(self.pager.pages),
            # metrics/v8: encoded-pages byte ledger for the WEIGHT page
            # stream — wire = what crossed the link per swap (encoded
            # payload + scales), raw = the fp32-dense equivalent, so
            # wire/raw is the weight-page compression ratio.  The KV
            # stream moves device-format rows (ratio 1.0) and reports
            # through its own pool member / kv_swaps counters.
            bytes_streamed_wire=(0 if self.pager is None
                                 else self.pager.bytes_streamed_wire),
            bytes_streamed_raw=(0 if self.pager is None
                                else self.pager.bytes_streamed_raw),
            # wire-serve: wire bytes that never paid a fetch decode
            # (served straight to the blockscale matmul); 0 unless the
            # engine attached with wire_serve=True
            decode_skipped_bytes=(0 if self.pager is None
                                  else self.pager.decode_skipped_bytes),
            # metrics/v4: the KV share of the same budgeted page stream
            kv_swaps=0 if kv is None else kv.swap_count,
            kv_pool_hits=0 if kv is None else kv.pool_hits,
            kv_writebacks=0 if kv is None else kv.writebacks,
            kv_dropped=0 if kv is None else kv.dropped,
            kv_preempt_drops=0 if kv is None else kv.preempt_drops,
            kv_exposed_s=self.kv_stall_s,
            kv_hidden_s=self.kv_hidden_s,
            kv_block_rows=0 if kv is None else kv.block_rows,
            # metrics/v9: per-device counter rows when the pager is a
            # mesh-sharded store ([] on single-device runs)
            devices=(getattr(self.pager, "device_summaries", lambda: [])()
                     if self.pager is not None else []))

    def faults_summary(self) -> Dict[str, int]:
        """Fault-path counters summed over the engine's paging components
        (weight pager + KV table) — the per-model body of the metrics v8
        ``faults`` section.  The scheduler layers ``deferred_ticks`` on
        top (tick deferral is its decision, not the stores')."""
        from repro.core.faults import merge_fault_counters
        parts = [s.fault_counters for s in (self.pager, self.kv_table)
                 if s is not None]
        return merge_fault_counters(parts)

    # -- slot management ------------------------------------------------------
    def submit(self, req: Request) -> None:
        self._check_fits(req)
        if req.arrival_s is None:
            req.arrival_s = _now()
        self.waiting.append(req)

    def _check_fits(self, req: Request) -> None:
        if len(req.prompt) == 0:
            raise ValueError("empty prompt: nothing to condition on (and "
                             "no first token to decode from)")
        if self.cfg.n_meta_tokens and len(req.prompt) < 2:
            # a 1-token prompt routes through the decode path (s==1),
            # which cannot build the meta-token prefix the position
            # accounting assumes — reject rather than serve garbage
            raise ValueError("meta-token models need prompts of >= 2 "
                             "tokens (single-token prefill cannot build "
                             "the prefix)")
        prefix = self.cfg.n_meta_tokens
        if prefix + len(req.prompt) + 1 > self.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens (+{prefix} prefix) "
                f"does not fit max_len={self.max_len}")

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def assign(self, req: Request, slot: int) -> None:
        """Bind a request to a batch slot (prefill starts next tick pass)."""
        if self.slot_req[slot] is not None:
            raise ValueError(f"slot {slot} is occupied")
        self._check_fits(req)
        if req.arrival_s is None:
            req.arrival_s = _now()
        req.prefill_pos = 0
        self._slot_gen[slot] += 1
        if self.kv_table is not None:
            # the previous tenant's pooled blocks were queued for drop at
            # its retirement and flush at the next fence — BEFORE this
            # request's first writeback, so the flush can never zero live
            # data.  Only the sync bookkeeping resets here.
            self._kv_synced[slot] = 0
        if "ssm" in self.cache:
            # recurrent state is live across the whole row — unlike the kv
            # cache there is no position mask hiding a predecessor's
            # leftovers, so a reused slot must start cold
            self.cache["ssm"] = jax.tree_util.tree_map(
                lambda c: c.at[:, slot].set(0), self.cache["ssm"])
        self.slot_req[slot] = req

    # -- mid-request preemption (the continuous-batching slot handover) -------
    def preempt(self, slot: int) -> SlotCheckpoint:
        """Evict the request occupying ``slot`` mid-service and return a
        bit-exact resumable :class:`SlotCheckpoint`.

        The device cache is authoritative for an occupied slot (host
        writebacks are copies), so the snapshot reads the valid KV rows
        and recurrent state straight from it.  The slot is then released
        exactly like a retirement from the paging side: its pooled KV
        blocks are queued for drop — flushed immediately when no KV pass
        is in flight (the single-scheduler admit point, which sits
        between fence and begin), else deferred to the upcoming fence,
        which in the tenancy tick order still lands before the slot's
        next occupant writes back its first block."""
        req = self.slot_req[slot]
        if req is None:
            raise ValueError(f"slot {slot} is empty; nothing to preempt")
        valid = self._kv_valid(slot)
        kv = None
        if "kv" in self.cache and valid > 0:
            kv = dict(
                k=np.asarray(self.cache["kv"]["k"][:, slot, :, :valid]),
                v=np.asarray(self.cache["kv"]["v"][:, slot, :, :valid]))
        ssm = None
        if "ssm" in self.cache:
            ssm = {n: np.asarray(c[:, slot])
                   for n, c in self.cache["ssm"].items()}
        ckpt = SlotCheckpoint(req=req, slot_pos=int(self.slot_pos[slot]),
                              valid=int(valid), kv=kv, ssm=ssm)
        req.preemptions += 1
        self.slot_req[slot] = None
        self._slot_gen[slot] += 1
        self.preempt_count += 1
        if self.kv_table is not None:
            self.kv_table.preempt_release(
                slot, in_flight=self._inflight_kv is not None)
            self._kv_synced[slot] = 0
        return ckpt

    def restore(self, ckpt: SlotCheckpoint, slot: int) -> None:
        """Rebind a preempted request to a free slot and scatter its
        checkpointed state back — decode resumes from ``generated[-1]``,
        chunked prefill from its chunk frontier, bit-exactly for greedy
        sampling.  The host KV image is NOT written here: ``_kv_synced``
        restarts at 0 and the normal end-of-tick ``sync_kv_tick`` re-
        writes the completed blocks back (fresh writeback + fetch events,
        which the ``kv_pass_counters`` replay follows natively)."""
        if self.slot_req[slot] is not None:
            raise ValueError(f"slot {slot} is occupied")
        req = ckpt.req
        self.slot_req[slot] = req
        self.slot_pos[slot] = ckpt.slot_pos
        self._slot_gen[slot] += 1
        self.restore_count += 1
        if ckpt.kv is not None:
            k, v = self.cache["kv"]["k"], self.cache["kv"]["v"]
            hi = ckpt.valid
            k = k.at[:, slot, :, :hi].set(
                jnp.asarray(ckpt.kv["k"], k.dtype))
            v = v.at[:, slot, :, :hi].set(
                jnp.asarray(ckpt.kv["v"], v.dtype))
            self.cache["kv"] = dict(k=k, v=v)
        if ckpt.ssm is not None:
            self.cache["ssm"] = {
                n: c.at[:, slot].set(jnp.asarray(ckpt.ssm[n], c.dtype))
                for n, c in self.cache["ssm"].items()}
        if self.kv_table is not None:
            self._kv_synced[slot] = 0

    @property
    def pending(self) -> bool:
        return bool(self.waiting
                    or any(r is not None for r in self.slot_req))

    # -- tick primitives (driven by step() or by sched.Scheduler) -------------
    def _chunk_shape(self, req: Request, chunk: Optional[int] = None
                     ) -> Tuple[int, int, bool, int]:
        """(n_tokens, bucket, add_prefix, insert_pos) of the next chunk."""
        prefix = self.cfg.n_meta_tokens
        remaining = len(req.prompt) - req.prefill_pos
        if self._bucketed:
            n = min(chunk if chunk is not None else self.prefill_chunk,
                    remaining)
            bucket = _next_pow2(n)
            # never let the padded window spill past the cache: near the
            # boundary shrink to the largest power of two that still fits
            # (the chunk loop absorbs the rest next round), so every
            # compiled prefill shape stays a power of two even for
            # non-pow2 max_len
            avail = self.max_len - prefix - req.prefill_pos
            if bucket > avail:
                bucket = _pow2_floor(avail)
                n = min(bucket, remaining)
        else:
            n = remaining          # exact-length single shot (hybrid / moe)
            bucket = n
        first = req.prefill_pos == 0
        # prefix is prepended inside the step only on the first chunk; the
        # flag is pinned True for prefix-free models so it never forks the
        # jit cache
        add_prefix = first if prefix else True
        insert_pos = 0 if first else prefix + req.prefill_pos
        return n, bucket, add_prefix, insert_pos

    def prefill_tick(self, params: Any, complete: bool = False,
                     chunk: Optional[int] = None,
                     plan: Optional[Dict[int, int]] = None
                     ) -> List[Request]:
        """Advance every prefilling slot by one chunk (``complete=True``
        loops until all prompts are absorbed — the legacy single-tick
        prefill).  ``chunk`` overrides the engine's default pacing for
        this call only (the Scheduler threads its own), and must be a
        power of two.  ``plan`` ({slot: token allocation}) is the
        budgeted continuous-batching composition: only the listed slots
        prefill this call, each at its OWN allocation — slots the
        scheduler left out of the plan simply hold their frontier for a
        tick.  Slots whose prompt completes sample their first token at
        the request's own temperature.  Returns the requests that got
        their first token this call."""
        if complete and plan is not None:
            raise ValueError("plan= paces one scheduler tick; it cannot "
                             "be combined with complete=True")
        started: List[Request] = []
        while True:
            pending = [(i, r) for i, r in enumerate(self.slot_req)
                       if r is not None and r.prefill_pos < len(r.prompt)
                       and (plan is None or plan.get(i, 0) > 0)]
            if not pending:
                break
            groups: Dict[Tuple[int, bool],
                         List[Tuple[int, Request, int, int]]] = {}
            for i, r in pending:
                c = plan[i] if plan is not None else chunk
                n, bucket, add_prefix, pos = self._chunk_shape(r, c)
                groups.setdefault((bucket, add_prefix),
                                  []).append((i, r, n, pos))
            for (bucket, add_prefix), rows in groups.items():
                self._run_prefill_group(params, bucket, add_prefix, rows,
                                        started)
            if not complete:
                break
        return started

    def _kv_span_for(self, bucket: int,
                     rows: List[Tuple[int, Request, int, int]]
                     ) -> Optional[int]:
        """KV-cache span one prefill group must attend: the next power of
        two covering every row's ``insert_pos + bucket`` (plus the
        meta-token prefix on first chunks), clamped to ``max_len``.  None
        for families without a KV cache."""
        if "kv" not in self.cache:
            return None
        prefix = self.cfg.n_meta_tokens
        need = max((prefix if r.prefill_pos == 0 else 0) + pos + bucket
                   for _i, r, _n, pos in rows)
        return min(self.max_len, _next_pow2(need))

    def _run_prefill_group(self, params: Any, bucket: int, add_prefix: bool,
                           rows: List[Tuple[int, Request, int, int]],
                           started: List[Request]) -> None:
        if self.cfg.family == "moe":
            # expert capacity is contended across the FLATTENED batch, so
            # padding rows (or co-batched neighbors) could displace real
            # tokens' routing; prefill MoE slots one at a time (batch-1,
            # the old engine's exact semantics)
            for row in rows:
                self._run_prefill_rows(params, bucket, add_prefix, [row],
                                       1, started)
            return
        self._run_prefill_rows(params, bucket, add_prefix, rows, self.slots,
                               started)

    def _run_prefill_rows(self, params: Any, bucket: int, add_prefix: bool,
                          rows: List[Tuple[int, Request, int, int]],
                          k: int, started: List[Request]) -> None:
        with span("engine.prefill", self.tracer, self.trace_track,
                  bucket=bucket, rows=len(rows)):
            self._prefill_rows(params, bucket, add_prefix, rows, k, started)

    def _prefill_rows(self, params: Any, bucket: int, add_prefix: bool,
                      rows: List[Tuple[int, Request, int, int]],
                      k: int, started: List[Request]) -> None:
        kv_span = self._kv_span_for(bucket, rows)
        tokens = np.zeros((k, bucket), np.int32)
        slot_idx = np.zeros((k,), np.int32)
        pos_vec = np.zeros((k,), np.int32)
        lengths = np.zeros((k,), np.int32)
        for j in range(k):
            # rows beyond the group repeat the last row: the duplicate
            # scatter writes identical values, so padding the batch to a
            # fixed k keeps the jit cache keyed by bucket alone
            i, r, n, pos = rows[min(j, len(rows) - 1)]
            tokens[j, :n] = r.prompt[r.prefill_pos:r.prefill_pos + n]
            slot_idx[j] = i
            pos_vec[j] = pos
            lengths[j] = n
        fn = self._prefill_for_bucket(bucket, add_prefix, kv_span)
        if self._bucketed and "ssm" in self.cache:
            logits, self.cache = fn(params, jnp.asarray(tokens), self.cache,
                                    jnp.asarray(slot_idx),
                                    jnp.asarray(pos_vec),
                                    jnp.asarray(lengths))
        else:
            logits, self.cache = fn(params, jnp.asarray(tokens), self.cache,
                                    jnp.asarray(slot_idx),
                                    jnp.asarray(pos_vec))
        for j, (i, r, n, _pos) in enumerate(rows):
            r.prefill_pos += n
            if r.prefill_pos < len(r.prompt):
                continue                      # more chunks next tick
            with span("engine.sample", self.tracer, self.trace_track):
                self.key, sub = jax.random.split(self.key)
                tok = int(sample_token(logits[j, n - 1], sub,
                                       r.temperature))
            r.generated.append(tok)
            r.first_token_s = _now()
            self.slot_pos[i] = len(r.prompt) + self.cfg.n_meta_tokens
            started.append(r)
            if len(r.generated) >= r.max_new_tokens:
                self._retire(i)

    def decode_tick(self, params: Any) -> List[Request]:
        """One batched decode step over the decode-ready slots; per-slot
        sampling at each request's own temperature.  Slots that are empty
        or still prefilling park their write at the scratch row
        (max_len - 1), which real decoding never reaches and the cache-
        length mask never attends.  Returns the requests finished this
        tick."""
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and r.prefill_pos >= len(r.prompt)]
        if not active:
            return []
        with span("engine.decode", self.tracer, self.trace_track,
                  rows=len(active)):
            return self._decode_rows(params, active)

    def _decode_rows(self, params: Any, active: List[int]) -> List[Request]:
        tokens = np.zeros((self.slots, 1), np.int32)
        temps = np.zeros((self.slots,), np.float32)
        pos = np.full((self.slots,), self.max_len - 1, np.int32)
        for i in active:
            req = self.slot_req[i]
            tokens[i, 0] = req.generated[-1]
            temps[i] = req.temperature
            pos[i] = self.slot_pos[i]
        # a KV slot mid-prefill parks its write at the scratch row, but
        # recurrent state has no position to park at — the batched decode
        # would advance a chunk-prefilling SSM slot's state with a
        # garbage token.  Save those slots' state and put it back after.
        parked: List[int] = []
        if "ssm" in self.cache:
            parked = [i for i, r in enumerate(self.slot_req)
                      if r is not None and r.prefill_pos < len(r.prompt)]
            if parked:
                p_idx = jnp.asarray(parked)
                p_saved = jax.tree_util.tree_map(
                    lambda c: jnp.take(c, p_idx, axis=1),
                    self.cache["ssm"])
        logits, self.cache = self._decode(params, jnp.asarray(tokens),
                                          self.cache, jnp.asarray(pos))
        if parked:
            self.cache["ssm"] = jax.tree_util.tree_map(
                lambda c, s: c.at[:, p_idx].set(s),
                self.cache["ssm"], p_saved)
        with span("engine.sample", self.tracer, self.trace_track):
            self.key, sub = jax.random.split(self.key)
            toks = np.asarray(sample_token_batch(logits[:, -1], sub, temps))
        finished: List[Request] = []
        for i in active:
            req = self.slot_req[i]
            req.generated.append(int(toks[i]))
            self.slot_pos[i] += 1
            if len(req.generated) >= req.max_new_tokens:
                finished.append(self._retire(i))
            elif self.slot_pos[i] >= self.max_len - 1:
                # cache exhausted mid-request: partial service, not a
                # natural completion — flag it so deadline accounting can
                # tell the two apart
                req.truncated = True
                finished.append(self._retire(i))
        return finished

    def _retire(self, slot: int) -> Request:
        req = self.slot_req[slot]
        req.done = True
        req.finish_s = _now()
        self.finished.append(req)
        self.slot_req[slot] = None
        self._slot_gen[slot] += 1
        if self.kv_table is not None:
            self.kv_table.queue_drop(slot)
            self._kv_synced[slot] = 0
        return req

    # -- legacy FIFO loop -----------------------------------------------------
    def _admit(self) -> None:
        for i in self.free_slots():
            if not self.waiting:
                break
            self.assign(self.waiting.pop(0), i)

    def step(self) -> List[Request]:
        """One engine tick: stream pages, admit FIFO, full prefill for the
        fresh slots, batched decode, retire.  Returns the requests that
        finished this tick."""
        before = len(self.finished)
        params = self.tick_params()
        self._admit()
        self.prefill_tick(params, complete=True)
        self.decode_tick(params)
        self.sync_kv_tick()
        return self.finished[before:]

    def run_until_done(self, max_ticks: int = 10_000) -> List[Request]:
        """Serve until the queue drains; returns the requests completed by
        THIS call (``self.finished`` keeps the all-time list)."""
        done: List[Request] = []
        ticks = 0
        while self.pending:
            done += self.step()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("serving loop did not converge")
        return done
