"""Software-assisted virtual weight paging (paper §II-B2).

For networks whose packed weights exceed the resident budget (on Siracusa:
4 MiB MRAM + 4 MiB tile SRAM = two live pages), the neural memory subsystem
becomes a page cache over background memory.  A tiny page handler compares
each access's page index against the live-page registers; on a miss the FC
programs the IO-DMA to swap the page.  Because DNN weight access order is
*deterministic*, pages can be swapped **proactively**, hiding swap latency
behind compute.

TPU-native realization: layer-granular weight pages live in host memory
("off-chip flash"); a double-buffered prefetcher moves page k+1 host->HBM
while page k's layers execute.  The same schedule object also drives the
analytical stall model used by the memsys benchmarks.

Two streaming modes share one schedule and one set of counters:

  * :meth:`HostPagedStore.stream` — the synchronous pass (iterate pages
    in access order, prefetch one ahead);
  * :meth:`HostPagedStore.begin_pass` -> :class:`AsyncPageStream` — the
    *overlapped* pass: the whole fetch loop is kicked up front and runs
    while the caller computes; ``fence()`` joins at first use and splits
    the pass wall into *exposed* wait (blocked the caller) and *hidden*
    overlap, the measured counterpart of the analytical
    ``stall += swap - hidden`` identity (:func:`memsys.overlap_stall`).
"""

from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from collections import OrderedDict
from concurrent.futures import CancelledError, Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import packing, quantize
from repro.core.faults import (FaultsArg, PageChecksumError, PageFetchError,
                               PageFetchTimeout, ScheduleError,
                               TransientFetchFault, as_injector,
                               new_fault_counters)
from repro.core.placement import Placement, PlacementPlan, path_key, \
    wire_served_bits
from repro.core.weight_store import WeightStore, PackedParam, SIRACUSA_MRAM_BYTES

# Scale-group width of the intN page wire codec (weights per f32 scale).
PAGE_ENC_BLOCK = quantize.PAGE_SCALE_BLOCK


@dataclasses.dataclass(frozen=True)
class Page:
    """One unit of host->device streaming.

    A page's "bytes" are deliberately NOT one number:

      * ``nbytes``      — *device* bytes: the packed device-format payload
        the page occupies while cached (what the pool budget charges);
      * ``wire_nbytes`` — *wire* bytes: what actually crosses the
        host->device link per swap — the encoded payload plus the scales
        that travel with it (drives stall predictions);
      * ``raw_nbytes``  — the fp32-dense-equivalent bytes an *unencoded*
        fp stream would have moved (``== wire_nbytes`` for the ``"fp"``
        encoding, which declares no compression).

    ``encoding`` is the wire encoding shared by every param on the page
    (:attr:`repro.core.placement.Placement.page_encoding`); mixed
    encodings never share a page, so scales stay with their payload.
    """
    index: int
    param_names: Tuple[str, ...]
    nbytes: int
    wire_nbytes: Optional[int] = None
    raw_nbytes: Optional[int] = None
    encoding: str = "fp"
    # CRC32 over the page's wire image (the ECC analogue of the At-MRAM
    # read path): a chain over the member params' own wire checksums,
    # stamped by build_pages(host=...) and verified by the fetch path
    # BEFORE decode/install.  None = unchecksummed (no host image given).
    crc32: Optional[int] = None

    def __post_init__(self):
        if self.wire_nbytes is None:
            object.__setattr__(self, "wire_nbytes", self.nbytes)
        if self.raw_nbytes is None:
            object.__setattr__(self, "raw_nbytes", self.wire_nbytes)


def page_sizes(pages: Sequence[Page]) -> List[Tuple[int, int, int]]:
    """``[(device, wire, raw), ...]`` byte triples in page order — the
    form the counter-prediction replays (:func:`shared_pass_counters` /
    :func:`kv_pass_counters`) take so their byte counters are exact in
    wire bytes while admission still charges device bytes."""
    return [(p.nbytes, p.wire_nbytes, p.raw_nbytes) for p in pages]


def _param_page_sizes(p: PackedParam, placement: Optional[Placement]
                      ) -> Tuple[str, int, int, int]:
    """(encoding, device, wire, raw) bytes for one paged param.

    Device bytes are the packed device payload (the pool-budget
    convention shared with ``plan_for_budget``'s resident accounting).
    Wire bytes add the scales — per-channel for the verbatim/identity
    encodings, per-block for a re-encoded page (the closed form
    :func:`repro.core.memsys.encoded_wire_bytes`).  Raw bytes are the
    fp32 dense equivalent for intN encodings and equal wire for fp.
    """
    dev = p.nbytes_packed
    n_weights = 1
    for d in p.orig_shape:
        n_weights *= int(d)
    enc = placement.page_encoding if placement is not None else "fp"
    page_bits = placement.page_bits if placement is not None else None
    scale_nb = int(np.prod(p.scale.shape)) * 4
    if page_bits is None or page_bits == p.bits:
        # verbatim device-format stream (fp), or run-quantized identity:
        # the wire form IS the device form (+ its per-channel scales)
        wire = dev + scale_nb
        raw = wire if page_bits is None else n_weights * 4
        return enc, dev, wire, raw
    from repro.core.memsys import encoded_wire_bytes
    rows = n_weights // int(p.orig_shape[-1])
    wire = encoded_wire_bytes(rows, int(p.orig_shape[-1]), page_bits,
                              PAGE_ENC_BLOCK)
    return enc, dev, wire, n_weights * 4


def page_crc(host_params: Sequence["HostParam"]) -> Optional[int]:
    """Chain the member params' wire CRCs into one page-level checksum.

    Chaining the 4-byte CRC words (rather than re-hashing the concatenated
    payloads) lets the fetch path verify per-param buffers it already
    holds without materialising one contiguous wire image."""
    acc = 0
    for hp in host_params:
        if hp is None or hp.crc32 is None:
            return None
        acc = zlib.crc32(int(hp.crc32).to_bytes(4, "little"), acc)
    return acc & 0xFFFFFFFF


def build_pages(store: WeightStore, page_bytes: int = SIRACUSA_MRAM_BYTES,
                order: Optional[Sequence[str]] = None,
                plan: Optional[PlacementPlan] = None,
                host: Optional[Dict[str, "HostParam"]] = None) -> List[Page]:
    """Greedy first-fit pagination preserving access (layer) order.

    Keeping pages contiguous in access order is what makes proactive
    prefetch a *static* schedule — the paper's "typically deterministic
    weight access pattern".

    When ``plan`` is given, only its ``paged`` parameters are paginated;
    the plan's resident hot set stays pinned outside the page cache (the
    §II-B2 split between live MRAM contents and background pages).  Each
    param's placement also fixes its wire *encoding*; params of different
    encodings never share a page (a page is decoded as one unit, and its
    scales travel inside its payload), so an encoding change closes the
    current page even when bytes would still fit.

    When ``host`` is given (the store's :class:`HostParam` wire images,
    fp and encoded alike), each page is stamped with a CRC32 over its
    wire bytes (:func:`page_crc`) and the fetch path verifies it before
    installing the page — corruption on the link re-fetches instead of
    silently decoding garbage.
    """
    names = list(order) if order is not None else list(store.params.keys())
    if plan is not None:
        names = [n for n in names if plan.placement_for(n).paged]
    pages: List[Page] = []
    cur: List[str] = []
    cur_dev = cur_wire = cur_raw = 0
    cur_enc = "fp"

    def _close():
        nonlocal cur, cur_dev, cur_wire, cur_raw
        crc = (page_crc([host.get(n) for n in cur])
               if host is not None else None)
        pages.append(Page(len(pages), tuple(cur), cur_dev, cur_wire,
                          cur_raw, cur_enc, crc))
        cur, cur_dev, cur_wire, cur_raw = [], 0, 0, 0

    for name in names:
        placement = plan.placement_for(name) if plan is not None else None
        enc, dev, wire, raw = _param_page_sizes(store.params[name],
                                                placement)
        if dev > page_bytes:
            where = (f"plan path {name!r} -> {placement.scenario}/"
                     f"{placement.weight_bits}b/{enc}" if placement
                     is not None else f"param {name!r} ({enc})")
            raise ValueError(
                f"{where}: {dev} B packed exceeds page size {page_bytes} B;"
                f" set page_bytes >= {dev} or split the parameter")
        if cur and (cur_dev + dev > page_bytes or enc != cur_enc):
            _close()
        cur.append(name)
        cur_enc = enc
        cur_dev += dev
        cur_wire += wire
        cur_raw += raw
    if cur:
        _close()
    return pages


@dataclasses.dataclass
class PageScheduleEntry:
    page: int
    prefetch_next: Optional[int]     # page to start swapping in while this runs
    evicts: Optional[int]            # page slot being overwritten


@dataclasses.dataclass
class StallModel:
    """Analytical stall accounting for a paged execution.

    swap_time(page)   = page.wire_nbytes / swap_bandwidth — the link moves
    the page's *wire* form (encoded payload + scales), not its decoded
    device footprint, so a compressed cold page stalls ~bits/32 of its fp
    cost.  compute_time(page) given by the caller per page; a swap started
    at the beginning of page k's compute hides min(compute_k, swap_{k+1}).
    """
    swap_bandwidth_bytes_per_s: float

    def run(self, pages: Sequence[Page],
            compute_time_s: Sequence[float]) -> Dict[str, float]:
        from repro.core.memsys import overlap_stall
        assert len(pages) == len(compute_time_s)
        total_compute = float(sum(compute_time_s))
        stall = 0.0
        # first page: cold miss, full swap cost
        stall += pages[0].wire_nbytes / self.swap_bandwidth_bytes_per_s
        for k in range(1, len(pages)):
            swap = pages[k].wire_nbytes / self.swap_bandwidth_bytes_per_s
            stall += overlap_stall(swap, compute_time_s[k - 1])["exposed_s"]
        return dict(total_compute_s=total_compute, stall_s=stall,
                    total_s=total_compute + stall,
                    stall_fraction=stall / max(total_compute + stall, 1e-12))


def make_schedule(n_pages: int, resident_slots: int = 2) -> List[PageScheduleEntry]:
    """Static proactive-prefetch schedule over a linear page access order.

    With a single live slot there is nowhere to double-buffer: prefetching
    page k+1 would evict the in-use page k (the schedule the old code
    emitted, which ``validate_schedule`` rightly rejects).  Single-slot
    passes therefore disable proactive prefetch and demand-fetch every
    page, evicting the previous one first — ``pass_counters`` then
    predicts ``swaps == misses == n_pages``.
    """
    if resident_slots < 1:
        raise ValueError(f"resident_slots must be >= 1, got {resident_slots}")
    entries: List[PageScheduleEntry] = []
    if resident_slots == 1:
        for k in range(n_pages):
            entries.append(PageScheduleEntry(
                page=k, prefetch_next=None,
                evicts=k - 1 if k > 0 else None))
        return entries
    for k in range(n_pages):
        nxt = k + 1 if k + 1 < n_pages else None
        # with S slots, prefetching page k+1 evicts page k+1-S
        ev = (k + 1 - resident_slots) if (nxt is not None and k + 1 - resident_slots >= 0) else None
        entries.append(PageScheduleEntry(page=k, prefetch_next=nxt, evicts=ev))
    return entries


def validate_schedule(entries: Sequence[PageScheduleEntry],
                      resident_slots: int = 2) -> None:
    """Invariants (property-tested): every page resident before use, the
    in-use page is never evicted, residency never exceeds the slot count.

    Violations raise :class:`repro.core.faults.ScheduleError` (with the
    offending page attached) — a *programming* error, distinct from the
    fault-path :class:`~repro.core.faults.PageFetchError` family a caller
    may want to retry or degrade on."""
    resident: List[int] = []
    for e in entries:
        if e.page not in resident:
            resident.append(e.page)      # demand fetch (cold miss)
        if e.evicts is not None:
            if e.evicts == e.page:
                raise ScheduleError(
                    f"schedule evicts the in-use page {e.page}",
                    page=e.page)
            if e.evicts in resident:
                resident.remove(e.evicts)
        if e.prefetch_next is not None and e.prefetch_next not in resident:
            resident.append(e.prefetch_next)
        if len(resident) > resident_slots:
            raise ScheduleError(
                f"residency {resident} exceeds {resident_slots} slots at "
                f"page {e.page}", page=e.page)


class SharedPagePool:
    """One device-bytes budget shared by every tenant's paged store.

    The §V concurrent-workload story: N models (hand tracking, gaze, an
    assistant LM) share ONE memory hierarchy, so their cold pages must
    contend for one pool of device bytes rather than each model assuming
    a private cache.  Members are :class:`HostPagedStore` instances that
    register under a model name; every page any member fetches is admitted
    here, and admission evicts least-recently-used pages of *other* models
    until the new page fits (the fetching model's own pages are never
    evicted mid-pass — its live window must survive).  A page still cached
    from an earlier pass satisfies a re-fetch without a host->device swap
    (a *pool hit*), so the counters expose exactly the cross-model
    contention: a tenant that fits alone starts thrashing when a
    co-tenant's working set squeezes it out.

    All bookkeeping is deterministic for a given pass order even when the
    passes are *overlapped* (:meth:`HostPagedStore.begin_pass`): every
    member store routes its page fetches through the pool's single shared
    fetch worker, so fetches execute serialized in begin order — the same
    lookup/admit sequence the sequential sync passes produce, which is why
    the per-model counters follow the static :func:`shared_pass_counters`
    prediction exactly with or without async overlap.
    """

    def __init__(self, budget_bytes: int):
        if budget_bytes <= 0:
            raise ValueError(f"budget_bytes must be > 0, got {budget_bytes}")
        self.budget_bytes = int(budget_bytes)
        self.members: "OrderedDict[str, HostPagedStore]" = OrderedDict()
        self._lock = threading.RLock()
        # (model, page) -> (nbytes, wire_nbytes, {name: PackedParam});
        # insertion/touch order IS the LRU order (front = coldest)
        self._cache: "OrderedDict[Tuple[str, int], Tuple[int, int, Dict[str, PackedParam]]]" = OrderedDict()
        self.live_bytes = 0           # device bytes held (what budget charges)
        self.live_wire_bytes = 0      # wire bytes those pages cost to re-swap
        self.counters: Dict[str, Dict[str, Any]] = {}
        # every member event in BEGIN order — which, because all member
        # fetches funnel through the single worker below, is also the
        # order the pool actually executes them in.  Events are
        #   ("pass", model)                       one full weight pass
        #   ("kv", model, ((page, nbytes), ...))  one KV fetch batch
        #   ("kvdrop", model, (page, ...))        slot-reuse invalidation
        # — the exact sequence :func:`kv_pass_counters` replays (and,
        # filtered to weight passes, the ``passes=`` argument
        # :func:`shared_pass_counters` needs), even when live submissions
        # make tenants begin out of registration rotation (an idle tenant
        # demand-begins only when it next ticks)
        self.events: List[Tuple] = []
        # ONE fetch worker for every member store: overlapped passes of
        # different tenants serialize here in begin order, keeping the
        # pool's lookup/admit sequence identical to the sync pass order
        self._exec = ThreadPoolExecutor(max_workers=1)
        # models whose pass fetches are still in flight — the async
        # extension of the "fetcher's own pages are protected" guard:
        # admit() never evicts pages of a model that is mid-fetch, so an
        # overlapped pass's live window survives co-tenant admissions
        self._active_fetch: set = set()
        # opt-in chrome-trace hook (duck-typed — see serving.trace; set
        # by ServingEngine.set_tracer): evictions become instant events,
        # live_bytes a counter track
        self.tracer = None

    def register(self, name: str, store: Any) -> None:
        """Join the pool.  ``store`` is a :class:`HostPagedStore` (weight
        pages) or a :class:`KVPageTable` (KV-cache pages) — both expose
        ``swap_count`` / ``miss_count`` / ``pages`` / ``close``, and both
        kinds of page contend for the SAME budget (one eviction domain)."""
        with self._lock:
            if name in self.members:
                raise ValueError(f"model {name!r} already joined this pool")
            self.members[name] = store
            self.counters[name] = dict(pool_hits=0, evicted=0,
                                       exposed_s=0.0, hidden_s=0.0)

    @property
    def pass_log(self) -> List[str]:
        """One entry per full WEIGHT streaming pass in begin order — the
        ``passes=`` view of :attr:`events` that ``shared_pass_counters``
        consumes (KV batches carry their own event kind)."""
        with self._lock:
            return [m for kind, m, *_rest in self.events if kind == "pass"]

    def log_event(self, *event) -> None:
        with self._lock:
            self.events.append(tuple(event))

    def _pass_begin(self, name: str) -> None:
        """Mark ``name``'s pass fetches in flight (eviction-protected)."""
        with self._lock:
            self._active_fetch.add(name)

    def _pass_end(self, name: str) -> None:
        """Release the fetch guard (idempotent — also called on cancel)."""
        with self._lock:
            self._active_fetch.discard(name)

    def lookup(self, name: str, page_idx: int
               ) -> Optional[Dict[str, PackedParam]]:
        """Device params for a page still cached from an earlier fetch, or
        None (the caller must then swap host->device and :meth:`admit`)."""
        with self._lock:
            key = (name, page_idx)
            entry = self._cache.get(key)
            if entry is None:
                return None
            self._cache.move_to_end(key)
            self.counters[name]["pool_hits"] += 1
            return entry[2]

    def admit(self, name: str, page_idx: int, nbytes: int,
              params: Dict[str, PackedParam],
              wire_nbytes: Optional[int] = None,
              raw_nbytes: Optional[int] = None) -> None:
        """Cache a freshly swapped page under the shared budget, evicting
        other models' LRU pages to make room.  If the budget cannot fit
        the page even after evicting every foreign page (the fetching
        model's own pages are protected), the page is simply not cached —
        it lives only as long as the pass's live window references it, and
        the next access swaps again.

        ``nbytes`` is the page's decoded *device* footprint — what the
        budget charges and eviction frees.  ``wire_nbytes`` (default:
        ``nbytes``) is what the swap moved across the link; the pool only
        tracks it (``live_wire_bytes``, the ``pool_bytes`` trace counter)
        — admission decisions never depend on it.  ``raw_nbytes`` is
        accepted for signature symmetry with the :class:`Page` ledger."""
        del raw_nbytes               # per-member ledgers live in the stores
        with self._lock:
            if nbytes > self.budget_bytes:
                return              # can NEVER fit: don't flush co-tenants
            wire = int(wire_nbytes) if wire_nbytes is not None else nbytes
            tr = self.tracer
            for key in list(self._cache.keys()):
                if self.live_bytes + nbytes <= self.budget_bytes:
                    break
                victim_model, victim_page = key
                if victim_model == name or victim_model in self._active_fetch:
                    # the fetching model's own pages — and any model whose
                    # overlapped pass is still mid-fetch — keep their live
                    # window intact
                    continue
                freed, freed_wire, _ = self._cache.pop(key)
                self.live_bytes -= freed
                self.live_wire_bytes -= freed_wire
                self.counters[victim_model]["evicted"] += 1
                if tr is not None:
                    tr.instant("evict", track="io", model=victim_model,
                               page=victim_page, nbytes=freed, by=name)
            if self.live_bytes + nbytes <= self.budget_bytes:
                self._cache[(name, page_idx)] = (nbytes, wire, params)
                self.live_bytes += nbytes
                self.live_wire_bytes += wire
            if tr is not None:
                tr.counter("pool_bytes", track="io", bytes=self.live_bytes,
                           wire_bytes=self.live_wire_bytes)

    def invalidate(self, name: str, page_idx: int) -> bool:
        """Drop ``name``'s cached page (owner-initiated, e.g. a KV block
        whose batch slot was handed to a new request).  Unlike pressure
        eviction this does NOT touch the victim's ``evicted`` counter —
        the owner declared the bytes dead; returns whether the page was
        present."""
        with self._lock:
            entry = self._cache.pop((name, page_idx), None)
            if entry is None:
                return False
            self.live_bytes -= entry[0]
            self.live_wire_bytes -= entry[1]
            if self.tracer is not None:
                self.tracer.counter("pool_bytes", track="io",
                                    bytes=self.live_bytes,
                                    wire_bytes=self.live_wire_bytes)
            return True

    def add_stall(self, name: str, exposed_s: float,
                  hidden_s: float = 0.0) -> None:
        """Account one pass's stall split for ``name``: ``exposed_s`` is
        the wait that actually blocked a tick, ``hidden_s`` the stream
        time overlapped behind compute (sync passes hide nothing)."""
        with self._lock:
            self.counters[name]["exposed_s"] += float(exposed_s)
            self.counters[name]["hidden_s"] += float(hidden_s)

    def summary(self) -> Dict[str, Any]:
        """Per-model swap/miss/pool-hit/evict counters, the wire/raw
        streamed-bytes ledger, and the exposed/hidden stall split + pool
        state — the ``shared_pool`` section of the metrics/v8 JSON.  The
        stall seconds here are the pool's per-model *view* of the same
        wall time the engines report in their own ``paging`` sections;
        totals must sum ONE of the two, never both.  ``bytes_streamed_*``
        are the member stores' own swap ledgers (wire = what crossed the
        link, raw = the fp32-equivalent an unencoded stream would have
        moved), surfaced here so one summary shows every tenant's
        compression ratio against one budget."""
        with self._lock:
            models = {}
            for name, store in self.members.items():
                c = self.counters[name]
                models[name] = dict(
                    swaps=store.swap_count, misses=store.miss_count,
                    pool_hits=c["pool_hits"], evicted=c["evicted"],
                    exposed_s=c["exposed_s"], hidden_s=c["hidden_s"],
                    n_pages=len(store.pages),
                    bytes_streamed_wire=getattr(store, "bytes_streamed_wire",
                                                0),
                    bytes_streamed_raw=getattr(store, "bytes_streamed_raw",
                                               0))
            return dict(
                budget_bytes=self.budget_bytes,
                live_bytes=self.live_bytes,
                live_wire_bytes=self.live_wire_bytes,
                cached_pages=len(self._cache),
                evictions=sum(c["evicted"] for c in self.counters.values()),
                bytes_streamed_wire=sum(m["bytes_streamed_wire"]
                                        for m in models.values()),
                bytes_streamed_raw=sum(m["bytes_streamed_raw"]
                                       for m in models.values()),
                models=models)

    def close(self, wait: bool = True) -> None:
        with self._lock:
            members = list(self.members.values())
            self._cache.clear()
            self.live_bytes = 0
            self.live_wire_bytes = 0
        for store in members:
            store.close(wait=wait)
        self._exec.shutdown(wait=wait, cancel_futures=not wait)

    def __enter__(self) -> "SharedPagePool":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def shared_pass_counters(page_nbytes: Dict[str, Sequence[int]],
                         budget_bytes: int, resident_slots: int = 2,
                         passes: Optional[Sequence[str]] = None,
                         ticks: int = 1) -> Dict[str, Dict[str, int]]:
    """Static per-model counter prediction for SharedPagePool streaming.

    ``page_nbytes`` maps each model name to its page sizes in access
    order — plain device-byte ints, or ``(device, wire, raw)`` triples
    (:func:`page_sizes`) to also predict each model's streamed
    ``bytes_wire``/``bytes_raw`` ledger exactly; ``passes`` is the exact
    sequence of full streaming passes (one
    entry per model tick, e.g. ``MultiScheduler.pass_log``), defaulting to
    ``ticks`` round-robin rounds over the models in dict order.  The
    actual replay — demand/prefetch fetch order per :func:`make_schedule`,
    pool lookup before swap, LRU admission that never evicts the fetching
    model's pages — lives in :func:`kv_pass_counters` (one copy of the
    admit semantics, shared with the KV event replay); this is its
    weights-only view, so the runtime ``SharedPagePool.summary()``
    counters must match it pass for pass (the multi-tenant analogue of
    :func:`pass_counters`)."""
    order = list(page_nbytes.keys())
    if passes is None:
        passes = [m for _ in range(ticks) for m in order]
    out = kv_pass_counters(page_nbytes, budget_bytes,
                           [("pass", m) for m in passes],
                           resident_slots=resident_slots)
    for m in order:
        out.setdefault(m, dict(swaps=0, misses=0, pool_hits=0, evicted=0,
                               dropped=0, bytes_wire=0, bytes_raw=0))
    # weight passes never drop pages; keep the historical key set
    return {m: {k: n for k, n in c.items() if k != "dropped"}
            for m, c in out.items()}


@dataclasses.dataclass
class HostParam:
    """Host-side ("background flash") image of ONE paged parameter, held
    in its page *wire* encoding.

    Two regimes, chosen by :attr:`identity`:

      * **identity** — ``page_bits`` is None (``"fp"``: stream the device
        format verbatim) or equals the param's own ``bits`` (the
        run-quantized case: the wire form IS the device form).  The
        payload is the device packed carrier, the scales the per-channel
        device scales; decode is a no-op.
      * **re-encoded** — the host keeps only blockwise-quantized
        ``page_bits`` levels (packed) + per-(row, ``PAGE_ENC_BLOCK``)
        f32 scales; :meth:`decode` reconstructs the per-channel device
        format at fetch: dequantize the blocks, re-quantize per channel
        at ``bits``, re-pack.  The round trip is deterministic, so a
        paged serve is bit-exact against a resident engine whose weights
        took the same trip (:func:`page_roundtrip_param`).
    """
    bits: int                         # device weight bits
    orig_shape: Tuple[int, ...]
    packed_shape: Tuple[int, ...]     # device carrier shape to rebuild
    scale_shape: Tuple[int, ...]      # device per-channel scale shape
    page_bits: Optional[int]          # wire bits (None = fp/verbatim)
    payload: np.ndarray
    scales: np.ndarray
    # CRC32 over (payload, scales) bytes — the param's share of its page's
    # wire checksum (:func:`page_crc`); stamped by encode_host_param
    crc32: Optional[int] = None

    @property
    def identity(self) -> bool:
        return self.page_bits is None or self.page_bits == self.bits

    @property
    def encoding(self) -> str:
        return "fp" if self.page_bits is None else f"int{self.page_bits}"

    @property
    def wire_nbytes(self) -> int:
        return int(self.payload.nbytes) + int(self.scales.nbytes)

    def wire_crc(self, payload: Optional[np.ndarray] = None,
                 scales: Optional[np.ndarray] = None) -> int:
        """CRC32 of the wire image — of the stored buffers, or of the
        buffers a fetch actually received (to verify before decode)."""
        payload = self.payload if payload is None else payload
        scales = self.scales if scales is None else scales
        crc = zlib.crc32(np.ascontiguousarray(payload).tobytes())
        crc = zlib.crc32(np.ascontiguousarray(scales).tobytes(), crc)
        return crc & 0xFFFFFFFF

    def decode(self, payload: Optional[np.ndarray] = None,
               scales: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Wire form -> device form ``(packed, scale)``, host-side.

        Identity encodings return the stored buffers untouched (zero
        decode cost — the fetch path device_puts them directly).  The
        optional ``payload``/``scales`` overrides decode a *transferred*
        copy of the wire buffers instead of the pristine host image — the
        fault-injection path uses this so a simulated in-flight bit-flip
        genuinely reaches the decode (and, absent checksums, the device)."""
        payload = self.payload if payload is None else payload
        scales = self.scales if scales is None else scales
        if self.identity:
            return payload, scales
        k = int(self.orig_shape[-1])
        levels = np.asarray(packing.unpack(payload, self.page_bits, k))
        dense = quantize.dequantize_blockwise(levels, scales,
                                              block=PAGE_ENC_BLOCK)
        qt = quantize.quantize_weights(dense, self.bits, channel_axis=0)
        packed = np.asarray(packing.pack(qt.values, self.bits))
        return (packed.reshape(self.packed_shape),
                np.asarray(qt.scale, np.float32).reshape(self.scale_shape))


def encode_host_param(p: PackedParam, page_bits: Optional[int]) -> HostParam:
    """Evacuate one paged param to its host wire image (see
    :class:`HostParam`).  For a re-encoded param the dense weights are
    reconstructed once (host-side, at store build) and blockwise-quantized
    to ``page_bits``; the original device carrier is NOT retained — the
    host truly holds only the compressed bytes the wire will move."""
    packed = np.asarray(p.packed)
    scale = np.asarray(p.scale)
    hp = HostParam(bits=p.bits, orig_shape=tuple(p.orig_shape),
                   packed_shape=tuple(packed.shape),
                   scale_shape=tuple(scale.shape),
                   page_bits=page_bits, payload=packed, scales=scale)
    if not hp.identity:
        k = int(p.orig_shape[-1])
        levels = np.asarray(packing.unpack(packed.reshape(-1,
                                                          packed.shape[-1]),
                                           p.bits, k), np.float32)
        dense = levels * scale.reshape(-1, 1).astype(np.float32)
        wire_levels, wire_scales = quantize.quantize_blockwise(
            dense, page_bits, block=PAGE_ENC_BLOCK)
        hp.payload = np.asarray(packing.pack(wire_levels, page_bits))
        hp.scales = wire_scales
    hp.crc32 = hp.wire_crc()
    return hp


def page_roundtrip_param(p: PackedParam, page_bits: Optional[int]
                         ) -> PackedParam:
    """One param encode->decode through the page wire codec — the exact
    transform :meth:`HostPagedStore._fetch_page` applies, exposed so a
    *resident* reference engine can pre-distort its weights identically
    and a lossy-encoded paged serve becomes bit-exact against it."""
    packed, scale = encode_host_param(p, page_bits).decode()
    return PackedParam(packed=packed, scale=scale, bits=p.bits,
                       orig_shape=tuple(p.orig_shape))


def page_crc_of_buffers(wire: Sequence[Tuple[str, "HostParam", np.ndarray,
                                             np.ndarray]]) -> int:
    """Page CRC recomputed from the buffers a fetch actually received —
    the verify-side counterpart of :func:`page_crc`."""
    acc = 0
    for _name, hp, payload, scales in wire:
        c = hp.wire_crc(payload=payload, scales=scales)
        acc = zlib.crc32(c.to_bytes(4, "little"), acc)
    return acc & 0xFFFFFFFF


def _span(name: str, tracer: Any, track: str, **args):
    """:func:`repro.serving.trace.span`, imported at first use: the
    serving package imports this module."""
    from repro.serving.trace import span
    return span(name, tracer, track, **args)


def retry_fetch(store: Any, idx: int, attempt_fn: Callable[[int], Any]) -> Any:
    """Run one logical page fetch under the store's retry policy.

    ``attempt_fn(attempt)`` performs attempt number ``attempt`` (0-based)
    and either returns the fetched result or raises
    :class:`~repro.core.faults.TransientFetchFault` (injected failure) /
    :class:`~repro.core.faults.PageChecksumError` (wire corruption caught
    before install).  Both retry with the plan's bounded deterministic
    exponential backoff; exhausting ``max_attempts`` raises a typed
    :class:`~repro.core.faults.PageFetchError` naming model/page/attempts.
    Runs on the fetch worker thread — the backoff sleeps are I/O latency,
    visible to ``fence()`` like any other stream time.  Counters land on
    ``store.fault_counters``; a store with no fault plan has a budget of
    one attempt (nothing injects faults into it, and a genuine checksum
    mismatch would re-read the same host bytes anyway)."""
    inj = store.faults
    plan = inj.plan if inj is not None else None
    max_attempts = plan.max_attempts if plan is not None else 1
    attempt = 0
    while True:
        try:
            return attempt_fn(attempt)
        except (TransientFetchFault, PageChecksumError) as e:
            if isinstance(e, TransientFetchFault):
                store.fault_counters["injected"] += 1
                if store.tracer is not None:
                    store.tracer.instant("fault", track="io",
                                         model=store.name, page=idx,
                                         kind="fail", attempt=attempt)
            else:
                store.fault_counters["checksum_failures"] += 1
                store.fault_counters["refetches"] += 1
            attempt += 1
            if attempt >= max_attempts:
                raise PageFetchError(model=store.name, page=idx,
                                     attempts=attempt, last_error=e) from e
            store.fault_counters["retries"] += 1
            if store.tracer is not None:
                store.tracer.instant("retry", track="io", model=store.name,
                                     page=idx, attempt=attempt,
                                     cause=type(e).__name__)
            time.sleep(plan.backoff(attempt))


class HostPagedStore:
    """Runtime paged weight streaming: host RAM = background flash, device
    HBM = the two live pages.  Double-buffered with a worker thread — the
    software analogue of the FC+IO-DMA proactive swap.

    With a ``plan``, the plan's resident parameters are uploaded once and
    stay pinned in ``self.resident`` (the live MRAM image); only the paged
    parameters flow through the page cache — each held host-side in its
    plan-assigned wire encoding (:class:`HostParam`) and decoded back to
    the device format at fetch, so a quantized cold page crosses the link
    compressed.  ``bytes_streamed_wire`` / ``bytes_streamed_raw``
    accumulate per swap what the link moved vs the fp32-equivalent an
    unencoded stream would have moved; ``decode_s`` is the cumulative
    fetch-side decode wall time.

    With a ``pool`` (:class:`SharedPagePool`), the store *joins* a shared
    device-bytes budget under ``name``: every fetched page is admitted to
    the pool (cross-model LRU eviction), and pages still pooled from an
    earlier pass are reused without a host->device swap.

    With ``faults`` (a :class:`~repro.core.faults.FaultPlan` or a shared
    :class:`~repro.core.faults.FaultInjector`), every fetch attempt runs
    under seeded fault injection; transient failures and checksum
    mismatches retry with bounded deterministic backoff
    (:func:`retry_fetch`), and ``fault_counters`` ledgers what was
    injected and survived.  Because every page carries a CRC32 over its
    wire bytes and a corrupted fetch re-reads the pristine host image,
    decode output stays bit-exact vs the fault-free run for any plan
    within the retry budget.
    """

    def __init__(self, store: WeightStore, page_bytes: int,
                 device: Optional[jax.Device] = None,
                 plan: Optional[PlacementPlan] = None,
                 pool: Optional[SharedPagePool] = None,
                 name: str = "default",
                 faults: FaultsArg = None):
        self.store = store
        self.plan = plan
        self.pool = pool
        self.name = name
        self.device = device or jax.devices()[0]
        # evacuate packed params to the host wire image (off-chip flash)
        # BEFORE paginating, so build_pages can stamp each page's CRC32
        # over the wire bytes it will actually move
        self._host: Dict[str, HostParam] = {}
        self.resident: Dict[str, PackedParam] = {}
        for name, p in store.params.items():
            if plan is not None and not plan.placement_for(name).paged:
                self.resident[name] = PackedParam(
                    packed=jax.device_put(p.packed, self.device),
                    scale=jax.device_put(p.scale, self.device),
                    bits=p.bits, orig_shape=p.orig_shape)
            else:
                pb = (plan.placement_for(name).page_bits
                      if plan is not None else None)
                self._host[name] = encode_host_param(p, pb)
        self.pages = build_pages(store, page_bytes, plan=plan,
                                 host=self._host)
        # wire-serve (plan.wire_serve=True): cold params whose fetch skips
        # the host decode entirely — the blockscale matmul consumes the
        # page's wire form directly (placement.wire_served_bits is the
        # single predicate the store and the model's `linear` both obey)
        self.wire_served = {n for n in self._host
                            if wire_served_bits(plan, n) is not None}
        self._pool = ThreadPoolExecutor(max_workers=1)
        self.swap_count = 0
        self.miss_count = 0
        self.bytes_streamed_wire = 0
        self.bytes_streamed_raw = 0
        self.decode_s = 0.0
        self.decode_skipped_bytes = 0
        self.faults = as_injector(faults)
        self.fault_counters = new_fault_counters()
        self._closed = False
        self._live: Dict[int, Dict[str, PackedParam]] = {}
        # opt-in chrome-trace hook (ServingEngine.set_tracer): per-page
        # fetch spans on the "io" track, emitted from the fetch worker
        self.tracer = None
        if pool is not None:
            pool.register(self.name, self)

    @property
    def _fetch_exec(self) -> ThreadPoolExecutor:
        """The worker page fetches run on: the shared pool worker for pool
        members (so overlapped tenant passes serialize in begin order and
        the pool bookkeeping stays deterministic), the store's private
        worker otherwise."""
        return self._pool if self.pool is None else self.pool._exec

    @property
    def fetch_track(self) -> str:
        """The Chrome track of this store's fetch spans: one per store,
        since each store's fetches run on one worker thread."""
        return f"io:{self.name}"

    def _fetch_page(self, idx: int, pass_id: int = 0
                    ) -> Dict[str, PackedParam]:
        """One page fetch on the worker, as a ``paging.fetch`` span;
        ``pass_id`` names the engine pass that submitted it (0: none)."""
        if self._closed:
            raise CancelledError(f"{self.name}: store closed before fetch "
                                 f"of page {idx} started")
        page = self.pages[idx]
        cached = (self.pool.lookup(self.name, idx)
                  if self.pool is not None else None)
        with _span("paging.fetch", self.tracer, self.fetch_track,
                   pass_id=pass_id, page=idx, wire_nbytes=page.wire_nbytes,
                   pool_hit=cached is not None):
            if cached is not None:       # pool hit: no host->device swap
                return cached
            out = retry_fetch(self, idx,
                              lambda attempt: self._fetch_page_once(
                                  idx, page, attempt))
            if self._closed:
                # close(wait=False) landed while this fetch was decoding:
                # drop the page instead of installing into a closed store
                raise CancelledError(f"{self.name}: store closed during "
                                     f"fetch of page {idx}")
            self.swap_count += 1
            self.bytes_streamed_wire += page.wire_nbytes
            self.bytes_streamed_raw += page.raw_nbytes
            if self.pool is not None:
                self.pool.admit(self.name, idx, page.nbytes, out,
                                wire_nbytes=page.wire_nbytes,
                                raw_nbytes=page.raw_nbytes)
            return out

    def _fetch_page_once(self, idx: int, page: Page,
                         attempt: int) -> Dict[str, PackedParam]:
        """One fetch attempt: inject faults, transfer the wire buffers,
        verify the page CRC *before* decoding, decode, device_put.

        Corruption (an injected bit-flip) lands on a transient copy of
        the wire payload — the pristine host image is never touched, so
        the retry a checksum mismatch triggers re-reads clean bytes."""
        inj = self.faults
        if inj is not None:
            self.fault_counters["injected"] += inj.pre_fetch(self.name, idx,
                                                             attempt)
        wire: List[Tuple[str, HostParam, np.ndarray, np.ndarray]] = []
        for name in page.param_names:
            hp = self._host[name]
            payload = hp.payload
            if inj is not None:
                flipped = inj.corrupt(self.name, idx, attempt,
                                      np.ascontiguousarray(payload).tobytes())
                if flipped is not None:
                    self.fault_counters["injected"] += 1
                    if self.tracer is not None:
                        self.tracer.instant("fault", track="io",
                                            model=self.name, page=idx,
                                            kind="bitflip", param=name,
                                            attempt=attempt)
                    payload = np.frombuffer(
                        flipped, dtype=payload.dtype).reshape(payload.shape)
            wire.append((name, hp, payload, hp.scales))
        if page.crc32 is not None:
            with _span("paging.crc", self.tracer, self.fetch_track):
                got = page_crc_of_buffers(wire)
            if got != page.crc32:
                raise PageChecksumError(model=self.name, page=idx,
                                        expected=page.crc32, got=got)
        out: Dict[str, PackedParam] = {}
        for name, hp, payload, scales in wire:
            if name in self.wire_served:
                # wire-serve fast path: ship the blockwise wire form
                # (packed page_bits levels + per-block scales) as-is; the
                # blockscale matmul expands it adjacent to the compute.
                # CRC already verified above, so corrupted wire bytes
                # never reach the device on this path either.
                self.decode_skipped_bytes += hp.wire_nbytes
                # the codec flattens to (rows, k); restore the device
                # carrier's leading dims (stacked-layer params scan over
                # the leading axis)
                lead = hp.packed_shape[:-1]
                with _span("paging.put", self.tracer, self.fetch_track):
                    out[name] = PackedParam(
                        packed=jax.device_put(payload.reshape(*lead, -1),
                                              self.device),
                        scale=jax.device_put(scales.reshape(*lead, -1),
                                             self.device),
                        bits=hp.page_bits, orig_shape=hp.orig_shape)
                continue
            t_dec = time.perf_counter()
            packed, scale = hp.decode(payload=payload, scales=scales)
            self.decode_s += time.perf_counter() - t_dec
            with _span("paging.put", self.tracer, self.fetch_track):
                out[name] = PackedParam(
                    packed=jax.device_put(packed, self.device),
                    scale=jax.device_put(scale, self.device),
                    bits=hp.bits, orig_shape=hp.orig_shape)
        return out

    def template_view(self) -> Dict[str, PackedParam]:
        """Device-format template leaves for every PAGED param — what the
        engine threads into its params tree so the jitted step traces the
        exact shapes/dtypes a streamed page will later fill.  Wire-served
        params present their WIRE buffers (leading dims restored to the
        device carrier's, as the fetch path does); everything else decodes
        the host image back to the device layout once, host-side."""
        view: Dict[str, PackedParam] = {}
        for name, hp in self._host.items():
            if name in self.wire_served:
                lead = hp.packed_shape[:-1]
                view[name] = PackedParam(
                    packed=hp.payload.reshape(*lead, -1),
                    scale=hp.scales.reshape(*lead, -1),
                    bits=hp.page_bits, orig_shape=hp.orig_shape)
                continue
            packed, scale = hp.decode()
            view[name] = PackedParam(packed=packed, scale=scale,
                                     bits=hp.bits, orig_shape=hp.orig_shape)
        return view

    def stream(self, resident_slots: int = 2) -> "PageStream":
        """(page, device params) in access order with proactive prefetch.

        Returns a :class:`PageStream` — iterate it directly, or use it as a
        context manager so breaking out mid-pass cancels/drains in-flight
        swaps instead of leaking them past interpreter teardown.  Each pass
        reclaims the live page slots on completion (the next inference
        starts from a cold page cache — what the 2-slot budget dictates for
        any network with more than ``resident_slots`` pages), so per-pass
        counters follow the static :func:`pass_counters` prediction.
        """
        return PageStream(self, resident_slots)

    def begin_pass(self, resident_slots: int = 2, pass_id: int = 0
                   ) -> "AsyncPageStream":
        """Kick ONE full overlapped streaming pass and return immediately.

        The whole double-buffered fetch loop is submitted to the fetch
        worker up front (demand/prefetch order and counters identical to
        :meth:`stream`), so host->device page traffic proceeds while the
        caller computes; :meth:`AsyncPageStream.fence` joins the futures
        at first use and splits the pass wall time into the *exposed*
        wait (time the caller actually blocked) and the *hidden* overlap
        — the §II-B2 proactive swap, realized across ticks instead of
        across pages.  Its fetch spans carry ``pass_id``."""
        return AsyncPageStream(self, resident_slots, pass_id)

    def close(self, wait: bool = True):
        """Shut the prefetch worker down.  ``wait=True`` (default) blocks
        until in-flight swaps finish — never leak a ``_fetch_page`` past
        interpreter teardown; ``wait=False`` cancels what it can instead.
        Either way the closed flag is raised FIRST, so a fetch already
        running on the worker (which ``cancel_futures`` cannot stop)
        aborts before installing its page into the store or pool."""
        self._closed = True
        self._pool.shutdown(wait=wait, cancel_futures=not wait)

    def __enter__(self) -> "HostPagedStore":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class PageStream:
    """One streaming pass over a :class:`HostPagedStore` — an iterable of
    ``(Page, {name: PackedParam})`` that is also a context manager.

    Closing (explicitly, via ``with``, or by exhausting the iterator)
    cancels or drains in-flight prefetches and reclaims the live page
    slots, so a consumer that stops early cannot leak a worker-thread
    fetch past teardown."""

    def __init__(self, store: HostPagedStore, resident_slots: int = 2):
        self._store = store
        self._sched = make_schedule(len(store.pages), resident_slots)
        self._inflight: Dict[int, Future] = {}
        if store.pool is not None:
            store.pool.log_event("pass", store.name)
        self._gen = self._iterate()

    def __iter__(self):
        return self._gen

    def __enter__(self) -> "PageStream":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def close(self):
        for fut in self._inflight.values():
            if not fut.cancel():
                try:
                    fut.result()    # already running: drain, don't leak
                except CancelledError:
                    pass            # store closed mid-fetch: nothing to keep
        self._inflight.clear()
        self._store._live.clear()   # slots reclaimed between passes
        self._gen.close()

    def _iterate(self):
        st = self._store
        try:
            for e in self._sched:
                if e.page in st._live:
                    page_params = st._live[e.page]
                elif e.page in self._inflight:
                    page_params = self._inflight.pop(e.page).result()
                    st._live[e.page] = page_params
                else:
                    st.miss_count += 1    # demand miss (cold start)
                    page_params = st._fetch_page(e.page)
                    st._live[e.page] = page_params
                if (e.prefetch_next is not None
                        and e.prefetch_next not in st._live):
                    self._inflight[e.prefetch_next] = st._fetch_exec.submit(
                        st._fetch_page, e.prefetch_next)
                if e.evicts is not None:
                    st._live.pop(e.evicts, None)
                yield st.pages[e.page], page_params
        finally:
            for fut in self._inflight.values():
                if not fut.cancel():
                    try:
                        fut.result()
                    except CancelledError:
                        pass        # store closed mid-fetch: drop the page
            self._inflight.clear()
            st._live.clear()


class AsyncPageStream:
    """One *overlapped* streaming pass over a :class:`HostPagedStore`.

    Construction (via :meth:`HostPagedStore.begin_pass`) submits every
    page fetch of the pass to the fetch worker in the exact order the
    synchronous :class:`PageStream` would perform them — same demand-miss
    accounting, same pool lookup/admit sequence, same swap counters; the
    only thing that changes is *when* the caller waits.  :meth:`fence`
    joins the futures at first use and records the stall split:

      * ``window_s``  — begin -> fence call: the compute the caller ran
        while the stream was in flight;
      * ``exposed_s`` — time the fence actually blocked (critical path);
      * ``hidden_s``  — stream wall time that genuinely overlapped the
        window: ``min(begin -> last-fetch-done, window)``;
      * ``swap_s``    — ``hidden_s + exposed_s``, the pass's full stream
        wall time, the traffic's cost wherever it lands.

    By construction ``exposed_s``/``hidden_s`` equal the analytical
    ``stall += swap - hidden`` identity of
    :func:`repro.core.memsys.overlap_stall` applied to (``swap_s``,
    ``window_s``) — tests assert the runtime against that closed form.

    For pool members the pass registers with the pool's fetch guard so
    co-tenant admissions cannot evict its in-flight pages mid-fetch; the
    guard releases automatically when the last fetch settles (finished OR
    cancelled), and :meth:`close` cancels/drains an unfenced pass without
    leaking worker fetches or guard entries.
    """

    def __init__(self, store: HostPagedStore, resident_slots: int = 2,
                 pass_id: int = 0):
        self._store = store
        self._result: Optional[Dict[str, PackedParam]] = None
        self._closed = False
        self.swap_s = 0.0
        self.window_s = 0.0
        self.exposed_s = 0.0
        self.hidden_s = 0.0
        pool = store.pool
        self._t_ready: Optional[float] = None   # last fetch completion
        self._t_begin = time.perf_counter()
        # replay the schedule's live/inflight bookkeeping so demand-miss
        # counting matches the sync pass, then submit EVERY fetch up
        # front; the single fetch worker executes them in this exact
        # order, which is the order PageStream fetches in
        self._futures: List[Tuple[int, Future]] = []
        self._marks: List[Future] = []
        if pool is not None:
            pool.log_event("pass", store.name)
            # the eviction guard must bracket pass EXECUTION, not pass
            # submission: marker tasks on the serialized fetch worker set
            # the guard right before this pass's first fetch runs and
            # release it right after its last — a begun-but-still-queued
            # co-tenant pass is NOT yet protected, so eviction decisions
            # (and counters) stay identical to the sequential sync order
            self._marks.append(
                store._fetch_exec.submit(pool._pass_begin, store.name))
        live: set = set()
        inflight: set = set()
        for e in make_schedule(len(store.pages), resident_slots):
            if e.page in live:
                pass
            elif e.page in inflight:
                inflight.discard(e.page)
                live.add(e.page)
            else:
                store.miss_count += 1        # demand miss (cold start)
                self._futures.append(
                    (e.page, store._fetch_exec.submit(store._fetch_page,
                                                      e.page, pass_id)))
                live.add(e.page)
            if e.prefetch_next is not None and e.prefetch_next not in live:
                inflight.add(e.prefetch_next)
                self._futures.append(
                    (e.prefetch_next,
                     store._fetch_exec.submit(store._fetch_page,
                                              e.prefetch_next, pass_id)))
            if e.evicts is not None:
                live.discard(e.evicts)
        if pool is not None:
            self._marks.append(
                store._fetch_exec.submit(pool._pass_end, store.name))
        if self._futures:
            # stamp the moment the LAST page lands, so hidden time is
            # the stream's true wall, never the whole compute window
            self._futures[-1][1].add_done_callback(self._mark_ready)
        else:
            self._t_ready = self._t_begin

    def _mark_ready(self, _fut) -> None:
        self._t_ready = time.perf_counter()

    @property
    def done(self) -> bool:
        """True once fenced (or closed) — the pass can't be consumed twice."""
        return self._result is not None or self._closed

    def fence(self, timeout_s: Optional[float] = None
              ) -> Dict[str, PackedParam]:
        """Join the pass: block until every page is device-ready, thread
        nothing (the caller owns template threading), and record the
        exposed/hidden stall split.  Idempotent — a second fence returns
        the same params without re-waiting or re-accounting.

        ``timeout_s`` bounds the TOTAL wait across the pass's remaining
        fetches; exceeding it raises
        :class:`~repro.core.faults.PageFetchTimeout` and leaves the pass
        fully resumable — no futures are dropped, no stall is accounted,
        and a later ``fence()`` picks up exactly where this one gave up
        (the degradation hook the scheduler's tick deferral rides)."""
        if self._closed:
            raise RuntimeError("fence() after close(): the pass was "
                               "cancelled")
        if self._result is not None:
            return self._result
        t_fence = time.perf_counter()
        dev: Dict[str, PackedParam] = {}
        for n_done, (_idx, fut) in enumerate(self._futures):
            try:
                remaining = (None if timeout_s is None else
                             max(0.0, timeout_s - (time.perf_counter()
                                                   - t_fence)))
                dev.update(fut.result(timeout=remaining))
            except FuturesTimeout:
                self._store.fault_counters["fetch_timeouts"] += 1
                raise PageFetchTimeout(
                    model=self._store.name, timeout_s=timeout_s,
                    pending=len(self._futures) - n_done) from None
        jax.block_until_ready([p.packed for p in dev.values()])
        t_join = time.perf_counter()
        # a result() can return a hair before the completion callback
        # fires on the worker; fall back to the join timestamp then
        t_ready = self._t_ready if self._t_ready is not None else t_join
        self.window_s = t_fence - self._t_begin
        self.exposed_s = t_join - t_fence
        self.hidden_s = min(t_ready - self._t_begin, self.window_s)
        self.swap_s = self.hidden_s + self.exposed_s
        self._futures.clear()
        self._result = dev
        return dev

    def close(self) -> None:
        """Cancel what hasn't started, drain what has (never leak a fetch
        past teardown), and release the pool's fetch guard even when its
        end marker was cancelled.  Safe to call on a fenced pass (no-op)
        and idempotent."""
        for fut in [f for _i, f in self._futures] + self._marks:
            if not fut.cancel():
                try:
                    fut.result()
                except Exception:
                    pass             # executor already shut down mid-drain
        self._futures.clear()
        self._marks.clear()
        if self._result is None:
            self._closed = True
        if self._store.pool is not None:
            self._store.pool._pass_end(self._store.name)

    def __enter__(self) -> "AsyncPageStream":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def pass_counters(n_pages: int, resident_slots: int = 2) -> Dict[str, int]:
    """Static swap/miss counts for ONE full streaming pass starting from a
    cold page cache — the closed-form prediction the runtime counters of
    :class:`HostPagedStore` must match pass for pass (every page is fetched
    exactly once; only the first is a demand miss, the rest ride the
    proactive prefetch)."""
    live: set = set()
    inflight: set = set()
    swaps = misses = 0
    for e in make_schedule(n_pages, resident_slots):
        if e.page in live:
            pass
        elif e.page in inflight:
            inflight.discard(e.page)
            live.add(e.page)
        else:
            misses += 1
            swaps += 1
            live.add(e.page)
        if e.prefetch_next is not None and e.prefetch_next not in live:
            inflight.add(e.prefetch_next)
            swaps += 1
        if e.evicts is not None:
            live.discard(e.evicts)
    return dict(swaps=swaps, misses=misses)


# ---------------------------------------------------------------------------
# Mesh-sharded paging: one engine, N parallel memory links (ROADMAP 1(a))
# ---------------------------------------------------------------------------

def shard_packed_param(p: PackedParam, axis: int, n: int, i: int
                       ) -> PackedParam:
    """Shard ``i`` of ``n`` of a packed param, sliced along dense ``axis``.

    ``axis`` must be a NON-LAST dim of ``orig_shape``
    (:func:`repro.parallel.sharding.shard_axis` guarantees this): the
    packed carrier shares every leading dim with the dense shape and the
    per-channel scales span ``orig_shape[:-1]``, so one slice expression
    covers payload and scales alike — and because the page wire codec
    operates per row (blocks along the last axis, channel scales on the
    ``(rows, k)`` view), encode->decode of a shard equals the shard of
    encode->decode: concatenating the per-device fetches reconstructs the
    single-device bytes exactly."""
    size = int(p.orig_shape[axis])
    if axis >= len(p.orig_shape) - 1:
        raise ValueError(f"cannot shard the packed last axis {axis} of "
                         f"shape {tuple(p.orig_shape)}")
    if size % n != 0:
        raise ValueError(f"axis {axis} of {tuple(p.orig_shape)} does not "
                         f"split into {n} shards")
    step = size // n
    sl = [slice(None)] * len(p.orig_shape)
    sl[axis] = slice(step * i, step * (i + 1))
    orig = list(p.orig_shape)
    orig[axis] = step
    return PackedParam(packed=np.asarray(p.packed)[tuple(sl)],
                       scale=np.asarray(p.scale)[tuple(sl[:-1])],
                       bits=p.bits, orig_shape=tuple(orig))


def store_shard_axes(store: WeightStore, plan: Optional[PlacementPlan],
                     mesh: Any) -> Dict[str, Tuple[int, int]]:
    """{param name: (axis, n_shards)} for every param the mesh's "model"
    axis tensor-shards under the :func:`~repro.parallel.sharding
    ._param_pspec` rules.  With a ``plan``, restricted to its PAGED params
    (the resident hot set stays whole on the compute device); without
    one, covers the full store — the form ``plan_for_budget``'s
    ``shard_factors`` wants *before* a plan exists."""
    from repro.parallel.sharding import shard_axis
    out: Dict[str, Tuple[int, int]] = {}
    for name, p in store.params.items():
        if plan is not None and not plan.placement_for(name).paged:
            continue
        ax = shard_axis(tuple(name.split("/")), tuple(p.orig_shape), mesh)
        if ax is not None:
            out[name] = ax
    return out


class ShardedPoolLedger:
    """N per-device page pools under ONE global device-bytes budget.

    The Siracusa reading: the cluster and N-EUREKA each stream their own
    At-MRAM slice over their own memory port, but the chip still has ONE
    byte budget — so each device link gets ``budget // n`` of it (a
    private :class:`SharedPagePool`), and this ledger re-aggregates the
    per-device ``(device, wire, raw)`` counters into the global view.
    ``budget_bytes=None`` models the pool-less default (every pass
    re-swaps every page on every link — the single-device
    :class:`HostPagedStore` discipline, N links wide).

    :meth:`predict` composes the per-device
    :func:`kv_pass_counters` replays into one global prediction: each
    device's pages and events replay independently (the links are
    independent), and the sums must match the runtime counters member
    for member — the same determinism contract the single-device pool
    keeps."""

    def __init__(self, budget_bytes: Optional[int], n_devices: int,
                 name: str = "default"):
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        self.name = name
        self.n_devices = int(n_devices)
        self.budget_bytes = (None if budget_bytes is None
                             else int(budget_bytes))
        self.pools: Optional[List[SharedPagePool]] = None
        if budget_bytes is not None:
            per = max(1, int(budget_bytes) // n_devices)
            self.pools = [SharedPagePool(per) for _ in range(n_devices)]
        self.stores: List["HostPagedStore"] = []
        self.pass_count = 0              # pool-less passes begun (predict)
        self._lock = threading.Lock()
        self.counters: Dict[str, Dict[str, float]] = {}
        self._tracer = None

    def register(self, store: "HostPagedStore") -> None:
        with self._lock:
            self.stores.append(store)

    def pool_for(self, device_index: int) -> Optional[SharedPagePool]:
        return None if self.pools is None else self.pools[device_index]

    def add_stall(self, name: str, exposed_s: float,
                  hidden_s: float = 0.0) -> None:
        """Ledger-level stall view of a joined pass (the engine fences
        ONE joined stream, so the split arrives already aggregated)."""
        with self._lock:
            c = self.counters.setdefault(name, dict(exposed_s=0.0,
                                                    hidden_s=0.0))
            c["exposed_s"] += float(exposed_s)
            c["hidden_s"] += float(hidden_s)

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer = tracer
        if self.pools is not None:
            for pool in self.pools:
                pool.tracer = tracer

    def predict(self, resident_slots: int = 2) -> Dict[str, int]:
        """Global counter prediction: per-device replays, summed."""
        total = dict(swaps=0, misses=0, pool_hits=0, evicted=0, dropped=0,
                     bytes_wire=0, bytes_raw=0)
        for i, store in enumerate(self.stores):
            pool = self.pool_for(i)
            if pool is not None:
                sizes = {m: page_sizes(s.pages)
                         for m, s in pool.members.items()}
                events, budget = pool.events, pool.budget_bytes
            else:
                sizes = {store.name: page_sizes(store.pages)}
                events = [("pass", store.name)] * self.pass_count
                budget = None
            pred = kv_pass_counters(sizes, budget, events,
                                    resident_slots=resident_slots)
            for c in pred.values():
                for k in total:
                    total[k] += int(c.get(k, 0))
        return total

    def summary(self) -> Dict[str, Any]:
        """The global byte ledger + the per-device split it aggregates."""
        per_device = []
        for i, store in enumerate(self.stores):
            d = dict(device=str(store.device), n_pages=len(store.pages),
                     swap_count=store.swap_count,
                     miss_count=store.miss_count,
                     bytes_streamed_wire=store.bytes_streamed_wire,
                     bytes_streamed_raw=store.bytes_streamed_raw)
            pool = self.pool_for(i)
            if pool is not None:
                d.update(budget_bytes=pool.budget_bytes,
                         live_bytes=pool.live_bytes,
                         cached_pages=len(pool._cache))
            per_device.append(d)
        with self._lock:
            stalls = {m: dict(c) for m, c in self.counters.items()}
        return dict(
            budget_bytes=self.budget_bytes,
            n_devices=self.n_devices,
            swap_count=sum(d["swap_count"] for d in per_device),
            miss_count=sum(d["miss_count"] for d in per_device),
            bytes_streamed_wire=sum(d["bytes_streamed_wire"]
                                    for d in per_device),
            bytes_streamed_raw=sum(d["bytes_streamed_raw"]
                                   for d in per_device),
            per_device=per_device, stalls=stalls)

    def close(self, wait: bool = True) -> None:
        if self.pools is not None:
            for pool in self.pools:
                pool.close(wait=wait)     # closes the member stores too
        else:
            for store in self.stores:
                store.close(wait=wait)


class ShardedPagedStore:
    """One paged store fanned out over the mesh's "model" devices — each
    device link streams ONLY its shard (duck-types
    :class:`HostPagedStore` for the engine's begin/fence pipeline).

    Parameter routing, per the :func:`store_shard_axes` rules:

      * tensor-shardable paged params are split with
        :func:`shard_packed_param`; device ``i`` holds shard ``i`` and its
        own page cache — per-link wire traffic drops ~1/N for them;
      * replicated paged params (and the plan's whole resident set, and
        the passthrough leaves) live on device 0 only — they are paged
        ONCE and broadcast at the join, so the global byte ledger for
        them equals the single-device ledger exactly.

    :meth:`begin_pass` starts one :class:`AsyncPageStream` per device
    store; the returned :class:`JoinedPageStream` fences all of them and
    concatenates the shard fetches back into full-shape device params on
    the compute device — the per-row page wire codec commutes with
    leading-axis slicing, so the joined bytes are bit-identical to a
    single-device fetch and decode stays bit-exact by construction."""

    def __init__(self, store: WeightStore, page_bytes: int, mesh: Any,
                 plan: Optional[PlacementPlan] = None,
                 budget_bytes: Optional[int] = None,
                 name: str = "default", faults: FaultsArg = None):
        axis_names = tuple(getattr(mesh, "axis_names", ()))
        if "model" not in axis_names:
            raise ValueError(f"mesh axes {axis_names} have no 'model' "
                             f"axis to shard the paged store on")
        n = int(mesh.shape["model"])
        if n < 2:
            raise ValueError("model axis of size 1 shards nothing — use "
                             "HostPagedStore directly")
        devs = np.asarray(mesh.devices).reshape(-1, n)[0]
        self.mesh = mesh
        self.devices: Tuple = tuple(devs.tolist())
        self.n_shards = n
        self.name = name
        self.plan = plan
        self.store = store
        self.shard_axes = store_shard_axes(store, plan, mesh)
        self.ledger = ShardedPoolLedger(budget_bytes, n, name=name)
        self.stores: List[HostPagedStore] = []
        self._tracer = None
        for i, dev in enumerate(self.devices):
            params: Dict[str, PackedParam] = {}
            passthrough: Dict[str, Any] = {}
            for pname, p in store.params.items():
                ax = self.shard_axes.get(pname)
                if ax is not None:
                    params[pname] = shard_packed_param(p, ax[0], n, i)
                elif i == 0:
                    params[pname] = p     # replicated/resident: dev 0 only
            if i == 0:
                passthrough = dict(store.passthrough)
            sub = HostPagedStore(
                WeightStore(params=params, passthrough=passthrough),
                page_bytes, device=dev, plan=plan,
                pool=self.ledger.pool_for(i),
                name=f"{name}@dev{i}", faults=faults)
            self.stores.append(sub)
            self.ledger.register(sub)

    # -- aggregate counters (the HostPagedStore surface) ---------------------
    @property
    def resident(self) -> Dict[str, PackedParam]:
        return self.stores[0].resident

    @property
    def pages(self) -> List[Page]:
        return [p for s in self.stores for p in s.pages]

    @property
    def swap_count(self) -> int:
        return sum(s.swap_count for s in self.stores)

    @property
    def miss_count(self) -> int:
        return sum(s.miss_count for s in self.stores)

    @property
    def bytes_streamed_wire(self) -> int:
        return sum(s.bytes_streamed_wire for s in self.stores)

    @property
    def bytes_streamed_raw(self) -> int:
        return sum(s.bytes_streamed_raw for s in self.stores)

    @property
    def decode_s(self) -> float:
        return sum(s.decode_s for s in self.stores)

    @property
    def decode_skipped_bytes(self) -> int:
        return sum(s.decode_skipped_bytes for s in self.stores)

    @property
    def wire_served(self) -> set:
        return set().union(*(s.wire_served for s in self.stores))

    @property
    def fault_counters(self) -> Dict[str, int]:
        from repro.core.faults import merge_fault_counters
        return merge_fault_counters([s.fault_counters
                                     for s in self.stores])

    @property
    def pool(self) -> Optional[ShardedPoolLedger]:
        """The engine's ``pager.pool`` hook: the ledger when a global
        budget was given (it answers ``add_stall``), None otherwise —
        mirroring the single-device pool-less default."""
        return self.ledger if self.ledger.pools is not None else None

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer = tracer
        for s in self.stores:
            s.tracer = tracer
        self.ledger.tracer = tracer

    def device_summaries(self) -> List[Dict[str, Any]]:
        """Per-device counter rows for the metrics v9 ``paging.devices``
        section (summary shape owned by the ledger)."""
        return self.ledger.summary()["per_device"]

    def template_view(self) -> Dict[str, PackedParam]:
        """Full-shape template leaves: device-0's view, with sharded
        params re-concatenated host-side along their shard axis."""
        per_dev = [s.template_view() for s in self.stores]
        view = dict(per_dev[0])
        for pname, (ax, _n) in self.shard_axes.items():
            parts = [pv[pname] for pv in per_dev]
            orig = list(parts[0].orig_shape)
            orig[ax] = sum(int(p.orig_shape[ax]) for p in parts)
            view[pname] = PackedParam(
                packed=np.concatenate([np.asarray(p.packed)
                                       for p in parts], axis=ax),
                scale=np.concatenate([np.asarray(p.scale)
                                      for p in parts], axis=ax),
                bits=parts[0].bits, orig_shape=tuple(orig))
        return view

    def begin_pass(self, resident_slots: int = 2, pass_id: int = 0
                   ) -> "JoinedPageStream":
        self.ledger.pass_count += 1
        return JoinedPageStream(self, resident_slots, pass_id)

    def predict(self, resident_slots: int = 2) -> Dict[str, int]:
        return self.ledger.predict(resident_slots)

    def close(self, wait: bool = True) -> None:
        self.ledger.close(wait=wait)

    def __enter__(self) -> "ShardedPagedStore":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class JoinedPageStream:
    """One overlapped pass over EVERY device link of a
    :class:`ShardedPagedStore` — duck-types :class:`AsyncPageStream` for
    the engine's fence.

    Construction begins one :class:`AsyncPageStream` per device store
    (all N links stream concurrently — each store owns its own fetch
    worker/pool, so the per-device orders stay deterministic
    independently).  :meth:`fence` joins ALL of them, re-concatenates the
    shard fetches into full-shape params on the join device (device 0 —
    the compute device, so tokens stay bit-exact vs the single-device
    run), and records ONE aggregate exposed/hidden split with
    :class:`AsyncPageStream`'s exact algebra: the stream-ready time is
    the LAST link's, because the tick cannot start until the slowest
    port delivers.

    A ``timeout_s`` expiry propagates the child's
    :class:`~repro.core.faults.PageFetchTimeout` and leaves EVERY link
    resumable — already-fenced children cache their result, the raising
    child keeps its futures — so a deferred tick re-fences the same
    joined pass.  :meth:`close` closes every child (each releases its own
    pool guard), so an early exit orphans no per-device pass."""

    def __init__(self, sharded: ShardedPagedStore,
                 resident_slots: int = 2, pass_id: int = 0):
        self._sharded = sharded
        self._result: Optional[Dict[str, PackedParam]] = None
        self._closed = False
        self.swap_s = 0.0
        self.window_s = 0.0
        self.exposed_s = 0.0
        self.hidden_s = 0.0
        self._t_begin = time.perf_counter()
        self._streams = [s.begin_pass(resident_slots, pass_id)
                         for s in sharded.stores]

    @property
    def done(self) -> bool:
        return self._result is not None or self._closed

    def fence(self, timeout_s: Optional[float] = None
              ) -> Dict[str, PackedParam]:
        if self._closed:
            raise RuntimeError("fence() after close(): the pass was "
                               "cancelled")
        if self._result is not None:
            return self._result
        import jax.numpy as jnp
        t_fence = time.perf_counter()
        per_dev = []
        for ps in self._streams:
            remaining = (None if timeout_s is None else
                         max(0.0, timeout_s - (time.perf_counter()
                                               - t_fence)))
            per_dev.append(ps.fence(timeout_s=remaining))
        target = self._sharded.devices[0]
        dev: Dict[str, PackedParam] = dict(per_dev[0])
        for name, (ax, _n) in self._sharded.shard_axes.items():
            parts = [pd[name] for pd in per_dev]
            orig = list(parts[0].orig_shape)
            orig[ax] = sum(int(p.orig_shape[ax]) for p in parts)
            dev[name] = PackedParam(
                packed=jnp.concatenate([jax.device_put(p.packed, target)
                                        for p in parts], axis=ax),
                scale=jnp.concatenate([jax.device_put(p.scale, target)
                                       for p in parts], axis=ax),
                bits=parts[0].bits, orig_shape=tuple(orig))
        jax.block_until_ready([p.packed for p in dev.values()])
        t_join = time.perf_counter()
        readys = [ps._t_ready for ps in self._streams
                  if ps._t_ready is not None]
        t_ready = max(readys) if readys else t_join
        self.window_s = t_fence - self._t_begin
        self.exposed_s = t_join - t_fence
        self.hidden_s = min(t_ready - self._t_begin, self.window_s)
        self.swap_s = self.hidden_s + self.exposed_s
        self._result = dev
        return dev

    def close(self) -> None:
        for ps in self._streams:
            ps.close()
        if self._result is None:
            self._closed = True

    def __enter__(self) -> "JoinedPageStream":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


# ---------------------------------------------------------------------------
# KV-cache paging: the per-slot KV cache flows through the SAME budget
# ---------------------------------------------------------------------------

class KVPageTable:
    """Pages a serving engine's per-slot KV cache through the *same*
    device-bytes budget — and the same begin/fence overlap — the weight
    pages use (the paper's one-memory-hierarchy constraint: §V's
    concurrent workloads share ONE At-MRAM, so long-context KV state
    cannot dodge the budget the weights respect).

    Addressing: a KV *page* is ``block_rows`` consecutive cache rows of
    one batch slot, across every layer and both k and v — page index
    ``slot * n_blocks + block`` (vLLM-style fixed-size blocks).  The
    engine's preallocated device cache stays the compute working buffer
    (jit shapes never change); the authoritative copy of every
    *completed* block lives in this table's host image:

      * a block is written back host-ward exactly once, when the
        prefill/decode frontier crosses its end (KV writes are
        append-only, so completed blocks are immutable from then on);
      * each tick the live span's completed blocks stream host->device
        through the pool and are scattered over the device cache — a
        pooled block satisfies the fetch without a swap (``pool_hits``),
        eviction under pressure is the pool's cross-model call, and a
        pool-less table re-swaps every block every pass (exactly the
        private ``HostPagedStore`` discipline);
      * the partially filled *frontier* block stays device-resident — it
        is still being appended to (vLLM keeps the active block on-GPU
        for the same reason);
      * when a batch slot is handed to a new request, the old request's
        pooled blocks are dropped (``queue_drop`` / ``flush_drops`` — the
        flush runs at the next fence, after every in-flight fetch has
        settled, so a late fetch can never resurrect a stale page).

    Counters (``swap_count`` == ``miss_count``: every non-pooled KV fetch
    is a demand swap), writebacks and drops follow the static
    :func:`kv_pass_counters` replay of the pool's event log.
    """

    def __init__(self, cache_kv: Dict[str, Any], *, block_rows: int = 16,
                 pool: Optional[SharedPagePool] = None,
                 name: str = "default/kv",
                 device: Optional[jax.Device] = None,
                 faults: FaultsArg = None):
        if block_rows < 1:
            raise ValueError(f"block_rows must be >= 1, got {block_rows}")
        k = np.asarray(cache_kv["k"])
        v = np.asarray(cache_kv["v"])
        # cache layout (n_layers, n_slots, n_kv_heads, max_len, head_dim)
        self.n_slots = int(k.shape[1])
        self.max_len = int(k.shape[3])
        self.block_rows = int(block_rows)
        self.n_blocks = -(-self.max_len // self.block_rows)
        self.host = dict(k=k.copy(), v=v.copy())
        self.row_nbytes = (k.nbytes + v.nbytes) // (self.n_slots
                                                    * self.max_len)
        self.page_nbytes = self.block_rows * self.row_nbytes
        self.name = name
        self.pool = pool
        self.device = device or jax.devices()[0]
        self.swap_count = 0
        self.miss_count = 0
        self.pool_hits = 0
        # KV rows stream in their device format ("fp" page encoding):
        # wire == raw == device bytes, so the ledger shows ratio 1.0
        self.bytes_streamed_wire = 0
        self.bytes_streamed_raw = 0
        self.writebacks = 0          # blocks written back host-ward
        self.dropped = 0             # pooled blocks invalidated (slot reuse)
        self.preempt_drops = 0       # of which: mid-request preemptions
        # KV rows move host numpy -> device directly (no wire codec), so
        # there is nothing for a bit-flip to corrupt pre-checksum: the
        # injector's transient failures / latency faults apply, bitflips
        # don't (weight pages carry the CRC-checked wire path)
        self.faults = as_injector(faults)
        self.fault_counters = new_fault_counters()
        self._closed = False
        # pool-less prediction log (pooled tables log into pool.events)
        self.events: List[Tuple] = []
        self._pending_drops: set = set()
        self._exec = ThreadPoolExecutor(max_workers=1)
        # opt-in chrome-trace hook (ServingEngine.set_tracer): per-block
        # fetch spans + kvdrop instants on the "io" track
        self.tracer = None
        if pool is not None:
            pool.register(name, self)

    @property
    def pages(self) -> range:
        return range(self.n_slots * self.n_blocks)

    @property
    def _fetch_exec(self) -> ThreadPoolExecutor:
        return self._exec if self.pool is None else self.pool._exec

    def _log(self, *event) -> None:
        if self.pool is not None:
            self.pool.log_event(*event)
        else:
            self.events.append(tuple(event))

    def page_index(self, slot: int, block: int) -> int:
        return slot * self.n_blocks + block

    def _block_rows_span(self, page_idx: int) -> Tuple[int, int, int]:
        slot, blk = divmod(page_idx, self.n_blocks)
        a = blk * self.block_rows
        return slot, a, min(a + self.block_rows, self.max_len)

    def _fetch_block(self, page_idx: int) -> Dict[str, Any]:
        tr = self.tracer
        t0 = tr.now() if tr is not None else 0.0
        if self._closed:
            raise CancelledError(f"{self.name}: table closed before fetch "
                                 f"of page {page_idx} started")
        if self.pool is not None:
            cached = self.pool.lookup(self.name, page_idx)
            if cached is not None:
                self.pool_hits += 1
                if tr is not None:       # pool hit: no host->device swap
                    tr.complete("kv_block", tr.now() - t0, track="io",
                                model=self.name, page=page_idx,
                                pool_hit=True)
                return cached
        slot, a, b = self._block_rows_span(page_idx)
        rows = retry_fetch(self, page_idx,
                           lambda attempt: self._fetch_block_once(
                               page_idx, slot, a, b, attempt))
        if self._closed:
            raise CancelledError(f"{self.name}: table closed during fetch "
                                 f"of page {page_idx}")
        self.swap_count += 1
        self.miss_count += 1
        nb = (b - a) * self.row_nbytes
        self.bytes_streamed_wire += nb
        self.bytes_streamed_raw += nb
        if self.pool is not None:
            self.pool.admit(self.name, page_idx, nb, rows)
        if tr is not None:
            tr.complete("kv_block", tr.now() - t0, track="io",
                        model=self.name, page=page_idx,
                        nbytes=(b - a) * self.row_nbytes, pool_hit=False)
        return rows

    def _fetch_block_once(self, page_idx: int, slot: int, a: int, b: int,
                          attempt: int) -> Dict[str, Any]:
        if self.faults is not None:
            self.fault_counters["injected"] += self.faults.pre_fetch(
                self.name, page_idx, attempt)
        return dict(
            k=jax.device_put(self.host["k"][:, slot, :, a:b], self.device),
            v=jax.device_put(self.host["v"][:, slot, :, a:b], self.device))

    def writeback(self, slot: int, block_lo: int, block_hi: int,
                  cache_kv: Dict[str, Any]) -> None:
        """Completed blocks ``[block_lo, block_hi)`` of ``slot`` move
        device->host from the engine's cache buffer — each row exactly
        once, at the moment its block fills (append-only KV means the
        block is immutable from here on)."""
        if block_hi <= block_lo:
            return
        a = block_lo * self.block_rows
        b = min(block_hi * self.block_rows, self.max_len)
        for part in ("k", "v"):
            self.host[part][:, slot, :, a:b] = np.asarray(
                cache_kv[part][:, slot, :, a:b])
        self.writebacks += block_hi - block_lo

    def queue_drop(self, slot: int) -> None:
        """Mark ``slot``'s pages stale (its request retired / the slot is
        being reassigned).  The actual pool invalidation is deferred to
        :meth:`flush_drops` at the next fence — after every in-flight
        fetch has settled — so a still-executing fetch of the old
        request's block cannot re-admit a page after the drop."""
        self._pending_drops.add(int(slot))

    def flush_drops(self) -> None:
        if not self._pending_drops:
            return
        for slot in sorted(self._pending_drops):
            pages = range(slot * self.n_blocks, (slot + 1) * self.n_blocks)
            if self.pool is not None:
                removed = tuple(p for p in pages
                                if self.pool.invalidate(self.name, p))
                if removed:
                    self.pool.log_event("kvdrop", self.name, removed)
                    if self.tracer is not None:
                        self.tracer.instant("kvdrop", track="io",
                                            model=self.name, slot=slot,
                                            pages=len(removed))
                self.dropped += len(removed)
            # stale rows must never be served again: zero them so a bug
            # that fetches a dropped block surfaces as loud wrong bytes
            self.host["k"][:, slot] = 0
            self.host["v"][:, slot] = 0
        self._pending_drops.clear()

    def preempt_release(self, slot: int, *, in_flight: bool) -> None:
        """Release ``slot``'s pooled blocks for a mid-request preemption.

        Same invalidation path as a retirement (``queue_drop``), but the
        flush timing is the preemption-safety decision: with no KV pass
        in flight (``in_flight=False`` — the single-scheduler admit
        point sits between fence and begin) the drop flushes NOW, so the
        slot's next occupant can write back this very tick without a
        later deferred flush zeroing its fresh blocks.  With a pass
        still unfenced (the tenancy admit point) the flush defers to
        that fence, which still lands before the usurper's first
        writeback.  Either way the pool sees one ``kvdrop`` event —
        ``kv_pass_counters`` replays preemptions natively."""
        self.queue_drop(slot)
        self.preempt_drops += 1
        if not in_flight:
            self.flush_drops()

    def begin_pass(self, full_blocks: Dict[int, int]) -> "KVPageStream":
        """Kick one overlapped KV streaming pass: ``full_blocks`` maps
        each live slot to its completed-block count; every listed block's
        fetch is submitted up front (slot order, then block order) and
        runs while the caller computes; blocks that complete between
        begin and fence are demand-fetched at the fence (that wait lands
        exposed, exactly where it belongs)."""
        return KVPageStream(self, full_blocks)

    def close(self, wait: bool = True) -> None:
        # flag first: a block fetch already running on the worker aborts
        # before installing into the pool (same discipline as
        # HostPagedStore.close)
        self._closed = True
        self._exec.shutdown(wait=wait, cancel_futures=not wait)

    def __enter__(self) -> "KVPageTable":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class KVPageStream:
    """One overlapped KV streaming pass — the KV counterpart of
    :class:`AsyncPageStream`, with the same exposed/hidden stall split
    (and the same ``stall += swap - hidden`` identity against
    :func:`repro.core.memsys.overlap_stall`).  ``fence(full_blocks)``
    takes the *current* completed-block spans so blocks that filled
    during the compute window are demand-fetched before the join."""

    def __init__(self, table: KVPageTable, full_blocks: Dict[int, int]):
        self._table = table
        self._begun = {int(s): int(n) for s, n in full_blocks.items()}
        self._futures: List[Tuple[int, Future]] = []
        self._marks: List[Future] = []
        self._result: Optional[Dict[int, Dict[str, Any]]] = None
        self._closed = False
        self.swap_s = 0.0
        self.window_s = 0.0
        self.exposed_s = 0.0
        self.hidden_s = 0.0
        self._t_last_done: Optional[float] = None
        self._t_begin = time.perf_counter()
        pages = self._page_list(self._begun)
        pool = table.pool
        if pool is not None and pages:
            # the guard brackets pass EXECUTION on the serialized worker,
            # exactly like AsyncPageStream's marker tasks
            self._marks.append(
                table._fetch_exec.submit(pool._pass_begin, table.name))
        self._submit(pages)
        if pool is not None and pages:
            self._marks.append(
                table._fetch_exec.submit(pool._pass_end, table.name))
        if not self._futures:
            # nothing streamed during the window: an all-demand fence
            # must read hidden == 0, never the whole compute window
            self._t_last_done = self._t_begin

    def _page_list(self, full_blocks: Dict[int, int],
                   already: Optional[Dict[int, int]] = None) -> List[int]:
        out = []
        for slot in sorted(full_blocks):
            lo = 0 if already is None else already.get(slot, 0)
            for blk in range(lo, full_blocks[slot]):
                out.append(self._table.page_index(slot, blk))
        return out

    def _submit(self, pages: List[int], track: bool = True) -> None:
        t = self._table
        if not pages:
            return
        t._log("kv", t.name, tuple((p, t.page_nbytes) for p in pages))
        for p in pages:
            fut = t._fetch_exec.submit(t._fetch_block, p)
            if track:
                # only the up-front (begin-batch) futures stamp the
                # stream-ready time: demand fetches submitted at the
                # fence complete after it and land wholly in exposed —
                # letting them stamp would inflate hidden to the entire
                # compute window (the trap AsyncPageStream avoids by
                # stamping only the last up-front fetch)
                fut.add_done_callback(self._mark_done)
            self._futures.append((p, fut))

    def _mark_done(self, _fut) -> None:
        self._t_last_done = time.perf_counter()

    @property
    def done(self) -> bool:
        return self._result is not None or self._closed

    def fence(self, full_blocks: Optional[Dict[int, int]] = None,
              timeout_s: Optional[float] = None
              ) -> Dict[int, Dict[str, Any]]:
        """Join the pass: demand-fetch blocks completed since begin, wait
        for every page, and record the exposed/hidden split.  Returns
        {page_index: {"k": rows, "v": rows}} for the engine to scatter.
        Idempotent, like :meth:`AsyncPageStream.fence`.

        ``timeout_s`` bounds the total wait; on expiry the fence raises
        :class:`~repro.core.faults.PageFetchTimeout` and stays resumable:
        demand fetches submitted here are folded into ``_begun`` *before*
        the join, so a re-fence after a deferred tick neither re-submits
        nor re-logs them."""
        if self._closed:
            raise RuntimeError("fence() after close(): the pass was "
                               "cancelled")
        if self._result is not None:
            return self._result
        t_fence = time.perf_counter()
        if full_blocks is not None:
            self._submit(self._page_list(full_blocks, already=self._begun),
                         track=False)
            for slot, n in full_blocks.items():
                self._begun[int(slot)] = max(self._begun.get(int(slot), 0),
                                             int(n))
        out: Dict[int, Dict[str, Any]] = {}
        for n_done, (p, fut) in enumerate(self._futures):
            try:
                remaining = (None if timeout_s is None else
                             max(0.0, timeout_s - (time.perf_counter()
                                                   - t_fence)))
                out[p] = fut.result(timeout=remaining)
            except FuturesTimeout:
                self._table.fault_counters["fetch_timeouts"] += 1
                raise PageFetchTimeout(
                    model=self._table.name, timeout_s=timeout_s,
                    pending=len(self._futures) - n_done) from None
        jax.block_until_ready([r for rows in out.values()
                               for r in rows.values()])
        t_join = time.perf_counter()
        t_ready = (self._t_last_done if self._t_last_done is not None
                   else t_join)
        self.window_s = t_fence - self._t_begin
        self.exposed_s = t_join - t_fence
        self.hidden_s = min(max(t_ready - self._t_begin, 0.0),
                            self.window_s)
        self.swap_s = self.hidden_s + self.exposed_s
        self._futures.clear()
        self._result = out
        return out

    def close(self) -> None:
        for fut in [f for _p, f in self._futures] + self._marks:
            if not fut.cancel():
                try:
                    fut.result()
                except Exception:
                    pass             # executor already shut down mid-drain
        self._futures.clear()
        self._marks.clear()
        if self._result is None:
            self._closed = True
        if self._table.pool is not None:
            self._table.pool._pass_end(self._table.name)

    def __enter__(self) -> "KVPageStream":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def kv_pass_counters(page_nbytes: Dict[str, Sequence[int]],
                     budget_bytes: Optional[int],
                     events: Sequence[Tuple],
                     resident_slots: int = 2) -> Dict[str, Dict[str, int]]:
    """Static per-member counter prediction for a pool whose members mix
    weight stores AND KV page tables — the unified eviction/accounting
    domain of KV-cache paging.

    ``events`` is the pool's :attr:`SharedPagePool.events` log (or a
    pool-less :attr:`KVPageTable.events`); ``page_nbytes`` maps each
    *weight* member to its page sizes in access order (KV batches carry
    their sizes inline).  Each size is either a plain int (device bytes;
    wire and raw default to it — the pre-encoding ledger) or a
    ``(device, wire, raw)`` triple as produced by :func:`page_sizes`:
    the cache simulation charges *device* bytes (what admission and
    eviction see) while every replayed swap accumulates *wire*/*raw*
    bytes into the member's ``bytes_wire``/``bytes_raw`` — so the
    prediction is exact in wire bytes even when cold pages stream
    compressed.  ``budget_bytes=None`` models a pool-less table: no
    cache, every fetch swaps.  Replays the runtime's exact
    lookup/admit/evict/invalidate sequence, so
    :meth:`SharedPagePool.summary` counters (and a private table's
    ``swap_count``) must match member for member.  On a weights-only
    event stream this agrees with :func:`shared_pass_counters`."""
    cache: "OrderedDict[Tuple[str, int], int]" = OrderedDict()
    live_bytes = 0
    out: Dict[str, Dict[str, int]] = {}

    def sizes3(entry) -> Tuple[int, int, int]:
        if isinstance(entry, (tuple, list)):
            dev, wire, raw = entry
            return int(dev), int(wire), int(raw)
        nb = int(entry)
        return nb, nb, nb

    def member(m: str) -> Dict[str, int]:
        return out.setdefault(m, dict(swaps=0, misses=0, pool_hits=0,
                                      evicted=0, dropped=0,
                                      bytes_wire=0, bytes_raw=0))

    def fetch(model: str, idx: int, size) -> None:
        nonlocal live_bytes
        nb, wire, raw = sizes3(size)
        key = (model, idx)
        if budget_bytes is not None and key in cache:
            cache.move_to_end(key)
            member(model)["pool_hits"] += 1
            return
        member(model)["swaps"] += 1
        member(model)["bytes_wire"] += wire
        member(model)["bytes_raw"] += raw
        if budget_bytes is None or nb > budget_bytes:
            return                  # mirrors admit's never-fits pre-check
        for victim in list(cache.keys()):
            if live_bytes + nb <= budget_bytes:
                break
            if victim[0] == model:
                continue
            live_bytes -= cache.pop(victim)
            member(victim[0])["evicted"] += 1
        if live_bytes + nb <= budget_bytes:
            cache[key] = nb
            live_bytes += nb

    for event in events:
        kind, model = event[0], event[1]
        if kind == "pass":
            m = member(model)
            sizes = page_nbytes[model]
            live: set = set()
            inflight: set = set()
            for e in make_schedule(len(sizes), resident_slots):
                if e.page in live:
                    pass
                elif e.page in inflight:
                    inflight.discard(e.page)
                    live.add(e.page)
                else:
                    m["misses"] += 1
                    fetch(model, e.page, sizes[e.page])
                    live.add(e.page)
                if e.prefetch_next is not None and e.prefetch_next not in live:
                    inflight.add(e.prefetch_next)
                    fetch(model, e.prefetch_next, sizes[e.prefetch_next])
                if e.evicts is not None:
                    live.discard(e.evicts)
        elif kind == "kv":
            m = member(model)
            for page, nb in event[2]:
                before = m["pool_hits"]
                fetch(model, int(page), nb)
                if m["pool_hits"] == before:
                    m["misses"] += 1     # every non-pooled KV fetch swaps
        elif kind == "kvdrop":
            for page in event[2]:
                nb = cache.pop((model, int(page)), None)
                if nb is not None:
                    live_bytes -= nb
                    member(model)["dropped"] += 1
        else:
            raise ValueError(f"unknown pool event kind {kind!r}")
    return out


def thread_packed(tree: Any, params: "Dict[str, PackedParam]") -> Any:
    """Return ``tree`` with each packed leaf group named in ``params``
    replaced by that PackedParam's packed/scale arrays — the inverse of
    :func:`packed_tree_store` for a subset of groups.  The serving runtime
    uses this to thread freshly streamed device pages (and the pinned
    resident set) into the tree its jitted step consumes; shapes and
    dtypes are unchanged, so the jit cache is stable across ticks."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = path_key(path)
        if key.endswith("/packed") and key[:-len("/packed")] in params:
            out.append(params[key[:-len("/packed")]].packed)
        elif key.endswith("/scale") and key[:-len("/scale")] in params:
            out.append(params[key[:-len("/scale")]].scale)
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def packed_tree_store(tree: Any, plan: Optional[PlacementPlan] = None
                      ) -> WeightStore:
    """:class:`WeightStore` view over a ``freeze_for_serving`` packed tree.

    Every packable leaf group (a ``{"packed", "scale"}`` dict at path P)
    becomes one :class:`PackedParam` entry keyed by P — for the stacked LM
    tree that is one entry per parameter *group* across all depths, the
    exact granularity of ``placement.packed_sizes``/``plan_for_budget``.
    Non-packed leaves (embeddings, norms) are exposed as passthrough.
    This is the bridge the serving runtime uses to put a serve tree behind
    a :class:`HostPagedStore`."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    leaves = {path_key(p): leaf for p, leaf in flat}
    params: Dict[str, PackedParam] = {}
    passthrough: Dict[str, Any] = {}
    for key, leaf in leaves.items():
        if key.endswith("/packed"):
            base = key[:-len("/packed")]
            bits = plan.bits_for(base) if plan is not None else 8
            factor = 8 // bits
            orig_shape = (tuple(leaf.shape[:-1])
                          + (int(leaf.shape[-1]) * factor,))
            params[base] = PackedParam(packed=leaf,
                                       scale=leaves[base + "/scale"],
                                       bits=bits, orig_shape=orig_shape)
        elif (key.endswith("/scale")
                and key[:-len("/scale")] + "/packed" in leaves):
            continue
        else:
            passthrough[key] = leaf
    return WeightStore(params=params, passthrough=passthrough)
