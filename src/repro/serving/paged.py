"""Paged KV cache + prefill/decode disaggregation (DESIGN.md §15).

The continuous-batching engine (§12) moves every request's whole KV cache
through each micro-batch: mixed-length sequences never share a batch (the
batch key includes the cache shape), rows are padded to pow-2 buckets,
and migrating a sequence repatriates megabytes.  That is why the fleet
*lost* to one device in fig9.  This module applies the GPU-virtualization
lesson (Li et al., arXiv:1511.07658): many clients share a device only
when their state is partitioned into fixed-size schedulable units.

* ``PagePool`` — one per device: two slab ``Buffer``s (k and v) of shape
  ``(layers, num_pages, page_size, kv_heads, head_dim)`` plus a free
  list.  Page 0 is *reserved* as the padding target: page-table slots
  past a sequence's tail must hold a valid index (the paged-attention
  kernel DMAs them before masking), so they all point at page 0 and no
  live sequence ever owns it.

* **Honest accounting.**  The slabs re-register under AGAS kind
  ``"pool"`` with 0 bytes — slab *capacity* is not memory pressure, and
  the LRU spiller must never evict a whole pool.  What counts is usage:
  every sequence is a ``SeqPages`` record (AGAS kind ``"buffer"``,
  ``nbytes`` = its pages × page bytes, re-declared through
  ``Registry.update_nbytes`` on every alloc/free/spill).  The §14
  memory-aware scheduler therefore sees page pressure per device, and
  its existing ``spill_lru`` evicts *cold sequences'* pages (host copy +
  pages returned to the pool), never the hot ones it placed work next to.

* ``PagedKVCache`` — the fleet-wide allocator: per-device pools,
  sequence lifecycle (``new_seq`` / ``append`` / ``free_seq``),
  ``defrag`` (compact a pool's live pages to the low slots),
  ``migrate`` (re-home a sequence's pages to another device in ONE
  coalesced move — all pages travel as one stacked array per slab, not
  one transfer per page), and ``table`` (page tables + lengths in the
  kernel's layout).

* ``PagedServeEngine`` — prefill/decode disaggregation.  Prefill is a
  throughput lane: prompts batch up to a token budget
  (``LanePolicy.token_budget``), the placement scheduler picks the
  sequence's home device (memory veto included), and the prompt's KV is
  paged in once.  Decode is a latency lane *per device*: exact-row
  batches of every active resident sequence — no row padding at all
  (``padding_waste`` ≈ 0), mixed lengths share one step because the page
  table, not the batch shape, encodes length — stepped continuously with
  a deadline-bounded wait for new arrivals.  Page-table width and pool
  shapes are static, so the jitted step stays hot across steps.  Every
  step charges the scheduler's recent-placement counter
  (``Scheduler.charge``) so ``least_loaded`` sees decode bursts that
  never touch a lane queue; every ``rebalance_every`` steps the lane
  asks ``Scheduler.select_batch`` (affinity over the ``SeqPages``
  records) whether its sequences still belong here — a different answer
  migrates one sequence, pages percolating in one coalesced move.

The model contract is two jitted steps that take their params as the
first argument; ``PagedServeEngine.from_config(cfg)`` wires them from a
zoo family's ``repro.models.model.paged_surface`` triple (DESIGN.md §17):

``prefill_fn(params, tokens, extras)``
    ``-> (k, v, state, last_logits)`` with k/v ``(B, L, T', K, D)`` —
    ``T'`` may exceed the prompt length (hybrid meta/register tokens
    page in too; the engine pages ``k.shape[2]`` tokens) — ``state`` an
    optional batch-leading pytree of fixed-size per-sequence residue
    (SSM recurrent state, conv windows, encoder cross K/V) and
    ``last_logits`` ``(B, V)``: the engine samples the first token
    host-side.  ``extras`` carries modality inputs (whisper frames),
    stacked from each request's ``submit(..., extras=...)``.
``decode_fn(params, k_pages, v_pages, state, tokens, positions, tables, lengths)``
    ``-> (k_pages, v_pages, state, logits)`` — one ragged step over the
    pools plus the batch's resident state, batch-leading (the engine
    gathers it out of the state slab on the device): scatter each row's
    incoming token's k/v into ``pages[tables[b, pos // P], pos % P]``,
    attend through the page table and return the ``(B, V)`` logits,
    which come back to the host for sampling.  Donating the two slabs
    keeps the page update in place.  ``_pool_step`` is the one place the
    step is called; the decode lanes and the cross-locality worker alike
    reach it through ``_decode_rows``.

``params`` is an executable argument, never a closure constant (a
captured array lowers into the program as a constant — gigabytes per
compiled shape at published widths).  The engine keeps one replica per
pool device (``weights``, keyed by device key): a prefill runs on the
device its request was placed on, with that device's replica, and each
decode lane steps with its own device's replica.

Resident state lives on the device next to the pages: each pool keeps a
``StateSlab`` per row signature (the state's tree structure, leaf shapes
and dtypes), and a sequence owns a slot in it.  Prefill scatters each row
into its sequence's slot, and each decode step gathers its batch's rows
out of the slab and scatters the stepped rows back, one jitted call each,
so the state crosses to the host only when the sequence spills, migrates
or ships (``SeqPages.set_state`` folds its bytes into the AGAS record —
the §14 memory-aware scheduler sees SSM state as honestly as KV pages).
Sampling is host-side and bit-reproducible: token ``position`` of
request ``request_id`` draws from
``np.random.default_rng([seed, request_id, position])`` — a pure
function of request identity, never of batch composition or fleet size
(greedy argmax when ``temperature <= 0``).

Spans and counters (``repro.core.trace``) mark the engine's stage
boundaries in a profiler trace (``paged.prefill.*``, ``paged.kv.*``,
``paged.decode.*``) and count its work: admissions and their wait, prompt
and output tokens, decode steps and the rows that sat them out, the rows
whose state came from a slab and the slabs that grew, spilled and
refetched pages, and the bytes copied between host and device, by stage.
``PagedServeEngine.counters()`` returns the totals; docs/index.md,
"Tracing the engine", says what each one means.
"""
from __future__ import annotations

import collections
import concurrent.futures as _cf
import contextlib
import dataclasses
import functools
import threading
import time
import weakref
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import agas
from repro.core.executor import coalesce
from repro.core.futures import Future, Promise
from repro.core.trace import Counters, span
from repro.serving.engine import EngineClosed, LanePolicy, QueueFull

__all__ = [
    "PAGE_SIZE",
    "PageSpec",
    "PagePool",
    "PagedKVCache",
    "PagedServeEngine",
    "SamplingParams",
    "SeqPages",
    "StateSlab",
    "OutOfPages",
    "sample_token",
]


def _now() -> float:
    return time.monotonic()


class OutOfPages(RuntimeError):
    """The pool has fewer free pages than the allocation needs."""


# Tokens per KV page of every zoo family's ``paged_spec``.
PAGE_SIZE = 16


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """Geometry of one KV page: ``page_size`` tokens × ``kv_heads`` ×
    ``head_dim`` per layer, k and v both."""

    layers: int
    page_size: int
    kv_heads: int
    head_dim: int
    dtype: Any = np.float32

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")

    @property
    def page_bytes(self) -> int:
        """Bytes one page pins across both slabs (k + v, all layers)."""
        return (2 * self.layers * self.page_size * self.kv_heads
                * self.head_dim * np.dtype(self.dtype).itemsize)

    def pages_for(self, tokens: int) -> int:
        return max(0, -(-int(tokens) // self.page_size))


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs.

    ``temperature <= 0`` means greedy argmax (the default, and the
    parity-oracle mode).  ``top_k``/``top_p`` filter the distribution
    after temperature scaling: keep the ``top_k`` highest-probability
    tokens (0 = unlimited), then the smallest prefix of the descending
    distribution whose cumulative probability reaches ``top_p``.
    ``seed`` keys the per-request PRNG stream."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0


def sample_token(logits, params: "SamplingParams | None",
                 request_id: int, position: int) -> int:
    """Sample ONE token from a ``(V,)`` logits row, bit-reproducibly.

    The PRNG is seeded ``[seed, request_id, position]`` — a pure
    function of the request's identity and the token's position, so the
    same request emits the same tokens whether it shared its decode
    batch with 0 or 63 neighbours and whether the fleet had 1 or 8
    devices.  Math is float64 on host: no accelerator, dtype or fusion
    variance can leak into the draw."""
    logits = np.asarray(logits, np.float64).reshape(-1)
    if params is None or params.temperature <= 0.0:
        return int(np.argmax(logits))
    x = logits / float(params.temperature)
    order = np.argsort(-x, kind="stable")  # stable: ties break by token id
    xs = x[order]
    keep = xs.size
    if params.top_k and params.top_k > 0:
        keep = min(keep, int(params.top_k))
    xs = xs[:keep]
    probs = np.exp(xs - xs.max())
    probs /= probs.sum()
    if params.top_p < 1.0:
        cum = np.cumsum(probs)
        # smallest prefix reaching top_p (always >= 1 token)
        cut = int(np.searchsorted(cum, params.top_p, side="left")) + 1
        probs = probs[:cut]
        probs /= probs.sum()
    rng = np.random.default_rng(
        [int(params.seed), int(request_id), int(position)])
    u = rng.random()
    idx = int(np.searchsorted(np.cumsum(probs), u, side="right"))
    idx = min(idx, probs.size - 1)
    return int(order[idx])


# Consecutive empty decode steps (nothing fits in the pool) tolerated
# before the lane declares the working set unservable and fails the
# stalled batch.  At the 2ms stall backoff this is ~1s of zero progress.
_MAX_DECODE_STALLS = 500


# Finished requests whose timelines ``PagedServeEngine.metrics()`` reads.
_RECENT_REQUESTS = 256


# Host-device copies are counted where they are made, as ``h2d_bytes`` and
# ``d2h_bytes`` keyed by stage: sizes reckoned from the arrays' ``nbytes``
# (pow-2 page padding and pad rows included), not timed transfers.

def _sent(counters: Counters, stage: str, tree) -> None:
    """Count the host arrays of ``tree`` as copied to the device (by a
    jitted call or ``device_put``)."""
    n = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree)
            if isinstance(a, np.ndarray))
    if n:
        counters.add("h2d_bytes", n, key=stage)


def _fetched(counters: Counters, stage: str, tree):
    """``tree`` with every leaf on the host; its device arrays count as
    copied to the host."""
    n = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree)
            if isinstance(a, jax.Array))
    if n:
        counters.add("d2h_bytes", n, key=stage)
    return jax.tree_util.tree_map(np.asarray, tree)


def _pow2_pad_idx(idx: np.ndarray) -> np.ndarray:
    """Pad a page-index vector to the next power-of-two length by
    repeating the last entry, bounding the distinct shapes the jitted
    slab gather/scatter ever compile to log2(max pages per move)."""
    n = idx.size
    want = 1
    while want < n:
        want *= 2
    if want == n:
        return idx
    return np.concatenate([idx, np.repeat(idx[-1:], want - n)])


@functools.cache
def zoo_steps(cfg):
    """``(paged_spec, prefill, decode)`` for a zoo config: the family's
    paged surface with ``cfg`` bound and ``params`` left as the first
    argument, jitted once per config so every engine and worker over the
    same config shares its compiled executables.  ``decode`` donates the
    two slabs (arguments 1 and 2), keeping the page update in place."""
    from repro.models.model import paged_surface

    spec_fn, prefill_fn, decode_fn = paged_surface(cfg)
    pre = jax.jit(functools.partial(prefill_fn, cfg))
    dec = jax.jit(functools.partial(decode_fn, cfg), donate_argnums=(1, 2))
    return spec_fn, pre, dec


@functools.partial(jax.jit, donate_argnums=(0,))
def _slab_scatter(slab, idx, vals):
    return slab.at[:, idx].set(vals)


@jax.jit
def _slab_gather(slab, idx):
    return slab[:, idx]


# A state-slot index past every slab: a scatter row aimed at it writes
# nothing (``mode="drop"``), which is how pad rows stay out of the slab.
_NO_SLOT = np.iinfo(np.int32).max

# Slot index vectors a slab keeps on its device before it drops them all.
_SLOT_VECTORS = 256


def _row_signature(rows):
    """What one row of the batch-leading state tree ``rows`` is made of:
    the tree's structure and each leaf's shape and dtype."""
    leaves, treedef = jax.tree_util.tree_flatten(rows)
    return treedef, tuple((tuple(a.shape[1:]), np.dtype(a.dtype).str) for a in leaves)


@jax.jit
def _state_rows(slab, slots):
    return jax.tree_util.tree_map(lambda a: a[slots], slab)


@functools.partial(jax.jit, donate_argnums=(0,))
def _state_put(slab, slots, rows):
    return jax.tree_util.tree_map(
        lambda a, r: a.at[slots].set(r, mode="drop"), slab, rows)


class StateSlab:
    """One device's resident state for one row signature:
    each leaf of the row with a leading axis of ``capacity`` slots, plus a
    free list of slots.  A sequence owns a slot; the decode lane gathers
    its batch's rows out of the slab and scatters the stepped rows back in
    one jitted call each, so the state never leaves the device between
    steps.  When the slots run out the slab doubles (``state_slab_grows``
    in ``counters``).

    ``take`` and ``put`` run under ``lock``, since ``put`` donates the slab.
    ``warm`` compiles the gather and the scatter, with a step between them,
    at the row counts a caller will use; a slab that grows warms them again
    at its new size.  Slot index vectors are copied to the device once and
    kept (a batch's slots change only when a sequence joins or leaves it,
    and a copy up costs more host time than the dispatch it feeds); each
    copy counts as ``h2d_bytes`` under ``state_slots``, host rows written in
    under ``state``."""

    def __init__(self, device, signature, capacity: int, counters: Counters):
        self.device = device
        self.signature = signature
        self.counters = counters
        self.row_bytes = sum(int(np.prod(shape)) * np.dtype(dt).itemsize
                             for shape, dt in signature[1])
        self.lock = threading.RLock()
        self.capacity = 0
        self.arrays = None
        self.warm_rows: "tuple[int, ...]" = ()
        self._free: "list[int]" = []
        self._slot_vectors: "dict[bytes, jax.Array]" = {}
        self._grow(max(1, int(capacity)))

    def _on_device(self, slots) -> jax.Array:
        """The slot index vector ``slots`` on the slab's device."""
        idx = np.asarray(slots, np.int32)
        key = idx.tobytes()
        vec = self._slot_vectors.get(key)
        if vec is None:
            if len(self._slot_vectors) >= _SLOT_VECTORS:
                self._slot_vectors.clear()
            _sent(self.counters, "state_slots", idx)
            vec = self._slot_vectors[key] = jax.device_put(idx, self.device.jax_device)
        return vec

    def _grow(self, capacity: int) -> None:
        treedef, leaves = self.signature
        dev = self.device.jax_device
        fresh = [jnp.zeros((capacity - self.capacity, *shape), dt, device=dev)
                 for shape, dt in leaves]
        if self.arrays is not None:
            fresh = [jnp.concatenate([a, f]) for a, f in
                     zip(jax.tree_util.tree_leaves(self.arrays), fresh)]
        self.arrays = jax.tree_util.tree_unflatten(treedef, fresh)
        self._free = list(range(capacity - 1, self.capacity - 1, -1)) + self._free
        self.capacity = capacity

    def alloc(self) -> int:
        with self.lock:
            if not self._free:
                self._grow(2 * self.capacity)
                self.counters.add("state_slab_grows")
                self.warm(self.warm_rows)
            return self._free.pop()

    def free(self, slot: int) -> None:
        with self.lock:
            if not 0 <= slot < self.capacity or slot in self._free:
                raise ValueError(f"slot {slot} is not a taken slot of this slab")
            self._free.append(slot)

    @property
    def num_free(self) -> int:
        with self.lock:
            return len(self._free)

    def take(self, slots) -> Any:
        """The rows at ``slots`` as one batch-leading tree on the device."""
        with self.lock:
            return _state_rows(self.arrays, self._on_device(slots))

    def put(self, slots, rows) -> None:
        """Scatter the batch-leading ``rows`` into ``slots``; a row aimed
        at ``_NO_SLOT`` writes nothing.  Host rows are copied up first."""
        if _row_signature(rows) != self.signature:
            raise ValueError("rows do not match the slab's state signature")
        dev = self.device.jax_device
        if not all(isinstance(a, jax.Array) and a.devices() == {dev}
                   for a in jax.tree_util.tree_leaves(rows)):
            _sent(self.counters, "state", rows)
            rows = jax.device_put(rows, dev)
        with self.lock:
            self.arrays = _state_put(self.arrays, self._on_device(slots), rows)

    def warm(self, rows: "Sequence[int]", step: "Callable | None" = None) -> None:
        """Compile the gather, ``step`` (batch-leading state in, stepped
        state out) and the scatter at each row count of ``rows``, writing
        nothing; the counts are warmed again if the slab grows."""
        self.warm_rows = tuple(sorted(set(self.warm_rows) | set(rows)))
        for n in rows:
            with self.lock:
                st = _state_rows(self.arrays, jax.device_put(
                    np.zeros(n, np.int32), self.device.jax_device))
            if step is not None:
                st = step(st)
            with self.lock:
                self.arrays = _state_put(self.arrays, jax.device_put(
                    np.full(n, _NO_SLOT, np.int32), self.device.jax_device), st)


class PagePool:
    """Per-device page pool: two slab Buffers + a free list.

    All slab mutation happens under ``lock`` — the prefill lane (paging
    a prompt in), the decode lane (swapping the stepped slabs back) and
    the spiller (reading a victim's pages out) race otherwise.  Page
    moves count their host-device bytes in ``counters``, the owning
    cache's.
    """

    def __init__(self, device, spec: PageSpec, num_pages: int, counters: Counters):
        if num_pages < 2:
            raise ValueError("PagePool needs >= 2 pages (page 0 is reserved)")
        self.device = device
        self.spec = spec
        self.num_pages = int(num_pages)
        self.counters = counters
        shape = (spec.layers, self.num_pages, spec.page_size,
                 spec.kv_heads, spec.head_dim)
        self.k_slab = device.create_buffer(shape, spec.dtype).get()
        self.v_slab = device.create_buffer(shape, spec.dtype).get()
        for b in (self.k_slab, self.v_slab):
            self._repin(b)
        self.lock = threading.RLock()
        self._free: "list[int]" = list(range(self.num_pages - 1, 0, -1))
        # Resident state next to the pages: one slab per
        # row signature, made by its first row.
        self.state_slabs: "dict[Any, StateSlab]" = {}

    def state_slab(self, signature, capacity: int = 1) -> StateSlab:
        """This device's slab for rows of ``signature``, made with
        ``capacity`` slots when there is none yet."""
        slab = self.state_slabs.get(signature)
        if slab is None:
            with self.lock:
                slab = self.state_slabs.get(signature)
                if slab is None:
                    slab = self.state_slabs[signature] = StateSlab(
                        self.device, signature, capacity, self.counters)
        return slab

    @staticmethod
    def _repin(buf) -> None:
        """Move a slab's AGAS record to kind ``"pool"`` at 0 bytes: the
        slab must be invisible to ``spill_lru`` (kind filter) and to the
        resident-bytes pressure signal — usage is accounted per sequence
        (``SeqPages``), capacity is not pressure."""
        agas.registry.unregister(buf.gid)
        if buf._finalizer is not None:
            buf._finalizer.detach()
        buf.gid = agas.registry.register(
            buf,
            agas.Placement(buf.device.key, buf.device.jax_device.process_index),
            kind="pool",
            nbytes=0,
        )
        buf._finalizer = weakref.finalize(buf, agas.registry.unregister, buf.gid)

    # -- allocation ----------------------------------------------------------

    def alloc(self, n: int) -> "list[int]":
        with self.lock:
            if n > len(self._free):
                raise OutOfPages(
                    f"{self.device.key}: need {n} page(s), {len(self._free)} free "
                    f"of {self.num_pages - 1}"
                )
            return [self._free.pop() for _ in range(n)]

    def free(self, pages: "Sequence[int]") -> None:
        with self.lock:
            for p in pages:
                if not 0 < p < self.num_pages:
                    raise ValueError(f"page {p} is not an allocatable page of this pool")
                if p in self._free:
                    raise ValueError(f"double free of page {p} on {self.device.key}")
                self._free.append(p)

    @property
    def num_free(self) -> int:
        with self.lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - self.num_free

    # -- slab views ----------------------------------------------------------

    def arrays(self) -> "tuple[jax.Array, jax.Array]":
        with self.lock:
            return self.k_slab.array(), self.v_slab.array()

    def set_arrays(self, k, v) -> None:
        """Swap the stepped slabs back in (decode returns whole pools —
        donation made the update in-place on device)."""
        with self.lock:
            self.k_slab._set_array(k)
            self.v_slab._set_array(v)

    def write_pages(self, pages: "Sequence[int]", k, v, stage: str = "other") -> None:
        """Scatter page contents into the slabs: k/v are
        ``(n, L, P, Kh, D)`` host or device arrays, one row per page.
        ``stage`` names the copy in the byte counters.

        Runs through a jitted, slab-donating scatter with the page count
        padded to a power of two (duplicate trailing index, same value —
        a benign rewrite): eager ``.at[].set`` would copy the whole slab
        AND recompile for every distinct page count."""
        n = len(pages)
        if n == 0:
            return
        idx = _pow2_pad_idx(np.asarray(pages, np.int32))
        k, v = _fetched(self.counters, stage, (k, v))
        kk = np.moveaxis(k, 0, 1)
        vv = np.moveaxis(v, 0, 1)
        if idx.size != n:
            kk = np.concatenate([kk, np.repeat(kk[:, -1:], idx.size - n, axis=1)], axis=1)
            vv = np.concatenate([vv, np.repeat(vv[:, -1:], idx.size - n, axis=1)], axis=1)
        _sent(self.counters, stage, (idx, kk, vv))
        dev = self.device.jax_device
        with self.lock:
            ks, vs = self.k_slab.array(), self.v_slab.array()
            idxd = jax.device_put(idx, dev)
            self.k_slab._set_array(_slab_scatter(ks, idxd, jax.device_put(kk, dev)))
            self.v_slab._set_array(_slab_scatter(vs, idxd, jax.device_put(vv, dev)))

    def read_pages(self, pages: "Sequence[int]",
                   stage: str = "other") -> "tuple[np.ndarray, np.ndarray]":
        """Gather page contents out: ``(n, L, P, Kh, D)`` host arrays.
        Jitted gather, page count padded to a power of two (extra rows
        sliced off) — same compile-churn guard as ``write_pages``.
        ``stage`` names the copy in the byte counters."""
        n = len(pages)
        if n == 0:
            sh = (0, self.spec.layers, self.spec.page_size,
                  self.spec.kv_heads, self.spec.head_dim)
            return np.empty(sh, self.spec.dtype), np.empty(sh, self.spec.dtype)
        idx = _pow2_pad_idx(np.asarray(pages, np.int32))
        _sent(self.counters, stage, idx)
        with self.lock:
            ks, vs = self.k_slab.array(), self.v_slab.array()
            idxd = jax.device_put(idx, self.device.jax_device)
            kg, vg = _slab_gather(ks, idxd), _slab_gather(vs, idxd)
        kg, vg = _fetched(self.counters, stage, (kg, vg))
        return np.moveaxis(kg, 1, 0)[:n], np.moveaxis(vg, 1, 0)[:n]

    def __repr__(self) -> str:
        return (f"PagePool({self.device.key}: {self.used_pages}/"
                f"{self.num_pages - 1} pages used)")


class SeqPages:
    """One sequence's pages: the AGAS-visible unit of KV residency.

    Registered kind ``"buffer"`` with ``nbytes`` = pages × page bytes
    (re-declared on every alloc/free), exposing ``gid``/``device``/
    ``nbytes`` so the §9 affinity scoring, the §14 memory veto AND
    ``spill_lru`` all see sequences as first-class residents: the
    scheduler places decode where a sequence's pages live, and evicts the
    least-recently-*decoded* sequence under pressure.  ``spill`` copies
    the pages to host RAM and returns them to the pool (record moves to
    ``agas.HOST_KEY``); ``ensure_resident`` re-allocates and writes back.
    """

    def __init__(self, cache: "PagedKVCache", pool: PagePool, seq_id: int):
        self._cache = cache
        self.pool = pool
        self.seq_id = seq_id
        self.pages: "list[int]" = []
        self.length = 0
        # Per-sequence resident state — SSM recurrent state,
        # conv windows, cross K/V — lives in a slot of the device's slab
        # for its row signature and rides with the pages through
        # spill/migrate/export.  Its bytes fold into ``nbytes`` so the
        # memory-aware scheduler and the LRU spiller see recurrent
        # residency as honestly as KV.
        self._slab: "StateSlab | None" = None
        self._slot: "int | None" = None
        # Host copies (k, v, state row or None) while spilled.
        self._spilled: "tuple | None" = None
        self._lock = threading.RLock()
        self._last_use = _now()
        dev = pool.device
        self.gid = agas.registry.register(
            self, agas.Placement(dev.key, dev.jax_device.process_index),
            kind="buffer", nbytes=0,
        )
        self._finalizer = weakref.finalize(self, agas.registry.unregister, self.gid)

    @property
    def device(self):
        return self.pool.device

    @property
    def nbytes(self) -> int:
        """Device-resident bytes: pages plus the recurrent state (which
        lives with the sequence — spilled sequences pin nothing)."""
        n = len(self.pages) * self.pool.spec.page_bytes
        if self._slot is not None:
            n += self._slab.row_bytes
        return n

    @property
    def spilled(self) -> bool:
        return self._spilled is not None

    @property
    def state(self) -> Any:
        """The resident state as one row: read out of the slot (device
        arrays), or the host copy while spilled; None without state."""
        with self._lock:
            if self._slot is not None:
                rows = self._slab.take([self._slot])
                return jax.tree_util.tree_map(lambda a: a[0], rows)
            return self._spilled[2] if self._spilled is not None else None

    def set_state(self, state, row: "int | None" = None) -> None:
        """Write the sequence's resident state into its slot
        on this device's slab for the row's signature.  ``state`` is one
        row, or, with ``row``, a batch-leading tree (a prefill's output)
        of which row ``row`` is this sequence's: the other rows write
        nothing, and rows already on the device stay there.  A slot is
        taken, and the bytes re-declared through AGAS — SSM/hybrid
        recurrent state is real device pressure the §14 spill and
        memory-aware placement must see — only when the signature
        changes.  ``None`` drops the state."""
        with self._lock:
            if self._spilled is not None:
                raise RuntimeError(
                    f"sequence #{self.seq_id} is spilled: refetch it first")
            if state is None:
                self._drop_slot()
                self._account()
                return
            if row is None:
                state = jax.tree_util.tree_map(
                    lambda a: a[None] if isinstance(a, jax.Array)
                    else np.asarray(a)[None], state)
            slab = self.pool.state_slab(_row_signature(state))
            if self._slab is not slab:
                self._drop_slot()
                self._slab, self._slot = slab, slab.alloc()
                self._account()
            slots = np.full(jax.tree_util.tree_leaves(state)[0].shape[0],
                            _NO_SLOT, np.int32)
            slots[row or 0] = self._slot
            slab.put(slots, state)

    def _drop_slot(self) -> None:
        if self._slot is not None:
            self._slab.free(self._slot)
        self._slab = self._slot = None

    def _account(self) -> None:
        try:
            agas.registry.update_nbytes(self.gid, self.nbytes)
        except KeyError:  # freed under a racing finalizer
            pass

    # -- spill / refetch (scheduler-driven, DESIGN.md §14) -------------------

    def spill(self) -> Future:
        """Evict to host RAM (future of True when pages or state were
        released): page contents and the state row copy out, the pages
        return to the pool's free list and the slot to its slab, and the
        AGAS record moves to ``HOST_KEY`` — device pressure drops
        immediately, exactly like ``Buffer.spill``."""
        return self.pool.device.ops_queue.submit(self._spill_now)

    def _spill_now(self) -> bool:
        with self._lock:
            if self._spilled is not None or not (self.pages or self._slot is not None):
                return False
            with span("paged.kv.spill", seq=self.seq_id):
                k, v = self.pool.read_pages(self.pages, stage="spill")
                state = None
                if self._slot is not None:
                    state = _fetched(self.pool.counters, "state", self.state)
                    self._drop_slot()
                self._spilled = (k, v, state)
            self.pool.counters.add("spilled_pages", len(self.pages))
            self.pool.free(self.pages)
            self.pages = []
            agas.registry.update_placement(
                self.gid,
                agas.Placement(agas.HOST_KEY, self.pool.device.jax_device.process_index),
            )
            self._account()
            return True

    def ensure_resident(self) -> None:
        """Refetch after a spill: re-allocate (page ids and the state slot
        may differ — the handle is the identity, not the page numbers) and
        write the host copies back."""
        with self._lock:
            if self._spilled is None:
                return
            k, v, state = self._spilled
            pages = self.pool.alloc(len(k))
            with span("paged.kv.refetch", seq=self.seq_id):
                self.pool.write_pages(pages, k, v, stage="refetch")
                self._spilled = None
                if state is not None:
                    self.set_state(state)
            self.pool.counters.add("refetched_pages", len(pages))
            self.pages = pages
            dev = self.pool.device
            agas.registry.update_placement(
                self.gid, agas.Placement(dev.key, dev.jax_device.process_index))
            self._account()
            self._last_use = _now()

    def __repr__(self) -> str:
        state = "spilled" if self.spilled else self.pool.device.key
        return (f"SeqPages(#{self.seq_id}: {self.length} tok / "
                f"{len(self.pages)} pages @ {state})")


class PagedKVCache:
    """Fleet-wide paged KV allocator: one ``PagePool`` per device plus
    the sequence lifecycle (``new_seq``/``append``/``free_seq``), pool
    compaction (``defrag``) and coalesced cross-device ``migrate``.
    ``counters`` holds the totals of the cache and its pools."""

    def __init__(self, spec: PageSpec, devices: "Sequence | None" = None,
                 pool_pages: "int | None" = None,
                 pool_bytes: int = 32 << 20):
        if devices is None:
            from repro.core.device import get_all_devices

            devices = list(get_all_devices().get())
        if pool_pages is None:
            pool_pages = max(2, pool_bytes // spec.page_bytes)
        self.spec = spec
        self.counters = Counters()
        self.pools: "dict[str, PagePool]" = {
            d.key: PagePool(d, spec, pool_pages, self.counters) for d in devices
        }
        self._seq_lock = threading.Lock()
        self._next_seq = 0
        self._seqs: "dict[int, SeqPages]" = {}

    def pool_of(self, device) -> PagePool:
        try:
            return self.pools[device.key]
        except KeyError:
            raise KeyError(f"no page pool on {device.key}") from None

    # -- sequence lifecycle --------------------------------------------------

    def new_seq(self, device) -> SeqPages:
        pool = self.pool_of(device)
        with self._seq_lock:
            sid = self._next_seq
            self._next_seq += 1
            seq = self._seqs[sid] = SeqPages(self, pool, sid)
        return seq

    def append(self, seq: SeqPages, k, v) -> None:
        """Page ``T`` new tokens in: k/v are ``(L, T, Kh, D)``.  Partial
        tail pages are zero-padded to the page boundary (masked by
        ``length`` at attention time)."""
        seq.ensure_resident()
        P = self.spec.page_size
        with seq._lock, span("paged.kv.append", seq=seq.seq_id):
            k, v = _fetched(self.counters, "prompt_kv", (k, v))
            L, T, Kh, D = k.shape
            if seq.length % P:
                raise ValueError(
                    "append must start on a page boundary (decode steps append "
                    "token-at-a-time inside decode_fn, not through append)"
                )
            n = self.spec.pages_for(T)
            pages = seq.pool.alloc(n)
            pad = n * P - T
            if pad:
                k = np.concatenate([k, np.zeros((L, pad, Kh, D), k.dtype)], axis=1)
                v = np.concatenate([v, np.zeros((L, pad, Kh, D), v.dtype)], axis=1)
            # (L, n*P, Kh, D) -> (n, L, P, Kh, D): one write per append.
            seq.pool.write_pages(
                pages,
                np.moveaxis(k.reshape(L, n, P, Kh, D), 1, 0),
                np.moveaxis(v.reshape(L, n, P, Kh, D), 1, 0),
                stage="prompt_kv",
            )
            seq.pages.extend(pages)
            seq.length += T
            seq._last_use = _now()
            seq._account()

    def ensure_slot(self, seq: SeqPages) -> None:
        """Grow the sequence by one page when the next decoded token has
        no slot (length sits on a page boundary)."""
        with seq._lock:
            if len(seq.pages) * self.spec.page_size < seq.length + 1:
                seq.pages.extend(seq.pool.alloc(1))
                seq._account()

    def note_decoded(self, seq: SeqPages) -> None:
        """One token was scattered into the sequence's tail slot by
        ``decode_fn``; the bookkeeping catches up here."""
        with seq._lock:
            seq.length += 1
            seq._last_use = _now()

    def free_seq(self, seq: SeqPages) -> None:
        with seq._lock:
            if seq.pages:
                seq.pool.free(seq.pages)
            seq.pages = []
            seq._spilled = None
            seq._drop_slot()
            seq.length = 0
            if seq._finalizer is not None:
                seq._finalizer.detach()
                seq._finalizer = None
            agas.registry.unregister(seq.gid)
        with self._seq_lock:
            self._seqs.pop(seq.seq_id, None)

    # -- layout for the kernel -----------------------------------------------

    def table(self, seqs: "Sequence[SeqPages]", max_pages: int):
        """(page_table (B, max_pages) int32, lengths (B,) int32) in the
        ``paged_attention`` layout: padding slots hold the reserved page
        0 so the kernel's prefetched DMAs stay in bounds."""
        B = len(seqs)
        tbl = np.zeros((B, max_pages), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, s in enumerate(seqs):
            n = len(s.pages)
            if n > max_pages:
                raise ValueError(
                    f"sequence #{s.seq_id} has {n} pages, table width is {max_pages}"
                )
            tbl[i, :n] = s.pages
            lens[i] = s.length
        return tbl, lens

    @staticmethod
    def _slab_of(seqs: "Sequence[SeqPages]") -> "StateSlab | None":
        slab = seqs[0]._slab
        if any(s._slab is not slab for s in seqs):
            raise ValueError("a batch's sequences must share one state slab")
        return slab

    def state_rows(self, seqs: "Sequence[SeqPages]", rows: int):
        """The resident state of ``seqs`` as one ``(rows, ...)`` tree,
        gathered on the device out of their slab in one call (rows past
        ``len(seqs)`` repeat the last), or None when they hold none."""
        slab = self._slab_of(seqs)
        if slab is None:
            return None
        slots = [s._slot for s in seqs]
        return slab.take(slots + slots[-1:] * (rows - len(slots)))

    def put_state_rows(self, seqs: "Sequence[SeqPages]", state) -> None:
        """Scatter a stepped ``(rows, ...)`` state tree back into the slots
        of ``seqs`` in one call on the device; rows past ``len(seqs)`` (pad
        rows) write nothing."""
        slots = np.full(jax.tree_util.tree_leaves(state)[0].shape[0],
                        _NO_SLOT, np.int32)
        slots[:len(seqs)] = [s._slot for s in seqs]
        self._slab_of(seqs).put(slots, state)

    # -- maintenance ---------------------------------------------------------

    def defrag(self, device) -> int:
        """Compact a pool: live pages move to the lowest slots (stable
        order), sequence tables are rewritten, the free list becomes the
        contiguous tail.  Returns the number of pages that moved.

        Lock discipline: every holder's ``seq._lock`` is acquired FIRST
        (in ``seq_id`` order) and only then the pool lock — the same
        seq-then-pool order spill/migrate/append/decode use, so the
        compaction serializes against an in-flight spill or decode step
        instead of deadlocking with it (pool-then-seq here would be the
        classic ABBA).  The free list is rebuilt from the locked holders'
        pages, so if the holder set changed while the locks were being
        collected (a raced-in ``new_seq``/``migrate`` allocated pages the
        pass cannot see), everything is released and the pass retries;
        after a few contended passes it returns 0 — defrag is
        maintenance, not a correctness gate."""
        pool = self.pool_of(device)
        moved = 0
        for _ in range(8):
            with self._seq_lock:
                holders = sorted(
                    (s for s in self._seqs.values() if s.pool is pool),
                    key=lambda s: s.seq_id)
            with contextlib.ExitStack() as stack:
                for s in holders:
                    stack.enter_context(s._lock)
                with pool.lock:
                    with self._seq_lock:
                        current = [s for s in self._seqs.values()
                                   if s.pool is pool]
                    if any(s not in holders for s in current):
                        continue  # unlocked holder raced in — retry
                    holders = [s for s in holders if s.pool is pool and s.pages]
                    live: "list[int]" = []
                    for s in holders:
                        live.extend(s.pages)
                    mapping = {old: new
                               for new, old in enumerate(sorted(live), start=1)}
                    moved = sum(1 for old, new in mapping.items() if old != new)
                    if moved:
                        order = np.arange(pool.num_pages, dtype=np.int32)
                        for old, new in mapping.items():
                            order[new] = old
                        _sent(self.counters, "defrag", (order, order))
                        ks, vs = pool.arrays()
                        pool.set_arrays(ks[:, order], vs[:, order])
                        for s in holders:
                            s.pages = [mapping[p] for p in s.pages]
                    pool._free = list(range(pool.num_pages - 1, len(live), -1))
                    return moved
        return 0

    def migrate(self, seq: SeqPages, device) -> None:
        """Re-home a sequence: ALL its pages leave the source slabs as one
        stacked read and land in the target pool as one stacked write —
        the §10 lesson (batch the percolation, never per-page transfers)
        applied to rebalancing.  The state row moves into the target
        device's slab.  The AGAS record moves with the pages, so affinity
        immediately scores the new home."""
        dst = self.pool_of(device)
        with seq._lock:
            if seq.pool is dst:
                return
            seq.ensure_resident()
            src = seq.pool
            with coalesce():
                k, v = src.read_pages(seq.pages, stage="migrate")
                pages = dst.alloc(len(seq.pages))
                dst.write_pages(pages, k, v, stage="migrate")
            state = None
            if seq._slot is not None:
                state = _fetched(self.counters, "state", seq.state)
                seq._drop_slot()
            src.free(seq.pages)
            seq.pool = dst
            seq.pages = pages
            if state is not None:
                seq.set_state(state)
            agas.registry.update_placement(
                seq.gid, agas.Placement(device.key, device.jax_device.process_index))
            seq._account()
            seq._last_use = _now()

    # -- cross-locality shipping (prefill -> decode disaggregation) ----------

    def export_seq(self, seq: SeqPages) -> dict:
        """Ship-ready snapshot of one sequence: page contents leave the
        slabs as ONE coalesced gather (``read_pages``), plus length and
        the resident state.  Plain numpy throughout — over a parcelport
        ``invoke`` the big arrays ride the PR 6 shm lane, so a prefill
        locality can hand a finished prompt to a decode locality without
        serializing megabytes through the control channel."""
        with seq._lock:
            seq.ensure_resident()
            k, v = seq.pool.read_pages(seq.pages, stage="export")
            state = _fetched(self.counters, "state", seq.state)
            return {"k": k, "v": v, "length": int(seq.length), "state": state}

    def import_seq(self, device, payload: dict) -> SeqPages:
        """Inverse of ``export_seq``, usually on another locality's
        cache: allocate, ONE coalesced scatter, state re-attached (its
        bytes re-declared against THIS device) — decode resumes from the
        shipped table as if the prompt had prefilled here."""
        seq = self.new_seq(device)
        k, v = _fetched(self.counters, "import", (payload["k"], payload["v"]))
        with seq._lock:
            pages = seq.pool.alloc(len(k))
            seq.pool.write_pages(pages, k, v, stage="import")
            seq.pages = pages
            seq.length = int(payload["length"])
            if payload.get("state") is not None:
                seq.set_state(payload["state"])
            seq._account()
            seq._last_use = _now()
        return seq

    def stats(self) -> dict:
        out = {}
        for key, pool in self.pools.items():
            out[key] = {
                "used_pages": pool.used_pages,
                "free_pages": pool.num_free,
                "resident_bytes": agas.registry.resident_bytes(key),
            }
        out["spilled_bytes"] = agas.registry.spilled_bytes()
        return out


class RequestTimeline(NamedTuple):
    """A finished request, as ``PagedServeEngine.metrics()`` reads it:
    host times (``time.monotonic``) of its arrival, prefill launch, first
    and last output token, its token count, and the gaps between its
    consecutive tokens (steps it sat out included)."""

    rid: int
    arrived: float
    launched: float
    first_token: float
    last_token: float
    tokens: int
    gaps: np.ndarray


class _PagedRequest:
    __slots__ = ("tokens", "max_new", "promise", "arrived", "seq", "out",
                 "launched", "token_times", "handed_off", "rid", "sampling",
                 "extras")

    def __init__(self, tokens, max_new, promise, arrived, rid=0,
                 sampling=None, extras=None):
        self.tokens = tokens
        self.max_new = max_new
        self.promise = promise
        self.arrived = arrived
        # Identity + knobs: ``rid`` keys the sampling PRNG
        # stream, ``sampling`` is a SamplingParams (None = greedy),
        # ``extras`` carries per-request modality inputs (whisper frames).
        self.rid = rid
        self.sampling = sampling
        self.extras = extras
        self.seq: "SeqPages | None" = None
        self.out: "list[int]" = []
        self.launched = arrived  # prefill launch
        self.token_times: "list[float]" = []  # host time each of ``out`` was appended
        # True once prefill is done with the request — settled or admitted
        # to a decode lane.  A prefill-batch failure must fail only the
        # requests still owned by prefill: settling an already-admitted
        # request's promise again would raise InvalidStateError out of
        # whichever lane thread finishes it.
        self.handed_off = False


def _filled(pol: "LanePolicy | None", **defaults) -> LanePolicy:
    """``pol`` with each unset field taken from ``defaults``: an explicit
    0 or 0.0 is a real bound (dispatch now), matching
    ``RequestEngine._lane_bounds``."""
    got = {} if pol is None else {
        k: v for k, v in dataclasses.asdict(pol).items() if v is not None}
    return LanePolicy(**{**defaults, **got})


class PagedServeEngine:
    """Prefill/decode-disaggregated serving over a ``PagedKVCache``.

    ``submit(prompt, max_new_tokens)`` returns a future of the generated
    token ids (np.int32).  One prefill lane batches prompts by token
    budget and pages their KV onto the scheduler-chosen device; one
    decode lane per device steps every resident sequence continuously in
    exact-row batches.  See the module docstring for the model contract
    and the placement/rebalance protocol.
    """

    def __init__(self, kv: PagedKVCache, prefill_fn: Callable, decode_fn: Callable,
                 *, weights: dict, max_seq_len: int, scheduler=None,
                 prefill: "LanePolicy | None" = None,
                 decode: "LanePolicy | None" = None,
                 max_queue: int = 512, rebalance_every: int = 32,
                 decode_shapes: "Sequence[int] | None" = None,
                 name: str = "paged"):
        if set(weights) != set(kv.pools):
            raise ValueError(
                f"weights must hold one params replica per pool device "
                f"{sorted(kv.pools)}, got {sorted(weights)}")
        self.kv = kv
        # device key -> that device's params replica.
        self.weights = weights
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        # The step as built, which ``_warm_state_path`` compiles: a wrapper
        # set over ``decode_fn`` later (one that counts served steps) sees
        # served steps alone.
        self._built_decode_fn = decode_fn
        self._next_rid = 0
        # Optional row-count palette preseeded into every decode lane's
        # warm-shape set (see _DecodeLane): a closed palette (e.g. powers
        # of two up to max_batch) makes the set of compiled decode shapes
        # deterministic across runs — benchmarks want that — at the cost
        # of padding whenever occupancy is off-palette.  None (default)
        # learns watermarks as they occur: ~0 steady-state padding,
        # compile count bounded by distinct high-water marks instead.
        self.decode_shapes = (
            tuple(sorted({int(s) for s in decode_shapes if int(s) > 0}))
            if decode_shapes is not None else ())
        self.name = name
        self.max_seq_len = int(max_seq_len)
        self.max_pages = kv.spec.pages_for(self.max_seq_len)
        self._scheduler = scheduler
        self.max_queue = int(max_queue)
        self.rebalance_every = max(1, int(rebalance_every))
        self.prefill_policy = _filled(
            prefill if prefill is not None else LanePolicy(token_budget=2048),
            max_batch=8, max_delay_s=0.004, token_budget=1 << 30)
        self.decode_policy = _filled(decode, max_batch=64, max_delay_s=0.001)

        self._cv = threading.Condition()
        self._queue: "list[_PagedRequest]" = []
        # Requests popped from the queue but not yet admitted/settled:
        # without this, drain() sees an idle engine while a prefill batch
        # is mid-flight (counted by neither the queue nor any lane).
        self._inflight = 0
        self._closed = False

        # Per-device decode lanes: inbox + thread, created on first use.
        self._lane_lock = threading.Lock()
        self._lanes: "dict[str, _DecodeLane]" = {}

        # Metrics: the window's counts (``reset_metrics`` zeroes them), the
        # cache's monotonic counters (shared with its pools; ``counters()``)
        # with their totals at the window's start, and the timelines of
        # the most recent finished requests.
        self._m_lock = threading.Lock()
        self._started_at = _now()
        self._submitted = 0
        self._completed = 0
        self._failed = 0
        self._prefill_batches = 0
        self._prefill_rows = 0
        self._prefill_padded = 0
        self._decode_rows = 0
        self._decode_padded = 0
        self._migrations = 0
        self._counters = kv.counters
        self._counted_before: dict = {}
        self._recent: "collections.deque[RequestTimeline]" = collections.deque(
            maxlen=_RECENT_REQUESTS)

        self._prefill_thread = threading.Thread(
            target=self._prefill_loop, name=f"paged:{name}:prefill", daemon=True)
        self._prefill_thread.start()

    # -- construction from the model zoo -------------------------------------

    @classmethod
    def from_config(cls, cfg, *, devices=None, params=None, seed: int = 0,
                    max_seq_len: "int | None" = None,
                    pool_pages: "int | None" = None,
                    pool_bytes: int = 32 << 20, **kw) -> "PagedServeEngine":
        """Wire any zoo architecture (``repro.configs``) into a paged
        engine: one ``PageSpec`` from ``paged_spec`` (multi-layer KV
        folded into one slab geometry), a jitted prefill and a jitted
        slab-donating decode step from ``paged_prefill`` /
        ``paged_decode_step``.  ``params`` defaults
        to ``init(cfg, PRNGKey(seed))`` — two localities building from
        the same seed hold bit-identical weights, which is what lets a
        shipped sequence resume decoding elsewhere.  The weights are
        copied once to every pool device and passed to the jitted steps
        as arguments."""
        from repro.models.model import get_model

        spec_fn, pre, dec = zoo_steps(cfg)
        spec = spec_fn(cfg)
        if params is None:
            params = get_model(cfg).init(cfg, jax.random.PRNGKey(int(seed)))
        kv = PagedKVCache(spec, devices=devices, pool_pages=pool_pages,
                          pool_bytes=pool_bytes)
        if max_seq_len is None:
            max_seq_len = 16 * spec.page_size
        weights = {key: jax.device_put(params, pool.device.jax_device)
                   for key, pool in kv.pools.items()}
        kw.setdefault("name", f"paged-{getattr(cfg, 'name', cfg.family)}")
        return cls(kv, pre, dec, weights=weights, max_seq_len=int(max_seq_len), **kw)

    # -- submission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               sampling: "SamplingParams | None" = None,
               extras: "dict | None" = None,
               request_id: "int | None" = None) -> Future:
        """Queue one request.  ``sampling`` selects the
        host-side sampler (None = greedy); ``extras`` carries modality
        inputs (e.g. whisper ``frames``); ``request_id`` keys the
        sampling PRNG stream — pass an explicit, fleet-stable id when
        reproducibility across deployments matters, else submission
        order numbers the stream."""
        tokens = np.asarray(prompt, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError("empty prompt")
        total = tokens.size + int(max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({tokens.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.max_seq_len})")
        promise: Promise = Promise(name=f"{self.name}:seq")
        with self._m_lock:
            rid = self._next_rid if request_id is None else int(request_id)
            self._next_rid += 1
        req = _PagedRequest(tokens, int(max_new_tokens), promise, _now(),
                            rid=rid, sampling=sampling, extras=extras)
        with self._cv:
            if self._closed:
                raise EngineClosed(f"engine {self.name!r} is closed")
            if len(self._queue) >= self.max_queue:
                raise QueueFull(
                    f"engine {self.name!r} admission queue is full "
                    f"({self.max_queue}) — backpressure: shed or retry")
            self._queue.append(req)
            self._cv.notify_all()
        with self._m_lock:
            self._submitted += 1
        return promise.get_future()

    def reset_metrics(self) -> None:
        """Zero the counts of ``metrics()`` and drop its record of finished
        requests (placement state, warm decode shapes, resident pages and
        the monotonic ``counters()`` are untouched).  Benchmarks call this
        after a warm-up pass so ``metrics()`` reflects only the measured
        window — warm-pass XLA compiles would otherwise dominate every
        latency percentile."""
        with self._m_lock:
            self._started_at = _now()
            self._submitted = self._completed = self._failed = 0
            self._prefill_batches = 0
            self._prefill_rows = self._prefill_padded = 0
            self._decode_rows = self._decode_padded = 0
            self._migrations = 0
            self._counted_before = self._counters.snapshot()
            self._recent.clear()

    def counters(self) -> dict:
        """Monotonic totals of the engine, its cache and its pools (see
        docs/index.md, "Tracing the engine"): ``admitted``,
        ``admission_wait_s``, ``prefill_tokens``, ``output_tokens``,
        ``decode_steps``, ``decode_active_row_steps``,
        ``decode_left_out_row_steps``, ``state_rows_on_device``,
        ``state_slab_grows``, ``spilled_pages``, ``refetched_pages``, and
        ``h2d_bytes`` / ``d2h_bytes`` keyed by stage.  A total appears
        once something has been counted in it."""
        return self._counters.snapshot()

    def __enter__(self) -> "PagedServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._prefill_thread.join(timeout=60)
        with self._lane_lock:
            lanes = list(self._lanes.values())
        for lane in lanes:
            lane.close()

    def drain(self) -> None:
        """Block until every submitted sequence has finished: nothing
        queued, nothing mid-prefill, nothing active on a decode lane."""
        while True:
            with self._cv:
                queued = len(self._queue) + self._inflight
            with self._lane_lock:
                active = sum(lane.active_count() for lane in self._lanes.values())
            if not queued and not active:
                return
            time.sleep(0.002)

    # -- prefill lane (throughput: token-budget batching) --------------------

    def _scheduler_for(self):
        if self._scheduler is not None:
            return self._scheduler
        from repro.core.scheduler import get_scheduler

        return get_scheduler()

    def _prefill_loop(self) -> None:
        pol = self.prefill_policy
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue:
                    return
                head = self._queue[0]
                deadline = head.arrived + pol.max_delay_s
                T = head.tokens.size
                cap = min(pol.max_batch, max(1, pol.token_budget // max(T, 1)))
                while (not self._closed and _now() < deadline
                       and sum(1 for r in self._queue if r.tokens.size == T) < cap):
                    self._cv.wait(timeout=max(deadline - _now(), 0.0005))
                group, kept = [], []
                for r in self._queue:
                    if r.tokens.size == T and len(group) < cap:
                        group.append(r)
                    else:
                        kept.append(r)
                self._queue[:] = kept
                self._inflight += len(group)
            if group:
                try:
                    self._run_prefill(group)
                except BaseException as e:  # noqa: BLE001 - lane must not die
                    # Fail only the requests prefill still owns: members
                    # already admitted to a decode lane (or settled) must
                    # not be settled twice, and a failed member's pages
                    # must go back to the pool.
                    for r in group:
                        if r.handed_off:
                            continue
                        self._finish(r, e)
                        self._prefill_done(r)

    def _run_prefill(self, group: "list[_PagedRequest]") -> None:
        """Place every request of an equal-length group, then prefill each
        device's share of the group on that device, with its weights
        replica."""
        sched = self._scheduler_for()
        homes = [self._prefill_home(sched.select(args=())) for _ in group]
        for home in dict.fromkeys(homes):
            self._prefill_on([r for r, h in zip(group, homes) if h is home], home)

    def _prefill_home(self, dev):
        """The device a placed request prefills on: the scheduler's pick
        when it holds a pool of this engine, else the pool device with the
        most free pages (the scheduler may span a wider fleet)."""
        pool = self.kv.pools.get(dev.key)
        if pool is None:
            pool = max(self.kv.pools.values(), key=lambda p: p.num_free)
        return pool.device

    def _prefill_on(self, group: "list[_PagedRequest]", home) -> None:
        c = self._counters
        launched = _now()
        for r in group:
            r.launched = launched
        c.add("admitted", len(group))
        c.add("admission_wait_s", sum(launched - r.arrived for r in group))
        batch = np.stack([r.tokens for r in group])  # (B, T) — equal-T: no padding
        extras = None
        rid = group[0].rid
        if group[0].extras is not None:
            extras = {key: np.stack([np.asarray(r.extras[key]) for r in group])
                      for key in group[0].extras}
        _sent(c, "prefill_tokens", (batch, extras))
        with span("paged.prefill.step", rid=rid):
            # Committed inputs put the computation on ``home``, next to
            # that device's weights replica.  The state stays there.
            on_home = jax.device_put((batch, extras), home.jax_device)
            k, v, state, logits = self.prefill_fn(self.weights[home.key], *on_home)
            logits = _fetched(c, "logits", logits)
        c.add("prefill_tokens", batch.size)
        with span("paged.prefill.kv_to_host", rid=rid):
            k, v = _fetched(c, "prompt_kv", (k, v))
        # First token samples host-side at position 0 of each request's
        # own PRNG stream — batch composition cannot leak.
        nxt = [sample_token(logits[i], r.sampling, r.rid, 0)
               for i, r in enumerate(group)]
        # Page k.shape[2] tokens, not the prompt length: hybrid archs
        # prepend meta/register tokens whose KV pages in with the prompt.
        Tp = k.shape[2]
        with self._m_lock:
            self._prefill_batches += 1
            self._prefill_rows += len(group)
        for i, req in enumerate(group):
            pool = self._pool_with_room(home, self.kv.spec.pages_for(Tp) + 1)
            req.seq = self.kv.new_seq(pool.device)
            # k[i]: (L, T', Kh, D) — the whole prompt pages in as one write.
            self.kv.append(req.seq, k[i], v[i])
            if state is not None:
                self._warm_state_path(pool, state)
                req.seq.set_state(state, row=i)
            req.out.append(nxt[i])
            req.token_times.append(_now())
            c.add("output_tokens", 1)
            if req.max_new <= 1:
                self._finish(req)
            else:
                self._lane_for(pool.device).admit(req)
            self._prefill_done(req)

    def _warm_state_path(self, pool: PagePool, state) -> None:
        """Make ``pool``'s slab for rows like those of ``state`` (a
        prefill's batch-leading output) on first use, one slot per row of
        the decode cap, and compile the decode lane's path over it at
        every row count of ``decode_shapes``: the gather, the decode step
        and the scatter.  A device tree in the state's place compiles a decode
        executable apart from one warmed with host stacks, so this lands
        with the first stateful prefill rather than in a decode step."""
        slab = pool.state_slab(_row_signature(state),
                               capacity=self.decode_policy.max_batch)
        if slab.warm_rows or not self.decode_shapes:
            return
        weights = self.weights[pool.device.key]

        def step(st):
            n = jax.tree_util.tree_leaves(st)[0].shape[0]
            tbl = np.zeros((n, self.max_pages), np.int32)
            lens = np.zeros((n,), np.int32)
            with pool.lock:
                return _pool_step(pool, self._built_decode_fn, weights, st,
                                  np.ones((n,), np.int32), tbl, lens)[0]

        slab.warm(self.decode_shapes, step)

    def _prefill_done(self, req: "_PagedRequest") -> None:
        """Prefill is done with this request (admitted or settled): mark
        it so a later batch failure cannot settle it twice, and release
        its in-flight slot for ``drain``."""
        req.handed_off = True
        with self._cv:
            self._inflight -= 1

    def _pool_with_room(self, dev, need_pages: int) -> PagePool:
        """The chosen device's pool if it has room, else spill its LRU
        sequences to make room, else the pool with the most free pages —
        admission never fails while ANY pool can hold the prompt."""
        pool = self.kv.pools.get(dev.key)
        if pool is not None and pool.num_free >= need_pages:
            return pool
        if pool is not None:
            need = (need_pages - pool.num_free) * self.kv.spec.page_bytes
            for f in self._scheduler_for().spill_lru(dev, need):
                f.get()
            if pool.num_free >= need_pages:
                return pool
        best = max(self.kv.pools.values(), key=lambda p: p.num_free)
        if best.num_free < need_pages:
            raise OutOfPages(
                f"no pool has {need_pages} free page(s); deepest is "
                f"{best.device.key} with {best.num_free}")
        return best

    def _lane_for(self, device) -> "_DecodeLane":
        with self._lane_lock:
            lane = self._lanes.get(device.key)
            if lane is None:
                lane = self._lanes[device.key] = _DecodeLane(self, device)
            return lane

    # -- completion ----------------------------------------------------------

    def _finish(self, req: "_PagedRequest", exc: "BaseException | None" = None) -> None:
        if req.seq is not None:
            self.kv.free_seq(req.seq)
            req.seq = None
        # An already-settled promise is absorbed, not raised: double
        # settlement can only mean two completion paths raced (e.g. a
        # prefill-batch failure vs. a lane that already admitted the
        # request), and a lane thread dying here would hang every other
        # active sequence's future forever.
        if exc is not None:
            try:
                req.promise.set_exception(exc)
            except _cf.InvalidStateError:
                return
            with self._m_lock:
                self._failed += 1
            return
        try:
            req.promise.set_value(np.asarray(req.out, np.int32))
        except _cf.InvalidStateError:
            return
        times = req.token_times
        timeline = RequestTimeline(
            req.rid, req.arrived, req.launched, times[0], times[-1], len(times),
            np.diff(times)) if times else None
        with self._m_lock:
            self._completed += 1
            if timeline is not None:
                self._recent.append(timeline)

    # -- metrics -------------------------------------------------------------

    @staticmethod
    def _pct(xs, q: float) -> float:
        xs = np.sort(np.asarray(xs, np.float64))
        if not xs.size:
            return 0.0
        return float(xs[int(q * (xs.size - 1))])

    def metrics(self) -> dict:
        """Counts since the last ``reset_metrics`` and latency percentiles
        over the most recent finished requests (at most
        ``_RECENT_REQUESTS``): ``ttft_p99_s`` from arrival to first token,
        ``token_latency_*`` over the gaps between a request's consecutive
        tokens, steps it sat out included."""
        counted = self._counters.snapshot()
        with self._m_lock:
            before = self._counted_before
            recent = list(self._recent)
            rows = self._prefill_rows + self._decode_rows
            padded = self._prefill_padded + self._decode_padded
            m = {
                "requests_submitted": self._submitted,
                "requests_completed": self._completed,
                "requests_failed": self._failed,
                "prefill_batches": self._prefill_batches,
                "prefill_tokens": (counted.get("prefill_tokens", 0)
                                   - before.get("prefill_tokens", 0)),
                "decode_steps": (counted.get("decode_steps", 0)
                                 - before.get("decode_steps", 0)),
                "rows": rows,
                "padded_rows": padded,
                "padding_waste": (padded / rows) if rows else 0.0,
                "migrations": self._migrations,
            }
        gaps = np.concatenate([t.gaps for t in recent]) if recent else []
        ttft = [t.first_token - t.arrived for t in recent]
        m["token_latency_p50_s"] = self._pct(gaps, 0.50)
        m["token_latency_p99_s"] = self._pct(gaps, 0.99)
        m["ttft_p99_s"] = self._pct(ttft, 0.99)
        elapsed = max(_now() - self._started_at, 1e-9)
        m["elapsed_s"] = elapsed
        m["seqs_per_s"] = m["requests_completed"] / elapsed
        m["kv"] = self.kv.stats()
        try:
            m["placements"] = self._scheduler_for().stats()
        except Exception:  # noqa: BLE001 - metrics never fail the caller
            pass
        with self._lane_lock:
            m["active_by_device"] = {
                k: lane.active_count() for k, lane in self._lanes.items()}
            m["decode_steps_by_device"] = {
                k: lane.steps for k, lane in self._lanes.items()}
        return m

    def __repr__(self) -> str:
        return (f"PagedServeEngine({self.name}: {self._completed}/"
                f"{self._submitted} sequences)")


def _pool_step(pool: PagePool, step_fn: Callable, params, state, tokens,
               tables, lengths, fetch: "Callable | None" = None):
    """The one call of a model decode step, over ``pool``'s slabs (the
    caller holds ``pool.lock``): ``fetch``, where given, takes the logits
    to the host before the stepped slabs swap in.  Returns the stepped
    state and the logits."""
    ks, vs = pool.arrays()
    k2, v2, state, logits = step_fn(
        params, ks, vs, state, tokens, lengths, tables, lengths)
    if fetch is not None:
        logits = fetch(logits)  # sync before the slabs swap
    pool.set_arrays(k2, v2)
    return state, logits


def _decode_rows(kv: PagedKVCache, pool: PagePool, step_fn: Callable, params,
                 seqs: "Sequence[SeqPages]", tokens, tables, lengths,
                 rows: int, *, step: int) -> np.ndarray:
    """One model decode step over ``seqs`` on ``pool``'s device, the
    operands padded to ``rows`` rows; returns the ``(rows, V)`` logits on
    the host.  Gathers the rows' resident state out of their slab (pad
    rows repeat the last row, and write nothing on the way back in), runs
    ``step_fn`` over the pool's slabs under ``pool.lock``, fetches the
    logits before the stepped slabs swap in, and scatters the stepped
    state back.  The caller has grown every tail by a slot, and
    ``note_decoded`` is its to call."""
    c = kv.counters
    state = None
    if seqs[0]._slot is not None:
        with span("paged.decode.state_in", step=step):
            state = kv.state_rows(seqs, rows)
        c.add("state_rows_on_device", len(seqs))
    with pool.lock, span("paged.decode.step", step=step):
        state, logits = _pool_step(pool, step_fn, params, state, tokens, tables,
                                   lengths, fetch=lambda a: _fetched(c, "logits", a))
    if state is not None:
        with span("paged.decode.state_out", step=step):
            kv.put_state_rows(seqs, state)
    return logits


class _DecodeLane:
    """One device's decode lane: continuous exact-row batched stepping.

    The lane thread owns the device's resident sequences.  Each
    iteration: fold in arrivals (deadline-bounded wait only when idle),
    take up to ``max_batch`` sequences, grow tails by a page where
    needed, run ONE ``decode_fn`` step over the pools, swap the slabs
    back, and retire finished sequences.  Mixed-length sequences share
    the step at their true lengths — no sequence-dimension padding ever,
    which is the entire point of paging.

    Row counts are kept shape-stable: ``decode_fn`` is jitted by the
    caller, so every new row count is a fresh XLA compile.  The lane
    remembers which row counts it has already run (``_warm``) and pads a
    smaller batch up to the nearest warm count — duplicating the last
    row, whose scatter rewrites the same slot with the same value and
    whose output is discarded — rather than compiling a one-off shape.
    A batch that sets a new high-water mark compiles exactly (and
    becomes warm), and padding is capped at 2x the real rows, so steady
    state runs exact with ~0 padding and a shrinking tail never
    recompiles."""

    def __init__(self, engine: PagedServeEngine, device):
        self.engine = engine
        self.device = device
        self._cv = threading.Condition()
        self._warm: "set[int]" = set(engine.decode_shapes)
        self._inbox: "list[_PagedRequest]" = []
        self._active: "list[_PagedRequest]" = []
        self._closed = False
        self._steps = 0
        self._stalls = 0  # consecutive steps where nothing fit in the pool
        self._thread = threading.Thread(
            target=self._loop, name=f"paged:{engine.name}:decode:{device.key}",
            daemon=True)
        self._thread.start()

    def admit(self, req: "_PagedRequest") -> None:
        with self._cv:
            self._inbox.append(req)
            self._cv.notify_all()

    def active_count(self) -> int:
        with self._cv:
            return len(self._inbox) + len(self._active)

    @property
    def steps(self) -> int:
        """Decode steps this lane has run."""
        return self._steps

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=60)

    def _loop(self) -> None:
        eng = self.engine
        pol = eng.decode_policy
        while True:
            with self._cv:
                if not self._active and not self._inbox:
                    if self._closed:
                        return
                    self._cv.wait(timeout=0.05)
                    continue
                if not self._active and self._inbox:
                    # Idle lane: give the batch one deadline window to
                    # fill (0.0 dispatches immediately).
                    deadline = _now() + pol.max_delay_s
                    while not self._closed and _now() < deadline:
                        self._cv.wait(timeout=max(deadline - _now(), 0.0005))
                self._active.extend(self._inbox)
                self._inbox.clear()
                # Residents first (stable, so round-robin order survives):
                # a spilled sequence can only rejoin once pages free up,
                # and putting it ahead of resident work would let one
                # unfittable sequence stall the whole lane.
                self._active.sort(key=lambda r: r.seq.spilled)
                batch = self._active[:pol.max_batch]
                active = len(self._active)
            if not batch:
                continue
            try:
                self._step(batch, active)
            except BaseException as e:  # noqa: BLE001 - fail the batch, not the lane
                with self._cv:
                    for r in batch:
                        if r in self._active:
                            self._active.remove(r)
                for r in batch:
                    eng._finish(r, e)

    def _step(self, batch: "list[_PagedRequest]", active: int) -> None:
        """One decode step over the ready members of ``batch``, taken
        from the lane's ``active`` sequences; the others sit it out."""
        eng = self.engine
        kv = eng.kv
        c = eng._counters
        step = self._steps
        # Page pressure IS the capacity limit on a small fleet: a
        # sequence whose pages cannot be made resident right now is
        # deferred — it stays active and retries as finishing sequences
        # free pages — rather than failed or force-spilling a batchmate
        # (which would thrash the same pool within one step).
        #
        # Every ready sequence's _lock is held from ensure_resident
        # through decode_fn and note_decoded, acquired in seq_id order
        # (the same order defrag uses).  The spiller's _spill_now and
        # defrag's compaction both take seq._lock first, so a batch
        # member's pages can be neither freed (and re-owned by a racing
        # prefill) nor renumbered between the page-table snapshot and
        # the scatter of the new token — without the pin, decode would
        # silently attend over another sequence's KV under pool
        # pressure, exactly the regime paging exists for.
        done: "list[_PagedRequest]" = []
        held: "list[SeqPages]" = []
        ok: "set[int]" = set()
        try:
            for r in sorted(batch, key=lambda q: q.seq.seq_id):
                s = r.seq
                s._lock.acquire()
                held.append(s)
                try:
                    s.ensure_resident()
                    kv.ensure_slot(s)
                except OutOfPages:
                    held.pop()
                    s._lock.release()
                    continue
                ok.add(s.seq_id)
            ready = [r for r in batch if r.seq.seq_id in ok]
            if not ready:
                self._stalls += 1
                if self._stalls > _MAX_DECODE_STALLS:
                    raise OutOfPages(
                        f"{self.device.key}: {len(batch)} sequence(s) stalled "
                        f"{self._stalls} consecutive steps waiting for pages — "
                        "the pool cannot hold this working set")
                time.sleep(0.002)  # wait for a sibling/finisher to free pages
                return
            self._stalls = 0
            batch = ready
            with span("paged.decode.table", step=step):
                seqs = [r.seq for r in batch]
                tbl, lens = kv.table(seqs, eng.max_pages)
                tokens = np.asarray([r.out[-1] for r in batch], np.int32)
                # Shape reuse (see class docstring): pad to the nearest warm
                # row count when that costs less than doubling the batch,
                # else compile this exact count and make it warm.
                b_real = len(batch)
                cand = min((w for w in self._warm if w >= b_real), default=None)
                want = cand if cand is not None and cand - b_real <= b_real else b_real
                self._warm.add(want)
                pad = want - b_real
                if pad:
                    tbl = np.concatenate([tbl, np.repeat(tbl[-1:], pad, axis=0)])
                    lens = np.concatenate([lens, np.repeat(lens[-1:], pad)])
                    tokens = np.concatenate([tokens, np.repeat(tokens[-1:], pad)])
            # Host operands ride the call uncommitted: the computation
            # follows the committed slabs to this lane's device, and the C++
            # dispatch path moves four tiny arrays faster than four
            # python-level device_put round-trips would.
            _sent(c, "decode_operands", (tokens, lens, tbl, lens))
            logits = _decode_rows(
                kv, kv.pool_of(self.device), eng.decode_fn,
                eng.weights[self.device.key], seqs, tokens, tbl, lens, want,
                step=step)
            with span("paged.decode.sample", step=step):
                # Position = tokens already emitted (prefill's token was
                # position 0): identity-keyed, batch-independent.
                nxt = [sample_token(logits[i], r.sampling, r.rid, len(r.out))
                       for i, r in enumerate(batch)]
            now = _now()
            for i, r in enumerate(batch):
                kv.note_decoded(r.seq)
                r.out.append(nxt[i])
                r.token_times.append(now)
                if len(r.out) >= r.max_new:
                    done.append(r)
        finally:
            for s in held:
                s._lock.release()
        # Direct-route placement charge (the fix select_batch alone cannot
        # make): this step never touched a lane queue, so the recency
        # counter is the only signal least_loaded has that this device
        # just did len(batch) rows of work.
        sched = eng._scheduler_for()
        charge = getattr(sched, "charge", None)
        if callable(charge):
            charge(self.device, len(batch))
        with eng._m_lock:
            eng._decode_rows += len(batch)
            eng._decode_padded += pad
        c.add("decode_steps")
        c.add("output_tokens", len(batch))
        c.add("decode_active_row_steps", active)
        c.add("decode_left_out_row_steps", active - len(batch))
        with self._cv:
            for r in done:
                self._active.remove(r)
            # Rotate survivors to the tail so an active set larger than
            # max_batch round-robins instead of starving the overflow.
            if len(self._active) > len(batch) - len(done):
                for r in batch:
                    if r in self._active:
                        self._active.remove(r)
                        self._active.append(r)
        for r in done:
            eng._finish(r)
        self._steps += 1
        if self._steps % eng.rebalance_every == 0:
            self._maybe_rebalance([r for r in batch if r not in done])

    def _maybe_rebalance(self, batch: "list[_PagedRequest]") -> None:
        """Ask the placement layer whether this lane's sequences still
        belong here: ``select_batch`` over the ``SeqPages`` handles keeps
        them home under affinity (the bytes ARE here) — unless memory
        pressure vetoes the device, in which case the coldest sequence
        migrates (one coalesced page move) to the chosen sibling.

        Gated on page pressure: with >=20% of the pool free there is
        nothing to rebalance away from, and under a pure load policy
        (``least_loaded`` scores this lane's own just-charged work)
        asking anyway ping-pongs sequences between lanes — each move a
        page gather + scatter — for no memory relief at all."""
        if not batch:
            return
        eng = self.engine
        pool = eng.kv.pool_of(self.device)
        if pool.num_free * 5 >= pool.num_pages:
            return
        sched = eng._scheduler_for()
        try:
            dev = sched.select_batch([[r.seq] for r in batch])
        except Exception:  # noqa: BLE001 - advisory; never fail decode
            return
        if dev.key == self.device.key or dev.key not in eng.kv.pools:
            return
        victim = min(batch, key=lambda r: r.seq._last_use)
        eng.kv.migrate(victim.seq, dev)
        with self._cv:
            self._active.remove(victim)
        with eng._m_lock:
            eng._migrations += 1
        eng._lane_for(dev).admit(victim)


# ---------------------------------------------------------------------------
# cross-locality disaggregation: parcel "invoke" actions (DESIGN.md §17)
# ---------------------------------------------------------------------------
#
# Prefill on one locality, decode on another: the prefill side runs
# ``paged_prefill`` + ``PagedKVCache.append`` locally, then ships
# ``export_seq``'s payload (pages as ONE coalesced gather, plus length
# and resident state) as a parcel —
#
#     port.call(lid, "invoke", {
#         "fn": "repro.serving.paged:paged_worker_decode",
#         "payload": {...}})
#
# — where the big arrays take the shm lane.  The decode side re-derives
# the weights from the config name + PRNG seed (bit-identical params;
# nothing but pages crosses the wire), imports the sequence into its own
# pool and resumes decoding from the shipped table.  Sampling stays
# keyed by (seed, request_id, position), so the shipped continuation is
# bit-identical to a single-locality decode.

_WORKER_LOCK = threading.Lock()
_WORKERS: "dict[str, dict]" = {}


def _worker_ctx(payload: dict) -> dict:
    """Decode-side context for one shipped-page stream, built once per
    ``name`` on this locality and cached: smoke'd (or full) config,
    seed-derived params placed on the worker's device, a single-device
    ``PagedKVCache`` and the shared jitted decode step (params passed as
    an argument)."""
    name = payload["name"]
    with _WORKER_LOCK:
        ctx = _WORKERS.get(name)
        if ctx is not None:
            return ctx
        from repro.configs import get_config
        from repro.configs import smoke as _smoke
        from repro.core.device import get_all_devices
        from repro.models.model import get_model

        cfg = get_config(payload["config"])
        if payload.get("smoke", True):
            cfg = _smoke(cfg)
        spec_fn, _, dec = zoo_steps(cfg)
        devs = list(get_all_devices().get())
        dev = devs[int(payload.get("device_index", 0)) % len(devs)]
        params = jax.device_put(
            get_model(cfg).init(cfg, jax.random.PRNGKey(int(payload.get("seed", 0)))),
            dev.jax_device)
        kv = PagedKVCache(spec_fn(cfg), devices=[dev],
                          pool_pages=payload.get("pool_pages"))
        ctx = _WORKERS[name] = {"cfg": cfg, "kv": kv, "dev": dev, "dec": dec,
                                "params": params}
        return ctx


def paged_worker_decode(payload: dict) -> np.ndarray:
    """Parcel ``invoke`` target: resume decoding a shipped sequence.

    payload keys: ``name`` (worker cache key), ``config`` (registry
    name), ``smoke``, ``seed``, ``device_index``, ``pool_pages``,
    ``seq`` (an ``export_seq`` payload), ``first_token`` (the
    prefill-sampled token), ``max_new``, ``max_pages`` (table width —
    must match the prefill side's so the attention geometry is
    identical), ``sampling`` (SamplingParams fields or None) and
    ``request_id``.  Returns all generated tokens (np.int32), first
    token included."""
    ctx = _worker_ctx(payload)
    kv: PagedKVCache = ctx["kv"]
    dev = ctx["dev"]
    pool = kv.pool_of(dev)
    seq = kv.import_seq(dev, payload["seq"])
    sp = payload.get("sampling")
    if sp is not None and not isinstance(sp, SamplingParams):
        sp = SamplingParams(**sp)
    rid = int(payload.get("request_id", 0))
    max_pages = int(payload["max_pages"])
    out = [int(payload["first_token"])]
    try:
        for step in range(int(payload["max_new"]) - 1):
            kv.ensure_slot(seq)
            tbl, lens = kv.table([seq], max_pages)
            tokens = np.asarray([out[-1]], np.int32)
            logits = _decode_rows(kv, pool, ctx["dec"], ctx["params"], [seq],
                                  tokens, tbl, lens, 1, step=step)
            kv.note_decoded(seq)
            out.append(sample_token(logits[0], sp, rid, len(out)))
    finally:
        kv.free_seq(seq)
    return np.asarray(out, np.int32)


def paged_worker_reset(payload: dict) -> bool:
    """Drop cached worker contexts (tests; ``payload`` may name one)."""
    with _WORKER_LOCK:
        name = (payload or {}).get("name")
        if name is None:
            _WORKERS.clear()
        else:
            _WORKERS.pop(name, None)
    return True
