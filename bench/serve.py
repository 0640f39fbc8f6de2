"""The system under test, as the benchmark drives it.

``make_weights`` makes the weights from the seed on the cell's first device
(the family's ``bench/reference/<family>.py`` ``init``, one jitted call) and
``build`` builds ``PagedServeEngine.from_config`` over the cell's devices
with the configuration file's engine settings; the engine copies the
weights to every other device.  ``Probe`` wraps the engine's two jitted
steps in ``TraceAnnotation`` spans and keeps a small record of every call:
the device it ran on, the prompt length of a prefill, the real rows of a
decode step with their resident lengths, when each step that makes output
tokens is launched and how many it makes, and the floating dtypes of the
weights, KV pages and resident state that the steps are handed and return.
``warm_up`` runs every prefill and decode shape the traffic can reach, on
every device.  ``OpenLoop`` submits each request at its due time,
``ClosedLoop`` keeps a fixed number of requests in flight; both record when
each future resolves.
"""
from __future__ import annotations

import dataclasses
import importlib
import threading
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` built from the configuration file."""
    from repro.configs.base import ArchConfig, SSMConfig

    m = dict(cfg["model"])
    if "ssm" in m:
        m["ssm"] = SSMConfig(**m["ssm"])
    return ArchConfig(name=cfg["name"], family=cfg["family"], source=cfg["source"], **m)


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.reference.{cfg['family']}")


def work_module(cfg: dict):
    return importlib.import_module(f"bench.work.{cfg['family']}")


def make_weights(cfg: dict, key, device):
    ref = reference_module(cfg)
    dtype = np.dtype(cfg["dtype"])
    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(lambda k: ref.init(cfg["model"], k, dtype), out_shardings=sharding)(key)


def device_of(array) -> int:
    """The id of the one device that holds a committed array."""
    return next(iter(array.devices())).id


class Probe:
    """Spans and call records around the engine's jitted steps.  A span is
    named ``bench.<kind>#<call>:<shape>@<device>``: the call's index in its
    record, its prompt length or padded rows, and the device its
    committed arguments put it on."""

    def __init__(self):
        # (T, tokens, last logits, device)
        self.prefills: "list[tuple[int, object, object, int]]" = []
        # (padded rows, first page of each real row, resident length of each, device)
        self.decodes: "list[tuple[int, np.ndarray, np.ndarray, int]]" = []
        self.dtypes: "set[str]" = set()
        self.produced: "list[tuple[float, int]]" = []  # (launch time, output tokens)
        self.lock = threading.Lock()

    def clear(self) -> None:
        with self.lock:
            self.prefills.clear()
            self.decodes.clear()
            self.dtypes.clear()
            self.produced.clear()

    def _stored(self, *trees) -> None:
        names = {str(a.dtype) for a in jax.tree_util.tree_leaves(trees)
                 if jax.numpy.issubdtype(a.dtype, jax.numpy.floating)}
        with self.lock:
            self.dtypes |= names

    def wrap(self, eng) -> None:
        prefill, decode = eng.prefill_fn, eng.decode_fn

        def traced_prefill(params, tokens, extras):
            with self.lock:
                i = len(self.prefills)
                self.prefills.append(None)
            T = int(tokens.shape[1])
            dev = device_of(tokens)
            self._stored(params)
            with self.lock:
                self.produced.append((time.perf_counter(), int(tokens.shape[0])))
            with TraceAnnotation(f"bench.prefill#{i}:{T}@{dev}"):
                out = prefill(params, tokens, extras)
            self.prefills[i] = (T, tokens, out[3], dev)
            self._stored(out[:3])
            return out

        def traced_decode(params, k_pages, v_pages, state, tokens, positions, tables, lengths):
            # Pad rows repeat the last real row, and each real row's first
            # page is its own: the distinct first pages count the real rows.
            first = np.asarray(tables)[:, 0]
            real = len(np.unique(first))
            dev = device_of(k_pages)
            with self.lock:
                i = len(self.decodes)
                self.decodes.append((int(first.size), first[:real].copy(),
                                     np.asarray(lengths)[:real].copy(), dev))
                self.produced.append((time.perf_counter(), real))
            self._stored(params, k_pages, v_pages, state)
            with TraceAnnotation(f"bench.decode#{i}:{tables.shape[0]}@{dev}"):
                return decode(params, k_pages, v_pages, state, tokens, positions, tables, lengths)

        eng.prefill_fn, eng.decode_fn = traced_prefill, traced_decode


def build(cfg: dict, devices, weights, *, name: str):
    """The engine over ``devices`` (JAX devices), placing requests round
    robin; ``weights`` lie on the first."""
    from repro.core import get_all_devices
    from repro.core.scheduler import Scheduler
    from repro.serving import LanePolicy, PagedServeEngine

    e = cfg["engine"]
    fleet = {d.jax_device: d for d in get_all_devices().get()}
    devices = [fleet[d] for d in devices]
    return PagedServeEngine.from_config(
        arch_config(cfg), params=weights, devices=devices,
        max_seq_len=e["max_seq_len"], pool_bytes=e["pool_bytes"],
        scheduler=Scheduler(devices, policy="round_robin"),
        prefill=LanePolicy(max_batch=e["prefill_max_batch"]),
        decode=LanePolicy(max_batch=e["decode_max_batch"]),
        decode_shapes=e["decode_shapes"], max_queue=e["max_queue"], name=name)


def warm_up(eng, probe: Probe, prompt_lengths) -> None:
    """Run every shape the traffic reaches, on every device: prefill at one
    row for each palette length (and the page write of its prompt) through
    the engine, submitted until ``probe`` has seen that length prefilled on
    every device, whatever the placement (round robin needs one submit a
    device; a placement that never reaches a device stops the run here,
    not with a compile in the window); then on each device the page gather
    and write of a sequence spilled to the host and fetched back, at every
    page count it can have, and the decode step at every warm row count."""
    devices = {pool.device.jax_device.id for pool in eng.kv.pools.values()}
    for T in sorted(set(int(t) for t in prompt_lengths)):
        prompt = np.ones((T,), np.int32)
        submits = 0
        while not devices <= {p[3] for p in probe.prefills if p is not None and p[0] == T}:
            if submits == 4 * len(devices):
                raise RuntimeError(f"warm-up: the placement put no {T}-token prompt on some of "
                                   f"devices {sorted(devices)} in {submits} submits")
            eng.submit(prompt, 2, request_id=-T).get(timeout=1200)
            submits += 1
    # The pool pads a move to a power of two of pages (``_pow2_pad_idx``).
    least = eng.kv.spec.pages_for(min(prompt_lengths))
    sizes = {1 << (n - 1).bit_length() for n in range(least, eng.max_pages + 1)}
    for dev_key, weights in eng.weights.items():
        pool = eng.kv.pools[dev_key]
        for n in sorted(sizes):
            pages = list(range(1, min(n, pool.num_pages - 1) + 1))
            pool.write_pages(pages, *pool.read_pages(pages))
        T = min(prompt_lengths)
        on_dev = jax.device_put((np.ones((1, int(T)), np.int32), None), pool.device.jax_device)
        _, _, state, _ = eng.prefill_fn(weights, *on_dev)
        state_row = None
        if state is not None:
            state_row = jax.tree_util.tree_map(lambda a: np.zeros(a.shape[1:], np.asarray(a).dtype), state)
        for B in eng.decode_shapes:
            tables = np.zeros((B, eng.max_pages), np.int32)
            lens = np.zeros((B,), np.int32)
            tokens = np.ones((B,), np.int32)
            st = None
            if state_row is not None:
                st = jax.tree_util.tree_map(lambda a: np.stack([a] * B), state_row)
            with pool.lock:
                ks, vs = pool.arrays()
                k2, v2, _, logits = eng.decode_fn(weights, ks, vs, st, tokens, lens, tables, lens)
                np.asarray(logits)
                pool.set_arrays(k2, v2)


@dataclasses.dataclass
class Sent:
    req: object
    due: float               # absolute perf_counter time
    sent: float = 0.0
    done: "float | None" = None
    future: object = None


class OpenLoop:
    """Submits each request at its due time (absolute ``origin + due_s``)."""

    def __init__(self, eng, requests, origin: float):
        self.eng = eng
        self.sent = [Sent(r, origin + r.due_s) for r in requests]
        self.next = 0
        # (time, in flight, used pages of each pool)
        self.samples: "list[tuple[float, int, tuple[int, ...]]]" = []

    def _complete(self, s: Sent):
        def cb(value):
            with TraceAnnotation("bench.complete"):
                s.done = time.perf_counter()
            return value
        return cb

    def in_flight(self) -> int:
        return sum(1 for s in self.sent[:self.next] if s.done is None and not s.future.done())

    def run_until(self, t_end: float, pools=(), tick: float = 0.1) -> None:
        """Submit everything due before ``t_end``; sample the backlog and the
        page pools at ``tick`` intervals meanwhile."""
        last = 0.0
        while True:
            now = time.perf_counter()
            if now - last >= tick:
                self.samples.append((now, self.in_flight(), tuple(p.used_pages for p in pools)))
                last = now
            if self.next < len(self.sent) and self.sent[self.next].due < t_end:
                s = self.sent[self.next]
                if s.due <= now:
                    with TraceAnnotation("bench.submit"):
                        s.sent = time.perf_counter()
                        s.future = self.eng.submit(s.req.prompt, s.req.max_new, request_id=s.req.rid)
                        s.future.then(self._complete(s), executor="inline")
                    self.next += 1
                    continue
                wake = min(s.due, last + tick, t_end)
            else:
                if now >= t_end:
                    return
                wake = min(last + tick, t_end)
            time.sleep(max(0.0, wake - time.perf_counter()))

    def wait(self, sent, deadline: float) -> None:
        for s in sent:
            left = deadline - time.perf_counter()
            if left <= 0:
                return
            try:
                s.future.get(timeout=left)
            except Exception:  # noqa: BLE001 - failures are counted, not raised
                pass


class ClosedLoop:
    """``clients`` clients, each sending the next request of ``requests``
    (in order) the moment its previous one resolves, with no think time.
    A request is due when it is sent: its ``Sent.due`` is its send time.
    The interface is ``OpenLoop``'s; ``wait`` keeps the clients sending
    until the requests it waits for have resolved, so that the last of them
    see the same load as the rest."""

    def __init__(self, eng, requests, clients: int):
        self.eng = eng
        self.requests = list(requests)
        self.clients = int(clients)
        self.sent: "list[Sent]" = []
        self.next = 0
        self.samples: "list[tuple[float, int, tuple[int, ...]]]" = []
        self._open: "list[Sent]" = []
        self._freed = threading.Event()

    def _complete(self, s: Sent):
        def cb(value):
            with TraceAnnotation("bench.complete"):
                s.done = time.perf_counter()
            self._freed.set()
            return value
        return cb

    def in_flight(self) -> int:
        self._open = [s for s in self._open if s.done is None and not s.future.done()]
        return len(self._open)

    def _fill(self) -> None:
        """Send requests until ``clients`` are in flight."""
        self._freed.clear()
        while self.in_flight() < self.clients:
            if self.next == len(self.requests):
                raise RuntimeError(f"closed loop: all {len(self.requests)} requests were sent; "
                                   "the mix needs a longer list")
            req = self.requests[self.next]
            with TraceAnnotation("bench.submit"):
                now = time.perf_counter()
                s = Sent(req, now, now)
                s.future = self.eng.submit(req.prompt, req.max_new, request_id=req.rid)
                s.future.then(self._complete(s), executor="inline")
            self.sent.append(s)
            self._open.append(s)
            self.next += 1

    def run_until(self, t_end: float, pools=(), tick: float = 0.1) -> None:
        """Keep ``clients`` requests in flight until ``t_end``; sample the
        requests in flight and the page pools at ``tick`` intervals.  A
        resolved request wakes the loop at once; one that fails is found by
        the next tick's look."""
        last = 0.0
        while True:
            now = time.perf_counter()
            if now - last >= tick:
                self.samples.append((now, self.in_flight(), tuple(p.used_pages for p in pools)))
                last = now
            if now >= t_end:
                return
            self._fill()
            self._freed.wait(max(0.0, min(last + tick, t_end) - time.perf_counter()))

    def wait(self, sent, deadline: float, tick: float = 0.1) -> None:
        """Keep the clients sending until every request of ``sent`` has
        resolved or ``deadline`` has passed; then send nothing more."""
        while (any(s.done is None and not s.future.done() for s in sent)
               and time.perf_counter() < deadline):
            self._fill()
            self._freed.wait(max(0.0, min(tick, deadline - time.perf_counter())))
