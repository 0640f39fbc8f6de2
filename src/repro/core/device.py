"""Logical device abstraction (paper §4, Fig. 2 ``device``).

A ``Device`` wraps one ``jax.Device`` (local *or* remote — in
multi-controller JAX remote accelerators appear as non-addressable entries
of ``jax.devices()``) and exposes HPXCL's surface:

  * ``create_buffer``  — async allocation (``cudaMalloc`` analogue)
  * ``create_program`` — async program creation (NVRTC source analogue)
  * per-device work lanes: ``ops`` (transfers/launch submission order) and
    ``compile`` (runtime compilation), separate so that building a kernel
    overlaps data transfers exactly as in Listing 2
  * ``create_stream`` / ``default_stream`` — N ordered lanes per device
    (``cudaStream_t`` analogue, DESIGN.md §11): independent transfer/
    launch chains overlap, same-stream order is preserved;
    ``ops_queue`` IS the default stream's lane, so stream-less code keeps
    the exact single-queue semantics
  * ``synchronize``    — drain ALL the device's streams (not just the
    default lane) plus the compile queue

``get_all_devices(major, minor)`` mirrors the paper's Listing 1: it returns
a *future* of the device list, filtered by a minimum capability.

Scheduler surface (DESIGN.md §9): ``Device.load()`` exposes the ops-queue
backlog and ``Device.resident_bytes()`` the AGAS byte total placed here —
the two signals the ``least_loaded`` and ``affinity`` placement policies
read.  ``Locality`` groups devices by owning process (HPX locality
analogue); ``get_all_localities()`` mirrors ``hpx::find_all_localities``.

Remote proxies (DESIGN.md §10): ``RemoteDevice``/``RemoteBuffer`` are the
parcel-backed twins of ``Device``/``Buffer`` — same async surface, but
``create_buffer`` / ``enqueue_write`` / ``enqueue_read`` / ``free`` (and
launches, through ``RemoteProgram``) travel as parcels to the owning
locality and resolve the caller's futures from reply parcels.  A proxy's
``ops_queue`` is a real local ``WorkQueue``: it orders parcel submission
per remote device and feeds the same ``load()`` signal the placement
policies read for local devices.
"""
from __future__ import annotations

import os
import threading
import weakref
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core import agas
from repro.core.executor import LaneDispatcher, QueueLoad, WorkQueue, get_runtime
from repro.core.futures import Future
from repro.core.stream import Stream

__all__ = [
    "Device",
    "Locality",
    "RemoteDevice",
    "RemoteBuffer",
    "get_all_devices",
    "get_all_localities",
    "capability_of",
]

# Pseudo "compute capability" per platform so the Listing-1 signature keeps
# meaning on TPU/CPU: (major, minor).
_PLATFORM_CAPABILITY = {
    "cpu": (1, 0),
    "gpu": (7, 0),
    "cuda": (7, 0),
    "rocm": (7, 0),
    "tpu": (9, 0),
}


def capability_of(jax_device: "jax.Device") -> "tuple[int, int]":
    return _PLATFORM_CAPABILITY.get(jax_device.platform, (1, 0))


def _default_memory_limit() -> int:
    """Per-device resident-bytes threshold for memory-aware placement
    (DESIGN.md §14).  0 means unlimited — the veto and LRU spill are off.
    The env default seeds every device; the attribute is plain and
    per-device, so a heterogeneous fleet can set different ceilings."""
    try:
        return int(os.environ.get("REPRO_SPILL_BYTES", "0") or 0)
    except ValueError:
        return 0


class Device:
    """Location-transparent handle to one accelerator."""

    def __init__(self, jax_device: "jax.Device"):
        self.jax_device = jax_device
        self.key = f"{jax_device.platform}:{jax_device.id}"
        rt = get_runtime()
        # Streams multiplex onto one lane dispatcher per device
        # (DESIGN.md §11); compilation keeps its own queue (NVRTC) so
        # building a kernel overlaps transfers on any stream.
        self._dispatcher: LaneDispatcher = rt.dispatcher(f"ops:{self.key}")
        self._streams: "list[Stream]" = []
        self._stream_lock = threading.Lock()
        self._replay_streams: "dict[int, Stream]" = {}
        self._default_stream = self.create_stream(name="default")
        # Back-compat alias: the default stream's lane IS the ops queue —
        # stream-less submission order is unchanged.
        self.ops_queue = self._default_stream.lane
        self.compile_queue: WorkQueue = rt.queue(f"compile:{self.key}")
        # Memory-aware placement threshold (DESIGN.md §14); 0 = unlimited.
        self.memory_limit: int = _default_memory_limit()
        self.gid: agas.GID = agas.registry.register(
            self, agas.Placement(self.key, jax_device.process_index), kind="device"
        )

    # -- identity ----------------------------------------------------------

    @property
    def platform(self) -> str:
        return self.jax_device.platform

    @property
    def process_index(self) -> int:
        return self.jax_device.process_index

    @property
    def is_local(self) -> bool:
        return self.jax_device.process_index == jax.process_index()

    def capability(self) -> "tuple[int, int]":
        return capability_of(self.jax_device)

    # -- streams (cudaStream_t analogue, DESIGN.md §11) ----------------------

    @property
    def default_stream(self) -> Stream:
        """Stream 0: the lane stream-less ops order through (``ops_queue``)."""
        return self._default_stream

    def create_stream(self, name: "str | None" = None) -> Stream:
        """A new ordered lane of work on this device (``cudaStreamCreate``).

        Work on distinct streams runs concurrently (the dispatcher
        multiplexes lanes onto a shared pool); work within one stream is
        strictly FIFO.  Streams are cheap — a deque plus counters; worker
        threads are pooled."""
        with self._stream_lock:
            idx = len(self._streams)
            label = name if name is not None else f"s{idx}"
            # Lane key is index-prefixed: dispatcher.lane() memoizes by
            # name, and two streams must NEVER share a lane (a user name
            # colliding with an auto 's{idx}' or 'replay' lane would
            # silently serialize them — or deadlock a wait_event).
            lane = self._dispatcher.lane(f"{idx}.{label}")
            s = Stream(self, lane, name=f"{self.key}/{label}")
            self._streams.append(s)
            return s

    def streams(self) -> "list[Stream]":
        with self._stream_lock:
            return list(self._streams)

    def _replay_lane(self, chain: int):
        """Lane carrying fused-graph chain ``chain`` at replay (DESIGN.md
        §11): chain 0 rides the default stream; higher chains get
        dedicated, memoized replay streams so independent chains of any
        captured graph overlap without growing a lane per ``GraphExec``."""
        if chain == 0:
            return self.ops_queue
        with self._stream_lock:
            s = self._replay_streams.get(chain)
            if s is None:
                # 'replay.' keys cannot collide with create_stream's
                # '{idx}.{label}' keys (idx is always an integer).
                lane = self._dispatcher.lane(f"replay.{chain}")
                s = Stream(self, lane, name=f"{self.key}/replay{chain}")
                self._streams.append(s)
                self._replay_streams[chain] = s
        return s.lane

    # -- scheduler signals --------------------------------------------------

    def load(self) -> QueueLoad:
        """Whole-device backlog snapshot: per-lane depths summed across
        every stream (``least_loaded`` input, DESIGN.md §9/§11)."""
        return self._dispatcher.load()

    def resident_bytes(self) -> int:
        """AGAS-registered bytes currently placed here (``affinity`` input)."""
        return agas.registry.resident_bytes(self.key)

    # -- factory surface (all async, returning futures) ---------------------

    def create_buffer(self, shape, dtype=np.float32, fill: Any = None) -> "Future":
        """Allocate a device buffer (async; ``cudaMalloc`` analogue).

        ``shape`` may be an int (1-D length in *elements*, not bytes — the
        dtype-safe adaptation of HPXCL's byte counts) or a tuple.
        """
        from repro.core.buffer import Buffer

        def _alloc():
            return Buffer._allocate(self, shape, dtype, fill)

        return self.ops_queue.submit(_alloc)

    def create_buffer_from(self, data) -> "Future":
        """Allocate + write in one async op."""
        from repro.core.buffer import Buffer

        def _alloc():
            arr = np.asarray(data)
            buf = Buffer._allocate(self, arr.shape, arr.dtype, None)
            buf._array = jax.device_put(arr, self.jax_device)
            return buf

        return self.ops_queue.submit(_alloc)

    def create_program(self, kernels, name: str = "program") -> "Future":
        """Create a program from ``{kernel_name: callable}`` (async)."""
        from repro.core.program import Program

        return self.compile_queue.submit(lambda: Program(self, kernels, name=name))

    def create_program_with_file(self, path: str) -> "Future":
        """Load kernels from a python file defining ``KERNELS`` (percolation:
        source code shipped to and compiled at the device — NVRTC analogue).
        """
        from repro.core.program import Program

        return self.compile_queue.submit(lambda: Program.from_file(self, path))

    # -- graph capture (CUDA Graphs analogue) --------------------------------

    def capture(self, name: str = "captured"):
        """Begin a graph-capture region on this thread (DESIGN.md §8).

        Transfers and launches recorded inside are fused and replayed with
        a single hop on this device's ops queue:

            with dev.capture("step") as g:
                buf.enqueue_write(0, host)
                prog.run([buf], "k", out=[out])
                r = out.enqueue_read()
            exe = g.instantiate()
            result = exe.replay().get()   # result[r] is the np.ndarray
        """
        from repro.core.graph import capture as _capture

        return _capture(name)

    # -- synchronization ----------------------------------------------------

    def synchronize(self) -> None:
        """Drain ALL of this device's streams — every lane, not just the
        default one — plus the compile queue (``cudaDeviceSynchronize``).
        The barrier covers everything submitted to any stream before the
        call; lanes drain in parallel, so synchronizing never serializes
        otherwise-overlapping streams."""
        self._dispatcher.drain()
        self.compile_queue.drain()

    def __repr__(self) -> str:
        where = "local" if self.is_local else "remote"
        return f"Device({self.key}, {where}, gid={self.gid})"


class Locality:
    """One process's worth of devices (the HPX *locality* analogue).

    In multi-controller JAX each participating process owns the devices
    whose ``process_index`` matches; scheduling across localities is what
    makes a placement "remote".
    """

    def __init__(self, process_index: int, devices: "list[Device]"):
        self.process_index = process_index
        self.devices = list(devices)

    @property
    def is_local(self) -> bool:
        return self.process_index == jax.process_index()

    def __len__(self) -> int:
        return len(self.devices)

    def __iter__(self):
        return iter(self.devices)

    def __repr__(self) -> str:
        where = "local" if self.is_local else "remote"
        return f"Locality(process={self.process_index}, {where}, {len(self.devices)} device(s))"


# ---------------------------------------------------------------------------
# remote proxies (parcel-backed; DESIGN.md §10)
# ---------------------------------------------------------------------------


def _release_remote(port, locality_id: int, gid: int, proxied: bool) -> None:
    """GC finalizer for RemoteBuffer: retire the local proxy record (the
    resident-bytes accounting must not outlive the handle) and send a
    best-effort free parcel (never raises — the port or worker may already
    be gone at collection time)."""
    if proxied:
        agas.registry.unregister(gid)
    try:
        port.call(locality_id, "free", {"gid": gid})
    except Exception:  # noqa: BLE001
        pass


class RemoteDevice:
    """Parcel-backed handle to a device owned by another locality.

    Duck-types ``Device`` everywhere the runtime reads it: ``key``,
    ``ops_queue``/``compile_queue`` (real local queues — parcel submission
    order per remote device, and the scheduler's ``load()`` signal),
    ``load()``, ``resident_bytes()``, ``capability()``, plus ``alive()``
    (heartbeat-fed; a dead locality is excluded from placement).
    ``jax_device`` is a *local staging anchor* (the host CPU device):
    values bound for this device are normalized onto it before they are
    shipped in a parcel.
    """

    is_remote_proxy = True

    def __init__(self, port, locality_id: int, remote_key: str, platform: str = "cpu",
                 capability: "tuple[int, int]" = (1, 0)):
        self._port = port
        self.locality_id = locality_id
        self.remote_key = remote_key
        self.key = f"L{locality_id}/{remote_key}"
        self._platform = platform
        self._capability = tuple(capability)
        # Staging anchor, not the executor: values bound for the remote
        # device are staged in host memory, never on a local accelerator.
        self.jax_device = jax.devices("cpu")[0]
        rt = get_runtime()
        self.ops_queue: WorkQueue = rt.queue(f"parcel-ops:{self.key}")
        self.compile_queue: WorkQueue = rt.queue(f"parcel-compile:{self.key}")
        # Streams on a remote device are ordered parcel *channels*: each
        # stream gets its own submission queue, so parcels of one stream
        # stay strictly ordered while different streams' parcels may be
        # in flight concurrently (DESIGN.md §11).
        self._stream_lock = threading.Lock()
        self._streams: "list[Stream]" = [Stream(self, self.ops_queue, name=f"{self.key}/default")]
        # Same memory-aware threshold as local devices: the veto reads the
        # proxied AGAS byte total for this locality's device key.
        self.memory_limit: int = _default_memory_limit()
        self.gid: agas.GID = agas.registry.register(
            self, agas.Placement(self.key, locality_id), kind="device"
        )

    # -- identity ----------------------------------------------------------

    @property
    def platform(self) -> str:
        return self._platform

    @property
    def process_index(self) -> int:
        return self.locality_id

    @property
    def is_local(self) -> bool:
        return False

    def capability(self) -> "tuple[int, int]":
        return self._capability

    # -- streams (ordered parcel channels, DESIGN.md §11) --------------------

    @property
    def default_stream(self) -> Stream:
        return self._streams[0]

    def create_stream(self, name: "str | None" = None) -> Stream:
        """A new ordered parcel channel to this remote device: stream verbs
        become parcels submitted through the channel's own queue, so each
        stream's parcels keep submission order while channels overlap."""
        rt = get_runtime()
        with self._stream_lock:
            idx = len(self._streams)
            label = name if name is not None else f"s{idx}"
            # Index-prefixed queue key: rt.queue() memoizes by name, and
            # two channels must never share a queue (see Device.create_stream).
            chan = rt.queue(f"parcel-ops:{self.key}:{idx}.{label}")
            s = Stream(self, chan, name=f"{self.key}/{label}")
            self._streams.append(s)
            return s

    def streams(self) -> "list[Stream]":
        with self._stream_lock:
            return list(self._streams)

    def _replay_lane(self, chain: int):
        # Remote fused segments replay as ONE parcel each; keeping every
        # chain on the default channel preserves the run_segment ordering
        # the multi-locality replay tests pin down.
        return self.ops_queue

    # -- scheduler signals ---------------------------------------------------

    def load(self) -> QueueLoad:
        """Backlog summed across every parcel channel of this device."""
        loads = [s.lane.load() for s in self.streams()]
        return QueueLoad(
            depth=sum(l.depth for l in loads),
            inflight=sum(l.inflight for l in loads),
            busy_for=max((l.busy_for for l in loads), default=0.0),
            busy_time=sum(l.busy_time for l in loads),
            submitted=sum(l.submitted for l in loads),
            completed=sum(l.completed for l in loads),
            busy_ewma=sum(l.busy_ewma for l in loads),
        )

    def resident_bytes(self) -> int:
        return agas.registry.resident_bytes(self.key)

    def alive(self) -> bool:
        """Heartbeat verdict for the owning locality (scheduler exclusion)."""
        return self._port.alive(self.locality_id)

    # -- parcel plumbing -----------------------------------------------------

    def _call(self, action: str, lane=None, **payload) -> "Future":
        """Send one action parcel, ordered through this device's default
        channel — or, when ``lane`` is given, through that stream's own
        parcel channel (same-stream parcels keep submission order).

        On a non-pipelined port the channel worker blocks on each reply,
        so the next parcel of the channel is only sent once the previous
        one has executed.  On a pipelined port the channel task only
        *stages and flushes* the parcel — the reply resolves the returned
        future asynchronously, and the channel is free to ship the next
        parcel immediately (same-channel order still holds end-to-end:
        staging order is wire order is the worker's execution order).
        NOTE: with pipelining, a drained lane proves dispatch, not remote
        completion — completion fences go through ``synchronize()`` (a
        ``barrier`` parcel) or the returned future itself."""
        payload.setdefault("device", self.remote_key)
        port, loc = self._port, self.locality_id
        if not port.alive(loc):
            return Future.failed(RuntimeError(
                f"parcel {action!r} to locality L{loc} failed fast: the locality is dead "
                "(missed heartbeat or worker exit) and is excluded from placement"
            ))
        q = self.ops_queue if lane is None else lane
        if getattr(port, "pipelined", False):
            from repro.core.futures import Promise, forward_failure

            promise: "Promise" = Promise(name=f"parcel:{action}:L{loc}")

            def _ship():
                port.stage(loc, action, payload, promise)
                port.flush(loc)

            forward_failure(q.submit(_ship), promise)
            return promise.get_future()
        return q.submit(lambda: port.call_sync(loc, action, payload))

    # -- factory surface -----------------------------------------------------

    def create_buffer(self, shape, dtype=np.float32, fill: Any = None) -> "Future":
        """Allocate a buffer on the remote locality (async; the
        ``create_buffer`` action parcel)."""
        shape_p = list(shape) if isinstance(shape, (tuple, list)) else int(shape)
        fut = self._call("create_buffer", shape=shape_p, dtype=np.dtype(dtype).str, fill=fill)
        return fut.then(lambda rep: RemoteBuffer(self, rep["gid"], rep["shape"], rep["dtype"]),
                        executor="inline")

    def create_buffer_from(self, data) -> "Future":
        fut = self._call("create_buffer_from", data=np.asarray(data))
        return fut.then(lambda rep: RemoteBuffer(self, rep["gid"], rep["shape"], rep["dtype"]),
                        executor="inline")

    def create_program(self, kernels, name: str = "program") -> "Future":
        """Create a program on the remote locality.  ``kernels`` are
        *names* (str or list of str) resolved by the remote's kernel
        registry, or a ``{name: callable}`` dict whose callables stay
        local as shape-inference shadows (percolation by reference)."""
        from repro.core.program import RemoteProgram

        return self.compile_queue.submit(lambda: RemoteProgram(self, kernels, name=name))

    # -- graph capture -------------------------------------------------------

    def capture(self, name: str = "captured"):
        from repro.core.graph import capture as _capture

        return _capture(name)

    # -- synchronization -----------------------------------------------------

    def synchronize(self) -> None:
        """Drain EVERY parcel channel of this device (all streams, not
        just the default one) plus the compile queue.  On a pipelined
        port a drained lane only proves every parcel was *shipped*, so a
        ``barrier`` parcel (executed on the worker's action pool, in
        arrival order, after everything shipped before it) closes the gap
        to remote completion."""
        for s in self.streams():
            s.lane.drain()
        self.compile_queue.drain()
        if getattr(self._port, "pipelined", False) and self._port.alive(self.locality_id):
            self._call("barrier").get()

    def __repr__(self) -> str:
        state = "alive" if self.alive() else "DEAD"
        return f"RemoteDevice({self.key}, {state}, gid={self.gid})"


class RemoteBuffer:
    """Location-transparent handle to a buffer owned by another locality.

    The remote-minted GID is proxied into the local AGAS registry (with
    ``nbytes``), so placement policies score remote-resident bytes exactly
    like local ones.  Transfers are parcels: ``enqueue_write`` ships host
    data out, ``enqueue_read`` brings it back, ``copy_to`` chains the two
    (the explicit cross-locality percolation move).
    """

    is_remote_proxy = True
    is_remote_buffer = True

    def __init__(self, device: RemoteDevice, gid: int, shape, dtype):
        self.device = device
        self.gid = gid
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self._freed = False
        self._free_future: "Future | None" = None
        self._proxied = agas.registry.register_proxy(
            self, gid, agas.Placement(device.key, device.locality_id),
            kind="buffer", nbytes=self.nbytes,
        )
        self._finalizer = weakref.finalize(
            self, _release_remote, device._port, device.locality_id, gid, self._proxied
        )

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    # -- async transfer surface ----------------------------------------------

    def enqueue_write(self, offset: int, data, count: "int | None" = None,
                      stream=None) -> "Future":
        from repro.core.graph import current_graph

        if current_graph() is not None:
            raise NotImplementedError(
                "graph capture writes to local buffers only; stage remote "
                "transfers outside the capture region (remote buffers may be "
                "read as extern inputs)"
            )
        lane = None if stream is None else stream._lane_for(self.device)
        fut = self.device._call("enqueue_write", lane=lane, gid=self.gid, offset=offset,
                                data=np.asarray(data), count=count)
        if stream is not None:
            # Pipelined ports resolve the reply AFTER the lane task ends —
            # note it so record()/synchronize() mean remote completion.
            stream._note_completion(fut)
        return fut

    def enqueue_read(self, offset: int = 0, count: "int | None" = None,
                     stream=None) -> "Future":
        from repro.core.graph import current_graph

        g = current_graph()
        if g is not None:
            return g.read(self, offset=offset, count=count)
        lane = None if stream is None else stream._lane_for(self.device)
        fut = self.device._call("enqueue_read", lane=lane, gid=self.gid,
                                offset=offset, count=count)
        if stream is not None:
            stream._note_completion(fut)
        return fut

    def enqueue_read_sync(self, offset: int = 0, count: "int | None" = None, stream=None):
        from repro.core.graph import current_graph

        if current_graph() is not None:
            raise RuntimeError(
                "enqueue_read_sync inside a graph-capture region: the value "
                "does not exist until replay. Use enqueue_read()."
            )
        return self.enqueue_read(offset, count, stream=stream).get()

    def _read_now(self) -> np.ndarray:
        """Synchronous read bypassing the proxy queue — for callers already
        running ON this device's ops queue (graph extern reads), where an
        ``enqueue_read`` would deadlock behind the calling task."""
        return self.device._port.call_sync(
            self.device.locality_id,
            "enqueue_read",
            {"device": self.device.remote_key, "gid": self.gid, "offset": 0, "count": None},
        )

    def copy_to(self, target_device) -> "Future":
        """Percolation across localities: one read parcel here, one write
        on the target — future of the *new* buffer on ``target_device``."""
        if target_device is self.device:
            return Future.ready(self)
        pool = get_runtime().pool
        return self.enqueue_read().then(
            lambda host: target_device.create_buffer_from(host).get(),
            executor=pool,
            name=f"copy:gid{self.gid}",
        )

    # -- lifetime --------------------------------------------------------------

    def free(self) -> "Future":
        """Release the remote storage (idempotent; future of None).

        The free parcel is gated on a barrier across ALL of the device's
        parcel channels: channels are mutually unordered, so a free sent
        straight down the default channel could execute on the owning
        locality before writes/launches still in flight on a stream
        channel (remote use-after-free) — the same all-lanes rule as the
        local ``Buffer.free``."""
        if self._free_future is None:
            self._freed = True
            if self._finalizer is not None:
                self._finalizer.detach()
                self._finalizer = None
            if self._proxied:
                agas.registry.unregister(self.gid)
            dev = self.device
            others = [s.lane for s in dev.streams() if s.lane is not dev.ops_queue]
            if not others:
                self._free_future = dev._call("free", gid=self.gid)
            else:
                from repro.core.futures import when_all

                barrier = when_all([ch.submit(lambda: None) for ch in others])
                # The continuation submits the free parcel to the default
                # channel and waits for its reply — host pool, never
                # inline on a channel worker.
                self._free_future = barrier.then(
                    lambda _: dev._call("free", gid=self.gid).get(),
                    executor=get_runtime().pool,
                    name=f"free:gid{self.gid}",
                )
        return self._free_future

    # -- kernel-facing view ----------------------------------------------------

    def array(self):
        raise RuntimeError(
            f"RemoteBuffer gid={self.gid} lives on locality "
            f"L{self.device.locality_id}; its value is not addressable here — "
            "use enqueue_read() (or launch through a RemoteProgram on that locality)"
        )

    def __repr__(self) -> str:
        return f"RemoteBuffer(gid={self.gid}, {self.dtype}{list(self.shape)} @ {self.device.key})"


_device_cache: "dict[str, Device]" = {}


def _wrap(jd: "jax.Device") -> Device:
    key = f"{jd.platform}:{jd.id}"
    dev = _device_cache.get(key)
    if dev is None:
        dev = _device_cache[key] = Device(jd)
    return dev


def _on_runtime_reset() -> None:
    """Drop cached devices whose queues died with the old runtime.

    Called by ``executor.reset_runtime``: the cached ``Device``s hold
    ``WorkQueue``s from the runtime being torn down, so keeping them would
    make the next ``submit`` raise "WorkQueue ... is shut down".  Their
    AGAS records are retired too; the next ``get_all_devices`` re-wraps
    and re-registers every device against the fresh runtime.
    """
    devices = list(_device_cache.values())
    _device_cache.clear()
    for dev in devices:
        agas.registry.unregister(dev.gid)


def get_all_devices(major: int = 0, minor: int = 0) -> "Future[list[Device]]":
    """Discover every (local and remote) device with capability >= (major,
    minor). Returns a *future* of the list — call ``.get()`` (Listing 1)."""

    def _discover() -> "list[Device]":
        out = []
        for jd in jax.devices():
            if capability_of(jd) >= (major, minor):
                out.append(_wrap(jd))
        return out

    return get_runtime().async_(_discover)


def get_all_localities(major: int = 0, minor: int = 0, cluster=None) -> "Future[list[Locality]]":
    """Group capability-filtered devices by owning process
    (``hpx::find_all_localities`` analogue); future of the list, ordered
    by process index with the local locality's devices first within it.
    With ``cluster`` (a ``Parcelport``), the port's remote localities are
    appended — the cluster-wide discovery surface."""

    def _group() -> "list[Locality]":
        by_proc: "dict[int, list[Device]]" = {}
        for dev in get_all_devices(major, minor).get():
            by_proc.setdefault(dev.process_index, []).append(dev)
        locs = [Locality(pi, devs) for pi, devs in sorted(by_proc.items())]
        if cluster is not None:
            for loc in cluster.localities():
                devs = [d for d in loc if d.capability() >= (major, minor)]
                if devs:
                    locs.append(Locality(loc.process_index, devs))
        return locs

    return get_runtime().async_(_group)
