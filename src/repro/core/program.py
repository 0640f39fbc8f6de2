"""Program: runtime-compiled device code (paper §4, Fig. 2 ``program``).

The NVRTC analogue on TPU/JAX is the JIT itself: ``build`` runs
``jax.jit(kernel).lower(specs).compile()`` asynchronously on the device's
*compile* queue, so compilation overlaps data transfers exactly like
Listing 2 (copies and ``prog.build`` futures run concurrently, joined by
``wait_all``).  Compiled executables are cached per (kernel, shapes, grid,
block).

Launch semantics keep HPXCL's user-visible tuning knobs: ``grid`` and
``block`` (``Dim3``) are forwarded to kernels that accept them (our Pallas
kernels map them onto grid/BlockSpec tiling — the TPU equivalent of CUDA
launch geometry, DESIGN.md §2).

Percolation: ``run`` executes where the program's device is; argument
buffers living on other devices are first moved there with async copies
(futures), never blocking the caller.  Executables are pinned to the
program's device (input shardings fixed at lowering), so a launch really
runs *there*, not wherever XLA's default placement lands.

``run_on_any`` (DESIGN.md §9) is the scheduler-routed launch: a placement
policy picks the device, the program's per-device *sibling* (same kernels,
compiled for that device — the paper's "any kernel on any device") runs
it, and argument percolation plus ``out``-buffer re-homing happen
automatically.  This is §3 percolation done by policy instead of by hand.

Hot-path notes (DESIGN.md §8): signature inspection is done once per
kernel (``inspect.signature`` costs ~10 µs — far more than a queue hop),
bound callables are cached per (kernel, grid, block), and the executable
cache key hashes interned dtype objects instead of ``str(dtype)``.
Inside a ``graph.capture()`` region ``run`` records a symbolic node
instead of executing (CUDA-Graphs stream capture analogue).
"""
from __future__ import annotations

import importlib.util
import inspect
import weakref
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from repro.core.buffer import Buffer
from repro.core.futures import Future, dataflow, when_all

__all__ = ["Dim3", "Program", "RemoteProgram"]


def _is_remote_buffer(a: Any) -> bool:
    return getattr(a, "is_remote_buffer", False)


@dataclass
class Dim3:
    """CUDA-style launch geometry, kept user-visible (paper's philosophy)."""

    x: int = 1
    y: int = 1
    z: int = 1

    def as_tuple(self) -> "tuple[int, int, int]":
        return (self.x, self.y, self.z)


def pin_specs(specs, jax_device) -> list:
    """``ShapeDtypeStruct``s with shardings pinned to one device.

    Pinned lowering is what makes a launch execute where its program (or
    graph segment) lives instead of on XLA's default device.  Shared by
    ``Program.build`` and graph segment compilation so the two launch
    paths pin the same way.
    """
    sharding = jax.sharding.SingleDeviceSharding(jax_device)
    return [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding) for s in specs]


def _normalize_dim(d) -> "tuple[int, ...] | None":
    if d is None:
        return None
    if isinstance(d, Dim3):
        return d.as_tuple()
    if isinstance(d, int):
        return (d, 1, 1)
    return tuple(d)


class Program:
    """A named set of kernels compiled on demand for one device."""

    def __init__(self, device, kernels, name: str = "program"):
        from repro.core import agas

        if callable(kernels) and not isinstance(kernels, dict):
            kernels = {getattr(kernels, "__name__", "kernel"): kernels}
        self.device = device
        self.name = name
        self._kernels: "dict[str, Callable]" = dict(kernels)
        self._cache: "dict[tuple, Any]" = {}
        self._build_futures: "dict[tuple, Future]" = {}
        # Hot-path caches: geometry-kwarg names per kernel (inspect.signature
        # once, not per launch) and bound callables per (name, grid, block).
        self._geo_params: "dict[str, tuple[bool, bool]]" = {}
        self._bound_cache: "dict[tuple, Callable]" = {}
        # Per-device sibling programs (run_on_any targets), device.key -> Program.
        self._siblings: "dict[str, Program]" = {}
        self.gid = agas.registry.register(
            self, agas.Placement(device.key, device.jax_device.process_index), kind="program"
        )
        # GC-safe AGAS retirement (same leak fix as Buffer): the registry
        # must not pin dead programs forever.
        self._finalizer = weakref.finalize(self, agas.registry.unregister, self.gid)

    # -- construction ---------------------------------------------------------

    @staticmethod
    def from_file(device, path: str) -> "Program":
        """Load kernels from a python source file defining ``KERNELS``.

        This is the percolation path for *code*: source is loaded and
        runtime-compiled at the device that will execute it
        (``create_program_with_file("kernel.cu")`` analogue).
        """
        spec = importlib.util.spec_from_file_location(f"repro_kernel_{abs(hash(path))}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        kernels = getattr(mod, "KERNELS", None)
        if kernels is None:
            raise ValueError(f"{path} does not define KERNELS = {{name: callable}}")
        return Program(device, kernels, name=path)

    def kernel_names(self) -> "list[str]":
        return sorted(self._kernels)

    def for_device(self, device) -> "Program":
        """This program's sibling on ``device`` (cached; self if home).

        Siblings share the kernel sources but keep their own compile
        caches — the "any kernel on any device" half of run_on_any: the
        same source percolates to whatever device the policy picks and is
        runtime-compiled there (NVRTC-per-device analogue).
        """
        if device is self.device or device.key == self.device.key:
            return self
        sib = self._siblings.get(device.key)
        if sib is None:
            if getattr(device, "is_remote_proxy", False):
                sib = RemoteProgram(device, self._kernels, name=f"{self.name}@{device.key}")
            else:
                sib = Program(device, self._kernels, name=f"{self.name}@{device.key}")
            sib = self._siblings.setdefault(device.key, sib)  # racing creator loses
        return sib

    # -- build (async runtime compilation) -------------------------------------

    def _geometry_of(self, name: str) -> "tuple[bool, bool]":
        """(accepts_grid, accepts_block) — computed once per kernel."""
        geo = self._geo_params.get(name)
        if geo is None:
            params = inspect.signature(self._kernels[name]).parameters
            geo = self._geo_params[name] = ("grid" in params, "block" in params)
        return geo

    def _bind(self, name: str, grid, block) -> Callable:
        """Bound callable for (kernel, normalized grid/block), cached."""
        grid_n, block_n = _normalize_dim(grid), _normalize_dim(block)
        bkey = (name, grid_n, block_n)
        bound = self._bound_cache.get(bkey)
        if bound is not None:
            return bound
        fn = self._kernels[name]
        has_grid, has_block = self._geometry_of(name)
        kwargs = {}
        if has_grid:
            kwargs["grid"] = grid_n
        if has_block:
            kwargs["block"] = block_n
        if kwargs:
            bound = lambda *args: fn(*args, **kwargs)  # noqa: E731
            bound.__name__ = name
        else:
            bound = fn
        self._bound_cache[bkey] = bound
        return bound

    def _key(self, name: str, specs, grid, block) -> tuple:
        # np.dtype objects are interned and hashable — hashing them directly
        # beats building str(dtype) per spec on every launch.
        sig = tuple((s.shape, s.dtype) for s in specs)
        return (name, sig, _normalize_dim(grid), _normalize_dim(block))

    def build(self, name: str, *specs, grid=None, block=None) -> Future:
        """Compile kernel ``name`` asynchronously (NVRTC analogue).

        With ``specs`` (``jax.ShapeDtypeStruct``/arrays) the executable is
        fully compiled and cached; without, the kernel is resolved/bound
        only (shape specialization then happens at first ``run``, still on
        the compile queue). Returns a future — a dependency for launches.
        """
        if name not in self._kernels:
            return Future.failed(KeyError(f"no kernel '{name}' in {self.name}"))
        if not specs:
            return self.device.compile_queue.submit(self._bind, name, grid, block)

        key = self._key(name, specs, grid, block)
        fut = self._build_futures.get(key)
        if fut is not None:
            return fut

        def _compile():
            compiled = self._cache.get(key)
            if compiled is None:
                bound = self._bind(name, grid, block)
                # Device-pinned lowering: a launch must execute where the
                # program lives (the paper's placement contract) — without
                # this, run_on_any siblings would all compile for device 0
                # and the scheduler would place nothing.
                arg_specs = pin_specs(specs, self.device.jax_device)
                try:
                    compiled = jax.jit(bound).lower(*arg_specs).compile()
                except jax.errors.JAXTypeError:
                    # Value-dependent kernel (shapes read from argument
                    # DATA, e.g. mandelbrot's int32[2] size vector): not
                    # traceable, so it runs eagerly — the NVRTC-refuses-
                    # to-compile path, degraded to interpretation.
                    compiled = bound
                self._cache[key] = compiled
            return compiled

        fut = self.device.compile_queue.submit(_compile)
        self._build_futures[key] = fut
        return fut

    # -- launch -----------------------------------------------------------------

    def run(
        self,
        args: "Sequence[Buffer | Any]",
        name: str,
        grid=None,
        block=None,
        out: "Sequence[Buffer] | None" = None,
        sync: str = "ready",
        stream=None,
    ):
        """Launch kernel ``name`` with buffer/array ``args`` (async).

        ``out``: buffers to receive the kernel's results (CUDA's mutate-
        in-place adapted to functional JAX) — the future resolves to them.
        Without ``out`` the future resolves to the raw result arrays.
        ``sync="ready"`` resolves at device completion (CUDA-event
        semantics); ``sync="dispatch"`` resolves at submission.
        ``stream`` scopes the submission order (DESIGN.md §11): the launch
        runs FIFO with that stream's other work and concurrently with the
        device's other streams; ``None`` means the default stream — the
        pre-stream single-queue semantics, unchanged.

        Inside a ``repro.core.graph.capture()`` region the launch is
        *recorded*, not executed: the return value is then the graph node
        (symbolic handle), and execution happens at ``replay()`` — capture
        ignores ``stream`` and assigns chains to streams itself at
        ``instantiate()`` (§11).
        """
        from repro.core.graph import current_graph

        g = current_graph()
        if g is not None:
            return g.run(self, args, name, grid=grid, block=block, out=out)

        home = self.device
        queue = home.ops_queue if stream is None else stream._lane_for(home)

        # Percolation: move foreign buffers to the program's device first.
        # A RemoteBuffer is always foreign to a local program — the move is
        # then an explicit cross-locality transfer (read parcel + device_put).
        moved: "dict[int, Future] | None" = None
        for i, a in enumerate(args):
            if (isinstance(a, Buffer) and a.device is not home) or _is_remote_buffer(a):
                if moved is None:
                    moved = {}
                moved[i] = a.copy_to(home)

        specs = [
            a.array() if isinstance(a, Buffer)
            else jax.ShapeDtypeStruct(a.shape, a.dtype) if _is_remote_buffer(a)
            else a
            for a in args
        ]
        build_fut = self.build(name, *specs, grid=grid, block=block)

        def _launch(compiled, *resolved_args):
            arg_list = list(args)
            if moved:
                for i, b in zip(moved.keys(), resolved_args):
                    arg_list[i] = b
            jd = home.jax_device
            vals = []
            for a in arg_list:
                v = a.array() if isinstance(a, Buffer) else a
                # Executables are device-pinned (see build): host values and
                # stragglers the percolation pass didn't cover land here.
                if not isinstance(v, jax.Array) or v.devices() != {jd}:
                    v = jax.device_put(v, jd)
                vals.append(v)
            res = compiled(*vals)
            if out is None:
                return res
            res_list = list(res) if isinstance(res, (tuple, list)) else [res]
            if len(res_list) != len(out):
                raise ValueError(
                    f"kernel '{name}' returned {len(res_list)} arrays for {len(out)} out buffers"
                )
            for b, v in zip(out, res_list):
                b._set_array(v)
                # Results live where they were computed; the handle follows
                # (location transparency: AGAS placement moves, GID doesn't).
                b._rehome(home)
            return list(out)

        # Order: (copies, build) -> ops-queue launch.  Non-percolating
        # launches enqueue on the ops queue *now* — compiled executables
        # run with one hop, uncompiled ones park the queue worker on the
        # build future (the compile queue never depends on the ops queue,
        # so this cannot deadlock).  Eager enqueue keeps the queue's depth
        # an honest load signal at submission time (DESIGN.md §9): the
        # scheduler sees a launch the moment it is placed, not after its
        # kernel finishes compiling.  (Head-of-line blocking during a cold
        # compile is accepted: per-device queues are in-order streams, and
        # a parked worker is exactly the backlog the signal should show.)
        # Percolating launches must not block
        # the worker (the copy lands *on this queue*), so they join via
        # dataflow off-queue; their depth shows up when the copy resolves.
        if moved is None:
            if build_fut.done():
                launched = queue.submit(_launch, build_fut.get())
            else:
                launched = queue.submit(lambda: _launch(build_fut.get()))
        else:

            def _enqueue(compiled, *resolved):
                return queue.submit(_launch, compiled, *resolved).get()

            launched = dataflow(_enqueue, build_fut, *moved.values(), name=f"run:{name}")

        if sync == "dispatch":
            # Dispatch-resolved future: stream events recorded after this
            # launch mean "dispatched", as cudaEventRecord would if the
            # work were still queued — completion events need sync="ready".
            if stream is not None:
                stream._note_completion(launched)
            return launched

        def _ready(res):
            vals = [b.array() for b in res] if out is not None else res
            jax.block_until_ready(vals)
            return res

        from repro.core.executor import get_runtime

        done = launched.then(_ready, executor=get_runtime().pool, name=f"done:{name}")
        if stream is not None:
            # Stream events must mean device completion (DESIGN.md §11):
            # the lane task ends at dispatch, this future at readiness.
            stream._note_completion(done)
        return done

    def launch(
        self,
        args: "Sequence[Buffer | Any]",
        name: str,
        grid=None,
        block=None,
        out: "Sequence[Buffer] | None" = None,
        sync: str = "ready",
        stream=None,
    ):
        """``run`` under its CUDA name — ``prog.launch([...], "k",
        stream=s)`` submits the kernel on stream ``s`` (``<<<grid, block,
        0, stream>>>``).  Identical semantics to ``run``."""
        return self.run(args, name, grid=grid, block=block, out=out, sync=sync, stream=stream)

    def run_on_any(
        self,
        args: "Sequence[Buffer | Any]",
        name: str,
        grid=None,
        block=None,
        out: "Sequence[Buffer] | None" = None,
        sync: str = "ready",
        scheduler=None,
        cluster=None,
    ):
        """Launch kernel ``name`` on whatever device the placement policy
        picks — the paper's "any kernel on any (local or remote) device",
        with §3 percolation done by policy instead of by hand.

        The scheduler (default: process scheduler, ``least_loaded``)
        chooses from its fleet; the launch runs through the per-device
        sibling program, foreign argument buffers percolate over, and
        ``out`` buffers are re-homed to the chosen device.  Semantics
        otherwise match ``run`` (works under graph capture too: the node
        records against the chosen device, giving multi-device graphs).

        ``cluster`` (a ``Parcelport``) widens the fleet to every locality
        the port reaches — ``hpx::async(locality, action)`` as a placement
        decision: the policy scores the full localities × devices grid
        (``percolation`` cost model by default), and a remote pick routes
        the launch through a ``RemoteProgram`` sibling as parcels.
        """
        from repro.core.graph import current_graph
        from repro.core.scheduler import get_scheduler

        if scheduler is not None:
            sched = scheduler
        elif cluster is not None:
            sched = cluster.scheduler()
        else:
            sched = get_scheduler()
        # Rebalancing path (DESIGN.md §14): with stealing enabled and more
        # than one device to balance, the launch parks in the scheduler's
        # steal pool so an idle sibling can take it if the placed device
        # falls behind.  Graph capture keeps the direct path — a recorded
        # node must bind its device at capture time.
        if current_graph() is None and getattr(sched, "steals", False):
            return sched.submit(self, args, name, grid=grid, block=block, out=out, sync=sync)
        dev = sched.select(args=args, program=self)
        return self.for_device(dev).run(args, name, grid=grid, block=block, out=out, sync=sync)


def _release_remote_program(port, locality_id: int, gid_future: "Future") -> None:
    """GC finalizer for RemoteProgram: best-effort free parcel so the
    worker's object table does not grow without bound.  Skips (rather than
    blocks) when the create reply never arrived."""
    try:
        if gid_future.done() and gid_future.exception() is None:
            port.call(locality_id, "free", {"gid": gid_future.get()})
    except Exception:  # noqa: BLE001 - teardown is best-effort
        pass


class RemoteProgram(Program):
    """Proxy for a program owned by a remote locality (DESIGN.md §10).

    Kernels percolate **by name**: construction sends a ``create_program``
    parcel listing kernel names, which the owning locality resolves
    through its own registry and runtime-compiles there (the NVRTC-at-the-
    device analogue, across a process boundary).  The callables kept here
    are *shadows* — used only for shape inference (``jax.eval_shape``
    during graph capture) and geometry binding; they never execute
    locally through this class.

    ``run`` turns into a ``launch`` parcel: locality-resident buffer
    arguments travel as GID references (zero copy), everything else is
    read back to the host and shipped inline; ``out`` buffers on the
    target locality keep results remote, local ``out`` buffers receive
    the reply arrays.  The reply parcel resolves the returned future —
    completion on the remote device, i.e. ``sync="ready"`` semantics.
    """

    def __init__(self, device, kernels, name: str = "program"):
        from repro.core.parcel import resolve_kernel

        if isinstance(kernels, str):
            kernels = [kernels]
        if callable(kernels) and not isinstance(kernels, dict):
            kernels = {getattr(kernels, "__name__", "kernel"): kernels}
        elif not isinstance(kernels, dict):
            kernels = {n: resolve_kernel(n) for n in kernels}
        super().__init__(device, kernels, name=name)
        self._remote_gid_f: Future = device._call(
            "create_program", kernels=list(self._kernels), name=name
        ).then(lambda rep: rep["gid"], executor="inline")
        # The owning locality holds its Program strongly in the action
        # server's object table; retire it when this proxy is collected
        # (same free parcel as buffers — _do_free pops any GID).
        self._remote_finalizer = weakref.finalize(
            self, _release_remote_program, device._port, device.locality_id, self._remote_gid_f
        )

    def remote_gid(self) -> int:
        """GID of the program object on the owning locality (blocks on the
        create reply the first time)."""
        return self._remote_gid_f.get()

    def build(self, name: str, *specs, grid=None, block=None) -> Future:
        """Remote runtime compilation (async): ships shape/dtype specs, the
        owning locality lowers and caches the executable there."""
        if name not in self._kernels:
            return Future.failed(KeyError(f"no kernel '{name}' in {self.name}"))
        spec_p = [(tuple(s.shape), np.dtype(s.dtype).str) for s in specs]
        dev = self.device
        port, loc = dev._port, dev.locality_id
        gid_f = self._remote_gid_f
        grid_n, block_n = _normalize_dim(grid), _normalize_dim(block)

        def _send():
            return port.call_sync(loc, "build", {
                "device": dev.remote_key, "program": gid_f.get(), "kernel": name,
                "specs": spec_p, "grid": grid_n, "block": block_n,
            })

        return dev.compile_queue.submit(_send)

    def run(
        self,
        args: "Sequence[Buffer | Any]",
        name: str,
        grid=None,
        block=None,
        out: "Sequence[Buffer] | None" = None,
        sync: str = "ready",
        stream=None,
    ):
        from repro.core.graph import current_graph

        g = current_graph()
        if g is not None:
            return g.run(self, args, name, grid=grid, block=block, out=out)
        if name not in self._kernels:
            return Future.failed(KeyError(f"no kernel '{name}' in {self.name}"))

        dev = self.device
        port, loc = dev._port, dev.locality_id
        # Stream-scoped remote launch: the parcel rides that stream's
        # ordered channel instead of the default one (DESIGN.md §11).
        lane = dev.ops_queue if stream is None else stream._lane_for(dev)

        # Argument descriptors: locality-resident buffers go as GID refs;
        # everything else materializes on the host and ships inline.
        descs: "list" = [None] * len(args)
        fetch_ix: "list[int]" = []
        fetch_futs: "list[Future]" = []
        for i, a in enumerate(args):
            if _is_remote_buffer(a) and a.device.locality_id == loc:
                descs[i] = ("gid", a.gid)
            elif isinstance(a, Buffer) or _is_remote_buffer(a):
                fetch_ix.append(i)
                fetch_futs.append(a.enqueue_read())
            elif isinstance(a, jax.Array):
                descs[i] = ("val", np.asarray(a))
            else:
                descs[i] = ("val", a)

        if out is None:
            out_gids, mode = None, "none"
        elif all(_is_remote_buffer(b) and b.device.locality_id == loc for b in out):
            out_gids, mode = [b.gid for b in out], "remote"
        elif all(isinstance(b, Buffer) for b in out):
            out_gids, mode = None, "local"
        else:
            raise ValueError(
                "out buffers of a remote launch must either all live on the "
                "target locality (results stay remote) or all be local "
                "buffers (results ship back)"
            )

        grid_n, block_n = _normalize_dim(grid), _normalize_dim(block)
        gid_f = self._remote_gid_f

        def _post(rep):
            if mode == "remote":
                return list(out)
            if mode == "local":
                for b, v in zip(out, rep):
                    b._set_array(jax.device_put(np.asarray(v), b.device.jax_device))
                return list(out)
            return rep

        def _payload(vals):
            for i, v in zip(fetch_ix, vals):
                descs[i] = ("val", np.asarray(v))
            return {
                "device": dev.remote_key, "program": gid_f.get(), "kernel": name,
                "args": descs, "out": out_gids, "grid": grid_n, "block": block_n,
            }

        # Pipelined port: the channel task stages+flushes the launch parcel
        # and releases the lane immediately — the reply resolves the result
        # future asynchronously, so back-to-back remote launches overlap on
        # the wire instead of serializing on round trips.
        if getattr(port, "pipelined", False):
            from repro.core.executor import get_runtime
            from repro.core.futures import Promise, forward_failure

            inner: "Promise" = Promise(name=f"parcel:launch:L{loc}")

            def _ship(*vals):
                port.stage(loc, "launch", _payload(vals), inner)
                port.flush(loc)

            if not fetch_futs:
                forward_failure(lane.submit(_ship), inner)
            else:
                forward_failure(dataflow(
                    lambda *vals: lane.submit(lambda: _ship(*vals)).get(),
                    *fetch_futs,
                    executor=get_runtime().pool,
                    name=f"remote-run:{name}",
                ), inner)
            # "local" mode writes device arrays — post-process on the host
            # pool, never inline on the port's reply-listener thread.
            result = inner.get_future().then(
                _post,
                executor="inline" if mode != "local" else get_runtime().pool,
                name=f"remote-run:{name}",
            )
            if stream is not None:
                stream._note_completion(result)
            return result

        def _send(*vals):
            return _post(port.call_sync(loc, "launch", _payload(vals)))

        # Ordering: the launch parcel goes through the remote device's ops
        # queue, after any previously submitted writes there.  Pending host
        # fetches join off-queue first (same discipline as the percolating
        # local launch path — a queue worker must not wait on its own queue).
        if not fetch_futs:
            return lane.submit(_send)
        from repro.core.executor import get_runtime

        return dataflow(
            lambda *vals: lane.submit(lambda: _send(*vals)).get(),
            *fetch_futs,
            executor=get_runtime().pool,
            name=f"remote-run:{name}",
        )
