"""Fault-tolerant elastic data-parallel training (DESIGN.md §16).

The cluster story, closed end-to-end: the same runtime that serves also
trains.  An ``ElasticTrainer`` holds the master params + optimizer state
on the driver (registered in AGAS, so the state is a resolvable cluster
object, not a Python local), shards each global batch across a fleet of
workers, and all-reduces the returned gradients before one AdamW update:

* **Local workers** run their shard on this process's devices.  The shard
  step is captured once per (family, device, rows) as a ``TaskGraph`` —
  params/tokens/labels as write-fed buffers, one fused launch returning
  ``(*grad_leaves, loss)`` — and every subsequent step is a pre-bound
  fast-plan replay with feeds (PR 6's dispatch-tax fix, reused verbatim).
  ``donate=False``: the driver feeds the same param arrays to every
  worker's graph.
* **Parcel workers** ship the shard as ONE ``invoke`` parcel to a remote
  locality (arrays ride the shared-memory lane when large); the remote
  side resolves ``repro.training.elastic:shard_action`` by name, runs the
  shard under its own jit cache, and replies with the gradient leaves —
  optionally int8-compressed (``grad_compression``, stochastic rounding
  seeded per (step, shard), so a replayed step re-rounds identically).

**Determinism contract** (what the chaos tests pin down): shard splits
are a pure function of (batch, active-worker count); gradients are
combined on the driver in numpy float32, in shard order, weighted by
shard rows; the update is one jitted AdamW.  A step is therefore a pure
function of (params, opt_state, cursor, active count) — re-executing it
after a failure, with any workers, from the same state gives bit-identical
results.

**Elasticity, both directions** (fail-stop model, DESIGN.md §6):

* *Down*: a worker death mid-step (Heartbeat miss, process exit, or the
  fault injector) discards that step's partial results and re-executes
  the WHOLE step resharded over the survivors — dask-style recomputation
  from the AGAS-resident driver state, no checkpoint restore.  The loss
  curve from the reshard point is bit-identical to a clean N-1-worker run
  from the same state (the property the chaos suite asserts).  Checkpoint
  restore remains the last resort for driver loss, via ``resume=True``.
* *Up*: a recovered (``revive()``) or newly added (``add_worker()``)
  worker is picked up at the next step boundary — the active set is
  re-read every step, exactly like the scheduler re-reads liveness.

Transient faults are not deaths: a dropped gradient parcel
(``ParcelDropped``) is re-sent to the same worker up to
``REPRO_ELASTIC_RETRIES`` times before the link is declared dead.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeConfig, smoke as smoke_cfg
from repro.core import agas
from repro.core.executor import get_runtime
from repro.core.graph import TaskGraph
from repro.data.pipeline import SyntheticTokens
from repro.distribution.recipes import plan_for
from repro.fault.inject import ParcelDropped
from repro.fault.monitor import Heartbeat, StepMonitor
from repro.models import get_model
from repro.training.grad_compression import compress
from repro.training.optimizer import OptConfig, adamw_update, init_opt_state

__all__ = ["ElasticTrainer", "LocalWorker", "ParcelWorker", "WorkerDied", "shard_action"]


class WorkerDied(RuntimeError):
    """A worker was lost mid-step; the trainer reshards over survivors."""


# ---------------------------------------------------------------------------
# the shard step, shared by every route
# ---------------------------------------------------------------------------

# (arch, smoke, seq, global_batch) -> family dict.  Module-level so every
# worker/trainer in the process shares one jit cache, one treedef, one
# captured-graph cache — repeated trainers (property tests, benchmark
# sweeps) pay compilation once per shard shape, not once per trainer.
_FAMILIES: "dict[tuple, dict]" = {}
_GEXECS: "dict[tuple, tuple]" = {}  # (famkey, device.key, rows) -> capture entry
_PROGRAMS: "dict[tuple, Any]" = {}  # (famkey, device.key) -> Program
_UPDATES: "dict[OptConfig, Any]" = {}  # opt_cfg -> jitted update
_CACHE_LOCK = threading.Lock()


def _on_runtime_reset() -> None:
    """Drop the captured-graph and program caches when the runtime is
    torn down (``executor.reset_runtime``): their buffers hold queues
    owned by the dying runtime, and their AGAS records must be retired
    with them — a later memory-pressure spill must never try to evict a
    stale buffer onto a shut-down lane.  ``_FAMILIES``/``_UPDATES`` stay:
    plain jits, no runtime objects."""
    with _CACHE_LOCK:
        entries = list(_GEXECS.values())
        _GEXECS.clear()
        _PROGRAMS.clear()
    for _gexec, param_nodes, tok_node, lab_node, _launch in entries:
        for node in (*param_nodes, tok_node, lab_node):
            gid = getattr(node.buf, "gid", None)
            if gid is not None:
                agas.registry.unregister(gid)


def _get_family(arch: str, use_smoke: bool, seq: int, global_batch: int) -> dict:
    key = (str(arch), bool(use_smoke), int(seq), int(global_batch))
    with _CACHE_LOCK:
        fam = _FAMILIES.get(key)
    if fam is not None:
        return fam
    cfg = smoke_cfg(get_config(arch)) if use_smoke else get_config(arch)
    shape = ShapeConfig("custom", seq_len=seq, global_batch=global_batch, kind="train")
    plan = plan_for(cfg, shape)
    m = get_model(cfg)
    compute_dtype = jnp.bfloat16 if plan.compute_dtype == "bfloat16" else jnp.float32

    def cast(p):
        if compute_dtype == jnp.float32:
            return p
        return jax.tree.map(
            lambda x: x.astype(compute_dtype)
            if (x.dtype == jnp.float32 and x.ndim >= 2)
            else x,
            p,
        )

    def loss_of(params, mb):
        return m.loss_fn(cfg, cast(params), mb, remat=plan.remat, q_block=plan.q_block)

    def grad_step(params, batch):
        """One shard's contribution: (mean loss over shard rows, f32 grads).
        No microbatching — a shard is already a batch fraction."""
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return loss.astype(jnp.float32), grads

    def init(rng_key):
        return m.init(cfg, rng_key)

    shapes = jax.eval_shape(init, jax.random.key(0))
    fam = {
        "key": key,
        "cfg": cfg,
        "treedef": jax.tree_util.tree_structure(shapes),
        "n_leaves": len(jax.tree_util.tree_leaves(shapes)),
        "grad_step": grad_step,
        "jit_grad": jax.jit(grad_step),
        "init": init,
    }
    with _CACHE_LOCK:
        return _FAMILIES.setdefault(key, fam)


def _pack_grads(flat: "list[np.ndarray]", loss, task: dict) -> dict:
    """Wire format of one shard's reply; int8 stochastic rounding when the
    task asks for compression.  The rounding key is derived from the
    task's ``ckey`` (a pure function of (seed, step, shard)), so a
    re-executed step re-rounds bit-identically."""
    out: dict = {"loss": np.float32(loss)}
    if task.get("compress"):
        base = jax.random.key(int(task["ckey"]) % (2**31 - 1))
        qs, scales = [], []
        for i, g in enumerate(flat):
            q, s = compress(jnp.asarray(g), jax.random.fold_in(base, i))
            qs.append(np.asarray(q))
            scales.append(np.float32(np.asarray(s)))
        out["q"] = qs
        out["scales"] = scales
    else:
        out["grads"] = flat
    return out


def shard_action(payload: dict) -> dict:
    """The remote half of one data-parallel shard step.

    Resolved BY NAME (``repro.training.elastic:shard_action``) through the
    parcel ``invoke`` action — source never crosses the wire.  The payload
    carries flat param leaves + the shard's tokens/labels + config knobs;
    the reply carries ``{loss, grads | (q, scales)}``.  Worker-side state
    (jit cache, treedef) lives in the module caches above, warmed on first
    use and reused for every later step."""
    fam = _get_family(
        payload["arch"], payload["smoke"], int(payload["seq"]), int(payload["global_batch"])
    )
    params = jax.tree_util.tree_unflatten(
        fam["treedef"], [jnp.asarray(a) for a in payload["params"]]
    )
    batch = {
        "tokens": jnp.asarray(payload["tokens"]),
        "labels": jnp.asarray(payload["labels"]),
    }
    loss, grads = fam["jit_grad"](params, batch)
    flat = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(grads)]
    return _pack_grads(flat, np.asarray(loss), payload)


def _gexec_for(fam: dict, dev, task: dict):
    """Captured shard graph for (family, device, rows): params + tokens +
    labels as write-fed buffers, one fused launch.  Instantiated with
    ``donate=False`` (the driver feeds shared param arrays) and cached so
    every replay takes PR 6's pre-bound fast plan."""
    rows = int(task["tokens"].shape[0])
    key = (fam["key"], dev.key, rows)
    with _CACHE_LOCK:
        entry = _GEXECS.get(key)
    if entry is not None:
        return entry

    n = fam["n_leaves"]
    treedef = fam["treedef"]
    grad_step = fam["grad_step"]

    def shard_grad(*args):
        params = jax.tree_util.tree_unflatten(treedef, list(args[:n]))
        batch = {"tokens": args[n], "labels": args[n + 1]}
        loss, grads = grad_step(params, batch)
        return tuple(jax.tree_util.tree_leaves(grads)) + (loss,)

    pkey = (fam["key"], dev.key)
    with _CACHE_LOCK:
        prog = _PROGRAMS.get(pkey)
    if prog is None:
        prog = dev.create_program({"shard_grad": shard_grad}, f"elastic:{dev.key}").get()
        with _CACHE_LOCK:
            prog = _PROGRAMS.setdefault(pkey, prog)

    g = TaskGraph(f"elastic:{dev.key}:r{rows}")
    param_nodes = []
    for leaf in task["params"]:
        arr = np.asarray(leaf)
        buf = dev.create_buffer(arr.shape, arr.dtype).get()
        param_nodes.append(g.write(buf, arr))
    toks = np.asarray(task["tokens"])
    labs = np.asarray(task["labels"])
    tbuf = dev.create_buffer(toks.shape, toks.dtype).get()
    tok_node = g.write(tbuf, toks)
    lbuf = dev.create_buffer(labs.shape, labs.dtype).get()
    lab_node = g.write(lbuf, labs)
    launch = g.run(prog, [w.buf for w in param_nodes] + [tbuf, lbuf], "shard_grad")
    gexec = g.instantiate(donate=False)
    entry = (gexec, param_nodes, tok_node, lab_node, launch)
    with _CACHE_LOCK:
        return _GEXECS.setdefault(key, entry)


def _run_shard_local(task: dict, dev, route: str) -> dict:
    fam = _get_family(task["arch"], task["smoke"], int(task["seq"]), int(task["global_batch"]))
    if route == "graph":
        gexec, param_nodes, tok_node, lab_node, launch = _gexec_for(fam, dev, task)
        feeds = {node: leaf for node, leaf in zip(param_nodes, task["params"])}
        feeds[tok_node] = np.ascontiguousarray(task["tokens"])
        feeds[lab_node] = np.ascontiguousarray(task["labels"])
        res = gexec.replay(feeds=feeds).get()
        outs = res[launch]
        outs = list(outs) if isinstance(outs, (tuple, list)) else [outs]
        flat = [np.asarray(g, np.float32) for g in outs[:-1]]
        loss = np.asarray(outs[-1])
    else:  # direct-jit route (REPRO_ELASTIC_ROUTE=jit)
        params = jax.tree_util.tree_unflatten(
            fam["treedef"],
            [jax.device_put(np.asarray(a), dev.jax_device) for a in task["params"]],
        )
        batch = {
            "tokens": jax.device_put(np.asarray(task["tokens"]), dev.jax_device),
            "labels": jax.device_put(np.asarray(task["labels"]), dev.jax_device),
        }
        loss, grads = fam["jit_grad"](params, batch)
        flat = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(grads)]
        loss = np.asarray(loss)
    return _pack_grads(flat, loss, task)


# ---------------------------------------------------------------------------
# workers
# ---------------------------------------------------------------------------


class LocalWorker:
    """One data-parallel worker on this process: its own serial work queue
    (shards overlap across workers), its own ``Heartbeat``, optionally
    pinned to one device.  ``occupancy_tokens_per_s`` models device busy
    time with a GIL-releasing sleep (benchmark use, fig6/fig8 precedent)."""

    kind = "local"

    def __init__(
        self,
        wid: int,
        device=None,
        *,
        route: "str | None" = None,
        occupancy_tokens_per_s: "float | None" = None,
        heartbeat_timeout: float = 600.0,
        on_dead=None,
    ):
        self.wid = int(wid)
        self.device = device
        self.route = route or os.environ.get("REPRO_ELASTIC_ROUTE", "graph")
        self.occupancy = occupancy_tokens_per_s
        self.queue = get_runtime().queue(f"elastic-w{self.wid}")
        self.heartbeat = Heartbeat(timeout_s=heartbeat_timeout, on_dead=on_dead)
        self._dead = False
        self._kill_at: "Optional[int]" = None

    def _device(self):
        if self.device is None:
            from repro.core.device import get_all_devices

            devs = get_all_devices().get()
            self.device = devs[self.wid % len(devs)]
        return self.device

    def alive(self) -> bool:
        return not self._dead

    def mark_dead(self) -> None:
        self._dead = True

    def kill(self) -> None:
        """Immediate death: heartbeat expires and ``on_dead`` edge-fires."""
        self._dead = True
        self.heartbeat.force_expire()
        self.heartbeat.check()

    def revive(self) -> None:
        """Re-admit: picked up by the trainer at the next step boundary."""
        self._dead = False
        self.heartbeat.tick()

    def kill_at_step(self, step: int) -> None:
        """Arm a mid-step death (fault injection): the worker dies inside
        its own shard execution at training step ``step``."""
        self._kill_at = int(step)

    def run_shard(self, task: dict):
        def _run():
            if self._kill_at is not None and task["step"] >= self._kill_at:
                self._kill_at = None
                self.kill()
                raise WorkerDied(
                    f"worker {self.wid} killed by fault injection at step {task['step']}"
                )
            if self.occupancy:
                time.sleep(np.asarray(task["tokens"]).size / float(self.occupancy))
            out = _run_shard_local(task, self._device(), self.route)
            self.heartbeat.tick()
            return out

        return self.queue.submit(_run)


class ParcelWorker:
    """One data-parallel worker behind a parcelport locality.  The shard
    ships as ONE ``invoke`` parcel (arrays take the shm lane when large);
    liveness is the port's (heartbeat monitor / fail-fast gate)."""

    kind = "parcel"

    def __init__(self, wid: int, port, locality_id: int):
        self.wid = int(wid)
        self.port = port
        self.lid = int(locality_id)
        self._dead = False
        self._kill_at: "Optional[int]" = None

    def alive(self) -> bool:
        return not self._dead and self.port.alive(self.lid)

    def mark_dead(self) -> None:
        self._dead = True

    def kill(self) -> None:
        self._dead = True
        if hasattr(self.port, "kill"):  # loopback: flip the fail-fast gate
            self.port.kill(self.lid)
        else:  # cluster: SIGKILL the worker process
            w = self.port._workers.get(self.lid)
            if w is not None and w.proc.is_alive():
                w.proc.kill()

    def revive(self) -> None:
        self._dead = False
        if hasattr(self.port, "revive"):
            self.port.revive(self.lid)

    def kill_at_step(self, step: int) -> None:
        self._kill_at = int(step)

    def run_shard(self, task: dict):
        if self._kill_at is not None and task["step"] >= self._kill_at:
            self._kill_at = None
            self.kill()  # the call below fails fast: a mid-step death
        return self.port.call(
            self.lid, "invoke", {"fn": "repro.training.elastic:shard_action", "payload": task}
        )


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------


def _update_for(opt_cfg: OptConfig):
    with _CACHE_LOCK:
        fn = _UPDATES.get(opt_cfg)
        if fn is None:
            def _upd(params, grads, state, _cfg=opt_cfg):
                return adamw_update(_cfg, params, grads, state)

            fn = _UPDATES[opt_cfg] = jax.jit(_upd)
    return fn


class ElasticTrainer:
    """Elastic data-parallel trainer over local and/or parcel workers.

    ``state=(params, opt_state), start_step=k`` seeds the trainer from a
    snapshot (the chaos tests' reference runs); ``total_steps`` pins the
    LR-schedule horizon so split runs match a single run bit-for-bit.
    """

    def __init__(
        self,
        arch: str = "olmo-1b",
        *,
        use_smoke: bool = True,
        batch: int = 8,
        seq: int = 64,
        lr: float = 3e-4,
        seed: int = 0,
        workers: int = 2,
        port=None,
        devices: "list | None" = None,
        grad_compression: bool = False,
        occupancy_tokens_per_s: "float | None" = None,
        total_steps: "int | None" = None,
        state: "tuple | None" = None,
        start_step: int = 0,
        ckpt_dir: "str | None" = None,
        ckpt_every: int = 0,
        resume: bool = False,
        max_retries: "int | None" = None,
        heartbeat_timeout: float = 600.0,
    ):
        from repro.checkpoint.checkpoint import CheckpointManager

        self.arch = arch
        self.use_smoke = bool(use_smoke)
        self.batch = int(batch)
        self.seq = int(seq)
        self.lr = float(lr)
        self.seed = int(seed)
        self.grad_compression = bool(grad_compression)
        self.total_steps = total_steps
        if max_retries is None:
            max_retries = int(os.environ.get("REPRO_ELASTIC_RETRIES", "2"))
        self.max_retries = int(max_retries)

        self._fam = _get_family(arch, use_smoke, seq, batch)
        self.source = SyntheticTokens(self._fam["cfg"].vocab_size, seq, batch, seed=seed)
        self.monitor = StepMonitor()
        self.events: "list[tuple]" = []  # ("death"|"retry"|"join", step, wid, ...)
        self.history: "list[float]" = []
        self._opt_cfg: "Optional[OptConfig]" = None
        self._ckpt_every = int(ckpt_every)
        self._mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self._ckpt_futs: list = []

        # -- state: snapshot > checkpoint > fresh init ----------------------
        self.cursor = int(start_step)
        if state is not None:
            params, opt_state = state
            params = jax.tree.map(jnp.asarray, params)
            opt_state = jax.tree.map(jnp.asarray, opt_state)
        else:
            params = self._fam["init"](jax.random.key(seed))
            opt_state = init_opt_state(params)
            if resume and self._mgr and self._mgr.latest_step() is not None:
                (params, opt_state), extra = self._mgr.restore((params, opt_state))
                self.cursor = int(extra.get("step", self._mgr.latest_step()))
        self.params, self.opt_state = params, opt_state

        # -- fleet -----------------------------------------------------------
        self.workers: list = []
        if port is not None:
            for i, loc in enumerate(port.localities()):
                self.workers.append(ParcelWorker(i, port, loc.process_index))
        else:
            for i in range(int(workers)):
                dev = devices[i % len(devices)] if devices else None
                self.workers.append(
                    LocalWorker(
                        i,
                        device=dev,
                        occupancy_tokens_per_s=occupancy_tokens_per_s,
                        heartbeat_timeout=heartbeat_timeout,
                    )
                )

        # The master state is an AGAS-resident cluster object: any locality
        # (or a post-mortem driver) can resolve it by GID — recovery reads
        # live state, not a stale checkpoint.
        self._agas_gid = agas.registry.register(
            self,
            agas.Placement(device_key=agas.HOST_KEY),
            kind="elastic-state",
            arch=str(arch),
            batch=self.batch,
            seq=self.seq,
        )

    # -- fleet management ----------------------------------------------------

    @property
    def agas_gid(self) -> int:
        return self._agas_gid

    def active_workers(self) -> list:
        return [w for w in self.workers if w.alive()]

    def add_worker(self, worker=None):
        """Scale up: admit ``worker`` (or spawn a fresh ``LocalWorker``)
        from the next step boundary on."""
        if worker is None:
            wid = max((w.wid for w in self.workers), default=-1) + 1
            worker = LocalWorker(wid)
        self.workers.append(worker)
        self.events.append(("join", self.cursor, worker.wid))
        return worker

    # -- one step -------------------------------------------------------------

    @staticmethod
    def _split(batch: dict, n: int) -> "list[dict]":
        toks = np.array_split(batch["tokens"], n)
        labs = np.array_split(batch["labels"], n)
        return [{"tokens": t, "labels": l} for t, l in zip(toks, labs)]

    def _task(self, shard: dict, shard_i: int, n_active: int, flat_params) -> dict:
        # ckey: pure function of (seed, step, shard, fleet size) — the
        # compression re-rounds identically when the step is re-executed.
        ckey = ((self.seed * 1_000_003 + self.cursor) * 131 + shard_i) * 31 + n_active
        return {
            "arch": self.arch,
            "smoke": self.use_smoke,
            "seq": self.seq,
            "global_batch": self.batch,
            "step": self.cursor,
            "params": flat_params,
            "tokens": shard["tokens"],
            "labels": shard["labels"],
            "compress": self.grad_compression,
            "ckey": ckey,
        }

    def _await_shard(self, w, fut, mk_task):
        """One shard's result, retrying dropped parcels on the same worker;
        everything else becomes a ``WorkerDied`` reshard."""
        attempts = 0
        while True:
            try:
                return fut.get()
            except ParcelDropped as e:
                attempts += 1
                if attempts > self.max_retries or not w.alive():
                    raise WorkerDied(
                        f"worker {w.wid}: {attempts} consecutive parcels dropped"
                    ) from e
                self.events.append(("retry", self.cursor, w.wid))
                fut = w.run_shard(mk_task())
            except WorkerDied:
                raise
            except Exception as e:  # noqa: BLE001 - transport/worker failure
                if w.alive():
                    raise  # a real error on a live worker is a bug, not a death
                raise WorkerDied(f"worker {w.wid} lost mid-step: {e}") from e

    def step(self) -> float:
        """One data-parallel step; survives any worker deaths inside it."""
        if self._opt_cfg is None:
            self._ensure_opt(1)
        t0 = time.time()
        batch = self.source.batch(self.cursor)
        B = int(batch["tokens"].shape[0])
        flat_params = [np.asarray(l) for l in jax.tree_util.tree_leaves(self.params)]

        while True:
            active = self.active_workers()
            if not active:
                raise RuntimeError(
                    "elastic trainer has no live workers: every worker died; "
                    "restart the driver with resume=True to recover from the "
                    "latest checkpoint"
                )
            n = min(len(active), B)
            active = active[:n]
            shards = self._split(batch, n)
            futs = [
                (w, i, w.run_shard(self._task(shards[i], i, n, flat_params)))
                for i, w in enumerate(active)
            ]
            results: list = [None] * n
            death = None
            for w, i, fut in futs:
                if death is not None:
                    try:  # settle the rest; their results are discarded
                        fut.exception()
                    except Exception:  # noqa: BLE001
                        pass
                    continue
                try:
                    results[i] = self._await_shard(
                        w, fut, lambda i=i, n=n: self._task(shards[i], i, n, flat_params)
                    )
                except WorkerDied as e:
                    w.mark_dead()
                    death = (w, e)
            if death is None:
                break
            # Reshard and re-execute the WHOLE step from the driver's
            # AGAS-resident state (pure: params/opt_state untouched so far).
            self.events.append(("death", self.cursor, death[0].wid, str(death[1])))

        rows = [int(s["tokens"].shape[0]) for s in shards]
        grads_flat, loss = self._combine(results, rows, B)
        grads = jax.tree_util.tree_unflatten(self._fam["treedef"], grads_flat)
        upd = _update_for(self._opt_cfg)
        self.params, self.opt_state, _metrics = upd(self.params, grads, self.opt_state)
        self.cursor += 1
        self.history.append(float(loss))
        self.monitor.record(self.cursor, time.time() - t0)
        if self._mgr and self._ckpt_every and self.cursor % self._ckpt_every == 0:
            self._ckpt_futs.append(
                self._mgr.save_async(
                    self.cursor, (self.params, self.opt_state), extra={"step": self.cursor}
                )
            )
        return float(loss)

    @staticmethod
    def _combine(results: list, rows: "list[int]", B: int) -> "tuple[list, np.float32]":
        """Driver-side all-reduce: rows-weighted sum in numpy float32, in
        shard order — bit-deterministic for a given (results, rows)."""
        total: "list[np.ndarray] | None" = None
        loss = np.float32(0.0)
        for r, res in zip(rows, results):
            w = np.float32(r / B)
            if "q" in res:  # int8 lane: decompress on the driver
                flat = [
                    q.astype(np.float32) * np.float32(s)
                    for q, s in zip(res["q"], res["scales"])
                ]
            else:
                flat = [np.asarray(g, np.float32) for g in res["grads"]]
            if total is None:
                total = [w * g for g in flat]
            else:
                total = [a + w * g for a, g in zip(total, flat)]
            loss = loss + w * np.float32(res["loss"])
        assert total is not None
        return total, loss

    # -- driving --------------------------------------------------------------

    def _ensure_opt(self, steps: int) -> None:
        if self._opt_cfg is None:
            horizon = int(self.total_steps or (self.cursor + steps))
            self._opt_cfg = OptConfig(
                lr=self.lr, warmup_steps=min(100, horizon // 10 + 1), total_steps=horizon
            )

    def run(self, steps: int, *, log_every: int = 0) -> dict:
        self._ensure_opt(steps)
        losses = []
        for _ in range(int(steps)):
            loss = self.step()
            losses.append(loss)
            if log_every and (self.cursor - 1) % log_every == 0:
                print(f"step {self.cursor - 1:5d} loss {loss:8.4f}", flush=True)
        self.wait()
        return {
            "losses": losses,
            "final_loss": losses[-1] if losses else float("nan"),
            "stragglers": len(self.monitor.events),
            "events": list(self.events),
            "params": self.params,
            "opt_state": self.opt_state,
        }

    def snapshot(self) -> dict:
        """Host copy of the full training state (reference-run seeding)."""
        return {
            "params": jax.tree.map(np.array, self.params),
            "opt_state": jax.tree.map(np.array, self.opt_state),
            "step": self.cursor,
        }

    def wait(self) -> None:
        """Drain in-flight checkpoint writes."""
        futs, self._ckpt_futs = self._ckpt_futs, []
        for f in futs:
            f.wait()
        if self._mgr:
            self._mgr.wait()

    def close(self) -> None:
        self.wait()
        agas.registry.unregister(self._agas_gid)
