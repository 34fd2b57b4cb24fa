"""Resident solver-in-the-loop inference engine for the consistent GNN.

The paper's end goal is interfacing the trained surrogate with a running
solver (NekRS): the solver streams snapshots into a RESIDENT model and
gets K-step predictions back, with partitioned inference arithmetically
identical to single-rank inference.  This module is that serving path:

* :class:`InferenceEngine` holds the trained params — loaded ONCE from a
  fingerprinted checkpoint (see the checkpoint contract below) — and a
  graph cache keyed by ``(mesh_fingerprint_hash, partitioner)``: the first
  request for a mesh pays the ``partition_mesh`` + ``ShardedGraph`` +
  ``NMPPlan`` build, every later request reuses it.  This is the maxtext
  offline-inference pattern (threaded engine loop, cached executables,
  explicit batch slots) and the hook where X-MeshGraphNet-style
  multi-geometry serving lands: one cache entry per geometry.
* Requests (global ``[N, F]`` snapshot fields) arrive on a BOUNDED
  thread-safe queue — :meth:`InferenceEngine.submit` blocks when the
  engine is saturated, which is the backpressure contract — get grouped
  into ``batch_slots`` fixed slots (zero-padded: the jitted program has
  exactly one batch shape, so there is never a recompile per request
  count), and run through the jitted K-step rollout eval from
  ``repro.train.rollout`` — the exact program the rollout consistency
  suite pins, not a reimplementation.
* Results stream back per request through single-shot futures;
  :meth:`InferenceEngine.stream` wires a multi-producer
  ``PrefetchingLoader`` (the repo's hang-safe transport) in front of the
  queue for solver-style feeds.
* Each request's host work is recorded as ``repro.obs`` spans with the
  request's sequence number as id: ``engine/queue_wait`` (submit to
  dequeue), ``engine/gather``, ``engine/predict`` (the batch's placement,
  device run and read-back, under its first request) and
  ``engine/scatter``.

Consistency contract (asserted in-process by ``tests/test_engine.py`` and
on real collectives by ``tests/drivers/serve_driver.py`` under the CI
serve-smoke job): the engine's streamed predictions are BITWISE identical
to the offline ``rollout_step`` eval of the same snapshot at the same
device count — batching, slot padding, queueing and threading are
arithmetically invisible — and consistent across device counts to fp32
tolerance (Eqs. 2-3: the paper's guarantee extends from training to
serving).  Zero-padded slots can't perturb real slots because the forward
has no cross-batch mixing; the batch dim rides through ``shard_map`` +
``scan`` elementwise.

Checkpoint contract: the engine refuses a checkpoint without a mesh
fingerprint, refuses params whose recorded model config disagrees with
the engine's ``GNNConfig`` (field named), and refuses requests or mesh
registrations whose ``mesh_fingerprint_hash`` differs from the
checkpoint's — naming BOTH hashes, so a solver pointed at the wrong model
learns which mesh the params were trained on instead of silently getting
garbage.  Corrupted newest checkpoints fall back to the previous
committed step, like the resilient trainer.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Any, Callable, Optional

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.ckpt import checkpoint as ckpt
from repro.core import GNNConfig, NMPPlan, init_gnn, partition_mesh
from repro.core.distributed import shard_graph
from repro.core.graph_state import ShardedGraph
from repro.core.mesh_gen import SEMMesh
from repro.core.partition import gather_node_features, scatter_node_outputs
from repro.data.pipeline import PrefetchingLoader
from repro.launch.mesh import make_mesh
from repro.train.loop import mesh_fingerprint_hash
from repro.train.rollout import make_rollout_predict_fn


class EngineError(RuntimeError):
    """Engine lifecycle/request failure (shutdown, saturation, bad input)."""


class MeshMismatchError(EngineError):
    """Request/registration mesh hash differs from the checkpoint's trained
    mesh — the engine refuses by name rather than serving garbage."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine policy.

    ``batch_slots`` is the FIXED slot count of the jitted program (requests
    are zero-padded up to it); ``max_pending`` bounds the request queue —
    the backpressure point; ``flush_timeout_s`` is how long a non-full
    batch waits for more requests before running padded (latency floor
    under light load).
    """
    batch_slots: int = 4
    rollout_steps: int = 1
    max_pending: int = 16
    flush_timeout_s: float = 0.02
    result_timeout_s: float = 300.0
    halo_mode: str = "a2a"
    partitioner: str = "block"

    def __post_init__(self):
        if self.batch_slots < 1 or self.rollout_steps < 1 \
                or self.max_pending < 1:
            raise ValueError(
                "batch_slots, rollout_steps and max_pending must be >= 1 "
                f"(got {self.batch_slots}/{self.rollout_steps}/"
                f"{self.max_pending})")


@dataclasses.dataclass
class InferenceResult:
    """One request's K-step prediction, scattered back to the global mesh."""
    step: int
    mesh_hash: str
    preds: np.ndarray          # [K, N_global, F_out]
    latency_s: float


class RequestFuture:
    """Single-shot future for one submitted snapshot."""

    def __init__(self, step: int):
        self.step = step
        self._ev = threading.Event()
        self._val: Optional[InferenceResult] = None
        self._err: Optional[BaseException] = None

    def _set(self, val: InferenceResult):
        self._val = val
        self._ev.set()

    def _fail(self, err: BaseException):
        self._err = err
        self._ev.set()

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None) -> InferenceResult:
        if not self._ev.wait(timeout):
            raise EngineError(
                f"request step={self.step} not completed after {timeout}s — "
                "is the engine started?")
        if self._err is not None:
            raise self._err
        return self._val


@dataclasses.dataclass
class _Request:
    seq: int                   # the engine's request number: its spans' id
    step: int
    key: tuple
    x: np.ndarray              # global [N, F] snapshot
    future: RequestFuture
    t_submit: float


@dataclasses.dataclass
class _GraphEntry:
    """One mesh's cached execution state (built once, reused per request)."""
    mesh_hash: str
    pg: Any
    plan: NMPPlan
    gs: ShardedGraph
    predict: Callable


def _dequeued(req: _Request) -> _Request:
    obs.record("engine/queue_wait", req.t_submit, time.perf_counter(), req.seq)
    return req


class InferenceEngine:
    """Resident serving engine over the jitted rollout eval step.

    Lifecycle: construct (loads params from ``ckpt_dir``), then
    :meth:`register_mesh` each geometry, optionally :meth:`warmup` (pays
    the jit compile up front), :meth:`start` the engine thread, feed it via
    :meth:`submit`/:meth:`stream`, and :meth:`close`.  Also a context
    manager (``with InferenceEngine(...) as eng``) that starts on enter and
    closes on exit.
    """

    def __init__(self, ckpt_dir, cfg: GNNConfig,
                 config: EngineConfig = EngineConfig(),
                 plan: NMPPlan = NMPPlan(), mesh_dev=None):
        self.cfg = cfg
        self.config = config
        # execution-policy fields forwarded into each mesh's NMPPlan.build
        # (halo specs are per-partition, derived at register_mesh time)
        self._policy = {
            "backend": plan.backend, "schedule": plan.schedule,
            "precision": plan.precision, "interpret": plan.interpret,
            "block_n": plan.block_n, "block_e": plan.block_e}
        self.mesh_dev = mesh_dev if mesh_dev is not None else make_mesh(
            (1, len(jax.devices())), ("data", "graph"))
        self.R = int(self.mesh_dev.shape["graph"])
        self.params, self.fingerprint, self.ckpt_step = \
            self._load_params(ckpt_dir)
        self._graphs: dict[tuple, _GraphEntry] = {}
        self._q: queue.Queue = queue.Queue(maxsize=config.max_pending)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._failure: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._seq = itertools.count()
        self.stats = {"requests": 0, "batches": 0, "padded_slots": 0,
                      "cache_hits": 0, "cache_builds": 0}

    # -- checkpoint ---------------------------------------------------------

    def _load_params(self, ckpt_dir):
        steps = ckpt.committed_steps(ckpt_dir)
        if not steps:
            raise EngineError(
                f"no committed checkpoint under {ckpt_dir} — train with "
                "TrainConfig.ckpt_dir (repro.train.loop) first")
        template = init_gnn(jax.random.PRNGKey(0), self.cfg)
        repl = NamedSharding(self.mesh_dev, P())
        shardings = jax.tree.map(lambda _: repl, template)
        last_err: Optional[BaseException] = None
        for step in reversed(steps):
            try:
                manifest = ckpt.peek_manifest(ckpt_dir, step)
                fp = (manifest.get("extra") or {}).get("fingerprint")
                if not fp or "mesh_hash" not in fp:
                    raise EngineError(
                        f"checkpoint step {step} under {ckpt_dir} carries no "
                        "mesh fingerprint — the engine only serves "
                        "fingerprinted checkpoints (repro.train.loop stamps "
                        "run_fingerprint into every manifest)")
                for field, have in (("hidden", self.cfg.hidden),
                                    ("n_levels", self.cfg.n_levels)):
                    if fp.get(field) is not None \
                            and int(fp[field]) != int(have):
                        raise EngineError(
                            f"engine GNNConfig.{field}={have} disagrees with "
                            f"the checkpoint fingerprint {field}={fp[field]} "
                            "— these params belong to a different model")
                params, _ = ckpt.restore_partial(
                    ckpt_dir, template, "params", step=step,
                    shardings=shardings)
                return params, fp, step
            except ckpt.CheckpointCorruption as e:
                # damaged-after-commit newest step: fall back, like the
                # resilient trainer (EngineError/ValueError are config
                # problems and propagate immediately)
                print(f"[engine] checkpoint step {step} corrupted, "
                      f"falling back: {e}")
                last_err = e
        raise EngineError(
            f"no valid committed checkpoint under {ckpt_dir} "
            f"({len(steps)} committed steps, all corrupted; last error: "
            f"{last_err})")

    # -- graph cache --------------------------------------------------------

    def _mismatch(self, mesh_hash: str) -> MeshMismatchError:
        return MeshMismatchError(
            f"mesh {mesh_hash} does not match the checkpoint's trained mesh "
            f"{self.fingerprint['mesh_hash']} "
            f"(n_global={self.fingerprint.get('n_global')}) — the engine "
            "refuses to run a model on a geometry it was not trained on; "
            "serve this mesh from its own checkpoint (multi-geometry "
            "serving keys the graph cache by this hash)")

    def register_mesh(self, sem_mesh: SEMMesh, rank_grid=None,
                      partitioner: Optional[str] = None,
                      hierarchy=None) -> str:
        """Build (or fetch from cache) the execution state for one mesh;
        returns its ``mesh_fingerprint_hash`` — the key every subsequent
        :meth:`submit`/:meth:`stream` call must present."""
        mesh_hash = mesh_fingerprint_hash(sem_mesh)
        if mesh_hash != self.fingerprint["mesh_hash"]:
            raise self._mismatch(mesh_hash)
        partitioner = partitioner or self.config.partitioner
        key = (mesh_hash, partitioner)
        with self._lock:
            if key in self._graphs:
                self.stats["cache_hits"] += 1
                return mesh_hash
            grid = tuple(rank_grid) if rank_grid is not None \
                else (self.R, 1, 1)
            if int(np.prod(grid)) != self.R:
                raise EngineError(
                    f"rank_grid {grid} does not cover the device mesh's "
                    f"graph axis (R={self.R})")
            pg = partition_mesh(sem_mesh, grid, method=partitioner)
            src = hierarchy if (hierarchy is not None
                                and self.cfg.n_levels > 1) else pg
            mode = self.config.halo_mode if self.R > 1 else "none"
            plan = NMPPlan.build(src, mode, axis="graph", **self._policy)
            graph = ShardedGraph.build(
                pg, sem_mesh.coords, plan,
                hierarchy=hierarchy if self.cfg.n_levels > 1 else None)
            plan = plan.autotune(graph, hidden=self.cfg.hidden)
            gs = shard_graph(self.mesh_dev, graph)
            predict = make_rollout_predict_fn(
                self.mesh_dev, self.cfg, plan, self.config.rollout_steps)
            self._graphs[key] = _GraphEntry(
                mesh_hash=mesh_hash, pg=pg, plan=plan, gs=gs,
                predict=predict)
            self.stats["cache_builds"] += 1
        return mesh_hash

    def _entry(self, mesh_hash: str, partitioner: Optional[str] = None
               ) -> _GraphEntry:
        if mesh_hash != self.fingerprint["mesh_hash"]:
            raise self._mismatch(mesh_hash)
        key = (mesh_hash, partitioner or self.config.partitioner)
        with self._lock:
            entry = self._graphs.get(key)
        if entry is None:
            raise EngineError(
                f"mesh {mesh_hash} (partitioner={key[1]!r}) is not "
                "registered — call register_mesh(sem_mesh) before "
                "submitting requests")
        return entry

    def warmup(self, mesh_hash: Optional[str] = None):
        """Compile each cached mesh's batch-slot program (one zero batch
        through the jitted rollout eval) so the first real request does not
        pay the compile."""
        with self._lock:
            entries = [e for k, e in self._graphs.items()
                       if mesh_hash is None or k[0] == mesh_hash]
        for entry in entries:
            x0 = np.stack([gather_node_features(
                entry.pg, np.zeros((entry.pg.n_global, self.cfg.node_in),
                                   np.float32))
                for _ in range(self.config.batch_slots)])
            np.asarray(entry.predict(self.params, x0, entry.gs))

    # -- lifecycle ----------------------------------------------------------

    @property
    def closed(self) -> bool:
        return self._stop.is_set()

    def _shutdown_error(self) -> EngineError:
        if self._failure is not None:
            return EngineError(f"engine terminated: {self._failure!r}")
        return EngineError("engine is shut down")

    def start(self) -> "InferenceEngine":
        if self._thread is not None:
            raise EngineError("engine already started")
        if self._stop.is_set():
            raise self._shutdown_error()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="inference-engine")
        self._thread.start()
        return self

    def close(self, error: Optional[BaseException] = None):
        """Stop the engine thread and fail every still-queued request (with
        ``error``, when given, as the terminal cause)."""
        if error is not None and self._failure is None:
            self._failure = error
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._drain_failed()

    def __enter__(self) -> "InferenceEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    def _drain_failed(self):
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                return
            req.future._fail(self._shutdown_error())

    # -- request path -------------------------------------------------------

    def submit(self, mesh_hash: str, x, step: int = 0,
               timeout: Optional[float] = None,
               partitioner: Optional[str] = None) -> RequestFuture:
        """Queue one global ``[N, F]`` snapshot; returns its future.

        Blocks while ``max_pending`` requests are already queued — the
        backpressure contract — for at most ``timeout`` seconds
        (:class:`EngineError` on expiry; ``None`` waits forever)."""
        if self._stop.is_set():
            raise self._shutdown_error()
        entry = self._entry(mesh_hash, partitioner)
        x = np.asarray(x, np.float32)
        want = (int(entry.pg.n_global), int(self.cfg.node_in))
        if tuple(x.shape) != want:
            raise EngineError(
                f"snapshot shape {tuple(x.shape)} does not match the "
                f"registered mesh ({want[0]} nodes x {want[1]} fields)")
        fut = RequestFuture(step)
        req = _Request(seq=next(self._seq), step=step,
                       key=(mesh_hash, partitioner or self.config.partitioner),
                       x=x, future=fut, t_submit=time.perf_counter())
        try:
            self._q.put(req, timeout=timeout)
        except queue.Full:
            raise EngineError(
                f"request queue full ({self.config.max_pending} pending) "
                f"after {timeout}s — the engine is saturated "
                "(backpressure)") from None
        if self._stop.is_set():
            # raced a shutdown: make sure this request cannot hang
            self._drain_failed()
        return fut

    def stream(self, mesh_hash: str, batch_fn: Callable[[int], Any],
               n_requests: int, n_producers: int = 1, prefetch: int = 4,
               start_step: int = 0):
        """Producer-threaded streaming: yields ``(step, InferenceResult)``
        in submission order.

        ``batch_fn(step) -> [N, F]`` global snapshot runs on ``n_producers``
        background threads inside a :class:`PrefetchingLoader` (the repo's
        hang-safe transport); a feeder thread submits each item into the
        bounded request queue, so a slow consumer backpressures all the way
        into the producers.  A dead producer (``batch_fn`` raised) drains
        what it already queued, then SHUTS THE ENGINE DOWN and raises
        :class:`EngineError` — a solver feed dying must never leave the
        service half-alive and hanging (the CI serve-smoke job pins this).
        """
        loader = PrefetchingLoader(batch_fn, prefetch=prefetch,
                                   start_step=start_step,
                                   n_producers=n_producers)
        futs: queue.Queue = queue.Queue()
        done = object()
        box: dict = {"err": None}

        def feed():
            try:
                for _ in range(n_requests):
                    step, batch = next(loader)
                    futs.put((step, self.submit(mesh_hash, np.asarray(batch),
                                                step=step)))
            except StopIteration:
                pass
            except BaseException as e:
                box["err"] = e
            finally:
                loader.close()
                futs.put(done)

        feeder = threading.Thread(target=feed, daemon=True,
                                  name="engine-stream-feeder")
        feeder.start()
        try:
            while True:
                item = futs.get()
                if item is done:
                    break
                step, fut = item
                yield step, fut.result(
                    timeout=self.config.result_timeout_s)
        finally:
            feeder.join(timeout=30)
        if box["err"] is not None:
            err = box["err"]
            self.close(error=err)
            raise EngineError(
                f"producer feed for mesh {mesh_hash} died; engine shut "
                f"down: {err!r}") from err

    # -- engine thread ------------------------------------------------------

    def _loop(self):
        try:
            while not self._stop.is_set():
                try:
                    first = self._q.get(timeout=0.05)
                except queue.Empty:
                    continue
                _dequeued(first)
                batch = [first]
                deadline = time.perf_counter() + self.config.flush_timeout_s
                while len(batch) < self.config.batch_slots:
                    rem = deadline - time.perf_counter()
                    if rem <= 0:
                        break
                    try:
                        batch.append(_dequeued(self._q.get(timeout=rem)))
                    except queue.Empty:
                        break
                # group by graph-cache key: multi-geometry ready (today all
                # requests share the checkpoint's one mesh)
                groups: dict = {}
                for r in batch:
                    groups.setdefault(r.key, []).append(r)
                for key, reqs in groups.items():
                    self._run_batch(key, reqs)
        except BaseException as e:
            # an internal failure poisons the engine: record it, fail every
            # queued request, and refuse further submits — never limp along
            self._failure = e
            self._stop.set()
            self._drain_failed()

    def _run_batch(self, key: tuple, reqs: list):
        entry = self._graphs[key]
        slots = self.config.batch_slots
        try:
            xs = []
            for r in reqs:
                with obs.span("engine/gather", r.seq):
                    xs.append(gather_node_features(entry.pg, r.x))
            n_pad = slots - len(xs)
            xs.extend(np.zeros_like(xs[0]) for _ in range(n_pad))
            with obs.span("engine/predict", reqs[0].seq):
                preds = np.asarray(
                    entry.predict(self.params, np.stack(xs), entry.gs))
            t_done = time.perf_counter()
            for i, r in enumerate(reqs):
                with obs.span("engine/scatter", r.seq):
                    out = np.stack([
                        scatter_node_outputs(entry.pg, preds[i, k])
                        for k in range(self.config.rollout_steps)])
                r.future._set(InferenceResult(
                    step=r.step, mesh_hash=key[0], preds=out,
                    latency_s=t_done - r.t_submit))
            self.stats["requests"] += len(reqs)
            self.stats["batches"] += 1
            self.stats["padded_slots"] += n_pad
        except BaseException as e:
            for r in reqs:
                r.future._fail(e)
            raise

    # -- offline oracle -----------------------------------------------------

    def offline_reference(self, mesh_hash: str, x,
                          partitioner: Optional[str] = None) -> np.ndarray:
        """Run ONE snapshot synchronously at batch=1 through the same
        cached plan/graph, bypassing the queue entirely — the documented
        oracle for the bitwise consistency contract (``benchmarks/serve.py``
        asserts engine == offline on every bench run; the CI driver builds
        its own rollout eval from scratch for a stronger check)."""
        entry = self._entry(mesh_hash, partitioner)
        xs = gather_node_features(entry.pg,
                                  np.asarray(x, np.float32))[None]
        preds = np.asarray(entry.predict(self.params, xs, entry.gs))[0]
        return np.stack([scatter_node_outputs(entry.pg, preds[k])
                         for k in range(self.config.rollout_steps)])
