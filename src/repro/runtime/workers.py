"""Multi-process fleet execution: sharded worker pool, zero-copy transport.

The whole platform so far runs in ONE Python process on one ``VirtualClock``
— the 1M-device columnar round (PR 6) saturates a single host and cohort
compute cannot overlap across fleet shards.  This module is the
coordinator/worker control plane that splits *cohort execution* across N
worker processes while the coordinator keeps everything stateful and
time-authoritative (``TaskEngine``, ``DeviceFlow``, ``AggregationService``,
fleet sampling, arrival stamping) in one place:

* **Workers compute, the coordinator decides.**  A round's cohort chunks —
  the exact ``(lo, hi)`` ranges + per-chunk rng subkeys the single-process
  engine would have run — are dispatched to workers (chunk ``i`` goes to
  worker ``i % N``, a stable fleet-shard assignment that keeps int8
  error-feedback residuals resident with "their" devices across rounds).
  Each worker owns its own jitted cohort loop (``run_cohort_zero_copy`` /
  ``run_cohort_quantized`` on tiers rebuilt from a picklable
  :class:`WorkerSpec` factory), so JAX compilation and dispatch parallelize
  across processes.

* **Zero-copy columnar transport.**  Results come back as the *existing*
  struct-of-arrays wire format: the chunk's ``UpdateBuffer`` leaves (int8 or
  f32, plus scale columns) are written into a ``multiprocessing
  .shared_memory`` segment in a canonical layout both sides compute from the
  update spec, and only a slim ``(call, chunk, shm_name, rows)`` header
  crosses the pipe — no pickling of model data.  The coordinator wraps the
  segment's numpy views in an ordinary ``UpdateBuffer``, so byte accounting
  (``row_nbytes`` → ``Shelf.total_bytes_*``) and the fused ``fed_reduce``
  aggregation path are untouched.

* **Recycled segment ring (the PR 3 donation discipline, across
  processes).**  Workers keep a free-list of segments and reuse one as soon
  as the coordinator releases it.  Release is GC-driven, mirroring how
  device buffers are freed: a ``weakref.finalize`` on each coordinator-side
  ``UpdateBuffer`` sends ``("free", name)`` back to the owning worker the
  moment the buffer is garbage-collected (i.e. when aggregation has consumed
  the round and dropped its handles).  Steady-state rounds therefore
  allocate no new segments.  Lifetime rule: anything read out of a buffer
  must be *copied* before the buffer is dropped — ``materialize`` /
  ``materialize_row`` already do this for shared-memory-backed leaves.

* **Graceful worker death.**  A worker dying mid-round (EOF on its pipe)
  does not hang the round barrier: its still-pending chunks are re-assigned
  to the survivors through ``runtime.fault_tolerance.redispatch_chunks`` and
  the failure is recorded on ``pool.failures``.  Re-dispatched int8 chunks
  restart their error-feedback residual from zero (the residual died with
  the worker) — the same semantics as a fresh device joining the fleet.

Determinism: because the coordinator precomputes the per-chunk subkeys by
walking the exact single-process rng split chain, and reassembles results in
chunk order before submission, a multi-process round is **bit-identical** to
the single-process columnar round — dispatch-group membership, ``created_t``
stamps, byte counters, and the reduced delta (property-tested in
``tests/test_workers.py``).  With ``stream_chunks=True`` results are instead
emitted in *completion* order so streaming partial reduction overlaps
still-running shards; global dispatch membership is then recovered by
arrival-time ordering exactly as in the single-process streaming trade-off.

``HybridSimulation(workers=N, worker_spec=WorkerSpec(factory, ...))``
selects this path; see ``examples/quickstart.py`` §11.
"""
from __future__ import annotations

import dataclasses
import os
import time
import traceback
import weakref
from multiprocessing import connection as mp_connection
from multiprocessing import shared_memory
from typing import Any, Callable, Mapping, Sequence

import numpy as np

from repro.analysis import sanitizers
from repro.runtime.fault_tolerance import redispatch_chunks

_ALIGN = 64  # segment field alignment (cache line; numpy view friendly)


class WorkerPoolError(RuntimeError):
    """Raised when the pool cannot make progress (all workers dead, a
    worker raised, or the round barrier timed out)."""


def _align(off: int) -> int:
    return (off + _ALIGN - 1) & ~(_ALIGN - 1)


def _np_dtype(name: Any) -> np.dtype:
    """``np.dtype`` lookup that also resolves ml_dtypes names (bfloat16)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(name)))


def _attach_shm(name: str) -> shared_memory.SharedMemory:
    """Attach to a segment another process created.

    The 3.10 resource tracker is one process shared by the whole tree and
    its cache is a *set*: the attach-side ``register`` is a no-op while the
    creator's entry exists, and the creator's eventual ``unlink`` clears it
    exactly once.  Unregistering here (the often-cited double-unlink
    workaround) would instead erase the creator's entry and make its unlink
    crash the tracker — so: attach, and leave the tracker alone.
    """
    return shared_memory.SharedMemory(name=name)


def segment_layout(shapes: Sequence[tuple], dtypes: Sequence[Any],
                   rows: int, wire: str) -> tuple[list, int]:
    """Canonical shared-memory layout of one chunk's ``UpdateBuffer``.

    Both sides compute this independently from the update spec — the pipe
    header never carries shapes or dtypes.  Layout: every leaf as its
    ``(rows, size)`` wire matrix (int8 for the quantized wire), then — int8
    only — one f32 ``(rows,)`` scale column per leaf, each field aligned to
    64 bytes.  Returns ``([(offset, shape, dtype), ...], total_bytes)`` with
    leaf fields first, scale fields after, in leaf order.
    """
    entries: list[tuple[int, tuple, np.dtype]] = []
    off = 0
    for shape, dt in zip(shapes, dtypes):
        size = int(np.prod(shape)) if shape else 1
        leaf_dt = np.dtype(np.int8) if wire == "int8" else _np_dtype(dt)
        off = _align(off)
        entries.append((off, (rows, size), leaf_dt))
        off += rows * size * leaf_dt.itemsize
    if wire == "int8":
        for _ in shapes:
            off = _align(off)
            entries.append((off, (rows,), np.dtype(np.float32)))
            off += rows * 4
    return entries, max(_align(off), _ALIGN)


@dataclasses.dataclass(frozen=True)
class WorkerSpec:
    """Picklable recipe for rebuilding the simulation tiers inside a worker.

    ``factory(**kwargs)`` must be a *module-level* callable (spawn pickles it
    by reference) returning ``(logical_tier, {grade: device_tier})`` built
    exactly like the coordinator's tiers — same local_train, dtypes, and
    cohort sizes — so worker-computed chunks are bit-identical to inline
    ones (on the same backend; workers run on the CPU, see
    :class:`FleetWorkerPool`).
    """

    factory: Callable[..., tuple]
    kwargs: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def build(self) -> tuple:
        logical, tiers = self.factory(**dict(self.kwargs))
        return logical, dict(tiers)


@dataclasses.dataclass(frozen=True)
class ChunkSpec:
    """One cohort chunk of a round: the same (range, subkey) the inline
    engine would run.  ``kind`` selects the tier: ``"logical"`` or a grade
    name.  ``key`` is the chunk's rng subkey as a host uint32 array."""

    index: int
    kind: str
    lo: int
    hi: int
    key: np.ndarray
    id_offset: int = 0

    @property
    def rows(self) -> int:
        return self.hi - self.lo


def _tree_desc(tree: Any, offset: int) -> tuple[dict, int, list]:
    """Describe a pytree for shared-memory transport: a picklable skeleton
    (leaves replaced by indices) + per-leaf (offset, shape, dtype) entries.
    Returns (desc, next_offset, leaves)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    skeleton = jax.tree_util.tree_unflatten(treedef, list(range(len(leaves))))
    entries = []
    for leaf in leaves:
        dt = _np_dtype(leaf.dtype)
        shape = tuple(int(s) for s in leaf.shape)
        offset = _align(offset)
        entries.append((offset, shape, str(dt)))
        offset += int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
    return {"skeleton": skeleton, "leaves": entries}, offset, leaves


def _tree_from_desc(desc: dict, buf) -> Any:
    """Rebuild a pytree of numpy views over a shared-memory buffer."""
    import jax

    leaves = [np.ndarray(shape, _np_dtype(dts), buffer=buf, offset=off)
              for off, shape, dts in desc["leaves"]]
    treedef = jax.tree_util.tree_structure(desc["skeleton"])
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Worker process
# ---------------------------------------------------------------------------
class _WorkerState:
    """Everything a worker process owns: lazily-built tiers, the jitted
    cohort loops' caches, error-feedback residuals for "its" device rows,
    and the recycled ring of result segments."""

    def __init__(self, worker_id: int, spec: WorkerSpec, delay_s: float):
        self.worker_id = worker_id
        self.spec = spec
        self.delay_s = delay_s  # test hook: interleaving jitter per chunk
        self.logical = None
        self.tiers: dict = {}
        self._ef: dict = {}
        self._free: list[shared_memory.SharedMemory] = []
        self._created: dict[str, shared_memory.SharedMemory] = {}
        self._park_close: list = []  # input segs with still-exported views
        self.fail_after: int | None = None  # test hook: die after N chunks
        self._sent = 0

    def _tier(self, kind: str):
        if self.logical is None:
            self.logical, self.tiers = self.spec.build()
        return self.logical if kind == "logical" else self.tiers[kind]

    def _acquire(self, nbytes: int) -> shared_memory.SharedMemory:
        for i, seg in enumerate(self._free):
            if seg.size >= nbytes:
                return self._free.pop(i)
        seg = shared_memory.SharedMemory(create=True, size=nbytes)
        self._created[seg.name] = seg
        return seg

    def release(self, name: str) -> None:
        seg = self._created.get(name)
        if seg is not None and all(s.name != name for s in self._free):
            self._free.append(seg)

    def _drain_parked(self) -> None:
        still = []
        for seg in self._park_close:
            try:
                seg.close()
            except BufferError:
                still.append(seg)
        self._park_close = still

    def run(self, conn, call_id: int, input_desc: dict,
            chunks: list[ChunkSpec], common: dict) -> None:
        import jax
        import jax.numpy as jnp

        self._drain_parked()
        seg = _attach_shm(input_desc["name"])
        try:
            params_np = _tree_from_desc(input_desc["params"], seg.buf)
            batches_np = _tree_from_desc(input_desc["batches"], seg.buf)
            # Params go on-device once per call; chunk slices are cheap
            # views copied at each cohort dispatch, like the inline path.
            params = jax.tree.map(jnp.asarray, params_np)
            del params_np
            wire = common["wire"]
            for c in chunks:
                if self.fail_after is not None and self._sent >= self.fail_after:
                    os._exit(1)  # test hook: simulated mid-round crash
                tier = self._tier(c.kind)
                chunk = jax.tree.map(lambda x: x[c.lo:c.hi], batches_np)
                rngs = jax.random.split(jnp.asarray(c.key), c.rows)
                if wire == "int8":
                    ef_key = (common["task_id"], c.kind,
                              c.id_offset + c.lo, c.id_offset + c.hi)
                    buf, metrics, res = tier.run_cohort_quantized(
                        params, chunk, rngs,
                        residual=self._ef.get(ef_key),
                        error_feedback=common["error_feedback"])
                    if common["error_feedback"]:
                        self._ef[ef_key] = res
                else:
                    buf, metrics = tier.run_cohort_zero_copy(
                        params, chunk, rngs)
                del chunk
                entries, total = segment_layout(
                    buf.shapes, buf.dtypes, buf.num_rows, wire)
                out = self._acquire(total)
                arrays = list(buf.leaves2d) + list(buf.scales or ())
                for (off, shape, dt), src in zip(entries, arrays):
                    dst = np.ndarray(shape, dt, buffer=out.buf, offset=off)
                    np.copyto(dst, np.asarray(src).astype(dt, copy=False))
                    del dst
                if self.delay_s:
                    time.sleep(self.delay_s)
                conn.send(("batch", call_id, c.index, out.name,
                           buf.num_rows, jax.device_get(metrics)))
                self._sent += 1
        finally:
            try:
                del batches_np
            except NameError:
                pass
            try:
                seg.close()
            except BufferError:  # a view outlived the call; retry later
                self._park_close.append(seg)

    def cleanup(self) -> None:
        for seg in self._created.values():
            try:
                seg.close()
            except BufferError:
                pass
            try:
                seg.unlink()
            except FileNotFoundError:
                pass


def _worker_main(worker_id: int, conn, spec: WorkerSpec,
                 delay_s: float) -> None:
    import jax

    # Before anything touches a device: the fleet runs on the host CPUs,
    # whatever backend the inherited environment names.
    jax.config.update("jax_platforms", "cpu")
    state = _WorkerState(worker_id, spec, delay_s)
    try:
        conn.send(("ready", worker_id))
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break  # coordinator gone
            tag = msg[0]
            if tag == "stop":
                break
            elif tag == "free":
                state.release(msg[1])
            elif tag == "poison":
                state.fail_after = msg[1]
            elif tag == "run":
                _, call_id, input_desc, chunks, common = msg
                try:
                    state.run(conn, call_id, input_desc, chunks, common)
                except Exception:
                    conn.send(("error", call_id, -1, traceback.format_exc()))
    finally:
        state.cleanup()


# ---------------------------------------------------------------------------
# Coordinator side
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _WorkerHandle:
    worker_id: int
    proc: Any
    conn: Any
    alive: bool = True
    announced: set = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class _Seg:
    shm: shared_memory.SharedMemory
    owner: int


class FleetWorkerPool:
    """Coordinator handle on N spawned cohort workers.

    Processes start lazily on the first :meth:`run_chunks` (spawn context —
    forking an initialized JAX runtime is unsafe) and are daemons, so a
    crashed coordinator never strands them.  See the module docstring for
    the transport/recycling/fault model.

    Workers run the fleet on the host CPUs (the host-CPU fleet design): each
    child sets JAX's platform to ``cpu`` before it touches a device,
    whatever the coordinator's environment says.  An accelerator belongs to
    one process at a time, and on a TPU host the coordinator holds it
    (aggregation and serving run there), so a child that tried to
    initialise the TPU would fail or hang.  Pooled chunks therefore run on the CPU backend; they are
    bit-identical to inline rounds when the coordinator's backend is the CPU
    too.
    """

    def __init__(self, spec: WorkerSpec, num_workers: int, *,
                 chunk_timeout_s: float = 600.0,
                 start_timeout_s: float = 120.0,
                 debug_delay_s: Sequence[float] | None = None):
        if num_workers < 1:
            raise ValueError("need at least one worker")
        self.spec = spec
        self.num_workers = int(num_workers)
        self.chunk_timeout_s = float(chunk_timeout_s)
        self.start_timeout_s = float(start_timeout_s)
        self._debug_delay_s = tuple(debug_delay_s or ())
        self._workers: list[_WorkerHandle] = []
        self._segments: dict[str, _Seg] = {}  # held by a live UpdateBuffer
        self._to_close: list[shared_memory.SharedMemory] = []
        self._dead_owner_names: set[str] = set()
        self._call_counter = 0
        self._closed = False
        self.failures: list = []
        self.stats = {"calls": 0, "chunks": 0, "segments_created": 0,
                      "segment_reuses": 0, "redispatched_chunks": 0,
                      "bytes_shipped": 0, "input_bytes": 0}

    # -- lifecycle ---------------------------------------------------------
    @property
    def started(self) -> bool:
        return bool(self._workers)

    def start(self) -> None:
        if self._workers or self._closed:
            return
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        for wid in range(self.num_workers):
            parent, child = ctx.Pipe()
            delay = (self._debug_delay_s[wid % len(self._debug_delay_s)]
                     if self._debug_delay_s else 0.0)
            proc = ctx.Process(target=_worker_main,
                               args=(wid, child, self.spec, delay),
                               daemon=True, name=f"fleet-worker-{wid}")
            proc.start()
            child.close()
            self._workers.append(_WorkerHandle(wid, proc, parent))
        deadline = time.monotonic() + self.start_timeout_s
        for h in self._workers:
            remaining = max(0.1, deadline - time.monotonic())
            if not h.conn.poll(remaining):
                self.close()
                raise WorkerPoolError(
                    f"worker {h.worker_id} did not report ready within "
                    f"{self.start_timeout_s}s")
            tag = h.conn.recv()
            if tag[0] != "ready":  # pragma: no cover - defensive
                self.close()
                raise WorkerPoolError(f"bad handshake from {h.worker_id}")

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for h in self._workers:
            if h.alive:
                try:
                    h.conn.send(("stop",))
                except (BrokenPipeError, OSError):
                    pass
        for h in self._workers:
            h.proc.join(timeout=5.0)
            if h.proc.is_alive():  # pragma: no cover - defensive
                h.proc.terminate()
                h.proc.join(timeout=5.0)
            try:
                h.conn.close()
            except OSError:
                pass
            h.alive = False
        # Names the (now exited) workers no longer own: make sure nothing
        # lingers in /dev/shm.  Held mappings stay valid for live buffers.
        for name, seg in self._segments.items():
            try:
                seg.shm.unlink()
            except FileNotFoundError:
                pass
        self._drain_closes()
        if sanitizers.enabled() and self._to_close:
            # A segment that cannot unmap at teardown means an exported
            # numpy view outlived its UpdateBuffer — the lifetime rule in
            # this module's docstring.  Unlinked above, so /dev/shm is
            # clean; the mapping itself leaks until the view dies.
            names = sorted(shm.name for shm in self._to_close)
            raise sanitizers.SegmentLeakError(
                f"{len(names)} shared-memory segment(s) still pinned at "
                f"pool teardown (views outlived their buffers): {names}")

    def __enter__(self) -> "FleetWorkerPool":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- segment bookkeeping ----------------------------------------------
    def _drain_closes(self) -> None:
        still = []
        for shm in self._to_close:
            try:
                shm.close()
            except BufferError:
                still.append(shm)
        self._to_close = still

    def _release_segment(self, name: str) -> None:
        """GC hook: the coordinator-side UpdateBuffer over segment ``name``
        was collected — hand the segment back to its worker's free ring."""
        entry = self._segments.pop(name, None)
        if entry is None:
            return
        if not self._closed and name not in self._dead_owner_names:
            h = self._workers[entry.owner]
            if h.alive:
                try:
                    h.conn.send(("free", name))
                except (BrokenPipeError, OSError):
                    pass
        # The buffer's views die right after this callback; close then.
        self._to_close.append(entry.shm)

    def _reap_worker_segments(self, h: _WorkerHandle) -> None:
        """A worker died: unlink every segment it ever announced.  Held
        mappings (live buffers) stay readable — unlink only drops the name."""
        for name in h.announced:
            self._dead_owner_names.add(name)
            entry = self._segments.get(name)
            try:
                shm = entry.shm if entry is not None else _attach_shm(name)
                shm.unlink()
                if entry is None:
                    shm.close()
            except (FileNotFoundError, OSError):
                pass

    # -- round execution ---------------------------------------------------
    def _write_input(self, params: Any, batches: Any) -> tuple:
        import jax

        off = 0
        p_desc, off, p_leaves = _tree_desc(params, off)
        b_desc, off, b_leaves = _tree_desc(batches, off)
        shm = shared_memory.SharedMemory(create=True, size=max(off, _ALIGN))
        for desc, leaves in ((p_desc, p_leaves), (b_desc, b_leaves)):
            for (o, shape, dts), leaf in zip(desc["leaves"], leaves):
                dst = np.ndarray(shape, _np_dtype(dts), buffer=shm.buf,
                                 offset=o)
                np.copyto(dst, np.asarray(leaf))
                del dst
        self.stats["input_bytes"] += int(off)
        return shm, {"name": shm.name, "params": p_desc, "batches": b_desc}

    def _wrap_result(self, h: _WorkerHandle, seg_name: str, rows: int,
                     chunk: ChunkSpec, spec: tuple, wire: str):
        """Wrap a worker's result segment in an ordinary ``UpdateBuffer``
        whose leaves are zero-copy numpy views; register a GC finalizer
        that recycles the segment back to the worker."""
        from repro.core.updates import UpdateBuffer

        treedef, shapes, dtypes = spec
        if rows != chunk.rows:  # pragma: no cover - defensive
            raise WorkerPoolError(
                f"worker {h.worker_id} returned {rows} rows for chunk "
                f"{chunk.index} ({chunk.rows} expected)")
        if seg_name in h.announced:
            self.stats["segment_reuses"] += 1
        else:
            h.announced.add(seg_name)
            self.stats["segments_created"] += 1
        entries, total = segment_layout(shapes, dtypes, rows, wire)
        shm = _attach_shm(seg_name)
        self._segments[seg_name] = _Seg(shm, h.worker_id)
        self.stats["bytes_shipped"] += int(total)
        fields = [np.ndarray(shape, dt, buffer=shm.buf, offset=off)
                  for off, shape, dt in entries]
        n_leaves = len(shapes)
        buf = UpdateBuffer(
            fields[:n_leaves], treedef, shapes, dtypes, wire=wire,
            scales=fields[n_leaves:] if wire == "int8" else None)
        weakref.finalize(buf, self._release_segment, seg_name)
        return buf

    def _on_worker_death(self, h: _WorkerHandle, call_id: int,
                         input_desc: dict, common: dict,
                         expected: dict, pending: dict) -> None:
        h.alive = False
        try:
            h.conn.close()
        except OSError:
            pass
        h.proc.join(timeout=1.0)
        self._reap_worker_segments(h)
        lost = sorted(pending.pop(h.worker_id, set()) & set(expected))
        survivors = [w.worker_id for w in self._workers if w.alive]
        assignment = redispatch_chunks(lost, survivors) if lost else {}
        for wid, idxs in assignment.items():
            self._workers[wid].conn.send(
                ("run", call_id, input_desc, [expected[i] for i in idxs],
                 common))
            pending.setdefault(wid, set()).update(idxs)
        self.stats["redispatched_chunks"] += len(lost)
        from repro.runtime.fault_tolerance import WorkerFailure

        self.failures.append(WorkerFailure(
            worker_id=h.worker_id, chunks=tuple(lost),
            survivors=tuple(survivors)))

    def run_chunks(self, *, task_id: int, round_idx: int, params: Any,
                   batches: Any, chunks: Sequence[ChunkSpec],
                   specs_by_kind: Mapping[str, tuple], wire: str = "f32",
                   error_feedback: bool = True,
                   on_result: Callable | None = None) -> list:
        """Execute one grade's cohort chunks across the pool.

        Ships ``params`` + the grade's stacked ``batches`` once through a
        per-call input segment, dispatches every chunk to its worker, and
        gathers ``(UpdateBuffer, metrics)`` per chunk — returned in CHUNK
        order (the bit-identical reassembly).  ``on_result(index, buf,
        metrics)`` additionally fires in COMPLETION order as shards finish,
        which is what overlaps streaming partial reduction with
        still-running workers.
        """
        if self._closed:
            raise WorkerPoolError("pool is closed")
        self.start()
        self._drain_closes()
        chunks = list(chunks)
        if not chunks:
            return []
        call_id = self._call_counter
        self._call_counter += 1
        self.stats["calls"] += 1
        alive = [h for h in self._workers if h.alive]
        if not alive:
            raise WorkerPoolError("no live workers")
        input_shm, input_desc = self._write_input(params, batches)
        common = {"task_id": int(task_id), "round_idx": int(round_idx),
                  "wire": wire, "error_feedback": bool(error_feedback)}
        try:
            # Stable fleet-shard assignment: chunk i -> worker i % N keeps
            # each row range (and its EF residual) with the same worker
            # across rounds; a dead worker's chunks fall to survivors.
            assign: dict[int, list[ChunkSpec]] = {}
            for c in chunks:
                h = self._workers[c.index % self.num_workers]
                if not h.alive:
                    h = alive[c.index % len(alive)]
                assign.setdefault(h.worker_id, []).append(c)
            pending: dict[int, set] = {}
            for wid, cs in assign.items():
                self._workers[wid].conn.send(
                    ("run", call_id, input_desc, cs, common))
                pending[wid] = {c.index for c in cs}
            expected = {c.index: c for c in chunks}
            results: dict[int, tuple] = {}
            deadline = time.monotonic() + self.chunk_timeout_s
            while expected:
                conns = {h.conn: h for h in self._workers if h.alive}
                if not conns:
                    raise WorkerPoolError(
                        f"all workers dead with {len(expected)} chunks "
                        f"outstanding")
                ready = mp_connection.wait(list(conns), timeout=1.0)
                if not ready:
                    for h in list(conns.values()):
                        if not h.proc.is_alive():
                            self._on_worker_death(h, call_id, input_desc,
                                                  common, expected, pending)
                    if time.monotonic() > deadline:
                        raise WorkerPoolError(
                            f"round barrier timed out with {len(expected)} "
                            f"chunks outstanding")
                    continue
                for conn in ready:
                    h = conns[conn]
                    try:
                        msg = conn.recv()
                    except (EOFError, OSError):
                        self._on_worker_death(h, call_id, input_desc,
                                              common, expected, pending)
                        continue
                    tag = msg[0]
                    if tag == "batch":
                        _, cid, index, seg_name, rows, metrics = msg
                        if cid != call_id or index not in expected:
                            continue  # stale duplicate (redispatch race)
                        c = expected.pop(index)
                        pending.get(h.worker_id, set()).discard(index)
                        buf = self._wrap_result(
                            h, seg_name, rows, c, specs_by_kind[c.kind],
                            wire)
                        results[index] = (buf, metrics)
                        self.stats["chunks"] += 1
                        if on_result is not None:
                            on_result(index, buf, metrics)
                    elif tag == "error":
                        raise WorkerPoolError(
                            f"worker {h.worker_id} raised:\n{msg[3]}")
            return [results[c.index] for c in chunks]
        finally:
            try:
                input_shm.close()
            except BufferError:  # pragma: no cover - defensive
                self._to_close.append(input_shm)
            try:
                input_shm.unlink()
            except FileNotFoundError:  # pragma: no cover - defensive
                pass

    # -- test / fault-injection hooks -------------------------------------
    def poison_worker(self, worker_id: int, fail_after_chunks: int) -> None:
        """Arrange for ``worker_id`` to crash (``os._exit``) after computing
        ``fail_after_chunks`` more chunks — the deterministic kill-a-worker
        fault injection used by the death-handling tests."""
        self.start()
        self._workers[worker_id].conn.send(("poison", fail_after_chunks))

    @property
    def alive_workers(self) -> list[int]:
        return [h.worker_id for h in self._workers if h.alive]
