"""Out-of-process Python UDF workers speaking Arrow IPC.

TPU-native analog of the reference's GPU-aware Python worker machinery
(ref: python/rapids/worker.py:22 + daemon.py — child processes that
initialize their own memory pools; GpuArrowEvalPythonExec.scala:58-260 —
Arrow batches streamed across the process boundary and paired back;
PythonWorkerSemaphore.scala — bounding concurrent python workers).

Redesign for this engine:

  * A `PythonWorker` is a subprocess running `worker_main()`.  Requests
    carry a cloudpickled task closure + N Arrow-IPC framed tables on the
    worker's stdin; responses return M Arrow-IPC tables (or a pickled
    scalar payload) on its stdout.  stderr passes through for user print
    debugging.
  * Workers are generic (no per-UDF state), pooled process-wide and
    reused across queries — the daemon-amortization idea without a fork
    server.  `PythonWorkerPool` bounds live workers with a semaphore
    (the PythonWorkerSemaphore analog).
  * Workers are pinned to the CPU (JAX_PLATFORMS=cpu): user
    python code must never contend for the device the engine owns —
    the exact concern the reference's worker RMM-pool bounds address.
  * Crash containment: a worker dying mid-request (OOM-kill, os._exit,
    segfault) surfaces as `PythonWorkerCrash` on that query; the pool
    discards the corpse and later queries get a fresh worker.
"""

from __future__ import annotations

import atexit
import io
import os
import struct
import subprocess
import sys
import threading
from typing import Callable, List, Optional, Sequence, Tuple

import pyarrow as pa

MAGIC = b"TPUW"
OP_TASK = 1
OP_SHUTDOWN = 2
OP_STREAM = 3
ST_OK = 0
ST_ERR = 1
TAG_BLOB = 1
TAG_END = 0


class PythonWorkerError(RuntimeError):
    """The user's UDF raised inside the worker (traceback attached)."""


class PythonWorkerCrash(RuntimeError):
    """The worker process died mid-request (crash/OOM-kill/exit)."""


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

def _write_blob(f, data: bytes) -> None:
    f.write(struct.pack("<Q", len(data)))
    f.write(data)


def _read_exact(f, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            raise EOFError("worker stream closed")
        buf += chunk
    return buf


def _read_blob(f) -> bytes:
    (n,) = struct.unpack("<Q", _read_exact(f, 8))
    return _read_exact(f, n)


def _table_to_ipc(tbl: pa.Table) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue()


def _ipc_to_table(data: bytes) -> pa.Table:
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        return r.read_all()


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------

def worker_main(stdin=None, stdout=None) -> None:
    """Request loop; runs in the child process."""
    import cloudpickle
    fin = stdin or sys.stdin.buffer
    fout = stdout or sys.stdout.buffer
    if stdout is None:
        # the framing protocol owns the real stdout; user print() (and any
        # library chatter) must land on stderr or it would corrupt frames
        sys.stdout = sys.stderr
    while True:
        try:
            head = _read_exact(fin, 5)
        except EOFError:
            return
        magic, op = head[:4], head[4]
        if magic != MAGIC:
            return
        if op == OP_SHUTDOWN:
            return
        if op == OP_STREAM:
            _serve_stream(fin, fout)
            continue
        payload = _read_blob(fin)
        (n_in,) = struct.unpack("<I", _read_exact(fin, 4))
        tables = [_ipc_to_table(_read_blob(fin)) for _ in range(n_in)]
        try:
            task, aux = cloudpickle.loads(payload)
            out_tables, out_obj = task(tables, aux)
            fout.write(bytes([ST_OK]))
            fout.write(struct.pack("<I", len(out_tables)))
            for tb in out_tables:
                _write_blob(fout, _table_to_ipc(tb))
            _write_blob(fout, cloudpickle.dumps(out_obj))
        except Exception:  # noqa: BLE001 — everything must cross the pipe
            import traceback
            fout.write(bytes([ST_ERR]))
            _write_blob(fout, cloudpickle.dumps(traceback.format_exc()))
        fout.flush()


def _serve_stream(fin, fout) -> None:
    """Streaming request: input tables arrive tagged and are consumed
    lazily by the task generator; each output table is written as soon as
    the task yields it.  Peak memory stays one batch per side — the
    contract mapInPandas promises (ref RebatchingRoundoffIterator streams
    batch-by-batch through the reference's workers too)."""
    import cloudpickle
    payload = _read_blob(fin)

    def gen():
        while True:
            tag = _read_exact(fin, 1)[0]
            if tag == TAG_END:
                return
            yield _ipc_to_table(_read_blob(fin))

    inputs = gen()
    try:
        task_gen, aux = cloudpickle.loads(payload)
        for tb in task_gen(inputs, aux):
            fout.write(bytes([TAG_BLOB]))
            _write_blob(fout, _table_to_ipc(tb))
            fout.flush()
        # the task may return without draining its input; the parent's
        # writer thread stops at TAG_END either way — drain to stay in
        # protocol sync
        for _ in inputs:
            pass
        fout.write(bytes([TAG_END, ST_OK]))
    except Exception:  # noqa: BLE001
        import traceback
        for _ in inputs:
            pass
        fout.write(bytes([TAG_END, ST_ERR]))
        _write_blob(fout, cloudpickle.dumps(traceback.format_exc()))
    fout.flush()


# ---------------------------------------------------------------------------
# task bodies (module-level so cloudpickle ships them by reference; the
# user fn rides inside `aux`)
# ---------------------------------------------------------------------------

def _cast_result(pdf, schema: pa.Schema) -> pa.Table:
    tbl = pa.Table.from_pandas(pdf, preserve_index=False)
    return tbl.select(schema.names).cast(schema)


def _group_pandas(tbl: pa.Table, key_names: List[str]):
    import pandas as pd
    if tbl.num_rows == 0:
        return []
    pdf = tbl.to_pandas()
    out = []
    for key, sub in pdf.groupby(key_names, dropna=False, sort=True):
        if not isinstance(key, tuple):
            key = (key,)
        key = tuple(None if (isinstance(k, float) and k != k) or
                    k is pd.NaT else k for k in key)
        out.append((key, sub.reset_index(drop=True)))
    out.sort(key=lambda kv: tuple((k is None, k) for k in kv[0]))
    return out


def task_map_in_pandas(tables, aux):
    fn, schema = aux
    outs = [ _cast_result(pdf, schema)
             for pdf in fn(tb.to_pandas() for tb in tables) if len(pdf) ]
    return ([pa.concat_tables(outs)] if outs else []), None


def task_stream_map_in_pandas(tables_iter, aux):
    """Streaming mapInPandas: fn's input iterator pulls batches off the
    pipe one at a time; each produced frame ships back immediately."""
    fn, schema = aux
    for pdf in fn(tb.to_pandas() for tb in tables_iter):
        if len(pdf):
            yield _cast_result(pdf, schema)


def task_grouped_map(tables, aux):
    fn, schema, key_names = aux
    outs = []
    for _, pdf in _group_pandas(tables[0], key_names):
        res = fn(pdf)
        if len(res):
            outs.append(_cast_result(res, schema))
    return ([pa.concat_tables(outs)] if outs else []), None


def task_cogrouped_map(tables, aux):
    fn, schema, lkeys, rkeys = aux
    ltbl, rtbl = tables
    lgroups = dict(_group_pandas(ltbl, lkeys))
    rgroups = dict(_group_pandas(rtbl, rkeys))
    keys = sorted(set(lgroups) | set(rgroups),
                  key=lambda kv: tuple((k is None, k) for k in kv))
    outs = []
    for key in keys:
        lpdf = lgroups.get(key)
        rpdf = rgroups.get(key)
        if lpdf is None:
            lpdf = ltbl.schema.empty_table().to_pandas()
        if rpdf is None:
            rpdf = rtbl.schema.empty_table().to_pandas()
        res = fn(lpdf, rpdf)
        if len(res):
            outs.append(_cast_result(res, schema))
    return ([pa.concat_tables(outs)] if outs else []), None


def task_grouped_agg(tables, aux):
    """One output row per group: keys then one scalar per UDF.  Returns
    the row dict as the pickled payload (scalars may not be
    Arrow-encodable before the declared cast)."""
    udfs, key_names = aux  # udfs: [(out_name, fn, in_cols)]
    tbl = tables[0]
    rows = {n: [] for n in key_names}
    for n, _, _ in udfs:
        rows[n] = []
    groups = _group_pandas(tbl, key_names) if key_names else \
        [((), tbl.to_pandas())]
    for key, pdf in groups:
        for k_name, k_val in zip(key_names, key):
            rows[k_name].append(k_val)
        for out_name, fn, in_cols in udfs:
            rows[out_name].append(fn(*[pdf[c] for c in in_cols]))
    return [], rows


def task_stream_eval_bound(tables_iter, aux):
    """Streaming row-UDF evaluation: one output table per input table,
    in order.  The closure/expression payload ships ONCE per partition
    (not per batch) and the input carries only the columns the UDFs
    actually reference."""
    for tbl in tables_iter:
        out, _ = task_eval_bound([tbl], aux)
        yield out[0]


def task_eval_bound(tables, aux):
    """Evaluate bound engine expressions (python row UDFs) against the
    batch — the worker runs the same host evaluator the in-process path
    uses, so null/coercion semantics are identical.  Returns ONLY the
    UDF output columns; the parent pairs them with its local child
    columns (the BatchQueue pairing, ref GpuArrowEvalPythonExec:189)."""
    bound, child_names, child_types, udf_names, ansi = aux
    import numpy as np
    from ..columnar.device import batch_to_device, batch_to_arrow, DeviceBatch
    from ..columnar.interop import to_arrow_schema
    from ..expr.core import EvalContext, ScalarValue, scalar_to_column
    tbl = tables[0].combine_chunks()
    rbs = tbl.to_batches()
    rb = rbs[0] if rbs else to_arrow_schema(
        child_names, child_types).empty_table().to_batches()[0]
    b = batch_to_device(rb, xp=np)
    ectx = EvalContext(np, b, ansi=ansi)
    cols = []
    for u in bound:
        v = u.eval(ectx)
        if isinstance(v, ScalarValue):
            v = scalar_to_column(ectx, v)
        cols.append(v.col)
    out = DeviceBatch(cols, b.num_rows, udf_names)
    return [pa.Table.from_batches([batch_to_arrow(out)])], None


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------

class PythonWorker:
    def __init__(self):
        env = dict(os.environ)
        # user code must not contend for the engine's TPU (the worker
        # analog of the reference's per-worker RMM pool bounds)
        env["JAX_PLATFORMS"] = "cpu"
        # the worker must resolve by-reference pickles of user modules:
        # propagate the parent's import path (the role Spark's pyfiles
        # shipping plays for its python workers)
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p] +
            [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        self.proc = subprocess.Popen(
            [sys.executable, "-c",
             "from spark_rapids_tpu.udf.worker import worker_main; "
             "worker_main()"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=env, cwd=os.getcwd())
        self.requests_served = 0

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def request(self, task: Callable, aux,
                tables: Sequence[pa.Table]
                ) -> Tuple[List[pa.Table], object]:
        import cloudpickle
        try:
            w = self.proc.stdin
            w.write(MAGIC + bytes([OP_TASK]))
            _write_blob(w, cloudpickle.dumps((task, aux)))
            w.write(struct.pack("<I", len(tables)))
            for tb in tables:
                _write_blob(w, _table_to_ipc(tb))
            w.flush()
            r = self.proc.stdout
            status = _read_exact(r, 1)[0]
            if status == ST_ERR:
                tb_str = cloudpickle.loads(_read_blob(r))
                raise PythonWorkerError(
                    f"python UDF raised in worker:\n{tb_str}")
            (n_out,) = struct.unpack("<I", _read_exact(r, 4))
            out_tables = [_ipc_to_table(_read_blob(r))
                          for _ in range(n_out)]
            out_obj = cloudpickle.loads(_read_blob(r))
            self.requests_served += 1
            return out_tables, out_obj
        except (EOFError, BrokenPipeError, OSError) as ex:
            rc = self.proc.poll()
            self.kill()
            raise PythonWorkerCrash(
                f"python worker died mid-request (rc={rc}): {ex}") from ex

    def request_stream(self, task_gen: Callable, aux, tables_iter):
        """Streaming request: a writer thread feeds input tables while
        this generator yields output tables as the worker produces them —
        one batch in flight per side, whatever the partition size."""
        import cloudpickle
        w = self.proc.stdin
        r = self.proc.stdout
        write_err: List[BaseException] = []

        def feed():
            try:
                for tb in tables_iter:
                    w.write(bytes([TAG_BLOB]))
                    _write_blob(w, _table_to_ipc(tb))
                    w.flush()
            except BaseException as ex:  # noqa: BLE001
                write_err.append(ex)
            # ALWAYS terminate the input stream — even when the upstream
            # iterator raised — or both sides would block forever waiting
            # for the next frame; the recorded error re-raises below
            try:
                w.write(bytes([TAG_END]))
                w.flush()
            except OSError as ex:
                if not write_err:
                    write_err.append(ex)

        try:
            w.write(MAGIC + bytes([OP_STREAM]))
            _write_blob(w, cloudpickle.dumps((task_gen, aux)))
            w.flush()
            feeder = threading.Thread(target=feed, daemon=True)
            # the feeder drives upstream execs on behalf of a borrow that
            # already holds a pool permit; mark it so nested borrows (a
            # stacked mapInPandas chain) skip the semaphore instead of
            # deadlocking against their own ancestor
            feeder._tpu_pool_nested = True
            feeder.start()
            while True:
                tag = _read_exact(r, 1)[0]
                if tag == TAG_END:
                    break
                yield _ipc_to_table(_read_blob(r))
            status = _read_exact(r, 1)[0]
            feeder.join(timeout=30)
            if status == ST_ERR:
                tb_str = cloudpickle.loads(_read_blob(r))
                raise PythonWorkerError(
                    f"python UDF raised in worker:\n{tb_str}")
            if write_err:
                raise write_err[0]
            self.requests_served += 1
        except (EOFError, BrokenPipeError, OSError) as ex:
            rc = self.proc.poll()
            self.kill()
            raise PythonWorkerCrash(
                f"python worker died mid-stream (rc={rc}): {ex}") from ex

    def kill(self):
        try:
            self.proc.kill()
            self.proc.wait(timeout=5)
        except Exception:
            pass


class PythonWorkerPool:
    """Reusable workers bounded by a semaphore
    (ref PythonWorkerSemaphore.scala; pooling plays daemon.py's
    fork-amortization role)."""

    _instance: Optional["PythonWorkerPool"] = None
    _lock = threading.Lock()

    def __init__(self, max_workers: int = 2):
        self.max_workers = max_workers
        self._sem = threading.BoundedSemaphore(max_workers)
        self._idle: List[PythonWorker] = []
        self._list_lock = threading.Lock()
        self._closed = False
        self.spawned = 0

    @classmethod
    def get(cls, max_workers: int = 2) -> "PythonWorkerPool":
        with cls._lock:
            if cls._instance is None or \
                    cls._instance.max_workers != max_workers:
                if cls._instance is not None:
                    cls._instance.shutdown()
                cls._instance = PythonWorkerPool(max_workers)
            return cls._instance

    def _checkout(self) -> PythonWorker:
        with self._list_lock:
            worker = self._idle.pop() if self._idle else None
        if worker is None or not worker.alive:
            worker = PythonWorker()
            self.spawned += 1
        return worker

    def _checkin(self, worker: PythonWorker):
        """Return a healthy worker; a closed pool reaps it instead (so a
        worker borrowed across a pool swap cannot leak as a zombie)."""
        with self._list_lock:
            if not self._closed and worker.alive:
                self._idle.append(worker)
                return
        worker.kill()

    def _acquire(self) -> bool:
        """Take a permit unless the current thread is a stream feeder
        already working on behalf of a held permit — a nested borrow
        blocking on its own ancestor would deadlock a single stacked
        query (permits bound CONCURRENT independent borrows; nesting
        depth is bounded by the plan height)."""
        if getattr(threading.current_thread(), "_tpu_pool_nested", False):
            return False
        self._sem.acquire()
        return True

    def run(self, task: Callable, aux, tables: Sequence[pa.Table]
            ) -> Tuple[List[pa.Table], object]:
        """Borrow a worker (blocking on the semaphore), run one request,
        return the worker to the pool if it survived.  A UDF exception
        (PythonWorkerError) leaves the worker in a clean protocol state —
        it is returned, not killed; only crashes cost a respawn."""
        held = self._acquire()
        worker = None
        try:
            worker = self._checkout()
            result = worker.request(task, aux, tables)
            self._checkin(worker)
            return result
        except PythonWorkerError:
            self._checkin(worker)
            raise
        except BaseException:
            if worker is not None and worker.alive:
                worker.kill()
            raise
        finally:
            if held:
                self._sem.release()

    def run_stream(self, task_gen: Callable, aux, tables_iter):
        """Streaming variant of run(); yields output tables lazily.  An
        abandoned generator (consumer stops early) kills the worker — the
        protocol is mid-stream and cannot be resynced."""
        held = self._acquire()
        worker = None
        try:
            worker = self._checkout()
            yield from worker.request_stream(task_gen, aux, tables_iter)
            self._checkin(worker)
        except PythonWorkerError:
            self._checkin(worker)
            raise
        except BaseException:
            if worker is not None and worker.alive:
                worker.kill()
            raise
        finally:
            if held:
                self._sem.release()

    def shutdown(self):
        with self._list_lock:
            self._closed = True
            workers, self._idle = self._idle, []
        for w in workers:
            try:
                w.proc.stdin.write(MAGIC + bytes([OP_SHUTDOWN]))
                w.proc.stdin.flush()
                w.proc.wait(timeout=2)
            except Exception:
                w.kill()


@atexit.register
def _shutdown_pool():
    if PythonWorkerPool._instance is not None:
        PythonWorkerPool._instance.shutdown()


def worker_path_usable(conf, *fns) -> bool:
    """Worker path is on and every fn survives cloudpickle (objects bound
    to unpicklable resources fall back in-process)."""
    from .. import config as cfg
    if not conf.get(cfg.PYTHON_WORKER_ENABLED):
        return False
    import cloudpickle
    try:
        for fn in fns:
            cloudpickle.dumps(fn)
        return True
    except Exception:
        return False


def pool_from_conf(conf) -> PythonWorkerPool:
    from .. import config as cfg
    return PythonWorkerPool.get(conf.get(cfg.CONCURRENT_PYTHON_WORKERS))


if __name__ == "__main__":
    worker_main()
