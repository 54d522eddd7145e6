"""Span tracing: public Chrome-trace emission for store operations.

Generalizes the private ``_TraceCollector`` that used to live in
``torchstore_tpu/logging.py`` into a public subsystem: set
``TORCHSTORE_TPU_TRACE=/path/trace.json`` and every ``span(...)`` — put/get
batches, per-volume fetches, transport transfers, resharding assembly,
weight-channel publishes — lands as a Chrome-trace complete event. The file
loads directly in Perfetto / chrome://tracing and aligns store phases with
jax profiler traces on one timeline.

Usage (sync context manager; works around ``await`` since it only brackets
wall time):

    from torchstore_tpu.observability import span

    with span("put_batch", keys=3, nbytes=total, transport="shm") as sp:
        ...
        sp.set(volume=vid)          # attrs may be added mid-span

Cost when disabled (no env var): one ``perf_counter`` call per span and an
attribute check — nothing is buffered.

One clock with the device trace: while tracing is enabled, a span opened in a
process that has ALREADY imported jax is also a
``jax.profiler.TraceAnnotation("ts/<name>")``, so under a running profiler
session it sits on the profiler's ``/host:`` plane beside ``XLA Ops``. This
module never imports jax itself: a chip belongs to one process, and the
volume/controller actors must stay jax-free — they simply get no annotation.

Events stream to disk in the JSON *array* format, appending every
``FLUSH_EVERY`` events — the format's closing ``]`` is optional, so the file
is loadable after a crash and memory stays bounded in long-running loops.
One file per process: the path is claimed with O_EXCL (volume actors and the
client all trace) and losers take a pid-suffixed name.
"""

from __future__ import annotations

import atexit
import glob as _glob
import json
import os
import re
import sys
import threading
import time
from typing import Optional

from torchstore_tpu.observability import context as trace_context
from torchstore_tpu.observability.metrics import _pid_alive

ENV_TRACE = "TORCHSTORE_TPU_TRACE"
# One id per RUN (process tree): minted by the first process to claim a
# trace file, inherited by every actor child through the TORCHSTORE_TPU_*
# env forwarding. Distinguishes "sibling of this run already exited" (its
# events must survive into the merge) from "leftover file of a FINISHED
# run" (must be cleared, or a reused output directory merges dead spans).
ENV_TRACE_RUN = "TORCHSTORE_TPU_TRACE_RUN"
# Store spans on the profiler's host plane carry this prefix.
ANNOTATION_PREFIX = "ts/"


def _current_run_id() -> str:
    rid = os.environ.get(ENV_TRACE_RUN)
    if not rid:
        rid = f"{os.getpid()}.{trace_context.new_id()}"
        os.environ[ENV_TRACE_RUN] = rid
    return rid


# spawn_actors calls this BEFORE forwarding env to children, so the whole
# process tree shares one run id (a child minting its own would mistake an
# exited sibling's file for a dead run's and truncate it).
ensure_run_id = _current_run_id


def process_label() -> str:
    """Human-readable track label for this process in a merged trace.
    Actor children are named ``ts-<actor>-<rank>`` by spawn_actors; the
    initiating process shows up as its script (or ``MainProcess``)."""
    import multiprocessing as mp
    import sys

    name = mp.current_process().name
    if name in ("MainProcess", None, ""):
        argv0 = os.path.basename(sys.argv[0] or "") or "python"
        name = argv0
    return f"{name}[{os.getpid()}]"


class TraceCollector:
    """Process-global Chrome-trace event buffer (enabled by env var)."""

    FLUSH_EVERY = 1000

    def __init__(self) -> None:
        self.path = os.environ.get(ENV_TRACE)
        # Buffered events stay plain tuples (name, start_s, dur_s, args,
        # tid) until a flush turns them into Chrome-trace objects: a span's
        # exit pays one append, and the chunk is encoded in one go.
        self.events: list[tuple] = []
        self._lock = threading.Lock()
        self._registered = False
        self._resolved_path: Optional[str] = None
        self._resolved_for: Optional[str] = None
        self._wrote_header = False

    @property
    def enabled(self) -> bool:
        return bool(self.path)

    def add_event(
        self,
        name: str,
        start_s: float,
        dur_s: float,
        args: Optional[dict] = None,
    ) -> None:
        """Record one complete ('X') event. ``args`` ride into the trace's
        ``args`` pane; a ``bytes`` entry gets a derived GBps alongside."""
        if not self.path:
            return
        event = (name, start_s, dur_s, args, threading.get_ident() & 0xFFFF)
        with self._lock:
            self.events.append(event)
            if not self._registered:
                self._registered = True
                atexit.register(self.flush)
            if len(self.events) >= self.FLUSH_EVERY:
                self._flush_locked()

    def add(
        self,
        name: str,
        phase: str,
        start_s: float,
        dur_s: float,
        nbytes: Optional[int],
    ) -> None:
        """LatencyTracker-shaped entry point (``{name}/{phase}`` naming) —
        kept so the tracker's phases land in the same trace as spans."""
        args = {"bytes": nbytes} if nbytes is not None else None
        self.add_event(f"{name}/{phase}", start_s, dur_s, args)

    def _resolve_path(self) -> str:
        # Claim the base path through a ``<base>.owner`` sidecar recording
        # the claimant's pid (same arbitration as the metrics dumper): a
        # LIVE concurrent process owning it sends us to a pid-suffixed
        # sibling, but a leftover file from a FINISHED run is taken over and
        # truncated — output directories are reused across runs, and a stale
        # base full of dead spans must not pollute the next merge. The pid
        # path is always truncated on claim: any existing content is ours
        # from a previous resolution or a recycled pid's dead run, and
        # appending to it would emit a second '[' header (corrupt JSON).
        if self._resolved_path is None or self._resolved_for != self.path:
            base = self.path
            root, ext = os.path.splitext(base)
            pid_path = f"{root}.{os.getpid()}{ext or '.json'}"
            self._resolved_path = self._claim(base, pid_path)
            self._resolved_for = self.path
            self._wrote_header = False
        return self._resolved_path

    @staticmethod
    def _claim(base: str, pid_path: str) -> str:
        def truncate(path: str) -> None:
            os.close(os.open(path, os.O_CREAT | os.O_TRUNC | os.O_WRONLY, 0o644))

        # The whole decide-and-claim sequence runs under an exclusive flock
        # on the owner sidecar: two processes racing a stale claim must
        # never BOTH conclude "dead owner, mine" — each would truncate the
        # other's header mid-append and corrupt the base file. flock is
        # released by the kernel even on SIGKILL, so a crashed claimant
        # can't wedge the path.
        import fcntl

        pid = os.getpid()
        run_id = _current_run_id()
        payload = f"{pid}\n{run_id}"
        try:
            fd = os.open(f"{base}.owner", os.O_CREAT | os.O_RDWR, 0o644)
        except OSError:
            try:
                truncate(pid_path)
            except OSError:
                pass
            return pid_path
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            lines = os.read(fd, 256).decode(errors="replace").splitlines()
            try:
                owner = int((lines[0] if lines else "").strip() or 0)
            except ValueError:
                owner = 0
            owner_run = lines[1].strip() if len(lines) > 1 else ""
            if owner and owner_run == run_id and owner != pid:
                # A sibling process of THIS run owns the base — alive, or
                # already exited with its events in the file. Either way
                # those events belong in the merge: take a pid path.
                claim_base = False
            elif owner and owner_run != run_id and _pid_alive(owner):
                claim_base = False  # live owner from another run
            else:
                # Unclaimed, our own re-claim, or a FINISHED run's leftover:
                # take the base and clear any dead run's file set so stale
                # spans can't pollute this run's merge.
                claim_base = True
            if claim_base:
                os.ftruncate(fd, 0)
                os.lseek(fd, 0, os.SEEK_SET)
                os.write(fd, payload.encode())
                truncate(base)
                if owner and owner != pid and owner_run != run_id:
                    for stale in trace_files(base):
                        if stale != base:
                            try:
                                os.unlink(stale)
                            except OSError:
                                pass
                return base
        except OSError:
            pass
        finally:
            os.close(fd)  # releases the flock
        try:
            truncate(pid_path)
        except OSError:
            pass
        return pid_path

    @staticmethod
    def _chrome_event(event: tuple, pid: int) -> dict:
        name, start_s, dur_s, args, tid = event
        out = {
            "name": name,
            "cat": "torchstore",
            "ph": "X",
            "ts": start_s * 1e6,
            "dur": dur_s * 1e6,
            "pid": pid,
            "tid": tid,
        }
        if args:
            args = dict(args)
            nbytes = args.get("bytes")
            if isinstance(nbytes, (int, float)) and "GBps" not in args:
                args["GBps"] = (
                    round(nbytes / dur_s / 1e9, 3) if dur_s > 0 else None
                )
            out["args"] = args
        return out

    def _flush_locked(self) -> None:
        if not self.path or not self.events:
            return
        pid = os.getpid()
        chunk = [self._chrome_event(event, pid) for event in self.events]
        self.events = []
        try:
            path = self._resolve_path()
            if not self._wrote_header:
                # First write into this file: lead with a process_name
                # metadata event so a merged multi-process trace shows
                # labeled tracks (client / controller / volume_N) instead
                # of bare pids.
                chunk.insert(
                    0,
                    {
                        "name": "process_name",
                        "ph": "M",
                        "pid": pid,
                        "args": {"name": process_label()},
                    },
                )
            # ONE json.dumps for the chunk (the one-shot C encoder; a call
            # per event costs three times the encoding), minus the list's
            # own brackets; one write.
            text = json.dumps(chunk)[1:-1]
            with open(path, "a") as f:
                f.write(("[\n" if not self._wrote_header else ",\n") + text)
            self._wrote_header = True
        except OSError:
            pass

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def reinit_after_fork(self) -> None:
        """Re-arm in a freshly forked actor child. The forkserver imports
        this module at ITS start (preload), so children inherit a collector
        whose ``path`` snapshot predates the spawner's env — e.g. disabled
        even though TORCHSTORE_TPU_TRACE is set in the child's corrected
        env. Re-read the env and drop any inherited buffer/claim state so
        this process claims its own file."""
        with self._lock:
            self.path = os.environ.get(ENV_TRACE)
            self.events = []
            self._resolved_path = None
            self._resolved_for = None
            self._wrote_header = False


_collector = TraceCollector()


def collector() -> TraceCollector:
    return _collector


def trace_enabled() -> bool:
    return _collector.enabled


def flush_trace() -> None:
    _collector.flush()


class span:
    """Context manager recording one named span with attributes.

    Attrs are arbitrary small values (key, nbytes, transport, volume, shard
    coords); ``bytes``/``nbytes`` get a derived GBps in the trace. Nesting
    works naturally — Chrome's 'X' events on one tid stack by containment.

    When tracing is enabled each span also mints a ``span_id``, records the
    active ``trace_id``/``parent_id`` (see observability/context.py), and
    becomes the parent of anything opened — or any RPC issued — inside it,
    so per-process files merge into one cross-process tree.
    """

    __slots__ = ("name", "attrs", "_t0", "_span_id", "_token", "_annotation")

    def __init__(self, name: str, **attrs) -> None:
        self.name = name
        self.attrs = attrs
        self._t0 = 0.0
        self._span_id = None
        self._token = None
        self._annotation = None

    def set(self, **attrs) -> "span":
        self.attrs.update(attrs)
        return self

    @property
    def elapsed(self) -> float:
        """Seconds since the span was entered: read right after its block,
        the block's duration (enabled or not)."""
        return time.perf_counter() - self._t0

    def __enter__(self) -> "span":
        self._t0 = time.perf_counter()
        if _collector.enabled:
            self._span_id = trace_context.new_id()
            self._token = trace_context.push_span(self._span_id)
            profiler = sys.modules.get("jax.profiler")
            if profiler is not None:
                # A no-op without a profiler session; with one, the span is
                # an event on the device trace's clock.
                self._annotation = profiler.TraceAnnotation(
                    ANNOTATION_PREFIX + self.name
                )
                self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        parent = None
        if self._token is not None:
            parent = trace_context.token_parent(self._token)
            trace_context.pop_span(self._token)
            self._token = None
        if not _collector.enabled:
            return
        dur = time.perf_counter() - self._t0
        args = {
            k: (v if isinstance(v, (int, float, bool, type(None))) else str(v))
            for k, v in self.attrs.items()
        }
        if "nbytes" in args and "bytes" not in args:
            args["bytes"] = args.pop("nbytes")
        if exc_type is not None:
            args["error"] = exc_type.__name__
        tid = trace_context.trace_id()
        if tid is not None:
            args["trace_id"] = tid
        if self._span_id is not None:
            args["span_id"] = self._span_id
        if parent is not None:
            args["parent_id"] = parent
        _collector.add_event(self.name, self._t0, dur, args or None)


# --------------------------------------------------------------------------
# cross-process trace merging
# --------------------------------------------------------------------------


def load_trace_events(path: str) -> list[dict]:
    """Events from one per-process trace file. The streaming writer leaves
    the closing ``]`` off (crash-safe JSON-array format) — repair it here."""
    try:
        with open(path) as f:
            content = f.read().strip()
    except OSError:
        return []
    if not content:
        return []
    if not content.endswith("]"):
        content += "\n]"
    try:
        events = json.loads(content)
    except ValueError:
        return []
    return [e for e in events if isinstance(e, dict)]


def trace_files(base: str) -> list[str]:
    """The per-process trace files belonging to one configured base path:
    the base itself (claimed by whichever process flushed first) plus every
    pid-suffixed sibling (``<root>.<pid><ext>``). Merged outputs and other
    non-numeric siblings are excluded."""
    root, ext = os.path.splitext(base)
    ext = ext or ".json"
    pid_re = re.compile(re.escape(root) + r"\.(\d+)" + re.escape(ext) + r"$")
    out = []
    if os.path.exists(base):
        out.append(base)
    for cand in sorted(_glob.glob(f"{root}.*{ext}")):
        if pid_re.match(cand):
            out.append(cand)
    return out


def merge_traces(paths: list[str], out_path: str) -> dict:
    """Merge per-process trace files into one Perfetto-loadable timeline.

    Events keep their originating pid (one track per process, labeled by
    each file's ``process_name`` metadata event) and are ordered by
    timestamp; the shared ``trace_id`` args stitch one logical operation
    across tracks. Returns ``{"path", "files", "events", "trace_ids"}``."""
    events: list[dict] = []
    for path in paths:
        events.extend(load_trace_events(path))
    meta = [e for e in events if e.get("ph") == "M"]
    rest = [e for e in events if e.get("ph") != "M"]
    rest.sort(key=lambda e: e.get("ts", 0))
    merged = meta + rest
    tmp = f"{out_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(merged, f)
    os.replace(tmp, out_path)
    trace_ids = {
        e["args"]["trace_id"]
        for e in rest
        if isinstance(e.get("args"), dict) and "trace_id" in e["args"]
    }
    return {
        "path": out_path,
        "files": list(paths),
        "events": len(rest),
        "trace_ids": sorted(trace_ids),
    }


def collect_trace(out_path: Optional[str] = None) -> Optional[dict]:
    """Flush this process's collector and merge every sibling process's
    trace file (same configured base path) into one timeline. Returns the
    merge summary dict, or None when tracing is disabled. Call after the
    store is shut down so actor processes have flushed their atexit dumps;
    default output is ``<root>.merged<ext>``."""
    base = _collector.path or os.environ.get(ENV_TRACE)
    if not base:
        return None
    _collector.flush()
    files = trace_files(base)
    if not files:
        return None
    if out_path is None:
        root, ext = os.path.splitext(base)
        out_path = f"{root}.merged{ext or '.json'}"
    return merge_traces(files, out_path)
