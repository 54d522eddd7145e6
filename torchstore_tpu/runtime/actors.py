"""Process-based actor runtime: the TPU build's Monarch replacement.

The reference runs every component inside Monarch actors (Rust hyperactor:
process spawning, typed async endpoints, actor meshes — SURVEY §2.3 row 1;
/root/reference/torchstore/utils.py:128-139). This module provides the same
contract natively: ``spawn_actors`` forks N OS processes each hosting an
``Actor`` with ``@endpoint`` methods served over an asyncio TCP server;
``ActorRef``/``ActorMesh`` are picklable handles whose ``.method.call()`` /
``.call_one()`` perform multiplexed RPC with zero-copy tensor framing
(see ``serialization.py``). Works intra-host today and across DCN hosts by
binding non-loopback (``TORCHSTORE_TPU_BIND_HOST``).
"""

from __future__ import annotations

import asyncio
import multiprocessing as mp
import os
import socket
import traceback
from typing import Any, Callable, Optional

from torchstore_tpu.logging import get_logger
from torchstore_tpu.observability import context as trace_context
from torchstore_tpu.utils import spawn_logged
from torchstore_tpu.observability.tracing import span
from torchstore_tpu.runtime.serialization import (
    KIND_CONTROL,
    KIND_ERROR,
    KIND_REQUEST,
    KIND_RESPONSE,
    read_message,
    write_message,
)

logger = get_logger("torchstore_tpu.runtime")

_ENDPOINT_ATTR = "_torchstore_tpu_endpoint"

SPAWN_TIMEOUT_S = 120.0
STOP_TIMEOUT_S = 10.0


def endpoint(fn: Callable) -> Callable:
    """Mark a method remotely callable (Monarch ``@endpoint`` analog)."""
    setattr(fn, _ENDPOINT_ATTR, True)
    return fn


class Actor:
    """Base class for actors. Subclasses define ``@endpoint`` methods; each
    instance lives in its own process (one actor per proc, like the
    reference's volume/controller actors)."""


class RemoteActorError(RuntimeError):
    """Raised client-side when the remote endpoint raised; carries the remote
    traceback. The original exception is re-raised when it round-trips pickle,
    with this error attached as ``__cause__``."""


class ActorDiedError(RuntimeError):
    pass


class ActorTimeoutError(ActorDiedError):
    """An RPC exceeded its deadline: the actor is alive-but-unresponsive
    (wedged) or the transfer outlasted the configured timeout. Subclasses
    ActorDiedError so existing died-handling paths also cover wedged actors
    (the supervision role Monarch plays for the reference, SURVEY §2.3)."""


# --------------------------------------------------------------------------
# Client side: connections + refs
# --------------------------------------------------------------------------


class _Connection:
    def __init__(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        self.reader = reader
        self.writer = writer
        self.write_lock = asyncio.Lock()
        self.pending: dict[int, asyncio.Future] = {}
        self.next_id = 0
        self.closed = False
        self._reader_task = asyncio.ensure_future(self._read_loop())

    async def _read_loop(self) -> None:
        try:
            while True:
                kind, msg = await read_message(self.reader)
                fut = self.pending.pop(msg["id"], None)
                if fut is None or fut.done():
                    continue
                if kind == KIND_RESPONSE:
                    fut.set_result(msg["value"])
                elif kind == KIND_ERROR:
                    fut.set_exception(_rebuild_remote_error(msg))
                else:
                    fut.set_exception(RemoteActorError(f"unexpected frame kind {kind}"))
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as exc:
            self._fail_all(ActorDiedError(f"actor connection lost: {exc!r}"))
        except asyncio.CancelledError:
            self._fail_all(ActorDiedError("connection closed"))
            raise
        except Exception as exc:  # pragma: no cover - defensive
            self._fail_all(RemoteActorError(f"connection reader failed: {exc!r}"))

    def _fail_all(self, exc: Exception) -> None:
        self.closed = True
        for fut in self.pending.values():
            if not fut.done():
                fut.set_exception(exc)
        self.pending.clear()

    async def request(
        self, kind: int, body: dict, timeout: Optional[float] = None
    ) -> Any:
        if self.closed:
            raise ActorDiedError("connection already closed")
        req_id = self.next_id
        self.next_id += 1
        body = dict(body, id=req_id)
        # Distributed tracing: the caller's trace context rides the frame so
        # server-side spans stitch into the same trace (client put ->
        # controller notify -> volume put share one trace_id). ~Free when no
        # trace is active (one contextvar read).
        ctx = trace_context.current()
        if ctx is not None:
            body["trace"] = ctx
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self.pending[req_id] = fut
        try:
            return await self._request_inner(fut, req_id, kind, body, timeout)
        except BaseException:
            # The awaiter is gone (cancelled mid-RPC, or the write itself
            # failed): drop the pending slot and mark any late-set
            # exception retrieved — otherwise a dying volume's _fail_all
            # sprays "exception was never retrieved" ActorDiedErrors into
            # whatever event loop hosts this connection.
            self.pending.pop(req_id, None)
            if fut.done() and not fut.cancelled():
                fut.exception()
            else:
                fut.cancel()
            raise

    async def _request_inner(
        self, fut: asyncio.Future, req_id: int, kind: int, body: dict, timeout
    ) -> Any:
        async with self.write_lock:
            await write_message(self.writer, kind, body)
        if timeout is None or timeout <= 0:
            return await fut
        try:
            return await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError:
            # asyncio.TimeoutError IS builtin TimeoutError (3.11+), so this
            # clause also catches a REMOTE endpoint's TimeoutError arriving
            # through the future (e.g. wait_for_committed expiry). A done,
            # uncancelled future means the response arrived — propagate the
            # remote exception; only a cancelled future is a local deadline.
            if fut.done() and not fut.cancelled():
                raise
            # A late response finds no pending future and is dropped; the
            # connection itself stays usable (requests are multiplexed).
            self.pending.pop(req_id, None)
            raise ActorTimeoutError(
                f"RPC {body.get('method', body.get('op'))!r} to "
                f"{body.get('actor')!r} timed out after {timeout:.0f}s "
                "(actor wedged, or transfer larger than the timeout allows)"
            ) from None

    async def close(self) -> None:
        self.closed = True
        self._reader_task.cancel()
        try:
            self.writer.close()
            await self.writer.wait_closed()
        except Exception:
            pass


def _rebuild_remote_error(msg: dict) -> Exception:
    remote = RemoteActorError(
        f"remote endpoint raised:\n{msg.get('traceback', '<no traceback>')}"
    )
    exc = msg.get("exception")
    if isinstance(exc, BaseException):
        exc.__cause__ = remote
        return exc
    return remote


# Actors HOSTED IN THIS PROCESS, keyed exactly as their published ActorRefs
# (host, port, name): endpoint calls on such refs bypass the RPC stack
# entirely — direct async method invocation, zero serialization (the
# colocated-volume fast path; remote processes still reach the same actor
# over its real server).
# Safe across forkserver: only the process that HOSTS an actor registers it
# here, and children never inherit a hosting role (each child registers its
# own actor in _child_async).
_inproc_actors: dict[tuple[str, int, str], Actor] = {}  # tslint: disable=fork-safety


def register_inproc(host: str, port: int, name: str, actor: Actor) -> None:
    _inproc_actors[(host, port, name)] = actor


def unregister_inproc(host: str, port: int, name: str) -> None:
    _inproc_actors.pop((host, port, name), None)


# Pools are per (event loop, address): tests run many asyncio.run loops;
# entries of closed loops are pruned so they never accumulate. Children
# fork from the forkserver HELPER, which imports this module but never
# opens a connection — the inherited pool is always empty.
_conn_pools: dict[  # tslint: disable=fork-safety
    tuple[int, str, int], tuple[asyncio.AbstractEventLoop, _Connection]
] = {}


async def get_connection(host: str, port: int) -> _Connection:
    loop = asyncio.get_running_loop()
    # Prune entries whose loop is closed. writer.close() would no-op on a
    # dead loop (transport.close() needs call_soon), and asyncio's
    # TransportSocket forbids close(); shutdown() is allowed and tears the
    # TCP connection down immediately (the server reaps its handler) — the
    # local fd itself is freed when GC collects the orphaned transport.
    for k, (pool_loop, conn) in list(_conn_pools.items()):
        if pool_loop.is_closed():
            conn.closed = True
            sock = conn.writer.get_extra_info("socket")
            if sock is not None:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
            _conn_pools.pop(k, None)
    key = (id(loop), host, port)
    entry = _conn_pools.get(key)
    if entry is not None:
        _, conn = entry
        if not conn.closed:
            return conn
    reader, writer = await asyncio.open_connection(host, port, limit=2**20)
    _set_sock_opts(writer)
    from torchstore_tpu.runtime.auth import client_authenticate

    await client_authenticate(reader, writer)
    conn = _Connection(reader, writer)
    _conn_pools[key] = (loop, conn)
    return conn


def _set_sock_opts(writer: asyncio.StreamWriter) -> None:
    sock = writer.get_extra_info("socket")
    if sock is not None:
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass


class ActorEndpointRef:
    def __init__(
        self, ref: "ActorRef", method: str, timeout: Optional[float] = None
    ):
        self._ref = ref
        self._method = method
        self._timeout = timeout

    def with_timeout(self, timeout: Optional[float]) -> "ActorEndpointRef":
        """Copy with an explicit deadline override (<=0 disables). Used for
        size-scaled data-plane timeouts; control RPCs use the ref/config
        default."""
        return ActorEndpointRef(self._ref, self._method, timeout)

    def _effective_timeout(self) -> Optional[float]:
        if self._timeout is not None:
            return self._timeout
        # isinstance guard: a ref unpickled from an older build lacks the
        # attribute and __getattr__ would hand back an endpoint ref instead.
        ref_timeout = self._ref.__dict__.get("rpc_timeout")
        if isinstance(ref_timeout, (int, float)):
            return ref_timeout
        from torchstore_tpu.config import default_config

        return default_config().rpc_timeout

    async def call_one(self, *args, **kwargs) -> Any:
        inproc = _inproc_actors.get(
            (self._ref.host, self._ref.port, self._ref.name)
        )
        if inproc is not None:
            # Same-process actor: direct invocation, no serialization. Note
            # that arguments pass BY REFERENCE — transports relying on this
            # path must copy data they store (the SHM transport does: puts
            # land in segments, never keep caller arrays).
            return await getattr(inproc, self._method)(*args, **kwargs)
        try:
            conn = await get_connection(self._ref.host, self._ref.port)
        except OSError as exc:
            raise ActorDiedError(
                f"cannot connect to actor {self._ref.name!r} at "
                f"{self._ref.host}:{self._ref.port}: {exc!r}"
            ) from exc
        return await conn.request(
            KIND_REQUEST,
            {
                "actor": self._ref.name,
                "method": self._method,
                "args": args,
                "kwargs": kwargs,
            },
            timeout=self._effective_timeout(),
        )

    # On a single ref, call == call_one (parity with Monarch's call on a
    # singleton mesh which returns a one-element result set).
    async def call(self, *args, **kwargs) -> Any:
        return await self.call_one(*args, **kwargs)


class ActorRef:
    """Picklable handle to one actor process."""

    def __init__(self, name: str, host: str, port: int, rank: int = 0):
        self.name = name
        self.host = host
        self.port = port
        self.rank = rank
        # Per-ref RPC deadline override; None defers to config.rpc_timeout.
        # Clients stamp this from their StoreConfig (see LocalClient).
        self.rpc_timeout: Optional[float] = None

    def __getattr__(self, method: str) -> ActorEndpointRef:
        if method.startswith("_"):
            raise AttributeError(method)
        return ActorEndpointRef(self, method)

    def __repr__(self) -> str:
        return f"ActorRef({self.name!r}@{self.host}:{self.port})"

    async def _control(self, op: str) -> Any:
        conn = await get_connection(self.host, self.port)
        return await conn.request(KIND_CONTROL, {"op": op, "actor": self.name})

    async def ping(self) -> bool:
        return await self._control("ping") == "pong"


class MeshEndpointRef:
    def __init__(self, mesh: "ActorMeshRef", method: str):
        self._mesh = mesh
        self._method = method

    async def call(self, *args, **kwargs) -> list[Any]:
        """Fan out to every actor in the mesh; gather results in rank order."""
        return list(
            await asyncio.gather(
                *(
                    getattr(ref, self._method).call_one(*args, **kwargs)
                    for ref in self._mesh.refs
                )
            )
        )

    async def call_one(self, *args, **kwargs) -> Any:
        if len(self._mesh.refs) != 1:
            raise ValueError(
                f"call_one on a mesh of size {len(self._mesh.refs)}; "
                "index the mesh first"
            )
        return await getattr(self._mesh.refs[0], self._method).call_one(
            *args, **kwargs
        )


class ActorMeshRef:
    """Picklable handle to a mesh of actors (rank-ordered)."""

    def __init__(self, refs: list[ActorRef]):
        self.refs = refs

    def __getattr__(self, method: str) -> MeshEndpointRef:
        if method.startswith("_") or method == "refs":
            raise AttributeError(method)
        return MeshEndpointRef(self, method)

    def __getitem__(self, idx) -> "ActorMeshRef":
        if isinstance(idx, int):
            return ActorMeshRef([self.refs[idx]])
        return ActorMeshRef(list(self.refs[idx]))

    def __len__(self) -> int:
        return len(self.refs)


class ActorMesh(ActorMeshRef):
    """Owner-side mesh: also holds the OS process handles for shutdown."""

    def __init__(self, refs: list[ActorRef], processes: list[mp.Process]):
        super().__init__(refs)
        self._processes = processes

    def __getstate__(self):
        return {"refs": self.refs}

    def __setstate__(self, state):
        self.refs = state["refs"]
        self._processes = []

    async def stop(self) -> None:
        for ref in self.refs:
            try:
                await asyncio.wait_for(ref._control("stop"), timeout=STOP_TIMEOUT_S)
            except Exception:
                pass
        loop = asyncio.get_running_loop()
        for proc in self._processes:
            await loop.run_in_executor(None, proc.join, STOP_TIMEOUT_S)
            if proc.is_alive():
                logger.warning("terminating unresponsive actor process %s", proc.pid)
                proc.terminate()
                await loop.run_in_executor(None, proc.join, 5.0)
        self._processes = []


# --------------------------------------------------------------------------
# Server side
# --------------------------------------------------------------------------


class ActorServer:
    def __init__(self) -> None:
        self.actors: dict[str, Actor] = {}
        self.stop_event = asyncio.Event()
        self._server: Optional[asyncio.AbstractServer] = None
        self.port: Optional[int] = None
        self._client_writers: set[asyncio.StreamWriter] = set()

    def register(self, name: str, actor: Actor) -> None:
        self.actors[name] = actor

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        self._server = await asyncio.start_server(
            self._handle_client, host, port, limit=2**20
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.port

    async def _handle_client(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        from torchstore_tpu.runtime.auth import server_authenticate

        # No frame is parsed (= nothing unpickled) before the peer proves
        # knowledge of the shared secret.
        if not await server_authenticate(reader, writer):
            try:
                writer.close()
            except Exception:
                pass
            return
        _set_sock_opts(writer)
        self._client_writers.add(writer)
        write_lock = asyncio.Lock()
        tasks: set[asyncio.Task] = set()
        try:
            while True:
                kind, msg = await read_message(reader)
                # _dispatch reports endpoint errors to the caller itself;
                # spawn_logged is the belt-and-braces for a failure in that
                # reporting path (and retains the task until done).
                spawn_logged(
                    self._dispatch(kind, msg, writer, write_lock),
                    name="actor.dispatch",
                    tasks=tasks,
                    log=logger,
                )
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            self._client_writers.discard(writer)
            for task in tasks:
                task.cancel()
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    async def _dispatch(
        self,
        kind: int,
        msg: dict,
        writer: asyncio.StreamWriter,
        write_lock: asyncio.Lock,
    ) -> None:
        req_id = msg.get("id")
        try:
            if kind == KIND_CONTROL:
                value = await self._handle_control(msg)
            elif kind == KIND_REQUEST:
                value = await self._handle_request(msg)
            else:
                raise RemoteActorError(f"unknown frame kind {kind}")
            async with write_lock:
                await write_message(writer, KIND_RESPONSE, {"id": req_id, "value": value})
        except asyncio.CancelledError:
            raise
        except BaseException as exc:  # noqa: BLE001 - forwarded to caller
            tb = traceback.format_exc()
            payload: dict[str, Any] = {"id": req_id, "traceback": tb}
            try:
                import pickle

                pickle.dumps(exc)
                payload["exception"] = exc
            except Exception:
                payload["exception"] = None
            try:
                async with write_lock:
                    await write_message(writer, KIND_ERROR, payload)
            except Exception:
                logger.exception("failed to report endpoint error to caller")

    async def _handle_control(self, msg: dict) -> Any:
        op = msg["op"]
        if op == "ping":
            # Faultpoint: arming "actor.ping" in a process makes ITS
            # heartbeat responses raise/stall — the handle the health
            # supervisor's quarantine tests use to simulate a wedged-but-
            # alive volume without blocking its event loop.
            from torchstore_tpu import faults

            await faults.afire("actor.ping")
            return "pong"
        if op == "stop":
            # Respond first; the serve loop exits after this dispatch returns.
            asyncio.get_running_loop().call_soon(self.stop_event.set)
            return "stopping"
        if op == "list":
            return sorted(self.actors)
        raise RemoteActorError(f"unknown control op {op!r}")

    async def _handle_request(self, msg: dict) -> Any:
        actor = self.actors.get(msg["actor"])
        if actor is None:
            raise RemoteActorError(
                f"no actor {msg['actor']!r} in this process "
                f"(have: {sorted(self.actors)})"
            )
        method = getattr(type(actor), msg["method"], None)
        if method is None or not getattr(method, _ENDPOINT_ATTR, False):
            raise RemoteActorError(
                f"{type(actor).__name__}.{msg['method']} is not an @endpoint"
            )
        # Adopt the caller's trace context (if any) for the whole dispatch:
        # the rpc span and everything the endpoint emits (transport spans,
        # nested RPCs to other actors) carry the client's trace_id and hang
        # off the client-side span that issued this request.
        with trace_context.activate(msg.get("trace")):
            with span(f"rpc/{msg['method']}", actor=msg["actor"]):
                result = method(actor, *msg["args"], **msg["kwargs"])
                if asyncio.iscoroutine(result):
                    result = await result
        return result

    async def serve_until_stopped(self) -> None:
        await self.stop_event.wait()
        await self.close()

    async def close(self) -> None:
        if self._server is not None:
            self._server.close()
        # Drop live client connections: py3.12's Server.wait_closed() waits
        # for handlers, which would otherwise block forever on open streams.
        for writer in list(self._client_writers):
            try:
                writer.close()
            except Exception:
                pass
        if self._server is not None:
            try:
                await asyncio.wait_for(self._server.wait_closed(), timeout=2.0)
            except asyncio.TimeoutError:
                pass


# --------------------------------------------------------------------------
# Spawning
# --------------------------------------------------------------------------


def _child_main(pipe, actor_cls, name: str, args: tuple, kwargs: dict, env: dict) -> None:
    # ``env`` is the COMPLETE framework environment for this child. The
    # forkserver parent snapshots os.environ at ITS start, so children can
    # inherit stale TORCHSTORE_TPU_* values from whatever test/store first
    # spawned an actor (e.g. an auth secret that was since unset) — remove
    # anything the spawner did not explicitly pass, then apply.
    for key in list(os.environ):
        if key.startswith("TORCHSTORE_TPU_") and key not in env:
            del os.environ[key]
    os.environ.update(env)
    from torchstore_tpu import config as _config_mod

    _config_mod._default_config = None  # re-seed from the corrected env
    # Re-arm env-gated observability against the CORRECTED env: the
    # forkserver's preload imported torchstore with whatever env IT started
    # under, and its dumper/exporter threads did not survive the fork.
    from torchstore_tpu import observability as _obs

    _obs.reinit_after_fork()
    # Landing-copy pool threads do not survive the fork either; drop the
    # inherited (dead) executor so the first landing re-creates a live one.
    from torchstore_tpu.transport import landing as _landing

    _landing.reinit_after_fork()
    # Re-arm faultpoints from the CORRECTED env: the forkserver's module
    # state carries whatever TORCHSTORE_TPU_FAULTPOINTS it imported under,
    # not what this child was spawned with.
    from torchstore_tpu import faults as _faults

    _faults.reinit_after_fork()
    # Re-read the bulk transport's emulated-bandwidth knob (bench/test DCN
    # emulation) from the corrected env for the same reason.
    from torchstore_tpu.transport import bulk as _bulk

    _bulk.reinit_after_fork()
    try:
        asyncio.run(_child_async(pipe, actor_cls, name, args, kwargs))
    except KeyboardInterrupt:
        pass
    finally:
        # Multiprocessing children exit via os._exit, which skips atexit —
        # the trace collector's and metrics dumper's exit hooks would never
        # fire in actor processes. Flush both explicitly so a volume's
        # spans/counters survive a clean stop (crash paths still lose at
        # most the last partial buffer; the streaming trace format and
        # periodic dumps keep earlier data loadable).
        try:
            from torchstore_tpu.observability import metrics as _obs_metrics
            from torchstore_tpu.observability.tracing import flush_trace

            flush_trace()
            _obs_metrics.dump_metrics()
        except Exception:
            pass


async def _child_async(pipe, actor_cls, name: str, args: tuple, kwargs: dict) -> None:
    server = ActorServer()
    try:
        actor = actor_cls(*args, **kwargs)
        server.register(name, actor)
        bind_host = os.environ.get("TORCHSTORE_TPU_BIND_HOST", "127.0.0.1")
        port = await server.start(bind_host)
        # Refs must carry a REACHABLE address: a 0.0.0.0 bind (multi-host
        # DCN) advertises the real hostname/IP instead.
        advertise = os.environ.get("TORCHSTORE_TPU_ADVERTISE_HOST")
        if advertise is None:
            advertise = (
                socket.gethostname() if bind_host in ("0.0.0.0", "::") else bind_host
            )
        pipe.send(("ready", advertise, port))
    except BaseException:
        pipe.send(("error", traceback.format_exc(), None))
        raise
    finally:
        pipe.close()
    await server.serve_until_stopped()


_ctx: Optional[mp.context.BaseContext] = None


def _mp_context() -> mp.context.BaseContext:
    # 'forkserver' keeps children clear of any jax/TPU state in the parent
    # (the fork server is a fresh process, never the jax-holding parent) while
    # amortizing interpreter+numpy startup (~2.5s on this image) across all
    # actor spawns. 'spawn' remains available via TORCHSTORE_TPU_MP_CONTEXT.
    global _ctx
    if _ctx is None:
        method = os.environ.get("TORCHSTORE_TPU_MP_CONTEXT", "forkserver")
        _ctx = mp.get_context(method)
        if method == "forkserver":
            _ctx.set_forkserver_preload(["torchstore_tpu.runtime"])
            # Launch the forkserver NOW with env-gated observability
            # stripped: the preload imports torchstore_tpu in the helper
            # process, which would otherwise start its own metrics dumper /
            # HTTP exporter for an idle registry — and could win the claim
            # on the configured dump path or port. Actor children re-arm
            # from their corrected env in _child_main (reinit_after_fork).
            from torchstore_tpu.observability import (
                ENV_METRICS_DUMP,
                ENV_METRICS_PORT,
                ENV_TRACE,
            )

            saved = {}
            for key in (ENV_METRICS_DUMP, ENV_METRICS_PORT, ENV_TRACE):
                if key in os.environ:
                    saved[key] = os.environ.pop(key)
            try:
                from multiprocessing import forkserver as _forkserver

                _forkserver.ensure_running()
            except Exception:  # noqa: BLE001 - lazy start on first spawn
                pass
            finally:
                os.environ.update(saved)
    return _ctx


def stop_spawn_helpers() -> None:
    """Stop multiprocessing's fork server and resource tracker and wait for
    both. They outlive every ``ActorMesh.stop()`` on purpose (the next spawn
    reuses the warm fork server) and otherwise exit only AFTER this process
    has: a program that must leave no process behind when it ends calls this
    once its last store is shut down. The next spawn starts them again."""
    global _ctx
    from multiprocessing import forkserver, resource_tracker

    # The fork server holds the tracker's pipe open: it goes first.
    forkserver._forkserver._stop()
    resource_tracker._resource_tracker._stop()
    _ctx = None  # the next _mp_context() relaunches with its env stripping


async def spawn_actors(
    num_actors: int,
    actor_cls: type,
    name: str,
    *args,
    env_fn: Optional[Callable[[int], dict[str, str]]] = None,
    **kwargs,
) -> ActorMesh:
    """Spawn ``num_actors`` processes each hosting one ``actor_cls`` instance.

    Each child gets rank env vars (``RANK``/``LOCAL_RANK``/``WORLD_SIZE``/
    ``LOCAL_WORLD_SIZE``) so strategies can derive volume ids the way the
    reference does from torchrun env (/root/reference/torchstore/strategy.py:164-188).
    """
    ctx = _mp_context()
    loop = asyncio.get_running_loop()
    procs: list[mp.Process] = []
    pipes = []
    # The whole process tree must share one trace run id BEFORE env capture
    # (see observability/tracing.py: sibling-vs-stale-run arbitration).
    from torchstore_tpu.observability.tracing import ensure_run_id

    ensure_run_id()
    # Forward store handles and config to children explicitly: forkserver
    # children inherit the fork server's env (snapshotted at its start), not
    # the parent's current env.
    inherited = {
        k: v for k, v in os.environ.items() if k.startswith("TORCHSTORE_TPU_")
    }
    for rank in range(num_actors):
        env = dict(inherited)
        env.update(
            {
                "RANK": str(rank),
                "LOCAL_RANK": str(rank),
                "WORLD_SIZE": str(num_actors),
                "LOCAL_WORLD_SIZE": str(num_actors),
            }
        )
        if env_fn is not None:
            env.update(env_fn(rank))
        parent_conn, child_conn = ctx.Pipe()
        proc = ctx.Process(
            target=_child_main,
            args=(child_conn, actor_cls, f"{name}_{rank}", args, kwargs, env),
            daemon=True,
            name=f"ts-{name}-{rank}",
        )
        proc.start()
        child_conn.close()
        procs.append(proc)
        pipes.append(parent_conn)

    refs: list[ActorRef] = []
    try:
        for rank, (proc, pipe) in enumerate(zip(procs, pipes)):
            msg = await loop.run_in_executor(
                None, _pipe_recv, pipe, proc, SPAWN_TIMEOUT_S
            )
            status, a, b = msg
            if status != "ready":
                raise ActorDiedError(
                    f"actor {name}_{rank} failed during spawn:\n{a}"
                )
            refs.append(ActorRef(f"{name}_{rank}", a, b, rank=rank))
    except BaseException:
        for proc in procs:
            if proc.is_alive():
                proc.terminate()
        for proc in procs:
            await loop.run_in_executor(None, proc.join, 5.0)
            if proc.is_alive():  # SIGTERM ignored mid-start: escalate
                proc.kill()
                await loop.run_in_executor(None, proc.join, 2.0)
        raise
    return ActorMesh(refs, procs)


def _pipe_recv(pipe, proc: mp.Process, timeout: float):
    if not pipe.poll(timeout):
        if not proc.is_alive():
            raise ActorDiedError(
                f"actor process exited during spawn (exitcode={proc.exitcode})"
            )
        raise ActorDiedError(f"actor spawn timed out after {timeout}s")
    return pipe.recv()


# --------------------------------------------------------------------------
# Singleton actors (get_or_spawn_controller analog)
# --------------------------------------------------------------------------

# Owner-side registry only: actor children never spawn singletons (the
# spawner owns process handles; children hold plain ActorRefs from env).
_singletons: dict[str, ActorMesh] = {}  # tslint: disable=fork-safety


async def get_or_spawn_singleton(name: str, actor_cls: type, *args, **kwargs) -> ActorRef:
    """Process-local singleton actor registry (Monarch
    ``get_or_spawn_controller`` analog, /root/reference/torchstore/api.py:118-123).
    Cross-rank sharing of the returned (picklable) ref is the SPMD layer's job."""
    mesh = _singletons.get(name)
    if mesh is None:
        mesh = await spawn_actors(1, actor_cls, name, *args, **kwargs)
        _singletons[name] = mesh
    return mesh.refs[0]


async def stop_singleton(name: str) -> None:
    mesh = _singletons.pop(name, None)
    if mesh is not None:
        await mesh.stop()


async def close_all_connections() -> None:
    for _, conn in list(_conn_pools.values()):
        await conn.close()
    _conn_pools.clear()
