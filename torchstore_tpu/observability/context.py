"""Trace-context propagation: one trace id across the whole fleet.

A logical store operation fans out over processes — client span -> controller
notify -> N volume puts — and PR 1's per-process Chrome traces land those
spans in disconnected files with no way to say "these belong to one put".
This module carries a W3C-traceparent-shaped context (``trace_id`` +
``parent_span_id``) in :mod:`contextvars`, so:

- ``span()`` (tracing.py) stamps every emitted event with the active
  ``trace_id``/``span_id``/``parent_id`` and pushes itself as the parent for
  anything nested under it — across ``await`` boundaries, since asyncio tasks
  snapshot the context at creation;
- the actor RPC layer (runtime/actors.py) injects the current context into
  every request frame and re-activates it around endpoint dispatch on the
  server, so a volume-side span carries the CLIENT's trace id;
- ``merge_traces`` / ``ts.collect_trace()`` then stitch the per-process files
  into one Perfetto timeline where the shared trace id (and parent links)
  align client, controller, and volume tracks.

Ids are hex strings (16 hex chars — 64 random bits from a per-process
generator seeded by the OS, reseeded in a forked child), cheap to mint per
logical op and per span: no system call, which on a sandboxed host costs
more than the rest of a span (6 of 11 us on the chip's host; PERF.md, PR 23). Context creation is O(two contextvar sets); when tracing is
disabled only the ids ride the RPC frames (useful for slow-op log
correlation) and nothing is buffered.
"""

from __future__ import annotations

import contextvars
import os
import random
from typing import Optional

_trace_id: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "torchstore_tpu_trace_id", default=None
)
_parent_span_id: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "torchstore_tpu_parent_span_id", default=None
)


# Ids name spans; they guard nothing, so a PRNG serves. Its own instance: a
# caller's ``random.seed(0)`` must not make two processes mint the same ids.
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)


def new_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def trace_id() -> Optional[str]:
    """The active trace id, or None outside any traced operation."""
    return _trace_id.get()


def parent_span_id() -> Optional[str]:
    return _parent_span_id.get()


def current() -> Optional[dict]:
    """The propagatable context: ``{"trace_id", "parent_span_id"}`` or None.

    This is exactly what rides an RPC frame — the receiving side's spans
    adopt the trace id and hang off the caller's span as children."""
    tid = _trace_id.get()
    if tid is None:
        return None
    return {"trace_id": tid, "parent_span_id": _parent_span_id.get()}


def push_span(span_id: str) -> "contextvars.Token":
    """Make ``span_id`` the parent of anything opened under it. Returns the
    token for :func:`pop_span`; the token's ``old_value`` is this span's own
    parent (used when emitting the span's trace event)."""
    return _parent_span_id.set(span_id)


def pop_span(token: "contextvars.Token") -> None:
    _parent_span_id.reset(token)


def token_parent(token: "contextvars.Token") -> Optional[str]:
    """The parent id that was active before ``push_span`` minted this token."""
    old = token.old_value
    return None if old is contextvars.Token.MISSING else old


class activate:
    """Adopt an incoming (RPC-carried) context for the duration of a block.

    ``activate(None)`` is a no-op — server dispatch wraps every endpoint call
    unconditionally and untraced callers cost nothing."""

    __slots__ = ("_ctx", "_tokens")

    def __init__(self, ctx: Optional[dict]) -> None:
        self._ctx = ctx if isinstance(ctx, dict) else None
        self._tokens = None

    def __enter__(self) -> "activate":
        if self._ctx is not None and self._ctx.get("trace_id"):
            self._tokens = (
                _trace_id.set(str(self._ctx["trace_id"])),
                _parent_span_id.set(self._ctx.get("parent_span_id")),
            )
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._tokens is not None:
            _trace_id.reset(self._tokens[0])
            _parent_span_id.reset(self._tokens[1])
            self._tokens = None


class ensure_root:
    """Start a new trace unless one is already active (client ops wrap their
    whole body in this, so every put/get roots exactly one trace and nested
    store calls — weight channel publishes, state-dict flattening — join
    their caller's)."""

    __slots__ = ("_token",)

    def __enter__(self) -> "ensure_root":
        self._token = (
            None if _trace_id.get() is not None else _trace_id.set(new_id())
        )
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._token is not None:
            _trace_id.reset(self._token)
            self._token = None
