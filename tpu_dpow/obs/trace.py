"""Per-request latency tracing: one WorkRequest stamped through the pipeline.

A trace follows one block hash from the HTTP face to the reply:

    server: receive -> accept -> queue -> publish -> result_in -> winner
            winner -> resolve -> reply
            winner -> cancel
    worker: dispatch -> submit
            dispatch -> pack -> device -> result

The server begins the trace and rides its id inside the existing MQTT
payloads (transport/mqtt_codec.py encode_work_payload appends it as an
optional trailing field, so pre-trace peers parse unchanged); the client
echoes it back in the result payload. Each stage has a PARENT, the stage
that caused it (``PARENTS``); ``mark`` observes the time since the parent's
mark in the same trace into the shared per-stage histogram
(``dpow_request_stage_seconds{stage=...}``), so /metrics carries the full
stage decomposition without any consumer having to correlate raw spans.
Timing from the parent, not from whatever was marked last, keeps
concurrent branches apart: the server marks ``cancel`` from the result
handler while the waiter's ``reply`` runs in another task. A stage whose
parent is not in the trace observes nothing — a worker in another process
starts its trace empty at ``alias``, so its ``dispatch`` has no ``publish``
to be timed from. A stage outside the table keeps the previous-mark rule.

Request stages stay in the histogram and never enter the JAX profiler: a
40 ms request span would outweigh every engine span in a trace's gap
labelling. ``span`` is the profiler side — short engine spans on the
profiler's own clock, so they line up with the device plane.

Stamps use time.time() (wall clock), not perf_counter: a trace can cross
process boundaries (server and worker on different hosts), where only wall
clock deltas mean anything. Within one process the extra jitter is ns-scale
against the ms-scale stages being measured.

Components that know only a block hash (the engines, the work handler) mark
through the hash alias (``mark_hash``) — the id→stages store and the
hash→id alias table are both bounded LRU so an abandoned trace can never
leak (the reference has nothing to leak: it measures nothing).
"""

from __future__ import annotations

import contextlib
import secrets
import sys
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from .registry import Histogram, Registry, get_registry

MAX_TRACES = 2048

STAGE_HISTOGRAM = "dpow_request_stage_seconds"

# Canonical stage order, for readers that want to sort a span chain the way
# the pipeline runs it. Marks outside this list are legal (forward compat);
# they simply sort last.
STAGES = (
    "receive",    # service request entered the handler, trace born (server)
    "accept",     # auth, throttle, validation and quota done (server)
    "queue",      # dispatcher picked it up / store writes started (server)
    "publish",    # work/ondemand (or precache) publish landed (server)
    "dispatch",   # worker received the work message (client)
    "submit",     # work handler handed the job to the engine (client)
    "pack",       # engine included the job in its first device launch
    "device",     # device launch solved it (result applied host-side)
    "result",     # worker published result/<type> (client)
    "result_in",  # server decoded the winning result
    "winner",     # server elected this result the winner
    "resolve",    # server resolved the waiting request's future
    "cancel",     # server fanned out cancel/<type> to the losers
    "reply",      # the request's reply passed final validation (server)
)

# stage -> the stage that caused it; a mark is timed from its parent's
# latest mark in the same trace. ``receive`` is the root.
PARENTS: Dict[str, str] = {
    "accept": "receive",
    "queue": "accept",
    "publish": "queue",
    "result_in": "publish",
    "winner": "result_in",
    "resolve": "winner",
    "cancel": "winner",
    "reply": "resolve",
    "dispatch": "publish",
    "submit": "dispatch",
    "pack": "dispatch",
    "device": "pack",
    "result": "device",
}


def span(name: str):
    """A JAX profiler span (``jax.profiler.TraceAnnotation``) when jax is
    already imported, else a no-op. Never imports jax: the server stays
    JAX-free. The profiler stamps the span itself, on the clock of its
    device planes; with the profiler off it costs one TraceMe."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return contextlib.nullcontext()
    return prof.TraceAnnotation(name)


def new_trace_id() -> str:
    return secrets.token_hex(8)


def is_trace_id(value: str) -> bool:
    """Cheap wire-side validation: 16 lowercase hex chars."""
    return (
        len(value) == 16
        and all(c in "0123456789abcdef" for c in value)
    )


class Tracer:
    def __init__(self, registry: Optional[Registry] = None):
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, List[Tuple[str, float]]]" = OrderedDict()
        self._aliases: "OrderedDict[str, str]" = OrderedDict()
        self._registry = registry
        self._hist: Optional[Histogram] = None

    def _histogram(self) -> Histogram:
        # Cached: registry families outlive reset(), only series are cleared.
        if self._hist is None:
            self._hist = (self._registry or get_registry()).histogram(
                STAGE_HISTOGRAM,
                "Per-stage latency of one work request (delta since the "
                "mark of the stage that caused it)",
                labelnames=("stage",),
            )
        return self._hist

    @staticmethod
    def now() -> float:
        """A stamp on the tracer's clock, for ``begin``/``mark`` ``at=``."""
        return time.time()

    def begin(
        self,
        key: Optional[str] = None,
        stage: str = "accept",
        at: Optional[float] = None,
    ) -> str:
        """Start a trace (stamping ``stage`` now, or at ``at``), optionally
        aliased to a key (the block hash) so hash-keyed components can mark
        it."""
        trace_id = new_trace_id()
        now = time.time() if at is None else at
        with self._lock:
            self._traces[trace_id] = [(stage, now)]
            self._traces.move_to_end(trace_id)
            while len(self._traces) > MAX_TRACES:
                self._traces.popitem(last=False)
            if key is not None:
                self._alias_locked(key, trace_id)
        return trace_id

    def _alias_locked(self, key: str, trace_id: str) -> None:
        self._aliases[key] = trace_id
        self._aliases.move_to_end(key)
        while len(self._aliases) > MAX_TRACES:
            self._aliases.popitem(last=False)

    def alias(self, key: str, trace_id: str) -> None:
        """Bind a block hash to a trace id received off the wire. Unknown
        ids get an empty trace created (a worker's marks are still useful
        even when the server restarted mid-flight) — under the same LRU
        bound as begin(): wire-supplied ids are untrusted input, and an
        unbounded insert here would let any peer grow the store forever."""
        with self._lock:
            if trace_id not in self._traces:
                self._traces[trace_id] = []
                self._traces.move_to_end(trace_id)
                while len(self._traces) > MAX_TRACES:
                    self._traces.popitem(last=False)
            self._alias_locked(key, trace_id)

    def mark(
        self, trace_id: Optional[str], stage: str, at: Optional[float] = None
    ) -> None:
        """Stamp ``stage`` on the trace (now, or at ``at``) and observe the
        time since its parent's mark (``PARENTS``; the previous mark for a
        stage outside the table). Nothing is observed when the parent is
        not in the trace. Unknown/None ids are a silent no-op: tracing must
        never be able to break the data path."""
        if not trace_id:
            return
        now = time.time() if at is None else at
        with self._lock:
            stages = self._traces.get(trace_id)
            if stages is None:
                return
            prev = _origin(stages, stage)
            stages.append((stage, now))
            self._traces.move_to_end(trace_id)
        if prev is not None:
            self._histogram().observe(max(0.0, now - prev), stage)

    def mark_hash(self, key: str, stage: str, at: Optional[float] = None) -> None:
        with self._lock:
            trace_id = self._aliases.get(key)
        self.mark(trace_id, stage, at)

    def id_for(self, key: str) -> Optional[str]:
        with self._lock:
            return self._aliases.get(key)

    def get(self, trace_id: str) -> List[Tuple[str, float]]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def spans(self, trace_id: str) -> List[Tuple[str, float]]:
        """[(stage, seconds-since-its-origin), ...], timed as ``mark``
        observes them; a mark with no origin in the trace (the first, or a
        parentless one) reads 0.0."""
        stages = self.get(trace_id)
        out = []
        for i, (stage, t) in enumerate(stages):
            prev = _origin(stages[:i], stage)
            out.append((stage, 0.0 if prev is None else max(0.0, t - prev)))
        return out

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()
            self._aliases.clear()


def _origin(stages: List[Tuple[str, float]], stage: str) -> Optional[float]:
    """The stamp ``stage`` is timed from: its parent's latest mark in
    ``stages``, or the latest mark of any stage when it has no parent."""
    parent = PARENTS.get(stage)
    if parent is None:
        return stages[-1][1] if stages else None
    for name, t in reversed(stages):
        if name == parent:
            return t
    return None


# Process-wide tracer, same rationale as the default registry: an in-process
# stack (server + client + engine) assembles one coherent span chain.
TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER
