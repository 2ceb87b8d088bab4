"""Host-side trace spans: chrome-trace/Perfetto-loadable, barrier-honest.

``jax.profiler`` (utils/profiler.py) answers "what did the DEVICE do";
these spans answer "what did the HOST wait for" — dispatch→fetch windows,
compiles, checkpoint save/restore, serving prefill/decode chunks — in the
chrome trace event format, so one ``obs_report --trace`` export loads in
Perfetto/chrome://tracing next to a device trace.

The API bakes in the repo's timing discipline (utils/sync.py): JAX
dispatch is asynchronous, so a timed region must end in something that
waits for the device — ``block_until_ready`` or a device-to-host VALUE
fetch. Every dispatch these spans time produces a value the host needs
anyway (costs, tokens), so the span uses the fetch: a
:meth:`SpanRecorder.dispatch` span **refuses to close** until
:meth:`~DispatchSpan.fetch` has materialized a value on the host — timing
a dispatch without it raises instead of silently recording enqueue time
(the class of bug that cost rounds 1-4 three separate debugging cycles).
Generic host work (compile, file I/O) uses :meth:`SpanRecorder.span`,
which has no such requirement.

One clock (PR 26): while a ``jax.profiler`` session is on, every span is
ALSO a ``jax.profiler.TraceAnnotation`` named ``dtf:<span name>`` in the
profile's ``/host:CPU`` plane, carrying the span's scalar arguments — it
opens where the span opens and closes where the span closes (a dispatch
span: at the fetch), on the clock of the device planes, so a device gap
is laid against what the program was doing with no arithmetic between
clocks. Outside a session an annotation costs half a microsecond.

jax-free (lean-import convention): the fetch coerces via ``__array__`` /
``float`` — a jax array's ``__array__`` IS the D2H copy, and numpy is
imported lazily only when an array-likes is fetched; the annotation is
taken from ``jax`` only when the process has imported it already (a
program that dispatches has).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time

from distributed_tensorflow_tpu.observability.names import ANNOTATION_PREFIX


def force_host(value):
    """Materialize ``value`` on the host — the trustworthy execution
    barrier. Device arrays come back as numpy (``__array__`` performs the
    D2H copy); Python/0-d scalars coerce through ``float``. ``None`` is
    refused: a dispatch that produced nothing fetchable has nothing to
    prove it ran."""
    if value is None:
        raise ValueError(
            "dispatch fetch needs a value produced by the dispatch "
            "(device array or scalar); got None"
        )
    if hasattr(value, "__array__"):
        import numpy as np

        return np.asarray(value)
    if isinstance(value, (list, tuple)):
        return type(value)(force_host(v) for v in value)
    if isinstance(value, dict):
        return {k: force_host(v) for k, v in value.items()}
    return float(value)


def _open_annotation(name: str, args: dict):
    """Enter a ``jax.profiler.TraceAnnotation`` named ``dtf:<name>`` with
    the scalar ``args`` as its own, and return it for
    :func:`_close`; None where this process has not imported
    jax (nothing to profile, and the package stays importable without
    it). Lists (``rids``) stay in the span event only."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(
        ANNOTATION_PREFIX + name,
        **{k: v for k, v in args.items()
           if isinstance(v, (bool, int, float, str))},
    )
    ann.__enter__()
    return ann


def _close(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


class DispatchSpan:
    """An open dispatch span. ``fetch(value)`` is the only way to close
    it cleanly: it performs the D2H materialization and stamps the span's
    end time AT the fetch — the honest dispatch+execute window — and
    closes the span's profiler annotation there too. ``args`` may be
    added to between the fetch and the end of the ``with`` block (what
    the dispatch delivered is known only then)."""

    def __init__(self, recorder: "SpanRecorder", name: str, args: dict):
        self._rec = recorder
        self.name = name
        self.args = args
        self._t0 = recorder._now()
        self._t_fetch = None
        self._ann = _open_annotation(name, args)

    def fetch(self, value):
        host = force_host(value)
        self._t_fetch = self._rec._now()
        self._close_annotation()
        return host

    def _close_annotation(self) -> None:
        ann, self._ann = self._ann, None
        _close(ann)

    @property
    def fetched(self) -> bool:
        return self._t_fetch is not None


class SpanRecorder:
    """In-memory span sink with chrome-trace export and optional journal
    mirroring (each closed span also lands as a ``span`` event, so
    ``obs_report`` can rebuild the trace from ``events.jsonl`` alone).
    Keeps at most ``max_spans`` (oldest dropped, ``dropped`` counts them)
    so a long-lived server cannot grow without bound."""

    def __init__(self, journal=None, *, max_spans: int = 100_000):
        self.journal = journal
        self.max_spans = int(max_spans)
        # deque(maxlen=...): O(1) eviction — a list's front-delete would
        # memmove the whole buffer per span once a long-lived server
        # reaches the cap.
        self.spans: collections.deque = collections.deque(maxlen=self.max_spans)
        self.dropped = 0
        self._wall0 = time.time()
        self._perf0 = time.perf_counter()

    def _now(self) -> float:
        return time.perf_counter() - self._perf0

    def _record(
        self, name: str, cat: str, t0: float, t1: float, args: dict
    ) -> dict:
        span = {
            "name": name,
            "cat": cat,
            "ts_us": t0 * 1e6,
            "dur_us": max(t1 - t0, 0.0) * 1e6,
            "wall_ts": self._wall0 + t0,
            "tid": threading.get_ident() & 0xFFFF,
        }
        if args:
            span["args"] = dict(args)
        if len(self.spans) == self.max_spans:
            self.dropped += 1  # deque maxlen evicts the oldest on append
        self.spans.append(span)
        if self.journal is not None:
            self.journal.emit(
                "span",
                name=name,
                cat=cat,
                ts_us=span["ts_us"],
                dur_us=span["dur_us"],
                **({"args": span["args"]} if args else {}),
            )
        return span

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", **args):
        """Generic host span (compile, checkpoint I/O, scheduler work)."""
        t0 = self._now()
        ann = _open_annotation(name, args)
        try:
            yield
        finally:
            _close(ann)
            self._record(name, cat, t0, self._now(), args)

    @contextlib.contextmanager
    def dispatch(self, name: str, **args):
        """A device-dispatch span. The body MUST call ``fetch(value)`` on
        something the dispatch produced; exiting without it raises
        RuntimeError — per CLAUDE.md's timing traps, a dispatch span
        without a D2H fetch would time enqueue, not execution. The span's
        end is the fetch completion time."""
        sp = DispatchSpan(self, name, args)
        try:
            yield sp
        except BaseException:
            # The dispatch died: record what we know, never mask the error.
            sp._close_annotation()
            self._record(
                name, "dispatch", sp._t0, self._now(),
                {**sp.args, "error": True},
            )
            raise
        if not sp.fetched:
            sp._close_annotation()
            raise RuntimeError(
                f"dispatch span {name!r} closed without a D2H fetch: call "
                "span.fetch(<value the dispatch produced>) before exiting "
                "— through the device link, timing without a value fetch "
                "measures enqueue, not execution (CLAUDE.md TIMING TRAP)"
            )
        self._record(
            name, "dispatch", sp._t0, sp._t_fetch,
            {**sp.args, "barrier": "d2h"},
        )

    # -- export ------------------------------------------------------------

    def chrome_trace(self) -> dict:
        return chrome_trace(self.spans)

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f)
        return path


def chrome_trace(spans) -> dict:
    """Span dicts (recorder-shaped OR ``span`` journal events) → the
    chrome trace event format Perfetto loads. Complete ("X") events with
    microsecond ts/dur, one process, tids preserved when present."""
    pid = os.getpid()
    events = []
    for s in spans:
        events.append(
            {
                "name": s.get("name", "?"),
                "cat": s.get("cat", "host"),
                "ph": "X",
                "ts": float(s.get("ts_us", 0.0)),
                "dur": float(s.get("dur_us", 0.0)),
                "pid": int(s.get("pid", pid)),
                "tid": int(s.get("tid", 0)),
                "args": dict(s.get("args", {})),
            }
        )
    return {"traceEvents": events, "displayTimeUnit": "ms"}
