"""Profiling (SURVEY.md §5 "Tracing/profiling").

The reference's only instrumentation is hand-rolled wall-clock timing in the
loop (AvgTime/Total Time, reference tfdist_between.py:98-110) — kept as-is in
``utils/logging.py``. This module adds the TPU-native upgrade the survey
prescribes: ``jax.profiler`` traces (XLA op-level timelines viewable in
TensorBoard/Perfetto) and an on-demand profiling server.

Round 10: :func:`trace` composes with the host-side span layer
(``observability/spans.py``) — pass a :class:`~observability.spans.
SpanRecorder` and the device trace window also lands as a host span, so
``obs_report --trace``'s chrome-trace export shows WHERE in the run the
device capture happened. The device trace remains the authority on what
the chip did; host spans are the authority on what the host waited for
(and their dispatch flavor enforces the D2H barrier that ``jax.profiler``
does not). PR 26: the recorder itself writes every span into the profile
as a ``dtf:<name>`` annotation, so a named region on the device timeline
is a ``recorder.span(name)`` — no wrapper here.
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def trace(logdir: str, recorder=None):
    """Capture a device trace for the enclosed block::

        with profiler.trace("./logs/profile"):
            state, cost = train_step(state, x, y)
            float(cost)  # D2H fetch: the trustworthy barrier (utils/sync.py)

    ``recorder`` (a SpanRecorder) additionally records the capture window
    as a host span named ``jax_profiler_trace``."""
    ctx = (
        recorder.span("jax_profiler_trace", cat="profiler", logdir=logdir)
        if recorder is not None
        else contextlib.nullcontext()
    )
    with ctx:
        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


def start_server(port: int = 9999):
    """Start the on-demand profiling server (connect with TensorBoard's
    profile tab or `xprof`); returns the server object."""
    return jax.profiler.start_server(port)
