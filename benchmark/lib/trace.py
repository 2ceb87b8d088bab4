"""From a profiler trace (xplane) to busy and idle time, time by
operation, time by program, exposed collective time, and idle gaps named
by the benchmark's annotation that covered them.

What the TPU's trace looks like (one look by hand, PR 25): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per program run, named ``jit_<function>(<fingerprint>)``), ``XLA Ops``
(one event per HLO operation, named by its HLO text; a ``while`` or a
``call`` is an event that *contains* its body's events) and ``Async XLA
Ops``; a plane ``/host:CPU`` whose thread lines hold the
``TraceAnnotation`` events. All times are nanoseconds on one clock.

Conventions, so that the numbers can be read:

- busy: the union of the ``XLA Ops`` events inside the window, per chip,
  averaged over the chips. A chip waiting inside a collective counts as
  busy (the operation is running on it).
- the window: from the start of the first ``bench:`` annotation to the
  end of the last one.
- an operation's time is its *self* time: its span less the spans of the
  events it contains. Containers (``while``, ``call``, ``conditional``)
  therefore show only their own overhead.
- exposed collective time: self time of collective operations on the
  ``XLA Ops`` line. That line is the core's one instruction stream, so
  whatever runs there runs instead of compute: an asynchronous
  collective shows only its ``-start`` and the wait in its ``-done``.
"""

from __future__ import annotations

import dataclasses
import json
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION = "bench:"
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)
SHORT_GAP_NS = 20_000
SHORT_GAP_NAME = "under_20_us__between_launches_"


def load(path: str) -> dict:
    """``{plane: {line: (names, starts_ns, durations_ns)}}`` of the
    device planes' op and module lines and of the host's annotations."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            names, starts, durs = [], [], []
            for ev in line.events:
                if not device and not ev.name.startswith(ANNOTATION):
                    continue
                names.append(ev.name)
                starts.append(ev.start_ns)
                durs.append(ev.duration_ns)
            if names:
                lines[line.name] = (
                    names, np.asarray(starts, np.float64),
                    np.asarray(durs, np.float64))
        if lines:
            planes[plane.name] = lines
    return planes


def short_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def _result_type_end(rest: str) -> int:
    """Where the result type of ``<type> <opcode>(...)`` ends: the first
    space outside brackets."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return i
    return len(rest)


def opcode(hlo: str) -> str:
    """The HLO opcode of an op event's name ('' if it has none)."""
    if " = " not in hlo:
        return ""
    rest = hlo.split(" = ", 1)[1]
    return rest[_result_type_end(rest) + 1:].split("(", 1)[0].strip()


def result_shapes(hlo: str) -> list[tuple[str, tuple[int, ...]]]:
    """Result shapes of an op event's name, as (dtype, dims); a tuple
    result gives one entry per element."""
    if " = " not in hlo:
        return []
    rest = hlo.split(" = ", 1)[1]
    return [
        (m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
        for m in re.finditer(
            r"([a-z]+[0-9]+)\[([0-9,]*)\]", rest[:_result_type_end(rest)])
    ]


def self_times(starts: np.ndarray, durs: np.ndarray) -> np.ndarray:
    """Each event's span less the spans of the events nested in it."""
    order = np.lexsort((-durs, starts))
    ends = starts + durs
    out = durs.copy()
    stack: list[int] = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            out[stack[-1]] -= durs[i]
        stack.append(i)
    return np.maximum(out, 0.0)


def merged(starts: np.ndarray, durs: np.ndarray, lo: float, hi: float):
    """The union of the intervals, clipped to [lo, hi], as (starts, ends)."""
    s = np.clip(starts, lo, hi)
    e = np.clip(starts + durs, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    order = np.argsort(s)
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [s.size - 1]])
    return s[first], e[last]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    op_self_s: dict  # short name -> seconds, summed over chips / chips
    opcode_of: dict  # short name -> opcode
    hlo_of: dict  # short name -> full HLO text (first seen)
    op_calls: dict  # short name -> list of per-call self seconds (chip 0)
    module_s: dict  # program name (no fingerprint) -> [(start_s, seconds)] (chip 0)
    collective_exposed_s: float
    gaps: list  # (annotation, seconds), chip 0
    chips: int

    def top_ops(self, n: int) -> list:
        ranked = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{k}__{self.opcode_of.get(k, '')}", v] for k, v in ranked]

    def top_gaps(self, n: int) -> list:
        total: dict = {}
        for name, s in self.gaps:
            total[name] = total.get(name, 0.0) + s
        return [list(kv) for kv in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def module_seconds(self, prefix: str) -> list:
        """Device seconds of each run of the programs named ``prefix*``."""
        return [d for name, v in self.module_s.items()
                if name.startswith(prefix) for _, d in v]

    def module_gaps(self, prefix: str) -> list:
        """Seconds between the end of one run and the start of the next."""
        runs = sorted(r for name, v in self.module_s.items()
                      if name.startswith(prefix) for r in v)
        return [b[0] - (a[0] + a[1]) for a, b in zip(runs, runs[1:])]


def _annotations(planes: dict):
    names, starts, durs = [], [], []
    for plane, lines in planes.items():
        if DEVICE_PLANE.match(plane):
            continue
        for n, s, d in lines.values():
            names += n
            starts.append(s)
            durs.append(d)
    if not names:
        return [], np.zeros(0), np.zeros(0)
    return names, np.concatenate(starts), np.concatenate(durs)


def summarize(planes: dict, chips: int) -> TraceSummary:
    a_names, a_starts, a_durs = _annotations(planes)
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))[:chips]
    if not devices or not a_names:
        raise RuntimeError("trace has no device plane or no bench: annotation")
    lo = float(a_starts.min())
    hi = float((a_starts + a_durs).max())
    busy = 0.0
    op_self: dict = {}
    opcode_of: dict = {}
    hlo_of: dict = {}
    op_calls: dict = {}
    module_s: dict = {}
    exposed = 0.0
    gaps: list = []
    for k, plane in enumerate(devices):
        names, starts, durs = planes[plane][OPS_LINE]
        inside = (starts + durs > lo) & (starts < hi)
        names = [n for n, ok in zip(names, inside) if ok]
        starts, durs = starts[inside], durs[inside]
        ms, me = merged(starts, durs, lo, hi)
        busy += float((me - ms).sum())
        selfs = self_times(starts, durs)
        for n, s in zip(names, selfs):
            key = short_name(n)
            op_self[key] = op_self.get(key, 0.0) + float(s) * 1e-9
            if key not in opcode_of:
                opcode_of[key] = opcode(n)
                hlo_of[key] = n
            if opcode_of[key].startswith(COLLECTIVES):
                exposed += float(s) * 1e-9
            if k == 0:
                op_calls.setdefault(key, []).append(float(s) * 1e-9)
        if k == 0:
            if MODULES_LINE in planes[plane]:
                for n, s, d in zip(*planes[plane][MODULES_LINE]):
                    if s + d > lo and s < hi:
                        module_s.setdefault(n.split("(", 1)[0], []).append(
                            (float(s) * 1e-9, float(d) * 1e-9))
            edges_s = np.concatenate([[lo], me])
            edges_e = np.concatenate([ms, [hi]])
            for g0, g1 in zip(edges_s, edges_e):
                if g1 <= g0:
                    continue
                if g1 - g0 < SHORT_GAP_NS:
                    gaps.append((SHORT_GAP_NAME, float(g1 - g0) * 1e-9))
                    continue
                # each annotation is credited the part of the gap it covers
                over = np.clip(
                    np.minimum(a_starts + a_durs, g1) - np.maximum(a_starts, g0),
                    0.0, None)
                for i in np.flatnonzero(over):
                    gaps.append((a_names[i], float(over[i]) * 1e-9))
                rest = (g1 - g0) - over.sum()
                if rest > SHORT_GAP_NS:
                    gaps.append(("no_annotation", float(rest) * 1e-9))
    n = len(devices)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        op_self_s={k: v / n for k, v in op_self.items()},
        opcode_of=opcode_of, hlo_of=hlo_of, op_calls=op_calls,
        module_s=module_s,
        collective_exposed_s=exposed / n,
        gaps=gaps, chips=n,
    )


def dump_fixture(planes: dict, path: str, per_line: int = 400) -> None:
    """The first events of every line as JSON: a recorded trace small
    enough to keep as a test fixture."""
    out = {}
    for plane, lines in planes.items():
        out[plane] = {
            line: [n[:per_line], s[:per_line].tolist(), d[:per_line].tolist()]
            for line, (n, s, d) in lines.items()
        }
    with open(path, "w") as f:
        json.dump(out, f)


def load_fixture(path: str) -> dict:
    with open(path) as f:
        raw = json.load(f)
    return {
        plane: {
            line: (n, np.asarray(s, np.float64), np.asarray(d, np.float64))
            for line, (n, s, d) in lines.items()
        }
        for plane, lines in raw.items()
    }
