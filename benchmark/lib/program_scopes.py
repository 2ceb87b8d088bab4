"""Device time of one program's runs in a profile, by the program's scopes.

``benchmark/scopes.reduce_xplane`` divides the whole traced window by
scope; a serving cell runs two kinds of program (prefill and the decode
chunk) and a share of the decode step needs the second alone. This reads
the xplane once more: the runs of the programs named ``prefix*`` on chip
0's ``XLA Modules`` line, the operations of ``XLA Ops`` that lie inside
them, each operation's self time credited to its scope
(``observability/names.scope_of`` of its ``tf_op``), the operations
outside every scope by the ``op_name`` they carry.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import trace


def reduce(path: str, prefix: str) -> dict | None:
    """``{"seconds": device seconds of the runs, "runs": count, "by_scope":
    {scope: seconds}, "unscoped": {op_name: seconds}}``, or None where the
    profile holds no run of such a program."""
    from jax.profiler import ProfileData

    from benchmark import scopes
    from distributed_tensorflow_tpu.observability import names

    planes = [p for p in ProfileData.from_file(path).planes
              if trace.DEVICE_PLANE.match(p.name)]
    if not planes:
        return None
    plane = min(planes, key=lambda p: int(trace.DEVICE_PLANE.match(p.name).group(1)))
    lines = {ln.name: ln for ln in plane.lines}
    if trace.MODULES_LINE not in lines or trace.OPS_LINE not in lines:
        return None
    runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                  for ev in lines[trace.MODULES_LINE].events
                  if ev.name.split("(", 1)[0].startswith(prefix))
    if not runs:
        return None
    r0 = np.asarray([a for a, _ in runs], np.float64)
    r1 = np.asarray([b for _, b in runs], np.float64)
    texts, starts, durs = [], [], []
    for ev in lines[trace.OPS_LINE].events:
        j = np.searchsorted(r0, ev.start_ns, side="right") - 1
        if j >= 0 and ev.start_ns + ev.duration_ns <= r1[j]:
            texts.append(ev.name)
            starts.append(ev.start_ns)
            durs.append(ev.duration_ns)
    meta = scopes.metadata_stats(path).get(plane.name, {})
    by_scope: dict = {}
    unscoped: dict = {}
    selfs = trace.self_times(np.asarray(starts, np.float64),
                             np.asarray(durs, np.float64)) * 1e-9
    for text, s in zip(texts, selfs):
        op_name = str(meta.get(text, {}).get(scopes.NAME_FIELD, ""))
        scope, _ = names.scope_of(op_name)
        if scope is None:
            key = op_name or trace.opcode(text)
            unscoped[key] = unscoped.get(key, 0.0) + float(s)
        else:
            by_scope[scope] = by_scope.get(scope, 0.0) + float(s)
    return {"seconds": float((r1 - r0).sum()) * 1e-9, "runs": len(runs),
            "by_scope": by_scope, "unscoped": unscoped}


def share_pct(reduced: dict | None, scopes_: tuple, unscoped_prefixes=()):
    """100 x the seconds under ``scopes_`` (and of unscoped operations whose
    op_name or opcode starts with one of ``unscoped_prefixes``) / the
    program's device seconds; None where nothing of them is found."""
    if not reduced or reduced["seconds"] <= 0:
        return None
    found = [reduced["by_scope"][s] for s in scopes_ if s in reduced["by_scope"]]
    found += [v for k, v in reduced["unscoped"].items()
              if unscoped_prefixes and k.startswith(tuple(unscoped_prefixes))]
    return 100.0 * sum(found) / reduced["seconds"] if found else None
