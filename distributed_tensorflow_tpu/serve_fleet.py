"""Serving fleet: a health-checked replica router with zero-loss failover.

The reference's availability story was training-side only (a restarted
worker re-attached to live PS state, reference tfdist_between.py:83);
rounds 6-14 rebuilt and surpassed it for training (durable checkpoints,
elastic gang resize, DiLoCo through failures). Serving — the surface the
north-star's "millions of users" actually touch — was still ONE Python
loop: a dead TextServer lost every resident request. This module is the
serving twin of that machinery, grounded in the paper's async-beats-sync
thesis: replicas fail and recover INDEPENDENTLY while the fleet keeps
serving, exactly as the reference's async PS workers did for training —
serving replicas share no collectives, so nothing gang-restarts.

Topology
--------
A :class:`ReplicaRouter` supervises N serving replicas. Each replica is a
:class:`ReplicaHandle` bundling the round-7 elastic primitives
(train/elastic.py — the reuse is deliberate, one supervision vocabulary
for training and serving):

- an ``ElasticAgent`` (spawn / poll the exit code / kill) over the
  replica process — ``run_replica`` below, a TextServer restored from
  ``checkpoint_dir`` driving submit/step/result against a filesystem
  mailbox;
- an ``HttpHealth`` probe over the replica's ``/healthz``
  (observability/exporter.py): dead / stalled verdicts mirror the
  heartbeat detector's, and the last good document carries the ROUTING
  signals (``queue_saturation``, ``slots_busy``, ``draining``);
- a :class:`MailboxClient`: requests in, results out, every file written
  atomically (tmp + ``os.replace``). The mailbox OUTLIVES the process —
  results a replica committed before dying are still collected, and
  anything without a result re-admits elsewhere.

Zero-loss failover
------------------
The router keeps the AUTHORITATIVE request table: every request carries
its trace id and full generation config end-to-end, so when a replica
dies (exit code, dead, or stalled verdict) its uncollected in-flight
requests are re-admitted to a healthy replica and re-served FROM SCRATCH.
Continuous batching makes chunk-boundary re-admission safe, and the
round-9 parity contract (greedy and seeded-sampling streams are
deterministic functions of prompt + config) makes the retried stream
token-identical — the client observes a latency blip, never a changed or
lost stream. Duplicate results (a slow replica finishing after its work
was re-served) deduplicate on the trace id: first terminal result wins.
A request the deadline cancelled is terminal — retries never resurrect
it (``request_cancelled`` is the record).

Failed replicas relaunch under a restart budget with jittered backoff
(``resilience.backoff_delay`` — the gang's own formula; members restart
independently, so there is no single retry() call to wrap). A replica
over budget is BENCHED; when the non-benched roster would fall below
``min_replicas`` the router fail-stops (:class:`FleetBelowFloor`, the
serving analog of ``GangBelowFloor`` — unserved requests stay with the
caller, nothing durable is lost).

Routing is prefix-cache-aware: same-prefix sessions stick to the replica
holding the warm radix (first ``affinity_tokens`` tokens key a sticky
map), spilling to the least-loaded replica when the sticky target is
saturated (``/healthz`` ``queue_saturation`` ≥ ``spill_threshold``) —
backed by TextServer's bounded admission queue, which rejects loudly
instead of growing without bound.

Live weight swap
----------------
``ReplicaRouter.swap_weights()`` sends each replica a swap control; the
replica adopts the newest CRC-verified checkpoint between chunk
boundaries (``TextServer.swap_from_checkpoint``: admission pauses, the
last old-weight resident finishes, the param tree is replaced — params
are runtime args of every compiled graph, so NOTHING recompiles) —
closing the DiLoCo train→publish→serve loop. Residents admitted before
the swap complete under the old weights' parity contract; new admissions
serve the new weights; no request is dropped.

Overload robustness (round 21)
------------------------------
Under load the router degrades gracefully instead of rejecting blindly
(docs/serving.md §overload). Requests carry ``priority`` + ``deadline_s``
fleet-wide: queued requests live in PER-CLASS queues served weighted-fair
(deficit round robin, weight ``priority+1`` — low classes still progress,
high classes get the larger share), earliest-deadline-first within a
class; a queued request past its deadline — or provably unable to finish
inside it (remaining budget x the fleet's measured per-token EWMA) — is
SHED before a route is spent on it (:class:`~serve_pool.RequestShed`
terminal result, ``request_shed`` journal event; distinct from a
``RequestCancelled`` resident). A per-replica CIRCUIT BREAKER watches
route timeouts (``route_timeout_s``; default None = off): consecutive
failures open it and divert routes immediately — BEFORE the slower
HttpHealth verdict lands — half-open admits one probe after
``breaker_reset_s``, any collected result closes it. Breaker transitions
are routing decisions: they emit ``breaker_*`` journal events and charge
NOTHING to the restart budget (supervision still owns kill/relaunch).
Default path (no priority/deadline, no route timeout) is byte-identical
to round 16.

Out of scope (deliberately): sharded (tensor-parallel) serving and the
HTTP/SSE streaming frontend — both gate on the partition-rule engine
(ROADMAP item 2) and deserve their own PR.

jax-free at import (the lean-import convention): the router runs on a
driver host with no accelerator stack; only ``run_replica`` (the spawned
worker) imports the engine. Proofs: tests/test_serve_fleet.py pins the
router state machine on a fake replica table (the test_elastic.py
pattern); tests/integration/test_serve_fleet_failover.py SIGKILLs a
replica of a live ≥3-replica fleet mid-decode and asserts zero failed
requests + token-identical streams (RUN_SLOW). docs/serving.md §fleet.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import sys
import time
from collections import deque
from typing import Sequence

from distributed_tensorflow_tpu.observability import journal as obs_journal
from distributed_tensorflow_tpu.observability import tracing
from distributed_tensorflow_tpu.observability.metrics import MetricsRegistry
from distributed_tensorflow_tpu.serve_pool import RequestCancelled, RequestShed
from distributed_tensorflow_tpu.train import failpoints, resilience
from distributed_tensorflow_tpu.train.elastic import (
    ElasticAgent,
    children_platform,
    HttpHealth,
    WorkerFailure,
)
from distributed_tensorflow_tpu.utils.summary import lifecycle_event


# GenerationConfig's field names, mirrored here so the jax-free router
# can refuse a malformed config at submit time instead of shipping it to
# a replica whose constructor would die on it (tests/test_serve_fleet.py
# pins the mirror against the real dataclass).
CONFIG_KEYS = ("max_new", "greedy", "temperature", "top_p", "seed", "eos_id")


class FleetBelowFloor(WorkerFailure):
    """Fewer than ``min_replicas`` non-benched replicas remain: the
    router fail-stops (the serving analog of ``GangBelowFloor``) rather
    than pretend a one-replica rump is the fleet the operator asked for."""


# ---------------------------------------------------------------------------
# Filesystem mailbox: the router<->replica transport.
# ---------------------------------------------------------------------------


# The one atomic-JSON primitive (checkpoint manifests, layout sidecars,
# and this mailbox all share it): tmp + os.replace, so a reader never
# sees a torn file and a writer killed mid-write leaves only a ``.tmp``
# that readers skip.
write_json_atomic = resilience.write_json_atomic


def _payload_crc(obj: dict) -> int:
    """CRC32C envelope over the canonical JSON bytes of a mailbox
    payload (sort_keys — writer and reader must agree byte-for-byte).
    Round-6 kernel: native fast path, table fallback, bit-identical."""
    return resilience._crc32c_bytes(
        json.dumps(obj, sort_keys=True).encode("utf-8")
    )


def _read_dir(dirpath: str, on_corrupt=None) -> list[dict]:
    """Read-and-remove every committed JSON file in ``dirpath``, oldest
    first (filenames carry a zero-padded sequence).

    Integrity (round 19): payloads carry a ``_crc`` envelope
    (:func:`_payload_crc`, popped before delivery); a committed file
    that fails the CRC or will not parse is QUARANTINED — removed,
    never delivered, surfaced via ``on_corrupt(name, reason)`` — so
    corrupt bytes cannot poison the router/replica AND cannot be
    re-read forever (the pre-round-19 behavior left unparseable files
    in place for every subsequent poll). Payloads without ``_crc``
    (older writers) deliver unchecked. Transient OSError on open skips
    WITHOUT removing — a racing writer's commit lands by next poll."""
    out = []
    failpoints.fire("fleet.read")
    try:
        names = sorted(os.listdir(dirpath))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue  # .tmp.* in flight
        path = os.path.join(dirpath, name)
        try:
            with open(path, encoding="utf-8") as f:
                obj = json.load(f)
        except OSError:  # pragma: no cover — racing writer
            continue
        except ValueError:
            _quarantine(path, name, "json", on_corrupt)
            continue
        crc = obj.pop("_crc", None) if isinstance(obj, dict) else None
        if crc is not None and crc != _payload_crc(obj):
            _quarantine(path, name, "crc", on_corrupt)
            continue
        try:
            os.remove(path)
        except OSError:  # pragma: no cover — racing reader took it
            continue
        out.append(obj)
    return out


def _quarantine(path: str, name: str, reason: str, on_corrupt) -> None:
    try:
        os.remove(path)
    except OSError:  # pragma: no cover
        pass
    if on_corrupt is not None:
        on_corrupt(name, reason)


class MailboxClient:
    """One replica's mailbox: ``<root>/inbox`` (router → replica:
    requests and control messages, one FIFO stream) and ``<root>/outbox``
    (replica → router: results). Both sides write atomically; the
    directories outlive the replica process — that persistence is the
    storage half of the zero-loss contract (committed results survive a
    crash; everything else visibly lacks a result and re-admits).

    Round 19: every write carries a ``_crc`` envelope verified (and
    popped) on read; corrupt committed files are quarantined — removed,
    never delivered, counted in ``corrupt_files`` and journaled as
    ``mailbox_corrupt`` (the router wires its journal in; standalone
    clients ride the process default). Stale ``.tmp`` orphans from
    writers killed mid-write are age-guard swept at construction and on
    ``clear_inbox`` (:func:`resilience.sweep_tmp_orphans` — the age
    guard keeps a live writer's in-flight tmp safe). Failpoints:
    ``fleet.submit``/``fleet.result`` (entry + tear of the committed
    file), ``fleet.read`` at every poll."""

    def __init__(
        self,
        root: str,
        *,
        journal=None,
        metrics=None,
        orphan_age_s: float = 60.0,
    ):
        self.root = root
        self.inbox = os.path.join(root, "inbox")
        self.outbox = os.path.join(root, "outbox")
        os.makedirs(self.inbox, exist_ok=True)
        os.makedirs(self.outbox, exist_ok=True)
        self._seq = 0
        self.journal = journal
        self.metrics = metrics  # round 21: counters beside the journal
        self.orphan_age_s = float(orphan_age_s)
        self.corrupt_files = 0  # quarantined corrupt mailbox files
        for d in (self.inbox, self.outbox):
            resilience.sweep_tmp_orphans(d, age_s=self.orphan_age_s)

    def _next(self, dirpath: str, tag: str) -> str:
        self._seq += 1
        return os.path.join(dirpath, f"{self._seq:08d}-{tag}.json")

    def _write(self, path: str, payload: dict) -> str:
        body = dict(payload)
        body["_crc"] = _payload_crc(payload)
        write_json_atomic(path, body)
        return path

    def _on_corrupt(self, box: str):
        def cb(name: str, reason: str) -> None:
            self.corrupt_files += 1
            if self.metrics is not None:
                self.metrics.counter("mailbox_corrupt_files_total").inc()
            j = self.journal
            if j is None:
                j = obs_journal.get_journal()
            j.emit(
                "mailbox_corrupt",
                mailbox="fleet",
                box=box,
                file=name,
                reason=reason,
                action="quarantined",
            )

        return cb

    # -- router side -------------------------------------------------------

    def submit(self, payload: dict) -> None:
        failpoints.fire("fleet.submit")
        path = self._write(
            self._next(self.inbox, payload.get("trace", "req")), payload
        )
        failpoints.tear("fleet.submit", path)

    def control(self, payload: dict) -> None:
        """Control messages ride the same FIFO stream as requests, so a
        swap lands AFTER everything routed before it."""
        self._write(
            self._next(self.inbox, f"ctl-{payload.get('control')}"), payload
        )

    def poll_results(self) -> list[dict]:
        return _read_dir(self.outbox, self._on_corrupt("outbox"))

    def clear_inbox(self) -> None:
        """Drop undelivered requests (before relaunching a replica: the
        router re-routes its in-flight itself; a fresh incarnation must
        not re-serve work that already failed over elsewhere)."""
        for name in os.listdir(self.inbox):
            try:
                os.remove(os.path.join(self.inbox, name))
            except OSError:  # pragma: no cover
                pass
        resilience.sweep_tmp_orphans(self.inbox, age_s=self.orphan_age_s)

    # -- replica side ------------------------------------------------------

    def take_inbox(self) -> list[dict]:
        return _read_dir(self.inbox, self._on_corrupt("inbox"))

    def put_result(self, payload: dict) -> None:
        failpoints.fire("fleet.result")
        path = self._write(
            self._next(self.outbox, payload.get("trace", "res")), payload
        )
        failpoints.tear("fleet.result", path)


def _np_dtype(name: str):
    """Resolve a dtype name, reaching into ml_dtypes for the storage
    dtypes numpy alone does not know (fp8 variants, bfloat16)."""
    import numpy as np

    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


class MigrationStore:
    """Shared directory of KV-migration posts (round 23,
    docs/serving.md §disaggregation): one CRC-enveloped npz file per
    prefill→decode handoff. File layout: a canonical-JSON header line
    ``{"meta":…, "tokens":…, "trace":…, "crc":…, "nbytes":…}`` followed
    by the raw npz bytes of the KV-block arrays — the CRC covers the npz
    body, so a torn write (truncated past the atomic commit by the
    ``fleet.migrate`` failpoint, or real storage rot) is detected at
    LOAD and quarantined once: removed, counted in ``corrupt_files``,
    journaled as ``mailbox_corrupt`` with ``mailbox="migrate"`` —
    never delivered and never re-read forever (the round-19 discipline).
    The importer does NOT delete a loaded post: the ROUTER owns the
    file's lifetime (removed when the request is terminal), so a decode
    replica dying mid-stream re-imports the same post on failover.

    jax-free; numpy is imported lazily (the router constructs the store
    but only replica workers move arrays through it)."""

    def __init__(self, root: str, *, journal=None, metrics=None):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.journal = journal
        self.metrics = metrics
        self.corrupt_files = 0
        resilience.sweep_tmp_orphans(root, age_s=60.0)

    def path(self, name: str) -> str:
        return os.path.join(self.root, name)

    def post(self, name: str, payload: dict) -> str:
        """Commit one migration post atomically (tmp + ``os.replace``).
        ``payload`` is a ``TextServer.take_export`` dict: ``arrays``
        (name → ndarray), ``meta``, ``tokens``, ``trace``. Raises
        OSError (incl. FailpointError) on failure — the caller falls
        back to migration-less handoff, never loses the request."""
        import io

        import numpy as np

        failpoints.fire("fleet.migrate")
        arrays: dict = {}
        exotic: dict = {}
        for k, v in payload["arrays"].items():
            a = np.asarray(v)
            if a.dtype.kind == "V":
                # ml_dtypes storage dtypes (fp8/bf16) do not survive
                # np.savez (they load back as opaque void) — ship the
                # raw bytes as uint8 and rebuild from the header's
                # dtype+shape at load (the round-17 mailbox discipline).
                exotic[k] = {"dtype": a.dtype.name, "shape": list(a.shape)}
                a = np.frombuffer(a.tobytes(), np.uint8)
            arrays[k] = a
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        body = buf.getvalue()
        head = {
            "meta": payload["meta"],
            "tokens": [int(t) for t in payload["tokens"]],
            "trace": payload.get("trace"),
            "crc": resilience._crc32c_bytes(body),
            "nbytes": len(body),
        }
        if exotic:
            head["exotic"] = exotic
        path = self.path(name)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(json.dumps(head, sort_keys=True).encode("utf-8"))
            f.write(b"\n")
            f.write(body)
        os.replace(tmp, path)
        failpoints.tear("fleet.migrate", path)
        return name

    def load(self, name: str) -> dict | None:
        """Read + verify one post. Returns the payload dict (arrays
        rehydrated), or None when the file is missing (already cleaned
        up) OR corrupt — corrupt commits are quarantined once, and the
        caller's contract is the same either way: fall back to
        re-prefill from the tokens+config that travel with the request
        (zero loss, round-19 stance)."""
        import io

        import numpy as np

        path = self.path(name)
        try:
            with open(path, "rb") as f:
                header = f.readline()
                body = f.read()
        except OSError:
            return None
        try:
            head = json.loads(header)
            if len(body) != int(head["nbytes"]) or (
                resilience._crc32c_bytes(body) != head["crc"]
            ):
                raise ValueError("crc/size mismatch")
            with np.load(io.BytesIO(body)) as z:
                arrays = {k: z[k] for k in z.files}
            for k, spec in (head.get("exotic") or {}).items():
                arrays[k] = np.frombuffer(
                    arrays[k].tobytes(), _np_dtype(spec["dtype"])
                ).reshape(spec["shape"])
        except (ValueError, KeyError, TypeError) as exc:
            self._quarantine(name, path, f"{type(exc).__name__}")
            return None
        return {
            "arrays": arrays,
            "meta": head["meta"],
            "tokens": head["tokens"],
            "trace": head.get("trace"),
        }

    def remove(self, name: str) -> None:
        try:
            os.remove(self.path(name))
        except OSError:
            pass

    def _quarantine(self, name: str, path: str, reason: str) -> None:
        try:
            os.remove(path)
        except OSError:  # pragma: no cover
            pass
        self.corrupt_files += 1
        if self.metrics is not None:
            self.metrics.counter("mailbox_corrupt_files_total").inc()
        j = self.journal if self.journal is not None else (
            obs_journal.get_journal()
        )
        j.emit(
            "mailbox_corrupt",
            mailbox="migrate",
            box="migrate",
            file=name,
            reason=reason,
            action="quarantined",
        )


# ---------------------------------------------------------------------------
# The router.
# ---------------------------------------------------------------------------


class _FleetRequest:
    __slots__ = (
        "rid", "trace", "tokens", "config", "deadline", "deadline_s",
        "t_submit", "replica", "attempts", "done", "cancelled", "failed",
        "shed", "priority", "out", "t_done", "t_routed",
        "leg", "resume_post", "prefill_replica", "leg1_tokens",
    )

    def __init__(self, rid, trace, tokens, config, deadline, deadline_s,
                 now, priority=0):
        self.rid = rid
        self.trace = trace
        self.tokens = tokens
        self.config = config
        self.deadline = deadline  # absolute, router clock; None = none
        self.deadline_s = deadline_s
        self.t_submit = now
        self.replica: str | None = None
        self.attempts = 0  # times (re)routed
        self.done = False
        self.cancelled = False
        self.failed: str | None = None  # terminal rejection (error text)
        self.shed = False  # dropped before any route/prefill (round 21)
        self.priority = priority  # int >= 0; higher = more important
        self.out: list[int] | None = None
        self.t_done: float | None = None
        self.t_routed: float | None = None  # last route, breaker timeout
        # Disaggregated two-leg lifecycle (round 23): "single" in a
        # homogeneous fleet (byte-identical round-21 path); a role fleet
        # routes leg "prefill" first, then — after the prefill replica's
        # migrated result — leg "decode" with the migration post.
        self.leg = "single"
        self.resume_post: str | None = None  # migration post filename
        self.prefill_replica: str | None = None
        self.leg1_tokens: list[int] | None = None

    @property
    def terminal(self) -> bool:
        return (
            self.done or self.cancelled or self.shed
            or self.failed is not None
        )


class ReplicaHandle:
    """One replica under router supervision: the elastic agent (process
    lifecycle), the mailbox client (transport), the /healthz probe
    (verdicts + routing signals), and the router-side supervision state —
    ``starting`` (spawned, health not yet confirmed), ``up``, ``backoff``
    (dead, relaunch scheduled), ``benched`` (restart budget exhausted).
    ``agent``/``health`` are optional so the fast-tier tests drive the
    whole state machine with fakes (the test_elastic.py pattern)."""

    def __init__(
        self,
        name: str,
        *,
        client,
        agent: ElasticAgent | None = None,
        health: HttpHealth | None = None,
        role: str = "both",
    ):
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"unknown replica role {role!r}; prefill|decode|both"
            )
        self.name = name
        self.client = client
        self.agent = agent
        self.health = health
        # Round-23 disaggregation: which leg(s) this replica serves.
        # "both" everywhere = the homogeneous fleet, bitwise round 21.
        self.role = role
        self.state = "starting"
        self.attempts = 0  # restarts charged
        self.relaunch_at: float | None = None
        self.backoff_s = 0.0
        self.inflight: dict[str, _FleetRequest] = {}
        self.cooldown_until = 0.0  # QueueFull backpressure hold-off
        self._next_probe = 0.0
        # Round-21 circuit breaker (routing layer, independent of the
        # supervision states above): closed / open / half_open.
        self.breaker = "closed"
        self.breaker_failures = 0  # consecutive route failures
        self.breaker_until = 0.0  # open -> half_open at this clock
        self.breaker_probe: str | None = None  # the half-open probe trace

    def breaker_reset(self) -> None:
        self.breaker = "closed"
        self.breaker_failures = 0
        self.breaker_until = 0.0
        self.breaker_probe = None

    @property
    def can_prefill(self) -> bool:
        return self.role in ("prefill", "both")

    @property
    def can_decode(self) -> bool:
        return self.role in ("decode", "both")

    @property
    def routable(self) -> bool:
        if self.state != "up":
            return False
        if self.breaker == "open":
            return False
        if self.breaker == "half_open" and self.breaker_probe is not None:
            return False  # one probe at a time
        doc = self.health.last if self.health is not None else None
        return not (doc and doc.get("draining"))


class ReplicaRouter:
    """N serving replicas behind one submit/result surface (module
    docstring for the full contract). Drive with :meth:`step` ticks (or
    :meth:`run_until_done`); ``clock``/``sleep``/``rng`` are injectable
    so the fast-tier tests run the state machine without wall time,
    processes, or sockets."""

    def __init__(
        self,
        replicas: Sequence[ReplicaHandle],
        *,
        min_replicas: int = 1,
        max_restarts: int = 2,
        backoff: float = 1.0,
        max_backoff: float = 30.0,
        jitter: float = 0.25,
        affinity_tokens: int = 16,
        affinity_cap: int = 4096,
        spill_threshold: float = 0.75,
        max_reroutes: int = 8,
        breaker_failures: int = 3,
        breaker_reset_s: float = 5.0,
        route_timeout_s: float | None = None,
        migrate_dir: str | None = None,
        prefix_block_tokens: int = 16,
        migrate_threshold: int | None = None,
        probe_interval_s: float = 0.5,
        poll_interval: float = 0.05,
        journal=None,
        metrics: MetricsRegistry | None = None,
        print_fn=print,
        clock=time.monotonic,
        sleep=time.sleep,
        rng=None,
    ):
        self.replicas = {h.name: h for h in replicas}
        if len(self.replicas) != len(replicas):
            raise ValueError("replica names must be unique")
        # Mailbox corruption events (round 19) ride the router's journal
        # unless a client already has its own (fakes lack the attr).
        for h in replicas:
            client = getattr(h, "client", None)
            if (
                client is not None
                and hasattr(client, "journal")
                and client.journal is None
                and journal is not None
            ):
                client.journal = journal
        self.min_replicas = int(min_replicas)
        if not 1 <= self.min_replicas <= len(replicas):
            raise ValueError(
                f"min_replicas must be in [1, {len(replicas)}], got "
                f"{min_replicas}"
            )
        self.max_restarts = int(max_restarts)
        self.backoff = float(backoff)
        self.max_backoff = float(max_backoff)
        self.jitter = float(jitter)
        self.affinity_tokens = int(affinity_tokens)
        self.affinity_cap = int(affinity_cap)
        self.spill_threshold = float(spill_threshold)
        self.max_reroutes = int(max_reroutes)
        self.breaker_failures = int(breaker_failures)
        self.breaker_reset_s = float(breaker_reset_s)
        # None (the default) disarms route-timeout detection entirely —
        # the round-16 path, byte-identical.
        self.route_timeout_s = (
            None if route_timeout_s is None else float(route_timeout_s)
        )
        self.probe_interval_s = float(probe_interval_s)
        self.poll_interval = float(poll_interval)
        self.journal = (
            journal if journal is not None else obs_journal.get_journal()
        )
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # Round-21 satellite: mailbox corruption counters ride the
        # router's registry (mailbox_corrupt_files_total) so dashboards
        # see rot, not a "silent replica" (docs/known_issues.md).
        for h in replicas:
            client = getattr(h, "client", None)
            if (
                client is not None
                and hasattr(client, "metrics")
                and client.metrics is None
            ):
                client.metrics = self.metrics
        self.print_fn = print_fn
        self.clock = clock
        self.sleep = sleep
        self.rng = rng
        # Per-priority-class queues (round 21). All-default traffic lives
        # in class 0 and dequeues exactly like the old single FIFO deque:
        # rids are monotone and every requeue is an appendleft of rids
        # lower than anything behind them, so FIFO order IS rid order and
        # the EDF key (deadline-or-inf, rid) degenerates to the head.
        self._queues: dict[int, deque[_FleetRequest]] = {}
        self._drr: dict[int, float] = {}  # deficit round-robin credits
        # Fleet per-token seconds (EWMA over completed requests): the
        # router-side "provably cannot finish" shed predicate's only
        # evidence. None until the first completion — the router never
        # sheds on a guess, only on expiry, before then.
        self._tok_ewma: float | None = None
        self._by_rid: dict[int, _FleetRequest] = {}
        self._by_trace: dict[str, _FleetRequest] = {}
        self._affinity: dict[tuple, str] = {}
        self._next_rid = 0
        self._started = False
        self._draining = False
        # Round-23 disaggregation: the two-leg lifecycle arms only when
        # a role-specialized replica exists — an all-"both" fleet keeps
        # the round-21 single-leg path (and its sticky affinity map)
        # bitwise. In a role fleet the sticky map is PROMOTED to a
        # fleet-wide radix-prefix index: routing sees which replica
        # holds which warm prefix (beliefs registered at route time,
        # dropped on death/relaunch/swap) before choosing the prefill
        # leg.
        self._two_leg = any(h.role != "both" for h in replicas)
        # Length-threshold routing (the DistServe-style policy knob):
        # prompts SHORTER than ``migrate_threshold`` tokens skip the
        # two-leg path and serve whole on a decode-capable replica —
        # the handoff only pays for itself when the prefill is long
        # enough to stall a decode batch. None (default) sends every
        # first leg through the prefill pool (the round-23 base path;
        # an all-"both" fleet ignores the knob entirely).
        self.migrate_threshold = (
            None if migrate_threshold is None else int(migrate_threshold)
        )
        self._migrate = (
            MigrationStore(migrate_dir, journal=self.journal,
                           metrics=self.metrics)
            if migrate_dir is not None
            else None
        )
        self._prefix_index = None
        if self._two_leg:
            from distributed_tensorflow_tpu.serve_pool import (
                FleetPrefixIndex,
            )

            self._prefix_index = FleetPrefixIndex(
                block_size=int(prefix_block_tokens)
            )
            self.journal.emit(
                "fleet_roles",
                roles={h.name: h.role for h in replicas},
                migrate_dir=migrate_dir,
            )
        # The checkpoint directory the fleet currently serves when a
        # swap ever pointed it AWAY from the replicas' spawn-time
        # default; re-sent to every replica as it comes (back) up, so a
        # relaunch cannot quietly revert to stale weights. Same-dir
        # swaps need none of this: a restarting replica restores the
        # newest CRC-verified step of its own directory anyway.
        self.current_checkpoint_dir: str | None = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Spawn every replica (no-op for externally-managed handles)."""
        if self._started:
            return
        self._started = True
        for h in self.replicas.values():
            if h.agent is not None:
                h.agent.start()
            if h.health is None:
                h.state = "up"  # nothing to confirm: trust the spawn
        self.metrics.gauge("replicas_total").set(len(self.replicas))

    def submit(
        self, tokens, config=None, *, deadline_s=None, priority: int = 0
    ) -> int:
        """Queue one request fleet-wide. ``config`` is a GenerationConfig
        dataclass or a plain dict of its fields (the router is jax-free
        and never imports the engine); the FULL config travels with the
        request so a failover re-serves the identical stream. Returns a
        router-scope request id for :meth:`result`.

        Round 21: ``priority`` picks the request's class queue (higher =
        more important, weighted-fair dequeue); a request that arrives
        with its deadline already spent is shed HERE — terminal
        :class:`~serve_pool.RequestShed`, never queued, never routed."""
        if self._draining:
            raise RuntimeError("router is draining: admission closed")
        if dataclasses.is_dataclass(config) and not isinstance(config, type):
            config = dataclasses.asdict(config)
        config = dict(config or {})
        unknown = sorted(set(config) - set(CONFIG_KEYS))
        if unknown:
            raise ValueError(
                f"unknown generation config keys {unknown}; valid: "
                f"{list(CONFIG_KEYS)}"
            )
        priority = int(priority)
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("empty prompt")
        now = self.clock()
        rid = self._next_rid
        self._next_rid += 1
        trace = tracing.new_trace_id()
        req = _FleetRequest(
            rid, trace, tokens, config,
            None if deadline_s is None else now + float(deadline_s),
            deadline_s, now, priority,
        )
        self._by_rid[rid] = req
        self._by_trace[trace] = req
        if deadline_s is not None and float(deadline_s) <= 0.0:
            # Arrived dead: shed at submit — it must never occupy queue
            # space or cost a route (round-21 satellite).
            self.metrics.counter("fleet_submitted_total").inc()
            self._emit_submit(req)
            self._shed(req, now, reason="expired_at_submit")
            return rid
        self._enqueue(req)
        self.metrics.counter("fleet_submitted_total").inc()
        self._emit_submit(req)
        return rid

    def _emit_submit(self, req: _FleetRequest) -> None:
        # The priority field appears ONLY when non-zero: default-path
        # journals stay byte-identical to round 16.
        self.journal.emit(
            "request_submit",
            rid=req.rid,
            trace=req.trace,
            prompt_len=len(req.tokens),
            max_new=int(req.config.get("max_new", 64)),
            greedy=bool(req.config.get("greedy", True)),
            **({"priority": req.priority} if req.priority else {}),
        )

    # -- per-class queues (round 21) ---------------------------------------

    def _enqueue(self, req: _FleetRequest) -> None:
        self._queues.setdefault(req.priority, deque()).append(req)

    def _requeue_front(self, req: _FleetRequest) -> None:
        self._queues.setdefault(req.priority, deque()).appendleft(req)

    def _queue_len(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _queued(self):
        for p in sorted(self._queues, reverse=True):
            yield from self._queues[p]

    def step(self) -> bool:
        """One router tick: collect results (every mailbox, dead
        replicas included — committed results survive their writer),
        supervise (verdicts → failover + relaunch scheduling), relaunch
        due members, shed overdue queued requests, route. Returns True
        while requests are outstanding."""
        if not self._started:
            self.start()
        now = self.clock()
        self._collect()
        self._breaker_scan(now)
        self._supervise(now)
        self._relaunch_due(now)
        self._shed_overdue(now)
        self._route(now)
        return not self.done_all()

    def wait_until_up(
        self, n: int | None = None, *, timeout_s: float = 600.0
    ) -> None:
        """Block until ``n`` replicas (default: all) have confirmed a
        good /healthz — the readiness gate between spawning a fleet and
        pointing traffic at it (replica startup is a jax import + restore
        + first compile; measuring it into TTFT would misstate serving)."""
        want = len(self.replicas) if n is None else int(n)
        deadline = self.clock() + timeout_s
        while True:
            self.step()
            up = sum(h.state == "up" for h in self.replicas.values())
            if up >= want:
                return
            if self.clock() > deadline:
                raise TimeoutError(
                    f"only {up}/{want} replicas up after {timeout_s}s "
                    f"({ {h.name: h.state for h in self.replicas.values()} })"
                )
            self.sleep(self.poll_interval)

    def done_all(self) -> bool:
        return self._queue_len() == 0 and all(
            r.terminal for r in self._by_rid.values()
        )

    def run_until_done(self, *, timeout_s: float | None = None) -> None:
        deadline = None if timeout_s is None else self.clock() + timeout_s
        while self.step():
            if deadline is not None and self.clock() > deadline:
                raise TimeoutError(
                    f"fleet did not finish within {timeout_s}s "
                    f"({self.stats()})"
                )
            self.sleep(self.poll_interval)

    def done(self, rid: int) -> bool:
        return self._by_rid[rid].terminal

    def result(self, rid: int) -> list[int]:
        """The served stream (router copy; consumes the record). Raises
        the same typed :class:`~serve_pool.RequestCancelled` as
        ``TextServer.result`` for a deadline-cancelled request,
        :class:`~serve_pool.RequestShed` for one the scheduler dropped
        before routing/prefill, and a RuntimeError naming the replica's
        error for a terminally rejected one."""
        req = self._by_rid[rid]
        if req.shed:
            del self._by_rid[rid]
            self._by_trace.pop(req.trace, None)
            raise RequestShed(
                f"request {rid} was shed before serving (deadline "
                "unreachable or displaced under overload)"
            )
        if req.cancelled:
            del self._by_rid[rid]
            self._by_trace.pop(req.trace, None)
            raise RequestCancelled(
                f"request {rid} was cancelled (deadline)"
            )
        if req.failed is not None:
            del self._by_rid[rid]
            self._by_trace.pop(req.trace, None)
            raise RuntimeError(f"request {rid} was rejected: {req.failed}")
        if not req.done:
            raise RuntimeError(f"request {rid} is not finished")
        del self._by_rid[rid]
        self._by_trace.pop(req.trace, None)
        return list(req.out)

    def generate(self, prompts, configs=None, *, timeout_s=None):
        """Submit a batch and serve it to completion (bench/test sugar)."""
        if configs is None or isinstance(configs, dict) or (
            dataclasses.is_dataclass(configs) and not isinstance(configs, type)
        ):
            configs = [configs] * len(prompts)
        rids = [
            self.submit(p, c) for p, c in zip(prompts, configs, strict=True)
        ]
        self.run_until_done(timeout_s=timeout_s)
        return [self.result(r) for r in rids]

    def swap_weights(self, checkpoint_dir: str | None = None) -> None:
        """Tell every live replica to adopt the newest CRC-verified
        checkpoint (optionally from a new directory) between chunk
        boundaries — the publish step of train→publish→serve. Each
        replica swaps independently; residents finish on old weights."""
        if checkpoint_dir is not None:
            self.current_checkpoint_dir = checkpoint_dir
        targets = [
            h for h in self.replicas.values() if h.state != "benched"
        ]
        for h in targets:
            payload: dict = {"control": "swap"}
            if checkpoint_dir is not None:
                payload["checkpoint_dir"] = checkpoint_dir
            h.client.control(payload)
            if self._prefix_index is not None:
                # The swap flushes the replica's radix (stale-weights
                # K/V): forget the fleet-level beliefs with it.
                self._prefix_index.drop_replica(h.name)
        self.journal.emit(
            "weight_swap_requested",
            source=checkpoint_dir,
            replicas=[h.name for h in targets],
        )

    def drain(self, *, timeout_s: float | None = None) -> None:
        """Close router admission and serve everything outstanding."""
        self._draining = True
        self.run_until_done(timeout_s=timeout_s)

    def shutdown(self) -> None:
        """Stop the fleet: ask every replica to exit its loop (graceful —
        the worker drains residents first), then reap/kill."""
        for h in self.replicas.values():
            try:
                h.client.control({"control": "stop"})
            except OSError:  # pragma: no cover — mailbox dir removed
                pass
        deadline = self.clock() + 30.0
        for h in self.replicas.values():
            if h.agent is None:
                continue
            while h.agent.poll() is None and self.clock() < deadline:
                self.sleep(self.poll_interval)
            h.agent.kill()
        self.journal.flush()

    def stats(self) -> dict:
        reqs = list(self._by_rid.values())
        return {
            "submitted": self._next_rid,
            "done": sum(r.done for r in reqs),
            "cancelled": sum(r.cancelled for r in reqs),
            "shed": sum(r.shed for r in reqs),
            "failed": sum(r.failed is not None for r in reqs),
            "queued": self._queue_len(),
            "inflight": sum(
                len(h.inflight) for h in self.replicas.values()
            ),
            "failovers": int(
                self.metrics.counter("failovers_total").value
            ),
            "reroutes": int(self.metrics.counter("reroutes_total").value),
            "replicas": {
                h.name: h.state for h in self.replicas.values()
            },
        }

    # -- the state machine -------------------------------------------------

    def _collect(self) -> None:
        for h in self.replicas.values():
            for payload in h.client.poll_results():
                # Any collected payload proves the mailbox round-trip is
                # alive: reset the breaker's consecutive-failure count
                # (and close it, if a half-open probe just came back).
                self._breaker_success(h)
                trace = payload.get("trace")
                # Pop BEFORE the dedupe check: a duplicate result (the
                # request already completed elsewhere) must still clear
                # this replica's inflight entry, or phantom load
                # accumulates and the replica reads saturated forever.
                h.inflight.pop(trace, None)
                req = self._by_trace.get(trace)
                if req is None or req.terminal:
                    continue  # dedupe: first terminal result won
                if payload.get("rejected"):
                    # A stale bounce (the request already failed over to
                    # another replica) must not re-queue a request that
                    # is live elsewhere — only the current owner's
                    # rejection counts. Stale COMPLETED results below
                    # are different: a committed stream is valid
                    # whoever serves the request now (first wins).
                    if req.replica == h.name:
                        self._rejected(h, req, payload)
                elif payload.get("cancelled"):
                    req.cancelled = True
                    req.t_done = self.clock()
                    self._cleanup_post(req)
                    self.metrics.counter("fleet_cancelled_total").inc()
                    self.journal.emit(
                        "fleet_result",
                        trace=trace,
                        rid=req.rid,
                        replica=h.name,
                        status="cancelled",
                    )
                elif payload.get("shed"):
                    # The replica's own scheduler shed it (queued there
                    # past its deadline / displaced under saturation).
                    req.shed = True
                    req.t_done = self.clock()
                    self._cleanup_post(req)
                    self.metrics.counter("fleet_shed_total").inc()
                    self.journal.emit(
                        "fleet_result",
                        trace=trace,
                        rid=req.rid,
                        replica=h.name,
                        status="shed",
                    )
                elif payload.get("migrated"):
                    # Leg 1 (prefill + first token) finished: schedule
                    # the decode leg under the SAME trace/rid. A failed
                    # post (post=None — the fleet.migrate failpoint, a
                    # full disk) degrades to re-prefill on the decode
                    # replica: slower, never lost. Only the current
                    # owner's report counts (stale-bounce rule above).
                    if req.replica == h.name:
                        req.leg = "decode"
                        req.resume_post = payload.get("post")
                        req.prefill_replica = h.name
                        req.leg1_tokens = [
                            int(t) for t in payload.get("tokens", [])
                        ]
                        req.replica = None
                        self.metrics.counter("fleet_migrations_total").inc()
                        self.journal.emit(
                            "request_migrated",
                            trace=trace,
                            rid=req.rid,
                            from_replica=h.name,
                            post=req.resume_post,
                            blocks=payload.get("blocks"),
                            nbytes=payload.get("nbytes"),
                        )
                        self._requeue_front(req)
                else:
                    req.out = [int(t) for t in payload.get("tokens", [])]
                    req.done = True
                    req.t_done = self.clock()
                    self._cleanup_post(req)
                    if req.out and req.t_routed is not None:
                        # Route-to-result seconds per emitted token: the
                        # hopeless-shed predicate's evidence. Includes
                        # replica-side queueing by design — that IS the
                        # completion-time a queued request faces.
                        inst = max(req.t_done - req.t_routed, 0.0) / len(
                            req.out
                        )
                        self._tok_ewma = (
                            inst
                            if self._tok_ewma is None
                            else 0.8 * self._tok_ewma + 0.2 * inst
                        )
                    self.metrics.counter("fleet_completions_total").inc()
                    self.journal.emit(
                        "fleet_result",
                        trace=trace,
                        rid=req.rid,
                        replica=h.name,
                        status="done",
                        tokens=len(req.out),
                        latency_s=round(req.t_done - req.t_submit, 6),
                        reroutes=max(req.attempts - 1, 0),
                    )

    def _cleanup_post(self, req: _FleetRequest) -> None:
        """The router owns a migration post's lifetime: remove it once
        its request is terminal (a decode-leg failover before then
        re-imports the SAME post — that is why the importer never
        deletes)."""
        if req.resume_post is not None and self._migrate is not None:
            self._migrate.remove(req.resume_post)

    def _rejected(self, h: ReplicaHandle, req, payload: dict) -> None:
        """A replica bounced the request. QueueFull is pure BACKPRESSURE:
        re-queue, cool the replica for a probe interval (the health doc
        the router routed on was stale), and charge NO budget — a
        saturated-but-healthy fleet holds requests, it never fails them.
        PERMANENT rejections (the replica's validation — geometry no
        replica will ever accept) and unknown rejection kinds past the
        re-route budget fail TERMINALLY: retrying a deterministic
        refusal forever would spin the router and never finish
        ``drain()``."""
        kind = payload.get("error_kind")
        if kind == "QueueFull":
            h.cooldown_until = self.clock() + self.probe_interval_s
            self.metrics.counter("reroutes_total").inc()
            self.journal.emit(
                "request_reroute",
                trace=req.trace,
                rid=req.rid,
                from_replica=h.name,
                attempt=req.attempts,
                reason="backpressure",
            )
            req.replica = None
            self._requeue_front(req)
            return
        permanent = kind in ("ValueError", "TypeError")
        if permanent or req.attempts > self.max_reroutes:
            req.failed = payload.get("error") or (
                f"routed {req.attempts} times (budget {self.max_reroutes})"
            )
            req.t_done = self.clock()
            self._cleanup_post(req)
            self.metrics.counter("fleet_failed_total").inc()
            self.journal.emit(
                "fleet_result",
                trace=req.trace,
                rid=req.rid,
                replica=h.name,
                status="rejected",
                error=req.failed,
            )
            return
        self.metrics.counter("reroutes_total").inc()
        self.journal.emit(
            "request_reroute",
            trace=req.trace,
            rid=req.rid,
            from_replica=h.name,
            attempt=req.attempts,
            reason="rejected",
        )
        req.replica = None
        self._requeue_front(req)  # older than anything queued behind it

    def _supervise(self, now: float) -> None:
        for h in self.replicas.values():
            if h.state not in ("starting", "up"):
                continue
            verdict = None
            rc = h.agent.poll() if h.agent is not None else None
            if rc is not None:
                # A serving replica has no legitimate self-exit while
                # supervised — rc 0 (a stop it was never sent) is as dead
                # as a SIGKILL.
                verdict = f"rc={rc}"
            elif h.health is not None and now >= h._next_probe:
                h._next_probe = now + self.probe_interval_s
                v = h.health.classify()
                if v != "ok":
                    verdict = v
                elif h.state == "starting" and h.health.last is not None:
                    h.state = "up"  # first good /healthz: routable
                    if self.current_checkpoint_dir is not None:
                        # Swap durability across relaunches: a fresh
                        # incarnation restored from its spawn-time
                        # directory and cleared its inbox — re-send the
                        # fleet's current serve dir (a replica already
                        # on it no-ops: swap_from_checkpoint adopts
                        # only NEWER steps).
                        h.client.control(
                            {
                                "control": "swap",
                                "checkpoint_dir":
                                    self.current_checkpoint_dir,
                            }
                        )
            if verdict is not None:
                self._fail(h, verdict)
        self.metrics.gauge("replicas_up").set(
            sum(h.state == "up" for h in self.replicas.values())
        )

    def _fail(self, h: ReplicaHandle, verdict: str) -> None:
        if h.agent is not None:
            h.agent.kill()  # stalled/health-dead: make the death real
        rerouted = [r for r in h.inflight.values() if not r.terminal]
        for req in reversed(rerouted):
            # Zero-loss re-admission: full config + the SAME trace id go
            # back to the queue front (original relative order kept), so
            # the retried stream is token-identical and the journal shows
            # one request across replicas. attempts counts ROUTES only
            # (incremented in _route) — one number, one meaning.
            req.replica = None
            self.metrics.counter("reroutes_total").inc()
            self.journal.emit(
                "request_reroute",
                trace=req.trace,
                rid=req.rid,
                from_replica=h.name,
                attempt=req.attempts,
                reason="replica_dead",
            )
            self._requeue_front(req)
        h.inflight.clear()
        h.breaker_reset()  # supervision owns the replica now
        if self._prefix_index is not None:
            # A dead replica's radix died with it: forget every warm-
            # prefix belief so the prefill leg stops preferring a ghost.
            self._prefix_index.drop_replica(h.name)
        h.attempts += 1
        self.metrics.counter("failovers_total").inc()
        lifecycle_event(
            "replica_dead",
            print_fn=self.print_fn,
            journal=self.journal,
            replica=h.name,
            verdict=verdict,
            rerouted=len(rerouted),
            attempt=h.attempts,
            max_restarts=self.max_restarts,
        )
        if h.attempts > self.max_restarts or h.agent is None:
            h.state = "benched"
            lifecycle_event(
                "replica_benched",
                print_fn=self.print_fn,
                journal=self.journal,
                replica=h.name,
                restarts=h.attempts,
                max_restarts=self.max_restarts,
            )
            active = [
                x for x in self.replicas.values() if x.state != "benched"
            ]
            if len(active) < self.min_replicas:
                lifecycle_event(
                    "fleet_below_floor",
                    print_fn=self.print_fn,
                    journal=self.journal,
                    replicas=len(active),
                    min_replicas=self.min_replicas,
                    cause=f"{h.name}={verdict}",
                )
                raise FleetBelowFloor({h.name: verdict})
        else:
            h.backoff_s = resilience.backoff_delay(
                h.attempts - 1,
                backoff=self.backoff,
                max_backoff=self.max_backoff,
                jitter=self.jitter,
                rng=self.rng,
            )
            h.state = "backoff"
            h.relaunch_at = self.clock() + h.backoff_s

    def _relaunch_due(self, now: float) -> None:
        for h in self.replicas.values():
            if h.state != "backoff" or now < (h.relaunch_at or 0.0):
                continue
            clear = getattr(h.client, "clear_inbox", None)
            if clear is not None:
                clear()  # stale routed work already failed over
            if h.health is not None:
                h.health.reset()  # fresh grace clock for the new process
            h.agent.start()
            h.state = "starting" if h.health is not None else "up"
            h.relaunch_at = None
            self.metrics.counter("relaunches_total").inc()
            lifecycle_event(
                "replica_relaunch",
                print_fn=self.print_fn,
                journal=self.journal,
                replica=h.name,
                attempt=h.attempts,
                max_restarts=self.max_restarts,
                backoff_s=h.backoff_s,
            )

    def _hopeless(self, req: _FleetRequest, now: float) -> bool:
        """Provably cannot finish: full remaining budget at the fleet's
        measured per-token pace overruns the slack. Conservative by
        construction — no EWMA yet means no verdict."""
        if req.deadline is None or self._tok_ewma is None:
            return False
        max_new = int(req.config.get("max_new", 64))
        return max_new * self._tok_ewma > req.deadline - now

    def _shed(self, req: _FleetRequest, now: float, *, reason: str) -> None:
        req.shed = True
        req.t_done = now
        self._cleanup_post(req)
        self.metrics.counter("fleet_shed_total").inc()
        self.journal.emit(
            "request_shed",
            rid=req.rid,
            trace=req.trace,
            priority=req.priority,
            reason=reason,
            age_s=round(now - req.t_submit, 6),
        )

    def _shed_overdue(self, now: float) -> None:
        """Router-side deadline enforcement for QUEUED requests (resident
        ones are cancelled replica-side and report back as cancelled).
        Round 21: a queued request past its deadline — or hopeless
        (:meth:`_hopeless`) — is SHED before a route is spent on it.
        A shed request is terminal: failover never resurrects it."""
        for prio in list(self._queues):
            q = self._queues[prio]
            if not any(
                r.deadline is not None
                and (now > r.deadline or self._hopeless(r, now))
                for r in q
            ):
                continue
            keep: deque[_FleetRequest] = deque()
            for req in q:
                if req.deadline is not None and now > req.deadline:
                    self._shed(req, now, reason="expired")
                elif self._hopeless(req, now):
                    self._shed(req, now, reason="hopeless")
                else:
                    keep.append(req)
            if keep:
                self._queues[prio] = keep
            else:
                del self._queues[prio]

    # -- circuit breaker (round 21) ----------------------------------------

    def _breaker_scan(self, now: float) -> None:
        """Per-replica circuit breaker: consecutive route timeouts open
        it, diverting routes IMMEDIATELY — before the slower HttpHealth
        verdict lands; after ``breaker_reset_s`` it half-opens and admits
        ONE probe; any collected result closes it (``_breaker_success``).
        Pure routing layer: no kill, no relaunch, nothing charged to the
        restart budget. ``route_timeout_s=None`` (default) disarms the
        timeout detector — round-16 behavior, byte-identical."""
        for h in self.replicas.values():
            if h.breaker == "open" and now >= h.breaker_until:
                h.breaker = "half_open"
                h.breaker_probe = None
                lifecycle_event(
                    "breaker_half_open",
                    print_fn=self.print_fn,
                    journal=self.journal,
                    replica=h.name,
                )
            if self.route_timeout_s is None or h.state != "up":
                continue
            timed_out = sorted(
                (
                    r
                    for r in h.inflight.values()
                    if not r.terminal
                    and r.t_routed is not None
                    and now - r.t_routed > self.route_timeout_s
                ),
                key=lambda r: r.rid,
            )
            for req in reversed(timed_out):
                h.inflight.pop(req.trace, None)
                req.replica = None
                self.metrics.counter("reroutes_total").inc()
                self.journal.emit(
                    "request_reroute",
                    trace=req.trace,
                    rid=req.rid,
                    from_replica=h.name,
                    attempt=req.attempts,
                    reason="route_timeout",
                )
                self._requeue_front(req)
            if timed_out:
                self._breaker_failure(
                    h, now, reason=f"{len(timed_out)} route timeout(s)"
                )

    def _breaker_failure(
        self, h: ReplicaHandle, now: float, *, reason: str
    ) -> None:
        h.breaker_failures += 1
        if h.breaker == "half_open":
            # The one probe failed: straight back to open.
            self._breaker_trip(h, now, reason=f"probe failed: {reason}")
        elif (
            h.breaker == "closed"
            and h.breaker_failures >= self.breaker_failures
        ):
            self._breaker_trip(h, now, reason=reason)

    def _breaker_trip(
        self, h: ReplicaHandle, now: float, *, reason: str
    ) -> None:
        h.breaker = "open"
        h.breaker_until = now + self.breaker_reset_s
        h.breaker_probe = None
        self.metrics.counter("breaker_opens_total").inc()
        lifecycle_event(
            "breaker_open",
            print_fn=self.print_fn,
            journal=self.journal,
            replica=h.name,
            failures=h.breaker_failures,
            reason=reason,
            reset_s=self.breaker_reset_s,
        )
        # Divert everything still routed there: the breaker's whole
        # point is not leaving work parked on a suspect replica until
        # the health verdict. Dedupe-on-trace keeps a late committed
        # result valid (first terminal wins), so diverting early is
        # free of double-serve risk.
        stuck = sorted(
            (r for r in h.inflight.values() if not r.terminal),
            key=lambda r: r.rid,
        )
        for req in reversed(stuck):
            h.inflight.pop(req.trace, None)
            req.replica = None
            self.metrics.counter("reroutes_total").inc()
            self.journal.emit(
                "request_reroute",
                trace=req.trace,
                rid=req.rid,
                from_replica=h.name,
                attempt=req.attempts,
                reason="breaker_open",
            )
            self._requeue_front(req)

    def _breaker_success(self, h: ReplicaHandle) -> None:
        h.breaker_failures = 0
        h.breaker_probe = None
        if h.breaker != "closed":
            h.breaker = "closed"
            lifecycle_event(
                "breaker_close",
                print_fn=self.print_fn,
                journal=self.journal,
                replica=h.name,
            )

    def _saturated(self, h: ReplicaHandle) -> bool:
        if self.clock() < h.cooldown_until:
            return True  # it just bounced a request: let it drain a beat
        doc = h.health.last if h.health is not None else None
        if not doc:
            return False
        sat = doc.get("queue_saturation")
        if isinstance(sat, (int, float)) and sat >= self.spill_threshold:
            return True
        lim = doc.get("queue_limit")
        if lim:
            # Router-side view: everything we routed and have not seen a
            # result for occupies a slot or a queue position there.
            return len(h.inflight) >= int(doc.get("slots", 0)) + int(lim)
        return False

    def _affinity_key(self, req: _FleetRequest):
        if self.affinity_tokens <= 0:
            return None
        return tuple(req.tokens[: self.affinity_tokens])

    def _pick(self, req: _FleetRequest) -> ReplicaHandle | None:
        routable = [h for h in self.replicas.values() if h.routable]
        if not routable:
            return None
        if self._two_leg:
            return self._pick_role(req, routable)
        key = self._affinity_key(req)
        if key is not None:
            sticky = self.replicas.get(self._affinity.get(key, ""), None)
            if (
                sticky is not None
                and sticky.routable
                and not self._saturated(sticky)
            ):
                self._affinity.pop(key, None)  # LRU refresh on hit
                self._affinity[key] = sticky.name
                return sticky
        open_ = [h for h in routable if not self._saturated(h)]
        if not open_:
            return None  # whole fleet saturated: hold at the router
        pick = min(open_, key=lambda h: len(h.inflight))
        if key is not None:
            # (Re)stick the prefix to the replica now warming its radix —
            # a dead sticky target is reassigned here, not mourned. The
            # map is LRU-bounded: unique-prompt traffic must not grow a
            # long-lived router's memory without limit.
            self._affinity.pop(key, None)
            self._affinity[key] = pick.name  # newest at the end
            while len(self._affinity) > self.affinity_cap:
                self._affinity.pop(next(iter(self._affinity)))
        return pick

    def _pick_role(
        self, req: _FleetRequest, routable: list[ReplicaHandle]
    ) -> ReplicaHandle | None:
        """Role-aware pick for disaggregated fleets (round 23). The leg
        decides the candidate pool (prefill-capable for the first leg,
        decode-capable for the resumed one); when no capable replica is
        routable, ANY routable replica serves the request whole — roles
        are scheduling policy, every replica runs the full engine, so a
        degraded fleet stays correct, just un-specialized. The prefill
        leg prefers the replica the fleet-wide prefix index says holds
        the deepest warm prefix, provided it is in the open pool. With
        ``migrate_threshold`` set, a first leg whose prompt is shorter
        than the threshold targets the DECODE pool instead — it serves
        whole where it would decode anyway, skipping a handoff that
        costs more than the prefill it would offload."""
        short = (
            req.leg != "decode"
            and self.migrate_threshold is not None
            and len(req.tokens) < self.migrate_threshold
        )
        want = (
            (lambda h: h.can_decode)
            if req.leg == "decode" or short
            else (lambda h: h.can_prefill)
        )
        pool = [h for h in routable if want(h)] or routable
        open_ = [h for h in pool if not self._saturated(h)]
        if not open_:
            return None  # capable pool saturated: hold at the router
        if req.leg != "decode" and self._prefix_index is not None:
            name, depth = self._prefix_index.lookup(req.tokens)
            if depth > 0 and name is not None:
                warm = self.replicas.get(name)
                if warm is not None and warm in open_:
                    return warm
        return min(open_, key=lambda h: len(h.inflight))

    def _next_queued(self) -> tuple[int, int] | None:
        """(priority, index) of the next dequeue candidate: weighted-fair
        ACROSS classes (deficit round robin, weight ``priority+1`` — low
        classes always progress, high classes get the larger share;
        replenished classes serve highest-first), earliest-deadline-first
        WITHIN a class (key ``(deadline-or-inf, rid)``; all-default
        traffic degenerates to the FIFO head — see the ``_queues``
        comment in ``__init__``)."""
        classes = sorted(
            (p for p, q in self._queues.items() if q), reverse=True
        )
        if not classes:
            return None
        if len(classes) == 1:
            prio = classes[0]
        else:
            funded = [p for p in classes if self._drr.get(p, 0.0) >= 1.0]
            if not funded:
                self._drr = {
                    p: self._drr.get(p, 0.0) + (p + 1) for p in classes
                }
                funded = classes
            prio = funded[0]
        q = self._queues[prio]
        idx = min(
            range(len(q)),
            key=lambda i: (
                math.inf if q[i].deadline is None else q[i].deadline,
                q[i].rid,
            ),
        )
        return prio, idx

    def _route(self, now: float) -> None:
        while True:
            nxt = self._next_queued()
            if nxt is None:
                return
            prio, idx = nxt
            q = self._queues[prio]
            req = q[idx]
            if req.terminal:
                # Became terminal while queued (a dead replica's
                # committed result arrived after the failover re-queue):
                # routing it again would re-serve a DONE request.
                del q[idx]
                if not q:
                    del self._queues[prio]
                continue
            if self._two_leg and req.leg == "single":
                req.leg = "prefill"  # first leg of a disaggregated request
            h = self._pick(req)
            if h is None:
                return
            # Charge DRR credit only while classes actually compete — a
            # lone class dequeues by the fast path above and must not
            # accumulate debt against classes that appear later.
            contested = sum(1 for qq in self._queues.values() if qq) > 1
            del q[idx]
            if not q:
                del self._queues[prio]
            if contested:
                self._drr[prio] = self._drr.get(prio, 0.0) - 1.0
            req.replica = h.name
            req.attempts += 1
            req.t_routed = now
            h.inflight[req.trace] = req
            if h.breaker == "half_open":
                h.breaker_probe = req.trace  # the one probe in flight
            payload = {
                "trace": req.trace,
                "tokens": req.tokens,
                "config": req.config,
            }
            if req.priority:
                payload["priority"] = req.priority
            if req.deadline is not None:
                payload["deadline_s"] = max(req.deadline - now, 0.0)
            if req.leg == "prefill" and h.role == "prefill":
                # Migrate only off a prefill-SPECIALIZED replica — a
                # "both" (or fallback decode) target just serves the
                # request whole; the handoff would be pure overhead.
                payload["migrate"] = True
            elif req.leg == "decode":
                if req.resume_post is not None:
                    payload["resume"] = req.resume_post
                    payload["emitted"] = req.leg1_tokens or []
                # resume_post None = the prefill leg's post failed or was
                # quarantined: the decode replica re-prefills from the
                # prompt (full re-serve, stream identical by parity).
            if self._prefix_index is not None and req.leg != "decode":
                # Optimistic: this replica is about to warm these prompt
                # blocks. A died-before-prefill entry is self-healing —
                # _fail drops the replica's entries wholesale.
                self._prefix_index.insert(req.tokens, h.name)
            try:
                h.client.submit(payload)
            except OSError as exc:
                # Transport failure counts as a breaker failure; the
                # request goes back to its queue front uncharged. Stop
                # routing this tick — retrying the same pick in a tight
                # loop would spin until the breaker trips.
                h.inflight.pop(req.trace, None)
                req.replica = None
                self.metrics.counter("reroutes_total").inc()
                self.journal.emit(
                    "request_reroute",
                    trace=req.trace,
                    rid=req.rid,
                    from_replica=h.name,
                    attempt=req.attempts,
                    reason="submit_error",
                )
                self._requeue_front(req)
                self._breaker_failure(
                    h, now, reason=f"submit {type(exc).__name__}"
                )
                return
            self.metrics.counter("routed_total").inc()
            route_kw = {}
            if req.leg != "single":
                route_kw["leg"] = req.leg
            self.journal.emit(
                "request_route",
                trace=req.trace,
                rid=req.rid,
                replica=h.name,
                attempt=req.attempts,
                queue_wait_s=round(now - req.t_submit, 6),
                **route_kw,
            )


# ---------------------------------------------------------------------------
# Local subprocess fleet (the launch_local analog for serving).
# ---------------------------------------------------------------------------


def port_file(replica_dir: str) -> str:
    """Where a replica publishes its ephemeral /healthz port."""
    return os.path.join(replica_dir, "port.json")


def replica_url(replica_dir: str) -> str | None:
    """The replica's /healthz URL, or None until the port is published."""
    try:
        with open(port_file(replica_dir), encoding="utf-8") as f:
            port = json.load(f)["port"]
    except (OSError, ValueError, KeyError):
        return None
    return f"http://127.0.0.1:{port}/healthz"


def local_fleet(
    model_kw: dict,
    checkpoint_dir: str,
    fleet_dir: str,
    *,
    replicas: int = 3,
    roles: list[str] | tuple[str, ...] | None = None,
    slots: int | list[int] | tuple[int, ...] = 4,
    chunk: int = 8,
    queue_limit: int = 32,
    buckets: tuple[int, ...] | None = None,
    paged: bool = False,
    block_size: int = 16,
    kv_blocks: int = 64,
    kv_dtype: str = "bf16",
    poll_s: float = 0.005,
    warm: bool = True,
    env: dict | None = None,
    grace_s: float = 300.0,
    dead_after_s: float = 10.0,
    print_fn=print,
    **router_kw,
) -> ReplicaRouter:
    """Build a router over N subprocess replicas on this host, each a
    ``run_replica`` worker (TextServer restored from ``checkpoint_dir``).
    ``model_kw`` are GPTLM constructor kwargs (JSON-serialized onto the
    worker's argv; ``compute_dtype`` as a dtype NAME string). Per-replica
    journals land at ``<fleet_dir>/events-<name>.jsonl`` (via
    ``DTF_EVENTS_PATH``) beside the router's ``events.jsonl`` — the files
    ``obs_report --fleet`` merges into one cross-replica timeline. The
    startup grace is generous by default: a cold jax import + restore on
    a loaded host must not read as death (CLAUDE.md's integration-test
    lesson). ``roles`` (one of ``prefill``/``decode``/``both`` per
    replica) arms the round-23 disaggregated two-leg path: any non-both
    role forces ``paged=True``, creates ``<fleet_dir>/migrate`` as the
    shared migration store, and passes ``migrate_dir`` to the router.
    ``slots`` may be a per-replica list — the role-tuning lever: decode
    replicas pack many resident streams (decode is memory-bound, round
    18), prefill replicas size to their batch-prefill width."""
    from distributed_tensorflow_tpu.observability.journal import EventJournal

    os.makedirs(fleet_dir, exist_ok=True)
    slot_list = (
        [int(s) for s in slots]
        if isinstance(slots, (list, tuple))
        else [int(slots)] * replicas
    )
    if len(slot_list) != replicas:
        raise ValueError(
            f"slots has {len(slot_list)} entries for {replicas} replicas"
        )
    if roles is not None:
        if len(roles) != replicas:
            raise ValueError(
                f"roles has {len(roles)} entries for {replicas} replicas"
            )
        if any(r != "both" for r in roles):
            paged = True  # a disaggregated fleet migrates paged KV
    migrate_dir = None
    if roles is not None and any(r != "both" for r in roles):
        migrate_dir = os.path.join(fleet_dir, "migrate")
        os.makedirs(migrate_dir, exist_ok=True)
        router_kw.setdefault("migrate_dir", migrate_dir)
    run_id = f"fleet-{os.getpid()}"
    journal = EventJournal.in_dir(fleet_dir, run_id=run_id)
    platform = children_platform(
        {**os.environ, **(env or {})}, replicas, "local_fleet"
    )
    print_fn(f"local_fleet: {replicas} replica processes on {platform}")
    handles = []
    for i in range(replicas):
        name = f"replica{i}"
        rdir = os.path.join(fleet_dir, name)
        os.makedirs(rdir, exist_ok=True)
        renv = dict(os.environ)
        renv.update(env or {})
        renv["DTF_EVENTS_PATH"] = os.path.join(
            fleet_dir, f"events-{name}.jsonl"
        )
        renv["DTF_RUN_ID"] = run_id
        cmd = [
            sys.executable, "-m", "distributed_tensorflow_tpu.serve_fleet",
            "--replica", "--dir", rdir,
            "--checkpoint-dir", checkpoint_dir,
            "--model", json.dumps(model_kw),
            "--slots", str(slot_list[i]), "--chunk", str(chunk),
            "--queue-limit", str(queue_limit), "--poll-s", str(poll_s),
        ]
        if buckets:
            cmd += ["--buckets", ",".join(str(b) for b in buckets)]
        if paged:
            cmd += [
                "--paged",
                "--block-size", str(block_size),
                "--kv-blocks", str(kv_blocks),
                "--kv-dtype", kv_dtype,
            ]
        if migrate_dir is not None:
            cmd += ["--migrate-dir", migrate_dir]
        if warm:
            cmd += ["--warm"]

        def _spawn(cmd=cmd, renv=renv, rdir=rdir, name=name):
            try:  # a relaunch must not probe the dead incarnation's port
                os.remove(port_file(rdir))
            except OSError:
                pass
            log = open(os.path.join(fleet_dir, f"{name}.log"), "ab")
            try:
                return subprocess.Popen(
                    cmd, stdout=log, stderr=subprocess.STDOUT, env=renv
                )
            finally:
                log.close()

        handles.append(
            ReplicaHandle(
                name,
                client=MailboxClient(rdir),
                agent=ElasticAgent(name, _spawn),
                health=HttpHealth(
                    (lambda rdir=rdir: replica_url(rdir)),
                    grace_s=grace_s,
                    dead_after_s=dead_after_s,
                ),
                role=roles[i] if roles is not None else "both",
            )
        )
    return ReplicaRouter(
        handles, journal=journal, print_fn=print_fn, **router_kw
    )


def publish_checkpoint(model, params, checkpoint_dir: str, step: int = 1):
    """Publish ``params`` as a dense, CRC-manifested ``step_N`` checkpoint
    that ``canonical_lm_params`` (and therefore every fleet replica)
    restores — the publish edge of train→publish→serve for callers that
    are not an LMTrainer: benches, tests, external trainers. Uses the
    reference-SGD optimizer whose slot state is empty, matching the
    serving restore default."""
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops import optim as optim_lib
    from distributed_tensorflow_tpu.parallel.strategy import TrainState
    from distributed_tensorflow_tpu.train.supervisor import Supervisor

    opt = optim_lib.sgd(0.001)
    Supervisor(checkpoint_dir=checkpoint_dir).save(
        TrainState(params, opt.init(params), jnp.asarray(step, jnp.int32)),
        int(step),
    )


# ---------------------------------------------------------------------------
# The replica worker (the only half that imports the engine / jax).
# ---------------------------------------------------------------------------

_DTYPES = {
    "float32": "float32",
    "bfloat16": "bfloat16",
    "float16": "float16",
}


def _model_from_kw(model_kw: dict):
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.gpt import GPTLM

    kw = dict(model_kw)
    cd = kw.get("compute_dtype")
    if isinstance(cd, str):
        if cd not in _DTYPES:
            raise ValueError(f"unknown compute_dtype {cd!r}")
        kw["compute_dtype"] = jnp.dtype(_DTYPES[cd])
    return GPTLM(**kw)


def run_replica(args) -> int:
    """One serving replica: TextServer from ``checkpoint_dir``, driven
    against the mailbox — admit at chunk boundaries, one ``step()`` per
    loop turn, results committed atomically the tick they finish (the
    zero-loss contract's write-before-crash half). SIGTERM is graceful:
    the loop exits, residents drain, results flush, rc 0 — the same
    preemption stance as the trainers (train/resilience.py)."""
    import signal

    from distributed_tensorflow_tpu.observability import (
        journal as obs_journal_mod,
    )
    from distributed_tensorflow_tpu.observability.exporter import (
        MetricsExporter,
    )
    from distributed_tensorflow_tpu.serve import (
        GenerationConfig,
        QueueFull,
        RequestCancelled,
        RequestShed,
        TextServer,
    )

    from distributed_tensorflow_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    obs_journal_mod.configure_from_env(announce=True)
    model = _model_from_kw(json.loads(args.model))
    buckets = (
        tuple(int(b) for b in args.buckets.split(","))
        if args.buckets
        else None
    )
    srv_kw: dict = {}
    if args.paged:
        srv_kw.update(
            paged=True,
            block_size=args.block_size,
            kv_blocks=args.kv_blocks,
            kv_dtype=args.kv_dtype,
        )
    srv = TextServer.from_checkpoint(
        model,
        args.checkpoint_dir,
        slots=args.slots,
        chunk=args.chunk,
        buckets=buckets,
        queue_limit=args.queue_limit or None,
        **srv_kw,
    )
    box = MailboxClient(args.dir, metrics=srv.metrics)
    store = (
        MigrationStore(args.migrate_dir, metrics=srv.metrics)
        if getattr(args, "migrate_dir", None)
        else None
    )
    # A fresh incarnation serves only newly routed work: anything in the
    # inbox predates this process and already failed over elsewhere.
    box.clear_inbox()
    if args.warm:
        # Pre-warm every compiled surface (one prefill per bucket + the
        # chunk executable) BEFORE publishing the health port: a replica
        # that reads "up" is ready to serve at serving speed, and first-
        # request TTFT is not a compile measurement.
        import numpy as _np

        for b in srv.buckets:
            if b + 2 > model.max_len:
                continue
            srv.generate(
                [_np.arange(1, b + 1, dtype=_np.int32)],
                GenerationConfig(max_new=2),
            )
        if store is not None:
            # Decode replicas must not pay the import-scatter compile
            # on their first resumed request (see warm_import).
            srv.warm_import()
    def _health():
        # Round-21 satellite: mailbox corruption is a health-visible
        # signal, not a "silent replica by design" (known_issues.md) —
        # router verdicts and dashboards see the quarantine count.
        doc = srv.health()
        doc["mailbox_corrupt_files"] = box.corrupt_files + (
            store.corrupt_files if store is not None else 0
        )
        return doc

    exporter = MetricsExporter(srv.metrics, port=args.port, health_fn=_health)
    write_json_atomic(port_file(args.dir), {"port": exporter.start()})

    stop: list[int] = []
    prev = signal.signal(signal.SIGTERM, lambda *a: stop.append(1))

    def _flush_done(rids: dict) -> None:
        for rid in list(rids):
            if srv.done(rid):
                export = srv.take_export(rid) if store is not None else None
                if export is not None:
                    # Prefill leg finished: post the KV payload on the
                    # migration store, then hand the baton back to the
                    # router. A failed post is NOT a failed request —
                    # post=None tells the router the decode leg must
                    # re-prefill (the fallback matrix's cheap row).
                    trace = rids.pop(rid)
                    t0 = time.perf_counter()
                    nbytes = sum(
                        a.nbytes for a in export["arrays"].values()
                    )
                    try:
                        post = store.post(f"{trace}.npz", export)
                    except OSError as exc:
                        post = None
                        obs_journal_mod.get_journal().emit(
                            "kv_migration",
                            phase="post_failed",
                            trace=trace,
                            error=f"{type(exc).__name__}: {exc}",
                        )
                    else:
                        obs_journal_mod.get_journal().emit(
                            "kv_migration",
                            phase="post",
                            trace=trace,
                            file=post,
                            blocks=int(export["meta"]["blocks"]),
                            nbytes=int(nbytes),
                            wall_ms=round(
                                (time.perf_counter() - t0) * 1e3, 3
                            ),
                        )
                    box.put_result(
                        {
                            "trace": trace,
                            "migrated": True,
                            "post": post,
                            "tokens": [int(t) for t in export["tokens"]],
                            "blocks": int(export["meta"]["blocks"]),
                            "nbytes": int(nbytes),
                        }
                    )
                    continue
                trace = rids.pop(rid)
                try:
                    toks = srv.result(rid)
                    box.put_result(
                        {"trace": trace, "tokens": [int(t) for t in toks]}
                    )
                except RequestShed:
                    box.put_result({"trace": trace, "shed": True})
                except RequestCancelled:
                    box.put_result({"trace": trace, "cancelled": True})

    rids: dict[int, str] = {}
    try:
        while not stop:
            for payload in box.take_inbox():
                ctl = payload.get("control")
                if ctl == "stop":
                    stop.append(1)
                elif ctl == "swap":
                    # A bad publish (typo'd dir, all-corrupt steps) must
                    # cost the SWAP, never the replica: journal the
                    # failure and keep serving the current weights — the
                    # same stance the submit guard below takes for
                    # poison requests.
                    try:
                        srv.swap_from_checkpoint(
                            payload.get("checkpoint_dir")
                        )
                    except Exception as exc:  # noqa: BLE001
                        obs_journal_mod.get_journal().emit(
                            "weight_swap_failed",
                            source=payload.get("checkpoint_dir"),
                            error=f"{type(exc).__name__}: {exc}",
                        )
                elif ctl is not None:
                    continue  # unknown control: ignore, stay alive
                else:
                    # TypeError covers a malformed config dict (unknown
                    # GenerationConfig keys): reject it back to the
                    # router — a poison request must cost ITSELF, never
                    # the replica process (the router fails it terminally
                    # on the error_kind, so it cannot cascade either).
                    sub_kw: dict = {}
                    if payload.get("migrate") and store is not None:
                        sub_kw["prefill_only"] = True
                    post_name = payload.get("resume")
                    if post_name is not None and store is not None:
                        loaded = store.load(post_name)
                        if loaded is None:
                            # Missing or quarantined post: fall back to a
                            # full re-prefill on THIS replica — the warm
                            # radix stays, the stream stays identical.
                            obs_journal_mod.get_journal().emit(
                                "kv_migration",
                                phase="fallback",
                                trace=payload.get("trace"),
                                file=post_name,
                                reason="load_failed",
                            )
                        else:
                            sub_kw["resume"] = {
                                "arrays": loaded["arrays"],
                                "meta": loaded["meta"],
                            }
                            sub_kw["emitted_tokens"] = payload.get(
                                "emitted", loaded.get("tokens")
                            )
                    try:
                        try:
                            rid = srv.submit(
                                payload["tokens"],
                                GenerationConfig(
                                    **(payload.get("config") or {})
                                ),
                                deadline_s=payload.get("deadline_s"),
                                priority=int(payload.get("priority", 0)),
                                trace=payload.get("trace"),
                                **sub_kw,
                            )
                        except ValueError:
                            if "resume" not in sub_kw:
                                raise
                            # Geometry/dtype mismatch between the post and
                            # THIS replica's cache (heterogeneous fleet,
                            # mid-roll kv_dtype change): re-prefill here
                            # rather than bounce the request.
                            obs_journal_mod.get_journal().emit(
                                "kv_migration",
                                phase="fallback",
                                trace=payload.get("trace"),
                                file=post_name,
                                reason="resume_rejected",
                            )
                            rid = srv.submit(
                                payload["tokens"],
                                GenerationConfig(
                                    **(payload.get("config") or {})
                                ),
                                deadline_s=payload.get("deadline_s"),
                                priority=int(payload.get("priority", 0)),
                                trace=payload.get("trace"),
                            )
                    except (
                        QueueFull, ValueError, TypeError, RuntimeError,
                    ) as exc:
                        box.put_result(
                            {
                                "trace": payload.get("trace"),
                                "rejected": True,
                                "error_kind": type(exc).__name__,
                                "error": f"{type(exc).__name__}: {exc}",
                            }
                        )
                    else:
                        rids[rid] = payload["trace"]
            srv.step()
            _flush_done(rids)
            if srv.idle():
                time.sleep(args.poll_s)
        srv.drain()  # graceful: residents finish, nothing dropped
        _flush_done(rids)
    finally:
        signal.signal(signal.SIGTERM, prev)
        exporter.stop()
        obs_journal_mod.get_journal().flush()
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--replica", action="store_true",
        help="run as a replica worker (spawned by local_fleet)",
    )
    ap.add_argument("--dir", help="replica mailbox directory")
    ap.add_argument("--checkpoint-dir")
    ap.add_argument("--model", help="GPTLM constructor kwargs as JSON")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--queue-limit", type=int, default=32)
    ap.add_argument("--buckets", default=None, help="comma-separated")
    ap.add_argument(
        "--port", type=int, default=0,
        help="/healthz port (0 = ephemeral, published to <dir>/port.json)",
    )
    ap.add_argument("--poll-s", type=float, default=0.005)
    ap.add_argument(
        "--paged", action="store_true",
        help="serve from the paged KV pool (required for migration)",
    )
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--kv-blocks", type=int, default=64)
    ap.add_argument("--kv-dtype", default="bf16")
    ap.add_argument(
        "--migrate-dir", default=None,
        help="shared migration-store directory (arms the prefill→decode "
        "KV handoff; posts are CRC-enveloped npz files)",
    )
    ap.add_argument(
        "--warm", action="store_true",
        help="compile every prefill bucket + the chunk executable before "
        "publishing the health port (readiness == serving-ready)",
    )
    args = ap.parse_args(argv)
    if not args.replica:
        ap.error("only --replica mode has a CLI; drive routers in-process "
                 "(serve_fleet.local_fleet)")
    for req in ("dir", "checkpoint_dir", "model"):
        if getattr(args, req) in (None, ""):
            ap.error(f"--replica requires --{req.replace('_', '-')}")
    return run_replica(args)


if __name__ == "__main__":
    sys.exit(main())
