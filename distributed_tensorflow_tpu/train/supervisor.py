"""Supervisor: chief election, init-or-restore, orderly shutdown (C13).

The reference's ``tf.train.Supervisor`` (reference tfdist_between.py:78,83)
provided: chief election (``is_chief = task_index == 0``), chief-only variable
init with non-chiefs waiting for an initialized model, session recovery for
restarted workers, and orderly stop (``sv.request_stop()`` / ``sv.stop()``,
reference tfdist_between_sync.py:120-123).

TPU-native mapping: there are no sessions to recover — state is an explicit
pytree. "Prepare or wait" becomes *restore-or-init* against a checkpoint
directory (a deliberate upgrade: the reference configured no saver at all,
SURVEY.md §5 "Checkpoint/resume"), and cross-process agreement comes from
``jax.distributed``'s coordination barrier plus every process computing the
same deterministic init (same seed ⇒ same params, no broadcast needed).
Checkpointing is orbax-backed, async-capable, and sharding-aware.

Round 6 makes the checkpoints *durable* (train/resilience.py): every save
commits a CRC32C manifest sidecar, restore verifies and falls back to the
newest VALID step when the latest is corrupt or partial, checkpoint I/O
retries with backoff, and a retention policy (``keep_last_n``) GCs old
steps without ever removing the last verified one. Contracts in
docs/resilience.md.
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import shutil
import threading
import warnings

from typing import TYPE_CHECKING

from distributed_tensorflow_tpu.observability import names
from distributed_tensorflow_tpu.train import resilience

if TYPE_CHECKING:  # jax-backed; the probe half of this module is file I/O
    from distributed_tensorflow_tpu.parallel.strategy import TrainState

try:
    import orbax.checkpoint as ocp

    _HAVE_ORBAX = True
except Exception:  # pragma: no cover
    _HAVE_ORBAX = False

_STEP_DIR = re.compile(r"^step_(\d+)$")

# Layout-sidecar keys that describe the saved state's SHAPES (which
# canonicalization a cross-topology restore needs). Everything else in the
# sidecar is restore POLICY — e.g. round 8's "world"/"global_batch", which
# the elastic resize path reads to preserve the global batch across a
# world-size change — and must not break same-layout compatibility checks.
# Round 17: "delta_dtype"/"overlap" are SHAPE keys — the compressed-delta
# residual and the in-flight delta are extra pytree nodes in DiLoCoState,
# so a checkpoint written with a lever on has a different structure than
# one without (the keys are only present when the lever is on, so old
# sidecars keep comparing equal to lever-off metas).
LAYOUT_SHAPE_KEYS = ("mode", "replicas", "stages", "delta_dtype", "overlap")


def layout_shape(layout: dict | None) -> dict:
    """The shape-determining slice of a checkpoint layout sidecar (see
    :data:`LAYOUT_SHAPE_KEYS`): what trainers compare to decide between
    the bitwise same-layout restore and the canonical cross-topology
    path. An old sidecar (no policy keys) and a round-8 one with
    identical topology compare equal here by construction."""
    return {
        k: v for k, v in (layout or {}).items() if k in LAYOUT_SHAPE_KEYS
    }


def checkpoint_steps(checkpoint_dir: str | None) -> list[int]:
    """All ``step_N`` under ``checkpoint_dir``, ascending. Read-only."""
    if not checkpoint_dir or not os.path.isdir(checkpoint_dir):
        return []
    return sorted(
        int(m.group(1))
        for d in os.listdir(checkpoint_dir)
        if (m := _STEP_DIR.match(d))
    )


def latest_checkpoint_step(
    checkpoint_dir: str | None, *, verify: bool = False
) -> int | None:
    """Newest ``step_N`` under ``checkpoint_dir``, or None. Read-only probe —
    never creates the directory (unlike constructing a Supervisor).

    ``verify=True`` returns the newest step whose bytes on disk pass the
    manifest integrity check (train/resilience.py) — skipping corrupt or
    partially written checkpoints AND pre-manifest ones (no manifest means
    nothing to verify against; use the default probe to see those)."""
    steps = checkpoint_steps(checkpoint_dir)
    if not verify:
        return steps[-1] if steps else None
    for step in reversed(steps):
        if resilience.verify_files(checkpoint_dir, step) is True:
            return step
    return None


class Supervisor:
    def __init__(
        self,
        *,
        is_chief: bool = True,
        checkpoint_dir: str | None = None,
        keep_last_n: int | None = None,
        io_retries: int = 3,
        io_backoff: float = 0.25,
        async_checkpoint: bool = False,
    ):
        self.is_chief = is_chief
        self.checkpoint_dir = os.path.abspath(checkpoint_dir) if checkpoint_dir else None
        self.keep_last_n = keep_last_n
        self.io_retries = max(1, int(io_retries))
        self.io_backoff = float(io_backoff)
        # Async checkpoint pipeline (round 22): ``save`` snapshots device
        # state to host and returns immediately; a depth-1 background
        # writer commits the EXACT synchronous byte sequence. Default OFF
        # here (bare Supervisors — inference, serving, launch probes —
        # have no training loop to unblock); TrainConfig.async_checkpoint
        # (default ON) flips it for the trainers.
        self.async_checkpoint = bool(async_checkpoint)
        self._writer = None
        self._write_lock = threading.Lock()
        self._saving = False  # main-thread sync save in progress
        self._last_snapshot = None  # (host_state, step, layout) — newest
        self._heartbeat_file = os.environ.get("DTF_HEARTBEAT_FILE") or None
        self._stop_requested = False
        self._heartbeat = None
        self._stall_timeout_ms = 0
        self._progress_fn = None
        self._ckptr = None
        self._journal = None
        self._metrics = None
        self._spans = None
        if self.checkpoint_dir and _HAVE_ORBAX:
            os.makedirs(self.checkpoint_dir, exist_ok=True)
            self._ckptr = ocp.StandardCheckpointer()

    def attach_observability(
        self, journal=None, metrics=None, spans=None
    ) -> None:
        """Arm checkpoint telemetry (round 10): each save/restore emits a
        ``checkpoint_save``/``checkpoint_restore`` journal event (step,
        bytes, duration), feeds the metrics registry (save count/bytes/
        duration histogram), and records a host span. All three sinks are
        optional — trainers wire theirs in; a bare Supervisor stays
        silent. Trace ids (round 12) need no plumbing here: saves happen
        inside the trainer's ambient trace context, so the journal tags
        every checkpoint event with the run's trace automatically
        (observability/tracing.py)."""
        self._journal = journal
        self._metrics = metrics
        self._spans = spans

    def _span(self, name: str, **args):
        import contextlib

        if self._spans is None:
            return contextlib.nullcontext()
        return self._spans.span(name, cat="checkpoint", **args)

    def attach_heartbeat(self, heartbeat, *, stall_timeout_ms: int = 0) -> None:
        """Arm failure-reactive stopping: when the attached
        HeartbeatCoordinator (runtime/native.py) reports a failed worker,
        ``should_stop`` turns true — so the chief's training loop exits at
        the next epoch boundary with checkpoints intact, instead of hanging
        in a collective the dead worker will never join (the reference's
        failure mode: gRPC calls blocking forever, SURVEY.md §5).

        ``stall_timeout_ms > 0`` (round 7) additionally trips the stop when
        a worker is LIVE-BUT-STALLED — beating, but its progress counter
        frozen past the window (``HeartbeatCoordinator.stalled_count``) —
        the failure mode silence timeouts can never see."""
        self._heartbeat = heartbeat
        self._stall_timeout_ms = int(stall_timeout_ms)

    def attach_progress(self, progress_fn) -> None:
        """Wire the heartbeat progress reporter (typically
        ``ProcessContext.report_progress``): trainers call
        :meth:`report_progress` with the global step at epoch boundaries,
        and the counter rides every outgoing beat so the detector — chief-
        or agent-hosted — can tell stalled from dead."""
        self._progress_fn = progress_fn

    def report_progress(self, progress: int) -> None:
        """Advance the attached heartbeat progress counter; no-op when no
        reporter is wired (single process, heartbeat unavailable).

        Round 22: when ``$DTF_HEARTBEAT_FILE`` names a path (the elastic
        launcher exports one per worker), each report also mtime-bumps
        that file and emits a ``heartbeat`` journal event — the progress
        watchdog's evidence that this member is alive AND advancing, not
        merely scheduled. Gated on the env var so default journal streams
        are byte-identical to round 21."""
        if self._progress_fn is not None:
            self._progress_fn(int(progress))
        if self._heartbeat_file:
            resilience.touch_heartbeat(self._heartbeat_file)
            if self._journal is not None:
                self._journal.emit(
                    "heartbeat",
                    rank=int(os.environ.get("DTF_RANK", "0") or 0),
                    step=int(progress),
                )

    # -- checkpoint/restore (upgrade over the reference's nothing) --------

    def latest_step(self, *, verify: bool = False) -> int | None:
        self.wait_pending()
        return latest_checkpoint_step(self.checkpoint_dir, verify=verify)

    def newest_restorable_step(self) -> int | None:
        """Newest step that is not KNOWN-bad: manifest-verified where a
        manifest exists, trusted where none does (pre-round-6 checkpoints
        carry no manifest but must keep restoring). The restore entry
        points use this so a corrupt latest checkpoint points them at the
        newest valid one instead.

        Reads drain writes (round 22): an in-flight async step directory
        has no manifest yet — ``verify_files`` would return None and this
        probe would TRUST a half-written step — so every restore entry
        point drains the writer first."""
        self.wait_pending()
        for step in reversed(checkpoint_steps(self.checkpoint_dir)):
            if resilience.verify_files(self.checkpoint_dir, step) is False:
                warnings.warn(
                    f"checkpoint step_{step} fails manifest verification; "
                    "falling back to the previous step",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            return step
        return None

    def _retry(self, fn, describe: str):
        return resilience.retry_io(
            fn,
            attempts=self.io_retries,
            backoff=self.io_backoff,
            describe=describe,
        )

    def save(
        self, state: TrainState, step: int, layout: dict | None = None
    ) -> None:
        """Chief-only checkpoint write (non-chiefs no-op, as with the
        reference's chief-owned init/teardown duties). ``layout`` is an
        optional topology descriptor (mode, pipeline stages, async
        replicas — see LMTrainer._layout_meta) written as a JSON sidecar
        ``step_N.layout.json``; cross-topology restore reads it to know
        which canonicalization the saved arrays need.

        Durability (round 6): the orbax write runs under bounded
        retry-with-backoff, then the manifest sidecar commits atomically
        (its presence marks a complete checkpoint), then the retention
        policy GCs steps beyond ``keep_last_n`` — never the last valid.

        Async (round 22, ``async_checkpoint=True``): the save boundary
        pays only the device→host snapshot; serialize+CRC+manifest+GC run
        on the background writer through the SAME ``_write_step`` the
        synchronous path uses, so artifacts are state-identical (test-
        pinned: byte-equal manifest leaf CRCs, bitwise-equal restores —
        orbax's own content-hashed filenames keep raw bytes
        nondeterministic even sync-vs-sync). The snapshot is retained as
        the emergency-save source; a
        prior writer error surfaces here (and at ``wait_pending``) rather
        than being swallowed."""
        if not (self.is_chief and self._ckptr):
            return
        resilience.failpoints.fire("ckpt.save")
        if self.async_checkpoint:
            import jax
            import numpy as _np

            # The snapshot must OWN its memory: on CPU backends
            # jax.device_get returns zero-copy VIEWS of the device
            # buffers, and a donated buffer is overwritten by the next
            # dispatched step while the write is still in flight (the
            # orbax bytes and the manifest CRCs would then disagree —
            # caught live by the corrupt-latest fallback test).
            host_state = jax.tree.map(
                lambda x: x.copy() if isinstance(x, _np.ndarray) else x,
                jax.device_get(state),
            )
            self._last_snapshot = (host_state, int(step), layout)
            if self._writer is None:
                self._writer = resilience.AsyncCheckpointWriter()
            else:
                self._writer.raise_deferred()
            self._writer.submit(
                lambda: self._write_step(host_state, int(step), layout),
                tag=int(step),
            )
            return
        self._saving = True
        try:
            self._write_step(state, int(step), layout)
        finally:
            self._saving = False

    def _write_step(
        self, state, step: int, layout: dict | None, *, quiet: bool = False
    ) -> None:
        """The one write sequence (round-6 order, both modes): orbax under
        retry → layout sidecar → manifest commit → telemetry → retention
        sweep. Runs on the main thread (sync) or the writer thread
        (async); ``_write_lock`` serializes the two. The sweep running
        HERE, after the manifest commit, is what keeps ``keep_last_n`` GC
        ordered behind every in-flight write — a step whose manifest
        isn't committed yet is never a sweep candidate's newest-valid
        competitor mid-write. ``quiet=True`` (emergency save from the
        signal-handler frame) skips span/journal/metrics — none of those
        sinks are reentrancy-safe there."""
        import time as _time

        path = os.path.join(self.checkpoint_dir, f"step_{step}")

        def _write():
            self._ckptr.save(path, state, force=True)
            self._ckptr.wait_until_finished()

        with self._write_lock:
            t0 = _time.perf_counter()
            span = (
                contextlib.nullcontext()
                if quiet
                else self._span(names.SPAN_CHECKPOINT_SAVE, step=int(step))
            )
            with span:
                self._retry(_write, f"save step_{step}")
                if layout is not None:
                    resilience.write_json_atomic(f"{path}.layout.json", layout)
                manifest = self._retry(
                    lambda: resilience.write_manifest(
                        self.checkpoint_dir, step, state
                    ),
                    f"manifest step_{step}",
                )
            duration_s = _time.perf_counter() - t0
            # The manifest already walked the step dir with sizes — the byte
            # count is free (no second disk pass).
            nbytes = sum(
                r["size"] for r in manifest.get("files", {}).values()
            ) + sum(r["size"] for r in manifest.get("sidecars", {}).values())
            if not quiet and self._journal is not None:
                self._journal.emit(
                    "checkpoint_save",
                    step=int(step),
                    bytes=int(nbytes),
                    duration_s=round(duration_s, 6),
                )
            if not quiet and self._metrics is not None:
                self._metrics.counter("checkpoint_saves_total").inc()
                self._metrics.counter("checkpoint_bytes_total").inc(nbytes)
                self._metrics.histogram("checkpoint_save_s").observe(
                    duration_s
                )
            self._retention_sweep()

    def wait_pending(self) -> None:
        """Drain the async writer: every submitted write committed (or
        its deferred error re-raised). No-op in sync mode. The final-save
        barrier — trainers call it on run() exit — and the read barrier
        every restore entry point takes (an in-flight step directory has
        no manifest yet and would read as 'unverifiable, trusted')."""
        w = self._writer
        if w is not None:
            w.wait_pending()

    def emergency_save(self) -> int | None:
        """Persist the newest retained host snapshot NOW (the preemption
        handler's hook). Drains the writer first — normally that alone
        lands the newest step — then writes the snapshot synchronously
        only if it is still not committed on disk (superseded queue slot,
        or the writer died on it). Reentrancy-guarded: no-op (None) when
        the signal interrupted a synchronous save in progress (a blocking
        wait here would deadlock the main thread against itself).
        Returns the snapshot's step when it is durable on disk after the
        call, else None."""
        if not (self.is_chief and self._ckptr) or self._saving:
            return None
        snap = self._last_snapshot
        if snap is None:
            return None
        host_state, step, layout = snap
        try:
            self.wait_pending()
        except Exception:  # noqa: BLE001 — writer died; write it ourselves
            pass
        if resilience.verify_files(self.checkpoint_dir, step) is not True:
            try:
                self._write_step(host_state, step, layout, quiet=True)
            except Exception:  # noqa: BLE001 — best-effort in a handler
                return None
        return int(step)

    def _retention_sweep(self) -> None:
        """Delete steps beyond the ``keep_last_n`` newest. The newest
        VALID step is never deleted, even when it falls outside the
        window — if every kept step were corrupt, the sweep must not have
        destroyed the one that restores."""
        n = self.keep_last_n
        if not n or n < 1:
            return
        steps = checkpoint_steps(self.checkpoint_dir)
        doomed = steps[:-n]
        if not doomed:
            return
        kept_valid = any(
            resilience.verify_files(self.checkpoint_dir, s) is True
            for s in steps[-n:]
        )
        protected: set[int] = set()
        if not kept_valid:
            for s in reversed(doomed):
                if resilience.verify_files(self.checkpoint_dir, s) is True:
                    protected.add(s)
                    break
        for s in doomed:
            if s in protected:
                continue
            shutil.rmtree(
                os.path.join(self.checkpoint_dir, f"step_{s}"),
                ignore_errors=True,
            )
            for side in (f"step_{s}.layout.json", f"step_{s}.manifest.json"):
                try:
                    os.remove(os.path.join(self.checkpoint_dir, side))
                except OSError:
                    pass

    def saved_layout(self, step: int) -> dict | None:
        """The layout sidecar written alongside ``step_N``, or None
        (pre-round-5 checkpoints have none — callers must treat that as
        "same layout as mine", the old behavior). A present-but-corrupt
        sidecar raises ValueError: silently taking the same-layout restore
        path for (say) an async checkpoint would surface later as an
        opaque orbax shape mismatch pointing nowhere near the cause."""
        if not self.checkpoint_dir:
            return None
        path = os.path.join(self.checkpoint_dir, f"step_{step}.layout.json")
        try:
            with open(path) as f:
                raw = f.read()
        except OSError:
            return None  # missing sidecar: pre-round-5 checkpoint
        try:
            return json.loads(raw)
        except ValueError as exc:
            raise ValueError(
                f"corrupt checkpoint layout sidecar {path}: {exc}"
            ) from exc

    def restore_raw(self, step: int, abstract):
        """Restore ``step_N`` against an explicit abstract pytree (shapes/
        dtypes of the SOURCE layout) — the cross-topology path: the caller
        canonicalizes the result rather than assuming it matches its own
        state's shapes the way :meth:`prepare_or_restore` does."""
        if self._ckptr is None:
            raise RuntimeError("no checkpointer (orbax unavailable or no dir)")
        self.wait_pending()
        import jax

        path = os.path.join(self.checkpoint_dir, f"step_{step}")
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, abstract)
        return self._retry(
            lambda: self._ckptr.restore(path, abstract),
            f"restore step_{step}",
        )

    def prepare_or_restore(
        self, state: TrainState, *, verified_step: int | None = None
    ) -> tuple[TrainState, int]:
        """Restore-or-init: the analog of ``prepare_or_wait_for_session``.

        Returns (state, start_step). With no checkpoint present, the passed-in
        freshly-initialized state is returned — every process computed the
        identical init from the shared seed, which is how "non-chief waits for
        chief's init" degenerates on a deterministic SPMD system.

        Durability (round 6): candidate steps are tried newest-first; a
        step whose manifest fails file verification, whose orbax restore
        raises, or whose restored leaves mismatch their recorded CRCs is
        skipped (with a RuntimeWarning naming it) and the next-newest is
        tried — a corrupt or partially written latest checkpoint costs
        one epoch of progress, not the run. But when checkpoints EXIST
        and every one of them fails, that is a systemic failure (storage
        outage outliving the retry budget, format mismatch, a fallback
        landing on an incompatible older layout) and it RAISES — silently
        re-initializing at step 0 would discard the run's progress and
        bury the cause. ``verified_step`` marks a step whose files the
        caller already verified this session (trainers probe
        ``newest_restorable_step`` first), skipping the redundant disk
        re-read+CRC pass for it."""
        if self._ckptr is None:
            return state, 0
        self.wait_pending()
        import jax

        candidates = list(reversed(checkpoint_steps(self.checkpoint_dir)))
        for step in candidates:
            if (
                step != verified_step
                and resilience.verify_files(self.checkpoint_dir, step) is False
            ):
                warnings.warn(
                    f"checkpoint step_{step} fails manifest verification; "
                    "trying the previous step",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            path = os.path.join(self.checkpoint_dir, f"step_{step}")
            abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, state)
            try:
                resilience.failpoints.fire("ckpt.restore")
                restored = self._retry(
                    lambda: self._ckptr.restore(path, abstract),
                    f"restore step_{step}",
                )
            except Exception as exc:  # noqa: BLE001 — fall back per contract
                warnings.warn(
                    f"checkpoint step_{step} failed to restore "
                    f"({type(exc).__name__}: {exc}); trying the previous step",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            try:
                manifest = resilience.load_manifest(self.checkpoint_dir, step)
            except ValueError:
                manifest = None
            if manifest is not None and not resilience.verify_leaves(
                restored, manifest
            ):
                warnings.warn(
                    f"checkpoint step_{step} restored with leaf CRC "
                    "mismatches; trying the previous step",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            if self._journal is not None:
                self._journal.emit(
                    "checkpoint_restore",
                    step=int(step),
                    fallback=step != candidates[0],
                )
            if self._metrics is not None:
                self._metrics.counter("checkpoint_restores_total").inc()
            return restored, step
        if candidates:
            raise RuntimeError(
                f"no restorable checkpoint in {self.checkpoint_dir}: all "
                f"{len(candidates)} candidate step(s) "
                f"({', '.join(f'step_{s}' for s in candidates)}) failed "
                "verification or restore — see the RuntimeWarnings above; "
                "refusing to silently re-initialize at step 0 over an "
                "existing run's progress"
            )
        return state, 0

    # -- orderly shutdown (reference sv.request_stop/sv.stop) -------------

    def request_stop(self) -> None:
        self._stop_requested = True

    @property
    def should_stop(self) -> bool:
        if self._stop_requested:
            return True
        if self._heartbeat is not None:
            if self._heartbeat.failed_count() > 0:
                self._stop_requested = True
            elif (
                self._stall_timeout_ms > 0
                and hasattr(self._heartbeat, "stalled_count")
                and self._heartbeat.stalled_count(self._stall_timeout_ms) > 0
            ):
                # Live-but-stalled worker (beating, progress frozen): same
                # exit as a dead one — stop at the boundary with the
                # checkpoints intact rather than hanging forever.
                self._stop_requested = True
        return self._stop_requested

    def stop(self) -> None:
        try:
            self.wait_pending()
        finally:
            if self._ckptr is not None:
                self._ckptr.wait_until_finished()
            self._stop_requested = True
