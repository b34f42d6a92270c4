"""Training-state checkpointing.

Parity: the reference checkpoints PS-side parameters every
`--checkpoint_steps` versions (pkg/ps/checkpoint.go + the python
CheckpointSaver, SURVEY.md §5) and resumes from the latest snapshot.

TPU design: checkpoints are the *backbone of elasticity*, not just crash
insurance — worker churn kills the whole jax.distributed world (a dead host
takes the slice's coordination service down), so re-formation is
restart-the-world + restore-latest.  In data-parallel mode the state is
replicated, so any rank-0 host snapshot is complete; the sharded-embedding
engine layers orbax sharded save/restore on top of this interface.

Format: one directory per step, written atomically (tmp + rename), holding
a pickled host pytree plus a CRC32 integrity manifest (`integrity.json`,
written before the commit rename).  Restore verifies every inventoried
file against its checksum: a torn write — power loss mid-flush, a dying
NFS client, an injected `ckpt.write:truncate` fault — is detected, the
snapshot is QUARANTINED (renamed aside, never deleted: it is forensic
evidence), and restore falls back to the next-newest good step instead of
crashing or silently loading garbage.  `keep_max` old checkpoints are
retained.
"""

from __future__ import annotations

import contextlib
import json
import os
import pickle
import resource
import shutil
import tempfile
import time
import zlib
from typing import Any, Dict, Optional, Tuple

from elasticdl_tpu import obs
from elasticdl_tpu.common import faults
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.obs import tracing

logger = get_logger("checkpoint.saver")


def _ckpt_metrics():
    """Checkpoint-plane registry handles (get-or-create; shared with the
    sharded saver and the master's task-progress persister)."""
    return (
        obs.histogram(
            "elasticdl_checkpoint_save_duration_seconds",
            "Checkpoint write latency, by checkpoint kind",
            labelnames=("kind",),
        ),
        obs.histogram(
            "elasticdl_checkpoint_restore_duration_seconds",
            "Checkpoint restore latency, by checkpoint kind",
            labelnames=("kind",),
        ),
        obs.counter(
            "elasticdl_checkpoint_saves_total",
            "Checkpoints committed, by checkpoint kind",
            labelnames=("kind",),
        ),
        obs.counter(
            "elasticdl_checkpoint_quarantines_total",
            "Corrupt checkpoints quarantined (integrity failures)",
        ),
    )

def tree_nbytes(tree) -> int:
    """Bytes of a pytree's array leaves (host or device)."""
    import jax

    return int(sum(
        getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree)
    ))


def _host_io_marks() -> dict:
    """What the host's page cache and this process's I/O look like now:
    `Dirty` / `Writeback` kB of /proc/meminfo and the getrusage
    counters a slow save moves (major faults, blocks written,
    involuntary context switches).  Two small reads."""
    marks = {}
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                key, _, rest = line.partition(":")
                if key in ("Dirty", "Writeback"):
                    marks[key.lower() + "_kb"] = int(rest.split()[0])
    except (OSError, ValueError, IndexError):
        pass
    usage = resource.getrusage(resource.RUSAGE_SELF)
    marks.update(
        majflt=usage.ru_majflt, oublock=usage.ru_oublock,
        nivcsw=usage.ru_nivcsw,
    )
    return marks


@contextlib.contextmanager
def save_span(**fields):
    """The parent `checkpoint.save` span of one save (every rank opens
    it around its gather and write; the children are
    `checkpoint.save.gather` / `.write` / `.crc` / `.commit`).  It
    closes with the host's dirty and write-back kB at its start and
    end and the process's rusage deltas over it — what tells a save
    slowed by piled-up dirty pages from one slowed by the device."""
    before = _host_io_marks()
    with tracing.span("checkpoint.save", **fields) as span:
        try:
            yield span
        finally:
            after = _host_io_marks()
            for key in ("dirty_kb", "writeback_kb"):
                if key in before and key in after:
                    span.fields[key + "_start"] = before[key]
                    span.fields[key + "_end"] = after[key]
            for key in ("majflt", "oublock", "nivcsw"):
                span.fields[key] = after[key] - before[key]


_STATE_FILE = "state.pkl"
_INTEGRITY_FILE = "integrity.json"
_QUARANTINE_SUFFIX = ".quarantined"

#: Tmp dirs untouched for this long are garbage from a crashed save.
#: Deliberately generous: the sweep runs at every saver CONSTRUCTION
#: (worker restarts coincide with in-flight peer saves during elastic
#: churn), directory mtime only advances on entry creation — writers
#: os.utime() their tmp dir after each large file write to stay fresh —
#: and deleting a live save costs a checkpoint while a leaked tmp dir
#: costs only disk for an hour.
STALE_TMP_GRACE_S = 3600.0


def file_crc32(path: str, chunk_bytes: int = 1 << 20) -> int:
    # Note: verification streams the file once and the restore then
    # re-reads it (2x restore I/O, page-cache-warm on local disk).
    # Folding the CRC into the load read would save the second pass on
    # NFS-scale states; measure before taking that complexity.
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(chunk_bytes)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def write_integrity_manifest(step_dir: str, filenames) -> str:
    """Checksum `filenames` (relative to `step_dir`) into integrity.json.
    Called while the checkpoint is still a tmp dir, BEFORE the atomic
    commit rename — the manifest is part of what the rename publishes."""
    manifest = {
        "files": {
            name: {
                "crc32": file_crc32(os.path.join(step_dir, name)),
                "size": os.path.getsize(os.path.join(step_dir, name)),
            }
            for name in filenames
        }
    }
    path = os.path.join(step_dir, _INTEGRITY_FILE)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


def verify_integrity(step_dir: str, check_crc: bool = True) -> Optional[str]:
    """None if `step_dir` passes its integrity manifest, else a reason
    string — returned ONLY for proven corruption (checksum/size
    mismatch, garbage manifest, inventoried file missing from a
    committed dir), which callers may quarantine.  Transient I/O errors
    (NFS blip, ESTALE) raise OSError instead: the snapshot may be
    perfectly good, so callers skip it for this attempt, never
    quarantine.  A checkpoint without a manifest (pre-integrity
    snapshots) passes vacuously — the pickle/npz load remains its only
    guard.

    `check_crc=False` verifies existence+size only (metadata ops, no
    data reads) — catches truncation/torn writes but not bit rot; used
    by non-zero ranks of a sharded restore so a world re-formation does
    not multiply full-checkpoint reads by the process count."""
    manifest_path = os.path.join(step_dir, _INTEGRITY_FILE)
    if not os.path.exists(manifest_path):
        return None
    with open(manifest_path) as f:
        try:
            inventory: Dict[str, dict] = json.load(f)["files"]
        except (ValueError, KeyError) as exc:
            return f"garbage integrity manifest (torn write?): {exc!r}"
    for name, meta in inventory.items():
        path = os.path.join(step_dir, name)
        try:
            size = os.path.getsize(path)
        except FileNotFoundError:
            return f"{name}: missing from committed checkpoint"
        if size != meta["size"]:
            return (
                f"{name}: size {size} != manifest {meta['size']} "
                "(torn write)"
            )
        if check_crc:
            crc = file_crc32(path)
            if crc != meta["crc32"]:
                return (
                    f"{name}: crc32 {crc:#010x} != manifest "
                    f"{meta['crc32']:#010x}"
                )
    return None


def _apply_write_fault(state_path: str) -> None:
    """The `ckpt.write` injection site: a `truncate` fault tears the
    just-written state file AFTER its checksum was recorded — exactly the
    corruption a crashed flush produces."""
    spec = faults.fire("ckpt.write")
    if spec is None or spec.kind != "truncate":
        return
    size = os.path.getsize(state_path)
    keep = int(spec.arg) if spec.arg else size // 2
    with open(state_path, "r+b") as f:
        f.truncate(keep)
    logger.warning(
        "FAULT INJECTION: truncated %s to %d of %d bytes",
        state_path, keep, size,
    )


class CheckpointSaver:
    def __init__(self, checkpoint_dir: str, keep_max: int = 3):
        self._dir = checkpoint_dir
        self._keep_max = keep_max
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.sweep_stale_tmp()

    # ------------------------------------------------------------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._dir, f"step_{step:012d}")

    def _is_committed(self, step_dir: str) -> bool:
        """Validity hook: a complete snapshot has a non-empty state file
        (subclasses narrow further, e.g. sharded saves require their
        manifest).  An empty/stateless step dir — a crashed save that got
        as far as the rename, or a stray mkdir — is skipped with a
        warning instead of surfacing later as a restore crash."""
        try:
            state_path = os.path.join(step_dir, _STATE_FILE)
            return os.path.getsize(state_path) > 0
        except (FileNotFoundError, NotADirectoryError):
            # Proven incomplete (no state file / not a dir).  Other
            # OSErrors are transient I/O and must propagate — reporting a
            # good checkpoint as uncommitted on an NFS blip would
            # silently restart training from an older step.
            return False

    def steps(self):
        # An unlistable checkpoint dir raises: pretending it is empty
        # would turn one transient I/O error into a silent fresh start.
        steps = []
        for name in os.listdir(self._dir):
            if (
                not name.startswith("step_")
                or ".tmp" in name
                or name.endswith(_QUARANTINE_SUFFIX)
            ):
                continue
            try:
                step = int(name[len("step_"):])
            except ValueError:
                continue
            if not self._is_committed(os.path.join(self._dir, name)):
                logger.warning(
                    "Skipping incomplete/unreadable checkpoint %s",
                    os.path.join(self._dir, name),
                )
                continue
            steps.append(step)
        return sorted(steps)

    # ------------------------------------------------------------------

    def save(self, state: Any, step: int) -> str:
        """Snapshot a (host or device) pytree at `step`, atomically, with
        a CRC32 integrity manifest covering the state file."""
        import jax

        start = time.monotonic()
        final_dir = self._step_dir(step)
        if os.path.exists(final_dir):
            return final_dir
        if any(isinstance(x, jax.Array) for x in jax.tree.leaves(state)):
            # A caller that hands over device arrays (the trainers'
            # `state_to_host` has its own gather span and hands over
            # host ones).
            with tracing.span(
                "checkpoint.save.gather", bytes=tree_nbytes(state)
            ):
                state = jax.device_get(state)
        tmp_dir = tempfile.mkdtemp(
            prefix=f"step_{step:012d}.tmp", dir=self._dir
        )
        state_path = os.path.join(tmp_dir, _STATE_FILE)
        with tracing.span("checkpoint.save.write") as span:
            with open(state_path, "wb") as f:
                pickle.dump(state, f)
            span.fields["bytes"] = os.path.getsize(state_path)
        with tracing.span(
            "checkpoint.save.crc", bytes=os.path.getsize(state_path)
        ):
            write_integrity_manifest(tmp_dir, [_STATE_FILE])
        with tracing.span("checkpoint.save.commit", bytes=0):
            _apply_write_fault(state_path)
            os.rename(tmp_dir, final_dir)
            save_hist, _restore, saves, _quarantines = _ckpt_metrics()
            save_hist.observe(time.monotonic() - start, kind="full")
            saves.inc(kind="full")
            obs.journal().record("checkpoint_saved", step=step, kind="full")
            logger.info(
                "Saved checkpoint at step %d -> %s", step, final_dir
            )
            self._garbage_collect()
        return final_dir

    def load_latest(self) -> Tuple[Optional[Any], int]:
        """Returns (state, step); (None, 0) when no checkpoint exists.
        Corrupt snapshots (checksum mismatch or unreadable pickle) are
        quarantined and the next-newest good one wins."""
        start = time.monotonic()
        for step in reversed(self.steps()):
            step_dir = self._step_dir(step)
            try:
                reason = verify_integrity(step_dir)
            except OSError:
                # Transient I/O — the snapshot may be intact; skip it for
                # THIS restore, never destroy evidence on a read blip.
                logger.exception(
                    "Could not verify checkpoint %s (transient I/O "
                    "error?); skipping it this restore", step_dir,
                )
                continue
            if reason is not None:
                self._quarantine(step_dir, reason)
                continue
            path = os.path.join(step_dir, _STATE_FILE)
            try:
                # The real restore (read + unpickle); the caller places
                # the state on the device inside `checkpoint.restore`.
                with tracing.span(
                    "checkpoint.restore.load", step=step,
                    bytes=os.path.getsize(path),
                ):
                    with open(path, "rb") as f:
                        state = pickle.load(f)
                _save, restore_hist, _saves, _q = _ckpt_metrics()
                restore_hist.observe(
                    time.monotonic() - start, kind="full"
                )
                obs.journal().record(
                    "checkpoint_restored", step=step, kind="full"
                )
                logger.info("Restored checkpoint from step %d", step)
                return state, step
            except OSError:
                logger.exception(
                    "Could not read checkpoint %s (transient I/O "
                    "error?); skipping it this restore", step_dir,
                )
            except (pickle.UnpicklingError, EOFError, ValueError) as exc:
                # The file read fine but is not a valid pickle stream:
                # corruption the (vacuously-passing, pre-integrity)
                # manifest could not catch.
                self._quarantine(step_dir, f"unloadable state: {exc!r}")
            except Exception:
                # Environment-shaped load failures (ImportError after a
                # bad deploy, MemoryError on a constrained restart) are
                # NOT corruption — quarantining here would eat every
                # snapshot in the dir, newest first.  Skip; the snapshot
                # stays restorable once the environment is fixed.
                logger.exception(
                    "Could not load checkpoint %s (environment error, "
                    "not corruption); skipping it this restore", step_dir,
                )
        return None, 0

    def _quarantine(self, step_dir: str, reason: str):
        """Move a corrupt snapshot aside (never delete: it is the evidence
        for the postmortem) so no future restore can pick it again."""
        target = step_dir + _QUARANTINE_SUFFIX
        # A previous incident at the same step keeps ITS evidence: pick
        # the next free suffix rather than deleting it.
        n = 2
        while os.path.exists(target):
            target = f"{step_dir}{_QUARANTINE_SUFFIX}.{n}"
            n += 1
        logger.error(
            "Quarantining corrupt checkpoint %s -> %s (%s); falling back "
            "to the previous step",
            step_dir, target, reason,
        )
        _save, _restore, _saves, quarantines = _ckpt_metrics()
        quarantines.inc()
        obs.journal().record(
            "checkpoint_quarantined", path=step_dir, reason=reason
        )
        try:
            os.rename(step_dir, target)
        except OSError:
            logger.exception("Quarantine rename failed for %s", step_dir)

    def sweep_stale_tmp(self, grace_s: float = STALE_TMP_GRACE_S):
        """Startup sweep: tmp dirs left by crashed saves (the very
        scenario checkpoints exist for) would otherwise pile up forever.
        Age-guarded — in a multi-process world a peer may be mid-save."""
        try:
            names = os.listdir(self._dir)
        except OSError:
            return
        for name in names:
            if not (name.startswith("step_") and ".tmp" in name):
                continue
            path = os.path.join(self._dir, name)
            try:
                stale = time.time() - os.path.getmtime(path) > grace_s
            except OSError:
                continue  # a peer committed (renamed) it mid-sweep
            if stale:
                logger.warning(
                    "Sweeping stale checkpoint tmp dir %s (crashed save)",
                    path,
                )
                shutil.rmtree(path, ignore_errors=True)

    def _garbage_collect(self):
        # Best-effort: by the time GC runs the new checkpoint is already
        # durable, so a transient I/O blip here must not crash the save
        # (the raise-on-transient policy in steps() protects RESTORES).
        try:
            steps = self.steps()
            for step in steps[: -self._keep_max]:
                shutil.rmtree(self._step_dir(step), ignore_errors=True)
        except OSError:
            logger.exception(
                "Checkpoint GC failed (transient I/O error?); old "
                "snapshots will be collected on a later save"
            )
        self.sweep_stale_tmp()
