"""Step-windowed jax.profiler tracing.

SURVEY.md §5 names `jax.profiler` the cheap observability win: a trace of
N real training steps captures XLA op timings, HBM transfers, and (on
real hardware) TPU utilization, viewable in TensorBoard's Profile plugin
from the same --tensorboard_log_dir the master's scalar service writes.

Usage: `--profile_steps=START,END` on the job; each worker traces its
own training steps with index in [START, END) (1-based, the value of
`trainer.step` after the step runs) into <log_dir>/profile/worker_<id>.
The training loop brackets its work with `before_steps(current, n)` /
`after_steps(current)`, so tracing starts BEFORE the first in-window
step executes (its XLA compile is captured) and stops right after the
last.  Windowed trainers that run K steps per device call (PS/AllReduce
`train_window`) trace the superset of whole windows overlapping the
range — boundaries round outward to window edges, never silently skip.
A window the loop has already passed logs a loud warning instead of
silently capturing nothing.
"""

from __future__ import annotations

import atexit
import os
from typing import Optional

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("common.profiler")


def parse_profile_steps(spec: str):
    """'100,120' -> (100, 120); '' -> None."""
    if not spec:
        return None
    try:
        start, end = (int(s) for s in spec.split(","))
    except ValueError as e:
        raise ValueError(
            f"--profile_steps must be 'start,end', got {spec!r}"
        ) from e
    if not (0 <= start < end):
        raise ValueError(f"--profile_steps needs 0 <= start < end: {spec!r}")
    return start, end


class StepProfiler:
    """Starts/stops one jax.profiler trace as the step counter crosses
    the configured window.  Inactive (all no-ops) when unconfigured;
    --profile_steps without a log dir is rejected loudly (a silently
    dangling flag is the round-1 failure mode this replaces)."""

    def __init__(self, log_dir: str, profile_steps: str, worker_id: int = 0):
        if profile_steps and not log_dir:
            raise ValueError(
                "--profile_steps requires --tensorboard_log_dir (traces "
                "are written under it for the TensorBoard Profile plugin)"
            )
        window = parse_profile_steps(profile_steps)
        self._window = window
        self._worker_id = int(worker_id)
        self._dir = (
            os.path.join(log_dir, "profile", f"worker_{worker_id}")
            if window
            else ""
        )
        self._tracing = False
        self._done = False
        if window:
            # Shutdown-path flush: a worker that exits (or is preempted)
            # mid-window would otherwise never reach the task loop's
            # stop() and lose the whole trace.  atexit + the worker
            # main's SIGTERM->SystemExit conversion flush a PARTIAL trace
            # instead; stop() is idempotent, so the normal path is
            # unaffected.
            atexit.register(self.stop)

    def before_steps(self, current_step: int, n: int = 1):
        """About to run steps current_step+1 .. current_step+n: start the
        trace if any of them fall in the window (called BEFORE the device
        dispatch so the first in-window step — and its compile — is
        captured even when n steps run as one fused window)."""
        if self._window is None or self._done or self._tracing:
            return
        start, end = self._window
        first, last = current_step + 1, current_step + n
        if first >= end:
            logger.warning(
                "Profile window [%d, %d) already passed at step %d — "
                "no trace captured (window smaller than the training "
                "loop's step granularity?)",
                start,
                end,
                current_step,
            )
            self._done = True
            return
        if last >= start:
            try:
                import jax

                # Inside the guard: an unwritable/unmounted trace dir must
                # disable profiling, never crash training.
                os.makedirs(self._dir, exist_ok=True)
                jax.profiler.start_trace(self._dir)
                self._tracing = True
                logger.info(
                    "Profiling steps [%d, %d) -> %s", start, end, self._dir
                )
                self._journal_window("open", at_step=current_step)
            except Exception:
                logger.exception("start_trace failed; profiling disabled")
                self._done = True

    def after_steps(self, current_step: int, wait_for=None):
        """Steps up to current_step have run: stop once the last
        in-window step (end - 1) is done.  `wait_for` is what the last
        dispatched program returns (its loss): the stop blocks on it so
        that the trace ends after that program, not inside it."""
        if self._tracing and current_step >= self._window[1] - 1:
            self.stop(wait_for)

    def stop(self, wait_for=None):
        # Drop the shutdown hook first (bound-method equality): repeated
        # in-process construction (tests, e2e harnesses) must not pin
        # every historical profiler until interpreter exit.
        atexit.unregister(self.stop)
        if not self._tracing:
            return
        import time

        import jax

        start = time.monotonic()
        try:
            # The device still runs the last dispatched program: wait
            # for it (or, with nothing to wait on, for every effect the
            # process has in flight) before the trace is cut.
            if wait_for is not None:
                jax.block_until_ready(wait_for)
            else:
                jax.effects_barrier()
            jax.profiler.stop_trace()
            logger.info("Profile trace written to %s", self._dir)
        except Exception:
            logger.exception("stop_trace failed")
        self._tracing = False
        self._done = True
        # The wait and the dump: what a traced run stalls for here.
        self._journal_window(
            "close", duration_s=round(time.monotonic() - start, 6)
        )

    def _journal_window(self, action: str, at_step=None, duration_s=None):
        """Journal a ``profile_window`` event so postmortem timelines
        (obs.report) can point at the TensorBoard trace that covers an
        anomalous window.  Best-effort: journaling failure must never
        break tracing (this also runs on the atexit shutdown path)."""
        try:
            from elasticdl_tpu import obs

            fields = dict(
                worker_id=self._worker_id,
                action=action,
                step_start=self._window[0],
                step_end=self._window[1],
                trace_dir=self._dir,
            )
            if at_step is not None:
                fields["at_step"] = int(at_step)
            if duration_s is not None:
                fields["duration_s"] = duration_s
            obs.journal().record("profile_window", **fields)
        except Exception:
            logger.exception("profile_window journal record failed")
