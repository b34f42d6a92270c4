"""`elasticdl train` as a user runs it, driven by a parent that never
imports jax: the master is the child process, the worker (which holds the
chip) is the master's child.  Copied in outline from `chip_smoke._run_job`
/ `_read_job`; the yardstick may not move with the program.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import signal
import subprocess
import sys
import time

from . import journal

JOB_NAME = "perfbench"
_LAUNCHED = re.compile(r"Launched worker (\d+) \(pid (\d+)\)")
_MESH = re.compile(
    r"Built mesh (\d+)x(\d+) .* over (\d+) (\S+) device\(s\) \[(.*)\]"
)
_LOSS = re.compile(r"task \d+ done: step=(\d+) loss=(\S+)")
_CODEC = re.compile(r"ETRF record codec: (\S+)")


class JobError(Exception):
    """The job did not do what the run needs; the message is the finding."""


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f"--- tail of {path} ---\n" + f.read().decode(
                "utf-8", "replace"
            )
    except OSError as exc:
        return f"--- {path}: {exc} ---"


class Follower:
    """Reads whole new lines of a growing journal, from where it left."""

    def __init__(self, path: str):
        self._path, self._offset = path, 0

    def new_events(self) -> list:
        try:
            with open(self._path, "rb") as f:
                if os.fstat(f.fileno()).st_size < self._offset:
                    self._offset = 0  # the journal was rotated
                f.seek(self._offset)
                chunk = f.read()
        except FileNotFoundError:
            return []
        end = chunk.rfind(b"\n") + 1
        self._offset += end
        events = []
        for line in chunk[:end].splitlines():
            try:
                events.append(json.loads(line))
            except ValueError:
                continue
        return events


class Job:
    def __init__(self, root: str, work: str, argv: list, env: dict):
        self.root, self.work = root, work
        self.ckpt = os.path.join(work, "ckpt")
        self.tb = os.path.join(work, "tb")
        self.log = os.path.join(work, "job.log")
        self.argv = [
            f"--job_name={JOB_NAME}", "--num_workers=1",
            "--model_zoo=model_zoo", *argv,
            f"--checkpoint_dir={self.ckpt}",
            f"--tensorboard_log_dir={self.tb}",
        ]
        self._env, self._proc, self._log_file = env, None, None

    def start(self) -> None:
        cmd = [sys.executable, "-m", "elasticdl_tpu.client.main", "train",
               *self.argv]
        self._log_file = open(self.log, "wb")
        # Its own session: the worker stays in it, so one killpg ends all.
        self._proc = subprocess.Popen(
            cmd, cwd=self.root, env=self._env, stdout=self._log_file,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def returncode(self):
        return self._proc.poll()

    def wait(self, timeout_s: float) -> int:
        try:
            return self._proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise JobError(
                f"the job did not end within {timeout_s:.0f}s\n"
                + tail(self.log) + "\n" + self.worker_tail()
            )

    def stop(self) -> None:
        """End every process the job started, and wait for the master."""
        if self._proc is None:
            return
        try:
            os.killpg(self._proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self._proc.wait()
        # The worker is the master's child, re-parented when the master
        # dies: wait until its pid is gone too.
        for _, pid in self.launched():
            deadline = time.monotonic() + 30
            while _alive(pid) and time.monotonic() < deadline:
                time.sleep(0.05)
        if self._log_file is not None:
            self._log_file.close()

    def launched(self) -> list:
        """[(worker_id, pid)] in launch order, by the master's log."""
        try:
            with open(self.log, errors="replace") as f:
                return [(int(w), int(p)) for w, p in _LAUNCHED.findall(f.read())]
        except OSError:
            return []

    def worker_logs(self) -> list:
        return sorted(
            glob.glob(os.path.join(
                self.ckpt, f"{JOB_NAME}_worker_logs", "worker_*.log")),
            key=os.path.getmtime,
        )

    def worker_tail(self) -> str:
        return "\n".join(tail(p, 6000) for p in self.worker_logs()[-1:])

    def worker_events(self) -> list:
        """Every worker process's journal, merged in time order."""
        events = []
        for path in glob.glob(os.path.join(self.tb, "events_worker_*.jsonl")):
            events.extend(journal.load(path))
        return sorted(events, key=lambda e: e["ts"])

    def device_now(self):
        """The device the worker took, as soon as its log says so (its first
        lines, before any state is built); None until then."""
        for path in self.worker_logs():
            with open(path, errors="replace") as f:
                mesh = _MESH.search(f.read())
            if mesh:
                return {"platform": mesh.group(4), "kind": mesh.group(5),
                        "count": int(mesh.group(3))}
        return None

    def facts(self) -> dict:
        """Device, codec and losses, as the workers' own logs state them."""
        text = ""
        for path in self.worker_logs():
            with open(path, errors="replace") as f:
                text += f.read()
        mesh = _MESH.search(text)
        if mesh is None:
            raise JobError("no 'Built mesh' line in the worker log\n"
                           + self.worker_tail())
        losses = [float(l) for _, l in _LOSS.findall(text)]
        codec = _CODEC.search(text)
        return {
            "device": {"platform": mesh.group(4), "kind": mesh.group(5),
                       "count": int(mesh.group(3))},
            "mesh": [int(mesh.group(1)), int(mesh.group(2))],
            "losses": len(losses),
            "losses_finite": bool(losses) and all(map(math.isfinite, losses)),
            "codec": codec.group(1) if codec else None,
        }


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    # A zombie still answers kill(0): read its state.
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False
