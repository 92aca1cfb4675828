"""The measured window of a job that commits versions.

A learner of this repository has no hook to stop and reports nothing
while it runs; what it does at every unit of progress is call
``rabit_tpu.checkpoint``.  The benchmark wraps that one public call:
each commit is stamped on the host clock after it returns (a commit
follows a device-to-host fetch, so the clock closes on finished work),
and at the deadline the wrapper raises :class:`WindowClosed`, which is
how the harness leaves ``run()`` (not by a calibrated ``max_iter``).

Only whole versions count: the window opens at the commit that ends
warm-up and the last version counted is the last one committed before
the deadline.

With several ranks every rank must leave at the same version, or the
others wait in the next collective for ever.  Rank 0's clock decides:
at its first commit past the deadline it writes ``version + 1`` into a
small file all ranks have mapped, and every rank raises after
committing that version.  A rank reads the word only after the
collective of the next version, which rank 0 entered after writing it.
"""
from __future__ import annotations

import gc
import mmap
import os
import struct
import time


class WindowClosed(Exception):
    """Raised from the commit wrapper to leave the learner's loop."""


class StopWord:
    """One 64-bit word in a file every rank maps: the version at which
    all ranks stop (0 = not decided)."""

    def __init__(self, path: str):
        fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o600)
        try:
            if os.fstat(fd).st_size < 8:
                os.write(fd, b"\0" * 8)
            self._map = mmap.mmap(fd, 8)
        finally:
            os.close(fd)

    def get(self) -> int:
        return struct.unpack_from("<q", self._map, 0)[0]

    def set(self, version: int) -> None:
        struct.pack_into("<q", self._map, 0, version)


class VersionClock:
    """Stamps commits, opens the window after ``warmup`` versions, closes
    it ``seconds`` later.  ``on_open`` / ``on_close`` run at those two
    commits (the traced run starts and stops the profiler there)."""

    def __init__(self, commit, version_number, seconds: float, warmup: int,
                 decides: bool, stop_word: StopWord | None,
                 on_open=None, on_close=None):
        self._commit = commit
        self._version_number = version_number
        self.seconds = seconds
        self.warmup = warmup
        self._decides = decides
        self._stop = stop_word
        self._on_open = on_open
        self._on_close = on_close
        self.stamps: list[float] = []      # perf_counter after each commit
        self.commit_s: list[float] = []    # seconds inside each commit
        self.first_wall = None             # time.time() at the 1st commit
        self.opened_wall = None            # time.time() at the opening
        self.deadline = None
        self.stop_at = None                # version at which to leave
        self.failed = 0

    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        before = self._version_number()
        self._commit(*args, **kwargs)
        now = time.perf_counter()
        version = self._version_number()
        if version != before + 1:          # a commit that was lost
            self.failed += 1
        self.commit_s.append(now - t0)
        if self.first_wall is None:
            self.first_wall = time.time()
        if len(self.stamps) + 1 == self.warmup:
            # the harness's own garbage is out of the window; the
            # program's collector stays on
            gc.collect()
            gc.freeze()
            if self._on_open is not None:
                self._on_open()
            now = time.perf_counter()
            self.opened_wall = time.time()
            self.deadline = now + self.seconds
        self.stamps.append(now)
        if self.deadline is None:
            return
        if self.stop_at is None:
            if self._stop is None:
                if now > self.deadline:
                    self.stop_at = version
            elif self._decides:
                if now > self.deadline:
                    self.stop_at = version + 1
                    self._stop.set(self.stop_at)
            else:
                self.stop_at = self._stop.get() or None
        if self.stop_at is not None and version >= self.stop_at:
            if self._on_close is not None:
                self._on_close()
            raise WindowClosed(version)

    # ---- what the window held ----------------------------------------
    def counted(self) -> list[float]:
        """Stamps of the window: the opening commit, then every commit
        up to the deadline."""
        opening = self.warmup - 1
        return [t for t in self.stamps[opening:] if t <= self.deadline]

    def versions(self) -> int:
        return len(self.counted()) - 1

    def span_s(self) -> float:
        c = self.counted()
        return c[-1] - c[0]

    def version_gaps(self) -> list[float]:
        c = self.counted()
        return [b - a for a, b in zip(c, c[1:])]

    def commit_seconds(self) -> list[float]:
        """Seconds inside the commits of the counted versions."""
        opening = self.warmup
        return self.commit_s[opening:opening + self.versions()]
