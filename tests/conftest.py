"""Shared fixtures."""
import os
import time

import pytest

from repro.core.jif import JifReader


class ReadPacer:
    """Paces the JIF chunk reads of every restore in this process to a set
    bandwidth, so a test can keep a restore in flight long enough to join,
    cancel or overlap it.  Call it with ``bw`` (bytes/s) and, optionally,
    the image paths to pace (all images when none are named); ``bw=None``
    lifts the pacing of those paths."""

    def __init__(self):
        self._bw = {}  # realpath of an image (None: any image) -> bytes/s

    def __call__(self, bw, *paths):
        for key in [os.path.realpath(p) for p in paths] or [None]:
            if bw is None:
                self._bw.pop(key, None)
            else:
                self._bw[key] = bw

    def delay_s(self, path: str, nbytes: int) -> float:
        bw = self._bw.get(os.path.realpath(path), self._bw.get(None))
        return nbytes / bw if bw else 0.0


@pytest.fixture
def slow_reads(monkeypatch):
    """A :class:`ReadPacer` wrapped around ``JifReader.pread_chunks``: each
    read sleeps ``len(raw) / bw`` after it returns.  Unpaced until called."""
    pacer = ReadPacer()
    read = JifReader.pread_chunks

    def paced(reader, chunk_start, n):
        raw = read(reader, chunk_start, n)
        delay = pacer.delay_s(reader.path, len(raw))
        if delay:
            time.sleep(delay)
        return raw

    monkeypatch.setattr(JifReader, "pread_chunks", paced)
    return pacer
