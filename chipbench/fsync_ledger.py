"""What a durable store's files hold for sure: every write and every
completed fsync of a file under one directory, recorded by wrapping
``os.open``, ``os.write``, ``os.fsync`` and ``os.fdatasync`` while the
ledger is installed.

The durable cells' comparison uses it twice.  At each ack: did a
completed fsync cover every byte the request wrote under the directory
(``uncovered``)?  At the window's close: a crash image, every file cut
back to what a completed fsync covered when the last ack returned
(``image``, then ``cut`` once the store is closed), from which the store
is reopened.  Files are told apart by inode, so a rename keeps a file's
record; a file opened empty starts a new one, so a reused inode does not
inherit an old file's fsyncs.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Tuple

FileKey = Tuple[int, int]          # (st_dev, st_ino)


class FsyncLedger:
    def __init__(self, root: str):
        self.root = os.path.realpath(root) + os.sep
        self.synced: Dict[FileKey, int] = {}   # bytes a completed fsync covers
        self.written: Dict[FileKey, int] = {}  # end of the newest write since mark()
        self._under: Dict[FileKey, bool] = {}
        self._lock = threading.Lock()
        self._orig = None

    # ------------------------------------------------------------ wrapping
    def install(self) -> "FsyncLedger":
        if self._orig is None:
            self._orig = {n: getattr(os, n)
                          for n in ("open", "write", "fsync", "fdatasync")}
            os.open = self._open
            os.write = self._write
            os.fsync = self._syncer("fsync")
            os.fdatasync = self._syncer("fdatasync")
        return self

    def uninstall(self) -> None:
        if self._orig is not None:
            for name, fn in self._orig.items():
                setattr(os, name, fn)
            self._orig = None

    def _file(self, fd: int):
        st = os.fstat(fd)
        key = (st.st_dev, st.st_ino)
        under = self._under.get(key)
        if under is None:
            try:
                path = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                path = ""
            under = self._under[key] = path.startswith(self.root)
        return key, st.st_size, under

    def _open(self, path, flags, mode=0o777, *, dir_fd=None):
        fd = self._orig["open"](path, flags, mode, dir_fd=dir_fd)
        key, size, under = self._file(fd)
        if under and size == 0:
            with self._lock:
                self.synced[key] = 0
        return fd

    def _write(self, fd, data):
        n = self._orig["write"](fd, data)
        key, size, under = self._file(fd)
        if under:
            with self._lock:
                self.written[key] = max(self.written.get(key, 0), size)
        return n

    def _syncer(self, name: str):
        real = self._orig[name]

        def sync(fd):
            key, size, under = self._file(fd)   # what the call will cover
            real(fd)
            if under:
                with self._lock:
                    self.synced[key] = max(self.synced.get(key, 0), size)
        return sync

    # ------------------------------------------------------------- reading
    def mark(self) -> None:
        """Start a request: forget the writes before it."""
        with self._lock:
            self.written = {}

    def uncovered(self) -> int:
        """Files written since ``mark()`` that hold bytes no completed
        fsync covers."""
        with self._lock:
            return sum(self.synced.get(k, 0) < end
                       for k, end in self.written.items())

    def image(self) -> Dict[str, int]:
        """The crash image as of now: each file under the directory, by
        path, with the bytes a completed fsync covers."""
        out = {}
        with self._lock:
            for dirpath, _, names in os.walk(self.root):
                for name in names:
                    path = os.path.join(dirpath, name)
                    st = os.stat(path)
                    out[path] = min(st.st_size, self.synced.get(
                        (st.st_dev, st.st_ino), 0))
        return out

    def cut(self, image: Dict[str, int]) -> None:
        """Bring the closed store's directory back to ``image``: files
        made since are removed, the others cut to their covered bytes."""
        for dirpath, _, names in os.walk(self.root):
            for name in names:
                path = os.path.join(dirpath, name)
                if path not in image:
                    os.unlink(path)
                elif os.path.getsize(path) > image[path]:
                    os.truncate(path, image[path])
