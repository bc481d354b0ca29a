"""Per-sandbox swap files (§3.4, Fig. 5).

Each instance owns two files, never shared between sandboxes (security,
§3.4) and deleted at termination:

  * :class:`SwapFile` — the page-fault file.  Units are written individually
    (hash-table of offsets, like the Swapping Mgr's de-dup table) and read
    back **one ``pread`` at a time** — the random-read path.
  * :class:`ReapFile` — the REAP file.  The recorded working set is written
    with one contiguous ``pwritev`` and read back with a single sequential
    ``preadv`` over the saved scatter io-vectors.

Both classes also serve *vectored* batch reads (:meth:`_FileBase.read_units`):
the fault set is extent-sorted, adjacent extents are merged into runs, and
each run is one ``preadv`` syscall — this is what turns a wake storm's
hundreds of random faults into a handful of sequential disk passes.

Real file descriptors and real disk IO: the benchmarks measure the actual
random-vs-sequential asymmetry of this host's storage.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Hashable, List, Sequence, Tuple

import numpy as np

#: max io-vectors per preadv/pwritev call (POSIX guarantees >= 16; Linux 1024)
IOV_MAX = 1024

_HAVE_PREADV = hasattr(os, "preadv")
_HAVE_PWRITEV = hasattr(os, "pwritev")


def _preadv_full(fd, bufs, offset: int) -> int:
    """``preadv`` that retries short reads (Linux caps one read at ~2 GiB;
    signals can also truncate) until every buffer is filled.  Returns the
    number of syscalls issued; raises ``EOFError`` on a true EOF."""
    views = [memoryview(b) for b in bufs]
    want = sum(len(v) for v in views)
    done, calls = 0, 0
    while done < want:
        pending, skip = [], done
        for v in views:
            if skip >= len(v):
                skip -= len(v)
                continue
            pending.append(v[skip:] if skip else v)
            skip = 0
        n = os.preadv(fd, pending, offset + done)
        calls += 1
        if n <= 0:                         # pragma: no cover - EOF/IO error
            raise EOFError(f"preadv: short read at offset {offset + done}")
        done += n
    return calls


def pwritev_full(fd, bufs, offset: int) -> int:
    """``pwritev`` that retries short writes (Linux caps one write at
    ~2 GiB) until every buffer is on disk.  Returns the syscall count."""
    views = [memoryview(b) for b in bufs]
    want = sum(len(v) for v in views)
    done, calls = 0, 0
    while done < want:
        pending, skip = [], done
        for v in views:
            if skip >= len(v):
                skip -= len(v)
                continue
            pending.append(v[skip:] if skip else v)
            skip = 0
        if _HAVE_PWRITEV:
            n = os.pwritev(fd, pending[:IOV_MAX], offset + done)
        else:                              # pragma: no cover - non-POSIX
            n = os.pwrite(fd, pending[0], offset + done)
        calls += 1
        if n <= 0:                         # pragma: no cover - IO error
            raise OSError(f"pwritev: wrote nothing at offset {offset + done}")
        done += n
    return calls


@dataclass
class _Extent:
    offset: int
    nbytes: int
    dtype: str
    shape: Tuple[int, ...]


@dataclass
class WriteReceipt:
    """What one batch of unit writes actually did to the disk tier.

    ``logical_bytes`` is what a verbatim per-sandbox layout would store;
    the other fields break that down for content-addressed backends
    (``SwapStore``).  Plain files store everything verbatim, so for them
    ``stored_bytes == logical_bytes``.
    """
    logical_bytes: int = 0       # raw bytes the caller asked to persist
    stored_bytes: int = 0        # new on-disk bytes this write added
    dedup_bytes: int = 0         # raw bytes satisfied by existing segments
    elided_bytes: int = 0        # raw bytes elided to constant-fill metadata

    def __iadd__(self, o: "WriteReceipt") -> "WriteReceipt":
        self.logical_bytes += o.logical_bytes
        self.stored_bytes += o.stored_bytes
        self.dedup_bytes += o.dedup_bytes
        self.elided_bytes += o.elided_bytes
        return self


def read_extents(fd, extents: Sequence[Tuple[int, int]]
                 ) -> Tuple[List[bytearray], int]:
    """Vectored read of ``(offset, nbytes)`` extents pre-sorted by offset:
    adjacent extents merge into runs and each run is one ``preadv``
    (chunked at ``IOV_MAX`` io-vectors).  Returns the filled buffers in
    input order plus the syscall count — shared by the per-sandbox files
    and the content-addressed ``SwapStore`` segment reads."""
    bufs: List[bytearray] = []
    run: List[bytearray] = []
    run_start = run_end = None
    calls = 0

    def flush():
        nonlocal calls
        if not run:
            return
        if _HAVE_PREADV:
            pos, i = run_start, 0
            while i < len(run):
                chunk = run[i:i + IOV_MAX]
                calls += _preadv_full(fd, chunk, pos)
                pos += sum(len(b) for b in chunk)
                i += IOV_MAX
        else:                              # pragma: no cover - non-POSIX
            pos = run_start
            for buf in run:
                buf[:] = os.pread(fd, len(buf), pos)
                calls += 1
                pos += len(buf)
        run.clear()

    for off, n in extents:
        if run_end is not None and off != run_end:
            flush()
            run_start = None
        if run_start is None:
            run_start = off
        buf = bytearray(n)
        run.append(buf)
        bufs.append(buf)
        run_end = off + n
    flush()
    return bufs, calls


class _FileBase:
    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.fd = os.open(path, os.O_RDWR | os.O_CREAT | os.O_TRUNC, 0o600)
        self.extents: Dict[Hashable, _Extent] = {}
        self._append_at = 0
        self.bytes_written = 0
        self.bytes_read = 0
        self.reads = 0
        self.writes = 0

    def delete(self) -> None:
        """Sandbox termination: close and unlink (§3.4).  Any ``.tmp``
        left by a write that crashed pre-commit goes with it."""
        if self.fd is not None:
            os.close(self.fd)
            self.fd = None
        for p in (self.path, self.path + ".tmp"):
            if os.path.exists(p):
                os.unlink(p)
        self.extents.clear()

    def __contains__(self, key) -> bool:
        return key in self.extents

    @property
    def file_bytes(self) -> int:
        return self._append_at

    # ------------------------------------------------------------- vectored
    def read_units(self, keys: Sequence[Hashable]
                   ) -> Dict[Hashable, np.ndarray]:
        """Vectored batch read of a fault set.

        Extents are sorted by file offset and adjacent extents are merged
        into runs; each run is served by one ``preadv`` (chunked at
        ``IOV_MAX`` io-vectors).  ``self.reads`` counts *syscalls*, so the
        per-unit vs vectored asymmetry is directly observable.
        """
        exts = sorted(((k, self.extents[k]) for k in keys),
                      key=lambda kv: kv[1].offset)
        bufs, calls = read_extents(self.fd,
                                   [(e.offset, e.nbytes) for _, e in exts])
        self.reads += calls
        out: Dict[Hashable, np.ndarray] = {}
        for (key, ext), buf in zip(exts, bufs):
            self.bytes_read += ext.nbytes
            out[key] = np.frombuffer(buf, ext.dtype).reshape(ext.shape).copy()
        return out

    def read_units_iter(self, keys: Sequence[Hashable],
                        chunk_bytes: int = 1 << 20):
        """Streaming variant of :meth:`read_units`: yields ``{key: array}``
        dicts of ~``chunk_bytes`` each, one vectored batch read per chunk.
        Callers overlap downstream work (install, decompress) with the next
        chunk's IO instead of materializing the whole fault set at once —
        the building block of the streamed wake pipeline."""
        batch: List[Hashable] = []
        pending = 0
        for k in keys:
            batch.append(k)
            pending += self.extents[k].nbytes
            if pending >= chunk_bytes:
                yield self.read_units(batch)
                batch, pending = [], 0
        if batch:
            yield self.read_units(batch)


class SwapFile(_FileBase):
    """Page-fault swap file: per-unit writes, random per-unit reads."""

    def write_unit(self, key: Hashable, arr: np.ndarray) -> None:
        arr = np.ascontiguousarray(arr)
        buf = arr.tobytes()
        ext = self.extents.get(key)
        if ext is None or ext.nbytes < len(buf):
            ext = _Extent(self._append_at, len(buf), str(arr.dtype), arr.shape)
            self._append_at += len(buf)
        else:
            ext = _Extent(ext.offset, len(buf), str(arr.dtype), arr.shape)
        pwritev_full(self.fd, [buf], ext.offset)
        self.extents[key] = ext
        self.bytes_written += len(buf)
        self.writes += 1

    def write_units(self, items: Sequence[Tuple[Hashable, np.ndarray]]
                    ) -> WriteReceipt:
        r = WriteReceipt()
        for k, a in items:
            self.write_unit(k, a)
            r.logical_bytes += a.nbytes
            r.stored_bytes += a.nbytes       # verbatim: no dedup/elision
        return r

    def read_unit(self, key: Hashable) -> np.ndarray:
        """One random read — the page-fault swap-in path."""
        ext = self.extents[key]
        buf = os.pread(self.fd, ext.nbytes, ext.offset)
        self.bytes_read += ext.nbytes
        self.reads += 1
        return np.frombuffer(buf, ext.dtype).reshape(ext.shape).copy()


class ReapFile(_FileBase):
    """REAP file: one batch-sequential write, one batch-sequential read."""

    def write_batch(self, items: Sequence[Tuple[Hashable, np.ndarray]]) -> None:
        """One vectored sequential write (``pwritev``) of the scatter
        io-vectors, committed torn-write-safely.

        The blob is written to ``<path>.tmp`` and ``os.rename``d over the
        live file only once fully on disk — rename is atomic within a
        filesystem, so a crash mid-write leaves the *previous* REAP
        snapshot (file and extent table) fully intact instead of a
        half-written scatter that would feed garbage into the next wake.
        Extents are installed only after the rename for the same reason.
        The tmp file is truncated-by-creation so ``file_bytes`` always
        reflects the real on-disk footprint (a smaller rewrite must not
        leave stale trailing bytes)."""
        bufs: List[bytes] = []
        new_extents: Dict[Hashable, _Extent] = {}
        off = 0
        for key, arr in items:
            arr = np.ascontiguousarray(arr)
            b = arr.tobytes()
            new_extents[key] = _Extent(off, len(b), str(arr.dtype), arr.shape)
            bufs.append(b)
            off += len(b)
        tmp = self.path + ".tmp"
        tmp_fd = os.open(tmp, os.O_RDWR | os.O_CREAT | os.O_TRUNC, 0o600)
        try:
            if bufs:
                pwritev_full(tmp_fd, bufs, 0)
                self.writes += 1
            os.rename(tmp, self.path)      # the commit point
        except BaseException:
            os.close(tmp_fd)
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        os.close(self.fd)
        self.fd = tmp_fd
        self.extents = new_extents
        self._append_at = off
        self.bytes_written += off

    def read_unit(self, key: Hashable) -> np.ndarray:
        """Random single-extent read (pagefault-mode access to a REAP file)."""
        ext = self.extents[key]
        buf = os.pread(self.fd, ext.nbytes, ext.offset)
        self.bytes_read += ext.nbytes
        self.reads += 1
        return np.frombuffer(buf, ext.dtype).reshape(ext.shape).copy()

    def read_batch(self) -> Dict[Hashable, np.ndarray]:
        """preadv analogue: one sequential read of the whole extent."""
        blob = os.pread(self.fd, self._append_at, 0)
        self.bytes_read += len(blob)
        self.reads += 1
        mv = memoryview(blob)                 # zero-copy scatter
        return {key: np.frombuffer(
                    mv[ext.offset:ext.offset + ext.nbytes],
                    ext.dtype).reshape(ext.shape)
                for key, ext in self.extents.items()}
