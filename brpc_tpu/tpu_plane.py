"""Device data plane — the PJRT-backed transport under ``tpu://`` endpoints.

Python face of ``native/src/tpu.{h,cc}`` (≙ the reference's RDMA transport,
``rdma/rdma_endpoint.h`` + ``rdma/block_pool.cpp``, re-designed for TPU):

* ``init()`` dlopens a PJRT C API plugin — the installed ``libtpu``
  package's library, or the one ``$TRPC_PJRT_PLUGIN`` names (the fake
  plugin in tests) — and creates a client of its own.  No JAX involvement
  — the native core talks PJRT directly; next to JAX's client in one
  process, libtpu grants this second one.
* ``h2d()/d2h()`` move bytes host↔HBM through single DMA transfers whose
  completion events store 1 into a butex and wake waiting fibers
  (the butex↔device-event seam the north star names: a fiber awaiting a
  device transfer costs no thread).
* RPC attachments ride this plane zero-copy: a large attachment lands in
  ONE IOBuf block (Socket::frame_bytes_hint) and that block's memory is
  the DMA source — ``stats()["zero_copy_sends"]`` counts the pointer-
  identity transfers, ``gather_copies`` the multi-block sends that needed
  one gather (never silent).
* Channels to ``tpu://`` endpoints run an explicit handshake on the
  connection's first call (meta tag 14) and settle into ``device`` or
  ``fallback_tcp`` — visible via ``Channel.transport_state``, never a
  silent downgrade (≙ rdma_endpoint.h:95 FALLBACK_TCP).
"""

from __future__ import annotations

import ctypes
import errno
import glob
import os
import threading
from typing import Dict, Optional, Tuple

from brpc_tpu._native import lib

TRANSPORT_STATES = {0: "tcp", 1: "handshaking", 2: "device",
                    3: "fallback_tcp"}


_GOOGLE_PCI_VENDOR = "0x1ae0"


def _google_pci(dev_dir: str) -> bool:
    try:
        with open(os.path.join(dev_dir, "vendor")) as f:
            return f.read().strip() == _GOOGLE_PCI_VENDOR
    except OSError:
        return False


def chip_attached() -> bool:
    """A TPU is attached when its driver's device node exists
    (/dev/accel0, ...) or a vfio group holds a Google PCI device (TPU v5e
    is handed to user space through vfio; hosts also load vfio for other
    devices, so /dev/vfio alone says nothing).  Checked before libtpu is
    loaded: with no chip, libtpu's client creation blocks for minutes."""
    if glob.glob("/dev/accel[0-9]*"):
        return True
    return any(_google_pci(d)
               for g in glob.glob("/dev/vfio/[0-9]*")
               for d in glob.glob(f"/sys/kernel/iommu_groups/"
                                  f"{os.path.basename(g)}/devices/*"))


_resolve_error = ""  # why init() found no plugin to load (Python side)


def _libtpu_path() -> str:
    """The installed libtpu package's library; no package, or no chip for
    it, is an error (RuntimeError), never a search."""
    if not chip_attached():
        raise RuntimeError("no TPU attached (no /dev/accel*, no vfio "
                           "group with a Google PCI device) and "
                           "TRPC_PJRT_PLUGIN is unset")
    try:
        import libtpu
    except ImportError as e:
        raise RuntimeError(
            "no PJRT plugin: the libtpu package is not installed and "
            "TRPC_PJRT_PLUGIN is unset") from e
    return libtpu.get_library_path()


def init(plugin_path: Optional[str] = None,
         timeout_s: Optional[float] = None) -> bool:
    """Bring up the device plane on ``plugin_path``, else
    ``$TRPC_PJRT_PLUGIN``, else the installed libtpu; returns
    availability.  Idempotent.  On failure the reason is in
    :func:`error` and callers fall back to TCP explicitly.

    ``timeout_s`` bounds the bring-up: client creation can block for
    minutes on a machine whose chip is broken, and a caller that must
    fail fast gets ``TimeoutError`` instead (the stuck init thread is
    abandoned; the process should exit)."""
    global _resolve_error
    L = lib()
    if L.trpc_tpu_plane_available():
        return True
    path = plugin_path or os.environ.get("TRPC_PJRT_PLUGIN")
    if not path:
        try:
            path = _libtpu_path()
        except RuntimeError as e:
            _resolve_error = str(e)
            return False
    _resolve_error = ""
    t = threading.Thread(target=L.trpc_tpu_plane_init,
                         args=(path.encode(),), name="tpu-plane-init",
                         daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise TimeoutError(
            f"device plane init did not finish in {timeout_s}s ({path})")
    return bool(L.trpc_tpu_plane_available())


def api_version() -> Dict[str, Tuple[int, int]]:
    """PJRT C API versions: ``header`` (what the native core was compiled
    against) and ``plugin`` (the loaded plugin's; (-1, -1) before init)."""
    out = (ctypes.c_int * 4)()
    lib().trpc_tpu_plane_api_version(out)
    return {"header": (out[0], out[1]), "plugin": (out[2], out[3])}


def available() -> bool:
    return bool(lib().trpc_tpu_plane_available())


def error() -> str:
    return _resolve_error or (lib().trpc_tpu_plane_error() or b"").decode()


def platform() -> str:
    return (lib().trpc_tpu_plane_platform() or b"").decode()


def device_count() -> int:
    return lib().trpc_tpu_device_count()


class DeviceBuffer:
    """A byte buffer resident in HBM.  Handle semantics are versioned
    (ABA-safe) like SocketIds; ``free()`` is idempotent.

    Source lifetime is handled natively: the ctypes boundary copies the
    bytes once and the DMA's release hook frees the copy when the
    transfer is done — Python object lifetimes never gate the DMA."""

    __slots__ = ("_id", "_len")

    def __init__(self, buf_id: int, length: int):
        self._id = buf_id
        self._len = length

    def __len__(self) -> int:
        return self._len

    @property
    def handle(self) -> int:
        return self._id

    def wait(self, timeout_s: float = 30.0) -> None:
        """Block (fiber-friendly) until the buffer is resident in HBM."""
        rc = lib().trpc_tpu_buf_wait(self._id, int(timeout_s * 1e6))
        if rc == 0:
            return
        if rc == -errno.ETIMEDOUT:
            raise TimeoutError(f"device transfer not ready: rc={rc}")
        raise IOError(f"device transfer failed: rc={rc} ({error()})")

    def to_host(self) -> bytes:
        """DMA the buffer back to host memory."""
        L = lib()
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = L.trpc_tpu_d2h(self._id, ctypes.byref(out))
        if n < 0:
            raise IOError(f"d2h failed: rc={n} ({error()})")
        try:
            return ctypes.string_at(out, n)
        finally:
            L.trpc_tpu_buf_release(out)

    def free(self) -> None:
        lib().trpc_tpu_buf_free(self._id)

    def __enter__(self) -> "DeviceBuffer":
        return self

    def __exit__(self, *exc) -> None:
        self.free()


def h2d(data: bytes, device: int = 0) -> DeviceBuffer:
    """DMA ``data`` into HBM; returns immediately (transfer is async —
    ``wait()`` parks on the completion butex)."""
    if not available():
        raise RuntimeError(f"device plane unavailable: {error()}")
    buf_id = lib().trpc_tpu_h2d(data, len(data), device)
    if buf_id == 0:
        raise IOError(f"h2d failed: {error()}")
    return DeviceBuffer(buf_id, len(data))


def stats() -> Dict[str, int]:
    """Plane counters (feeds /vars via the native metrics seam)."""
    out = (ctypes.c_uint64 * 11)()
    lib().trpc_tpu_plane_stats(out)
    keys = ("h2d_transfers", "d2h_transfers", "h2d_bytes", "d2h_bytes",
            "events_fired", "gather_copies", "zero_copy_sends",
            "live_buffers", "errors", "d2d_transfers", "d2d_bytes")
    return dict(zip(keys, out))


def plane_uid() -> int:
    """Nonzero token identifying THIS process's PJRT client; exchanged in
    the tpu:// handshake so connections learn whether both ends share one
    client (enabling device-to-device stream frames)."""
    return lib().trpc_tpu_plane_uid()


def d2d(buf: DeviceBuffer, device: int) -> DeviceBuffer:
    """Copy a device buffer to another device of THIS client over the
    device fabric (PJRT CopyToDevice — no host landing zone).  Returns a
    new buffer; the source stays valid and still needs its own free()."""
    nb = lib().trpc_tpu_d2d(buf.handle, device)
    if nb == 0:
        raise IOError(f"d2d failed: {error()}")
    return DeviceBuffer(nb, len(buf))


class PoolExhausted(Exception):
    """alloc() against a pool whose block budget is spent — the caller
    sheds or preempts; the pool NEVER queues (admission control happens
    above the device plane, before any DMA is issued)."""


class DeviceBufPool:
    """Budgeted fixed-size-block allocator over the plane's DeviceBuffers
    (≙ the reference's rdma/block_pool.cpp: a hard block budget with
    every allocation charged against it, re-designed: blocks are HBM
    DeviceBuffers and migration is a PJRT d2d hop instead of an ibverbs
    MR hand-off).

    Hard accounting: every `alloc()` charges one block until `free()`;
    `migrate()` moves a block between devices without changing the
    charge (the source is freed as soon as the copy is enqueued).
    `assert_balanced()` proves nothing leaked — the serving plane calls
    it after every drain and the suite calls it after every cancel leg.

    Thread-safe: the ledger mutates under one lock; DMA waits happen
    outside it."""

    def __init__(self, block_bytes: int, max_blocks: int):
        if block_bytes <= 0 or max_blocks <= 0:
            raise ValueError("block_bytes and max_blocks must be positive")
        self.block_bytes = block_bytes
        self.max_blocks = max_blocks
        self._lock = threading.Lock()
        self._live: Dict[int, DeviceBuffer] = {}   # handle -> buffer
        self._allocs = 0
        self._frees = 0
        self._migrations = 0
        self._exhausted = 0

    # -- ledger -------------------------------------------------------------

    @property
    def used_blocks(self) -> int:
        with self._lock:
            return len(self._live)

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return self.max_blocks - len(self._live)

    def pool_stats(self) -> Dict[str, int]:
        with self._lock:
            return {"block_bytes": self.block_bytes,
                    "max_blocks": self.max_blocks,
                    "used_blocks": len(self._live),
                    "allocs": self._allocs, "frees": self._frees,
                    "migrations": self._migrations,
                    "exhausted": self._exhausted}

    def assert_balanced(self) -> None:
        """Every charged block was freed; raises with the leak count
        otherwise (the serving accounting proof rides on this)."""
        with self._lock:
            leaked = len(self._live)
        if leaked:
            raise AssertionError(
                f"DeviceBufPool leaked {leaked} block(s): "
                f"allocs={self._allocs} frees={self._frees}")

    # -- data path ----------------------------------------------------------

    def alloc(self, data: bytes, device: int = 0) -> DeviceBuffer:
        """Charge one block and DMA `data` (at most block_bytes, padded
        to the block size so every block is pool-shaped) onto `device`.
        Raises PoolExhausted when the budget is spent — callers shed
        BEFORE this ever queues."""
        if len(data) > self.block_bytes:
            raise ValueError(
                f"block payload {len(data)} > block_bytes "
                f"{self.block_bytes}")
        with self._lock:
            if len(self._live) >= self.max_blocks:
                self._exhausted += 1
                raise PoolExhausted(
                    f"block budget spent ({self.max_blocks} blocks)")
            self._allocs += 1
        pad = self.block_bytes - len(data)
        try:
            buf = h2d(data + b"\x00" * pad, device)
        except Exception:
            with self._lock:
                self._allocs -= 1
            raise
        with self._lock:
            self._live[buf.handle] = buf
        return buf

    def migrate(self, buf: DeviceBuffer, device: int) -> DeviceBuffer:
        """Move a charged block to `device` over the d2d fabric; the
        charge transfers to the new buffer and the source is freed.  On
        d2d failure the source stays charged and valid."""
        with self._lock:
            if buf.handle not in self._live:
                raise KeyError("migrate() of a buffer not in this pool")
        nb = d2d(buf, device)
        with self._lock:
            del self._live[buf.handle]
            self._live[nb.handle] = nb
            self._migrations += 1
        buf.free()
        return nb

    def adopt(self, buf: DeviceBuffer) -> DeviceBuffer:
        """Charge an externally-created DeviceBuffer (e.g. a host-rail
        re-upload) against this pool's budget.  Raises PoolExhausted
        rather than over-committing; the buffer is NOT freed on refusal
        (it was never ours)."""
        with self._lock:
            if len(self._live) >= self.max_blocks:
                self._exhausted += 1
                raise PoolExhausted(
                    f"block budget spent ({self.max_blocks} blocks)")
            self._allocs += 1
            self._live[buf.handle] = buf
        return buf

    def release(self, buf: DeviceBuffer) -> None:
        """Un-charge a block WITHOUT freeing the underlying buffer —
        ownership leaves the pool (e.g. handed to stream.write_device,
        which consumes the buffer on success)."""
        with self._lock:
            if self._live.pop(buf.handle, None) is not None:
                self._frees += 1

    def free(self, buf: DeviceBuffer) -> None:
        """Return a block: idempotent, like DeviceBuffer.free itself."""
        with self._lock:
            if self._live.pop(buf.handle, None) is None:
                return
            self._frees += 1
        buf.free()

    def free_all(self) -> None:
        """Drop every outstanding block (teardown path)."""
        with self._lock:
            live = list(self._live.values())
            self._live.clear()
            self._frees += len(live)
        for b in live:
            b.free()
