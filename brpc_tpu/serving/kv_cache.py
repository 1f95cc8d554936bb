"""KV-cache block plane — sequences' K/V as budgeted device-plane blocks
(≙ the reference's rdma/block_pool.cpp block budget, re-designed: blocks
are HBM DeviceBuffers, migration is a PJRT d2d hop, and fabric-lib's
point-to-point KV rail — PAPERS.md arXiv 2510.27656 — is the template
for keeping KV transfer distinct from the collective plane).

Lifecycle of one sequence:

    seq_alloc(id, kv_bytes)   prefill K/V chunked into blocks, DMA'd onto
                              the PREFILL device (h2d); charged against
                              the pool budget — PoolExhausted here means
                              the batcher must shed or preempt
    seq_migrate(id)           blocks hop to the DECODE device:
                                local rail — tpu_d2d per block, no host
                                  landing (both ends share one PJRT
                                  client; stats()["d2d_transfers"] is the
                                  proof counter)
                                host rail — d2h → optional bf16/int8
                                  codec on the landing bytes → h2d
                                  (non-shared-PJRT fallback per the
                                  PARITY ruling; the codec mirrors
                                  parallel/quantize.py wire formats)
    seq_grow(id)              one more block as decode crosses a block
                              boundary (the preemption trigger)
    seq_fetch(id)             the migrated bytes, host-side, for
                              models/decode.install()
    seq_free(id)              EVERY block back to the pool — finish,
                              eviction, and cancel all end here;
                              idempotent, and assert_balanced() proves
                              nothing leaked
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from brpc_tpu import tpu_plane
from brpc_tpu.utils import flags

flags.define_int32(
    "serving_block_bytes",
    int(os.environ.get("TRPC_SERVING_BLOCK_BYTES", "4096")),
    "KV-cache block size in bytes (serving/kv_cache.py)",
    reloadable=False)
flags.define_int32(
    "serving_kv_blocks",
    int(os.environ.get("TRPC_SERVING_KV_BLOCKS", "64")),
    "KV-cache pool budget in blocks; admission sheds beyond it",
    reloadable=False)
flags.define_string(
    "serving_kv_rail",
    os.environ.get("TRPC_SERVING_KV_RAIL", "local"),
    "prefill->decode KV migration rail: local (d2d) | host (d2h->h2d); "
    "either needs the device plane up — a down plane raises, it never "
    "picks another rail",
    validator=lambda v: v in ("local", "host"))
flags.define_string(
    "serving_kv_codec",
    os.environ.get("TRPC_SERVING_KV_CODEC", "none"),
    "codec applied to host-rail KV migration bytes: none|bf16|int8 "
    "(the local d2d rail is device-native and rides uncoded)",
    validator=lambda v: v in ("none", "bf16", "int8"))


@dataclass
class _SeqBlocks:
    nbytes: int                                  # real payload bytes
    blocks: List[tpu_plane.DeviceBuffer] = field(default_factory=list)
    device: int = 0                              # where the blocks live
    migrated: bool = False


class KvBlockPlane:
    """Per-sequence block tables over one DeviceBufPool.  Thread-safe:
    the decode loop migrates/grows while handler threads cancel."""

    def __init__(self, block_bytes: Optional[int] = None,
                 n_blocks: Optional[int] = None,
                 prefill_device: int = 0,
                 decode_device: Optional[int] = None,
                 rail: Optional[str] = None,
                 codec: Optional[str] = None):
        self.block_bytes = block_bytes or flags.get_flag(
            "serving_block_bytes")
        self.n_blocks = n_blocks or flags.get_flag("serving_kv_blocks")
        self.prefill_device = prefill_device
        if decode_device is None:
            # disaggregated when the plane has a second device; with the
            # plane down every block op raises (h2d), never a host path
            decode_device = 1 if tpu_plane.device_count() > 1 else 0
        self.decode_device = decode_device
        self.rail = rail or flags.get_flag("serving_kv_rail")
        self.codec = codec or flags.get_flag("serving_kv_codec")
        self.pool = tpu_plane.DeviceBufPool(self.block_bytes, self.n_blocks)
        self._lock = threading.Lock()
        self._seqs: Dict[int, _SeqBlocks] = {}
        self._migrations_local = 0
        self._migrations_host = 0
        self._codec_bytes = 0
        self._grown = 0
        self._freed_seqs = 0

    # -- sizing -------------------------------------------------------------

    def blocks_needed(self, nbytes: int) -> int:
        return max(1, -(-nbytes // self.block_bytes))

    @property
    def free_blocks(self) -> int:
        return self.pool.free_blocks

    @property
    def used_blocks(self) -> int:
        return self.pool.used_blocks

    def live_seqs(self) -> int:
        with self._lock:
            return len(self._seqs)

    # -- sequence lifecycle -------------------------------------------------

    def seq_alloc(self, seq_id: int, kv_bytes: bytes) -> int:
        """Chunk a sequence's prefill K/V into blocks on the prefill
        device.  All-or-nothing: a mid-sequence PoolExhausted rolls back
        the blocks already charged before re-raising."""
        with self._lock:
            if seq_id in self._seqs:
                raise KeyError(f"seq {seq_id} already has a block table")
        table = _SeqBlocks(nbytes=len(kv_bytes),
                           device=self.prefill_device)
        try:
            for off in range(0, max(len(kv_bytes), 1), self.block_bytes):
                table.blocks.append(self.pool.alloc(
                    kv_bytes[off:off + self.block_bytes],
                    self.prefill_device))
        except tpu_plane.PoolExhausted:
            for b in table.blocks:
                self.pool.free(b)
            raise
        with self._lock:
            self._seqs[seq_id] = table
        return len(table.blocks)

    def seq_migrate(self, seq_id: int) -> str:
        """Move the sequence's blocks prefill→decode device; returns the
        rail taken ("local"/"host"/"none" when devices coincide)."""
        with self._lock:
            table = self._seqs[seq_id]
        if table.migrated or self.decode_device == table.device:
            table.migrated = True
            return "none"
        use_local = self.rail == "local"
        # in-place per-block replacement: a mid-migration failure leaves
        # every charged block reachable from the table, so seq_free still
        # returns all of them
        if use_local:
            for i, b in enumerate(table.blocks):
                table.blocks[i] = self.pool.migrate(b, self.decode_device)
            with self._lock:
                self._migrations_local += len(table.blocks)
        else:
            for i, b in enumerate(table.blocks):
                b.wait()
                data = self._transcode(b.to_host())
                # free-then-alloc so a full pool can still land the hop
                # (alloc-first would deadlock at the budget edge); on an
                # alloc failure the engine sheds the sequence and
                # seq_free skips the already-freed source (idempotent)
                self.pool.free(b)
                table.blocks[i] = self.pool.alloc(data, self.decode_device)
            with self._lock:
                self._migrations_host += len(table.blocks)
        table.device = self.decode_device
        table.migrated = True
        return "local" if use_local else "host"

    def seq_grow(self, seq_id: int, tail: bytes = b"") -> int:
        """Charge one more block (decode crossed a block boundary).
        PoolExhausted propagates — the batcher preempts on it."""
        blk = self.pool.alloc(tail[:self.block_bytes], self.decode_device)
        with self._lock:
            table = self._seqs[seq_id]
            table.blocks.append(blk)
            self._grown += 1
        return len(table.blocks)

    def seq_blocks(self, seq_id: int) -> int:
        """Blocks currently charged to the sequence (0 if unknown)."""
        with self._lock:
            table = self._seqs.get(seq_id)
            return len(table.blocks) if table else 0

    def seq_fetch(self, seq_id: int) -> bytes:
        """The migrated K/V bytes, host-side (feeds decode.install)."""
        with self._lock:
            table = self._seqs[seq_id]
        out = []
        for b in table.blocks:
            b.wait()
            out.append(b.to_host())
        return b"".join(out)[:table.nbytes]

    def seq_free(self, seq_id: int) -> int:
        """Return every block of the sequence; idempotent (finish,
        evict, and cancel can race — first caller wins)."""
        with self._lock:
            table = self._seqs.pop(seq_id, None)
            if table is None:
                return 0
            self._freed_seqs += 1
        for b in table.blocks:
            self.pool.free(b)
        return len(table.blocks)

    def free_all(self) -> None:
        with self._lock:
            ids = list(self._seqs)
        for sid in ids:
            self.seq_free(sid)

    # -- accounting ---------------------------------------------------------

    def assert_balanced(self) -> None:
        """No live sequences and no charged blocks — the accounting
        proof after a drain."""
        with self._lock:
            live = len(self._seqs)
        if live:
            raise AssertionError(f"{live} sequence table(s) still live")
        self.pool.assert_balanced()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            d = {"kv_live_seqs": len(self._seqs),
                 "kv_migrations_local": self._migrations_local,
                 "kv_migrations_host": self._migrations_host,
                 "kv_codec_bytes": self._codec_bytes,
                 "kv_grown_blocks": self._grown,
                 "kv_freed_seqs": self._freed_seqs}
        d.update({f"kv_pool_{k}": v
                  for k, v in self.pool.pool_stats().items()})
        return d

    # -- host-rail codec ----------------------------------------------------

    def _transcode(self, data: bytes) -> bytes:
        """bf16/int8 quantize→dequantize pass on host-rail landing bytes
        (same per-block formats as the wire codec; lossy but bounded —
        parallel/quantize.py)."""
        if self.codec in ("", "none"):
            return data
        import numpy as np
        from brpc_tpu.parallel import quantize
        n = len(data) // 4 * 4
        if n == 0:
            return data
        arr = np.frombuffer(data[:n], np.float32)
        out = np.asarray(quantize.fake_quant(arr, self.codec),
                         np.float32).tobytes()
        with self._lock:
            self._codec_bytes += n
        return out + data[n:]
