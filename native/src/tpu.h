// tpu.h — the PJRT device data plane (capability of the reference's RDMA
// transport, rdma/rdma_endpoint.h + rdma/block_pool.cpp, re-designed for
// TPU): host memory moves to/from HBM through single PJRT DMA transfers
// whose completion events wake butexes (≙ CQ events → EventDispatcher →
// bthread), IOBuf blocks serve directly as DMA sources/targets (≙ posting
// SGEs straight from IOBuf blocks, rdma_endpoint.h:82), and a per-
// connection handshake decides DEVICE vs FALLBACK_TCP explicitly
// (≙ the RdmaEndpoint state machine, rdma_endpoint.h:95-110).
//
// The plane binds to a PJRT C API plugin (the installed libtpu package's
// libtpu.so on TPU VMs; the fake plugin in tests) via dlopen — no
// link-time PJRT dependency; absence degrades to tpu_plane_available() ==
// false and the endpoints take FALLBACK_TCP visibly.
#pragma once

#include <cstddef>
#include <cstdint>

#include "iobuf.h"

namespace trpc {

// --- plane lifecycle -------------------------------------------------------

// Load the PJRT plugin at `plugin_path` (required; the Python side
// resolves it) and create the PJRT client.  Idempotent; returns 0,
// -ENOENT (no plugin), -ENOSYS (built without the PJRT header), or -EIO
// (plugin/client error; see tpu_plane_error()).
int tpu_plane_init(const char* plugin_path);
bool tpu_plane_available();
// Human-readable reason when init failed (empty if ok / not attempted).
const char* tpu_plane_error();
int tpu_plane_device_count();
// Platform name reported by the plugin ("tpu", ...; empty if down).
const char* tpu_plane_platform();
// Random nonzero token minted at plane init, exchanged in the tag-14/15
// handshake: equal tokens on both ends of a connection mean both ends
// share THIS process's PJRT client, so buffers can move device-to-device
// (CopyToDevice over ICI) with no host landing zone.  0 when down.
uint64_t tpu_plane_uid();
// PJRT C API versions: out[0..1] the header this core was compiled
// against (major, minor), out[2..3] the loaded plugin's (-1 before a
// plugin was loaded).
void tpu_plane_api_version(int out[4]);

// --- device buffers --------------------------------------------------------
// Handles are (version<<32)|slot over a versioned pool — the same ABA-safe
// discipline as SocketId/fiber_t.  0 is the invalid handle.

typedef uint64_t TpuBufId;

// Asynchronously DMA `len` bytes at `data` into HBM on device
// `device_index`.  The memory must stay valid until the transfer releases
// it; `release` (may be null) is called exactly once at that point — the
// hook IOBuf device blocks ride (≙ append_user_data's deleter, iobuf.h:259).
// Completion (buffer ready in HBM) stores 1 to the handle's butex and
// wakes waiters: a fiber awaiting a device transfer costs no thread.
TpuBufId tpu_h2d(const void* data, size_t len, int device_index,
                 void (*release)(void*, void*), void* release_arg);

// Zero-copy H2D from an IOBuf: when the buf is a single contiguous block
// ref, the DMA source IS the block memory (pointer identity; the block
// stays ref'd until the transfer completes).  Multi-block bufs gather
// into one staging block first — counted in stats.gather_copies, never
// silent.
TpuBufId tpu_h2d_from_iobuf(const IOBuf& buf, int device_index);

// Wait until the buffer is resident in HBM (or errored / timed out).
// Fiber-friendly: parks on the completion butex.  0 / -ETIMEDOUT / -EIO.
int tpu_buf_wait(TpuBufId id, int64_t timeout_us);
int64_t tpu_buf_size(TpuBufId id);  // -1 if stale

// Residency-wait budget (µs, default 30s) for device-to-device copies and
// the HbmEcho handler's transfer waits, tunable via the
// TRPC_TPU_D2D_TIMEOUT_US env var — mirror of the d2h path's
// TRPC_TPU_D2H_TIMEOUT_US (a plugin that drops an event must not park a
// fiber forever; tests shrink it to exercise the timeout paths).
int64_t tpu_d2d_timeout_us();

// Asynchronously DMA the device buffer into one fresh host IOBuf block
// appended to `out` (the block is the DMA target — no extra host copy;
// the socket writev sends straight from it).  Blocks in the calling
// fiber until the transfer completes.  0 / -EIO / -EINVAL.
int tpu_d2h_into_iobuf(TpuBufId id, IOBuf* out);
// Same single-landing-zone DMA, handing the malloc'd memory to the
// caller (who free()s it) — the ctypes surface uses this to avoid a
// second host copy.
int tpu_d2h_raw(TpuBufId id, char** mem_out, size_t* len_out);
// Free a d2h landing zone from tpu_d2h_raw (or any host block the plane
// allocated): routes pool slots back to the ring's registered-buffer
// pool and everything else to free(3).
void tpu_host_free(void* p);

// Device-to-device copy WITHIN this process's PJRT client (≙ the RDMA
// template posting sends straight from registered blocks — no host
// round-trip; here the bytes ride ICI via PJRT CopyToDevice).  Returns a
// NEW buffer handle on `dst_device` (readiness async, same butex seam as
// h2d); the source buffer is untouched.  0 on failure.
TpuBufId tpu_d2d(TpuBufId src, int dst_device);

void tpu_buf_free(TpuBufId id);

// --- observability (feeds the native metrics seam) -------------------------

struct TpuPlaneStats {
  uint64_t h2d_transfers = 0;
  uint64_t d2h_transfers = 0;
  uint64_t h2d_bytes = 0;
  uint64_t d2h_bytes = 0;
  uint64_t events_fired = 0;    // PJRT completion callbacks delivered
  uint64_t gather_copies = 0;   // multi-block sends that needed a gather
  uint64_t zero_copy_sends = 0; // single-block sends (pointer identity)
  uint64_t live_buffers = 0;
  uint64_t errors = 0;
  uint64_t d2d_transfers = 0;   // CopyToDevice moves (no host landing)
  uint64_t d2d_bytes = 0;
};
TpuPlaneStats tpu_plane_stats();

}  // namespace trpc
