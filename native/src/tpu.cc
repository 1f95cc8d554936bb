// tpu.cc — PJRT device data plane implementation.  See tpu.h for the
// design; the reference analogue is rdma/ (registered memory pool, CQ
// completions into the dispatcher, TCP-assisted bring-up).
#include "tpu.h"

#include <dlfcn.h>
#include <errno.h>
#include <stdlib.h>
#include <string.h>
#include <unistd.h>

#include <atomic>
#include <mutex>
#include <string>
#include <vector>

#include "fiber.h"
#include "heap_profiler.h"
#include "object_pool.h"
#include "timer_thread.h"
#include "uring.h"

#if defined(TRPC_HAVE_PJRT_HEADER)
#include "xla/pjrt/c/pjrt_c_api.h"
#endif

namespace trpc {

// D2H landing zones draw from the ring engine's registered-buffer pool
// when the io_uring transport is up (≙ fabric-lib pre-registered
// transfer buffers): the zone the DMA writes becomes an IOBuf user
// block and leaves the host as a fixed-buffer SEND_ZC — the attachment
// rides registered memory end to end with zero host copies.  Pool
// exhausted / ring down: plain malloc, same lifecycle.
namespace {
char* zc_host_alloc(size_t len) {
  void* p = uring_zc_alloc(len);
  return p != nullptr ? (char*)p : (char*)hp_malloc(len);
}
}  // namespace

void tpu_host_free(void* p) {
  if (p != nullptr && !uring_zc_free(p)) {
    hp_free(p);
  }
}

namespace {
// Env-tunable wait budget shared by the bounded device waits (µs;
// unparseable/negative values keep the safe default).  Compiled
// unconditionally: the HbmEcho handler (rpc.cc) budgets its waits with
// tpu_d2d_timeout_us even on PJRT-less builds.
int64_t env_wait_budget_us(const char* name) {
  int64_t budget_us = 30 * 1000 * 1000;
  const char* bv = getenv(name);
  if (bv != nullptr && bv[0] != '\0') {
    int64_t v = strtoll(bv, nullptr, 10);
    if (v > 0) {
      budget_us = v;
    }
  }
  return budget_us;
}
}  // namespace

int64_t tpu_d2d_timeout_us() {
  // parsed once per process: this sits on the per-request HbmEcho path,
  // and getenv is a linear environ scan.  (The d2h budget below stays a
  // per-call getenv on purpose — test_tpu_plane.py flips it mid-process
  // between transfer attempts.)
  static const int64_t cached = env_wait_budget_us("TRPC_TPU_D2D_TIMEOUT_US");
  return cached;
}

#if defined(TRPC_HAVE_PJRT_HEADER)

namespace {

struct Plane {
  void* dso = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  std::vector<PJRT_Device*> devices;
  std::string platform;
  std::string error;
  std::atomic<bool> up{false};
  std::mutex init_mu;

  uint64_t uid = 0;  // handshake token (tpu_plane_uid); set at init
  // the loaded plugin's PJRT C API version (-1: none loaded yet)
  std::atomic<int> plugin_api_major{-1}, plugin_api_minor{-1};

  // stats (relaxed: monotonic counters)
  std::atomic<uint64_t> h2d_transfers{0}, d2h_transfers{0};
  std::atomic<uint64_t> h2d_bytes{0}, d2h_bytes{0};
  std::atomic<uint64_t> events_fired{0}, gather_copies{0};
  std::atomic<uint64_t> zero_copy_sends{0}, live_buffers{0}, errors{0};
  std::atomic<uint64_t> d2d_transfers{0}, d2d_bytes{0};
};

Plane& plane() {
  static Plane* p = new Plane();  // leaked on purpose
  return *p;
}

// Post-init errors are written from arbitrary fiber/plugin threads and
// read from Python: guard the string, and hand readers a per-thread copy
// so the returned c_str can't be yanked by a concurrent writer.
std::mutex& err_mu() {
  static std::mutex* m = new std::mutex();
  return *m;
}

void set_plane_error(std::string msg) {
  std::lock_guard<std::mutex> lk(err_mu());
  plane().error = std::move(msg);
}

std::string pjrt_error_string(const PJRT_Api* api, PJRT_Error* err) {
  if (err == nullptr) {
    return "";
  }
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  std::string s(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return s;
}

// A device buffer slot.  One outstanding H2D rides `ready` (armed 0 ->
// completion stores 1); D2H ops use ephemeral contexts below.
struct DeviceBuf {
  PJRT_Buffer* buf = nullptr;
  size_t len = 0;
  uint32_t slot = 0;
  std::atomic<uint32_t> version{1};
  Butex* ready = nullptr;        // 1 = resident in HBM (or errored)
  std::atomic<int32_t> error{0};
  // Slot pin: 1 (owned by tpu_buf_free) + 1 per registered PJRT callback.
  // The slot returns to the pool only when this drains to 0 — a late
  // completion callback must never touch a recycled slot's next occupant.
  std::atomic<int32_t> pins{0};
  // H2D source pinning: released by the done_with_host_buffer callback
  void (*release)(void*, void*) = nullptr;
  void* release_arg = nullptr;
  void* release_data = nullptr;

  TpuBufId id() const {
    return ((uint64_t)version.load(std::memory_order_relaxed) << 32) | slot;
  }
};

void destroy_pjrt_buf(DeviceBuf* b);

void unpin_buf(DeviceBuf* b) {
  if (b->pins.fetch_sub(1, std::memory_order_acq_rel) == 1) {
    // last pin out destroys the PJRT buffer (deferred from tpu_buf_free
    // when a pinned waiter/callback was still live — the buffer handle
    // must outlive every reader) and only then recycles the slot
    destroy_pjrt_buf(b);
    ResourcePool<DeviceBuf>::Return(b->slot);
  }
}

DeviceBuf* addr_buf(TpuBufId id) {
  DeviceBuf* b = ResourcePool<DeviceBuf>::Address((uint32_t)id);
  if (b == nullptr ||
      b->version.load(std::memory_order_acquire) != (uint32_t)(id >> 32)) {
    return nullptr;
  }
  return b;
}

// Take a reader pin on the slot (≙ Socket::Address giving readers a ref,
// socket.h:430): the slot cannot recycle — and the PJRT buffer cannot be
// destroyed — while the pin is held.  Fails when the id's occupant is
// gone or already draining (pins only climb from a live, nonzero count;
// the version re-check under the pin rejects a recycled slot).
DeviceBuf* pin_buf(TpuBufId id) {
  DeviceBuf* b = ResourcePool<DeviceBuf>::Address((uint32_t)id);
  if (b == nullptr) {
    return nullptr;
  }
  int32_t cur = b->pins.load(std::memory_order_acquire);
  do {
    if (cur <= 0) {
      return nullptr;  // draining or recycled: nothing to pin
    }
  } while (!b->pins.compare_exchange_weak(cur, cur + 1,
                                          std::memory_order_acq_rel));
  if (b->version.load(std::memory_order_acquire) != (uint32_t)(id >> 32)) {
    unpin_buf(b);  // pinned the slot's NEXT occupant: back out
    return nullptr;
  }
  return b;
}

// PJRT completion callbacks run on plugin-owned threads; they only touch
// atomics + butex wakes (the butex↔device-event seam: store 1, wake).
void on_ready_cb(PJRT_Error* err, void* user) {
  DeviceBuf* b = (DeviceBuf*)user;
  Plane& p = plane();
  p.events_fired.fetch_add(1, std::memory_order_relaxed);
  if (err != nullptr) {
    p.errors.fetch_add(1, std::memory_order_relaxed);
    b->error.store(EIO, std::memory_order_release);
    pjrt_error_string(p.api, err);  // consume + free
  }
  butex_value(b->ready).store(1, std::memory_order_release);
  butex_wake_all(b->ready);
  unpin_buf(b);
}

// done_with_host_buffer: the DMA engine no longer reads the source; drop
// the pin (an IOBuf block ref, a malloc'd gather buffer, ...).
void on_source_released_cb(PJRT_Error* err, void* user) {
  DeviceBuf* b = (DeviceBuf*)user;
  Plane& p = plane();
  p.events_fired.fetch_add(1, std::memory_order_relaxed);
  if (err != nullptr) {
    pjrt_error_string(p.api, err);
  }
  if (b->release != nullptr) {
    auto rel = b->release;
    b->release = nullptr;
    rel(b->release_data, b->release_arg);
  }
  unpin_buf(b);
}

}  // namespace

int tpu_plane_init(const char* plugin_path) {
  Plane& p = plane();
  if (p.up.load(std::memory_order_acquire)) {
    return 0;
  }
  std::lock_guard<std::mutex> lk(p.init_mu);
  if (p.up.load(std::memory_order_acquire)) {
    return 0;
  }
  // The caller names the plugin: brpc_tpu/tpu_plane.py resolves it (its
  // argument, $TRPC_PJRT_PLUGIN, else the installed libtpu package's
  // library).  No search list: a plugin that is not where it was named is
  // an error, never a different plugin.
  if (plugin_path == nullptr || plugin_path[0] == '\0') {
    set_plane_error("no PJRT plugin path given");
    return -ENOENT;
  }
  const std::string path = plugin_path;
  void* dso = dlopen(path.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (dso == nullptr) {
    const char* why = dlerror();
    set_plane_error("dlopen " + path + ": " + (why != nullptr ? why : "?"));
    return -ENOENT;
  }
  typedef const PJRT_Api* (*GetApiFn)();
  GetApiFn get_api = (GetApiFn)dlsym(dso, "GetPjrtApi");
  if (get_api == nullptr) {
    set_plane_error("plugin has no GetPjrtApi");
    dlclose(dso);
    return -EIO;
  }
  const PJRT_Api* api = get_api();
  if (api == nullptr) {
    set_plane_error("GetPjrtApi returned null");
    dlclose(dso);
    return -EIO;
  }
  p.plugin_api_major.store(api->pjrt_api_version.major_version,
                           std::memory_order_relaxed);
  p.plugin_api_minor.store(api->pjrt_api_version.minor_version,
                           std::memory_order_relaxed);
  // plugin bring-up (≙ PJRT_Plugin_Initialize contract: call before use;
  // a second call after JAX initialized the same libtpu is harmless)
  if (api->PJRT_Plugin_Initialize != nullptr) {
    PJRT_Plugin_Initialize_Args iargs;
    memset(&iargs, 0, sizeof(iargs));
    iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    PJRT_Error* err = api->PJRT_Plugin_Initialize(&iargs);
    if (err != nullptr) {
      set_plane_error("plugin init: " + pjrt_error_string(api, err));
      dlclose(dso);
      return -EIO;
    }
  }
  // A second client next to JAX's in one process: libtpu 0.0.34 grants
  // it on a v5e, in either order of creation (PR 21 chip probe).
  PJRT_Client_Create_Args cargs;
  memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  PJRT_Error* err = api->PJRT_Client_Create(&cargs);
  if (err != nullptr) {
    set_plane_error("client create: " + pjrt_error_string(api, err));
    dlclose(dso);
    return -EIO;
  }
  PJRT_Client_PlatformName_Args pargs;
  memset(&pargs, 0, sizeof(pargs));
  pargs.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  pargs.client = cargs.client;
  if (api->PJRT_Client_PlatformName(&pargs) == nullptr) {
    p.platform.assign(pargs.platform_name, pargs.platform_name_size);
  }
  PJRT_Client_AddressableDevices_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = cargs.client;
  err = api->PJRT_Client_AddressableDevices(&dargs);
  if (err != nullptr) {
    set_plane_error("devices: " + pjrt_error_string(api, err));
    dlclose(dso);
    return -EIO;
  }
  p.devices.assign(dargs.addressable_devices,
                   dargs.addressable_devices + dargs.num_addressable_devices);
  // mint the handshake token: unique per plane instance, never zero
  p.uid = ((uint64_t)getpid() << 32) ^ (uint64_t)monotonic_ns();
  if (p.uid == 0) {
    p.uid = 1;
  }
  p.dso = dso;
  p.api = api;
  p.client = cargs.client;
  p.error.clear();
  p.up.store(true, std::memory_order_release);
  return 0;
}

bool tpu_plane_available() {
  return plane().up.load(std::memory_order_acquire);
}

const char* tpu_plane_error() {
  static thread_local std::string* copy = new std::string();
  std::lock_guard<std::mutex> lk(err_mu());
  *copy = plane().error;
  return copy->c_str();
}

int tpu_plane_device_count() {
  Plane& p = plane();
  return p.up.load(std::memory_order_acquire) ? (int)p.devices.size() : 0;
}

const char* tpu_plane_platform() { return plane().platform.c_str(); }

uint64_t tpu_plane_uid() {
  Plane& p = plane();
  return p.up.load(std::memory_order_acquire) ? p.uid : 0;
}

void tpu_plane_api_version(int out[4]) {
  // lock-free: readable while an init is still stuck in client create
  Plane& p = plane();
  out[0] = PJRT_API_MAJOR;
  out[1] = PJRT_API_MINOR;
  out[2] = p.plugin_api_major.load(std::memory_order_relaxed);
  out[3] = p.plugin_api_minor.load(std::memory_order_relaxed);
}

TpuBufId tpu_h2d(const void* data, size_t len, int device_index,
                 void (*release)(void*, void*), void* release_arg) {
  Plane& p = plane();
  if (!p.up.load(std::memory_order_acquire) ||
      device_index >= (int)p.devices.size() || len == 0) {
    if (release != nullptr) {
      release((void*)data, release_arg);
    }
    return 0;
  }
  DeviceBuf* b = nullptr;
  uint32_t slot = ResourcePool<DeviceBuf>::Get(&b);
  b->slot = slot;
  if (b->ready == nullptr) {
    b->ready = butex_create();
  }
  butex_value(b->ready).store(0, std::memory_order_release);
  b->error.store(0, std::memory_order_relaxed);
  b->pins.store(1, std::memory_order_relaxed);  // tpu_buf_free's pin
  b->len = len;
  b->release = release;
  b->release_arg = release_arg;
  b->release_data = (void*)data;

  PJRT_Client_BufferFromHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = p.client;
  args.data = data;
  args.type = PJRT_Buffer_Type_U8;
  int64_t dims[1] = {(int64_t)len};
  args.dims = dims;
  args.num_dims = 1;
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = p.devices[device_index];
  PJRT_Error* err = p.api->PJRT_Client_BufferFromHostBuffer(&args);
  if (err != nullptr) {
    p.errors.fetch_add(1, std::memory_order_relaxed);
    set_plane_error("h2d: " + pjrt_error_string(p.api, err));
    if (release != nullptr) {
      release((void*)data, release_arg);
    }
    b->version.fetch_add(1, std::memory_order_release);
    unpin_buf(b);  // no callbacks registered: recycles immediately
    return 0;
  }
  b->buf = args.buffer;
  TpuBufId id = b->id();
  p.h2d_transfers.fetch_add(1, std::memory_order_relaxed);
  p.h2d_bytes.fetch_add(len, std::memory_order_relaxed);
  p.live_buffers.fetch_add(1, std::memory_order_relaxed);
  // source pin release: the DMA engine is done reading host memory.
  // Each registered callback takes a slot pin BEFORE registration (the
  // callback may fire on a plugin thread immediately).
  b->pins.fetch_add(1, std::memory_order_acq_rel);
  PJRT_Event_OnReady_Args oargs;
  memset(&oargs, 0, sizeof(oargs));
  oargs.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
  oargs.event = args.done_with_host_buffer;
  oargs.callback = on_source_released_cb;
  oargs.user_arg = b;
  p.api->PJRT_Event_OnReady(&oargs);
  // NOTE: the event handle is intentionally not destroyed here — some
  // plugins drop the pending OnReady callback with the handle.
  // residency: buffer usable in HBM -> store 1 + butex_wake (the seam)
  PJRT_Buffer_ReadyEvent_Args rargs;
  memset(&rargs, 0, sizeof(rargs));
  rargs.struct_size = PJRT_Buffer_ReadyEvent_Args_STRUCT_SIZE;
  rargs.buffer = b->buf;
  err = p.api->PJRT_Buffer_ReadyEvent(&rargs);
  if (err != nullptr) {
    pjrt_error_string(p.api, err);
    // no ready event: consider it ready (Await on use will still work)
    butex_value(b->ready).store(1, std::memory_order_release);
    butex_wake_all(b->ready);
  } else {
    b->pins.fetch_add(1, std::memory_order_acq_rel);
    PJRT_Event_OnReady_Args wargs;
    memset(&wargs, 0, sizeof(wargs));
    wargs.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
    wargs.event = rargs.event;
    wargs.callback = on_ready_cb;
    wargs.user_arg = b;
    p.api->PJRT_Event_OnReady(&wargs);
  }
  return id;
}

namespace {
void release_block_ref(void* data, void* arg) {
  (void)data;
  ((IOBlock*)arg)->Unref();
}
void release_free(void* data, void* arg) {
  (void)arg;
  hp_free(data);
}
}  // namespace

TpuBufId tpu_h2d_from_iobuf(const IOBuf& buf, int device_index) {
  Plane& p = plane();
  if (buf.empty()) {
    return 0;
  }
  if (buf.block_count() == 1) {
    // pointer identity: the DMA reads the IOBuf block itself; the block
    // ref taken here is dropped by the done_with_host_buffer callback
    const BlockRef& r = buf.ref_at(0);
    r.block->Ref();
    TpuBufId id = tpu_h2d(r.block->data + r.offset, r.length, device_index,
                          release_block_ref, r.block);
    if (id != 0) {
      p.zero_copy_sends.fetch_add(1, std::memory_order_relaxed);
    }
    return id;
  }
  // multi-block: one gather into a fresh staging buffer (explicit in
  // stats — never a silent extra copy)
  char* staging = (char*)hp_malloc(buf.size());
  buf.copy_to(staging, buf.size());
  p.gather_copies.fetch_add(1, std::memory_order_relaxed);
  return tpu_h2d(staging, buf.size(), device_index, release_free, nullptr);
}

namespace {
// Residency wait on an ALREADY-PINNED buf (callers own the pin).
int wait_ready_pinned(DeviceBuf* b, int64_t timeout_us) {
  while (butex_value(b->ready).load(std::memory_order_acquire) == 0) {
    if (butex_wait(b->ready, 0, timeout_us) != 0 && errno == ETIMEDOUT) {
      if (butex_value(b->ready).load(std::memory_order_acquire) != 0) {
        break;
      }
      return -ETIMEDOUT;
    }
  }
  return b->error.load(std::memory_order_acquire) == 0 ? 0 : -EIO;
}
}  // namespace

TpuBufId tpu_d2d(TpuBufId src_id, int dst_device) {
  Plane& p = plane();
  if (!p.up.load(std::memory_order_acquire) ||
      dst_device < 0 || dst_device >= (int)p.devices.size()) {
    return 0;
  }
  DeviceBuf* src = pin_buf(src_id);
  if (src == nullptr) {
    return 0;
  }
  // the source must be resident before CopyToDevice (PJRT would queue it
  // anyway; waiting here keeps the error attribution crisp)
  int rc = wait_ready_pinned(src, tpu_d2d_timeout_us());
  if (rc != 0 || src->buf == nullptr) {
    set_plane_error(rc == -ETIMEDOUT
                        ? "d2d: source never became resident"
                        : "d2d: source transfer failed or buffer gone");
    unpin_buf(src);
    return 0;
  }
  PJRT_Buffer_CopyToDevice_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_CopyToDevice_Args_STRUCT_SIZE;
  args.buffer = src->buf;
  args.dst_device = p.devices[dst_device];
  PJRT_Error* err = p.api->PJRT_Buffer_CopyToDevice(&args);
  size_t len = src->len;
  unpin_buf(src);
  if (err != nullptr) {
    p.errors.fetch_add(1, std::memory_order_relaxed);
    set_plane_error("d2d: " + pjrt_error_string(p.api, err));
    return 0;
  }
  // arm a fresh slot for the destination buffer — same butex seam as h2d
  DeviceBuf* b = nullptr;
  uint32_t slot = ResourcePool<DeviceBuf>::Get(&b);
  b->slot = slot;
  if (b->ready == nullptr) {
    b->ready = butex_create();
  }
  butex_value(b->ready).store(0, std::memory_order_release);
  b->error.store(0, std::memory_order_relaxed);
  b->pins.store(1, std::memory_order_relaxed);  // tpu_buf_free's pin
  b->len = len;
  b->release = nullptr;
  b->release_arg = nullptr;
  b->release_data = nullptr;
  b->buf = args.dst_buffer;
  TpuBufId id = b->id();
  p.d2d_transfers.fetch_add(1, std::memory_order_relaxed);
  p.d2d_bytes.fetch_add(len, std::memory_order_relaxed);
  p.live_buffers.fetch_add(1, std::memory_order_relaxed);
  PJRT_Buffer_ReadyEvent_Args rargs;
  memset(&rargs, 0, sizeof(rargs));
  rargs.struct_size = PJRT_Buffer_ReadyEvent_Args_STRUCT_SIZE;
  rargs.buffer = b->buf;
  PJRT_Error* rerr = p.api->PJRT_Buffer_ReadyEvent(&rargs);
  if (rerr != nullptr) {
    pjrt_error_string(p.api, rerr);
    butex_value(b->ready).store(1, std::memory_order_release);
    butex_wake_all(b->ready);
  } else {
    b->pins.fetch_add(1, std::memory_order_acq_rel);
    PJRT_Event_OnReady_Args wargs;
    memset(&wargs, 0, sizeof(wargs));
    wargs.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
    wargs.event = rargs.event;
    wargs.callback = on_ready_cb;
    wargs.user_arg = b;
    p.api->PJRT_Event_OnReady(&wargs);
  }
  return id;
}

int tpu_buf_wait(TpuBufId id, int64_t timeout_us) {
  // the pin keeps the slot (and its butex arming) ours for the whole
  // wait: without it a racing tpu_buf_free could recycle the slot and a
  // parked waiter would be reading the NEXT occupant's ready/error
  DeviceBuf* b = pin_buf(id);
  if (b == nullptr) {
    return -EINVAL;
  }
  int rc = wait_ready_pinned(b, timeout_us);
  unpin_buf(b);
  return rc;
}

int64_t tpu_buf_size(TpuBufId id) {
  DeviceBuf* b = pin_buf(id);
  if (b == nullptr) {
    return -1;
  }
  int64_t n = (int64_t)b->len;
  unpin_buf(b);
  return n;
}

// DMA the device buffer into fresh malloc'd host memory.  On success the
// caller owns *mem (free()); *len_out is the byte count.
static int tpu_d2h_alloc(TpuBufId id, char** mem_out, size_t* len_out) {
  Plane& p = plane();
  // pinned for the whole op: the PJRT buffer handle must stay alive
  // across the ToHostBuffer call and its completion (a racing free only
  // schedules the destroy; it runs when the last pin drains)
  DeviceBuf* b = pin_buf(id);
  if (b == nullptr) {
    return -EINVAL;
  }
  int rc = wait_ready_pinned(b, 30 * 1000 * 1000);
  if (rc != 0 || b->buf == nullptr) {
    unpin_buf(b);
    return rc != 0 ? rc : -EINVAL;
  }
  size_t len = b->len;
  // DMA straight into fresh host memory: exactly one host-side landing
  // zone, shared by the IOBuf path and the C-API path.  The landing zone
  // is OWNED BY THE CONTEXT until the caller claims it on success — a
  // timed-out caller walks away and the late DMA still writes valid
  // memory, freed by whoever drops the last context reference.
  struct D2hCtx {
    Butex* done;
    std::atomic<int32_t> err{0};
    std::atomic<int32_t> refs{2};  // caller + callback
    char* mem = nullptr;
    // single teardown shared by caller and callback: the last ref out
    // frees the landing zone unless the caller claimed it
    static void Drop(D2hCtx* c) {
      if (c->refs.fetch_sub(1, std::memory_order_acq_rel) == 1) {
        tpu_host_free(c->mem);
        butex_destroy(c->done);
        delete c;
      }
    }
  };
  D2hCtx* ctx = new D2hCtx{butex_create()};
  ctx->mem = zc_host_alloc(len);
  PJRT_Buffer_ToHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = b->buf;
  args.dst = ctx->mem;
  args.dst_size = len;
  PJRT_Error* err = p.api->PJRT_Buffer_ToHostBuffer(&args);
  if (err != nullptr) {
    p.errors.fetch_add(1, std::memory_order_relaxed);
    set_plane_error("d2h: " + pjrt_error_string(p.api, err));
    ctx->refs.store(1, std::memory_order_relaxed);  // no callback coming
    D2hCtx::Drop(ctx);
    unpin_buf(b);
    return -EIO;
  }
  PJRT_Event_OnReady_Args oargs;
  memset(&oargs, 0, sizeof(oargs));
  oargs.struct_size = PJRT_Event_OnReady_Args_STRUCT_SIZE;
  oargs.event = args.event;
  oargs.callback = [](PJRT_Error* e, void* u) {
    D2hCtx* c = (D2hCtx*)u;
    Plane& pl = plane();
    pl.events_fired.fetch_add(1, std::memory_order_relaxed);
    if (e != nullptr) {
      pl.errors.fetch_add(1, std::memory_order_relaxed);
      c->err.store(EIO, std::memory_order_release);
      pjrt_error_string(pl.api, e);
    }
    butex_value(c->done).store(1, std::memory_order_release);
    butex_wake_all(c->done);
    D2hCtx::Drop(c);
  };
  oargs.user_arg = ctx;
  p.api->PJRT_Event_OnReady(&oargs);
  // BOUNDED wait for the copy event: a plugin that drops the event must
  // not park a usercode-pool thread forever (that silently shrinks the
  // handler pool).  Budget tunable for tests via TRPC_TPU_D2H_TIMEOUT_US.
  int64_t budget_us = env_wait_budget_us("TRPC_TPU_D2H_TIMEOUT_US");
  int64_t ev_deadline = monotonic_us() + budget_us;
  bool timed_out = false;
  while (butex_value(ctx->done).load(std::memory_order_acquire) == 0) {
    int64_t left = ev_deadline - monotonic_us();
    if (left <= 0) {
      timed_out = true;
      break;
    }
    butex_wait(ctx->done, 0, left < 100 * 1000 ? left : 100 * 1000);
  }
  if (timed_out) {
    p.errors.fetch_add(1, std::memory_order_relaxed);
    set_plane_error("d2h: copy event never completed (plugin dropped it)");
    D2hCtx::Drop(ctx);  // ctx keeps the landing zone for the late DMA
    unpin_buf(b);
    return -ETIMEDOUT;
  }
  int32_t cerr = ctx->err.load(std::memory_order_acquire);
  char* mem = nullptr;
  if (cerr == 0) {
    mem = ctx->mem;  // claim: the last ctx ref must not free it
    ctx->mem = nullptr;
  }
  D2hCtx::Drop(ctx);
  unpin_buf(b);
  if (cerr != 0) {
    return -EIO;
  }
  p.d2h_transfers.fetch_add(1, std::memory_order_relaxed);
  p.d2h_bytes.fetch_add(len, std::memory_order_relaxed);
  *mem_out = mem;
  *len_out = len;
  return 0;
}

int tpu_d2h_into_iobuf(TpuBufId id, IOBuf* out) {
  char* mem = nullptr;
  size_t len = 0;
  int rc = tpu_d2h_alloc(id, &mem, &len);
  if (rc != 0) {
    return rc;
  }
  // the landing zone becomes an IOBuf user block: the socket egress
  // (fixed-buffer SEND_ZC on the ring, writev otherwise) sends from it
  // with no further copies
  out->append_user_data(
      mem, len, [](void* d, void*) { tpu_host_free(d); }, nullptr);
  return 0;
}

int tpu_d2h_raw(TpuBufId id, char** mem_out, size_t* len_out) {
  return tpu_d2h_alloc(id, mem_out, len_out);
}

namespace {
// Runs at last-pin drain (often the freer's own unpin): with no readers
// or callbacks left, the handle release cannot race a ToHostBuffer.
void destroy_pjrt_buf(DeviceBuf* b) {
  if (b->buf == nullptr) {
    return;
  }
  Plane& p = plane();
  PJRT_Buffer_Destroy_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = b->buf;
  PJRT_Error* err = p.api->PJRT_Buffer_Destroy(&args);
  if (err != nullptr) {
    pjrt_error_string(p.api, err);
  }
  b->buf = nullptr;
  p.live_buffers.fetch_sub(1, std::memory_order_relaxed);
}
}  // namespace

void tpu_buf_free(TpuBufId id) {
  DeviceBuf* b = ResourcePool<DeviceBuf>::Address((uint32_t)id);
  if (b == nullptr) {
    return;
  }
  // claim the slot by bumping the version; only one freer wins.  The
  // PJRT buffer is destroyed when the last pin drains (usually the
  // freer's own unpin right here), never under a live reader.
  uint32_t ver = (uint32_t)(id >> 32);
  uint32_t expected = ver;
  if (!b->version.compare_exchange_strong(expected, ver + 1,
                                          std::memory_order_acq_rel)) {
    return;
  }
  unpin_buf(b);
}

TpuPlaneStats tpu_plane_stats() {
  Plane& p = plane();
  TpuPlaneStats s;
  s.h2d_transfers = p.h2d_transfers.load(std::memory_order_relaxed);
  s.d2h_transfers = p.d2h_transfers.load(std::memory_order_relaxed);
  s.h2d_bytes = p.h2d_bytes.load(std::memory_order_relaxed);
  s.d2h_bytes = p.d2h_bytes.load(std::memory_order_relaxed);
  s.events_fired = p.events_fired.load(std::memory_order_relaxed);
  s.gather_copies = p.gather_copies.load(std::memory_order_relaxed);
  s.zero_copy_sends = p.zero_copy_sends.load(std::memory_order_relaxed);
  s.live_buffers = p.live_buffers.load(std::memory_order_relaxed);
  s.errors = p.errors.load(std::memory_order_relaxed);
  s.d2d_transfers = p.d2d_transfers.load(std::memory_order_relaxed);
  s.d2d_bytes = p.d2d_bytes.load(std::memory_order_relaxed);
  return s;
}

#else  // !TRPC_HAVE_PJRT_HEADER — stubs: the plane is simply unavailable

int tpu_plane_init(const char*) { return -ENOSYS; }
bool tpu_plane_available() { return false; }
const char* tpu_plane_error() {
  return "built without the PJRT C API header";
}
int tpu_plane_device_count() { return 0; }
const char* tpu_plane_platform() { return ""; }
uint64_t tpu_plane_uid() { return 0; }
void tpu_plane_api_version(int out[4]) {
  out[0] = out[1] = out[2] = out[3] = -1;
}
TpuBufId tpu_d2d(TpuBufId, int) { return 0; }
TpuBufId tpu_h2d(const void* data, size_t, int,
                 void (*release)(void*, void*), void* release_arg) {
  if (release != nullptr) {
    release((void*)data, release_arg);
  }
  return 0;
}
TpuBufId tpu_h2d_from_iobuf(const IOBuf&, int) { return 0; }
int tpu_buf_wait(TpuBufId, int64_t) { return -EINVAL; }
int64_t tpu_buf_size(TpuBufId) { return -1; }
int tpu_d2h_into_iobuf(TpuBufId, IOBuf*) { return -EINVAL; }
int tpu_d2h_raw(TpuBufId, char**, size_t*) { return -EINVAL; }
void tpu_buf_free(TpuBufId) {}
TpuPlaneStats tpu_plane_stats() { return TpuPlaneStats{}; }

#endif

}  // namespace trpc
