// capi.cc — C API consumed by the Python package through ctypes
// (brpc_tpu/_native/__init__.py).  The reference has no language bindings
// (SURVEY.md §2: java/python are TBD placeholders); this surface is new
// design for the TPU build: Python is the control plane, C++ the data plane.
#include <cerrno>
#include <cstdlib>
#include <cstring>

#include <vector>

#include "codec.h"
#include "dump.h"
#include "fiber.h"
#include "fiber_sync.h"
#include "h2.h"
#include "http.h"
#include "iobuf.h"
#include "metrics.h"
#include "overload.h"
#include "profiler.h"
#include "crc32c.h"
#include "rpc.h"
#include "sched_perturb.h"
#include "shard.h"
#include "snappy.h"
#include "socket.h"
#include "stream.h"
#include "tls.h"
#include "tpu.h"
#include "heap_profiler.h"
#include "uring.h"

using namespace trpc;

extern "C" {

// --- runtime ---------------------------------------------------------------

int trpc_init(int num_workers) { return fiber_runtime_init(num_workers); }
int trpc_workers() { return fiber_runtime_workers(); }

void trpc_runtime_stats(uint64_t out[5]) {
  FiberRuntimeStats s = fiber_runtime_stats();
  out[0] = s.fibers_created;
  out[1] = s.context_switches;
  out[2] = s.steals;
  out[3] = s.parks;
  out[4] = (uint64_t)s.workers;
}

// --- fibers ----------------------------------------------------------------

typedef void (*trpc_fiber_fn)(void* arg);

int trpc_fiber_start(uint64_t* out, trpc_fiber_fn fn, void* arg) {
  return fiber_start((fiber_t*)out, fn, arg);
}

int trpc_fiber_join(uint64_t f) { return fiber_join(f); }

// FORK scheduling surface (bound queues / jump_group / worker hooks)
int trpc_fiber_start_bound(int group, uint64_t* out, trpc_fiber_fn fn,
                           void* arg) {
  return fiber_start_bound(group, (fiber_t*)out, fn, arg);
}
int trpc_fiber_jump_group(int target) { return fiber_jump_group(target); }
int trpc_fiber_worker_index() { return fiber_worker_index(); }
int trpc_fiber_register_worker_hook(void (*fn)(void*, int), void* user) {
  return fiber_register_worker_hook(fn, user);
}

// fiber-local storage (≙ bthread_key_t)
int trpc_fiber_key_create(uint64_t* key, void (*dtor)(void*)) {
  return fiber_key_create(key, dtor);
}
int trpc_fiber_key_delete(uint64_t key) { return fiber_key_delete(key); }
int trpc_fiber_setspecific(uint64_t key, void* data) {
  return fiber_setspecific(key, data);
}
void* trpc_fiber_getspecific(uint64_t key) {
  return fiber_getspecific(key);
}
void trpc_fiber_yield() { fiber_yield(); }
void trpc_fiber_usleep(int64_t us) { fiber_usleep(us); }
int trpc_in_fiber() { return in_fiber() ? 1 : 0; }

// --- butex (device-event wake hook: PJRT host callbacks call
// trpc_butex_wake_all to resume fibers awaiting a transfer) ----------------

void* trpc_butex_create() { return butex_create(); }
void trpc_butex_destroy(void* b) { butex_destroy((Butex*)b); }
int32_t trpc_butex_load(void* b) {
  return butex_value((Butex*)b).load(std::memory_order_acquire);
}
void trpc_butex_store(void* b, int32_t v) {
  butex_value((Butex*)b).store(v, std::memory_order_release);
}
int32_t trpc_butex_add(void* b, int32_t v) {
  return butex_value((Butex*)b).fetch_add(v, std::memory_order_acq_rel) + v;
}
int trpc_butex_wait(void* b, int32_t expected, int64_t timeout_us) {
  int rc = butex_wait((Butex*)b, expected, timeout_us);
  if (rc != 0) {
    return -errno;
  }
  return 0;
}
int trpc_butex_wake(void* b) { return butex_wake((Butex*)b); }
int trpc_butex_wake_all(void* b) { return butex_wake_all((Butex*)b); }

// --- fiber sync primitives (fiber_sync.h ≙ bthread mutex/cond/rwlock/
// countdown_event) — usable from fibers AND pthreads -----------------------

void* trpc_mutex_create() { return new FiberMutex(); }
void trpc_mutex_destroy(void* m) { delete (FiberMutex*)m; }
void trpc_mutex_lock(void* m) { ((FiberMutex*)m)->lock(); }
int trpc_mutex_trylock(void* m) {
  return ((FiberMutex*)m)->try_lock() ? 1 : 0;
}
void trpc_mutex_unlock(void* m) { ((FiberMutex*)m)->unlock(); }

void* trpc_cond_create() { return new FiberCond(); }
void trpc_cond_destroy(void* c) { delete (FiberCond*)c; }
int trpc_cond_wait(void* c, void* m, int64_t timeout_us) {
  return ((FiberCond*)c)->wait((FiberMutex*)m, timeout_us);
}
void trpc_cond_notify_one(void* c) { ((FiberCond*)c)->notify_one(); }
void trpc_cond_notify_all(void* c) { ((FiberCond*)c)->notify_all(); }

void* trpc_countdown_create(int initial) {
  return new CountdownEvent(initial);
}
void trpc_countdown_destroy(void* e) { delete (CountdownEvent*)e; }
void trpc_countdown_signal(void* e, int n) {
  ((CountdownEvent*)e)->signal(n);
}
void trpc_countdown_add(void* e, int n) { ((CountdownEvent*)e)->add(n); }
int trpc_countdown_wait(void* e, int64_t timeout_us) {
  return ((CountdownEvent*)e)->wait(timeout_us);
}

void* trpc_rwlock_create() { return new FiberRWLock(); }
void trpc_rwlock_destroy(void* l) { delete (FiberRWLock*)l; }
void trpc_rwlock_rdlock(void* l) { ((FiberRWLock*)l)->rdlock(); }
void trpc_rwlock_rdunlock(void* l) { ((FiberRWLock*)l)->rdunlock(); }
void trpc_rwlock_wrlock(void* l) { ((FiberRWLock*)l)->wrlock(); }
void trpc_rwlock_wrunlock(void* l) { ((FiberRWLock*)l)->wrunlock(); }

// --- server ----------------------------------------------------------------

void* trpc_server_create() { return server_create(); }

int trpc_server_add_echo(void* s) {
  return server_add_service((Server*)s, "Echo", 0, nullptr, nullptr);
}

int trpc_server_add_service(void* s, const char* name, HandlerCb cb,
                            void* user) {
  return server_add_service((Server*)s, name, 1, cb, user);
}

int trpc_server_start(void* s, const char* ip, int port) {
  return server_start((Server*)s, ip, port);
}

int trpc_server_port(void* s) { return server_port((Server*)s); }
int trpc_server_stop(void* s) { return server_stop((Server*)s); }
void trpc_server_destroy(void* s) { server_destroy((Server*)s); }
uint64_t trpc_server_requests(void* s) { return server_requests((Server*)s); }

void trpc_set_usercode_workers(int n) { set_usercode_workers(n); }
void trpc_set_usercode_max_inflight(int64_t n) {
  set_usercode_max_inflight(n);
}

// --- overload-control plane (overload.h, ISSUE 11) --------------------------

// Reloadable master switch + gradient knobs (TRPC_OVERLOAD_* seed the
// defaults; the overload_* flags push through here).  Off = the plane
// is inert: no admits, no charges — behavior-identical to before.
void trpc_set_overload(int on) { set_overload(on); }
int trpc_overload_active() { return overload_enabled() ? 1 : 0; }
void trpc_set_overload_min_concurrency(int n) {
  set_overload_min_concurrency(n);
}
void trpc_set_overload_max_concurrency(int n) {
  set_overload_max_concurrency(n);
}
void trpc_set_overload_window_ms(int ms) { set_overload_window_ms(ms); }

// Folded read side (/status's per-family limit/inflight/reject block).
int64_t trpc_overload_limit(int family) { return overload_limit(family); }
int64_t trpc_overload_inflight(int family) {
  return overload_inflight(family);
}
uint64_t trpc_overload_rejects(int family) {
  return overload_rejects(family);
}
uint64_t trpc_overload_admits(int family) {
  return overload_admits(family);
}

// Per-method max_concurrency override (≙ MaxConcurrencyOf; pre-start).
int trpc_server_set_method_max_concurrency(void* s, const char* method,
                                           int64_t n) {
  return server_set_method_max_concurrency((Server*)s, method, n);
}

// Deterministic gradient-math test hooks (tests/test_overload.py): feed
// synthetic samples/clock, reset an agent — the adaptation becomes a
// pure function of the fed sequence.
void trpc_overload_test_feed(int family, int shard, int64_t lat_us,
                             int count, int64_t now_ns) {
  overload_test_feed(family, shard, lat_us, count, now_ns);
}
void trpc_overload_test_reset(int family, int shard) {
  overload_test_reset(family, shard);
}

// --- million-connection ingress (ISSUE 16) ---------------------------------

// Accept-storm pacing knobs (TRPC_ACCEPT_* seed the defaults;
// reloadable): per-listener accepts/sec bucket, burst, and the
// accepted-but-silent connection cap.
void trpc_set_accept_rate(int per_sec) { set_accept_rate(per_sec); }
void trpc_set_accept_burst(int n) { set_accept_burst(n); }
void trpc_set_accept_max_pending(int n) { set_accept_max_pending(n); }
// Per-connection memory diet: idle heartbeat interval (TRPC_IDLE_KICK_MS
// seeds the default; 0 = off; reloadable).
void trpc_set_idle_kick_ms(int ms) { set_idle_kick_ms(ms); }

// Ingress fast path (run-to-completion dispatch + response corking):
// reloadable A/B switch (TRPC_INLINE_DISPATCH env var seeds the default)
// and the per-drain inline budget.
void trpc_set_inline_dispatch(int on) { set_inline_dispatch(on); }
int trpc_inline_dispatch_active() {
  return inline_dispatch_enabled() ? 1 : 0;
}
void trpc_set_inline_budget_requests(int reqs) {
  set_inline_budget_requests(reqs);
}
void trpc_set_inline_budget_us(int64_t us) { set_inline_budget_us(us); }
// Coarse-clock arm time (ns) of a pending usercode request — the rpcz /
// LatencyRecorder arm stamp, queue-inclusive; 0 for stale tokens.
int64_t trpc_token_arm_ns(uint64_t token) { return token_arm_ns(token); }

// --- deadline-budget propagation (ISSUE 19) --------------------------------

// Reloadable master switch + per-hop reserve (TRPC_DEADLINE_PROPAGATE /
// TRPC_DEADLINE_RESERVE_US seed the defaults; the deadline_* flags push
// through here).  Off = no tag-18 stamp, no expired-budget sheds —
// byte-identical to the pre-ISSUE wire.
void trpc_set_deadline_propagate(int on) { set_deadline_propagate(on); }
int trpc_deadline_propagate_active() {
  return deadline_propagate_enabled() ? 1 : 0;
}
void trpc_set_deadline_reserve_us(int64_t us) {
  set_deadline_reserve_us(us);
}
int64_t trpc_deadline_reserve_us() { return deadline_reserve_us(); }
// Live remaining budget of a pending usercode request: 1 = *left_us set
// (may be <= 0), 0 = the request carried no budget, -1 = stale token.
int trpc_token_deadline_left_us(uint64_t token, int64_t* left_us) {
  return token_deadline_left_us(token, left_us);
}

// Native redis cache + cached-response HTTP builtins (pre-start only).
int trpc_server_enable_redis_cache(void* s) {
  return server_enable_redis_cache((Server*)s);
}
int trpc_server_http_cache_put(void* s, const char* path, int status,
                               const char* headers_blob,
                               const uint8_t* body, size_t body_len) {
  return server_http_cache_put((Server*)s, path, status, headers_blob,
                               body, body_len);
}

void trpc_set_event_dispatcher_num(int n) {
  g_event_dispatcher_num.store(n, std::memory_order_relaxed);
}

// Multi-reactor runtime sharding (shard.h): boot-time shard count
// (TRPC_SHARDS env seeds the default; frozen once the fiber runtime
// starts — returns -EBUSY after) and the SO_REUSEPORT listener gate.
int trpc_set_shards(int n) { return shard_set_count(n); }
int trpc_shard_count() { return shard_count(); }
int trpc_set_reuseport(int on) { return shard_set_reuseport(on); }
int trpc_reuseport_enabled() { return shard_reuseport_enabled() ? 1 : 0; }
// Shard of the calling context (-1 off-worker) and the cross-shard hop
// counter (mailbox traffic — near zero on the echo path by design).
int trpc_current_shard() { return current_shard(); }
uint64_t trpc_cross_shard_hops() { return cross_shard_hops(); }

// io_uring transport (FORK RingListener ≙ socket.h:360): opt-in; falls
// back to epoll transparently when the kernel refuses the ring.
void trpc_set_io_uring(int on) { uring_set_enabled(on != 0); }
int trpc_io_uring_available() { return uring_available() ? 1 : 0; }

// Zero-copy egress rail (uring.h SEND_ZC): rides the ring transport;
// large write-queue blocks leave as IORING_OP_SEND_ZC.
void trpc_set_sendzc(int on) { uring_set_sendzc(on != 0); }
void trpc_set_sendzc_threshold(uint64_t bytes) {
  uring_set_sendzc_threshold((size_t)bytes);
}
int trpc_sendzc_available() { return uring_sendzc_available() ? 1 : 0; }
// 1 = a send submitted now would ride SEND_ZC; 0 = writev (rail off,
// kernel without SEND_ZC, or zerocopy notifications reported copies).
int trpc_sendzc_active() { return uring_egress_ready() ? 1 : 0; }

int trpc_respond(uint64_t token, int32_t error_code, const char* error_text,
                 const uint8_t* data, size_t len, const uint8_t* attach,
                 size_t attach_len) {
  return respond(token, error_code, error_text, data, len, attach,
                 attach_len);
}

int trpc_respond_compressed(uint64_t token, int32_t error_code,
                            const char* error_text, const uint8_t* data,
                            size_t len, const uint8_t* attach,
                            size_t attach_len, int compress_type) {
  return respond(token, error_code, error_text, data, len, attach,
                 attach_len, (uint8_t)compress_type);
}

int trpc_token_compress(uint64_t token) { return token_compress_type(token); }

// Pluggable-Authenticator surface (≙ Authenticator::VerifyCredential,
// authenticator.h:30-75): the request's raw credential (meta tag 13) and
// the peer address, read per token on the usercode side.  trpc_token_auth
// returns the credential's FULL length (copy truncated at cap).
size_t trpc_token_auth(uint64_t token, char* buf, size_t cap) {
  return token_auth(token, buf, cap);
}
size_t trpc_token_peer(uint64_t token, char* buf, size_t cap) {
  return token_peer(token, buf, cap);
}

// --- heap + contention profiler (heap_profiler.h ≙ /pprof/heap,
// /pprof/growth, sampled lock-wait stacks) ---------------------------------

void trpc_heap_profiler_enable(int64_t interval_bytes) {
  heap_profiler_enable(interval_bytes);
}
int trpc_heap_profiler_enabled() {
  return heap_profiler_enabled() ? 1 : 0;
}
// which: 0 = live ("heap"), 1 = cumulative ("growth")
size_t trpc_heap_dump(int which, char** out) {
  return heap_profiler_dump(which != 0, out);
}
size_t trpc_contention_dump(char** out) { return contention_dump(out); }
void trpc_contention_profiler_set(int on) {
  contention_profiler_set(on != 0);
}
// all profiler dump texts (CPU/heap/contention) free via
// trpc_profiler_free — one contract, one function

// --- HTTP on the shared port ----------------------------------------------

void trpc_server_set_http_handler(void* s, HttpHandlerCb cb, void* user) {
  server_set_http_handler((Server*)s, cb, user);
}

int trpc_http_respond(uint64_t token, int status, const char* headers_blob,
                      const uint8_t* body, size_t body_len) {
  return http_respond(token, status, headers_blob, body, body_len);
}

int trpc_http_respond_trailers(uint64_t token, int status,
                               const char* headers_blob,
                               const uint8_t* body, size_t body_len,
                               const char* trailers_blob) {
  return http_respond2(token, status, headers_blob, body, body_len,
                       trailers_blob);
}

// --- redis on the shared port ----------------------------------------------

void trpc_server_set_redis_handler(void* s, RedisHandlerCb cb, void* user) {
  server_set_redis_handler((Server*)s, cb, user);
}

int trpc_redis_respond(uint64_t token, const uint8_t* data, size_t len) {
  return redis_respond(token, data, len);
}

// --- framed thrift on the shared port ---------------------------------------

void trpc_server_set_thrift_handler(void* s, ThriftHandlerCb cb, void* user) {
  server_set_thrift_handler((Server*)s, cb, user);
}

int trpc_thrift_respond(uint64_t token, const uint8_t* data, size_t len) {
  return thrift_respond(token, data, len);
}

// --- user-registered protocols ----------------------------------------------

int trpc_server_register_protocol(void* s, const char* name,
                                  const uint8_t* magic, size_t magic_len,
                                  ProtoParseCb parse, ProtoHandlerCb handler,
                                  void* user) {
  return server_register_protocol((Server*)s, name, magic, magic_len, parse,
                                  handler, user);
}

int trpc_proto_respond(uint64_t token, const uint8_t* data, size_t len) {
  return proto_respond(token, data, len);
}

// --- progressive (chunked) HTTP responses -----------------------------------

uint64_t trpc_http_respond_progressive(uint64_t token, int status,
                                       const char* headers_blob) {
  return http_respond_progressive(token, status, headers_blob);
}

int trpc_pa_write(uint64_t pa, const uint8_t* data, size_t len) {
  return pa_write(pa, data, len);
}

int trpc_pa_close(uint64_t pa) { return pa_close(pa); }

// h2 progressive responses end with a trailing HEADERS block (gRPC
// status rides here); trailers_blob is "Key: Value\r\n" lines, ignored
// on HTTP/1.1 connections.
int trpc_pa_close_trailers(uint64_t pa, const char* trailers_blob) {
  return pa_close_trailers(pa, trailers_blob);
}

// --- HTTP/2 client ----------------------------------------------------------

void* trpc_h2_client_create(const char* ip, int port,
                            int64_t connect_timeout_us, int* rc_out) {
  return h2_client_create(ip, port, connect_timeout_us, rc_out);
}

void* trpc_h2_client_create_tls(const char* ip, int port,
                                int64_t connect_timeout_us, int verify,
                                const char* ca_file, int* rc_out) {
  void* ctx = tls_client_ctx_create(verify, ca_file, nullptr, nullptr);
  if (ctx == nullptr) {
    *rc_out = -EPROTO;
    return nullptr;
  }
  void* conn = h2_client_create_tls(ip, port, connect_timeout_us, ctx,
                                    rc_out);
  // ctx lifetime: the TlsState holds what it needs; context can go once
  // the session is up (OpenSSL refcounts the SSL_CTX under the SSL)
  tls_ctx_destroy(ctx);
  return conn;
}

int trpc_h2_client_call(void* conn, const char* method, const char* path,
                        const char* headers_blob, const uint8_t* body,
                        size_t body_len, int64_t timeout_us, void** result) {
  H2ClientResult* r = new H2ClientResult();
  int rc = h2_client_call(conn, method, path, headers_blob, body, body_len,
                          timeout_us, r);
  *result = r;
  return rc;
}

int trpc_h2_result_status(void* r) { return ((H2ClientResult*)r)->status; }

size_t trpc_h2_result_headers(void* r, const uint8_t** p) {
  H2ClientResult* res = (H2ClientResult*)r;
  *p = (const uint8_t*)res->headers.data();
  return res->headers.size();
}

size_t trpc_h2_result_body(void* r, const uint8_t** p) {
  H2ClientResult* res = (H2ClientResult*)r;
  *p = (const uint8_t*)res->body.data();
  return res->body.size();
}

size_t trpc_h2_result_trailers(void* r, const uint8_t** p) {
  H2ClientResult* res = (H2ClientResult*)r;
  *p = (const uint8_t*)res->trailers.data();
  return res->trailers.size();
}

void trpc_h2_result_destroy(void* r) { delete (H2ClientResult*)r; }

// streaming h2/gRPC client calls (h2.h streaming section)
void* trpc_h2_stream_open(void* conn, const char* method, const char* path,
                          const char* headers_blob, int* rc_out) {
  return h2_client_stream_open(conn, method, path, headers_blob, rc_out);
}
int trpc_h2_stream_write(void* st, const uint8_t* data, size_t len,
                         int64_t timeout_us) {
  return h2_client_stream_write(st, data, len, timeout_us);
}
int trpc_h2_stream_close_send(void* st) {
  return h2_client_stream_close_send(st);
}
int64_t trpc_h2_stream_read(void* st, int64_t timeout_us, uint8_t** out) {
  return h2_client_stream_read(st, timeout_us, out);
}
void trpc_h2_stream_chunk_free(uint8_t* p) {
  h2_client_stream_chunk_free(p);
}
int trpc_h2_stream_status(void* st) { return h2_client_stream_status(st); }
size_t trpc_h2_stream_headers(void* st, const uint8_t** p) {
  return h2_client_stream_headers(st, p);
}
size_t trpc_h2_stream_trailers(void* st, const uint8_t** p) {
  return h2_client_stream_trailers(st, p);
}
void trpc_h2_stream_destroy(void* st) { h2_client_stream_destroy(st); }

void trpc_h2_client_destroy(void* conn) { h2_client_destroy(conn); }

// --- auth ------------------------------------------------------------------

void trpc_server_set_auth(void* s, const uint8_t* secret, size_t len) {
  server_set_auth((Server*)s, secret, len);
}

// --- TLS (tls.h: libssl dlopen'd at runtime) -------------------------------

int trpc_tls_available() { return tls_available() ? 1 : 0; }
// LIFETIME: own per-thread buffer, valid until the same thread's next
// trpc_tls_error call (independent of trpc_tpu_plane_error's buffer).
const char* trpc_tls_error() { return tls_error(); }
int trpc_server_add_tls_sni(void* s, const char* pattern, const char* cert,
                            const char* key) {
  return server_add_tls_sni((Server*)s, pattern, cert, key);
}

int trpc_server_set_tls(void* s, const char* cert, const char* key,
                        const char* verify_ca) {
  return server_set_tls((Server*)s, cert, key, verify_ca);
}
int trpc_channel_set_tls(void* c, int verify, const char* ca,
                         const char* cert, const char* key) {
  return channel_set_tls((Channel*)c, verify, ca, cert, key);
}

void trpc_channel_set_connection_type(void* c, int t) {
  channel_set_connection_type((Channel*)c, t);
}

void trpc_channel_set_auth(void* c, const uint8_t* secret, size_t len) {
  channel_set_auth((Channel*)c, secret, len);
}

// --- introspection ---------------------------------------------------------

size_t trpc_server_conn_stats(void* s, char* buf, size_t cap) {
  return server_conn_stats((Server*)s, buf, cap);
}

size_t trpc_socket_dump(char* buf, size_t cap) {
  return socket_dump_all(buf, cap);
}

size_t trpc_ids_dump(char* buf, size_t cap) {
  return pending_call_dump(buf, cap);
}

// --- payload-codec rail (codec.h: identity/snappy/bf16/int8) ----------------

// Reloadable request codec (TRPC_PAYLOAD_CODEC seeds the default; the
// `payload_codec` flag pushes through here).
void trpc_set_payload_codec(int id) { set_payload_codec(id); }
int trpc_payload_codec() { return payload_codec(); }
void trpc_set_codec_min_bytes(int64_t n) { set_codec_min_bytes(n); }
int trpc_codec_id(const char* name) { return codec_id_from_name(name); }
const char* trpc_codec_name(int id) { return codec_name(id); }

// Bytes-level encode/decode for the Python surface (tests, tools): the
// result is malloc'd; free with trpc_codec_buf_free.  Returns the
// encoded/decoded length, 0 = declined (encode left the part plain),
// -1 = error.  `codec_out` (encode only, nullable) receives the codec
// id actually applied.
int64_t trpc_codec_encode(int codec, const uint8_t* in, size_t n,
                          uint8_t** out, int* codec_out) {
  IOBuf part;
  if (n > 0) {
    part.append(in, n);
  }
  uint8_t applied = codec_encode((uint8_t)codec, &part);
  if (codec_out != nullptr) {
    *codec_out = applied;
  }
  if (applied == 0) {
    return 0;
  }
  *out = (uint8_t*)malloc(part.size() > 0 ? part.size() : 1);
  if (*out == nullptr) {
    return -1;
  }
  part.copy_to(*out, part.size());
  return (int64_t)part.size();
}

int64_t trpc_codec_decode(int codec, const uint8_t* in, size_t n,
                          uint8_t** out) {
  IOBuf part;
  if (n > 0) {
    part.append(in, n);
  }
  if (codec_decode((uint8_t)codec, &part) != 0) {
    return -1;
  }
  *out = (uint8_t*)malloc(part.size() > 0 ? part.size() : 1);
  if (*out == nullptr) {
    return -1;
  }
  part.copy_to(*out, part.size());
  return (int64_t)part.size();
}

void trpc_codec_buf_free(uint8_t* p) { free(p); }

// Property-test hook: roundtrip `data` through a CHAINED IOBuf built
// from `chunk`-byte appends (multi-block, element-straddling seams).
// 0 = byte-exact, 1 = lossy (max |f32 err| in *max_err), -1 = failure.
int trpc_codec_roundtrip_chained(int codec, const uint8_t* data, size_t n,
                                 size_t chunk, double* max_err) {
  return codec_roundtrip_chained(codec, data, n, chunk, max_err);
}

// --- snappy codec -----------------------------------------------------------

uint32_t trpc_crc32c_extend(uint32_t init, const uint8_t* data, size_t n) {
  return crc32c_extend(init, data, n);
}

int trpc_crc32c_hardware() { return crc32c_hardware() ? 1 : 0; }

size_t trpc_snappy_max_compressed_length(size_t n) {
  return snappy_max_compressed_length(n);
}

size_t trpc_snappy_compress(const uint8_t* in, size_t n, uint8_t* out) {
  return snappy_compress(in, n, out);
}

size_t trpc_snappy_uncompressed_length(const uint8_t* in, size_t n) {
  size_t hdr;
  return snappy_uncompressed_length(in, n, &hdr);
}

size_t trpc_snappy_decompress(const uint8_t* in, size_t n, uint8_t* out,
                              size_t out_cap) {
  return snappy_decompress(in, n, out, out_cap);
}

// --- channel ---------------------------------------------------------------

void* trpc_channel_create(const char* ip, int port) {
  return channel_create(ip, port);
}

void trpc_channel_destroy(void* c) { channel_destroy((Channel*)c); }
void trpc_channel_set_connect_timeout(void* c, int64_t us) {
  channel_set_connect_timeout((Channel*)c, us);
}

// Synchronous call.  Response/attachment/error_text are returned through a
// heap CallResult the caller must free with trpc_result_destroy.
int trpc_channel_call(void* c, const char* method, const uint8_t* req,
                      size_t req_len, const uint8_t* attach,
                      size_t attach_len, int64_t timeout_us, void** result) {
  CallResult* r = new CallResult();
  int rc = channel_call((Channel*)c, method, req, req_len, attach, attach_len,
                        timeout_us, r);
  *result = r;
  return rc;
}

int trpc_channel_call_compressed(void* c, const char* method,
                                 const uint8_t* req, size_t req_len,
                                 const uint8_t* attach, size_t attach_len,
                                 int64_t timeout_us, int compress_type,
                                 void** result) {
  CallResult* r = new CallResult();
  int rc = channel_call((Channel*)c, method, req, req_len, attach, attach_len,
                        timeout_us, r, 0, (uint8_t)compress_type);
  *result = r;
  return rc;
}

// Unified call entry with a pre-published call id: *call_id_out is
// written before the request hits the wire, so another thread can
// trpc_call_cancel() it while this one is blocked (≙ StartCancel).
int trpc_channel_call_cancelable(void* c, const char* method,
                                 const uint8_t* req, size_t req_len,
                                 const uint8_t* attach, size_t attach_len,
                                 int64_t timeout_us, uint64_t stream,
                                 int compress_type, uint64_t* call_id_out,
                                 void** result) {
  CallResult* r = new CallResult();
  int rc = channel_call((Channel*)c, method, req, req_len, attach,
                        attach_len, timeout_us, r, stream,
                        (uint8_t)compress_type, call_id_out);
  *result = r;
  return rc;
}

// Client egress fast path: request corking A/B switch (TRPC_CLIENT_CORK
// env seeds the default; reloadable).
void trpc_set_client_cork(int on) { set_client_cork(on); }
int trpc_client_cork_active() { return client_cork_enabled() ? 1 : 0; }

// Serialize-once fan-out: one request body serialized once, shared as
// refcounted blocks across n sub-calls (one per channels[i]); results[i]
// receives a CallResult handle the caller frees with trpc_result_destroy
// (read error_code per sub).  Returns the number of failed sub-calls.
int trpc_fanout_call(void** channels, int n, const char* method,
                     const uint8_t* req, size_t req_len,
                     const uint8_t* attach, size_t attach_len,
                     int64_t timeout_us, void** results) {
  std::vector<CallResult*> outs((size_t)(n > 0 ? n : 0));
  for (int i = 0; i < n; ++i) {
    outs[(size_t)i] = new CallResult();
    results[i] = outs[(size_t)i];
  }
  return channel_fanout_call((Channel**)channels, n, method, req, req_len,
                             attach, attach_len, timeout_us, outs.data());
}

int trpc_call_cancel(uint64_t call_id) { return call_cancel(call_id); }

// Server-side cancellation observation (≙ IsCanceled/NotifyOnCancel).
int trpc_call_canceled(uint64_t token) { return call_canceled(token); }
int trpc_call_wait_canceled(uint64_t token, int64_t timeout_us) {
  return call_wait_canceled(token, timeout_us);
}

int32_t trpc_result_error_code(void* r) {
  return ((CallResult*)r)->error_code;
}
const char* trpc_result_error_text(void* r) {
  return ((CallResult*)r)->error_text.c_str();
}
size_t trpc_result_data(void* r, const uint8_t** p) {
  CallResult* cr = (CallResult*)r;
  *p = (const uint8_t*)cr->response.data();
  return cr->response.size();
}
size_t trpc_result_attachment(void* r, const uint8_t** p) {
  CallResult* cr = (CallResult*)r;
  *p = (const uint8_t*)cr->attachment.data();
  return cr->attachment.size();
}
int trpc_result_compress(void* r) {
  return ((CallResult*)r)->compress_type;
}
void trpc_result_destroy(void* r) { delete (CallResult*)r; }

int trpc_channel_call_stream(void* c, const char* method, const uint8_t* req,
                             size_t req_len, const uint8_t* attach,
                             size_t attach_len, int64_t timeout_us,
                             uint64_t stream, void** result) {
  CallResult* r = new CallResult();
  int rc = channel_call((Channel*)c, method, req, req_len, attach,
                        attach_len, timeout_us, r, stream);
  *result = r;
  return rc;
}

// Replay rail (dump.h): req/attach are WIRE-form bytes from a captured
// sample — the payload-codec encode is skipped and tags 16/17 carry
// payload_codec/attach_codec verbatim, so the replayed frame is
// byte-identical to the one the flight recorder captured.
int trpc_channel_call_raw(void* c, const char* method, const uint8_t* req,
                          size_t req_len, const uint8_t* attach,
                          size_t attach_len, int64_t timeout_us,
                          int compress_type, int payload_codec,
                          int attach_codec, void** result) {
  CallResult* r = new CallResult();
  int rc = channel_call((Channel*)c, method, req, req_len, attach,
                        attach_len, timeout_us, r, 0,
                        (uint8_t)compress_type, nullptr,
                        (payload_codec & 0xff) | ((attach_codec & 0xff) << 8));
  *result = r;
  return rc;
}

// --- streaming RPC (stream.h) ----------------------------------------------

uint64_t trpc_stream_create(uint64_t window_bytes) {
  return stream_create(window_bytes);
}
uint64_t trpc_token_stream_id(uint64_t token) {
  return token_stream_id(token);
}
uint64_t trpc_stream_accept(uint64_t token, uint64_t window_bytes) {
  return stream_accept(token, window_bytes);
}
int trpc_stream_write(uint64_t h, const uint8_t* data, size_t len,
                      int64_t timeout_us) {
  return stream_write(h, data, len, timeout_us);
}
// Returns msg length (>=0), 0 = clean EOF, <0 = -errno.  *out must be
// freed with trpc_stream_buf_free.
int64_t trpc_stream_read(uint64_t h, int64_t timeout_us, uint8_t** out) {
  return (int64_t)stream_read(h, timeout_us, out);
}
void trpc_stream_buf_free(uint8_t* p) { stream_buf_free(p); }
// Tensor frames: write transfers ownership of the device buffer on
// success; read returns a NEW buffer on dst_device (see stream.h).
int trpc_stream_write_device(uint64_t h, uint64_t buf, int64_t timeout_us) {
  return stream_write_device(h, buf, timeout_us);
}
int trpc_stream_read_device(uint64_t h, int dst_device, int64_t timeout_us,
                            uint64_t* out, uint64_t* len_out) {
  return stream_read_device(h, dst_device, timeout_us, out, len_out);
}
int trpc_stream_close(uint64_t h) { return stream_close(h); }
// Abortive close carrying an error code; the peer's reads surface it
// (never a clean EOF) and trpc_stream_rst_code reports the code.
int trpc_stream_rst(uint64_t h, int32_t error_code) {
  return stream_rst(h, error_code);
}
int32_t trpc_stream_rst_code(uint64_t h) { return stream_rst_code(h); }
void trpc_stream_destroy(uint64_t h) { stream_destroy(h); }
int trpc_stream_remote_closed(uint64_t h) { return stream_remote_closed(h); }
int trpc_stream_failed(uint64_t h) { return stream_failed(h); }
int64_t trpc_stream_pending_bytes(uint64_t h) {
  return stream_pending_bytes(h);
}

// --- native metrics + profiler (metrics.h, profiler.h) ----------------------

// "name value\n" lines of the native core's internals (merged into the
// Python bvar registry; ≙ the reference's self-instrumenting bvars).
size_t trpc_native_metrics_dump(char* buf, size_t cap) {
  return native_metrics_dump(buf, cap);
}

// --- hot-path telemetry plane (metrics.h, ISSUE 9) --------------------------

// Reloadable master switch (TRPC_TELEMETRY seeds the default; the
// `telemetry` flag pushes through here) — off is the bench A/B baseline.
void trpc_set_telemetry(int on) { set_telemetry(on); }
int trpc_telemetry_active() { return telemetry_enabled() ? 1 : 0; }

// Folded per-family histogram reads (percentile by log-bucket walk).
int64_t trpc_telemetry_percentile_us(int family, double q) {
  return telemetry_percentile_us(family, q);
}
uint64_t trpc_telemetry_count(int family) { return telemetry_count(family); }
int64_t trpc_telemetry_inflight(int family) {
  return telemetry_inflight(family);
}
const char* trpc_telemetry_family_name(int family) {
  return telemetry_family_name(family);
}
// Number of method families — the Python layer derives its family list
// from name(0..n-1) so a family added in metrics.h shows up in /status
// and the span labels without touching Python.
int trpc_telemetry_families() { return TF_FAMILIES; }

// Prometheus exposition: real cumulative _bucket{le=...} series per
// family + _sum/_count (the portal appends this to /metrics).
size_t trpc_telemetry_prom_dump(char* buf, size_t cap) {
  return telemetry_prom_dump(buf, cap);
}

// Native rpcz: span capture for inline-dispatched / native-client calls.
// The Python enable_rpcz flag drives the switch; the budget mirrors
// rpcz_max_samples_per_second (collector-style rate limit).
void trpc_set_rpcz(int on) { rpcz_set_enabled(on); }
int trpc_rpcz_active() { return rpcz_native_enabled() ? 1 : 0; }
void trpc_set_rpcz_budget(int64_t per_second) {
  rpcz_set_budget(per_second);
}
// Drain captured spans as tab-separated lines (consumed; they surface
// exactly once, through the Python Collector into span.py's store).
size_t trpc_rpcz_drain(char* buf, size_t cap) { return rpcz_drain(buf, cap); }

// Native flight recorder (dump.h): wire-form traffic capture on the
// fast paths.  The Python rpc_dump flag drives the switch; the budget
// mirrors rpc_dump_max_samples_per_second (collector-style rate limit).
void trpc_set_dump(int on) { dump_set_enabled(on); }
int trpc_dump_active() { return dump_native_enabled() ? 1 : 0; }
void trpc_set_dump_budget(int64_t per_second) {
  dump_set_budget(per_second);
}
// Drain captured frames as length-prefixed v2 sample blobs (consumed;
// they surface exactly once, through dump.py's drain into recordio).
size_t trpc_dump_drain(char* buf, size_t cap) { return dump_drain(buf, cap); }

// Cross-hop trace context of the calling thread (fiber-local parent):
// trace_set_current(0,0,0) clears; python_owned=1 marks "the Python
// layer created this hop's client span" so native skips its duplicate.
void trpc_trace_set_current(uint64_t trace_id, uint64_t span_id,
                            int python_owned) {
  trace_set_current(trace_id, span_id, python_owned);
}
int trpc_trace_current(uint64_t* trace_id, uint64_t* span_id) {
  TraceCtx tc = trace_current();
  if (trace_id != nullptr) {
    *trace_id = tc.trace_id;
  }
  if (span_id != nullptr) {
    *span_id = tc.span_id;
  }
  return tc.python_owned ? 1 : 0;
}
// TRACEPRINTF twin: annotation rides the next native span captured on
// this thread (no-op while rpcz is off).
void trpc_trace_annotate(const char* text) { trace_annotate(text); }

// Inbound trace/span ids (meta tags 7/8) of a pending usercode request —
// the Controller.trace_id surface.  Returns 0, -1 for stale tokens.
int trpc_token_trace(uint64_t token, uint64_t* trace_id,
                     uint64_t* span_id) {
  return token_trace(token, trace_id, span_id);
}

// --- schedule perturbation / replay (sched_perturb.h) -----------------------

// Seed the schedule-fuzzing mode (0 disables; the `sched_seed`
// reloadable flag pushes through here).  The trace hash is the replay
// fingerprint: same seed + fixed scenario => same hash
// (tests/test_sched_replay.py).
void trpc_sched_set_seed(uint64_t seed) { sched_perturb_set_seed(seed); }
uint64_t trpc_sched_seed() { return sched_perturb_seed(); }
uint64_t trpc_sched_trace_hash() { return sched_trace_hash(); }
size_t trpc_sched_trace_dump(char* buf, size_t cap) {
  return sched_trace_dump(buf, cap);
}

int trpc_profiler_start(int hz) { return profiler_start(hz); }
// Folded flamegraph stacks; caller frees with trpc_profiler_free.
size_t trpc_profiler_stop(char** out) { return profiler_stop(out); }
void trpc_profiler_free(char* p) { profiler_free(p); }
int trpc_profiler_running() { return profiler_running() ? 1 : 0; }
size_t trpc_symbolize(const void* addr, char* buf, size_t cap) {
  return profiler_symbolize(addr, buf, cap);
}

// --- device data plane (tpu.h: PJRT-backed, dlopen'd at runtime) -----------

int trpc_tpu_plane_init(const char* plugin_path) {
  return tpu_plane_init(plugin_path);
}
int trpc_tpu_plane_available() { return tpu_plane_available() ? 1 : 0; }
// LIFETIME: the returned pointer is this function's own per-THREAD
// buffer, valid until the SAME thread calls trpc_tpu_plane_error again —
// copy it out before the next query (the ctypes layer converts to bytes
// immediately, which satisfies this).
const char* trpc_tpu_plane_error() { return tpu_plane_error(); }
const char* trpc_tpu_plane_platform() { return tpu_plane_platform(); }
int trpc_tpu_device_count() { return tpu_plane_device_count(); }

// H2D from caller memory.  The DMA reads the source ASYNCHRONOUSLY
// (kImmutableUntilTransferCompletes), and a ctypes caller cannot be
// trusted to keep its bytes object alive that long — so this boundary
// takes ONE explicit host copy and hands lifetime to the native release
// hook.  (The zero-copy path is tpu_h2d_from_iobuf, used by the RPC
// attachment plane; this is the convenience surface.)
uint64_t trpc_tpu_h2d(const uint8_t* data, size_t len, int device) {
  void* copy = hp_malloc(len > 0 ? len : 1);
  if (copy == nullptr) {
    return 0;
  }
  memcpy(copy, data, len);
  return tpu_h2d(copy, len, device,
                 [](void* d, void*) { hp_free(d); }, nullptr);
}
int trpc_tpu_buf_wait(uint64_t id, int64_t timeout_us) {
  return tpu_buf_wait(id, timeout_us);
}
int64_t trpc_tpu_buf_size(uint64_t id) { return tpu_buf_size(id); }
// D2H into a fresh malloc'd buffer the caller frees with trpc_tpu_buf_release.
int64_t trpc_tpu_d2h(uint64_t id, uint8_t** out) {
  char* mem = nullptr;
  size_t n = 0;
  int rc = tpu_d2h_raw(id, &mem, &n);
  if (rc != 0) {
    return rc;
  }
  *out = (uint8_t*)mem;  // the DMA landing zone itself — no second copy
  return (int64_t)n;
}
void trpc_tpu_buf_release(uint8_t* p) { tpu_host_free(p); }
void trpc_tpu_buf_free(uint64_t id) { tpu_buf_free(id); }

void trpc_tpu_plane_stats(uint64_t out[11]) {
  TpuPlaneStats s = tpu_plane_stats();
  out[0] = s.h2d_transfers;
  out[1] = s.d2h_transfers;
  out[2] = s.h2d_bytes;
  out[3] = s.d2h_bytes;
  out[4] = s.events_fired;
  out[5] = s.gather_copies;
  out[6] = s.zero_copy_sends;
  out[7] = s.live_buffers;
  out[8] = s.errors;
  out[9] = s.d2d_transfers;
  out[10] = s.d2d_bytes;
}

uint64_t trpc_tpu_d2d(uint64_t src, int dst_device) {
  return tpu_d2d(src, dst_device);
}

uint64_t trpc_tpu_plane_uid() { return tpu_plane_uid(); }

void trpc_tpu_plane_api_version(int out[4]) { tpu_plane_api_version(out); }

// HBM echo service (kind=2): attachments round-trip host->HBM->host.
int trpc_server_add_hbm_echo(void* s, const char* name) {
  return server_add_service((Server*)s, name, 2, nullptr, nullptr);
}

// Device-plane handshake on tpu:// channels.
void trpc_channel_request_device_plane(void* c, int enable) {
  channel_request_device_plane((Channel*)c, enable);
}
int trpc_channel_transport_state(void* c) {
  return channel_transport_state((Channel*)c);
}

// --- HTTP client -----------------------------------------------------------

typedef void (*trpc_http_chunk_cb)(void* user, const uint8_t* data,
                                   size_t len);

void trpc_channel_set_http(void* c, const char* host) {
  channel_set_http((Channel*)c, host);
}

// Synchronous HTTP call; the result handle is read with the getters below
// and freed with trpc_http_result_destroy.  chunk_cb (nullable) streams
// the body progressively instead of buffering it.
int trpc_http_client_call(void* c, const char* method, const char* target,
                          const char* headers_blob, const uint8_t* body,
                          size_t body_len, int64_t timeout_us,
                          trpc_http_chunk_cb chunk_cb, void* chunk_user,
                          void** result) {
  HttpClientResult* r = new HttpClientResult();
  int rc = http_client_call((Channel*)c, method, target, headers_blob,
                            body, body_len, timeout_us, r, chunk_cb,
                            chunk_user);
  *result = r;
  return rc;
}

int trpc_http_result_status(void* r) {
  return ((HttpClientResult*)r)->status;
}
const char* trpc_http_result_error_text(void* r) {
  return ((HttpClientResult*)r)->error_text.c_str();
}
size_t trpc_http_result_headers(void* r, const uint8_t** p) {
  HttpClientResult* hr = (HttpClientResult*)r;
  *p = (const uint8_t*)hr->headers.data();
  return hr->headers.size();
}
size_t trpc_http_result_body(void* r, const uint8_t** p) {
  HttpClientResult* hr = (HttpClientResult*)r;
  *p = (const uint8_t*)hr->body.data();
  return hr->body.size();
}
void trpc_http_result_destroy(void* r) { delete (HttpClientResult*)r; }

// --- bench -----------------------------------------------------------------

int trpc_run_echo_bench(const char* ip, int port, int nconn, int concurrency,
                        int payload_size, int attach_size, double seconds,
                        double out[9]) {
  BenchResult br;
  int rc = run_echo_bench(ip, port, nconn, concurrency, payload_size,
                          attach_size, seconds, &br);
  out[0] = br.qps;
  out[1] = br.p50_us;
  out[2] = br.p90_us;
  out[3] = br.p99_us;
  out[4] = br.p999_us;
  out[5] = br.max_us;
  out[6] = (double)br.calls;
  out[7] = (double)br.errors;
  out[8] = br.gbps;
  return rc;
}

}  // extern "C"
