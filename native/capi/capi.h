// C API over the native RPC stack — the ctypes boundary for the Python
// bindings (brpc_tpu.runtime). The reference keeps python/ as a stub
// (python/README.md "TBD"); our bindings are first-class because the TPU
// data plane (JAX) lives in Python and needs the host RPC fabric.
#pragma once

#include <stddef.h>
#include <stdint.h>

extern "C" {

// ---- server ----
void* tbrpc_server_create();
// addr: "0.0.0.0:0" for ephemeral. Returns the bound port, or -1.
int tbrpc_server_start(void* server, const char* addr);
// Same, with TLS: cert/key (PEM paths) non-empty makes the port ALSO accept
// TLS (first-byte sniffing; plaintext clients unaffected; ALPN h2+http/1.1).
int tbrpc_server_start_tls(void* server, const char* addr, const char* cert,
                           const char* key);
int tbrpc_server_stop(void* server);
void tbrpc_server_destroy(void* server);
// Built-in native echo service "EchoService" (methods: Echo) — payload and
// attachment are echoed back untouched. Used by benchmarks and smoke tests.
int tbrpc_server_add_echo_service(void* server);

// Inline fast path: run SMALL requests (bodies <= ici_small_msg_threshold)
// to `service` directly on the input fiber, skipping the dispatch hop.
// ONLY services whose native implementation declares itself non-blocking
// qualify (Service::inline_safe); Python-backed services are always
// refused — their handlers park the fiber on the GIL-safe callback pool,
// and a parked input fiber head-of-line-blocks its whole connection.
// Returns 0 on success, -1 on unknown service or a non-inline-safe one.
int tbrpc_server_set_inline(void* server, const char* service, int enabled);

// Python-backed service: the callback runs on a dedicated pthread from a
// small pool (NOT on the fiber — ctypes pairs PyGILState_Ensure/Release on
// one OS thread, and a fiber that parks mid-callback could resume on a
// different worker; the service fiber parks until the callback returns).
// It must fill *resp/resp_len via tbrpc_alloc (ownership passes back).
// On failure it sets *error_code and MAY write a NUL-terminated message
// into err_text (err_text_cap bytes, provided by the caller) — the text
// rides the wire back to the client's errbuf.
typedef void (*tbrpc_handler_cb)(void* ctx, const char* method,
                                 const void* req, size_t req_len,
                                 const void* attach, size_t attach_len,
                                 void** resp, size_t* resp_len,
                                 void** resp_attach, size_t* resp_attach_len,
                                 int* error_code, char* err_text,
                                 size_t err_text_cap);
int tbrpc_server_add_callback_service(void* server, const char* name,
                                      tbrpc_handler_cb cb, void* ctx);

// ---- channel ----
// protocol: 0 = tstd (default), 5 = gRPC over HTTP/2.
void* tbrpc_channel_create_ex(const char* addr, int64_t timeout_ms,
                              int max_retry, int protocol);
void* tbrpc_channel_create(const char* addr, int64_t timeout_ms,
                           int max_retry);
void tbrpc_channel_destroy(void* channel);

// Synchronous call. On success (return 0) *resp/*resp_attach are
// tbrpc_alloc'd buffers the caller frees with tbrpc_free. On failure
// returns the error code and fills errbuf.
int tbrpc_call(void* channel, const char* service_method, const void* req,
               size_t req_len, const void* attach, size_t attach_len,
               void** resp, size_t* resp_len, void** resp_attach,
               size_t* resp_attach_len, char* errbuf, size_t errbuf_len);

void* tbrpc_alloc(size_t n);
void tbrpc_free(void* p);
// A tbrpc_alloc'd copy of [p, p+n): malloc + memcpy in one call (a
// handler's response bytes), freed with tbrpc_free.
void* tbrpc_dup(const void* p, size_t n);

// ---- TensorArena: registered transfer memory for tensor payloads ----
// The RDMA-registration analog (reference rdma_helper.h:48): a shm-backed
// region both ends of a tpu:// connection map. Attachments that live in an
// arena cross the transport BY REFERENCE (zero host-side copies); over
// plain TCP they writev straight from arena pages.
void* tbrpc_arena_create(size_t bytes);  // null on failure; bytes <= 4GB
void tbrpc_arena_destroy(void* arena);
void* tbrpc_arena_base(void* arena);
// First-fit range allocator (64B-aligned). Returns offset or -1.
int64_t tbrpc_arena_alloc(void* arena, size_t len);
// Deferred free: the range returns to the allocator once every local and
// remote (wire) reference has dropped.
int tbrpc_arena_free(void* arena, uint64_t off);
int64_t tbrpc_arena_busy_bytes(void* arena);
// Aggregates over EVERY live arena in the process (occupancy gauges and
// /tensorz use the same walk) — safe to call concurrently with arena
// destruction, unlike per-handle reads from another thread.
int64_t tbrpc_arenas_busy_bytes(void);
int64_t tbrpc_arenas_total_bytes(void);
// Expose those aggregates as NATIVE PassiveStatus gauges
// (tensor_arena_busy_bytes / tensor_arena_total_bytes) so scrapes never
// leave C++ (a Python-callback gauge would pay a callback-pool hop + GIL
// per scrape for a value computable natively). Idempotent.
void tbrpc_var_arena_gauges_create(void);
// Block the calling thread until `off`'s range has no references (safe to
// overwrite). timeout_ms < 0 waits forever. 0 ok, -1 timeout.
int tbrpc_arena_wait_reusable(void* arena, uint64_t off, int64_t timeout_ms);

// Synchronous call whose request attachment is the arena range
// [att_off, att_off+att_len). The response attachment comes back as a VIEW
// when it is contiguous (zero-copy for single-range tensor responses over
// tpu://): *view must be released with tbrpc_view_free (that release is
// what returns the server's arena range); *ratt_ptr/*ratt_len point at the
// bytes in place. *ratt_copied=1 means it was flattened into a tbrpc_alloc
// buffer instead (then *view is null and *ratt_ptr is freed by the caller
// via tbrpc_free). arena may be null / att_len 0 for no attachment.
int tbrpc_call_tensor(void* channel, const char* service_method,
                      const void* req, size_t req_len, void* arena,
                      uint64_t att_off, size_t att_len, void** resp,
                      size_t* resp_len, void** view, const void** ratt_ptr,
                      size_t* ratt_len, int* ratt_copied, char* errbuf,
                      size_t errbuf_len);
void tbrpc_view_free(void* view);

// ---- async tensor RPC: futures over the native async CallMethod ----
// The pipelined data-plane primitive: submit keeps the calling thread free
// while the RPC is in flight, so N tensors cost ~1 round-trip + N wire
// times instead of N full round-trips (the PipelineWindow in
// brpc_tpu/runtime/tensor.py rides this).
//
// Completion callback (optional, may be null): runs on a dedicated
// callback-pool pthread (same PyGILState discipline as service handlers)
// BEFORE the future becomes waitable, carrying the same resp/view/ratt
// values a subsequent tbrpc_future_wait returns. It is a NOTIFICATION:
// ownership does not transfer here (the future still owns the buffers
// until a wait consumes them or cancel/destroy releases them), so the
// callback must not free anything — and must not call tbrpc_future_wait
// on its own future (the wait cannot complete until the callback returns).
typedef void (*tbrpc_tensor_done_cb)(void* ctx, int status, const void* resp,
                                     size_t resp_len, void* view,
                                     const void* ratt_ptr, size_t ratt_len,
                                     int ratt_copied, const char* err_text);
// Start the RPC and return a future handle (never null). Request/arena
// semantics are identical to tbrpc_call_tensor; the arena range gets its
// local reference before this returns, so the caller may tbrpc_arena_free
// the range any time after submission (deferred-free semantics hold the
// bytes until every wire reference drops).
void* tbrpc_call_tensor_async(void* channel, const char* service_method,
                              const void* req, size_t req_len, void* arena,
                              uint64_t att_off, size_t att_len,
                              tbrpc_tensor_done_cb done_cb, void* done_ctx);
// Block the calling thread until completion, then hand out the results
// EXACTLY ONCE (the same out-param contract as tbrpc_call_tensor,
// including the deferred view release via tbrpc_view_free). Returns 0 on
// success or the RPC error code; a second wait (or a wait after cancel)
// returns the code with every out zeroed. The future handle stays valid
// until tbrpc_future_destroy.
int tbrpc_future_wait(void* fut, void** resp, size_t* resp_len, void** view,
                      const void** ratt_ptr, size_t* ratt_len,
                      int* ratt_copied, char* errbuf, size_t errbuf_len);
// Like tbrpc_future_wait but gives up after timeout_ms (>= 0): returns -1
// with nothing consumed when the RPC is still in flight — the future can
// be waited again. (RPC failures always return the positive framework
// code, never -1, so the two cannot collide.)
int tbrpc_future_timed_wait(void* fut, int64_t timeout_ms, void** resp,
                            size_t* resp_len, void** view,
                            const void** ratt_ptr, size_t* ratt_len,
                            int* ratt_copied, char* errbuf,
                            size_t errbuf_len);
// Cancel: in flight, raises TRPC_ECANCELED through the controller (the
// attempt socket's pending id), ending the RPC early; already complete
// and unconsumed, releases the response view/buffers NOW (exactly once —
// a later destroy will not touch them). After cancel every wait returns
// TRPC_ECANCELED with zeroed outs. Always 0.
int tbrpc_future_cancel(void* fut);
// Release the future. In flight: detaches — the RPC is canceled and the
// completion path frees everything, including the response view if the
// response wins the race (the exactly-once release the lifetime tests
// pin down). Completed: frees whatever a wait has not consumed.
void tbrpc_future_destroy(void* fut);
// Async tensor RPCs currently between submit and completion, process-wide.
// Also exposed as the native PassiveStatus gauge `tensor_rpc_inflight`
// (created on the first async submit) on /vars + /brpc_metrics.
int64_t tbrpc_async_inflight(void);

// Tensor service: the handler sees the request attachment IN PLACE (no
// copy when it arrived as one zero-copy block) and may return its response
// attachment as a range of a local arena — it rides back by reference.
// resp_arena null => no response attachment. Setting *resp_att_autofree=1
// frees the range AFTER the response reference is taken (i.e. the range
// returns to the allocator once the client's release arrives) — the safe
// fire-and-forget mode for per-response allocations; freeing inside the
// handler instead would let a concurrent request reuse the range before
// the response is sent.
typedef void (*tbrpc_tensor_handler_cb)(
    void* ctx, const char* method, const void* req, size_t req_len,
    const void* att, size_t att_len,
    void** resp, size_t* resp_len,  // tbrpc_alloc'd, ownership passes back
    void** resp_arena, uint64_t* resp_att_off, size_t* resp_att_len,
    int* resp_att_autofree, int* error_code, char* err_text,
    size_t err_text_cap);
int tbrpc_server_add_tensor_service(void* server, const char* name,
                                    tbrpc_tensor_handler_cb cb, void* ctx);

// ---- observability: tbvar metrics from the data plane ----
// Native variables created and fed from Python (or any embedder): they live
// in the SAME registry as the framework's own metrics, so /vars,
// /brpc_metrics and /tensorz show the Python tensor path next to the fiber
// runtime. Handles are immortal (the registry is process-lifetime);
// create returns null when the name is already taken (tbvar semantics:
// the second expose of a name fails and its series would flatline).
void* tbrpc_var_adder_create(const char* name);
void tbrpc_var_adder_add(void* adder, int64_t delta);
int64_t tbrpc_var_adder_value(void* adder);

// LatencyRecorder bundle: exposes {prefix}_latency/_max_latency/_qps/
// _count/_latency_50/_latency_99/_latency_999 like every native RPC leg.
void* tbrpc_var_latency_create(const char* prefix);
void tbrpc_var_latency_record(void* rec, int64_t latency_us);
// what: 0=count, 1=qps, 2=avg latency, 3=max latency; 50/90/99/999 =
// that percentile. Unknown selectors return -1.
int64_t tbrpc_var_latency_value(void* rec, int what);

// PassiveStatus gauge: cb(ctx) is evaluated at scrape/dump time (the
// busy-bytes pattern — the value is owned elsewhere). cb must stay callable
// for the process lifetime.
typedef int64_t (*tbrpc_gauge_cb)(void* ctx);
void* tbrpc_var_gauge_create(const char* name, tbrpc_gauge_cb cb, void* ctx);

// ---- observability: dumps ----
// Each writes a NUL-terminated snapshot into buf (truncated at cap) and
// returns the FULL length required excluding the NUL — if the return is
// >= cap, call again with a larger buffer. buf may be null with cap 0 to
// size a first call.
// All exposed vars as "name : value" lines; prefix ("" = all) filters.
int64_t tbrpc_vars_dump(const char* prefix, char* buf, size_t cap);
// Prometheus text format — byte-identical to the /brpc_metrics page.
int64_t tbrpc_vars_dump_prometheus(char* buf, size_t cap);
// Collected rpcz spans as a JSON array (newest first), annotations
// included; trace_id != 0 filters to one trace (oldest first).
int64_t tbrpc_rpcz_dump_json(uint64_t trace_id, char* buf, size_t cap);
// Every live fiber with its state and (for parked fibers) symbolized
// stack — the /fibers page through the capi. Callable from ANY plain
// pthread even when every fiber worker is parked (the wedge-hunting
// entry point: a Python watchdog thread can ask a stuck process what its
// fibers are waiting on).
int64_t tbrpc_debug_dump_fibers(char* buf, size_t cap);
// Sender/receiver state of every live tpu:// endpoint (TX credit level,
// pending control bytes, parked-writer flags — ttpu::DebugDumpEndpoints).
// The companion hang-forensics view to the fiber dump.
int64_t tbrpc_debug_dump_ici(char* buf, size_t cap);

// ---- observability: flight recorder + stall watchdog ----
// The always-on flight recorder (tbvar/flight_recorder.h): newest
// `max_events` (<= 0 = all retained) events across every thread ring,
// merged and time-sorted, one text line per event — the same view /flightz
// serves. Same copy-out convention as the dumps above. Callable from any
// plain pthread even when every fiber worker is parked.
int64_t tbrpc_flight_snapshot(int64_t max_events, char* buf, size_t cap);
// Events ever recorded process-wide (the rpc_flight_events gauge).
int64_t tbrpc_flight_total_events(void);

// Start the stall-watchdog pthread (idempotent; 0 ok). `dump_dir` receives
// the stall auto-dumps (fibers + ICI credit state + flight tail); null or
// "" keeps the health state machine but skips dumping. Configure via
// tbrpc_flag_set: watchdog_poll_ms / watchdog_degraded_ms /
// watchdog_stalled_ms / watchdog_credit_stall_ms / watchdog_autodump,
// plus flight_recorder_enabled / flight_recorder_ring_events.
int tbrpc_watchdog_start(const char* dump_dir);
// Stop and join the watchdog pthread (tests; restartable). Always 0.
int tbrpc_watchdog_stop(void);
// Current health state: 0 ok, 1 degraded, 2 stalled (rpc_health_state).
int tbrpc_health_state(void);
// The /healthz JSON body: state, reason, transition history, stall count,
// last auto-dump path. Copy-out convention.
int64_t tbrpc_health_dump_json(char* buf, size_t cap);
// Absolute path of the newest stall auto-dump ("" before the first one).
int64_t tbrpc_health_last_dump_path(char* buf, size_t cap);

// TEST-ONLY stall injection: start `nfibers` fibers (<= 0: one per worker)
// that each BLOCK their worker pthread on a private futex until
// tbrpc_debug_release_workers or `hold_ms` elapses — from the scheduler's
// point of view every worker is wedged, which is exactly what the watchdog
// must detect. Returns the number of holder fibers started.
int tbrpc_debug_hold_workers(int nfibers, int64_t hold_ms);
void tbrpc_debug_release_workers(void);

// TEST-ONLY contention generator: run `nfibers` fibers hammering one
// FiberMutex (a short sleep inside the critical section) for ~`ms`,
// BLOCKING the calling pthread until they finish. Guarantees the
// /contention profiler has waits to sample inside a profile window.
// Returns total acquisitions.
int64_t tbrpc_debug_induce_contention(int nfibers, int64_t ms);

// ---- observability: tracing ----
// The fiber-local trace context the native stack propagates (span.h):
// reading/writing it from Python lets the tensor path join native traces.
// On a plain (non-fiber) thread the context rides a thread-local slot, so
// a Python client thread can carry a root span across its calls too.
int tbrpc_rpcz_enabled(void);
void tbrpc_rpcz_set_enabled(int on);
// Head-sampling gate for a Python-created ROOT span (trace_span with no
// surrounding context): 1 = collect this root. Combines rpcz_enabled with
// the reloadable rpcz_sample_1_in_n flag (1 = every trace; N = 1-in-N on
// average), the same gate the native client/server protocols consult, so
// production keeps rpcz live at bounded cost. Spans inside an already
// sampled trace must NOT re-consult this — a sampled trace stays complete.
int tbrpc_rpcz_sample_root(void);
// Current rpcz_sample_1_in_n value (>= 1; set via tbrpc_flag_set or
// /flags/rpcz_sample_1_in_n?setvalue=N).
int tbrpc_rpcz_sample_1_in_n(void);
uint64_t tbrpc_trace_new_id(void);
void tbrpc_trace_current(uint64_t* trace_id, uint64_t* span_id);
void tbrpc_trace_set(uint64_t trace_id, uint64_t span_id);
void tbrpc_trace_clear(void);
// Attach "key=value" stage text to the ACTIVE span (the current trace
// context's span — a server handler annotates its server span; a Python
// trace_span() annotates itself). No-op when no span is active.
void tbrpc_span_annotate(const char* text);
// Record an externally-timed span (Python-created spans: trace_span()
// times the body and emits here). No-op when span_id == 0 or rpcz is off.
void tbrpc_span_emit(uint64_t trace_id, uint64_t span_id,
                     uint64_t parent_span_id, int server_side,
                     int64_t start_us, int64_t end_us, int error_code,
                     const char* name);
// Wall-clock microseconds on the same clock spans use (gettimeofday).
int64_t tbrpc_now_us(void);
// Address of the rpcz_enabled flag's int64 storage, stable for the
// process: a caller that decides "is rpcz on" on every call reads it in
// place instead of calling tbrpc_rpcz_enabled.
const void* tbrpc_rpcz_enabled_flag(void);
// This thread's stamp (tbrpc_now_us clock) of the last traced crossing of
// the ctypes boundary, 0 when that request or call was not traced:
// - on a callback-pool pthread: when it called the Python callback of a
//   request with a server span (the callback's first line times its GIL
//   wait from it; the pool wait before it is annotated as pool_wait);
// - on a caller's thread: when tbrpc_call_tensor returned to a caller
//   with an active trace context.
int64_t tbrpc_callback_called_us(void);
int64_t tbrpc_call_returned_us(void);

// Reloadable-flag access (the /flags page, from code): 0 ok, -1 on unknown
// flag / parse error / validator veto.
int tbrpc_flag_set(const char* name, const char* value);

// ---- overload protection: priority lanes, tenant quotas, deadlines ----
// Ambient QoS context (trpc/qos.h): a fiber-local (thread-local off-fiber)
// slot — the same discipline as the trace context — read by every
// Channel::CallMethod on this thread. priority: 0 HIGH, 1 NORMAL (the
// unmarked default), 2 BULK; tenant (may be null/"") keys the server's
// per-tenant quota gate. Stamped requests carry both in the tstd meta
// behind a flag bit; an unmarked request's wire is byte-identical to the
// pre-QoS format. Always 0.
// tenant is capped at 256 bytes (-1 when longer — tenant ids are short
// labels, and the wire field is length-prefixed).
int tbrpc_qos_set(int priority, const char* tenant);
void tbrpc_qos_clear(void);
// Read the slot back (the qos() scope-nesting restore in the Python
// bindings reads the REAL ambient values — including those a server
// handler scope installed — instead of a Python-side shadow). *priority
// gets the current lane; the tenant copies out (copy-out convention).
int64_t tbrpc_qos_get(int* priority, char* tenant_buf, size_t cap);
// Remaining budget of the request the CURRENT thread is handling (set by
// the server around every handler, including the Python callback-pool
// threads): milliseconds left, 0 when expired, -1 when no deadline is in
// scope. Nested RPCs clamp to this automatically; handlers use it to shed
// doomed work early.
int64_t tbrpc_deadline_remaining_ms(void);
// Concurrency gate for the server (0 = unlimited). Pre-start only (the
// limiter is built at Start): -1 once the server is running.
int tbrpc_server_set_max_concurrency(void* server, int32_t max);
// Per-tenant in-flight quota layered UNDER the global gate (0 = off):
// each tenant (QoS meta tenant, falling back to the peer ip) sheds its
// own overflow with TRPC_ELIMIT + a retry_after_ms hint before it can
// crowd out other tenants. Runtime-safe. 0 ok.
int tbrpc_server_set_tenant_quota(void* server, int32_t max_inflight);
// The /tenantz document for one server: {"quota":N,"tenants":[{name,
// admitted,shed,inflight,quota}...]}. Copy-out convention.
int64_t tbrpc_server_tenantz_json(void* server, char* buf, size_t cap);

// TEST-ONLY fault injection beside tbrpc_debug_hold_workers: every
// ADMITTED tstd request to `service` parks its dispatch fiber for `ms`
// while holding its gate slot — a slow handler's exact footprint, so
// overload/shed tests create deterministic queueing without
// host-steal-sensitive busy loops. ms <= 0 clears; empty/null service
// clears every injection. Always 0.
int tbrpc_debug_inject_latency(const char* service, int64_t ms);

// ---- quantized tensor wire: codec registry + accounting ----
// The tensor-codec negotiation seam (trpc/compress.h — the registry that
// sits beside gzip/snappy): ids/names are the per-call currency of the
// quantized tensor wire format (block-wise int8 / fp8-e4m3 with
// per-block fp32 scales; encode/decode math lives in
// brpc_tpu/runtime/codec.py). Codec id for a name ("raw"/"" = 0), or -1
// when unknown to this build — the mixed-fleet degrade probe.
int tbrpc_tensor_codec_id(const char* name);
// CSV of registered codec names (the capability advertisement servers
// put in Meta). Copy-out convention (see the dump section above).
int64_t tbrpc_tensor_codec_list(char* buf, size_t cap);
// Per-tensor wire accounting from either end of a quantized transfer:
// bumps the process-wide tensor_codec_bytes_logical /
// tensor_codec_bytes_wire adders (and the tensor_codec_ratio gauge) on
// /vars + /brpc_metrics, and the bounded per-tensor table /tensorz
// renders (last codec + cumulative logical/wire + compression ratio).
void tbrpc_tensor_codec_note(const char* tensor, int codec_id,
                             uint64_t logical_bytes, uint64_t wire_bytes);
// {"bytes_logical":N,"bytes_wire":N,"tensors":[{name,codec,logical,
// wire,count}...]} — the accounting table as JSON. Copy-out convention.
int64_t tbrpc_tensor_codec_stats_json(char* buf, size_t cap);

// ---- one-sided tensor reads: published arena windows (ttpu/oneside.h) --
// Memory-semantics pulls beside the RPC plane: a server PUBLISHES
// committed tensor versions into seqlock-stamped slots of its
// TensorArena, and a same-host client that mapped the window READS them
// directly — no request frame, no handler dispatch, no response frame.
// The seqlock protects the descriptor (torn snapshots retry); epoch-based
// reclamation protects the payload bytes (a republish retires the old
// range and frees it only once no mapped reader can still be traversing
// it). Any non-OK read means "use the two-sided Pull RPC" — fallback is
// the contract, off-host or when the window is gone.
//
// Publisher: create a window inside a tbrpc_arena (returns a handle;
// null on failure). The directory consumes arena space.
void* tbrpc_oneside_window_create(void* arena, int32_t n_slots,
                                  int32_t n_readers);
void tbrpc_oneside_window_destroy(void* win);
// Publish `name` -> the payload the caller already wrote at [off,
// off+len) in the window's arena. take_ownership != 0 hands the range to
// the window (the PREVIOUS range published under `name` retires and
// returns to the arena allocator once reclaimable; the caller must not
// free either range); 0 publishes in place without ever freeing (serving
// KV pages — the session owns its plane). 0 ok, -1 on a bad name/range
// or a full directory.
int tbrpc_oneside_publish(void* win, const char* name, uint64_t off,
                          uint64_t len, uint64_t version,
                          int take_ownership);
// Write-lock `name`'s slot so readers retry while the caller rewrites
// the payload in place (the not-owned mode); the next publish commits.
void tbrpc_oneside_begin_rewrite(void* win, const char* name);
int tbrpc_oneside_unpublish(void* win, const char* name);
// The mapping-handshake descriptor, served to clients over any ordinary
// RPC: {"shm","bytes","dir_off","token","pid",...}. Copy-out convention.
int64_t tbrpc_oneside_window_describe(void* win, char* buf, size_t cap);
//
// Reader: map a published window from its descriptor. Returns a reader
// handle, or null when the shm name cannot be mapped (off-host, server
// gone), the token mismatches, or the window's reader table is full —
// every null means "stay on the RPC path".
void* tbrpc_oneside_map(const char* shm_name, uint64_t bytes,
                        uint64_t dir_off, uint64_t token);
// Copy out the committed payload under `name`: 0 ok (*data tbrpc_alloc-
// compatible, caller frees with tbrpc_free; *len/*version filled), 1 not
// published, 2 torn (descriptor stayed write-locked past the retry
// budget — transient), 3 gone (window destroyed: unmap and stop trying).
int tbrpc_oneside_read(void* reader, const char* name, void** data,
                       uint64_t* len, uint64_t* version);
// Descriptor-only probe (size + version, no payload touch): what a
// caller allocates from before tbrpc_oneside_read_into.
int tbrpc_oneside_stat(void* reader, const char* name, uint64_t* len,
                       uint64_t* version);
// Copy the committed payload into CALLER memory (`cap` bytes at `buf`)
// — the large-tensor hot path: exactly one memcpy into a buffer whose
// alignment and lifetime the caller controls. Statuses as read, plus
// 4 = buffer too small (*len = needed size; reallocate and retry — the
// payload was republished bigger between stat and read).
int tbrpc_oneside_read_into(void* reader, const char* name, void* buf,
                            uint64_t cap, uint64_t* len, uint64_t* version);
int tbrpc_oneside_unmap(void* reader);
// Process-wide counters + per-window reclamation state as JSON
// ({"publishes","reads","read_retries","reads_torn","reclaims",
// "reader_evictions","windows":[...]}). Copy-out convention.
int64_t tbrpc_oneside_stats_json(char* buf, size_t cap);

// ---- fleet: service registry (trpc/registry.h) ----
// Install the in-process service registry: after this, EVERY server in the
// process answers /registry/register, /registry/deregister and
// /registry/list (watch mode via ?index=N&wait_ms=M) on its builtin HTTP
// port — any server can BE the fleet's registry. The table is
// process-global and entries expire ttl_s after their last heartbeat.
// Idempotent; returns 0.
int tbrpc_registry_install(void);
// Drop every registry entry (test isolation between fleets sharing one
// process — the table is process-global). Returns 0.
int tbrpc_registry_clear(void);

// ---- streaming RPC: token streams over the native Stream (trpc/stream.h) --
// The serving plane's transport: an ordered, credit-flow-controlled,
// full-duplex message stream established by an RPC and multiplexed on its
// connection (tcp AND tpu://). The capi surface runs every stream in
// MANUAL-consumption mode: received messages queue in a native read buffer
// and the flow-control feedback advances only when tbrpc_stream_read
// drains them — so a slow Python reader exhausts ITS OWN peer window
// (that stream's writers park/EAGAIN) without buffering unboundedly or
// stalling any other stream.
//
// Server: call from INSIDE a Python service handler (callback-pool
// thread), before returning — the response carries the acceptance.
// Returns the stream id (> 0), or -1 when no handler RPC is in scope /
// the client didn't attach a stream. max_buf_size <= 0 uses the default
// 2MB receive window.
int64_t tbrpc_stream_accept(int64_t max_buf_size);
// Client: open `service_method` with a stream attached; blocks for the
// RPC like tbrpc_call. On success returns the CONNECTED stream id (> 0)
// and hands out the RPC response body (*resp tbrpc_alloc'd, caller frees
// via tbrpc_free). On failure returns the negated RPC error code and
// fills errbuf; no stream is left behind.
int64_t tbrpc_stream_create(void* channel, const char* service_method,
                            const void* req, size_t req_len,
                            int64_t max_buf_size, void** resp,
                            size_t* resp_len, char* errbuf,
                            size_t errbuf_len);
// Ordered write of one message. timeout_ms < 0 blocks the calling thread
// until the peer's window opens (credit backpressure), 0 probes, > 0
// bounds the wait. Returns 0, EAGAIN when the window stayed exhausted for
// the whole bound, EINVAL on an unknown/closed id, or the close/socket
// error once the stream died.
int tbrpc_stream_write(uint64_t stream_id, const void* data, size_t len,
                       int64_t timeout_ms);
// Pop the next message: 0 = delivered (*data tbrpc_alloc'd, caller frees;
// consumption feedback advances by its size), 1 = clean EOF (peer closed
// and the queue is drained), -1 = timeout, -2 = unknown stream id, any
// other positive value = the error the stream closed with (after the
// queue drained). timeout_ms < 0 waits forever.
int tbrpc_stream_read(uint64_t stream_id, int64_t timeout_ms, void** data,
                      size_t* len);
// Close the local half (peer's on_closed fires), wait for the close to
// complete, release the read buffer. error_code > 0 rides the CLOSE
// control frame — which bypasses the data credit window — so the peer's
// reads observe the code after draining instead of a clean EOF (how a
// shed session stays distinguishable from a completed one even when the
// reader's window is full). 0 = clean EOF. Idempotent per id; 0 always.
int tbrpc_stream_close(uint64_t stream_id, int error_code);

// ---- serving observability: the /sessionz console page ----
// The session table lives in Python (brpc_tpu/serving); the console
// renders whatever the registered provider reports. cb fills the
// /sessionz JSON document into (buf, cap) with the dump copy-out
// convention and runs on a callback-pool pthread (GIL discipline), the
// page's fiber blocking — not parking — meanwhile (the PassiveStatus
// gauge pattern). cb null clears the provider. Registers the /sessionz
// page on first use; 0 ok.
typedef int64_t (*tbrpc_sessionz_cb)(void* ctx, char* buf, size_t cap);
int tbrpc_sessionz_set_provider(tbrpc_sessionz_cb cb, void* ctx);

// ---- HTTP streaming fallback (ProgressiveAttachment over the console) ----
// Register a Python-backed HTTP handler at `path` on every server's
// builtin HTTP port whose responses MAY stream: the callback receives a
// pre-allocated progressive id; setting *use_progressive=1 turns the
// response into an unbounded chunked body the handler keeps feeding via
// tbrpc_progressive_write until tbrpc_progressive_close — so plain-HTTP
// clients (curl) consume token streams without speaking tstd. The id is
// LIVE (writes buffer) from before the callback runs, so an engine thread
// may start emitting the moment the session is registered. *body/
// *body_len (tbrpc_alloc'd) is the plain — or first — chunk; *status the
// HTTP status. Returns 0, -1 when the path is taken.
typedef void (*tbrpc_http_stream_cb)(void* ctx, const char* path,
                                     const char* query,
                                     uint64_t progressive_id, void** body,
                                     size_t* body_len, int* use_progressive,
                                     int* status);
int tbrpc_http_stream_register(const char* path, tbrpc_http_stream_cb cb,
                               void* ctx);
// 0 on success; -1 once the peer is gone / the id was closed or unused.
int tbrpc_progressive_write(uint64_t progressive_id, const void* data,
                            size_t len);
int tbrpc_progressive_close(uint64_t progressive_id);

// ---- echo load loop (in C so Python overhead is out of the path) ----
// Small-payload (4 B) loopback echo over TCP for ~`seconds` with
// `concurrency` fiber callers: returns calls/sec; if p99_us_out is
// non-null, stores the p99 latency in microseconds.
double tbrpc_bench_echo_qps(int seconds, int concurrency, double* p99_us_out);

}  // extern "C"
