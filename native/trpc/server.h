// Server: service registry + listener + request dispatch.
// Capability parity: reference src/brpc/server.h:62-488 (AddService with
// method maps, Start/Stop/Join, ServerOptions.max_concurrency gate,
// session-local data via user services) and the canonical request path
// policy/baidu_rpc_protocol.cpp:565 ProcessRpcRequest (concurrency gate ->
// find method -> CallMethod(done=SendResponse)).
#pragma once

#include <atomic>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <string_view>
#include <vector>

#include "tbutil/endpoint.h"
#include "tbutil/flat_map.h"
#include "tbutil/iobuf.h"
#include "trpc/acceptor.h"
#include "trpc/closure.h"
#include "trpc/concurrency_limiter.h"
#include "trpc/controller.h"
#include "trpc/qos.h"
#include "trpc/rpc_dump.h"

namespace trpc {

// A service handles named methods on serialized payloads. The native core
// is payload-agnostic (IOBuf in/out); typed layers (pb, json, tensors) wrap
// this in the bindings.
class Service {
 public:
  virtual ~Service() = default;
  virtual std::string_view service_name() const = 0;
  // Fill *response / cntl fields, then call done->Run() exactly once
  // (possibly from another fiber later — async handlers just keep `done`).
  virtual void CallMethod(const std::string& method, Controller* cntl,
                          const tbutil::IOBuf& request,
                          tbutil::IOBuf* response, Closure* done) = 0;

  // ---- inline execution (the small-RPC fast path) ----
  // An implementation that NEVER parks the calling fiber (no nested RPCs,
  // no fiber mutex/sleep/join, no Python callback pool — its CallMethod
  // runs to done->Run() on the caller's stack) may override this to true.
  // The declaration is a liveness contract: an inline handler runs ON THE
  // INPUT FIBER, so parking it head-of-line-blocks the whole connection.
  // tpulint's `inline-handler` rule statically checks marked handler
  // bodies; Python-backed services (capi CallbackService et al.) must keep
  // the default — their handlers park the fiber on the callback pool.
  virtual bool inline_safe() const { return false; }
  // Run SMALL requests to this service right on the input fiber, skipping
  // the dispatch hop (set via capi tbrpc_server_set_inline). Refused (-1)
  // unless the implementation declares inline_safe().
  int set_allow_inline(bool on) {
    if (on && !inline_safe()) return -1;
    _allow_inline.store(on, std::memory_order_release);
    return 0;
  }
  bool allow_inline() const {
    return _allow_inline.load(std::memory_order_acquire);
  }

 private:
  // Atomic: flipped from a control thread (capi) while input fibers read
  // it per-message in tstd_parse.
  std::atomic<bool> _allow_inline{false};
};

// Pre-dispatch hook: runs after admission, before the service method.
// Reject by returning a nonzero error code (sent to the client verbatim).
// Covers the reference's Interceptor AND the Authenticator use case —
// cntl->remote_side() identifies the peer; the request bytes are available
// for credential extraction (reference server.h interceptor +
// authenticator, details/method_status pre-dispatch path).
class Interceptor {
 public:
  virtual ~Interceptor() = default;
  virtual int OnRequest(Controller* cntl, const std::string& service_method,
                        const tbutil::IOBuf& request,
                        std::string* error_text) = 0;
};

class RedisService;
class ThriftFramedService;

// Per-tenant admission bookkeeping (overload protection): one entry per
// tenant id ever seen, immortal for the server's lifetime so the hot path
// caches raw pointers. The gate is the inflight/quota atomic pair (the
// ConstantLimiter admission rule inlined) rather than a swappable limiter
// object: a live quota change is then just an atomic store consulted by
// the NEXT admission — no object replacement racing lock-free readers.
// Counters feed /tenantz and the shed-storm tests.
struct TenantStats {
  std::string name;
  std::atomic<int32_t> quota{0};  // <= 0 admits everything
  std::atomic<int64_t> admitted{0};
  std::atomic<int64_t> shed{0};
  std::atomic<int64_t> inflight{0};

  // ConstantLimiter semantics with a live-readable quota.
  bool TryBegin() {
    const int32_t q = quota.load(std::memory_order_relaxed);
    const int64_t prev = inflight.fetch_add(1, std::memory_order_acquire);
    if (q > 0 && prev >= q) {
      inflight.fetch_sub(1, std::memory_order_release);
      shed.fetch_add(1, std::memory_order_relaxed);
      return false;
    }
    admitted.fetch_add(1, std::memory_order_relaxed);
    return true;
  }
  void End() { inflight.fetch_sub(1, std::memory_order_release); }
};

// The admission decision's inputs (from the request's tstd QoS meta) and
// outputs (what EndRequest must release + the shed answer).
struct RequestQos {
  int priority = PRIORITY_NORMAL;
  std::string_view tenant;  // "" = fall back to the peer's ip
  int64_t deadline_us = 0;  // propagated absolute deadline (0 = none)
};

struct Admission {
  TenantStats* tenant = nullptr;  // counted into this gate when non-null
  int priority = PRIORITY_NORMAL;
  // Filled when BeginRequest sheds: the error code and a reason text
  // carrying the computed " (retry_after_ms=N)" hint clients pace on.
  int error = 0;
  std::string text;
};

struct ServerOptions {
  // 0 = unlimited. Requests over the cap are rejected with TRPC_ELIMIT
  // (reference ServerOptions.max_concurrency server.h:132).
  int32_t max_concurrency = 0;
  // Not owned; must outlive the server. nullptr = no interception.
  Interceptor* interceptor = nullptr;
  // Sample inbound requests (post-decompression) to this file for offline
  // replay with rpc_replay/rpc_press (reference rpc_dump.h:67; sampling
  // rate via the rpc_dump_sample_every flag). Empty = off.
  std::string rpc_dump_path;
  // Auto-register the builtin /grpc.health.v1.Health responder (standard
  // gRPC health probes). A user service with that name always wins.
  bool enable_grpc_health = true;
  // TLS (reference ServerOptions.ssl_options / ssl_helper.cpp): both set =
  // the port ALSO accepts TLS — the first byte is sniffed, so plaintext and
  // TLS clients share the listener. ALPN advertises h2 + http/1.1.
  std::string ssl_cert_file;
  std::string ssl_key_file;
  // Adaptive gate (overrides max_concurrency): a gradient limiter tracks
  // the no-load latency and sheds load when latency inflates past it
  // (reference max_concurrency = "auto",
  // policy/auto_concurrency_limiter.cpp). See concurrency_limiter.h.
  bool auto_concurrency = false;
  // Timeout-aware gate (overrides both of the above when > 0): sheds a
  // request when the queue ahead of it cannot drain within this budget at
  // the observed average latency (reference max_concurrency = "timeout",
  // policy/timeout_concurrency_limiter.cpp).
  int64_t timeout_concurrency_ms = 0;
  // Per-tenant concurrency quota layered UNDER the global gate (overload
  // protection): each tenant id (tstd QoS meta field, falling back to the
  // peer ip) gets its own constant gate of this many in-flight requests,
  // so one greedy client sheds before it crowds out others. 0 = off.
  // Runtime-adjustable via Server::set_tenant_quota / the capi.
  int32_t tenant_max_concurrency = 0;
  // Non-null = this port ALSO speaks RESP (reference
  // ServerOptions.redis_service). Not owned; must outlive the server.
  class RedisService* redis_service = nullptr;
  // Non-null = this port ALSO answers thrift framed calls (reference
  // ServerOptions.thrift_service). Not owned; must outlive the server.
  class ThriftFramedService* thrift_service = nullptr;
};

class Server {
 public:
  Server() = default;
  ~Server();

  // Not owned; must outlive the server.
  int AddService(Service* service);

  // port only ("0.0.0.0:port"); or addr "ip:port". port 0 = ephemeral.
  int Start(int port, const ServerOptions* options = nullptr);
  int Start(const char* addr, const ServerOptions* options = nullptr);
  int Stop();
  // Blocks until Stop() is called (from a signal handler or another fiber).
  int Join();

  Service* FindService(std::string_view name) const;
  void ListServices(std::vector<std::string>* out) const;
  const tbutil::EndPoint& listen_address() const { return _listen_address; }
  size_t connection_count() const { return _acceptor.connection_count(); }
  void ListConnections(std::vector<SocketId>* out) const {
    _acceptor.ListConnections(out);
  }
  bool running() const { return _running.load(std::memory_order_acquire); }
  int64_t start_time_us() const { return _start_time_us; }

  // Request-level concurrency gate. Always counts in-flight requests (not
  // only when capped): Stop() drains to zero before returning, so a done
  // closure can never touch a destroyed Server (handlers may outlive their
  // connection). Admission itself is layered (overload protection):
  //   1. a request whose propagated deadline already passed is shed
  //      (TRPC_ERPCTIMEDOUT) without consuming any gate — a defensive
  //      layer for direct callers: on the tstd path the deadline is
  //      reconstructed at dispatch from a wire budget clamped >= 1ms, so
  //      the burned-in-queue re-check AFTER dispatch delay
  //      (tstd_protocol.cpp) is the one that fires in practice;
  //   2. the per-tenant quota gate (when configured) sheds a greedy
  //      tenant's overflow BEFORE it reaches the shared gate;
  //   3. the BULK lane is admitted only while the global gate keeps
  //      10 percent of slots free (HIGH/NORMAL use the full gate), so
  //      bulk saturation can't starve the control plane;
  //   4. the configured limiter (constant/auto/timeout) has the last word.
  // On a shed, `admit->error/text` carry the answer — the text ends with
  // " (retry_after_ms=N)" computed from the server's EMA latency so
  // clients pace instead of hot-retrying.
  bool BeginRequest(const RequestQos& qos, const tbutil::EndPoint& peer,
                    Admission* admit);
  // Legacy single-lane entry (HTTP/h2 server paths): NORMAL priority, no
  // tenant, no deadline — exactly the old behavior.
  bool BeginRequest();
  // latency_us: handler wall time for admitted+finished requests; -1 from
  // the shed path (never reached the limiter's accounting). The Admission
  // overload also releases the tenant gate and feeds the per-lane
  // recorders.
  void EndRequest(int64_t latency_us);
  void EndRequest(int64_t latency_us, const Admission& admit);

  // Per-tenant quota (0 = off). Runtime-safe: the hot path reads an
  // atomic; existing tenant gates are rebuilt lazily on quota change.
  void set_tenant_quota(int32_t max_inflight);
  int32_t tenant_quota() const {
    return _tenant_quota.load(std::memory_order_relaxed);
  }
  // EMA of admitted-request latency (us): the retry-after source.
  int64_t ema_latency_us() const {
    return _ema_latency_us.load(std::memory_order_relaxed);
  }
  // The retry-after hint every shed path shares (EMA latency scaled by
  // gate oversubscription, clamped to [1, 2000] ms) — ONE home so the
  // admission sheds and the burned-in-queue deadline shed cannot drift.
  int64_t ComputeRetryAfterMs(int32_t inflight_now) const;
  // The /tenantz document: {"quota":N,"tenants":[{name,admitted,shed,
  // inflight,quota}...]} (sorted by name).
  void TenantzJson(std::string* out) const;

  // Server-side streams (StreamAccept) hold the server exactly like an
  // in-flight request: Stop() must not return while a stream's consumer
  // fiber or its handler's on_closed can still run — the handler is
  // typically user memory that dies right after Stop(). Balanced by
  // finish_close (stream.cpp).
  void AddStreamHold() {
    _concurrency.fetch_add(1, std::memory_order_acquire);
  }
  void ReleaseStreamHold() { EndRequest(-1); }
  int32_t concurrency() const {
    return _concurrency.load(std::memory_order_relaxed);
  }
  // Current admission gate (0 = unlimited); live for the auto policy.
  int32_t current_max_concurrency() const;
  Interceptor* interceptor() const { return _options.interceptor; }
  RpcDumper* dumper() const { return _dumper.get(); }
  RedisService* redis_service() const { return _options.redis_service; }
  ThriftFramedService* thrift_service() const {
    return _options.thrift_service;
  }

 private:
  TenantStats* TenantEntry(std::string_view tenant);

  tbutil::FlatMap<std::string, Service*> _services;
  ServerOptions _options;
  std::unique_ptr<ConcurrencyLimiter> _limiter;
  // Tenant table: entries immortal for the server's lifetime (hot paths
  // hold raw pointers across the request). O(1)-bounded critical sections
  // — lookup/insert only, no parking inside.
  mutable std::mutex _tenant_mu;  // tpulint: allow(fiber-blocking)
  std::map<std::string, TenantStats*, std::less<>> _tenants;
  std::atomic<int32_t> _tenant_quota{0};
  std::atomic<int64_t> _ema_latency_us{0};
  std::unique_ptr<RpcDumper> _dumper;
  Acceptor _acceptor;
  tbutil::EndPoint _listen_address;
  std::atomic<bool> _running{false};
  std::atomic<int32_t> _concurrency{0};
  int64_t _start_time_us = 0;
  tbthread::Butex* _stop_butex = nullptr;
  tbthread::Butex* _drain_butex = nullptr;  // woken when concurrency hits 0
};

// TEST-ONLY fault injection (capi tbrpc_debug_inject_latency, beside
// tbrpc_debug_hold_workers): every ADMITTED tstd request to `service`
// parks its dispatch fiber for `ms` while holding its gate slot — exactly
// the footprint of a slow handler, so overload/shed tests create
// deterministic queueing without host-steal-sensitive busy loops.
// ms <= 0 clears the injection; empty service clears all.
void SetDebugInjectedLatency(const std::string& service, int64_t ms);
int64_t DebugInjectedLatencyMs(const std::string& service);

}  // namespace trpc
