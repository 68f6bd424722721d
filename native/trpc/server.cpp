#include "trpc/server.h"

#include "trpc/errno.h"

#include <netinet/in.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstring>

#include "tbutil/json.h"
#include "tbutil/logging.h"
#include "tbutil/time.h"
#include "trpc/rpc_metrics.h"
#include "trpc/tstd_protocol.h"

namespace trpc {

// Percentage of the active concurrency gate RESERVED for HIGH/NORMAL
// traffic: BULK requests are admitted only while that many slots stay
// free, so a saturating tensor client can never occupy the last slots a
// heartbeat or version poll needs.
constexpr int64_t kBulkHeadroomPct = 10;

Server::~Server() {
  Stop();
  if (_stop_butex != nullptr) {
    tbthread::butex_destroy(_stop_butex);
  }
  if (_drain_butex != nullptr) {
    tbthread::butex_destroy(_drain_butex);
  }
  // Stop() drained every in-flight request, so no Admission still points
  // at a tenant entry.
  for (auto& [name, t] : _tenants) {
    (void)name;
    delete t;
  }
}

void Server::EndRequest(int64_t latency_us) {
  if (_limiter != nullptr && latency_us >= 0) {
    _limiter->OnRequestEnd(latency_us);
  }
  if (_concurrency.fetch_sub(1, std::memory_order_release) == 1 &&
      _drain_butex != nullptr) {
    tbthread::butex_increment_and_wake_all(_drain_butex);
  }
}

void Server::EndRequest(int64_t latency_us, const Admission& admit) {
  if (latency_us >= 0) {
    // Lossy racy EMA (alpha 1/8) of admitted-request latency: the
    // retry-after source. Precision is irrelevant next to the question
    // "roughly how long until a slot frees".
    const int64_t cur = _ema_latency_us.load(std::memory_order_relaxed);
    _ema_latency_us.store(
        cur == 0 ? latency_us : cur + (latency_us - cur) / 8,
        std::memory_order_relaxed);
    if (admit.priority == PRIORITY_HIGH) {
      GlobalRpcMetrics::instance().server_high_latency << latency_us;
    } else if (admit.priority == PRIORITY_BULK) {
      GlobalRpcMetrics::instance().server_bulk_latency << latency_us;
    }
  }
  if (admit.tenant != nullptr) {
    admit.tenant->End();
  }
  EndRequest(latency_us);
}

TenantStats* Server::TenantEntry(std::string_view tenant) {
  const int32_t quota = _tenant_quota.load(std::memory_order_relaxed);
  std::lock_guard<std::mutex> lk(_tenant_mu);  // tpulint: allow(fiber-blocking)
  auto it = _tenants.find(tenant);
  if (it == _tenants.end()) {
    // Tenant ids arrive off the wire: cap the table (the GetMethodStatus
    // discipline) so a client cycling fresh tenant strings can't grow
    // immortal entries without bound — overflow tenants share one
    // aggregate bucket, still quota-gated and visible on /tenantz.
    constexpr size_t kMaxTenants = 1024;
    if (_tenants.size() >= kMaxTenants) {
      it = _tenants.find(std::string_view("(overflow)"));
      if (it == _tenants.end()) {
        auto* of = new TenantStats;
        of->name = "(overflow)";
        it = _tenants.emplace(of->name, of).first;
      }
    } else {
      auto* t = new TenantStats;
      t->name = std::string(tenant);
      it = _tenants.emplace(t->name, t).first;
    }
  }
  // Propagate a live quota change as a plain atomic store: the entry's
  // gate is an inflight/quota atomic pair (server.h), so there is no
  // limiter object to swap under lock-free readers — the next admission
  // simply reads the new bound.
  it->second->quota.store(quota, std::memory_order_relaxed);
  return it->second;
}

void Server::set_tenant_quota(int32_t max_inflight) {
  _tenant_quota.store(max_inflight < 0 ? 0 : max_inflight,
                      std::memory_order_relaxed);
}

bool Server::BeginRequest() {
  // Legacy single-lane path (HTTP/h2): the pre-QoS behavior exactly — no
  // tenant accounting (its matching EndRequest(latency) overload releases
  // no tenant gate) and no lane reservation.
  _concurrency.fetch_add(1, std::memory_order_acquire);
  if (_limiter != nullptr && !_limiter->OnRequestBegin()) {
    EndRequest(-1);
    return false;
  }
  return true;
}

bool Server::BeginRequest(const RequestQos& qos,
                          const tbutil::EndPoint& peer, Admission* admit) {
  auto& gm = GlobalRpcMetrics::instance();
  admit->priority = clamp_priority(qos.priority);
  const int32_t inflight_now =
      _concurrency.fetch_add(1, std::memory_order_acquire) + 1;

  auto shed = [&](int error, std::string text) {
    admit->error = error;
    admit->text = std::move(text);
    admit->text += " (retry_after_ms=" +
                   std::to_string(ComputeRetryAfterMs(inflight_now)) + ")";
    gm.shed_total << 1;
    EndRequest(-1, *admit);
    admit->tenant = nullptr;
    return false;
  };

  // 1. Dead on arrival: the budget the client propagated is already gone —
  // answering TRPC_ERPCTIMEDOUT here costs nothing downstream.
  if (qos.deadline_us > 0 &&
      tbutil::gettimeofday_us() >= qos.deadline_us) {
    gm.shed_deadline << 1;
    return shed(TRPC_ERPCTIMEDOUT,
                "propagated deadline already expired; shed at admission");
  }

  // 2. Per-tenant quota: a greedy tenant sheds BEFORE it reaches the
  // shared gate, so it cannot crowd the others out of it.
  if (_tenant_quota.load(std::memory_order_relaxed) > 0) {
    std::string peer_key;
    std::string_view tname = qos.tenant;
    if (tname.empty()) {
      // Fall back to peer identity — the ip, not ip:port, so one client
      // host is one tenant regardless of connection churn.
      peer_key = tbutil::endpoint2str(peer);
      const size_t colon = peer_key.rfind(':');
      if (colon != std::string::npos) peer_key.resize(colon);
      tname = peer_key;
    }
    TenantStats* t = TenantEntry(tname);
    if (!t->TryBegin()) {
      gm.shed_tenant << 1;
      return shed(TRPC_ELIMIT, "tenant '" + t->name + "' over quota");
    }
    admit->tenant = t;
  }

  // 3. Priority lanes: BULK is admitted only while the gate keeps
  // headroom free for the control plane.
  if (admit->priority == PRIORITY_BULK && _limiter != nullptr) {
    const int32_t limit = _limiter->max_concurrency();
    if (limit > 0) {
      const int32_t headroom = std::max<int32_t>(
          1, static_cast<int32_t>(limit * kBulkHeadroomPct / 100));
      if (inflight_now > limit - headroom) {
        gm.shed_bulk << 1;
        return shed(TRPC_ELIMIT, "bulk lane shed: gate headroom reserved "
                                 "for control-plane traffic");
      }
    }
  }

  // 4. The configured limiter (constant / auto / timeout) has the last
  // word for every lane.
  if (_limiter != nullptr && !_limiter->OnRequestBegin()) {
    return shed(TRPC_ELIMIT, "server concurrency limit reached");
  }
  return true;
}

int64_t Server::ComputeRetryAfterMs(int32_t inflight_now) const {
  // Roughly how long until a slot frees at the observed EMA latency,
  // scaled by how oversubscribed the gate is. Clamped so a cold EMA
  // still paces (>= 1ms) and a pathological spike can't tell clients to
  // sleep forever.
  const int64_t ema = _ema_latency_us.load(std::memory_order_relaxed);
  if (ema <= 0) return 1;
  const int32_t limit =
      _limiter != nullptr ? _limiter->max_concurrency() : 0;
  const int64_t factor =
      limit > 0 ? std::max<int64_t>(1, inflight_now / limit) : 1;
  return std::clamp<int64_t>(ema * factor / 1000, 1, 2000);
}

void Server::TenantzJson(std::string* out) const {
  tbutil::JsonValue doc = tbutil::JsonValue::Object();
  doc.set("quota",
          static_cast<int64_t>(_tenant_quota.load(std::memory_order_relaxed)));
  tbutil::JsonValue arr = tbutil::JsonValue::Array();
  {
    std::lock_guard<std::mutex> lk(_tenant_mu);  // tpulint: allow(fiber-blocking)
    for (const auto& [name, t] : _tenants) {
      tbutil::JsonValue o = tbutil::JsonValue::Object();
      o.set("name", name);
      o.set("admitted", t->admitted.load(std::memory_order_relaxed));
      o.set("shed", t->shed.load(std::memory_order_relaxed));
      o.set("inflight", t->inflight.load(std::memory_order_relaxed));
      o.set("quota", static_cast<int64_t>(
                         t->quota.load(std::memory_order_relaxed)));
      arr.push_back(std::move(o));
    }
  }
  doc.set("tenants", std::move(arr));
  *out = doc.Dump();
}

int32_t Server::current_max_concurrency() const {
  return _limiter != nullptr ? _limiter->max_concurrency() : 0;
}

// ---------------- test-only latency injection ----------------

namespace {

struct InjectedLatency {
  std::mutex mu;  // tpulint: allow(fiber-blocking) — O(1) map ops
  std::map<std::string, int64_t> by_service;
  std::atomic<int64_t> active{0};  // fast-path gate: 0 == nothing injected
};

InjectedLatency& injected_latency() {
  static InjectedLatency* p = new InjectedLatency;
  return *p;
}

}  // namespace

void SetDebugInjectedLatency(const std::string& service, int64_t ms) {
  InjectedLatency& inj = injected_latency();
  std::lock_guard<std::mutex> lk(inj.mu);  // tpulint: allow(fiber-blocking)
  if (service.empty()) {
    inj.by_service.clear();
  } else if (ms <= 0) {
    inj.by_service.erase(service);
  } else {
    inj.by_service[service] = ms;
  }
  inj.active.store(static_cast<int64_t>(inj.by_service.size()),
                   std::memory_order_release);
}

int64_t DebugInjectedLatencyMs(const std::string& service) {
  InjectedLatency& inj = injected_latency();
  // One relaxed load on the hot path while the hook is unused.
  if (inj.active.load(std::memory_order_acquire) == 0) return 0;
  std::lock_guard<std::mutex> lk(inj.mu);  // tpulint: allow(fiber-blocking)
  auto it = inj.by_service.find(service);
  return it != inj.by_service.end() ? it->second : 0;
}

namespace {

// Builtin gRPC health responder: standard probes (k8s, grpcurl, cloud
// LBs) call /grpc.health.v1.Health/Check and expect a protobuf
// HealthCheckResponse{status: SERVING} — on the wire exactly the two
// bytes 0x08 0x01 (field 1, varint 1), so no protobuf dependency is
// needed. Watch (server-streaming) answers UNIMPLEMENTED via ENOMETHOD.
// The reference serves gRPC health through its builtin health service
// family; ours registers automatically unless the app supplied its own.
class GrpcHealthService;
GrpcHealthService* builtin_grpc_health();

class GrpcHealthService : public Service {
 public:
  std::string_view service_name() const override {
    return "grpc.health.v1.Health";
  }
  void CallMethod(const std::string& method, Controller* cntl,
                  const tbutil::IOBuf& request, tbutil::IOBuf* response,
                  Closure* done) override {
    (void)request;  // any service name shares the server-wide answer
    if (method == "Check") {
      // SERVING only while the owning server is actually running: during
      // Stop/drain probes must see NOT_SERVING (0x08 0x02) so LBs pull
      // the instance before its listener vanishes (ADVICE r4).
      bool serving = true;
      SocketUniquePtr s;
      if (Socket::Address(ControllerPrivateAccessor(cntl).server_socket(),
                          &s) == 0 &&
          s->user() != nullptr) {
        serving = static_cast<Server*>(s->user())->running();
      }
      response->append(serving ? "\x08\x01" : "\x08\x02", 2);
    } else {
      cntl->SetFailed(TRPC_ENOMETHOD, "unimplemented: " + method);
    }
    done->Run();
  }
};

GrpcHealthService* builtin_grpc_health() {
  static GrpcHealthService* health = new GrpcHealthService;  // immortal
  return health;
}

}  // namespace

int Server::AddService(Service* service) {
  if (service == nullptr) return -1;
  if (_running.load(std::memory_order_acquire)) {
    TB_LOG(ERROR) << "AddService after Start";
    return -1;
  }
  std::string name(service->service_name());
  Service** existing = _services.seek(name);
  if (existing != nullptr && *existing == builtin_grpc_health()) {
    *existing = service;  // a user health service replaces the builtin
    return 0;
  }
  if (existing != nullptr) {
    TB_LOG(ERROR) << "duplicate service: " << name;
    return -1;
  }
  _services.insert(std::move(name), service);
  return 0;
}

int Server::Start(int port, const ServerOptions* options) {
  char addr[32];
  snprintf(addr, sizeof(addr), "0.0.0.0:%d", port);
  return Start(addr, options);
}

int Server::Start(const char* addr, const ServerOptions* options) {
  if (_running.load(std::memory_order_acquire)) return -1;
  GlobalInitializeOrDie();
  if (options != nullptr) _options = *options;
  if (_options.tenant_max_concurrency > 0) {
    set_tenant_quota(_options.tenant_max_concurrency);
  }
  if (_options.enable_grpc_health &&
      _services.seek(std::string("grpc.health.v1.Health")) == nullptr) {
    AddService(builtin_grpc_health());
  }
  if (_options.timeout_concurrency_ms > 0) {
    _limiter = NewTimeoutLimiter(_options.timeout_concurrency_ms * 1000);
  } else if (_options.auto_concurrency) {
    _limiter = NewAutoLimiter();
  } else {
    _limiter = NewConstantLimiter(_options.max_concurrency);
  }
  if (!_options.rpc_dump_path.empty()) {
    _dumper.reset(RpcDumper::Open(_options.rpc_dump_path));
  }
  if (!_options.ssl_cert_file.empty() || !_options.ssl_key_file.empty()) {
    SslServerOptions sopts;
    sopts.cert_file = _options.ssl_cert_file;
    sopts.key_file = _options.ssl_key_file;
    sopts.alpn = {"h2", "http/1.1"};  // gRPC-over-TLS negotiates h2
    auto ctx = SslContext::NewServer(sopts);
    if (ctx == nullptr) {
      TB_LOG(ERROR) << "TLS configuration failed; refusing to start";
      return -1;
    }
    _acceptor.set_ssl_ctx(std::move(ctx));
  } else {
    // Restart without TLS options must not keep a previous run's ctx (and
    // its possibly rotated-out cert) alive on the acceptor.
    _acceptor.set_ssl_ctx(nullptr);
  }
  if (_stop_butex == nullptr) _stop_butex = tbthread::butex_create();
  if (_drain_butex == nullptr) _drain_butex = tbthread::butex_create();

  tbutil::EndPoint pt;
  if (tbutil::str2endpoint(addr, &pt) != 0) {
    TB_LOG(ERROR) << "bad listen address: " << addr;
    return -1;
  }
  int fd = socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in sin{};
  sin.sin_family = AF_INET;
  sin.sin_addr = pt.ip;
  sin.sin_port = htons(static_cast<uint16_t>(pt.port));
  if (bind(fd, reinterpret_cast<sockaddr*>(&sin), sizeof(sin)) != 0 ||
      listen(fd, 1024) != 0) {
    TB_LOG(ERROR) << "bind/listen " << addr << " failed: " << strerror(errno);
    close(fd);
    return -1;
  }
  socklen_t len = sizeof(sin);
  getsockname(fd, reinterpret_cast<sockaddr*>(&sin), &len);
  _listen_address = tbutil::EndPoint(sin.sin_addr, ntohs(sin.sin_port));

  if (_acceptor.StartAccept(fd, this) != 0) {
    close(fd);
    return -1;
  }
  _start_time_us = tbutil::gettimeofday_us();
  _running.store(true, std::memory_order_release);
  TB_LOG(INFO) << "server started on "
               << tbutil::endpoint2str(_listen_address);
  return 0;
}

void Server::ListServices(std::vector<std::string>* out) const {
  out->clear();
  for (const auto& [name, svc] : _services) {
    (void)svc;
    out->push_back(name);
  }
}

int Server::Stop() {
  if (!_running.exchange(false, std::memory_order_acq_rel)) return -1;
  _acceptor.StopAccept();
  // Drain: in-flight handlers may park well past their connection's death;
  // their done closures call EndRequest() on this Server, so it must not be
  // destroyed under them. (Do not call Stop from inside a handler.)
  while (_concurrency.load(std::memory_order_acquire) > 0) {
    const int v =
        tbthread::butex_value(_drain_butex)->load(std::memory_order_acquire);
    if (_concurrency.load(std::memory_order_acquire) == 0) break;
    tbthread::butex_wait(_drain_butex, v, nullptr);
  }
  tbthread::butex_increment_and_wake_all(_stop_butex);
  return 0;
}

int Server::Join() {
  if (_stop_butex == nullptr) return -1;
  while (_running.load(std::memory_order_acquire)) {
    const int v =
        tbthread::butex_value(_stop_butex)->load(std::memory_order_acquire);
    if (!_running.load(std::memory_order_acquire)) break;
    tbthread::butex_wait(_stop_butex, v, nullptr);
  }
  return 0;
}

Service* Server::FindService(std::string_view name) const {
  Service* const* p = _services.seek(std::string(name));
  return p != nullptr ? *p : nullptr;
}

}  // namespace trpc
