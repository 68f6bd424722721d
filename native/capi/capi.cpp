#include "capi/capi.h"

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "tbthread/fiber.h"
#include "tbthread/fiber_id.h"
#include "tbthread/sync.h"
#include "tbthread/sys_futex.h"
#include "tbthread/tracer.h"
#include "tbutil/json.h"
#include "tbutil/time.h"
#include "tbvar/flight_recorder.h"
#include "tbvar/tbvar.h"
#include "trpc/channel.h"
#include "trpc/compress.h"
#include "trpc/errno.h"
#include "trpc/flags.h"
#include "trpc/qos.h"
#include "trpc/registry.h"
#include "trpc/rpc_metrics.h"
#include "trpc/server.h"
#include "trpc/http_protocol.h"
#include "trpc/span.h"
#include "trpc/stall_watchdog.h"
#include "trpc/stream.h"
#include "trpc/tstd_protocol.h"
#include "ttpu/ici_segment.h"
#include "ttpu/oneside.h"
#include "ttpu/tensor_arena.h"

using namespace trpc;

namespace {

// Python callbacks MUST run on pthread-stable threads. ctypes pairs
// PyGILState_Ensure/Release around every callback on the CURRENT OS
// thread — but a fiber that parks mid-callback (a Python handler issuing
// a nested tbrpc_call parks on the correlation id) can resume on a
// DIFFERENT worker pthread, tearing the GIL pairing apart ("auto-releasing
// thread-state, but no thread-state for this thread" aborts). So every
// Python callback runs on a small dedicated pthread pool: the service
// fiber parks on a CountdownEvent until the callback returns, nested calls
// block the POOL thread (butex takes pthread waiters), and the fiber's
// trace context is handed across explicitly so downstream calls still
// link to the server span at /rpcz.
static auto* g_python_cb_threads = TRPC_DEFINE_FLAG(
    python_callback_threads, 8,
    "idle pthreads RETAINED for Python service callbacks; the pool grows "
    "on demand (every concurrent handler gets a thread — a hard cap would "
    "deadlock nested Python->Python in-process calls) and shrinks back");

static auto* g_python_cb_max = TRPC_DEFINE_FLAG(
    python_callback_max_threads, 256,
    "admission bound on OUTSTANDING Python callback jobs (each costs one "
    "pool pthread while it runs); beyond it new jobs fail with ELIMIT "
    "instead of minting threads without bound");

class PyCallbackPool {
 public:
  static PyCallbackPool& instance() {
    static PyCallbackPool* p = new PyCallbackPool;
    return *p;
  }

  // Run `job` on a pool pthread; the CALLING fiber parks until it returns.
  // False = admission bound hit (job not run): fail the RPC with ELIMIT.
  // `priority` (qos.h RequestPriority) is the overload-protection lane:
  // HIGH jobs jump the queue, and BULK jobs shed 1/8 of the admission
  // bound early so pool saturation by tensor traffic can never consume
  // the last threads a heartbeat handler needs.
  bool Run(std::function<void()> job, int priority = PRIORITY_NORMAL) {
    tbthread::CountdownEvent done(1);
    if (!Enqueue([&job, &done] {
          job();
          done.signal();
        }, priority)) {
      return false;
    }
    done.wait();  // fiber-aware park
    return true;
  }

  // Like Run, but the caller BLOCKS ITS WORKER PTHREAD instead of parking.
  // Required when invoked under a lock other fibers contend for (the tbvar
  // registry walk evaluating a gauge): parking would free this worker to
  // pick fibers that then block on that same lock — with every worker
  // blocked, the parked scraper can never resume (a 2-worker process
  // wedges). Blocking keeps the caller on its worker; the pool thread
  // completes independently, so progress is guaranteed.
  bool RunBlocking(std::function<void()> job) {
    std::mutex mu;
    std::condition_variable cv;
    bool finished = false;
    if (!Enqueue([&] {
          job();
          // Notify UNDER the lock: the waiter destroys cv the moment its
          // predicate-wait returns, which it cannot do before we release.
          std::lock_guard<std::mutex> lk(mu);
          finished = true;
          cv.notify_one();
        }, PRIORITY_HIGH)) {  // scrape/gauge paths are control plane
      return false;
    }
    // Deliberate pthread block (see above).
    std::unique_lock<std::mutex> lk(mu);  // tpulint: allow(fiber-blocking)
    cv.wait(lk, [&] { return finished; });
    return true;
  }

 private:
  struct Job {
    std::function<void()> fn;
  };

  bool Enqueue(std::function<void()> fn, int priority) {
    {
      // O(1) queue push; pool threads block by design (dedicated pthreads,
      // not fiber workers).
      std::lock_guard<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking)
      int64_t max_jobs = std::max<int64_t>(
          1, g_python_cb_max->load(std::memory_order_relaxed));
      if (priority == PRIORITY_BULK) {
        // BULK sheds early: at least one slot (1/8 of larger bounds)
        // stays reserved for HIGH/NORMAL handlers while bulk tensor
        // traffic saturates — the max(1,...) floor keeps the reservation
        // real for small operator-tuned bounds too.
        max_jobs = std::max<int64_t>(
            1, max_jobs - std::max<int64_t>(1, max_jobs / 8));
      }
      if (_outstanding >= max_jobs) {
        return false;  // admission bound: shed instead of minting threads
      }
      ++_outstanding;
      if (priority == PRIORITY_HIGH) {
        _queue.push_front(Job{std::move(fn)});  // jump the bulk backlog
      } else {
        _queue.push_back(Job{std::move(fn)});
      }
      // Grow whenever queued jobs outnumber idle threads: a hard spawn cap
      // (or an _idle==0 test, which two racing enqueues can both pass with
      // one idle thread) would strand a job with no thread to serve it —
      // and DEADLOCK the nested case, where every pool thread is blocked
      // inside a handler whose downstream Python-handler job sits in this
      // very queue. Thread count is bounded by the admission check above;
      // surplus threads retire in Loop() once a burst drains.
      if (_idle < static_cast<int>(_queue.size())) {
        std::thread([this] { Loop(); }).detach();
      }
    }
    _cv.notify_one();
    return true;
  }

  void Loop() {
    for (;;) {
      Job job;
      {
        // Dedicated pthread, not a fiber worker: blocking here is the
        // whole point.
        std::unique_lock<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking)
        ++_idle;
        while (_queue.empty()) {
          const auto rc = _cv.wait_for(lk, std::chrono::seconds(5));
          const int64_t keep = std::max<int64_t>(
              1, g_python_cb_threads->load(std::memory_order_relaxed));
          if (rc == std::cv_status::timeout && _queue.empty() &&
              _idle > keep) {
            --_idle;
            return;  // retire a surplus idle thread once the burst drains
          }
        }
        --_idle;
        job = std::move(_queue.front());
        _queue.pop_front();
      }
      job.fn();
      {
        std::lock_guard<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking)
        --_outstanding;
      }
    }
  }

  std::mutex _mu;  // tpulint: allow(fiber-blocking)
  std::condition_variable _cv;
  std::deque<Job> _queue;
  int _idle = 0;
  int64_t _outstanding = 0;
};

// Per-thread stamps of traced crossings of the ctypes boundary
// (tbrpc_callback_called_us, tbrpc_call_returned_us): the Python side
// subtracts them from its own first timestamp to time its GIL wait.
thread_local int64_t t_callback_called_us = 0;
thread_local int64_t t_call_returned_us = 0;

// rpcz timing of one Python callback of a request with a server span:
// pool_wait runs from the service fiber's enqueue to the pool pthread
// calling the callback; that call stamp stays on the pool thread for the
// callback's gil_enter. Nothing is stamped for an untraced request.
class CallbackTiming {
 public:
  explicit CallbackTiming(uint64_t span_id)
      : _span_id(span_id),
        _queued_us(span_id != 0 ? tbutil::gettimeofday_us() : 0) {}

  // On the pool pthread, just before the callback.
  void Calling() {
    t_callback_called_us =
        _span_id != 0 ? (_called_us = tbutil::gettimeofday_us()) : 0;
  }

  // On the service fiber once the job ran, before done->Run() records the
  // span.
  void Annotate() const {
    if (_called_us == 0) return;
    AnnotateSpan(_span_id,
                 "pool_wait=" + std::to_string(_called_us - _queued_us) + "us");
  }

 private:
  const uint64_t _span_id;
  const int64_t _queued_us;
  int64_t _called_us = 0;
};

// The Controller of the RPC a Python handler is CURRENTLY serving, on the
// callback-pool pthread running it. Lets in-handler capi entry points that
// need the Controller (tbrpc_stream_accept: the response must carry the
// stream acceptance, so it has to happen before done->Run()) work without
// widening every handler ABI. Thread-local is exactly right here: the pool
// thread runs ONE handler at a time, synchronously.
thread_local Controller* t_handler_cntl = nullptr;

struct ScopedHandlerController {
  explicit ScopedHandlerController(Controller* c) { t_handler_cntl = c; }
  ~ScopedHandlerController() { t_handler_cntl = nullptr; }
};

class NativeEchoService : public Service {
 public:
  std::string_view service_name() const override { return "EchoService"; }
  // inline_safe contract: the body below must never park the calling
  // fiber — tpulint's inline-handler rule checks the marked region.
  bool inline_safe() const override { return true; }
  // tpulint: inline-handler-begin
  void CallMethod(const std::string& method, Controller* cntl,
                  const tbutil::IOBuf& request, tbutil::IOBuf* response,
                  Closure* done) override {
    if (method == "Echo") {
      response->append(request);
      cntl->response_attachment().append(cntl->request_attachment());
    } else {
      cntl->SetFailed(TRPC_ENOMETHOD, "no such method: " + method);
    }
    done->Run();
  }
  // tpulint: inline-handler-end
};

class CallbackService : public Service {
 public:
  CallbackService(std::string name, tbrpc_handler_cb cb, void* ctx)
      : _name(std::move(name)), _cb(cb), _ctx(ctx) {}
  std::string_view service_name() const override { return _name; }
  void CallMethod(const std::string& method, Controller* cntl,
                  const tbutil::IOBuf& request, tbutil::IOBuf* response,
                  Closure* done) override {
    const std::string req = request.to_string();
    const std::string att = cntl->request_attachment().to_string();
    void* resp = nullptr;
    size_t resp_len = 0;
    void* resp_att = nullptr;
    size_t resp_att_len = 0;
    int error_code = 0;
    char err_text[256];
    err_text[0] = '\0';
    const TraceContext trace_ctx = current_trace_context();
    const QosContext qos_ctx = current_qos_context();
    CallbackTiming timing(trace_ctx.span_id);
    const bool ran = PyCallbackPool::instance().Run([&] {
      // The pool thread inherits the server span: nested calls the Python
      // handler issues parent there, keeping the trace linked. Same for
      // the request QoS — a nested RPC the handler issues inherits the
      // tenant/priority and clamps to the remaining deadline budget.
      ScopedTraceContext scope(trace_ctx.trace_id, trace_ctx.span_id);
      ScopedQosContext qos_scope(qos_ctx);
      ScopedHandlerController hc(cntl);  // tbrpc_stream_accept's doorway
      timing.Calling();
      _cb(_ctx, method.c_str(), req.data(), req.size(), att.data(),
          att.size(), &resp, &resp_len, &resp_att, &resp_att_len,
          &error_code, err_text, sizeof(err_text));
    }, qos_ctx.priority);
    timing.Annotate();
    if (!ran) {
      // A pool shed is an overload answer like any gate shed: count it
      // and carry a retry-after hint (drain time of a saturated pool is
      // one callback's runtime — unknown here, so a small fixed pace
      // beats the client's blind exponential floor).
      error_code = TRPC_ELIMIT;
      GlobalRpcMetrics::instance().shed_total << 1;
      snprintf(err_text, sizeof(err_text), "%s",
               "python callback pool saturated "
               "(python_callback_max_threads) (retry_after_ms=10)");
    }
    if (error_code != 0) {
      err_text[sizeof(err_text) - 1] = '\0';
      cntl->SetFailed(error_code, err_text[0] != '\0'
                                      ? err_text
                                      : "service callback failed");
    } else {
      if (resp != nullptr && resp_len > 0) {
        response->append(resp, resp_len);
      }
      if (resp_att != nullptr && resp_att_len > 0) {
        cntl->response_attachment().append(resp_att, resp_att_len);
      }
    }
    free(resp);
    free(resp_att);
    done->Run();
  }

 private:
  std::string _name;
  tbrpc_handler_cb _cb;
  void* _ctx;
};

class TensorCallbackService : public Service {
 public:
  TensorCallbackService(std::string name, tbrpc_tensor_handler_cb cb,
                        void* ctx)
      : _name(std::move(name)), _cb(cb), _ctx(ctx) {}
  std::string_view service_name() const override { return _name; }
  void CallMethod(const std::string& method, Controller* cntl,
                  const tbutil::IOBuf& request, tbutil::IOBuf* response,
                  Closure* done) override;

 private:
  std::string _name;
  tbrpc_tensor_handler_cb _cb;
  void* _ctx;
};

struct ServerBox {
  Server server;
  NativeEchoService echo;
  bool echo_added = false;
  // Options applied at Start — the pre-start setters
  // (tbrpc_server_set_max_concurrency, tbrpc_server_set_tenant_quota)
  // write here.
  ServerOptions opts;
  std::vector<Service*> services;
  ~ServerBox() {
    for (auto* s : services) delete s;
  }
};

struct ChannelBox {
  Channel channel;
};

}  // namespace

void* tbrpc_server_create() { return new ServerBox; }

int tbrpc_server_start(void* server, const char* addr) {
  auto* box = static_cast<ServerBox*>(server);
  if (box->server.Start(addr, &box->opts) != 0) return -1;
  return box->server.listen_address().port;
}

// cert/key non-empty => the port also accepts TLS (same-port sniffing;
// ALPN h2 + http/1.1 — gRPC-over-TLS peers negotiate h2).
int tbrpc_server_start_tls(void* server, const char* addr, const char* cert,
                           const char* key) {
  auto* box = static_cast<ServerBox*>(server);
  if (cert != nullptr) box->opts.ssl_cert_file = cert;
  if (key != nullptr) box->opts.ssl_key_file = key;
  if (box->server.Start(addr, &box->opts) != 0) return -1;
  return box->server.listen_address().port;
}

int tbrpc_server_set_max_concurrency(void* server, int32_t max) {
  if (server == nullptr) return -1;
  auto* box = static_cast<ServerBox*>(server);
  if (box->server.running()) return -1;  // the limiter is built at Start
  box->opts.max_concurrency = max < 0 ? 0 : max;
  return 0;
}

int tbrpc_server_set_tenant_quota(void* server, int32_t max_inflight) {
  if (server == nullptr) return -1;
  auto* box = static_cast<ServerBox*>(server);
  // Runtime-safe (atomic + lazy gate rebuild); also seeds the Start-time
  // option so a pre-start call behaves identically.
  box->opts.tenant_max_concurrency = max_inflight < 0 ? 0 : max_inflight;
  box->server.set_tenant_quota(max_inflight);
  return 0;
}

int tbrpc_server_stop(void* server) {
  return static_cast<ServerBox*>(server)->server.Stop();
}

void tbrpc_server_destroy(void* server) {
  delete static_cast<ServerBox*>(server);
}

int tbrpc_server_add_echo_service(void* server) {
  auto* box = static_cast<ServerBox*>(server);
  if (box->echo_added) return 0;
  box->echo_added = true;
  return box->server.AddService(&box->echo);
}

int tbrpc_server_set_inline(void* server, const char* service, int enabled) {
  if (server == nullptr || service == nullptr) return -1;
  auto* box = static_cast<ServerBox*>(server);
  // AddService registers every service (echo, callback, builtin) in the
  // server's map at registration time, so the registry lookup covers all.
  Service* svc = box->server.FindService(service);
  if (svc == nullptr) return -1;
  return svc->set_allow_inline(enabled != 0);
}

int tbrpc_server_add_callback_service(void* server, const char* name,
                                      tbrpc_handler_cb cb, void* ctx) {
  auto* box = static_cast<ServerBox*>(server);
  auto* svc = new CallbackService(name, cb, ctx);
  if (box->server.AddService(svc) != 0) {
    delete svc;
    return -1;
  }
  box->services.push_back(svc);
  return 0;
}

void* tbrpc_channel_create(const char* addr, int64_t timeout_ms,
                           int max_retry) {
  auto* box = new ChannelBox;
  ChannelOptions opts;
  opts.timeout_ms = timeout_ms;
  opts.max_retry = max_retry;
  if (box->channel.Init(addr, &opts) != 0) {
    delete box;
    return nullptr;
  }
  return box;
}

// protocol: 0 = tstd (default), 5 = gRPC over HTTP/2 (kH2ProtocolIndex).
void* tbrpc_channel_create_ex(const char* addr, int64_t timeout_ms,
                              int max_retry, int protocol) {
  auto* box = new ChannelBox;
  ChannelOptions opts;
  opts.timeout_ms = timeout_ms;
  opts.max_retry = max_retry;
  opts.protocol = protocol;
  if (box->channel.Init(addr, &opts) != 0) {
    delete box;
    return nullptr;
  }
  return box;
}

void tbrpc_channel_destroy(void* channel) {
  delete static_cast<ChannelBox*>(channel);
}

void* tbrpc_alloc(size_t n) { return malloc(n); }
void tbrpc_free(void* p) { free(p); }

void* tbrpc_dup(const void* p, size_t n) {
  void* out = malloc(n);
  if (out != nullptr) memcpy(out, p, n);
  return out;
}

// ---------------- TensorArena ----------------

namespace {

struct ArenaBox {
  std::shared_ptr<ttpu::TensorArena> arena;
};

// THE user-data deleter for locally-owned arena ranges riding in IOBufs.
void local_arena_release(void* ptr) {
  auto arena = ttpu::TensorArena::FindContaining(ptr);
  if (arena != nullptr) arena->OnLocalRelease(ptr);
}

// Append [off, off+len) of `arena` to `buf` as a tagged zero-copy block
// (the tag lets the tpu:// send path ship it by reference).
void append_arena_range(tbutil::IOBuf* buf, ttpu::TensorArena* arena,
                        uint64_t off, size_t len) {
  arena->AddLocalRef(off);
  buf->append_user_data_with_meta(arena->base() + off, len,
                                  &local_arena_release,
                                  ttpu::arena_meta(arena->id()));
}

struct ViewBox {
  tbutil::IOBuf buf;
};

}  // namespace

void* tbrpc_arena_create(size_t bytes) {
  auto arena = ttpu::TensorArena::Create(bytes);
  if (arena == nullptr) return nullptr;
  return new ArenaBox{std::move(arena)};
}

void tbrpc_arena_destroy(void* arena) {
  auto* box = static_cast<ArenaBox*>(arena);
  if (box == nullptr) return;
  // Keep the mapping alive until in-flight references drain — a socket
  // write queue may still point into the pages.
  ttpu::TensorArena::DestroyWhenIdle(std::move(box->arena));
  delete box;
}

void* tbrpc_arena_base(void* arena) {
  return static_cast<ArenaBox*>(arena)->arena->base();
}

int64_t tbrpc_arena_alloc(void* arena, size_t len) {
  return static_cast<ArenaBox*>(arena)->arena->Alloc(len);
}

int tbrpc_arena_free(void* arena, uint64_t off) {
  return static_cast<ArenaBox*>(arena)->arena->Free(off);
}

int64_t tbrpc_arena_busy_bytes(void* arena) {
  return static_cast<ArenaBox*>(arena)->arena->busy_bytes();
}

int64_t tbrpc_arenas_busy_bytes(void) {
  std::vector<std::shared_ptr<ttpu::TensorArena>> arenas;
  ttpu::TensorArena::ListAll(&arenas);
  int64_t n = 0;
  for (const auto& a : arenas) n += a->busy_bytes();
  return n;
}

int64_t tbrpc_arenas_total_bytes(void) {
  std::vector<std::shared_ptr<ttpu::TensorArena>> arenas;
  ttpu::TensorArena::ListAll(&arenas);
  int64_t n = 0;
  for (const auto& a : arenas) n += static_cast<int64_t>(a->bytes());
  return n;
}

void tbrpc_var_arena_gauges_create(void) {
  static std::once_flag once;
  std::call_once(once, [] {
    // Immortal native gauges: evaluated entirely in C++ at scrape time.
    (new tbvar::PassiveStatus<int64_t>(
         [] { return tbrpc_arenas_busy_bytes(); }))
        ->expose("tensor_arena_busy_bytes");
    (new tbvar::PassiveStatus<int64_t>(
         [] { return tbrpc_arenas_total_bytes(); }))
        ->expose("tensor_arena_total_bytes");
  });
}

int tbrpc_arena_wait_reusable(void* arena, uint64_t off, int64_t timeout_ms) {
  return static_cast<ArenaBox*>(arena)->arena->WaitReusable(off, timeout_ms);
}

// ---------------- one-sided tensor reads (ttpu/oneside.h) ----------------

namespace {
// Defined in the observability-dumps section below (same anonymous
// namespace; declarations merge).
int64_t copy_out(const std::string& s, char* buf, size_t cap);

struct OnesideWindowBox {
  std::shared_ptr<ttpu::OnesideWindow> win;
};
}  // namespace

void* tbrpc_oneside_window_create(void* arena, int32_t n_slots,
                                  int32_t n_readers) {
  if (arena == nullptr || n_slots <= 0 || n_readers <= 0) return nullptr;
  auto win = ttpu::OnesideWindow::Create(
      static_cast<ArenaBox*>(arena)->arena,
      static_cast<uint32_t>(n_slots), static_cast<uint32_t>(n_readers));
  if (win == nullptr) return nullptr;
  return new OnesideWindowBox{std::move(win)};
}

void tbrpc_oneside_window_destroy(void* win) {
  delete static_cast<OnesideWindowBox*>(win);
}

int tbrpc_oneside_publish(void* win, const char* name, uint64_t off,
                          uint64_t len, uint64_t version,
                          int take_ownership) {
  if (win == nullptr || name == nullptr) return -1;
  return static_cast<OnesideWindowBox*>(win)->win->Publish(
      name, off, len, version, take_ownership != 0);
}

void tbrpc_oneside_begin_rewrite(void* win, const char* name) {
  if (win == nullptr || name == nullptr) return;
  static_cast<OnesideWindowBox*>(win)->win->BeginRewrite(name);
}

int tbrpc_oneside_unpublish(void* win, const char* name) {
  if (win == nullptr || name == nullptr) return -1;
  return static_cast<OnesideWindowBox*>(win)->win->Unpublish(name);
}

int64_t tbrpc_oneside_window_describe(void* win, char* buf, size_t cap) {
  if (win == nullptr) return copy_out("", buf, cap);
  return copy_out(static_cast<OnesideWindowBox*>(win)->win->DescribeJson(),
                  buf, cap);
}

void* tbrpc_oneside_map(const char* shm_name, uint64_t bytes,
                        uint64_t dir_off, uint64_t token) {
  if (shm_name == nullptr) return nullptr;
  auto rd = ttpu::OnesideReader::Map(shm_name, bytes, dir_off, token);
  return rd.release();  // boxed as-is; unmap deletes
}

int tbrpc_oneside_read(void* reader, const char* name, void** data,
                       uint64_t* len, uint64_t* version) {
  if (reader == nullptr || name == nullptr) return ttpu::ONESIDE_GONE;
  // The reader mallocs, tbrpc_free frees — same allocator by contract.
  return static_cast<ttpu::OnesideReader*>(reader)->Read(name, data, len,
                                                         version);
}

int tbrpc_oneside_stat(void* reader, const char* name, uint64_t* len,
                       uint64_t* version) {
  if (reader == nullptr || name == nullptr) return ttpu::ONESIDE_GONE;
  return static_cast<ttpu::OnesideReader*>(reader)->Stat(name, len, version);
}

int tbrpc_oneside_read_into(void* reader, const char* name, void* buf,
                            uint64_t cap, uint64_t* len, uint64_t* version) {
  if (reader == nullptr || name == nullptr || buf == nullptr) {
    return ttpu::ONESIDE_GONE;
  }
  return static_cast<ttpu::OnesideReader*>(reader)->ReadInto(name, buf, cap,
                                                             len, version);
}

int tbrpc_oneside_unmap(void* reader) {
  delete static_cast<ttpu::OnesideReader*>(reader);
  return 0;
}

int64_t tbrpc_oneside_stats_json(char* buf, size_t cap) {
  return copy_out(ttpu::OnesideStatsJson(), buf, cap);
}

int tbrpc_call_tensor(void* channel, const char* service_method,
                      const void* req, size_t req_len, void* arena,
                      uint64_t att_off, size_t att_len, void** resp,
                      size_t* resp_len, void** view, const void** ratt_ptr,
                      size_t* ratt_len, int* ratt_copied, char* errbuf,
                      size_t errbuf_len) {
  // Declared first, so destroyed last: stamps the very return to a traced
  // caller, after every local's teardown.
  struct ReturnStamp {
    const bool traced =
        rpcz_enabled() && current_trace_context().span_id != 0;
    ~ReturnStamp() {
      t_call_returned_us = traced ? tbutil::gettimeofday_us() : 0;
    }
  } return_stamp;
  auto* box = static_cast<ChannelBox*>(channel);
  Controller cntl;
  tbutil::IOBuf request, response;
  if (req_len > 0) request.append(req, req_len);
  if (arena != nullptr && att_len > 0) {
    append_arena_range(&cntl.request_attachment(),
                       static_cast<ArenaBox*>(arena)->arena.get(), att_off,
                       att_len);
  }
  box->channel.CallMethod(service_method, &cntl, request, &response, nullptr);
  if (cntl.Failed()) {
    if (errbuf != nullptr && errbuf_len > 0) {
      snprintf(errbuf, errbuf_len, "%s", cntl.ErrorText().c_str());
    }
    return cntl.ErrorCode() != 0 ? cntl.ErrorCode() : -1;
  }
  if (resp != nullptr) {
    *resp_len = response.size();
    *resp = malloc(response.size() > 0 ? response.size() : 1);
    response.copy_to(*resp, response.size());
  }
  if (view != nullptr) {
    *view = nullptr;
    *ratt_ptr = nullptr;
    *ratt_len = cntl.response_attachment().size();
    *ratt_copied = 0;
    if (*ratt_len > 0) {
      tbutil::IOBuf& att = cntl.response_attachment();
      if (att.backing_block_num() == 1) {
        // Contiguous (the single-ref tensor case): hand back the bytes in
        // place; the view keeps the block — and through its deleter the
        // remote arena range — alive until tbrpc_view_free.
        auto* vb = new ViewBox;
        vb->buf.append(att);
        *view = vb;
        *ratt_ptr = vb->buf.backing_block(0).data();
      } else {
        void* flat = malloc(*ratt_len);
        att.copy_to(flat, *ratt_len);
        *ratt_ptr = flat;
        *ratt_copied = 1;
      }
    }
  }
  return 0;
}

void tbrpc_view_free(void* view) { delete static_cast<ViewBox*>(view); }

// ---------------- async tensor RPC ----------------

namespace {

std::atomic<int64_t> g_async_inflight{0};

// Native gauge over the submit/completion counter: evaluated entirely in
// C++ at scrape time, like the arena occupancy gauges. Idempotent.
void async_inflight_gauge_create() {
  static std::once_flag once;
  std::call_once(once, [] {
    (new tbvar::PassiveStatus<int64_t>([] {
      return g_async_inflight.load(std::memory_order_relaxed);
    }))->expose("tensor_rpc_inflight");
  });
}

// One in-flight async tensor RPC. Shared between the caller's handle and
// the completion closure: `refs` starts at 2 and whoever drops the last
// reference deletes. Waiters are plain pthreads (ctypes releases the GIL
// around the wait), so a std::mutex/condition_variable pair is the right
// primitive — never fiber waiters.
struct FutureBox {
  std::mutex mu;  // tpulint: allow(fiber-blocking) — pthread waiters only
  std::condition_variable cv;
  int refs = 2;            // caller handle + completion closure
  bool done = false;
  bool abandoned = false;  // cancel/destroy: results released, not handed out
  bool consumed = false;   // a wait transferred ownership out
  int rc = 0;
  std::string err;
  void* resp = nullptr;
  size_t resp_len = 0;
  void* view = nullptr;
  const void* ratt_ptr = nullptr;
  size_t ratt_len = 0;
  int ratt_copied = 0;
  tbrpc_tensor_done_cb cb = nullptr;
  void* cb_ctx = nullptr;
  Controller cntl;
  tbutil::IOBuf response;

  ~FutureBox() { ReleaseResultsLocked(); }  // sole owner by then

  // Free unconsumed result buffers; idempotent (fields nulled) so the
  // cancel-then-destroy sequence releases the response view exactly once.
  void ReleaseResultsLocked() {
    if (view != nullptr) {
      tbrpc_view_free(view);
    } else if (ratt_copied && ratt_ptr != nullptr) {
      free(const_cast<void*>(ratt_ptr));
    }
    view = nullptr;
    ratt_ptr = nullptr;
    ratt_len = 0;
    ratt_copied = 0;
    free(resp);
    resp = nullptr;
    resp_len = 0;
  }
};

// Completion closure body: runs wherever EndRPC ran done->Run() — a fiber
// on the response path, the canceling pthread on the cancel path. Extracts
// results exactly as the sync tbrpc_call_tensor does (view deferral
// included), fires the notification callback, then publishes.
void async_on_done(FutureBox* fut) {
  Controller& cntl = fut->cntl;
  int rc = 0;
  std::string err;
  void* resp = nullptr;
  size_t resp_len = 0;
  void* view = nullptr;
  const void* ratt_ptr = nullptr;
  size_t ratt_len = 0;
  int ratt_copied = 0;
  if (cntl.Failed()) {
    // Never -1 here: -1 is tbrpc_future_timed_wait's "still in flight".
    rc = cntl.ErrorCode() != 0 ? cntl.ErrorCode() : TRPC_EINTERNAL;
    err = cntl.ErrorText();
  } else {
    resp_len = fut->response.size();
    resp = malloc(resp_len > 0 ? resp_len : 1);
    fut->response.copy_to(resp, resp_len);
    tbutil::IOBuf& att = cntl.response_attachment();
    ratt_len = att.size();
    if (ratt_len > 0) {
      if (att.backing_block_num() == 1) {
        // Contiguous: hand back in place; the view keeps the block — and
        // through its deleter the remote arena range — alive until
        // tbrpc_view_free (sync-path parity).
        auto* vb = new ViewBox;
        vb->buf.append(att);
        view = vb;
        ratt_ptr = vb->buf.backing_block(0).data();
      } else {
        void* flat = malloc(ratt_len);
        att.copy_to(flat, ratt_len);
        ratt_ptr = flat;
        ratt_copied = 1;
      }
    }
  }
  bool abandoned;
  {
    std::lock_guard<std::mutex> lk(fut->mu);  // tpulint: allow(fiber-blocking)
    abandoned = fut->abandoned;
    fut->rc = rc;
    fut->err = std::move(err);
    if (!abandoned) {
      fut->resp = resp;
      fut->resp_len = resp_len;
      fut->view = view;
      fut->ratt_ptr = ratt_ptr;
      fut->ratt_len = ratt_len;
      fut->ratt_copied = ratt_copied;
    }
  }
  if (abandoned) {
    // Canceled/destroyed before the response: nobody will consume.
    // Releasing HERE (not in destroy) is what makes the release happen
    // exactly once whichever side wins the race.
    if (view != nullptr) {
      tbrpc_view_free(view);
    } else if (ratt_copied && ratt_ptr != nullptr) {
      free(const_cast<void*>(ratt_ptr));
    }
    free(resp);
  } else if (fut->cb != nullptr) {
    // Notification BEFORE the future becomes waitable: the waiter cannot
    // consume (and free) the buffers the callback is reading. Python
    // callbacks need a pthread-stable thread (GIL pairing); pool
    // saturation drops the notification, never the completion.
    tbrpc_tensor_done_cb cb = fut->cb;
    void* cb_ctx = fut->cb_ctx;
    PyCallbackPool::instance().Run([&] {
      cb(cb_ctx, fut->rc, fut->resp, fut->resp_len, fut->view,
         fut->ratt_ptr, fut->ratt_len, fut->ratt_copied, fut->err.c_str());
    });
  }
  g_async_inflight.fetch_sub(1, std::memory_order_relaxed);
  bool del;
  {
    std::lock_guard<std::mutex> lk(fut->mu);  // tpulint: allow(fiber-blocking)
    // A cancel/destroy that raced in AFTER the store above (abandoned
    // flipped between the two critical sections) would otherwise strand
    // the stored buffers until destroy: release promptly, exactly once.
    if (fut->abandoned && !fut->consumed) fut->ReleaseResultsLocked();
    fut->done = true;
    del = (--fut->refs == 0);
    // Notify UNDER the lock: a waiter may consume, destroy the handle and
    // free the box the moment its predicate-wait returns — which it
    // cannot do before we release.
    if (!del) fut->cv.notify_all();
  }
  if (del) delete fut;  // handle already destroyed; no waiter can exist
}

// Hand results out under fut->mu. First successful take transfers
// ownership; later calls (or abandoned futures) return the code with
// every out zeroed.
int future_take_locked(FutureBox* fut, void** resp, size_t* resp_len,
                       void** view, const void** ratt_ptr, size_t* ratt_len,
                       int* ratt_copied, char* errbuf, size_t errbuf_len) {
  if (resp != nullptr) *resp = nullptr;
  if (resp_len != nullptr) *resp_len = 0;
  if (view != nullptr) *view = nullptr;
  if (ratt_ptr != nullptr) *ratt_ptr = nullptr;
  if (ratt_len != nullptr) *ratt_len = 0;
  if (ratt_copied != nullptr) *ratt_copied = 0;
  if (fut->abandoned) {
    if (errbuf != nullptr && errbuf_len > 0) {
      snprintf(errbuf, errbuf_len, "%s", "rpc canceled by caller");
    }
    return TRPC_ECANCELED;
  }
  if (fut->rc != 0) {
    if (errbuf != nullptr && errbuf_len > 0) {
      snprintf(errbuf, errbuf_len, "%s", fut->err.c_str());
    }
    return fut->rc;
  }
  if (fut->consumed) return 0;  // second wait: success code, zeroed outs
  fut->consumed = true;
  if (resp != nullptr) *resp = fut->resp;
  if (resp_len != nullptr) *resp_len = fut->resp_len;
  if (view != nullptr) *view = fut->view;
  if (ratt_ptr != nullptr) *ratt_ptr = fut->ratt_ptr;
  if (ratt_len != nullptr) *ratt_len = fut->ratt_len;
  if (ratt_copied != nullptr) *ratt_copied = fut->ratt_copied;
  fut->resp = nullptr;
  fut->view = nullptr;
  fut->ratt_ptr = nullptr;
  return 0;
}

}  // namespace

void* tbrpc_call_tensor_async(void* channel, const char* service_method,
                              const void* req, size_t req_len, void* arena,
                              uint64_t att_off, size_t att_len,
                              tbrpc_tensor_done_cb done_cb, void* done_ctx) {
  auto* box = static_cast<ChannelBox*>(channel);
  async_inflight_gauge_create();
  auto* fut = new FutureBox;
  fut->cb = done_cb;
  fut->cb_ctx = done_ctx;
  tbutil::IOBuf request;
  if (req_len > 0) request.append(req, req_len);
  if (arena != nullptr && att_len > 0) {
    append_arena_range(&fut->cntl.request_attachment(),
                       static_cast<ArenaBox*>(arena)->arena.get(), att_off,
                       att_len);
  }
  g_async_inflight.fetch_add(1, std::memory_order_relaxed);
  // Async CallMethod: serializes, issues attempt 0 and returns; the done
  // closure runs from EndRPC (response, timeout, retry exhaustion or
  // cancel). Immediate failures run it inline — the returned future is
  // then already completed.
  box->channel.CallMethod(service_method, &fut->cntl, request,
                          &fut->response,
                          NewCallback([fut] { async_on_done(fut); }));
  return fut;
}

int tbrpc_future_wait(void* f, void** resp, size_t* resp_len, void** view,
                      const void** ratt_ptr, size_t* ratt_len,
                      int* ratt_copied, char* errbuf, size_t errbuf_len) {
  auto* fut = static_cast<FutureBox*>(f);
  // Caller threads are Python pthreads with the GIL released (ctypes) —
  // blocking them is the contract, same as the sync call path's join.
  std::unique_lock<std::mutex> lk(fut->mu);  // tpulint: allow(fiber-blocking)
  fut->cv.wait(lk, [&] { return fut->done; });
  return future_take_locked(fut, resp, resp_len, view, ratt_ptr, ratt_len,
                            ratt_copied, errbuf, errbuf_len);
}

int tbrpc_future_timed_wait(void* f, int64_t timeout_ms, void** resp,
                            size_t* resp_len, void** view,
                            const void** ratt_ptr, size_t* ratt_len,
                            int* ratt_copied, char* errbuf,
                            size_t errbuf_len) {
  auto* fut = static_cast<FutureBox*>(f);
  std::unique_lock<std::mutex> lk(fut->mu);  // tpulint: allow(fiber-blocking)
  if (!fut->cv.wait_for(lk, std::chrono::milliseconds(
                                timeout_ms > 0 ? timeout_ms : 0),
                        [&] { return fut->done; })) {
    return -1;  // still in flight; nothing consumed, wait again later
  }
  return future_take_locked(fut, resp, resp_len, view, ratt_ptr, ratt_len,
                            ratt_copied, errbuf, errbuf_len);
}

int tbrpc_future_cancel(void* f) {
  auto* fut = static_cast<FutureBox*>(f);
  tbthread::fiber_id_t cid = tbthread::INVALID_FIBER_ID;
  {
    std::lock_guard<std::mutex> lk(fut->mu);  // tpulint: allow(fiber-blocking)
    if (fut->abandoned) return 0;
    fut->abandoned = true;
    if (fut->done) {
      if (!fut->consumed) fut->ReleaseResultsLocked();
      return 0;
    }
    cid = fut->cntl.call_id();
  }
  // Raise ECANCELED through the correlation id — the controller ends the
  // RPC early (OnError's cancel path) and the completion closure sees
  // `abandoned` and releases. A lost race (response already accepted) is
  // fine: the error raise no-ops on a destroyed id.
  if (cid != tbthread::INVALID_FIBER_ID) {
    tbthread::fiber_id_error(cid, TRPC_ECANCELED);
  }
  return 0;
}

void tbrpc_future_destroy(void* f) {
  if (f == nullptr) return;
  auto* fut = static_cast<FutureBox*>(f);
  tbthread::fiber_id_t cid = tbthread::INVALID_FIBER_ID;
  bool del;
  {
    std::lock_guard<std::mutex> lk(fut->mu);  // tpulint: allow(fiber-blocking)
    if (!fut->abandoned) {
      fut->abandoned = true;
      if (fut->done) {
        if (!fut->consumed) fut->ReleaseResultsLocked();
      } else {
        cid = fut->cntl.call_id();  // hurry the in-flight RPC to an end
      }
    }
    del = (--fut->refs == 0);
  }
  if (cid != tbthread::INVALID_FIBER_ID) {
    tbthread::fiber_id_error(cid, TRPC_ECANCELED);
  }
  if (del) delete fut;
}

int64_t tbrpc_async_inflight(void) {
  return g_async_inflight.load(std::memory_order_relaxed);
}

void TensorCallbackService::CallMethod(const std::string& method,
                                       Controller* cntl,
                                       const tbutil::IOBuf& request,
                                       tbutil::IOBuf* response,
                                       Closure* done) {
  const std::string req = request.to_string();
  // Request attachment IN PLACE when it arrived as one block (the
  // zero-copy tensor receive: the pointer is inside this process's mapping
  // of the sender's arena / the connection's RX segment).
  const tbutil::IOBuf& att = cntl->request_attachment();
  std::string att_flat;
  const void* att_ptr = nullptr;
  const size_t att_len = att.size();
  if (att.backing_block_num() == 1) {
    att_ptr = att.backing_block(0).data();
  } else if (att_len > 0) {
    att.copy_to(&att_flat, att_len);
    att_ptr = att_flat.data();
  }
  void* resp = nullptr;
  size_t resp_len = 0;
  void* resp_arena = nullptr;
  uint64_t resp_att_off = 0;
  size_t resp_att_len = 0;
  int resp_att_autofree = 0;
  int error_code = 0;
  char err_text[256];
  err_text[0] = '\0';
  const TraceContext trace_ctx = current_trace_context();
  const QosContext qos_ctx = current_qos_context();
  CallbackTiming timing(trace_ctx.span_id);
  const bool ran = PyCallbackPool::instance().Run([&] {
    ScopedTraceContext scope(trace_ctx.trace_id, trace_ctx.span_id);
    ScopedQosContext qos_scope(qos_ctx);
    ScopedHandlerController hc(cntl);  // tbrpc_stream_accept's doorway
    timing.Calling();
    _cb(_ctx, method.c_str(), req.data(), req.size(), att_ptr, att_len,
        &resp, &resp_len, &resp_arena, &resp_att_off, &resp_att_len,
        &resp_att_autofree, &error_code, err_text, sizeof(err_text));
  }, qos_ctx.priority);
  timing.Annotate();
  if (!ran) {
    error_code = TRPC_ELIMIT;
    GlobalRpcMetrics::instance().shed_total << 1;
    snprintf(err_text, sizeof(err_text), "%s",
             "python callback pool saturated (python_callback_max_threads)"
             " (retry_after_ms=10)");
  }
  if (error_code != 0) {
    err_text[sizeof(err_text) - 1] = '\0';
    cntl->SetFailed(error_code, err_text[0] != '\0'
                                    ? err_text
                                    : "tensor service callback failed");
    if (resp_arena != nullptr && resp_att_len > 0 && resp_att_autofree) {
      // The handler allocated a response range before failing: honor the
      // autofree so the arena doesn't leak one range per failed call.
      static_cast<ArenaBox*>(resp_arena)->arena->Free(resp_att_off);
    }
  } else {
    if (resp != nullptr && resp_len > 0) {
      response->append(resp, resp_len);
    }
    if (resp_arena != nullptr && resp_att_len > 0) {
      // The response tensor lives in the server's arena: it rides back by
      // reference; the client's view release returns the range.
      ttpu::TensorArena* a = static_cast<ArenaBox*>(resp_arena)->arena.get();
      append_arena_range(&cntl->response_attachment(), a, resp_att_off,
                         resp_att_len);
      if (resp_att_autofree) {
        // Ref taken above, so this free defers until the client releases —
        // freeing inside the handler would race a concurrent realloc.
        a->Free(resp_att_off);
      }
    }
  }
  free(resp);
  done->Run();
}

int tbrpc_server_add_tensor_service(void* server, const char* name,
                                    tbrpc_tensor_handler_cb cb, void* ctx) {
  auto* box = static_cast<ServerBox*>(server);
  auto* svc = new TensorCallbackService(name, cb, ctx);
  if (box->server.AddService(svc) != 0) {
    delete svc;
    return -1;
  }
  box->services.push_back(svc);
  return 0;
}

int tbrpc_call(void* channel, const char* service_method, const void* req,
               size_t req_len, const void* attach, size_t attach_len,
               void** resp, size_t* resp_len, void** resp_attach,
               size_t* resp_attach_len, char* errbuf, size_t errbuf_len) {
  auto* box = static_cast<ChannelBox*>(channel);
  Controller cntl;
  tbutil::IOBuf request, response;
  if (req_len > 0) request.append(req, req_len);
  if (attach_len > 0) cntl.request_attachment().append(attach, attach_len);
  box->channel.CallMethod(service_method, &cntl, request, &response, nullptr);
  if (cntl.Failed()) {
    if (errbuf != nullptr && errbuf_len > 0) {
      snprintf(errbuf, errbuf_len, "%s", cntl.ErrorText().c_str());
    }
    return cntl.ErrorCode() != 0 ? cntl.ErrorCode() : -1;
  }
  auto out = [](const tbutil::IOBuf& buf, void** p, size_t* n) {
    *n = buf.size();
    *p = malloc(buf.size() > 0 ? buf.size() : 1);
    buf.copy_to(*p, buf.size());
  };
  if (resp != nullptr) out(response, resp, resp_len);
  if (resp_attach != nullptr) {
    out(cntl.response_attachment(), resp_attach, resp_attach_len);
  }
  return 0;
}

// ---------------- observability ----------------

namespace {

// Copy-out convention shared by the dump entry points: NUL-terminated
// truncation into (buf, cap), return the untruncated length.
int64_t copy_out(const std::string& s, char* buf, size_t cap) {
  if (buf != nullptr && cap > 0) {
    const size_t n = std::min(s.size(), cap - 1);
    memcpy(buf, s.data(), n);
    buf[n] = '\0';
  }
  return static_cast<int64_t>(s.size());
}

}  // namespace

void* tbrpc_var_adder_create(const char* name) {
  auto* adder = new tbvar::Adder<int64_t>();
  if (adder->expose(name != nullptr ? name : "") != 0) {
    delete adder;
    return nullptr;
  }
  return adder;  // immortal: the registry references it by name forever
}

void tbrpc_var_adder_add(void* adder, int64_t delta) {
  *static_cast<tbvar::Adder<int64_t>*>(adder) << delta;
}

int64_t tbrpc_var_adder_value(void* adder) {
  return static_cast<tbvar::Adder<int64_t>*>(adder)->get_value();
}

void* tbrpc_var_latency_create(const char* prefix) {
  const std::string p = prefix != nullptr ? prefix : "";
  // LatencyRecorder::expose can't fail, so probe the registry for EVERY
  // facade name ourselves — a collision on any one of them must be
  // visible to the caller, or that series silently flatlines. (The probe
  // and the expose are not atomic; concurrent same-prefix creators still
  // race, but each of them sees the other's names on its next probe.)
  for (const char* suffix :
       {"_latency", "_max_latency", "_qps", "_count", "_latency_50",
        "_latency_99", "_latency_999"}) {
    std::ostringstream probe;
    if (tbvar::Variable::describe_exposed(
            tbvar::to_underscored_name(p + suffix), probe)) {
      return nullptr;
    }
  }
  auto* rec = new tbvar::LatencyRecorder();
  rec->expose(p);
  return rec;  // immortal
}

void tbrpc_var_latency_record(void* rec, int64_t latency_us) {
  *static_cast<tbvar::LatencyRecorder*>(rec) << latency_us;
}

int64_t tbrpc_var_latency_value(void* rec, int what) {
  auto* r = static_cast<tbvar::LatencyRecorder*>(rec);
  switch (what) {
    case 0: return r->count();
    case 1: return r->qps();
    case 2: return r->latency();
    case 3: return r->max_latency();
    case 50: return r->p50();
    case 90: return r->p90();
    case 99: return r->p99();
    case 999: return r->p999();
    default: return -1;
  }
}

void* tbrpc_var_gauge_create(const char* name, tbrpc_gauge_cb cb, void* ctx) {
  auto* gauge = new tbvar::PassiveStatus<int64_t>([cb, ctx]() -> int64_t {
    // Scrapes evaluate getters on server FIBERS while the registry walk
    // holds its mutex: the Python callback must run on a pthread-stable
    // pool thread (GIL pairing), and the caller must BLOCK, not park —
    // parking under that held mutex lets the workers fill up with fibers
    // blocked on the same mutex, leaving no worker to resume the scraper.
    int64_t v = -1;  // saturation/shed reads as -1, not a stale 0
    PyCallbackPool::instance().RunBlocking([&] { v = cb(ctx); });
    return v;
  });
  if (gauge->expose(name != nullptr ? name : "") != 0) {
    delete gauge;
    return nullptr;
  }
  return gauge;  // immortal
}

int64_t tbrpc_vars_dump(const char* prefix, char* buf, size_t cap) {
  const std::string want = prefix != nullptr ? prefix : "";
  std::map<std::string, std::string> vars;
  tbvar::Variable::dump_exposed(&vars);
  std::string out;
  for (const auto& [name, value] : vars) {
    if (!want.empty() && name.compare(0, want.size(), want) != 0) continue;
    out += name;
    out += " : ";
    out += value;
    out += '\n';
  }
  return copy_out(out, buf, cap);
}

int64_t tbrpc_vars_dump_prometheus(char* buf, size_t cap) {
  std::string out;
  tbvar::dump_prometheus(&out);
  return copy_out(out, buf, cap);
}

int64_t tbrpc_rpcz_dump_json(uint64_t trace_id, char* buf, size_t cap) {
  // Renderer shared with the console's /rpcz?format=json (span.cpp) — the
  // cross-process fleet scrape and the in-process dump cannot drift.
  return copy_out(RpczDumpJson(trace_id), buf, cap);
}

int64_t tbrpc_debug_dump_fibers(char* buf, size_t cap) {
  std::vector<tbthread::FiberTrace> traces;
  tbthread::fiber_trace_all(&traces);
  std::string out;
  char line[128];
  for (const auto& t : traces) {
    snprintf(line, sizeof(line), "fiber %llu %s\n",
             static_cast<unsigned long long>(t.tid),
             t.running ? "RUNNING" : "parked");
    out += line;
    for (size_t i = 0; i < t.frames.size(); ++i) {
      snprintf(line, sizeof(line), "  #%zu %p %s\n", i, t.frames[i],
               i < t.symbols.size() ? t.symbols[i].c_str() : "?");
      out += line;
    }
  }
  return copy_out(out, buf, cap);
}

int64_t tbrpc_debug_dump_ici(char* buf, size_t cap) {
  return copy_out(ttpu::DebugDumpEndpoints(false), buf, cap);
}

// ---------------- flight recorder + stall watchdog ----------------

int64_t tbrpc_flight_snapshot(int64_t max_events, char* buf, size_t cap) {
  const size_t n = max_events > 0 ? static_cast<size_t>(max_events) : 0;
  return copy_out(tbvar::flight_snapshot_text(n), buf, cap);
}

int64_t tbrpc_flight_total_events(void) {
  return tbvar::flight_total_events();
}

int tbrpc_watchdog_start(const char* dump_dir) {
  return StallWatchdog::singleton().Start(
      dump_dir != nullptr ? dump_dir : "");
}

int tbrpc_watchdog_stop(void) {
  StallWatchdog::singleton().Stop();
  return 0;
}

int tbrpc_health_state(void) { return StallWatchdog::singleton().state(); }

int64_t tbrpc_health_dump_json(char* buf, size_t cap) {
  return copy_out(StallWatchdog::singleton().DumpJson(), buf, cap);
}

int64_t tbrpc_health_last_dump_path(char* buf, size_t cap) {
  return copy_out(StallWatchdog::singleton().last_dump_path(), buf, cap);
}

namespace {

// tbrpc_debug_hold_workers state. The holder fibers deliberately block
// their worker PTHREAD (a raw futex wait, not a fiber park) — the whole
// point is to deny the scheduler its workers the way the historical
// all-threads-parked wedge did, so the watchdog's probe path can be tested
// deterministically.
//
// Inline-fast-path audit (small-RPC PR): an inline handler runs on the
// INPUT fiber, but input fibers are scheduled on these same worker
// pthreads — fiber_start_urgent only ENQUEUES (its run-inline fallback
// fires on spawn failure, not on busy workers), so holding every worker
// still wedges inline-registered methods exactly like dispatched ones.
// No exclusion needed; tests/test_small_rpc.py::test_hold_workers_still_
// wedges_inline_path pins this.
std::atomic<int> g_hold_release{1};  // 0 = holding, 1 = released

void* worker_holder_fn(void* deadline_ptr) {
  const int64_t deadline_us =
      reinterpret_cast<intptr_t>(deadline_ptr);
  while (g_hold_release.load(std::memory_order_acquire) == 0) {
    const int64_t left_us = deadline_us - tbutil::gettimeofday_us();
    if (left_us <= 0) break;
    timespec rel;
    rel.tv_sec = left_us / 1000000;
    rel.tv_nsec = (left_us % 1000000) * 1000;
    tbthread::futex_wait_private(&g_hold_release, 0, &rel);
  }
  return nullptr;
}

}  // namespace

int tbrpc_debug_hold_workers(int nfibers, int64_t hold_ms) {
  if (nfibers <= 0) nfibers = tbthread::fiber_get_concurrency();
  if (nfibers <= 0) return 0;
  if (hold_ms <= 0) hold_ms = 1000;
  const int64_t deadline_us = tbutil::gettimeofday_us() + hold_ms * 1000;
  g_hold_release.store(0, std::memory_order_release);
  int started = 0;
  for (int i = 0; i < nfibers; ++i) {
    tbthread::fiber_t tid;
    if (tbthread::fiber_start_background(
            &tid, nullptr, worker_holder_fn,
            reinterpret_cast<void*>(static_cast<intptr_t>(deadline_us))) ==
        0) {
      ++started;
    }
  }
  return started;
}

void tbrpc_debug_release_workers(void) {
  g_hold_release.store(1, std::memory_order_release);
  tbthread::futex_wake_private(&g_hold_release, INT32_MAX);
}

namespace {

struct ContendArg {
  tbthread::FiberMutex* mu;
  std::atomic<int64_t>* acquisitions;
  int64_t deadline_us;
};

void* contender_fn(void* argv) {
  auto* a = static_cast<ContendArg*>(argv);
  while (tbutil::gettimeofday_us() < a->deadline_us) {
    a->mu->lock();
    // Hold briefly so every OTHER contender measurably waits — the
    // contention profiler samples wait time, not acquisitions.
    tbthread::fiber_usleep(1000);
    a->mu->unlock();
    a->acquisitions->fetch_add(1, std::memory_order_relaxed);
    tbthread::fiber_usleep(100);  // let a waiter win the next round
  }
  return nullptr;
}

}  // namespace

int64_t tbrpc_debug_induce_contention(int nfibers, int64_t ms) {
  if (nfibers < 2) nfibers = 2;
  if (nfibers > 64) nfibers = 64;
  if (ms <= 0) ms = 1000;
  tbthread::FiberMutex mu;
  std::atomic<int64_t> acquisitions{0};
  ContendArg arg{&mu, &acquisitions,
                 tbutil::gettimeofday_us() + ms * 1000};
  std::vector<tbthread::fiber_t> fibers;
  fibers.reserve(nfibers);
  for (int i = 0; i < nfibers; ++i) {
    tbthread::fiber_t tid;
    if (tbthread::fiber_start_background(&tid, nullptr, contender_fn,
                                         &arg) == 0) {
      fibers.push_back(tid);
    }
  }
  for (tbthread::fiber_t tid : fibers) {
    tbthread::fiber_join(tid, nullptr);  // caller is a plain pthread
  }
  return acquisitions.load(std::memory_order_relaxed);
}

int tbrpc_rpcz_enabled(void) { return rpcz_enabled() ? 1 : 0; }

void tbrpc_rpcz_set_enabled(int on) {
  FlagRegistry::global().Set("rpcz_enabled", on != 0 ? "1" : "0");
}

int tbrpc_rpcz_sample_root(void) {
  // One combined gate for Python-created root spans (trace_span):
  // rpcz off OR an unsampled root both mean "don't collect".
  return rpcz_enabled() && rpcz_sample_root() ? 1 : 0;
}

int tbrpc_rpcz_sample_1_in_n(void) {
  const int64_t n = rpcz_sample_1_in_n();
  return n > INT32_MAX ? INT32_MAX : static_cast<int>(n);
}

uint64_t tbrpc_trace_new_id(void) { return new_trace_or_span_id(); }

void tbrpc_trace_current(uint64_t* trace_id, uint64_t* span_id) {
  const TraceContext ctx = current_trace_context();
  if (trace_id != nullptr) *trace_id = ctx.trace_id;
  if (span_id != nullptr) *span_id = ctx.span_id;
}

void tbrpc_trace_set(uint64_t trace_id, uint64_t span_id) {
  set_current_trace_context({trace_id, span_id});
}

void tbrpc_trace_clear(void) { clear_current_trace_context(); }

void tbrpc_span_annotate(const char* text) {
  if (text == nullptr) return;
  const TraceContext ctx = current_trace_context();
  AnnotateSpan(ctx.span_id, text);
}

void tbrpc_span_emit(uint64_t trace_id, uint64_t span_id,
                     uint64_t parent_span_id, int server_side,
                     int64_t start_us, int64_t end_us, int error_code,
                     const char* name) {
  if (!rpcz_enabled()) return;
  EmitSpan(trace_id, span_id, parent_span_id, server_side != 0, start_us,
           end_us, error_code, name != nullptr ? name : "");
}

int64_t tbrpc_now_us(void) { return tbutil::gettimeofday_us(); }

const void* tbrpc_rpcz_enabled_flag(void) { return rpcz_enabled_flag(); }

int64_t tbrpc_callback_called_us(void) { return t_callback_called_us; }

int64_t tbrpc_call_returned_us(void) { return t_call_returned_us; }

int tbrpc_flag_set(const char* name, const char* value) {
  if (name == nullptr || value == nullptr) return -1;
  return FlagRegistry::global().Set(name, value) ? 0 : -1;
}

// ---------------- overload protection: QoS + tenant quotas ----------------

int tbrpc_qos_set(int priority, const char* tenant) {
  QosContext ctx = current_qos_context();
  ctx.priority = clamp_priority(priority);
  std::string t = tenant != nullptr ? tenant : "";
  if (t.size() > 256) {
    return -1;  // tenant ids are short labels; refuse wire-bloating ones
  }
  ctx.tenant = std::move(t);
  set_current_qos_context(ctx);
  return 0;
}

void tbrpc_qos_clear(void) { clear_current_qos_context(); }

int64_t tbrpc_qos_get(int* priority, char* tenant_buf, size_t cap) {
  const QosContext ctx = current_qos_context();
  if (priority != nullptr) *priority = ctx.priority;
  return copy_out(ctx.tenant, tenant_buf, cap);
}

int64_t tbrpc_deadline_remaining_ms(void) {
  const QosContext ctx = current_qos_context();
  if (ctx.deadline_us <= 0) return -1;
  const int64_t left_us = ctx.deadline_us - tbutil::gettimeofday_us();
  return left_us > 0 ? left_us / 1000 : 0;
}

int64_t tbrpc_server_tenantz_json(void* server, char* buf, size_t cap) {
  if (server == nullptr) return copy_out("{}", buf, cap);
  std::string out;
  static_cast<ServerBox*>(server)->server.TenantzJson(&out);
  return copy_out(out, buf, cap);
}

int tbrpc_debug_inject_latency(const char* service, int64_t ms) {
  SetDebugInjectedLatency(service != nullptr ? service : "", ms);
  return 0;
}

// ---------------- quantized tensor wire: codec registry ----------------

int tbrpc_tensor_codec_id(const char* name) {
  GlobalInitializeOrDie();  // registry is filled by the builtin hookup
  return TensorCodecId(name);
}

int64_t tbrpc_tensor_codec_list(char* buf, size_t cap) {
  GlobalInitializeOrDie();
  return copy_out(TensorCodecList(), buf, cap);
}

void tbrpc_tensor_codec_note(const char* tensor, int codec_id,
                             uint64_t logical_bytes, uint64_t wire_bytes) {
  GlobalInitializeOrDie();  // builtin codec names must resolve in stats
  if (codec_id < 0 || codec_id > 255) return;
  NoteTensorCodec(tensor, static_cast<uint8_t>(codec_id), logical_bytes,
                  wire_bytes);
}

int64_t tbrpc_tensor_codec_stats_json(char* buf, size_t cap) {
  GlobalInitializeOrDie();
  return copy_out(TensorCodecStatsJson(), buf, cap);
}

// ---------------- fleet: service registry ----------------

int tbrpc_registry_install(void) {
  RegistryService::Install();
  return 0;
}

int tbrpc_registry_clear(void) {
  RegistryService::clear();
  return 0;
}

// ---------------- streaming RPC: token streams ----------------

namespace {

// Native read buffer for one capi stream, running in MANUAL consumption
// mode: delivery queues here, and flow-control feedback advances only as
// tbrpc_stream_read drains — a slow Python reader exhausts its own peer
// window (that stream's writers park/EAGAIN) instead of buffering without
// bound. Waiters are plain Python pthreads (ctypes releases the GIL), so
// mutex/condvar is the right primitive; the consumer fiber's push is a
// brief non-parking critical section.
class StreamReadBuffer : public StreamInputHandler {
 public:
  int on_received_messages(StreamId, tbutil::IOBuf* const messages[],
                           size_t size) override {
    std::lock_guard<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking) — brief push, never parks
    for (size_t i = 0; i < size; ++i) {
      _msgs.push_back(messages[i]->to_string());
    }
    _cv.notify_all();
    return 0;
  }

  void on_closed(StreamId id) override {
    // The registry entry is still live inside on_closed: capture the
    // close error while it can be read.
    const int err = StreamCloseError(id);
    std::lock_guard<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking)
    _closed = true;
    _close_error = err;
    _cv.notify_all();
  }

  bool Closed() {
    std::lock_guard<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking)
    return _closed;
  }

  // The tbrpc_stream_read contract: 0 message, 1 clean EOF, -1 timeout,
  // positive close error once drained.
  int Read(uint64_t id, int64_t timeout_ms, void** data, size_t* len) {
    std::string msg;
    {
      std::unique_lock<std::mutex> lk(_mu);  // tpulint: allow(fiber-blocking) — plain Python pthread
      auto ready = [&] { return !_msgs.empty() || _closed; };
      if (timeout_ms < 0) {
        _cv.wait(lk, ready);
      } else if (!_cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                               ready)) {
        return -1;
      }
      if (_msgs.empty()) {
        return _close_error != 0 ? _close_error : 1;  // EOF after drain
      }
      msg = std::move(_msgs.front());
      _msgs.pop_front();
    }
    *len = msg.size();
    *data = malloc(msg.size() > 0 ? msg.size() : 1);
    memcpy(*data, msg.data(), msg.size());
    // Feedback advances NOW — the whole point of manual mode (a closed
    // stream makes this a no-op, which is fine: nobody is waiting for
    // credit on it anymore).
    StreamConsume(id, static_cast<int64_t>(msg.size()));
    return 0;
  }

 private:
  std::mutex _mu;  // tpulint: allow(fiber-blocking)
  std::condition_variable _cv;
  std::deque<std::string> _msgs;
  bool _closed = false;
  int _close_error = 0;
};

std::mutex g_streams_mu;
std::unordered_map<uint64_t, std::shared_ptr<StreamReadBuffer>> g_streams;

std::shared_ptr<StreamReadBuffer> find_stream_buf(uint64_t id) {
  std::lock_guard<std::mutex> lk(g_streams_mu);  // tpulint: allow(fiber-blocking)
  auto it = g_streams.find(id);
  return it != g_streams.end() ? it->second : nullptr;
}

}  // namespace

int64_t tbrpc_stream_accept(int64_t max_buf_size) {
  Controller* cntl = t_handler_cntl;
  if (cntl == nullptr) return -1;  // not inside a Python handler
  auto rbuf = std::make_shared<StreamReadBuffer>();
  StreamOptions opts;
  if (max_buf_size > 0) opts.max_buf_size = max_buf_size;
  opts.handler = rbuf.get();
  opts.manual_consumption = true;
  StreamId sid = INVALID_STREAM_ID;
  if (StreamAccept(&sid, *cntl, &opts) != 0) {
    return -1;  // the client didn't attach a stream
  }
  {
    std::lock_guard<std::mutex> lk(g_streams_mu);  // tpulint: allow(fiber-blocking)
    g_streams[sid] = std::move(rbuf);
  }
  return static_cast<int64_t>(sid);
}

int64_t tbrpc_stream_create(void* channel, const char* service_method,
                            const void* req, size_t req_len,
                            int64_t max_buf_size, void** resp,
                            size_t* resp_len, char* errbuf,
                            size_t errbuf_len) {
  auto* box = static_cast<ChannelBox*>(channel);
  if (resp != nullptr) {
    *resp = nullptr;
    *resp_len = 0;
  }
  auto rbuf = std::make_shared<StreamReadBuffer>();
  StreamOptions opts;
  if (max_buf_size > 0) opts.max_buf_size = max_buf_size;
  opts.handler = rbuf.get();
  opts.manual_consumption = true;
  Controller cntl;
  StreamId sid = INVALID_STREAM_ID;
  StreamCreate(&sid, cntl, &opts);
  tbutil::IOBuf request, response;
  if (req_len > 0) request.append(req, req_len);
  box->channel.CallMethod(service_method, &cntl, request, &response,
                          nullptr);
  // `rbuf` must survive until the stream's close COMPLETES (the handler
  // pointer lives in the Stream); StreamWait provides that barrier on
  // both failure paths below.
  if (cntl.Failed()) {
    if (errbuf != nullptr && errbuf_len > 0) {
      snprintf(errbuf, errbuf_len, "%s", cntl.ErrorText().c_str());
    }
    StreamClose(sid);  // idempotent with OnRpcFailed's close
    StreamWait(sid);
    const int code =
        cntl.ErrorCode() != 0 ? cntl.ErrorCode() : TRPC_EINTERNAL;
    return -static_cast<int64_t>(code);
  }
  if (!StreamIsConnected(sid) && !rbuf->Closed()) {
    // RPC succeeded but the handler never called StreamAccept: writers
    // would park forever on a window that can never open. (A stream
    // that WAS accepted but already closed again — the server shed the
    // session before we processed the acceptance, e.g. an
    // already-expired deadline — is handed out instead: its reads drain
    // whatever arrived, then surface the close error.)
    if (errbuf != nullptr && errbuf_len > 0) {
      snprintf(errbuf, errbuf_len, "%s",
               "server answered without accepting a stream");
    }
    StreamClose(sid);
    StreamWait(sid);
    return -static_cast<int64_t>(ENOTCONN);
  }
  if (resp != nullptr) {
    *resp_len = response.size();
    *resp = malloc(response.size() > 0 ? response.size() : 1);
    response.copy_to(*resp, response.size());
  }
  {
    std::lock_guard<std::mutex> lk(g_streams_mu);  // tpulint: allow(fiber-blocking)
    g_streams[sid] = std::move(rbuf);
  }
  return static_cast<int64_t>(sid);
}

int tbrpc_stream_write(uint64_t stream_id, const void* data, size_t len,
                       int64_t timeout_ms) {
  tbutil::IOBuf msg;
  if (len > 0) msg.append(data, len);
  return StreamWriteTimed(stream_id, msg, timeout_ms);
}

int tbrpc_stream_read(uint64_t stream_id, int64_t timeout_ms, void** data,
                      size_t* len) {
  if (data != nullptr) *data = nullptr;
  if (len != nullptr) *len = 0;
  auto rbuf = find_stream_buf(stream_id);
  if (rbuf == nullptr) return -2;
  return rbuf->Read(stream_id, timeout_ms, data, len);
}

int tbrpc_stream_close(uint64_t stream_id, int error_code) {
  // EINVAL when already gone — close is idempotent.
  StreamCloseWithError(stream_id, error_code);
  // Barrier: the close has fully completed (consumer joined, on_closed
  // delivered) before the read buffer the Stream points at can be freed.
  StreamWait(stream_id);
  std::shared_ptr<StreamReadBuffer> rbuf;
  {
    std::lock_guard<std::mutex> lk(g_streams_mu);  // tpulint: allow(fiber-blocking)
    auto it = g_streams.find(stream_id);
    if (it != g_streams.end()) {
      rbuf = std::move(it->second);
      g_streams.erase(it);
    }
  }
  return 0;  // rbuf's last reference may drop here (or with a late reader)
}

// ---------------- serving observability: /sessionz ----------------

namespace {

std::mutex g_sessionz_mu;  // tpulint: allow(fiber-blocking) — pointer swap
tbrpc_sessionz_cb g_sessionz_cb = nullptr;
void* g_sessionz_ctx = nullptr;

void sessionz_page(const HttpRequest& req, HttpResponse* resp) {
  // The mutex is held across the WHOLE scrape (not just the pointer
  // copy): a provider swap — which frees the previous Python trampoline
  // — must not land between reading cb and calling it. Scrapes serialize
  // against each other as a side effect; both are rare and cheap.
  std::lock_guard<std::mutex> lk(g_sessionz_mu);  // tpulint: allow(fiber-blocking)
  tbrpc_sessionz_cb cb = g_sessionz_cb;
  void* ctx = g_sessionz_ctx;
  if (cb == nullptr) {
    resp->status = 404;
    resp->body = "no serving engine registered in this process\n";
    return;
  }
  // The provider is Python: it must run on a callback-pool pthread (GIL
  // pairing) while this fiber BLOCKS its worker — the PassiveStatus gauge
  // discipline (parking could strand the scrape with every worker stuck
  // behind the same page).
  std::string doc;
  const bool ran = PyCallbackPool::instance().RunBlocking([&] {
    // Grow-retry like every copy-out consumer: the document may grow
    // between the size probe and the fill (a session opening mid-scrape
    // must not truncate the JSON).
    int64_t need = cb(ctx, nullptr, 0);
    for (int attempt = 0; attempt < 4 && need > 0; ++attempt) {
      doc.resize(static_cast<size_t>(need) + 1);
      const int64_t got = cb(ctx, doc.data(), doc.size());
      if (got <= 0) {
        doc.clear();
        break;
      }
      if (static_cast<size_t>(got) < doc.size()) {
        doc.resize(static_cast<size_t>(got));
        break;
      }
      need = got;  // grew under us: refetch at the new size
    }
  });
  if (!ran) {
    resp->status = 503;
    resp->body = "python callback pool saturated\n";
    return;
  }
  if (req.query_param("format") == "json") {
    resp->content_type = "application/json";
    resp->body = doc + "\n";
    return;
  }
  std::string& b = resp->body;
  const auto parsed = tbutil::JsonValue::Parse(doc);
  if (!parsed.has_value()) {
    b = "sessionz provider returned unparseable JSON\n" + doc + "\n";
    return;
  }
  auto top_int = [&](const char* key) -> int64_t {
    const tbutil::JsonValue* v = parsed->find(key);
    return v != nullptr ? v->as_int() : 0;
  };
  char line[320];
  snprintf(line, sizeof(line),
           "active sessions: %lld\nkv bytes: %lld\ntokens/s: %lld\n"
           "ttft p99 (us): %lld\ntokens total: %lld\nshed total: %lld\n",
           static_cast<long long>(top_int("active")),
           static_cast<long long>(top_int("kv_bytes")),
           static_cast<long long>(top_int("tokens_per_s")),
           static_cast<long long>(top_int("ttft_p99_us")),
           static_cast<long long>(top_int("tokens_total")),
           static_cast<long long>(top_int("shed_total")));
  b += line;
  // Speculative decoding: cumulative accepted/proposed (0/0 = spec off).
  const int64_t spec_prop = top_int("spec_proposed");
  const int64_t spec_acc = top_int("spec_accepted");
  snprintf(line, sizeof(line),
           "spec accept: %.1f%% (%lld/%lld proposed)\n",
           spec_prop > 0 ? 100.0 * static_cast<double>(spec_acc) /
                               static_cast<double>(spec_prop)
                         : 0.0,
           static_cast<long long>(spec_acc),
           static_cast<long long>(spec_prop));
  b += line;
  // Paged KV: prefix-cache hit rate (aggregate hits/lookups — 0/0 =
  // monolithic mode) + block-pool occupancy.
  const int64_t pfx_hits = top_int("prefix_hits");
  const int64_t pfx_miss = top_int("prefix_misses");
  const int64_t lookups = pfx_hits + pfx_miss;
  snprintf(line, sizeof(line),
           "prefix hit: %.1f%% (%lld/%lld lookups), blocks "
           "free/shared/cached: %lld/%lld/%lld, cow faults: %lld\n\n",
           lookups > 0 ? 100.0 * static_cast<double>(pfx_hits) /
                             static_cast<double>(lookups)
                       : 0.0,
           static_cast<long long>(pfx_hits),
           static_cast<long long>(lookups),
           static_cast<long long>(top_int("kv_blocks_free")),
           static_cast<long long>(top_int("kv_blocks_shared")),
           static_cast<long long>(top_int("kv_blocks_cached")),
           static_cast<long long>(top_int("cow_faults")));
  b += line;
  const tbutil::JsonValue* sessions = parsed->find("sessions");
  if (sessions == nullptr || sessions->size() == 0) {
    b += "(no live sessions)\n";
    return;
  }
  // Per-tenant counts folded from the rows (the JSON carries per-session
  // truth; the rollup is presentation).
  std::map<std::string, int64_t> per_tenant;
  b += "session                tenant        pri state     tokens  "
       "kv_bytes   age_s  pending\n";
  for (size_t i = 0; i < sessions->size(); ++i) {
    const tbutil::JsonValue& s = (*sessions)[i];
    auto fint = [&](const char* key) -> int64_t {
      const tbutil::JsonValue* v = s.find(key);
      return v != nullptr ? v->as_int() : 0;
    };
    auto fstr = [&](const char* key) -> std::string {
      const tbutil::JsonValue* v = s.find(key);
      return v != nullptr ? v->as_string() : "?";
    };
    const std::string tenant = fstr("tenant");
    ++per_tenant[tenant];
    snprintf(line, sizeof(line),
             "%-22s %-13s %3lld %-9s %6lld %9lld %7lld %8lld\n",
             fstr("id").c_str(), tenant.c_str(),
             static_cast<long long>(fint("priority")),
             fstr("state").c_str(), static_cast<long long>(fint("tokens")),
             static_cast<long long>(fint("kv_bytes")),
             static_cast<long long>(fint("age_s")),
             static_cast<long long>(fint("pending")));
    b += line;
  }
  b += "\nper-tenant sessions:\n";
  for (const auto& [tenant, n] : per_tenant) {
    snprintf(line, sizeof(line), "  %-20s %lld\n", tenant.c_str(),
             static_cast<long long>(n));
    b += line;
  }
}

}  // namespace

int tbrpc_sessionz_set_provider(tbrpc_sessionz_cb cb, void* ctx) {
  {
    std::lock_guard<std::mutex> lk(g_sessionz_mu);  // tpulint: allow(fiber-blocking)
    g_sessionz_cb = cb;
    g_sessionz_ctx = ctx;
  }
  static std::once_flag once;
  std::call_once(once, [] { RegisterHttpHandler("/sessionz", sessionz_page); });
  return 0;
}

// ---------------- HTTP streaming fallback ----------------

namespace {

std::mutex g_prog_mu;  // tpulint: allow(fiber-blocking)
uint64_t g_prog_next_id = 1;
std::unordered_map<uint64_t, std::shared_ptr<ProgressiveAttachment>> g_prog;

std::shared_ptr<ProgressiveAttachment> find_progressive(uint64_t id) {
  std::lock_guard<std::mutex> lk(g_prog_mu);  // tpulint: allow(fiber-blocking)
  auto it = g_prog.find(id);
  return it != g_prog.end() ? it->second : nullptr;
}

}  // namespace

int tbrpc_http_stream_register(const char* path, tbrpc_http_stream_cb cb,
                               void* ctx) {
  if (path == nullptr || cb == nullptr) return -1;
  return RegisterHttpHandler(
      path, [cb, ctx](const HttpRequest& req, HttpResponse* resp) {
        // The id is live BEFORE the callback runs: an engine thread the
        // handler hands the session to may emit the first token before
        // the handler returns, and ProgressiveAttachment buffers writes
        // until the response binds the socket.
        auto pa = std::make_shared<ProgressiveAttachment>();
        uint64_t pid;
        {
          std::lock_guard<std::mutex> lk(g_prog_mu);  // tpulint: allow(fiber-blocking)
          pid = g_prog_next_id++;
          g_prog[pid] = pa;
        }
        void* body = nullptr;
        size_t body_len = 0;
        int use_progressive = 0;
        int status = 200;
        const std::string path_copy = req.path;
        const std::string query = req.query;
        const TraceContext trace_ctx = current_trace_context();
        const bool ran = PyCallbackPool::instance().Run([&] {
          ScopedTraceContext scope(trace_ctx.trace_id, trace_ctx.span_id);
          cb(ctx, path_copy.c_str(), query.c_str(), pid, &body, &body_len,
             &use_progressive, &status);
        });
        if (!ran) {
          std::lock_guard<std::mutex> lk(g_prog_mu);  // tpulint: allow(fiber-blocking)
          g_prog.erase(pid);
          resp->status = 503;
          resp->body = "python callback pool saturated\n";
          free(body);
          return;
        }
        resp->status = status;
        if (body != nullptr && body_len > 0) {
          resp->body.assign(static_cast<const char*>(body), body_len);
        }
        free(body);
        if (use_progressive != 0) {
          resp->progressive = pa;
        } else {
          std::lock_guard<std::mutex> lk(g_prog_mu);  // tpulint: allow(fiber-blocking)
          g_prog.erase(pid);
        }
      });
}

int tbrpc_progressive_write(uint64_t progressive_id, const void* data,
                            size_t len) {
  auto pa = find_progressive(progressive_id);
  if (pa == nullptr) return -1;
  tbutil::IOBuf chunk;
  if (len > 0) chunk.append(data, len);
  return pa->Write(chunk);
}

int tbrpc_progressive_close(uint64_t progressive_id) {
  std::shared_ptr<ProgressiveAttachment> pa;
  {
    std::lock_guard<std::mutex> lk(g_prog_mu);  // tpulint: allow(fiber-blocking)
    auto it = g_prog.find(progressive_id);
    if (it != g_prog.end()) {
      pa = std::move(it->second);
      g_prog.erase(it);
    }
  }
  if (pa != nullptr) pa->Close();
  return 0;
}

// ---------------- echo load loop (tbrpc_bench_echo_qps) ----------------

namespace {

struct BenchEnv {
  ServerBox* server;
  ChannelBox* channel = nullptr;
  bool ok = false;

  BenchEnv() {
    server = new ServerBox;
    tbrpc_server_add_echo_service(server);
    // The native echo handler is non-blocking: register it on the inline
    // fast path.
    tbrpc_server_set_inline(server, "EchoService", 1);
    int port = tbrpc_server_start(server, "127.0.0.1:0");
    if (port <= 0) return;
    char addr[48];
    snprintf(addr, sizeof(addr), "127.0.0.1:%d", port);
    auto* box = new ChannelBox;
    ChannelOptions opts;
    opts.timeout_ms = 20000;
    opts.max_retry = 0;
    if (box->channel.Init(addr, &opts) != 0) {
      delete box;
      return;
    }
    channel = box;
    ok = true;
  }
  ~BenchEnv() {
    if (channel != nullptr) tbrpc_channel_destroy(channel);
    tbrpc_server_stop(server);
    tbrpc_server_destroy(server);
  }
};

}  // namespace

double tbrpc_bench_echo_qps(int seconds, int concurrency, double* p99_us_out) {
  BenchEnv env;
  if (!env.ok) return 0;
  if (concurrency < 1) concurrency = 1;
  std::atomic<int64_t> total_calls{0};
  std::atomic<bool> stop{false};
  std::mutex lat_mu;
  std::vector<int64_t> latencies;
  const std::string payload(4, 'b');
  // Callers are FIBERS, the framework's native concurrency unit (the
  // reference's multi_threaded_echo benchmarks drive with bthreads the
  // same way): a parked fiber caller wakes by a queue push on an already
  // running worker — no per-RPC futex wake/wait pair, which dominated the
  // small-RPC profile with pthread callers.
  struct CallerArg {
    BenchEnv* env;
    std::atomic<bool>* stop;
    std::atomic<int64_t>* total_calls;
    std::mutex* lat_mu;
    std::vector<int64_t>* latencies;
    const std::string* payload;
  };
  auto caller = [](void* argv) -> void* {
    auto* a = static_cast<CallerArg*>(argv);
    std::vector<int64_t> local;
    local.reserve(1 << 14);
    while (!a->stop->load(std::memory_order_relaxed)) {
      Controller cntl;
      tbutil::IOBuf request, response;
      request.append("x");
      cntl.request_attachment().append(*a->payload);
      a->env->channel->channel.CallMethod("EchoService/Echo", &cntl,
                                          request, &response, nullptr);
      if (!cntl.Failed()) {
        a->total_calls->fetch_add(1, std::memory_order_relaxed);
        local.push_back(cntl.latency_us());
      }
    }
    std::lock_guard<std::mutex> lk(*a->lat_mu);
    a->latencies->insert(a->latencies->end(), local.begin(), local.end());
    delete a;
    return nullptr;
  };
  std::vector<tbthread::fiber_t> fibers(concurrency);
  for (int t = 0; t < concurrency; ++t) {
    auto* arg = new CallerArg{&env, &stop, &total_calls, &lat_mu,
                              &latencies, &payload};
    if (tbthread::fiber_start_background(&fibers[t], nullptr, caller, arg) !=
        0) {
      delete arg;
      fibers[t] = 0;
    }
  }
  const int64_t t0 = tbutil::monotonic_time_us();
  std::this_thread::sleep_for(std::chrono::seconds(seconds));
  stop.store(true);
  for (auto& f : fibers) {
    if (f != 0) tbthread::fiber_join(f, nullptr);
  }
  const double elapsed_s = (tbutil::monotonic_time_us() - t0) / 1e6;
  if (p99_us_out != nullptr) {
    *p99_us_out = 0;
    if (!latencies.empty()) {
      std::sort(latencies.begin(), latencies.end());
      *p99_us_out = static_cast<double>(
          latencies[static_cast<size_t>(latencies.size() * 0.99)]);
    }
  }
  return static_cast<double>(total_calls.load()) / elapsed_s;
}
