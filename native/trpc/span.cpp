#include "trpc/span.h"

#include <deque>
#include <map>
#include <mutex>

#include "tbthread/key.h"
#include "tbutil/fast_rand.h"
#include "tbutil/json.h"
#include "trpc/flags.h"

namespace trpc {

static auto* g_rpcz_enabled = TRPC_DEFINE_FLAG(
    rpcz_enabled, 0, "collect per-RPC spans for /rpcz (1 = on)");
static auto* g_rpcz_max_spans = TRPC_DEFINE_FLAG(
    rpcz_max_spans, 2048, "span ring capacity (applied at first record)");
// Production keeps rpcz live at bounded cost: only 1 of every N NEW root
// traces is collected (validator keeps the divisor sane; 1 = every trace).
// Registered through FlagRegistry so /flags/rpcz_sample_1_in_n?setvalue=N
// and tbrpc_flag_set reload it live.
static auto* g_rpcz_sample_1_in_n = FlagRegistry::global().DefineInt(
    "rpcz_sample_1_in_n", 1,
    "collect 1 of every N new root traces while rpcz is on (1 = all)",
    [](int64_t v) { return v >= 1 && v <= (int64_t{1} << 32); });

bool rpcz_enabled() {
  return g_rpcz_enabled->load(std::memory_order_relaxed) != 0;
}

const std::atomic<int64_t>* rpcz_enabled_flag() { return g_rpcz_enabled; }

int64_t rpcz_sample_1_in_n() {
  const int64_t n = g_rpcz_sample_1_in_n->load(std::memory_order_relaxed);
  return n >= 1 ? n : 1;
}

bool rpcz_sample_root() {
  const int64_t n = g_rpcz_sample_1_in_n->load(std::memory_order_relaxed);
  if (n <= 1) return true;
  return tbutil::fast_rand() % static_cast<uint64_t>(n) == 0;
}

uint64_t new_trace_or_span_id() {
  uint64_t id;
  do {
    id = tbutil::fast_rand();
  } while (id == 0);
  return id;
}

// ---------------- pending annotations ----------------
// Annotations arrive while a span is active (before its Record). Buffered
// here by span_id; Record drains matches. Capped: a span whose Record never
// comes (rpcz flipped off mid-flight, handler crashed) must not leak — the
// oldest span's buffer is dropped once kMaxPendingSpans is reached.

namespace {

struct PendingAnnotations {
  // O(1)-bounded critical sections (map insert/erase, capped), no parking
  // inside — same discipline as SpanStore's ring mutex below.
  std::mutex mu;  // tpulint: allow(fiber-blocking)
  std::map<uint64_t, std::vector<std::string>> by_span;
  std::deque<uint64_t> order;  // insertion order, for eviction
};

PendingAnnotations& pending_annotations() {
  static PendingAnnotations* p = new PendingAnnotations;
  return *p;
}

constexpr size_t kMaxPendingSpans = 1024;
constexpr size_t kMaxAnnotationsPerSpan = 64;
constexpr size_t kMaxAnnotationLen = 256;

}  // namespace

void AnnotateSpan(uint64_t span_id, const std::string& text) {
  if (span_id == 0) return;
  PendingAnnotations& p = pending_annotations();
  std::lock_guard<std::mutex> lk(p.mu);  // tpulint: allow(fiber-blocking)
  auto it = p.by_span.find(span_id);
  if (it == p.by_span.end()) {
    while (p.order.size() >= kMaxPendingSpans) {
      p.by_span.erase(p.order.front());
      p.order.pop_front();
    }
    it = p.by_span.emplace(span_id, std::vector<std::string>()).first;
    p.order.push_back(span_id);
  }
  if (it->second.size() >= kMaxAnnotationsPerSpan) return;
  it->second.push_back(text.size() <= kMaxAnnotationLen
                           ? text
                           : text.substr(0, kMaxAnnotationLen));
}

static void drain_annotations(Span* span) {
  PendingAnnotations& p = pending_annotations();
  std::lock_guard<std::mutex> lk(p.mu);  // tpulint: allow(fiber-blocking)
  auto it = p.by_span.find(span->span_id);
  if (it == p.by_span.end()) return;
  span->annotations = std::move(it->second);
  p.by_span.erase(it);
  // The deque entry stays until eviction wraps around; a stale id with no
  // map entry is skipped for free there.
}

// ---------------- ring store ----------------

struct SpanStore::Impl {
  std::mutex mu;
  std::vector<Span> ring;  // sized lazily from the flag
  size_t next = 0;         // ring cursor
  uint64_t seq = 0;        // total recorded (recency ordering)
  std::vector<uint64_t> seqs;
};

SpanStore::SpanStore() : _impl(new Impl) {}

void SpanStore::Record(Span&& span) {
  drain_annotations(&span);
  std::lock_guard<std::mutex> lk(_impl->mu);
  if (_impl->ring.empty()) {
    size_t cap = static_cast<size_t>(
        g_rpcz_max_spans->load(std::memory_order_relaxed));
    if (cap < 16) cap = 16;
    _impl->ring.resize(cap);
    _impl->seqs.assign(cap, 0);
  }
  _impl->ring[_impl->next] = std::move(span);
  _impl->seqs[_impl->next] = ++_impl->seq;
  _impl->next = (_impl->next + 1) % _impl->ring.size();
}

void SpanStore::Dump(std::vector<Span>* out, uint64_t trace_id) {
  out->clear();
  std::lock_guard<std::mutex> lk(_impl->mu);
  const size_t n = _impl->ring.size();
  if (n == 0) return;
  // Walk backward from the cursor: most recent first.
  for (size_t i = 0; i < n; ++i) {
    const size_t idx = (_impl->next + n - 1 - i) % n;
    if (_impl->seqs[idx] == 0) break;  // never filled
    const Span& s = _impl->ring[idx];
    if (trace_id != 0 && s.trace_id != trace_id) continue;
    out->push_back(s);
  }
}

SpanStore& SpanStore::global() {
  static SpanStore* s = new SpanStore;
  return *s;
}

void RecordServerSpan(uint64_t trace_id, uint64_t span_id,
                      uint64_t parent_span_id, int64_t start_us,
                      int64_t latency_us, int error_code,
                      const std::string& service_method,
                      const tbutil::EndPoint& remote) {
  if (span_id == 0) return;
  Span sp;
  sp.trace_id = trace_id;
  sp.span_id = span_id;
  sp.parent_span_id = parent_span_id;
  sp.server_side = true;
  sp.start_us = start_us;
  sp.end_us = start_us + latency_us;
  sp.error_code = error_code;
  sp.service_method = service_method;
  sp.remote_side = remote;
  SpanStore::global().Record(std::move(sp));
}

void EmitSpan(uint64_t trace_id, uint64_t span_id, uint64_t parent_span_id,
              bool server_side, int64_t start_us, int64_t end_us,
              int error_code, const std::string& name) {
  if (span_id == 0) return;
  Span sp;
  sp.trace_id = trace_id;
  sp.span_id = span_id;
  sp.parent_span_id = parent_span_id;
  sp.server_side = server_side;
  sp.start_us = start_us;
  sp.end_us = end_us;
  sp.error_code = error_code;
  sp.service_method = name;
  SpanStore::global().Record(std::move(sp));
}

std::string RpczDumpJson(uint64_t trace_id) {
  std::vector<Span> spans;
  SpanStore::global().Dump(&spans, trace_id);
  if (trace_id != 0) std::reverse(spans.begin(), spans.end());  // oldest 1st
  char hex[20];
  tbutil::JsonValue arr = tbutil::JsonValue::Array();
  for (const Span& s : spans) {
    tbutil::JsonValue o = tbutil::JsonValue::Object();
    // Ids as 16-digit hex strings: they are opaque u64 tokens (JSON
    // numbers would lose the top bit), and /rpcz?trace= takes hex.
    snprintf(hex, sizeof(hex), "%016llx",
             static_cast<unsigned long long>(s.trace_id));
    o.set("trace_id", hex);
    snprintf(hex, sizeof(hex), "%016llx",
             static_cast<unsigned long long>(s.span_id));
    o.set("span_id", hex);
    snprintf(hex, sizeof(hex), "%016llx",
             static_cast<unsigned long long>(s.parent_span_id));
    o.set("parent_span_id", hex);
    o.set("server_side", s.server_side);
    o.set("start_us", s.start_us);
    o.set("end_us", s.end_us);
    o.set("latency_us", s.end_us - s.start_us);
    o.set("error_code", s.error_code);
    o.set("service_method", s.service_method);
    o.set("peer", tbutil::endpoint2str(s.remote_side));
    tbutil::JsonValue ann = tbutil::JsonValue::Array();
    for (const std::string& a : s.annotations) ann.push_back(a);
    o.set("annotations", std::move(ann));
    arr.push_back(std::move(o));
  }
  return arr.Dump();
}

// ---------------- fiber-local context ----------------

namespace {

void trace_ctx_dtor(void* p) { delete static_cast<TraceContext*>(p); }

tbthread::FiberKey trace_key() {
  static tbthread::FiberKey key = [] {
    tbthread::FiberKey k;
    tbthread::fiber_key_create(&k, trace_ctx_dtor);
    return k;
  }();
  return key;
}

}  // namespace

TraceContext current_trace_context() {
  auto* ctx =
      static_cast<TraceContext*>(tbthread::fiber_getspecific(trace_key()));
  return ctx != nullptr ? *ctx : TraceContext{};
}

void set_current_trace_context(const TraceContext& ctx) {
  auto* cur =
      static_cast<TraceContext*>(tbthread::fiber_getspecific(trace_key()));
  if (cur == nullptr) {
    cur = new TraceContext;
    tbthread::fiber_setspecific(trace_key(), cur);
  }
  *cur = ctx;
}

void clear_current_trace_context() {
  auto* cur =
      static_cast<TraceContext*>(tbthread::fiber_getspecific(trace_key()));
  if (cur != nullptr) *cur = TraceContext{};  // keep the allocation
}

}  // namespace trpc
