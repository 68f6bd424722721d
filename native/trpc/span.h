// rpcz: per-RPC span collection with trace propagation.
// Capability parity: reference src/brpc/span.h:47-69 (Span with
// trace/span/parent ids riding the RpcMeta; collected per leg) +
// builtin/rpcz_service.cpp (the /rpcz page). Differences by design: spans
// land in a fixed ring (no disk spill), and the cross-call context rides a
// fiber-local slot (the reference uses bthread-local storage the same way).
//
// Propagation: a server handler's fiber carries {trace_id, span_id} while
// the handler runs; any Channel::CallMethod issued from it stamps
// parent_span_id = the server span, same trace_id — so a client -> A -> B
// chain renders as one linked trace at /rpcz.
#pragma once

#include <atomic>
#include <cstdint>
#include <string>
#include <vector>

#include "tbutil/endpoint.h"

namespace trpc {

struct Span {
  uint64_t trace_id = 0;
  uint64_t span_id = 0;
  uint64_t parent_span_id = 0;
  bool server_side = false;
  int64_t start_us = 0;   // gettimeofday clock
  int64_t end_us = 0;
  int error_code = 0;
  std::string service_method;
  tbutil::EndPoint remote_side;
  // Stage annotations ("device_put=812us") attached while the span was
  // active — AnnotateSpan buffers them by span_id; Record drains the buffer
  // into the span. The Python data plane reports its stage timings here.
  std::vector<std::string> annotations;
};

// Fixed ring of the most recent spans (rpcz_max_spans flag). Recording is
// gated on the rpcz_enabled flag at the CALL SITES, not here.
class SpanStore {
 public:
  void Record(Span&& span);
  // Most-recent-first. trace_id != 0 filters to one trace.
  void Dump(std::vector<Span>* out, uint64_t trace_id = 0);
  static SpanStore& global();

 private:
  struct Impl;
  Impl* _impl;
  SpanStore();
};

// True when spans should be collected (rpcz_enabled flag, hot-path cached).
bool rpcz_enabled();
// The flag's storage, stable for the process: callers that cannot afford a
// function call per check (Python, through ctypes) read it in place.
const std::atomic<int64_t>* rpcz_enabled_flag();

// Head sampling for always-on production rpcz (rpcz_sample_1_in_n flag,
// default 1 = every trace). Consulted ONLY where a NEW root trace would be
// minted (a client call with no surrounding context; a server request whose
// wire meta carries no trace_id): true = collect this root. Spans that are
// already part of a sampled trace are never re-gated — a sampled trace
// stays complete across every process it touches, because only sampled
// clients stamp trace ids onto the wire. 1-in-n is probabilistic
// (fast_rand), so concurrent callers need no shared counter line.
bool rpcz_sample_root();
// Current rpcz_sample_1_in_n value (>= 1).
int64_t rpcz_sample_1_in_n();

// The collected spans as a JSON array string (newest first; trace_id != 0
// filters to one trace, oldest first) — one renderer shared by the capi
// dump (tbrpc_rpcz_dump_json) and the console's /rpcz?format=json, so the
// cross-process scrape the fleet observer does cannot drift from the
// in-process dump.
std::string RpczDumpJson(uint64_t trace_id);

// Fiber-local trace context (valid while a traced handler runs).
struct TraceContext {
  uint64_t trace_id = 0;
  uint64_t span_id = 0;
};
TraceContext current_trace_context();
void set_current_trace_context(const TraceContext& ctx);
void clear_current_trace_context();

// Non-zero random id (fast_rand based).
uint64_t new_trace_or_span_id();

// Attach a stage annotation to a span that is still ACTIVE (its Record has
// not happened yet). Buffered by span_id in a capped pending store; the
// matching Record drains it into Span::annotations. No-op when span_id == 0.
void AnnotateSpan(uint64_t span_id, const std::string& text);

// Record an externally-timed span (the capi path for Python-created spans:
// trace_span() times the body in Python and emits the result here). No-op
// when span_id == 0.
void EmitSpan(uint64_t trace_id, uint64_t span_id, uint64_t parent_span_id,
              bool server_side, int64_t start_us, int64_t end_us,
              int error_code, const std::string& name);

// One server leg, shared by every server protocol (tstd/HTTP/h2): no-op
// when span_id == 0.
void RecordServerSpan(uint64_t trace_id, uint64_t span_id,
                      uint64_t parent_span_id, int64_t start_us,
                      int64_t latency_us, int error_code,
                      const std::string& service_method,
                      const tbutil::EndPoint& remote);

// RAII fiber trace context for the synchronous part of a traced handler;
// no-op when span_id == 0.
class ScopedTraceContext {
 public:
  ScopedTraceContext(uint64_t trace_id, uint64_t span_id)
      : _active(span_id != 0) {
    if (_active) set_current_trace_context({trace_id, span_id});
  }
  ~ScopedTraceContext() {
    if (_active) clear_current_trace_context();
  }
  ScopedTraceContext(const ScopedTraceContext&) = delete;
  ScopedTraceContext& operator=(const ScopedTraceContext&) = delete;

 private:
  bool _active;
};

}  // namespace trpc
