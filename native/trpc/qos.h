// Request QoS: priority lanes + tenant identity + deadline propagation.
//
// The overload-protection plane's shared currency (ISSUE 9). A request
// carries an optional priority class and tenant id in its tstd meta
// (kTstdFlagHasQos — the wire is byte-identical when neither is set), and
// the server's admission point (Server::BeginRequest) uses them to keep
// the control plane live while bulk traffic saturates:
//
//   HIGH    control-plane RPCs (heartbeats, version polls, Epoch/Meta,
//           migrator handshakes): admitted up to the FULL concurrency gate.
//   NORMAL  unmarked legacy traffic: full gate, no reservation.
//   BULK    tensor pull/push: admitted only while the gate keeps
//           10 percent of slots free, so a saturating bulk client can
//           never occupy the last slots a heartbeat needs.
//
// The AMBIENT QoS CONTEXT is the cross-call propagation vehicle — the same
// fiber-local (thread-local off-fiber) slot discipline as the rpcz trace
// context (span.h): a client sets priority/tenant around its calls; a
// server handler runs inside a scope carrying the REQUEST's tenant,
// priority and absolute deadline, so nested RPCs it issues inherit all
// three — in particular the deadline clamps to min(own timeout, parent
// remaining) in Channel::CallMethod, and a doomed request stops consuming
// downstream capacity instead of timing out independently at every hop.
#pragma once

#include <cstdint>
#include <string>

namespace trpc {

enum RequestPriority {
  PRIORITY_HIGH = 0,
  PRIORITY_NORMAL = 1,  // the unmarked-wire default
  PRIORITY_BULK = 2,
};

// Clamp an untrusted wire/capi value onto the enum.
inline int clamp_priority(int p) {
  return p < PRIORITY_HIGH ? PRIORITY_HIGH
                           : (p > PRIORITY_BULK ? PRIORITY_BULK : p);
}

struct QosContext {
  int priority = PRIORITY_NORMAL;
  std::string tenant;       // empty = unset (server falls back to peer ip)
  int64_t deadline_us = 0;  // absolute, gettimeofday clock; 0 = none
};

// Fiber-local (thread-local off-fiber) ambient QoS — same slot discipline
// as current_trace_context().
QosContext current_qos_context();
void set_current_qos_context(const QosContext& ctx);
void clear_current_qos_context();

// RAII server-handler scope: carries the request's QoS (tenant, priority,
// deadline) across the synchronous part of the handler — and, via the
// explicit hand-off in the capi callback services, across the Python
// callback-pool thread — so nested client calls inherit it.
class ScopedQosContext {
 public:
  explicit ScopedQosContext(const QosContext& ctx) {
    _prev = current_qos_context();
    set_current_qos_context(ctx);
  }
  ~ScopedQosContext() { set_current_qos_context(_prev); }
  ScopedQosContext(const ScopedQosContext&) = delete;
  ScopedQosContext& operator=(const ScopedQosContext&) = delete;

 private:
  QosContext _prev;
};

}  // namespace trpc
