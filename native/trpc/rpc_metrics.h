// Framework-level metrics wired into the RPC hot paths.
// Capability parity: reference per-method MethodStatus
// (details/method_status.h: per-method latency/qps/concurrency exposed as
// bvars) + client-side LatencyRecorders + socket byte counters feeding
// /vars and /brpc_metrics.
#pragma once

#include <cstdint>
#include <mutex>
#include <string>
#include <unordered_map>

#include "tbvar/tbvar.h"

namespace trpc {

// Per-(service/method) server-side stats, created lazily on first request.
// Entries are immortal — hot paths cache the pointer.
class MethodStatus {
 public:
  explicit MethodStatus(const std::string& full_name);

  void OnRequested() { _concurrency << 1; }
  void OnResponded(int error_code, int64_t latency_us) {
    _concurrency << -1;
    if (error_code == 0) {
      _latency << latency_us;
    } else {
      _errors << 1;
    }
  }

  int64_t concurrency() const { return _concurrency.get_value(); }
  int64_t error_count() const { return _errors.get_value(); }
  const tbvar::LatencyRecorder& latency() const { return _latency; }

 private:
  tbvar::Adder<int64_t> _concurrency;
  tbvar::Adder<int64_t> _errors;
  tbvar::LatencyRecorder _latency;
};

MethodStatus* GetMethodStatus(const std::string& service_method);

// Global counters (exposed as rpc_client_*, rpc_socket_*, rpc_shed_*).
struct GlobalRpcMetrics {
  tbvar::LatencyRecorder client_latency{60};
  tbvar::Adder<int64_t> client_errors;
  tbvar::Adder<int64_t> client_backup_requests;
  tbvar::Adder<int64_t> bytes_in;
  tbvar::Adder<int64_t> bytes_out;
  tbvar::Adder<int64_t> connections_accepted;
  // Overload-protection plane (server admission, server.cpp): why requests
  // were shed, and the per-lane server latency (HIGH-lane p99 must stay
  // flat while BULK saturates; tests/test_overload.py reads it).
  tbvar::Adder<int64_t> shed_total;     // every shed, any reason
  tbvar::Adder<int64_t> shed_bulk;      // BULK lane lost its headroom race
  tbvar::Adder<int64_t> shed_tenant;    // per-tenant quota
  tbvar::Adder<int64_t> shed_deadline;  // propagated deadline already gone
  tbvar::LatencyRecorder server_high_latency{60};  // rpc_server_lane_high
  tbvar::LatencyRecorder server_bulk_latency{60};  // rpc_server_lane_bulk

  static GlobalRpcMetrics& instance();

 private:
  GlobalRpcMetrics();
};

}  // namespace trpc
