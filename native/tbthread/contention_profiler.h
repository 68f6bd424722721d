// Contention profiler: which call stacks spend time WAITING on FiberMutex
// (reference bthread/mutex.cpp:122-151 ContentionProfiler). The FiberMutex
// fast path is untouched; the contended slow path, when profiling is on,
// measures the wait and offers it to a rate-limited SampleCollector
// (tbvar/collector.h) which caps per-second capture cost. Rendered at the
// /contention console page.
#pragma once

#include <atomic>
#include <cstdint>
#include <string>

namespace tbthread {

namespace contention_internal {
extern std::atomic<bool> g_enabled;
// Slow-path callback: wait_ns spent blocked before acquiring (ns, so a
// sub-microsecond wait still counts). Captures the
// caller's stack (exact fiber bounds when on a fiber) under the collector's
// speed limit.
void Record(int64_t wait_ns);
}  // namespace contention_internal

inline bool contention_profiling_enabled() {
  return contention_internal::g_enabled.load(std::memory_order_relaxed);
}

void contention_profiling_start();
void contention_profiling_stop();   // keeps the data for rendering
void contention_profiling_reset();  // drops the data

// Human-readable report: stacks by total wait time.
std::string contention_report(size_t topn = 30);

}  // namespace tbthread
