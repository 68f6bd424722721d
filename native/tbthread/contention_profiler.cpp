#include "tbthread/contention_profiler.h"

#include <dlfcn.h>
#include <execinfo.h>

#include <cstdio>
#include <vector>

#include "tbthread/task_group.h"
#include "tbthread/task_meta.h"
#include "tbvar/collector.h"

namespace tbthread {

namespace {

tbvar::SampleCollector& collector() {
  // 200 contention samples/sec: plenty for attribution, bounded cost.
  static auto* c = new tbvar::SampleCollector(200);
  return *c;
}

// Self stack walk. On a fiber: frame-pointer chain bounded to the fiber's
// exact stack (libgcc's unwinder does not understand context.S stacks).
// On a plain pthread: libc backtrace() — safe outside signal context.
size_t self_stack(void** pcs, size_t max) {
  TaskGroup* g = TaskGroup::current();
  TaskMeta* m = g != nullptr ? g->cur_meta() : nullptr;
  if (m == nullptr || m->stack == nullptr || m->stack->stack_base == nullptr) {
    const int n = backtrace(pcs, static_cast<int>(max));
    return n > 0 ? static_cast<size_t>(n) : 0;
  }
  const uintptr_t lo = reinterpret_cast<uintptr_t>(m->stack->stack_base);
  const uintptr_t hi = lo + m->stack->stack_size;
  uintptr_t rbp = reinterpret_cast<uintptr_t>(__builtin_frame_address(0));
  size_t n = 0;
  while (n < max) {
    if (rbp < lo || rbp + 16 > hi || (rbp & 7) != 0) break;
    void* ret = *reinterpret_cast<void**>(rbp + 8);
    if (ret == nullptr) break;
    pcs[n++] = ret;
    const uintptr_t next = *reinterpret_cast<uintptr_t*>(rbp);
    if (next <= rbp) break;
    rbp = next;
  }
  return n;
}

std::string symbolize(void* pc) {
  Dl_info info;
  char buf[256];
  if (dladdr(pc, &info) != 0 && info.dli_sname != nullptr) {
    return info.dli_sname;
  }
  snprintf(buf, sizeof(buf), "%p", pc);
  return buf;
}

}  // namespace

namespace contention_internal {

std::atomic<bool> g_enabled{false};

void Record(int64_t wait_ns) {
  if (!collector().Admit()) return;
  void* pcs[24];
  const size_t n = self_stack(pcs, 24);
  if (n == 0) return;
  // No frames are skipped: FiberMutex::lock is header-inline, so the
  // first return address (out of Record) already lands in the CONTENDED
  // CALL SITE itself.
  std::vector<void*> stack(pcs, pcs + n);
  collector().Add(stack, wait_ns);
}

}  // namespace contention_internal

void contention_profiling_start() {
  contention_internal::g_enabled.store(true, std::memory_order_relaxed);
}

void contention_profiling_stop() {
  contention_internal::g_enabled.store(false, std::memory_order_relaxed);
}

void contention_profiling_reset() { collector().Reset(); }

std::string contention_report(size_t topn) {
  const auto entries = collector().Snapshot();
  std::string out;
  char line[256];
  snprintf(line, sizeof(line),
           "%zu contended stack(s); %lld sample(s) kept, %lld over the "
           "speed limit\n",
           entries.size(), static_cast<long long>(collector().admitted()),
           static_cast<long long>(collector().rejected()));
  out += line;
  size_t shown = 0;
  for (const auto& e : entries) {
    if (shown++ >= topn) break;
    // Totals under a microsecond render in ns rather than as "0us".
    const bool sub_us = e.total < 1000;
    snprintf(line, sizeof(line), "-- waited %lld%s total over %lld hit(s):\n",
             static_cast<long long>(sub_us ? e.total : e.total / 1000),
             sub_us ? "ns" : "us", static_cast<long long>(e.count));
    out += line;
    for (void* pc : e.stack) {
      out += "    ";
      out += symbolize(pc);
      out += '\n';
    }
  }
  return out;
}

}  // namespace tbthread
