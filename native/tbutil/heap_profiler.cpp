// Heap profiler backend + global operator new/delete overrides.
// Reference role: tcmalloc's sampling heap profile behind bRPC's heap
// profiler console (details/tcmalloc_extension.cpp); mechanism is our own —
// TLS byte-countdown sampling in the new/delete overrides, frame-pointer
// stacks, live map of sampled pointers.
//
// ASan/TSan builds: the overrides would fight the sanitizers' own
// new/delete interposers (TSan's win symbol resolution outright, so ours
// never run), so the whole override block compiles out (the explicit
// RecordAlloc / RecordFree hooks still work).
#include "tbutil/heap_profiler.h"
#include "tbthread/sanitizer_fiber.h"  // canonical __SANITIZE_ADDRESS__ detection

#include <pthread.h>

#include <algorithm>
#include <atomic>
#include <cstddef>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <new>
#include <unordered_map>
#include <vector>

#include "tbthread/task_group.h"
#include "tbthread/task_meta.h"
#include "tbutil/stack_walk.h"

namespace tbutil {

namespace {

struct SampledAlloc {
  uint32_t depth;
  void* pcs[stack_walk::kMaxDepth];
  size_t size;    // actual bytes of this allocation
  size_t weight;  // estimated bytes represented (>= size)
};

std::atomic<bool> g_running{false};
std::atomic<size_t> g_period{512 << 10};
// Non-zero while sampled pointers might be in the live map — the only cost
// a free pays when profiling never ran is one relaxed load of this.
std::atomic<size_t> g_live_count{0};

// Leaked on purpose: frees can arrive during static destruction.
std::mutex* g_mu = new std::mutex;
auto* g_live = new std::unordered_map<void*, SampledAlloc>;
// Serializes Start/Stop lifecycle transitions.
std::mutex* g_lifecycle_mu = new std::mutex;

// Approximate membership of sampled pointers (a Bloom filter: set-only
// during a window, cleared at Start). Lets the free path skip g_mu for the
// ~99.8% of deletes that were never sampled — without it every delete in
// the process serializes on one mutex while a window is open. False
// positives just pay the lock.
constexpr size_t kBloomWords = 1024;  // 64Kbit
std::atomic<uint64_t> g_bloom[kBloomWords];

inline uint64_t mix_ptr(void* p, uint64_t salt) {
  uint64_t x = reinterpret_cast<uintptr_t>(p) + salt;
  x ^= x >> 33;
  x *= 0xff51afd7ed558ccdULL;
  x ^= x >> 33;
  return x;
}

inline void bloom_add(void* p) {
  const uint64_t h1 = mix_ptr(p, 0x9e3779b97f4a7c15ULL);
  const uint64_t h2 = mix_ptr(p, 0xc2b2ae3d27d4eb4fULL);
  g_bloom[(h1 >> 6) % kBloomWords].fetch_or(1ULL << (h1 & 63),
                                            std::memory_order_relaxed);
  g_bloom[(h2 >> 6) % kBloomWords].fetch_or(1ULL << (h2 & 63),
                                            std::memory_order_relaxed);
}

inline bool bloom_maybe_contains(void* p) {
  const uint64_t h1 = mix_ptr(p, 0x9e3779b97f4a7c15ULL);
  const uint64_t h2 = mix_ptr(p, 0xc2b2ae3d27d4eb4fULL);
  return (g_bloom[(h1 >> 6) % kBloomWords].load(std::memory_order_relaxed) &
          (1ULL << (h1 & 63))) != 0 &&
         (g_bloom[(h2 >> 6) % kBloomWords].load(std::memory_order_relaxed) &
          (1ULL << (h2 & 63))) != 0;
}

// Re-entrancy guard: the live map's own rehash/insert allocates, and any
// public entry point that mutates/reads the map under g_mu allocates too
// (map nodes, symbol strings) — those inner new/delete calls must bypass
// the hooks or they self-deadlock on g_mu.
thread_local bool tls_in_hook = false;

struct HookGuard {
  HookGuard() { tls_in_hook = true; }
  ~HookGuard() { tls_in_hook = false; }
};
thread_local intptr_t tls_countdown = 0;
// First tracked allocation on a thread arms the countdown with a full
// period — sampling it unconditionally would attribute a whole period of
// phantom bytes to whatever incidental site allocates first (tcmalloc
// arms the same way).
thread_local bool tls_armed = false;

// Stack bounds of the current thread (fiber-aware), for the bounded walk.
void current_stack_bounds(uintptr_t sp, uintptr_t* lo, uintptr_t* hi) {
  *lo = 1;
  *hi = 0;  // empty window: PC-only
  if (tbthread::TaskGroup* g = tbthread::TaskGroup::current()) {
    if (tbthread::TaskMeta* m = g->cur_meta()) {
      if (m->stack != nullptr && m->stack->stack_base != nullptr) {
        const uintptr_t base =
            reinterpret_cast<uintptr_t>(m->stack->stack_base);
        if (sp >= base && sp < base + m->stack->stack_size) {
          *lo = base;
          *hi = base + m->stack->stack_size;
          return;
        }
      }
    }
  }
  // Plain pthread: bounds cached per-thread. pthread_getattr_np may
  // allocate (main thread parses /proc/self/maps) — tls_in_hook is already
  // set by our caller, so that recursion skips sampling.
  static thread_local uintptr_t t_lo = 0, t_hi = 0;
  if (t_lo == 0) {
    pthread_attr_t attr;
    if (pthread_getattr_np(pthread_self(), &attr) == 0) {
      void* addr = nullptr;
      size_t size = 0;
      pthread_attr_getstack(&attr, &addr, &size);
      pthread_attr_destroy(&attr);
      t_lo = reinterpret_cast<uintptr_t>(addr);
      t_hi = t_lo + size;
    } else {
      t_lo = 1;  // mark probed; keep empty window
      t_hi = 0;
    }
  }
  if (sp >= t_lo && sp < t_hi) {
    *lo = t_lo;
    *hi = t_hi;
  }
}

// NOINLINE so caller_pc/caller_fp (captured in the override one frame up)
// stay meaningful regardless of optimization.
__attribute__((noinline)) void sample_alloc(void* ptr, size_t size,
                                            void* caller_pc,
                                            void* caller_fp) {
  SampledAlloc s;
  s.size = size;
  const size_t period = g_period.load(std::memory_order_relaxed);
  s.weight = std::max(size, period);
  uintptr_t lo = 1, hi = 0;
  current_stack_bounds(reinterpret_cast<uintptr_t>(caller_fp), &lo, &hi);
  s.depth = stack_walk::walk(reinterpret_cast<uintptr_t>(caller_pc),
                             reinterpret_cast<uintptr_t>(caller_fp), lo, hi,
                             s.pcs);
  // walk() records caller_pc then *(caller_fp+8) — the same call site when
  // caller_fp is the allocating function's frame. Drop the duplicate.
  if (s.depth >= 2 && s.pcs[1] == s.pcs[0]) {
    memmove(&s.pcs[1], &s.pcs[2], (s.depth - 2) * sizeof(void*));
    --s.depth;
  }
  bloom_add(ptr);
  std::lock_guard<std::mutex> lk(*g_mu);
  if ((*g_live).emplace(ptr, s).second) {
    g_live_count.fetch_add(1, std::memory_order_relaxed);
  }
}

// The per-allocation fast path: countdown in TLS bytes; cross zero -> take
// a sample and re-arm. Inlined into the overrides.
inline void on_alloc(void* ptr, size_t size, void* caller_pc,
                     void* caller_fp) {
  if (ptr == nullptr || !g_running.load(std::memory_order_relaxed)) return;
  if (tls_in_hook) return;
  if (!tls_armed) {
    tls_armed = true;
    tls_countdown = static_cast<intptr_t>(g_period.load(std::memory_order_relaxed));
  }
  tls_countdown -= static_cast<intptr_t>(size);
  if (tls_countdown > 0) return;
  HookGuard guard;
  tls_countdown = static_cast<intptr_t>(g_period.load(std::memory_order_relaxed));
  sample_alloc(ptr, size, caller_pc, caller_fp);
}

inline void on_free(void* ptr) {
  if (ptr == nullptr) return;
  if (g_live_count.load(std::memory_order_relaxed) == 0) return;
  // Frees only cancel samples while the window is open; after Stop the
  // profile is a frozen snapshot until the next Start clears it.
  if (!g_running.load(std::memory_order_relaxed)) return;
  if (tls_in_hook) return;
  if (!bloom_maybe_contains(ptr)) return;  // definitely never sampled
  HookGuard guard;
  std::lock_guard<std::mutex> lk(*g_mu);
  if ((*g_live).erase(ptr) != 0) {
    g_live_count.fetch_sub(1, std::memory_order_relaxed);
  }
}

}  // namespace

bool HeapProfiler::Start(size_t sample_period) {
  // Reset everything BEFORE flipping g_running: a racing allocation must
  // not sample against the previous window's period or land between the
  // map clear and the counter reset.
  std::lock_guard<std::mutex> lifecycle(*g_lifecycle_mu);
  if (g_running.load(std::memory_order_relaxed)) return false;
  if (sample_period < 4096) sample_period = 4096;
  {
    HookGuard guard;  // clear() frees nodes -> operator delete -> on_free
    std::lock_guard<std::mutex> lk(*g_mu);
    g_live->clear();
  }
  for (size_t i = 0; i < kBloomWords; ++i) {
    g_bloom[i].store(0, std::memory_order_relaxed);
  }
  g_live_count.store(0, std::memory_order_relaxed);
  g_period.store(sample_period, std::memory_order_relaxed);
  g_running.store(true, std::memory_order_release);
  return true;
}

void HeapProfiler::Stop() { g_running.store(false, std::memory_order_release); }

bool HeapProfiler::running() { return g_running.load(); }

void HeapProfiler::RecordAlloc(void* ptr, size_t size) {
  on_alloc(ptr, size, __builtin_return_address(0),
           __builtin_frame_address(0));
}

void HeapProfiler::RecordFree(void* ptr) { on_free(ptr); }

size_t HeapProfiler::sampled_live_bytes() {
  HookGuard guard;
  std::lock_guard<std::mutex> lk(*g_mu);
  size_t total = 0;
  for (const auto& [p, s] : *g_live) total += s.weight;
  return total;
}

size_t HeapProfiler::sample_count() {
  return g_live_count.load(std::memory_order_relaxed);
}

std::string HeapProfiler::Collapsed() {
  HookGuard guard;  // agg inserts allocate while g_mu is held below
  std::map<std::vector<void*>, size_t> agg;
  {
    std::lock_guard<std::mutex> lk(*g_mu);
    for (const auto& [p, s] : *g_live) {
      std::vector<void*> key(s.depth);
      for (uint32_t d = 0; d < s.depth; ++d) {
        key[d] = s.pcs[s.depth - 1 - d];  // reverse: outer ... inner
      }
      agg[key] += s.weight;
    }
  }
  std::string out;
  for (const auto& [stack, bytes] : agg) {
    std::string line;
    for (size_t i = 0; i < stack.size(); ++i) {
      if (i != 0) line += ';';
      line += stack_walk::symbolize(stack[i]);
    }
    char tail[32];
    snprintf(tail, sizeof(tail), " %zu\n", bytes);
    out += line;
    out += tail;
  }
  return out;
}

std::string HeapProfiler::FlatText(size_t topn) {
  HookGuard guard;  // by_site inserts allocate while g_mu is held below
  std::map<void*, size_t> by_site;  // allocation call site -> bytes
  size_t total = 0, n = 0;
  {
    std::lock_guard<std::mutex> lk(*g_mu);
    for (const auto& [p, s] : *g_live) {
      if (s.depth > 0) by_site[s.pcs[0]] += s.weight;
      total += s.weight;
      ++n;
    }
  }
  std::map<std::string, size_t> by_sym;
  for (const auto& [pc, bytes] : by_site) {
    by_sym[stack_walk::symbolize(pc)] += bytes;
  }
  std::vector<std::pair<size_t, std::string>> ranked;
  ranked.reserve(by_sym.size());
  for (auto& [sym, bytes] : by_sym) ranked.emplace_back(bytes, sym);
  std::sort(ranked.rbegin(), ranked.rend());
  std::string out;
  char line[512];
  snprintf(line, sizeof(line),
           "%zu sampled allocations, ~%.1f MB in use (period %zu bytes)\n",
           n, total / 1048576.0, g_period.load(std::memory_order_relaxed));
  out += line;
  for (size_t i = 0; i < ranked.size() && i < topn; ++i) {
    snprintf(line, sizeof(line), "%10.1f KB  %5.1f%%  %s\n",
             ranked[i].first / 1024.0,
             total > 0 ? 100.0 * ranked[i].first / total : 0.0,
             ranked[i].second.c_str());
    out += line;
  }
  return out;
}

}  // namespace tbutil

#if !defined(__SANITIZE_ADDRESS__) && !defined(__SANITIZE_THREAD__)

// Global operator new/delete overrides. Every C++ allocation in the process
// funnels through these once libbrpc_tpu is linked; cost while not
// profiling is a single relaxed load. malloc/free stay untouched (IOBuf's
// block allocator reports via RecordAlloc/RecordFree instead).
//
// They keep libstdc++'s contract exactly, because they also serve every
// library loaded after this one (libstdc++ binds its own new/delete to the
// first definition in its lookup scope): size 0 is a valid request, an
// alignment below sizeof(void*) is legal (posix_memalign would reject it
// with EINVAL), and a null result runs the new_handler before giving up.
namespace {

inline void* raw_alloc(size_t size, size_t align) {
  if (size == 0) size = 1;
  if (align < sizeof(void*)) align = sizeof(void*);
  for (;;) {
    void* p = nullptr;
    if (align <= alignof(std::max_align_t)) {
      p = malloc(size);
    } else if (posix_memalign(&p, align, size) != 0) {
      p = nullptr;
    }
    if (p != nullptr) return p;
    std::new_handler handler = std::get_new_handler();
    if (handler == nullptr) return nullptr;
    handler();
  }
}

inline void* throwing_alloc(size_t size, size_t align) {
  void* p = raw_alloc(size, align);
  if (p == nullptr) throw std::bad_alloc();
  return p;
}

inline void* nothrow_alloc(size_t size, size_t align) noexcept {
  try {
    return raw_alloc(size, align);
  } catch (...) {  // a new_handler may throw bad_alloc
    return nullptr;
  }
}

}  // namespace

#define TB_SAMPLE(p, size)                                           \
  tbutil::on_alloc(p, size, __builtin_return_address(0),             \
                   __builtin_frame_address(0))

void* operator new(size_t size) {
  void* p = throwing_alloc(size, 0);
  TB_SAMPLE(p, size);
  return p;
}

void* operator new[](size_t size) {
  void* p = throwing_alloc(size, 0);
  TB_SAMPLE(p, size);
  return p;
}

void* operator new(size_t size, const std::nothrow_t&) noexcept {
  void* p = nothrow_alloc(size, 0);
  TB_SAMPLE(p, size);
  return p;
}

void* operator new[](size_t size, const std::nothrow_t&) noexcept {
  void* p = nothrow_alloc(size, 0);
  TB_SAMPLE(p, size);
  return p;
}

void* operator new(size_t size, std::align_val_t al) {
  void* p = throwing_alloc(size, static_cast<size_t>(al));
  TB_SAMPLE(p, size);
  return p;
}

void* operator new[](size_t size, std::align_val_t al) {
  void* p = throwing_alloc(size, static_cast<size_t>(al));
  TB_SAMPLE(p, size);
  return p;
}

void* operator new(size_t size, std::align_val_t al,
                   const std::nothrow_t&) noexcept {
  void* p = nothrow_alloc(size, static_cast<size_t>(al));
  TB_SAMPLE(p, size);
  return p;
}

void* operator new[](size_t size, std::align_val_t al,
                     const std::nothrow_t&) noexcept {
  void* p = nothrow_alloc(size, static_cast<size_t>(al));
  TB_SAMPLE(p, size);
  return p;
}

#undef TB_SAMPLE

void operator delete(void* p) noexcept { tbutil::on_free(p); free(p); }
void operator delete[](void* p) noexcept { tbutil::on_free(p); free(p); }
void operator delete(void* p, size_t) noexcept { tbutil::on_free(p); free(p); }
void operator delete[](void* p, size_t) noexcept { tbutil::on_free(p); free(p); }
void operator delete(void* p, const std::nothrow_t&) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete[](void* p, const std::nothrow_t&) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete(void* p, std::align_val_t) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete[](void* p, std::align_val_t) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete(void* p, size_t, std::align_val_t) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete[](void* p, size_t, std::align_val_t) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete(void* p, std::align_val_t,
                     const std::nothrow_t&) noexcept {
  tbutil::on_free(p);
  free(p);
}
void operator delete[](void* p, std::align_val_t,
                       const std::nothrow_t&) noexcept {
  tbutil::on_free(p);
  free(p);
}

#endif  // !__SANITIZE_ADDRESS__ && !__SANITIZE_THREAD__
