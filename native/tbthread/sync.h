// Fiber-aware synchronization primitives built on butex: mutex, condition
// variable, countdown event, semaphore. All of them block the calling FIBER
// (the worker pthread keeps running other fibers) and also work from plain
// pthreads (which block on a futex waiter).
// Capability parity: reference src/bthread/{mutex,condition_variable,
// countdown_event,semaphore}.cpp incl. the contention-profiling hook
// (mutex.cpp:122 ContentionProfiler): the contended slow path reports its
// wait time to tbthread/contention_profiler.h when profiling is on — the
// uncontended fast path stays a single CAS.
#pragma once

#include <cerrno>
#include <cstdint>

#include "tbthread/butex.h"
#include "tbthread/contention_profiler.h"
#include "tbutil/time.h"

namespace tbthread {

class FiberMutex {
 public:
  FiberMutex() : _b(butex_create()) {}
  ~FiberMutex() { butex_destroy(_b); }
  FiberMutex(const FiberMutex&) = delete;
  FiberMutex& operator=(const FiberMutex&) = delete;

  void lock() {
    // 0 free, 1 locked no waiters, 2 locked with (possible) waiters.
    int expected = 0;
    if (_b->value.compare_exchange_strong(expected, 1,
                                          std::memory_order_acquire,
                                          std::memory_order_relaxed)) {
      return;
    }
    const bool profile = contention_profiling_enabled();
    const int64_t t0 = profile ? tbutil::monotonic_time_ns() : 0;
    // Canonical contended loop (reference bthread/mutex.cpp
    // mutex_lock_contended): exchange(2) returning 0 means WE acquired —
    // the word stays 2, so our unlock wakes (possibly spuriously, which
    // butex waiters tolerate); nonzero means someone else holds it, so
    // park while the word still reads 2. The previous CAS-retry shape had
    // a fatal window: a holder unlocking between the failed fast-path CAS
    // and the exchange made the exchange return 0 (free), the retry CAS
    // then failed against the 2 the locker itself had just written, and
    // it parked on a mutex NOBODY owned — every later locker piled up
    // behind it forever. That was the rare all-callers-parked in-process
    // wedge: the flight recorder pinned it as two FIBER_PARKs on a socket
    // _pending_mu butex with no UNPARK ever and no live holder.
    while (_b->value.exchange(2, std::memory_order_acquire) != 0) {
      butex_wait(_b, 2, nullptr);
    }
    if (profile) {
      contention_internal::Record(tbutil::monotonic_time_ns() - t0);
    }
  }

  bool try_lock() {
    int expected = 0;
    return _b->value.compare_exchange_strong(expected, 1,
                                             std::memory_order_acquire,
                                             std::memory_order_relaxed);
  }

  void unlock() {
    if (_b->value.exchange(0, std::memory_order_release) == 2) {
      butex_wake(_b);
    }
  }

  Butex* internal_butex() { return _b; }

 private:
  Butex* _b;
};

class FiberCond {
 public:
  FiberCond() : _b(butex_create()) {}
  ~FiberCond() { butex_destroy(_b); }
  FiberCond(const FiberCond&) = delete;
  FiberCond& operator=(const FiberCond&) = delete;

  // mutex must be held; released while waiting, re-acquired before return.
  void wait(FiberMutex& m) {
    const int seq = _b->value.load(std::memory_order_relaxed);
    m.unlock();
    butex_wait(_b, seq, nullptr);
    m.lock();
  }

  // Returns false on timeout (abstime on the gettimeofday_us clock).
  bool wait_until(FiberMutex& m, const timespec& abstime) {
    const int seq = _b->value.load(std::memory_order_relaxed);
    m.unlock();
    int rc = butex_wait(_b, seq, &abstime);
    m.lock();
    return !(rc != 0 && errno == ETIMEDOUT);
  }

  void notify_one() {
    _b->value.fetch_add(1, std::memory_order_release);
    butex_wake(_b);
  }

  void notify_all() {
    _b->value.fetch_add(1, std::memory_order_release);
    butex_wake_all(_b);
  }

 private:
  Butex* _b;
};

// One-shot countdown: wait() blocks until the count reaches zero.
// (reference countdown_event.cpp — used heavily by tests and ParallelChannel)
class CountdownEvent {
 public:
  explicit CountdownEvent(int initial = 1) : _b(butex_create()) {
    _b->value.store(initial, std::memory_order_relaxed);
  }
  ~CountdownEvent() { butex_destroy(_b); }

  void signal(int by = 1) {
    int prev = _b->value.fetch_sub(by, std::memory_order_acq_rel);
    if (prev - by <= 0) butex_wake_all(_b);
  }

  void add_count(int by = 1) {
    _b->value.fetch_add(by, std::memory_order_release);
  }

  void wait() {
    int v;
    while ((v = _b->value.load(std::memory_order_acquire)) > 0) {
      butex_wait(_b, v, nullptr);
    }
  }

  // false on timeout.
  bool timed_wait(const timespec& abstime) {
    int v;
    while ((v = _b->value.load(std::memory_order_acquire)) > 0) {
      if (butex_wait(_b, v, &abstime) != 0 && errno == ETIMEDOUT) {
        return false;
      }
    }
    return true;
  }

 private:
  Butex* _b;
};

// Counting semaphore (reference bthread/semaphore).
class FiberSemaphore {
 public:
  explicit FiberSemaphore(int initial = 0) : _b(butex_create()) {
    _b->value.store(initial, std::memory_order_relaxed);
  }
  ~FiberSemaphore() { butex_destroy(_b); }
  FiberSemaphore(const FiberSemaphore&) = delete;
  FiberSemaphore& operator=(const FiberSemaphore&) = delete;

  void post(int n = 1) {
    _b->value.fetch_add(n, std::memory_order_release);
    if (n == 1) {
      butex_wake(_b);
    } else {
      butex_wake_all(_b);
    }
  }

  void wait() {
    while (true) {
      int v = _b->value.load(std::memory_order_acquire);
      if (v > 0) {
        if (_b->value.compare_exchange_weak(v, v - 1,
                                            std::memory_order_acq_rel,
                                            std::memory_order_acquire)) {
          return;
        }
        continue;
      }
      butex_wait(_b, v, nullptr);
    }
  }

  bool try_wait() {
    int v = _b->value.load(std::memory_order_acquire);
    while (v > 0) {
      if (_b->value.compare_exchange_weak(v, v - 1,
                                          std::memory_order_acq_rel,
                                          std::memory_order_acquire)) {
        return true;
      }
    }
    return false;
  }

 private:
  Butex* _b;
};

// Reader/writer lock, writer-preferring: once a writer queues, new readers
// wait — a steady reader stream cannot starve writers (reference
// bthread/rwlock). Built on FiberMutex+FiberCond: the hot uncontended path
// is one fiber-mutex lock/unlock pair; contended paths park fibers.
class FiberRWLock {
 public:
  void rdlock() {
    _mu.lock();
    while (_writer || _writers_waiting > 0) _rcond.wait(_mu);
    ++_readers;
    _mu.unlock();
  }
  void rdunlock() {
    _mu.lock();
    if (--_readers == 0 && _writers_waiting > 0) _wcond.notify_one();
    _mu.unlock();
  }
  void wrlock() {
    _mu.lock();
    ++_writers_waiting;
    while (_writer || _readers > 0) _wcond.wait(_mu);
    --_writers_waiting;
    _writer = true;
    _mu.unlock();
  }
  void wrunlock() {
    _mu.lock();
    _writer = false;
    if (_writers_waiting > 0) {
      _wcond.notify_one();
    } else {
      _rcond.notify_all();
    }
    _mu.unlock();
  }

 private:
  FiberMutex _mu;
  FiberCond _rcond;  // readers wait here while writers own/queue
  FiberCond _wcond;  // writers wait here for exclusivity
  int _readers = 0;
  int _writers_waiting = 0;
  bool _writer = false;
};

}  // namespace tbthread
