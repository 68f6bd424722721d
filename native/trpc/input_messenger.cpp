#include "trpc/rpc_metrics.h"
#include "trpc/input_messenger.h"

#include <cerrno>

#include "tbthread/fiber.h"
#include "tbutil/logging.h"
#include "tbvar/flight_recorder.h"
#include "trpc/errno.h"
#include "trpc/flags.h"
#include "trpc/socket.h"

namespace trpc {

namespace {

// Doorbell-free polling mode for the batched input path: after a read
// pass drains its fd (EAGAIN) with no handler left to run, the input
// fiber keeps RE-POLLING the fd for this many microseconds instead of
// releasing its claim and parking back into epoll. Back-to-back small
// RPCs (a ping-pong client, a pipelined window) then skip the
// doorbell-edge wakeup entirely — no epoll_wait, no dispatcher hop, no
// fiber re-spawn between consecutive messages; on the tpu:// transport
// the doorbell stream is consumed the moment it lands rather than when
// its readiness edge schedules us. Costs one spinning worker pthread per
// polled connection while armed, so it is an explicit low-latency
// opt-in, never a default.
const auto* g_input_poll_us = trpc::FlagRegistry::global().DefineInt(
    "rpc_input_poll_us", 0,
    "Busy-poll the fd this many us after each drained read pass "
    "(doorbell-free wakeup for back-to-back small RPCs; 0 = off)",
    [](int64_t v) { return v >= 0 && v <= 1000000; });

// Dispatch-path instrumentation: batch-size distribution plus the
// inline-vs-spawned split, all visible at /vars and /brpc_metrics.
struct DispatchMetrics {
  tbvar::LatencyRecorder batch_size;  // value = messages per dispatch fiber
  tbvar::Adder<int64_t> inline_count;
  tbvar::Adder<int64_t> spawned_count;

  static DispatchMetrics& instance() {
    static DispatchMetrics* m = new DispatchMetrics;  // immortal, like bvars
    return *m;
  }

 private:
  DispatchMetrics() {
    batch_size.expose("rpc_dispatch_batch_size");
    inline_count.expose("rpc_dispatch_inline");
    spawned_count.expose("rpc_dispatch_spawned");
  }
};

void DispatchMessage(InputMessageBase* msg, bool server_side) {
  const Protocol* proto = GetProtocol(msg->protocol_index);
  if (proto == nullptr) {
    msg->Destroy();
    return;
  }
  if (server_side) {
    proto->process_request(msg);
  } else {
    proto->process_response(msg);
  }
}

struct ProcessArg {
  InputMessageBase* msg;
  bool server_side;
  Socket* sock;  // counted + ref'd when non-null (client-side dispatch)
};

void* ProcessThunk(void* argv) {
  auto* arg = static_cast<ProcessArg*>(argv);
  DispatchMessage(arg->msg, arg->server_side);
  if (arg->sock != nullptr) {
    arg->sock->EndDispatch();
    arg->sock->Deref();
  }
  delete arg;
  return nullptr;
}

struct BatchArg {
  InputMessageBase* head;  // batch_next-chained, parse order
  int count;
  bool server_side;
  // False on the fiber-spawn-failure degrade path, where the thunk runs
  // ON the input fiber under its read claim: responses then take the
  // normal Socket::Write path (the seed's behavior) — an adopted chain
  // whose flush hit backpressure there would have no claim-safe owner.
  bool coalesce;
  Socket* sock;  // the batch's connection — always ref'd by the batch
};

void* BatchThunk(void* argv) {
  auto* arg = static_cast<BatchArg*>(argv);
  tbvar::flight_record(tbvar::FLIGHT_BATCH_DISPATCH,
                       arg->sock != nullptr ? arg->sock->id() : 0,
                       static_cast<uint64_t>(arg->count));
  DispatchMetrics::instance().batch_size << arg->count;
  {
    // Responses the handlers of this batch write synchronously chain into
    // the connection's write queue and flush ONCE at scope exit — one
    // writev/doorbell flush carries the whole batch's responses. Pinned
    // to the batch's own socket: a handler's nested client RPC (another
    // socket) is sent immediately, never held for this flush.
    WriteCoalesceScope scope(arg->coalesce, arg->sock);
    InputMessageBase* m = arg->head;
    while (m != nullptr) {
      InputMessageBase* next = m->batch_next;
      m->batch_next = nullptr;
      // Per-message isolation: DispatchMessage owns msg and reports any
      // protocol-level failure through that message's own response path;
      // the loop continues to m+1 regardless.
      DispatchMessage(m, arg->server_side);
      if (!arg->server_side && arg->sock != nullptr) {
        arg->sock->EndDispatch();
      }
      m = next;
    }
  }
  if (arg->sock != nullptr) arg->sock->Deref();
  delete arg;
  return nullptr;
}

}  // namespace

int64_t input_poll_us() {
  return g_input_poll_us->load(std::memory_order_relaxed);
}

void InputMessenger::ProcessInline(Socket* s, InputMessageBase* msg) {
  // No dispatch accounting here: in-place messages (stream frames, inline
  // fast-path requests) run UNDER the input claim, and the trailing
  // message's count was taken at parse time (OnNewMessages) — its
  // EndDispatch is the caller's job.
  (void)s;
  DispatchMessage(msg, _server_side);
}

void InputMessenger::ProcessInFiber(Socket* s, InputMessageBase* msg) {
  // The dispatch COUNT was taken at parse time, while the input claim was
  // held (see OnNewMessages) — a later EOF input event is guaranteed to
  // observe it. Here we only carry a ref so EndDispatch outlives recycling.
  Socket* counted = nullptr;
  if (!_server_side && s != nullptr) {
    counted = s;
    s->Ref();
  }
  DispatchMetrics::instance().spawned_count << 1;
  auto* arg = new ProcessArg{msg, _server_side, counted};
  tbthread::fiber_t tid;
  if (tbthread::fiber_start_urgent(&tid, nullptr, ProcessThunk, arg) != 0) {
    ProcessThunk(arg);
  }
}

void InputMessenger::ProcessBatchInFiber(Socket* s, InputMessageBase* head,
                                         int count) {
  if (head == nullptr) return;
  // The ref pins the socket for the coalescing scope (both sides) and for
  // the client-side EndDispatch accounting.
  if (s != nullptr) s->Ref();
  DispatchMetrics::instance().spawned_count << count;
  auto* arg = new BatchArg{head, count, _server_side, /*coalesce=*/true, s};
  tbthread::fiber_t tid;
  if (tbthread::fiber_start_urgent(&tid, nullptr, BatchThunk, arg) != 0) {
    arg->coalesce = false;  // running under the caller's read claim
    BatchThunk(arg);
  }
}

ParseResult InputMessenger::CutInputMessage(Socket* s, int* protocol_index) {
  tbutil::IOBuf& buf = s->read_buf();
  // A parser may CONSUME bytes yet return TRY_OTHERS: the tici transport
  // eats credit/doorbell frames and defers when the next bytes are inline
  // tstd. The scan must then RESTART from the top — the new head may belong
  // to an already-visited (or the skipped preferred) protocol. Without the
  // restart, a weak-magic protocol later in the order can claim the exposed
  // frame with NOT_ENOUGH_DATA, get cached as preferred, and wedge the
  // connection permanently (the r3 tpu:// flake: memcache claimed "TRPC"
  // bytes after tici consumed the credits ahead of them).
  while (true) {
    const size_t size_at_entry = buf.size();
    // Fast path: the protocol that parsed the last message on this
    // connection almost always parses the next (reference
    // input_messenger.cpp:84).
    const int preferred = s->preferred_protocol();
    if (preferred >= 0) {
      const Protocol* proto = GetProtocol(preferred);
      if (proto != nullptr) {
        ParseResult r = proto->parse(&buf, s);
        if (r.error == PARSE_OK || r.error == PARSE_ERROR_NOT_ENOUGH_DATA) {
          *protocol_index = preferred;
          return r;
        }
        if (r.error == PARSE_ERROR_ABSOLUTELY_WRONG) return r;
        if (buf.size() != size_at_entry) continue;  // consumed: rescan all
      }
    }
    bool restart = false;
    for (int i = 0; i < kMaxProtocols; ++i) {
      if (i == preferred) continue;
      const Protocol* proto = GetProtocol(i);
      if (proto == nullptr) continue;
      const size_t before = buf.size();
      ParseResult r = proto->parse(&buf, s);
      if (r.error == PARSE_OK || r.error == PARSE_ERROR_NOT_ENOUGH_DATA) {
        if (proto->weak_magic && r.error == PARSE_ERROR_NOT_ENOUGH_DATA) {
          // A weak-magic protocol claiming an unparsed buffer is how a
          // preferred-cache lock-in starts; keep it visible.
          char head[16] = {0};
          const size_t n = buf.copy_to(head, sizeof(head));
          char hex[40];
          for (size_t k = 0; k < n && k < 16; ++k) {
            snprintf(hex + 2 * k, 4, "%02x", (unsigned char)head[k]);
          }
          TB_LOG(WARNING) << "protocol " << i << " (" << proto->name
                          << ") claimed " << buf.size()
                          << " unparsed bytes on sock " << s->id()
                          << " head=" << hex;
        }
        *protocol_index = i;
        s->set_preferred_protocol(i);
        return r;
      }
      if (r.error == PARSE_ERROR_ABSOLUTELY_WRONG) return r;
      if (buf.size() != before) {
        restart = true;  // consumed then deferred: rescan from the top
        break;
      }
    }
    if (restart) continue;
    // Nobody recognizes the bytes. If the buffer is non-trivial, it is junk.
    ParseResult r;
    r.error = buf.empty() ? PARSE_ERROR_NOT_ENOUGH_DATA
                          : PARSE_ERROR_TRY_OTHERS;
    return r;
  }
}

InputMessageBase* InputMessenger::OnNewMessages(Socket* s, int* defer_error) {
  // Keep only the newest complete message as the inline candidate; older
  // ones accumulate into a batch_next chain and go to ONE dispatch fiber
  // per <= kDispatchBatchMax messages.
  InputMessageBase* pending = nullptr;
  InputMessageBase* batch_head = nullptr;
  InputMessageBase* batch_tail = nullptr;
  int batch_len = 0;
  auto flush_batch = [&] {
    if (batch_head != nullptr) {
      ProcessBatchInFiber(s, batch_head, batch_len);
      batch_head = batch_tail = nullptr;
      batch_len = 0;
    }
  };
  while (true) {
    ssize_t nr = s->DoRead(1 << 19);
    if (nr < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) break;
      if (errno == EINTR) continue;
      *defer_error = errno != 0 ? errno : TRPC_EFAILEDSOCKET;
      break;
    }
    if (nr == 0) {
      TB_VLOG(2) << "read EOF sid=" << s->id() << " buf="
                 << s->read_buf().size() << " pending=" << (pending != nullptr);
      *defer_error = TRPC_EEOF;
      break;
    }
    GlobalRpcMetrics::instance().bytes_in << nr;
    s->NoteInputProgress(tbutil::cpuwide_time_us());
    while (true) {
      int proto_index = -1;
      ParseResult r = CutInputMessage(s, &proto_index);
      if (r.error == PARSE_ERROR_NOT_ENOUGH_DATA) break;
      if (r.error != PARSE_OK) {
        char dbg[17] = {0};
        s->read_buf().copy_to(dbg, 16);
        for (int i = 0; i < 16; ++i) if (dbg[i] && !isprint((unsigned char)dbg[i])) dbg[i] = '.';
        TB_LOG(WARNING) << "unparsable bytes from "
                        << tbutil::endpoint2str(s->remote_side())
                        << ", closing; err=" << (int)r.error
                        << " size=" << s->read_buf().size()
                        << " head=" << dbg;
        *defer_error = TRPC_EREQUEST;
        // Messages parsed BEFORE the junk are intact — dispatch them; the
        // deferred error is applied by the caller after delivery.
        flush_batch();
        return pending;
      }
      r.msg->socket_id = s->id();
      r.msg->protocol_index = proto_index;
      if (r.msg->process_in_place) {
        // Order-sensitive (stream frames) or the inline fast path (a
        // request to a non-blocking service): handle now, in parse order.
        if (r.msg->inline_fast_path) {
          DispatchMetrics::instance().inline_count << 1;
        }
        ProcessInline(s, r.msg);
        continue;
      }
      // Count the dispatch NOW, while this fiber still owns the input
      // claim: an EOF event can only start after the claim is released,
      // so it is guaranteed to see the count and wait for the delivery
      // (client side). Ended by ProcessThunk/BatchThunk / the ProcessEvent
      // tail path.
      if (!_server_side) s->BeginDispatch();
      if (pending != nullptr) {
        if (pending->dispatch_batchable) {
          pending->batch_next = nullptr;
          if (batch_tail == nullptr) {
            batch_head = pending;
          } else {
            batch_tail->batch_next = pending;
          }
          batch_tail = pending;
          if (++batch_len >= kDispatchBatchMax) flush_batch();
        } else {
          // Non-batchable (large) message: release the accumulated batch
          // first so cross-message dispatch keeps parse order, then give
          // this one its own fiber.
          flush_batch();
          ProcessInFiber(s, pending);
        }
      }
      pending = r.msg;
    }
  }
  flush_batch();
  return pending;
}

InputMessenger* InputMessenger::client_messenger() {
  static InputMessenger* m = new InputMessenger(false);
  return m;
}

}  // namespace trpc
