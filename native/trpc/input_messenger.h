// InputMessenger: the protocol-multiplexing read pipeline. One instance for
// all client sockets, one per Server (its Acceptor shares it).
// Capability parity: reference src/brpc/input_messenger.h/.cpp:361
// (OnNewMessages: DoRead loop -> CutInputMessage trying last-used protocol
// then all -> per-message processing fiber, last message inline).
//
// Small-RPC fast path (this repo, beyond the reference): all complete
// messages of one read event are chained and handed to ONE dispatch fiber
// (kDispatchBatchMax) instead of one fiber_start_urgent per message —
// at 64B-echo rates the per-message spawn was the dominant cost. A
// protocol-level failure in message k of a batch (unknown service, bad
// payload) is answered like any other request and must never poison
// k+1..n: the batch loop treats every message independently.
#pragma once

#include <cstddef>

#include "trpc/protocol.h"

namespace trpc {

class Socket;

// Upper bound on messages handed to one dispatch fiber: it bounds how
// long a burst monopolizes one worker.
constexpr int kDispatchBatchMax = 16;

class InputMessenger {
 public:
  // server_side: dispatch parsed messages to process_request (vs response).
  explicit InputMessenger(bool server_side) : _server_side(server_side) {}
  virtual ~InputMessenger() = default;

  // Read everything available on `s` (until EAGAIN/EOF), cutting complete
  // messages. All but the LAST are dispatched in batches to dispatch
  // fibers (one fiber per <= kDispatchBatchMax batchable messages; a
  // non-batchable one gets its own fiber, the reference's shape); the last
  // is RETURNED so the caller (Socket::ProcessEvent) can run it inline AFTER
  // releasing the input-fiber claim — a handler that parks must not
  // head-of-line-block later requests on the connection (reference
  // input_messenger.cpp:182-223).
  //
  // EOF / read errors are NOT SetFailed here: they are reported through
  // *defer_error and applied by the caller AFTER the returned message is
  // dispatched. A peer that responds-then-closes must have its response
  // delivered before the failure errors the in-flight correlation ids —
  // otherwise a received response gets dropped and the RPC spuriously
  // retried.
  virtual InputMessageBase* OnNewMessages(Socket* s, int* defer_error);

  // Dispatch a parsed message (request or response per _server_side).
  // `s` is the connection the message arrived on: client-side dispatches
  // are counted on it (BeginDispatch/EndDispatch) so a deferred EOF can
  // drain them before erroring the pending correlation ids.
  void ProcessInline(Socket* s, InputMessageBase* msg);
  void ProcessInFiber(Socket* s, InputMessageBase* msg);
  // One fiber for a whole batch_next-chained run of `count` messages,
  // processed in parse order. Dispatch counts were taken at parse time.
  void ProcessBatchInFiber(Socket* s, InputMessageBase* head, int count);

  bool server_side() const { return _server_side; }

  // The process-wide messenger for client-created sockets.
  static InputMessenger* client_messenger();

 private:
  // Try the socket's preferred protocol, then all registered. Returns
  // PARSE_OK with a message, NOT_ENOUGH_DATA, or ABSOLUTELY_WRONG.
  ParseResult CutInputMessage(Socket* s, int* protocol_index);

  bool _server_side;
};

// Live value of rpc_input_poll_us (>= 0; 0 = doorbell-free input polling
// off): how long Socket::ProcessEvent busy-polls its fd after a drained
// read pass before handing the read claim back to epoll.
int64_t input_poll_us();

}  // namespace trpc
