// Protocol registry: each wire protocol is a struct of function pointers
// plugged into the InputMessenger parse pipeline and the Channel pack path.
// Capability parity: reference src/brpc/protocol.h:77-186 (struct Protocol
// {parse, serialize_request, pack_request, process_request, process_response,
// ...}; RegisterProtocol) — all protocols multiplex on one port: the parser
// that recognizes the bytes wins (PARSE_ERROR_TRY_OTHERS).
#pragma once

#include <cstdint>
#include <string>

#include "tbutil/iobuf.h"

namespace trpc {

class Socket;
class Controller;

enum ParseError {
  PARSE_OK = 0,
  PARSE_ERROR_NOT_ENOUGH_DATA,  // wait for more bytes
  PARSE_ERROR_TRY_OTHERS,       // magic mismatch: not this protocol
  PARSE_ERROR_ABSOLUTELY_WRONG,  // recognized but corrupt: kill connection
};

// A parsed-but-not-yet-processed inbound message. Concrete protocols extend
// this with their decoded fields (reference InputMessageBase).
struct InputMessageBase {
  uint64_t socket_id = 0;  // re-Address'ed by the process fn
  int protocol_index = -1;
  // Process in PARSE ORDER on the input fiber instead of a per-message
  // fiber. Set by parse() for order-sensitive cheap messages — stream
  // frames, whose handling is an enqueue (reference: streaming frames go
  // straight to Stream::OnReceived from the parse context) — and for
  // requests to inline-registered services (the small-RPC fast path).
  bool process_in_place = false;
  // True iff process_in_place was set by the INLINE FAST PATH (a request
  // to a non-blocking service), not by a stream frame: keeps the
  // inline-vs-spawned counters honest.
  bool inline_fast_path = false;
  // Eligible for batched dispatch — OPT-IN, set by the parser only when
  // processing this message provably cannot (a) dominate a core or (b)
  // park the dispatch fiber. Batching a LARGE message serializes exactly
  // the work that wants multi-core parallelism (measured 0.65x at 1MB
  // when everything batched), and batching a PARKING handler (Python
  // callback pool) holds every later message — and every already-adopted
  // response — hostage to one handler's run time. tstd grants it to
  // small responses and to small requests targeting inline_safe (or
  // nonexistent) services; everything else keeps the reference's
  // fiber-per-message dispatch.
  bool dispatch_batchable = false;
  // Intrusive link for batched dispatch: the messenger chains the messages
  // of one read event and hands the whole chain to ONE dispatch fiber
  // (kDispatchBatchMax). Owned by the messenger until dispatch.
  InputMessageBase* batch_next = nullptr;
  virtual ~InputMessageBase() = default;
  // The ONE teardown path: protocols with pooled message objects override
  // this to reset + return to their pool instead of freeing. Every owner
  // that would `delete` an InputMessageBase must call Destroy() instead.
  virtual void Destroy() { delete this; }
};

struct ParseResult {
  ParseError error = PARSE_OK;
  InputMessageBase* msg = nullptr;
};

struct Protocol {
  // Cut one message from *source (bytes already read from the socket).
  // Consuming bytes without returning a message is allowed only for
  // transport-control frames (tici credits/doorbells); the messenger
  // rescans all protocols whenever a parse consumed bytes and deferred,
  // since the new head may belong to a different protocol.
  ParseResult (*parse)(tbutil::IOBuf* source, Socket* socket);
  // Client side: frame a request. correlation_id goes on the wire.
  // `socket` is the acquired connection — stateful protocols (h2) keep
  // per-connection context (stream ids, HPACK, windows) on it and may
  // write flow-controlled frames directly, returning an empty *out.
  void (*pack_request)(tbutil::IOBuf* out, Controller* cntl,
                       uint64_t correlation_id,
                       const std::string& service_method,
                       const tbutil::IOBuf& payload, Socket* socket);
  // Server side: run the request (ends by writing a response). Takes
  // ownership of msg.
  void (*process_request)(InputMessageBase* msg);
  // Client side: resolve the correlation id. Takes ownership of msg.
  void (*process_response)(InputMessageBase* msg);
  // Client RPCs use a dedicated connection per call instead of the shared
  // SocketMap connection (reference CONNECTION_TYPE_SHORT; the standard
  // type for HTTP, whose wire carries no correlation id).
  bool short_connection = false;
  // Text protocols without a magic number (redis, memcache) can only gate
  // on plausibility, so a NOT_ENOUGH_DATA claim from them during the
  // multi-protocol scan is logged — a wrong claim poisons the
  // preferred-protocol cache and wedges the connection (the r3 tpu flake).
  bool weak_magic = false;
  const char* name;
};

inline constexpr int kMaxProtocols = 16;

// index: stable small int (also stored in Socket's preferred-protocol cache).
// Returns 0, or -1 if the slot is taken.
int RegisterProtocol(int index, const Protocol& proto);
const Protocol* GetProtocol(int index);

}  // namespace trpc
