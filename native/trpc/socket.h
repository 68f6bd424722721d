// Socket: THE connection object — versioned-refcounted, wait-free write
// queue, fiber-parked reads/connects.
//
// Capability parity: reference src/brpc/socket.h + socket.cpp:
//  - versioned refcount lifecycle (socket_id.h:30-50): Address(id) fails
//    after SetFailed, recycle on last deref
//  - wait-free Write (socket.cpp:1696 StartWrite): producers exchange into
//    _write_head and return; the producer that found it empty writes inline
//    once and hands leftovers to a KeepWrite fiber (socket.cpp:1806) which
//    parks on _epollout_butex (socket.cpp:1253 WaitEpollOut)
//  - read events start one input fiber per socket via an event counter
//    (socket.cpp:1183 StartInputEvent / ProcessEvent)
//  - pending correlation-ids errored out on SetFailed (failure propagation
//    to in-flight RPCs), health-check revival hook
#pragma once

#include <atomic>
#include <cstdint>
#include <mutex>
#include <vector>

#include <memory>

#include "tbthread/butex.h"
#include "tbthread/fiber_id.h"
#include "tbthread/sync.h"
#include "tbutil/endpoint.h"
#include "tbutil/iobuf.h"
#include "trpc/ssl.h"
#include "trpc/versioned_ref.h"

namespace ttpu {
class IciEndpoint;
}  // namespace ttpu

namespace trpc {

class Socket;
class InputMessenger;
using SocketId = uint64_t;
inline constexpr SocketId INVALID_SOCKET_ID = INVALID_VREF_ID;
using SocketUniquePtr = VersionedRefWithId<Socket>::Ptr;

// One queued write. Pooled (tbutil::ObjectPool) — creation is pointer pops.
struct WriteRequest {
  tbutil::IOBuf data;
  std::atomic<WriteRequest*> next{nullptr};
  // Correlation id notified with the error if this write fails (0 = none).
  tbthread::fiber_id_t notify_id = 0;
};

// Fiber-scoped response coalescing (the small-RPC fast path's write half).
// While a scope is active on the current fiber, the FIRST small write to a
// socket that would have become the inline writer instead leaves the bytes
// queued and records the writer role here; Flush() (or the destructor)
// drains the whole accumulated chain through KeepWrite/WriteBatch — one
// writev (plain TCP) or one doorbell flush (tpu://) carries every response
// the scope's handlers produced. Without a scope, queued *requests* already
// gather into batched writes but each response pays its own flush; this is
// the seam that extends the batching to the server's reply path.
//
// PINNED to one socket — the batch's own connection, known at scope
// construction: a dispatch batch is per-connection, and responses answer
// on the socket the requests arrived on. Writes to ANY other socket take
// the normal Socket::Write path unchanged — critically, a handler's
// nested synchronous client RPC (issued on a client socket while the
// handler's fiber will park for the response) must be SENT immediately,
// not adopted into a flush that can only run after the handler returns.
// Large writes and writes while no scope is active are also unchanged.
// The scope lives on the dispatching fiber's stack, so a handler that
// parks mid-batch delays the flush by at most its own run time — never
// past the scope's end.
class WriteCoalesceScope {
 public:
  // enabled=false constructs an inert scope (the batch fiber's
  // spawn-failure degrade path, whose writes take the normal Socket::Write
  // path). `only` is the single socket this scope may adopt.
  WriteCoalesceScope(bool enabled, Socket* only);
  ~WriteCoalesceScope();
  WriteCoalesceScope(const WriteCoalesceScope&) = delete;
  WriteCoalesceScope& operator=(const WriteCoalesceScope&) = delete;

  // Drain the adopted chain now (idempotent; the scope can adopt again
  // afterwards). May park on transport backpressure, like any writer.
  void Flush();
  // Hand the adopted chain to a background KeepWrite fiber instead of
  // draining on THIS fiber. For flush points where parking is not
  // allowed — the input fiber still holding its read claim must never
  // park in WaitCredit/WaitEpollOut: on tpu:// the credit frames that
  // would wake it arrive through the very read path it is blocking.
  void FlushDetached();

  // The scope active on the current fiber/thread (nullptr when none).
  static WriteCoalesceScope* current();

 private:
  friend class Socket;
  Socket* _only = nullptr;  // the one socket this scope may adopt
  Socket* _sock = nullptr;  // ref held while a chain is adopted
  WriteRequest* _todo = nullptr;
  WriteRequest* _last = nullptr;
  WriteCoalesceScope* _prev = nullptr;
  bool _installed = false;
};

class Socket : public VersionedRefWithId<Socket> {
 public:
  struct Options {
    int fd = -1;  // owned once passed; -1 = client socket, connect on demand
    tbutil::EndPoint remote_side;
    // Parses+dispatches inbound bytes (server: Acceptor's messenger;
    // client: the client messenger). May be null (write-only socket).
    InputMessenger* messenger = nullptr;
    bool server_side = false;
    void* user = nullptr;  // Server* on accepted sockets
    // Client side: upgrade to the tpu:// ICI transport after the TCP
    // connect (HELLO/ACK handshake inside ConnectIfNot — the reference's
    // app_connect seam, socket.h RdmaConnect). Servers need no flag: a
    // HELLO arriving on any connection upgrades it.
    bool tpu_transport = false;
    // TLS. Server side: non-null enables same-port sniffing (a 0x16
    // handshake byte upgrades the accepted connection; anything else stays
    // plain — the reference's ssl sniffing). Client side: non-null makes
    // ConnectIfNot run a TLS handshake right after the TCP connect;
    // sni_host carries the pre-resolution hostname for SNI.
    std::shared_ptr<SslContext> ssl_ctx;
    std::string sni_host;
  };

  // -- lifecycle (versioned_ref.h) --
  static int Create(const Options& opt, SocketId* id);
  static int Address(SocketId id, SocketUniquePtr* out);
  // error: errno-style reason recorded for debugging/health-check.
  int SetFailed(int error);
  using VersionedRefWithId<Socket>::Failed;

  // -- write path --
  // Wait-free: ownership of *data is taken (swapped out) on success.
  // Returns 0 on queue/success, -1 with errno on hard failure (failed
  // socket). notify_id (optional) gets fiber_id_error on write failure.
  int Write(tbutil::IOBuf* data, tbthread::fiber_id_t notify_id = 0);

  // -- read path (called from the input fiber / messenger) --
  ssize_t DoRead(size_t size_hint);
  tbutil::IOPortal& read_buf() { return _read_buf; }
  // Input-progress timestamp for the doorbell-free polling mode: the
  // read loop stamps every pass that got bytes; ProcessEvent polls until
  // the stamp ages past rpc_input_poll_us.
  void NoteInputProgress(int64_t now_us) {
    _last_input_us.store(now_us, std::memory_order_relaxed);
  }
  int64_t last_input_us() const {
    return _last_input_us.load(std::memory_order_relaxed);
  }

  // Ensure the client socket is connected (fiber-blocking; parks on the
  // epollout butex during a non-blocking connect). deadline_us on the
  // gettimeofday clock, 0 = default 1s.
  int ConnectIfNot(int64_t deadline_us = 0);

  // -- event entry points (EventDispatcher thread) --
  static void StartInputEvent(SocketId sid);
  static void HandleEpollOut(SocketId sid);

  // Diagnostic snapshot (racy atomic reads only; safe anytime).
  std::string DebugString() const;
  // Console support: every live socket id (server and client side), and a
  // bounded snapshot of this socket's pending RPC ids (returns the total).
  static void ListAll(std::vector<SocketId>* out);
  size_t PendingIdsSnapshot(std::vector<tbthread::fiber_id_t>* out,
                            size_t cap);
  // Hex of read_buf's first bytes. ONLY safe on a quiescent connection (the
  // hang state it exists to debug); returns a placeholder if input
  // processing is active.
  std::string DebugReadBufHead() const;

  // -- pending RPC correlation (errored on SetFailed) --
  void AddPendingId(tbthread::fiber_id_t id);
  void RemovePendingId(tbthread::fiber_id_t id);
  // Oldest pending id (0 when none) — correlation for protocols whose wire
  // carries no id (HTTP): the short connection has one in-flight RPC.
  tbthread::fiber_id_t FirstPendingId();

  // After the write queue fully drains, fail the socket (graceful
  // "Connection: close" semantics). One-way.
  void BeginDispatch() {
    _inflight_dispatch.fetch_add(1, std::memory_order_acq_rel);
  }
  void EndDispatch() {
    _inflight_dispatch.fetch_sub(1, std::memory_order_acq_rel);
  }
  // Bounded-patience drain (EOF cleanup path only — never hot).
  void WaitDispatchDrain();

  void MarkCloseAfterLastWrite() {
    _close_after_write.store(true, std::memory_order_release);
  }

  // -- streams multiplexed on this connection (closed on SetFailed) --
  using StreamFailCallback = void (*)(uint64_t stream_id, int error);
  static void SetStreamFailCallback(StreamFailCallback cb);
  void AddPendingStream(uint64_t stream_id);
  void RemovePendingStream(uint64_t stream_id);

  // -- tpu:// transport (ttpu/ici_endpoint.h) --
  // The endpoint is owned by the socket: installed during the handshake,
  // deleted on recycle. While non-null and active, WriteOnce routes
  // payloads through TX segment blocks instead of the TCP fd.
  ttpu::IciEndpoint* ici_endpoint() const {
    return _ici.load(std::memory_order_acquire);
  }
  void set_ici_endpoint(ttpu::IciEndpoint* ep) {
    _ici.store(ep, std::memory_order_release);
  }

  // Parse-pipeline cache: index of the protocol that parsed the last
  // message on this connection (input_messenger.cpp fast path).
  int preferred_protocol() const { return _preferred_protocol; }
  void set_preferred_protocol(int idx) { _preferred_protocol = idx; }

  // Per-connection protocol state (e.g. the HTTP/2 connection context:
  // HPACK tables, stream map, windows). Owned by the socket: `dtor` runs
  // at recycle, when no parser or writer can still touch it. Set once,
  // from the input fiber.
  void* protocol_data() const {
    return _protocol_data.load(std::memory_order_acquire);
  }
  void set_protocol_data(void* data, void (*dtor)(void*)) {
    _protocol_data_dtor = dtor;
    _protocol_data.store(data, std::memory_order_release);
  }

  int fd() const { return _fd.load(std::memory_order_acquire); }
  const tbutil::EndPoint& remote_side() const { return _remote_side; }
  bool server_side() const { return _server_side; }
  // TLS state: established iff non-null (reads/writes then route through
  // it). ALPN result is on the conn.
  SslConn* ssl_conn() const { return _ssl.load(std::memory_order_acquire); }
  void* user() const { return _user; }
  InputMessenger* messenger() const { return _messenger; }
  int error_code() const { return _error_code; }

  // Bytes in flight in the write queue (EOVERCROWDED guard; bvar-exposed).
  int64_t write_queue_bytes() const {
    return _write_queue_bytes.load(std::memory_order_relaxed);
  }

  // -- versioned_ref hooks --
  void OnRecycle();
  void OnFailed(int error);

  Socket();
  ~Socket();

 private:
  friend class VersionedRefWithId<Socket>;
  friend class WriteCoalesceScope;

  // Writer-side machinery (see socket.cpp for the protocol).
  void StartWrite(WriteRequest* req);
  static void* KeepWriteThunk(void* arg);
  void KeepWrite(WriteRequest* todo, WriteRequest* last);
  // Shared drain body: may_park=false returns false (with the remaining
  // chain in the out-params) instead of parking on backpressure.
  bool KeepWriteImpl(WriteRequest** todo_io, WriteRequest** last_io,
                     bool may_park);
  // Write out req->data as far as the kernel accepts. 1 = fully written,
  // 0 = EAGAIN with leftover, -1 = error.
  int WriteOnce(WriteRequest* req);
  // Plain-TCP fast path: gather the claimed chain [*todo ..] into ONE
  // writev (small pipelined RPCs collapse into a single syscall — 38% of
  // the 64B-echo profile was per-request writev calls). Fully-written
  // requests other than `last` are released and *todo advances past them.
  // Returns like WriteOnce, where 1 = chain empty. Falls back to
  // WriteOnce(head) for tpu:///TLS sockets.
  int WriteBatch(WriteRequest** todo, WriteRequest* last);
  int WaitEpollOut(int64_t deadline_us);
  void WaitSslReady();
  void ReleaseAllWrites(WriteRequest* todo, WriteRequest* last, int error);
  static void* ProcessEventThunk(void* arg);
  void ProcessEvent();

  std::atomic<int> _fd{-1};
  std::atomic<void*> _protocol_data{nullptr};
  void (*_protocol_data_dtor)(void*) = nullptr;
  tbutil::EndPoint _remote_side;
  InputMessenger* _messenger = nullptr;
  std::atomic<ttpu::IciEndpoint*> _ici{nullptr};
  bool _tpu_requested = false;
  bool _server_side = false;
  // TLS plumbing. _ssl_state: 0 = plain, 1 = server sniff pending, 2 =
  // handshaking (reads back off), 3 = established (_ssl non-null).
  enum : int { kSslOff = 0, kSslSniff = 1, kSslHandshaking = 2, kSslOn = 3 };
  std::shared_ptr<SslContext> _ssl_ctx;
  std::string _sni_host;
  std::atomic<int> _ssl_state{kSslOff};
  std::atomic<SslConn*> _ssl{nullptr};  // owned; freed in OnRecycle
  void* _user = nullptr;
  int _error_code = 0;
  int _preferred_protocol = -1;

  std::atomic<WriteRequest*> _write_head{nullptr};
  std::atomic<int64_t> _write_queue_bytes{0};
  std::atomic<bool> _close_after_write{false};
  tbthread::Butex* _epollout_butex;
  std::atomic<int> _nevent{0};  // pending read edges; input fiber active while > 0
  // When input bytes last arrived (cpuwide us; 0 = never). Fed by the
  // read loop, consumed by the doorbell-free polling mode
  // (rpc_input_poll_us): ProcessEvent keeps busy-polling the fd until
  // this falls poll_us behind now.
  std::atomic<int64_t> _last_input_us{0};
  // Parsed messages handed to dispatch whose handlers have not returned
  // yet. A deferred EOF on a CLIENT socket waits for this to hit zero
  // before SetFailed — the respond-then-close race across two input
  // events (response in event 1, EOF in event 2) must not error the
  // correlation id while the response dispatch is still in flight.
  std::atomic<int> _inflight_dispatch{0};
  // True from fd-publication until the non-blocking connect completes —
  // gates ConnectIfNot's lock-free fast path.
  std::atomic<bool> _connecting{false};
  // Serializes concurrent ConnectIfNot. Fiber mutex: it is held across the
  // connect park, and a std::mutex held across a fiber switch can deadlock a
  // single-worker scheduler.
  tbthread::FiberMutex _connect_mu;
  tbutil::IOPortal _read_buf;

  tbthread::FiberMutex _pending_mu;
  std::vector<tbthread::fiber_id_t> _pending_ids;
  std::vector<uint64_t> _pending_streams;
};

}  // namespace trpc
