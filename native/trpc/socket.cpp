#include "trpc/rpc_metrics.h"
#include "trpc/socket.h"

#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/uio.h>
#include <sys/socket.h>
#include <unistd.h>

#include <cerrno>
#include <cstring>
#include <set>

#include "tbthread/fiber.h"
#include "tbthread/key.h"
#include "tbthread/task_group.h"
#include "tbutil/logging.h"
#include "tbutil/object_pool.h"
#include "tbutil/time.h"
#include "trpc/errno.h"
#include "trpc/event_dispatcher.h"
#include "trpc/flags.h"
#include "trpc/input_messenger.h"
#include "ttpu/ici_endpoint.h"

namespace trpc {

namespace {

// EOVERCROWDED cap, hot-reloadable via /flags (reference
// FLAGS_socket_max_unwritten_bytes).
std::atomic<int64_t>* g_max_write_queue_bytes = TRPC_DEFINE_FLAG(
    socket_max_write_queue_bytes, 256LL << 20,
    "Max bytes queued on one socket before Write fails with EOVERCROWDED");
constexpr int64_t kDefaultConnectTimeoutUs = 1000000;

int make_non_blocking(int fd) {
  int flags = fcntl(fd, F_GETFL, 0);
  if (flags < 0) return -1;
  return fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

void set_no_delay(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
}

// Live-socket registry backing the /sockets and /ids console pages
// (reference builtin/sockets_service.cpp enumerates its SocketMap the same
// way). Create/recycle are not hot paths; a mutexed set is fine.
tbthread::FiberMutex g_live_mu;
std::set<trpc::SocketId> g_live_sockets;

struct KeepWriteArg {
  Socket* sock;  // carries one ref, released by KeepWrite
  WriteRequest* todo;
  WriteRequest* last;
};

// Fiber-local slot holding the active WriteCoalesceScope. The scope object
// itself lives on the owning fiber's stack; the slot stores only the
// pointer (no dtor — nothing to free). Works on plain pthreads too (key.h
// gives non-fiber threads a thread-local table).
tbthread::FiberKey coalesce_key() {
  static tbthread::FiberKey key = [] {
    tbthread::FiberKey k;
    tbthread::fiber_key_create(&k, nullptr);
    return k;
  }();
  return key;
}

// Writes at or below this size are worth deferring into a gathered flush.
// Tracks the reloadable ici_small_msg_threshold so "small" means the same
// thing for the inline channel, batchability, inline execution, and
// response coalescing (one knob, four gates — PERF.md round 7).
size_t small_write_bytes() { return ttpu::ici_small_msg_threshold(); }

}  // namespace

// ---------------- response coalescing scope ----------------

WriteCoalesceScope::WriteCoalesceScope(bool enabled, Socket* only)
    : _only(only) {
  if (!enabled || only == nullptr) return;
  _prev = static_cast<WriteCoalesceScope*>(
      tbthread::fiber_getspecific(coalesce_key()));
  tbthread::fiber_setspecific(coalesce_key(), this);
  _installed = true;
}

WriteCoalesceScope::~WriteCoalesceScope() {
  if (!_installed) return;
  Flush();
  tbthread::fiber_setspecific(coalesce_key(), _prev);
}

WriteCoalesceScope* WriteCoalesceScope::current() {
  return static_cast<WriteCoalesceScope*>(
      tbthread::fiber_getspecific(coalesce_key()));
}

void WriteCoalesceScope::Flush() {
  if (_sock == nullptr) return;
  Socket* s = _sock;
  WriteRequest* todo = _todo;
  WriteRequest* last = _last;
  _sock = nullptr;
  _todo = _last = nullptr;
  // Drain on THIS fiber: KeepWrite gathers everything queued behind the
  // adopted head (WriteBatch → one writev / one doorbell flush), retires
  // the queue, and handles failure/backpressure exactly like the
  // dedicated-writer fiber would.
  s->KeepWrite(todo, last);
  s->Deref();
}

void WriteCoalesceScope::FlushDetached() {
  if (_sock == nullptr) return;
  Socket* s = _sock;
  WriteRequest* todo = _todo;
  WriteRequest* last = _last;
  _sock = nullptr;
  _todo = _last = nullptr;
  // Common case: the kernel takes everything and the drain finishes here
  // with no park and no extra fiber. Only genuine backpressure (EAGAIN /
  // tpu:// credit starvation / TLS handshake) hands the leftovers to a
  // background writer fiber — the caller may hold the connection's read
  // claim, and parking under it would block the very reads (tpu:// credit
  // frames included) that could unpark the drain.
  if (s->KeepWriteImpl(&todo, &last, /*may_park=*/false)) {
    s->Deref();
    return;
  }
  auto* arg = new KeepWriteArg;
  arg->sock = s;  // the adoption ref transfers to the fiber
  arg->todo = todo;
  arg->last = last;
  tbthread::fiber_t tid;
  if (tbthread::fiber_start_background(&tid, nullptr, Socket::KeepWriteThunk,
                                       arg) != 0) {
    // Spawn failed (resource exhaustion): draining here could PARK under
    // the caller's read claim — the invariant this function exists to
    // keep. Re-adopt instead; the scope's later (post-claim) Flush or
    // destructor drains it.
    delete arg;
    _sock = s;
    _todo = todo;
    _last = last;
  }
}

const char* rpc_error_text(int error) {
  switch (error) {
    case TRPC_EEOF: return "EOF";
    case TRPC_EFAILEDSOCKET: return "socket failed";
    case TRPC_EOVERCROWDED: return "write queue overcrowded";
    case TRPC_ECONNECT: return "connect failed";
    case TRPC_ERPCTIMEDOUT: return "RPC timed out";
    case TRPC_EBACKUPREQUEST: return "backup request";
    case TRPC_ENOSERVICE: return "no such service";
    case TRPC_ENOMETHOD: return "no such method";
    case TRPC_EREQUEST: return "malformed request";
    case TRPC_EINTERNAL: return "server internal error";
    case TRPC_ERESPONSE: return "malformed response";
    case TRPC_ELIMIT: return "rejected by concurrency limit";
    case TRPC_ECANCELED: return "RPC canceled";
    case TRPC_ENODATA: return "no server available";
    default: return strerror(error);
  }
}

Socket::Socket() : _epollout_butex(tbthread::butex_create()) {}

Socket::~Socket() { tbthread::butex_destroy(_epollout_butex); }

int Socket::Create(const Options& opt, SocketId* id) {
  SocketUniquePtr ptr;
  VRefId vid;
  if (VersionedRefWithId<Socket>::Create(&ptr, &vid) != 0) return -1;
  Socket* s = ptr.get();
  s->_remote_side = opt.remote_side;
  s->_messenger = opt.messenger;
  s->_server_side = opt.server_side;
  s->_tpu_requested = opt.tpu_transport;
  s->_ssl_ctx = opt.ssl_ctx;
  s->_sni_host = opt.sni_host;
  s->_ssl_state.store(opt.ssl_ctx == nullptr ? kSslOff
                      : opt.server_side      ? kSslSniff
                                             : kSslHandshaking,
                      std::memory_order_relaxed);
  s->_user = opt.user;
  s->_ici.store(nullptr, std::memory_order_relaxed);
  s->_error_code = 0;
  s->_preferred_protocol = -1;
  s->_nevent.store(0, std::memory_order_relaxed);
  s->_write_queue_bytes.store(0, std::memory_order_relaxed);
  s->_close_after_write.store(false, std::memory_order_relaxed);
  s->_connecting.store(false, std::memory_order_relaxed);
  s->_fd.store(opt.fd, std::memory_order_release);
  {
    std::lock_guard<tbthread::FiberMutex> lk(g_live_mu);
    g_live_sockets.insert(vid);
  }
  if (opt.fd >= 0) {
    make_non_blocking(opt.fd);
    set_no_delay(opt.fd);
    if (EventDispatcher::shard(vid).AddConsumer(vid, opt.fd) != 0) {
      // On failure the CALLER keeps ownership of opt.fd: detach it before
      // the recycle path (OnRecycle must not close a caller-owned fd).
      s->_fd.store(-1, std::memory_order_release);
      ptr->SetFailed(errno != 0 ? errno : TRPC_EFAILEDSOCKET);
      return -1;
    }
  }
  *id = vid;
  return 0;
}

int Socket::Address(SocketId id, SocketUniquePtr* out) {
  return VersionedRefWithId<Socket>::Address(id, out);
}

int Socket::SetFailed(int error) {
  TB_VLOG(2) << "SetFailed sid=" << id() << " fd=" << fd() << " err=" << error
             << (server_side() ? " (server)" : " (client)");
  return VersionedRefWithId<Socket>::SetFailed(error);
}

namespace {
std::atomic<Socket::StreamFailCallback> g_stream_fail_cb{nullptr};
}  // namespace

void Socket::SetStreamFailCallback(StreamFailCallback cb) {
  g_stream_fail_cb.store(cb, std::memory_order_release);
}

void Socket::AddPendingStream(uint64_t stream_id) {
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  _pending_streams.push_back(stream_id);
}

void Socket::RemovePendingStream(uint64_t stream_id) {
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  for (size_t i = 0; i < _pending_streams.size(); ++i) {
    if (_pending_streams[i] == stream_id) {
      _pending_streams[i] = _pending_streams.back();
      _pending_streams.pop_back();
      return;
    }
  }
}

void Socket::OnFailed(int error) {
  _error_code = error;
  // Wake connect/KeepWrite parkers: they re-check Failed() and bail.
  tbthread::butex_increment_and_wake_all(_epollout_butex);
  ttpu::IciEndpoint* ici = _ici.load(std::memory_order_acquire);
  if (ici != nullptr) {
    ici->OnSocketFailed();  // wake handshake/credit parkers
  }
  // Propagate to every in-flight RPC and stream on this connection.
  std::vector<tbthread::fiber_id_t> ids;
  std::vector<uint64_t> streams;
  {
    std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
    ids.swap(_pending_ids);
    streams.swap(_pending_streams);
  }
  for (tbthread::fiber_id_t id : ids) {
    tbthread::fiber_id_error(id, error);
  }
  StreamFailCallback cb = g_stream_fail_cb.load(std::memory_order_acquire);
  if (cb != nullptr) {
    for (uint64_t sid : streams) cb(sid, error);
  }
}

void Socket::OnRecycle() {
  {
    std::lock_guard<tbthread::FiberMutex> lk(g_live_mu);
    g_live_sockets.erase(id());
  }
  // SslConn's destructor sends a best-effort close_notify through the fd:
  // it must run BEFORE close() — after close the number may already belong
  // to an unrelated descriptor and the TLS record would corrupt it.
  delete _ssl.exchange(nullptr, std::memory_order_acq_rel);
  int fd = _fd.exchange(-1, std::memory_order_acq_rel);
  if (fd >= 0) {
    TB_VLOG(2) << "recycle close fd=" << fd << " sid=" << id();
    EventDispatcher::shard(id()).RemoveConsumer(fd);
    close(fd);
  }
  // Last ref: no input fiber or writer can be touching the endpoint.
  delete _ici.exchange(nullptr, std::memory_order_acq_rel);
  _ssl_ctx.reset();
  _sni_host.clear();
  _ssl_state.store(kSslOff, std::memory_order_relaxed);
  if (void* pd = _protocol_data.exchange(nullptr, std::memory_order_acq_rel)) {
    if (_protocol_data_dtor != nullptr) _protocol_data_dtor(pd);
  }
  _protocol_data_dtor = nullptr;
  _tpu_requested = false;
  _read_buf.clear();
  _messenger = nullptr;
  _user = nullptr;
  _nevent.store(0, std::memory_order_relaxed);
  _inflight_dispatch.store(0, std::memory_order_relaxed);
  // The write queue is drained by the active writer before it drops its ref,
  // so by the time the last ref dies the head is null (or was released by
  // ReleaseAllWrites on failure).
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  _pending_ids.clear();
  _pending_streams.clear();
}

void Socket::AddPendingId(tbthread::fiber_id_t id) {
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  _pending_ids.push_back(id);
}

void Socket::RemovePendingId(tbthread::fiber_id_t id) {
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  for (size_t i = 0; i < _pending_ids.size(); ++i) {
    if (_pending_ids[i] == id) {
      _pending_ids[i] = _pending_ids.back();
      _pending_ids.pop_back();
      return;
    }
  }
}

tbthread::fiber_id_t Socket::FirstPendingId() {
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  return _pending_ids.empty() ? 0 : _pending_ids.front();
}

// ---------------- write path ----------------

int Socket::Write(tbutil::IOBuf* data, tbthread::fiber_id_t notify_id) {
  if (Failed()) {
    errno = TRPC_EFAILEDSOCKET;
    return -1;
  }
  if (_write_queue_bytes.load(std::memory_order_relaxed) >
      g_max_write_queue_bytes->load(std::memory_order_relaxed)) {
    errno = TRPC_EOVERCROWDED;
    return -1;
  }
  WriteRequest* req = tbutil::get_object<WriteRequest>();
  req->data.clear();
  req->data.swap(*data);
  req->next.store(nullptr, std::memory_order_relaxed);
  req->notify_id = notify_id;
  _write_queue_bytes.fetch_add(static_cast<int64_t>(req->data.size()),
                               std::memory_order_relaxed);
  StartWrite(req);
  return 0;
}

void Socket::StartWrite(WriteRequest* req) {
  // Wait-free enqueue (reference socket.cpp:1696): producers that find a
  // non-empty head just link behind it and return — only the producer that
  // installed into an empty head becomes the writer.
  WriteRequest* prev = _write_head.exchange(req, std::memory_order_acq_rel);
  if (prev != nullptr) {
    req->next.store(prev, std::memory_order_release);
    return;
  }
  // Deterministic coalescing: a small RESPONSE on the batch's own
  // connection, issued under an active WriteCoalesceScope (batch dispatch
  // / inline fast path), leaves the bytes queued and hands the writer
  // role to the scope — its Flush at batch end gathers every response of
  // the batch into one writev/doorbell flush. Only the first write adopts
  // (later producers see a non-empty head above and just link), and ONLY
  // the scope's pinned socket: a write to any other socket — e.g. a
  // handler's nested client RPC, which must hit the wire before the
  // handler parks for its response — goes out the normal way.
  if (WriteCoalesceScope* scope = WriteCoalesceScope::current();
      scope != nullptr && scope->_only == this && scope->_sock == nullptr &&
      req->data.size() <= small_write_bytes()) {
    Ref();
    scope->_sock = this;
    scope->_todo = req;
    scope->_last = req;
    return;
  }
  // Coalescing defer: a SMALL write from a worker that still has runnable
  // fibers queued (a response burst mid-drain, pipelined callers about to
  // send) hands off to a KeepWrite fiber instead of flushing inline — the
  // fiber runs after those producers, gathering their messages into one
  // writev. A lone write (idle worker) keeps the zero-switch inline path:
  // deferring it would only add latency. Under concurrent 64B callers the
  // coalescing factor sets the small-RPC floor (VERDICT r4 #4).
  if (req->data.size() <= small_write_bytes() &&
      tbthread::fiber_worker_busy()) {
    auto* arg = new KeepWriteArg;
    Ref();
    arg->sock = this;
    arg->todo = req;
    arg->last = req;
    tbthread::fiber_t tid;
    if (tbthread::fiber_start_background(&tid, nullptr, KeepWriteThunk,
                                         arg) == 0) {
      return;
    }
    delete arg;
    Deref();
  }
  // We are the writer. Write inline once (the common small-message case
  // finishes here without any context switch), then hand off leftovers.
  int rc = WriteOnce(req);
  if (rc < 0) {
    int err = errno != 0 ? errno : TRPC_EFAILEDSOCKET;
    SetFailed(err);
    ReleaseAllWrites(req, req, err);
    return;
  }
  if (rc == 1) {
    WriteRequest* expected = req;
    if (_write_head.compare_exchange_strong(expected, nullptr,
                                            std::memory_order_acq_rel)) {
      tbutil::return_object(req);
      if (_close_after_write.load(std::memory_order_acquire)) {
        TB_VLOG(2) << "graceful close (inline) sid=" << id();
        SetFailed(TRPC_EEOF);  // graceful Connection: close
      }
      return;
    }
  }
  // Leftover bytes or new requests arrived: continue in a KeepWrite fiber
  // so the caller returns immediately (reference socket.cpp:1806).
  auto* arg = new KeepWriteArg;
  Ref();
  arg->sock = this;
  arg->todo = (rc == 1) ? nullptr : req;
  arg->last = req;
  tbthread::fiber_t tid;
  if (tbthread::fiber_start_background(&tid, nullptr, KeepWriteThunk, arg) !=
      0) {
    KeepWriteThunk(arg);  // degrade: write in the caller
  }
}

void* Socket::KeepWriteThunk(void* argv) {
  auto* arg = static_cast<KeepWriteArg*>(argv);
  Socket* s = arg->sock;
  s->KeepWrite(arg->todo, arg->last);
  delete arg;
  s->Deref();
  return nullptr;
}

// todo: FIFO chain of claimed-but-unwritten requests (next = newer, null
// terminated). last: the newest claimed request — the detach point in
// _write_head. `last` is only released after a successful detach CAS to
// prevent pool-reuse ABA on the head pointer.
void Socket::KeepWrite(WriteRequest* todo, WriteRequest* last) {
  if (!KeepWriteImpl(&todo, &last, /*may_park=*/true)) {
    TB_LOG(ERROR) << "KeepWrite(may_park) returned unfinished";  // unreachable
  }
}

// Shared writer-drain body. may_park=true: waits out backpressure (the
// dedicated-writer behavior) and always returns true. may_park=false:
// returns false with *todo_io/*last_io holding the remaining chain the
// moment a park would be needed — the caller (a fiber that must not
// park, e.g. the input fiber under its read claim) hands the leftovers
// to a background writer fiber instead.
bool Socket::KeepWriteImpl(WriteRequest** todo_io, WriteRequest** last_io,
                           bool may_park) {
  WriteRequest* todo = *todo_io;
  WriteRequest* last = *last_io;
  while (true) {
    while (todo != nullptr) {
      if (Failed()) {
        // _error_code may not be published yet (SetFailed bumps the version
        // before OnFailed stores the code): never propagate 0 as an error.
        const int err = _error_code != 0 ? _error_code : TRPC_EFAILEDSOCKET;
        ReleaseAllWrites(todo, last, err);
        return true;
      }
      int rc = WriteBatch(&todo, last);
      if (rc < 0) {
        int err = errno != 0 ? errno : TRPC_EFAILEDSOCKET;
        SetFailed(err);
        ReleaseAllWrites(todo, last, err);
        return true;
      }
      if (rc == 1) break;  // chain drained; try to retire the queue
      if (rc == 0) {
        if (!may_park) {
          *todo_io = todo;
          *last_io = last;
          return false;
        }
        // Three park reasons: TCP backpressure (epollout), an exhausted
        // tpu:// credit window (the peer still holds our TX blocks), or a
        // TLS handshake still in flight.
        ttpu::IciEndpoint* ici = _ici.load(std::memory_order_acquire);
        const int sstate = _ssl_state.load(std::memory_order_acquire);
        if (ici != nullptr && ici->credit_starved()) {
          ici->WaitCredit();
        } else if (sstate == kSslSniff || sstate == kSslHandshaking) {
          WaitSslReady();
        } else {
          WaitEpollOut(0);
        }
        continue;
      }
    }
    // Everything claimed is on the wire: try to retire the queue.
    WriteRequest* expected = last;
    if (_write_head.compare_exchange_strong(expected, nullptr,
                                            std::memory_order_acq_rel)) {
      tbutil::return_object(last);
      if (_close_after_write.load(std::memory_order_acquire)) {
        TB_VLOG(2) << "graceful close (keepwrite) sid=" << id();
        SetFailed(TRPC_EEOF);  // graceful Connection: close
      }
      return true;
    }
    // New requests arrived while we wrote. expected = current head
    // (newest). Walk newest -> older until `last`, reversing into a FIFO
    // chain. A producer may have exchanged itself in but not yet linked
    // next: spin for the link (it is two instructions away).
    WriteRequest* fifo = nullptr;
    WriteRequest* p = expected;
    while (p != last) {
      WriteRequest* older = p->next.load(std::memory_order_acquire);
      while (older == nullptr) {
        tbthread::fiber_yield();
        older = p->next.load(std::memory_order_acquire);
      }
      p->next.store(fifo, std::memory_order_relaxed);
      fifo = p;
      p = older;
    }
    tbutil::return_object(last);
    todo = fifo;
    last = expected;
  }
}

int Socket::WriteBatch(WriteRequest** todo, WriteRequest* last) {
  WriteRequest* head = *todo;
  if (head == nullptr) return 1;
  // tpu:// path: move the WHOLE chain into blocks/inline control bytes,
  // one flush syscall at the chain's end (WriteMessage flush_now=false
  // batches; starvation/backpressure force the flush before any park).
  ttpu::IciEndpoint* ici = _ici.load(std::memory_order_acquire);
  if (ici != nullptr && ici->active()) {
    const int ifd = _fd.load(std::memory_order_acquire);
    if (ifd < 0) {
      errno = ENOTCONN;
      return -1;
    }
    WriteRequest* r = head;
    while (r != nullptr) {
      WriteRequest* next = r->next.load(std::memory_order_relaxed);
      const size_t before = r->data.size();
      const int rc = ici->WriteMessage(&r->data, ifd,
                                       /*flush_now=*/next == nullptr);
      _write_queue_bytes.fetch_sub(
          static_cast<int64_t>(before - r->data.size()),
          std::memory_order_relaxed);
      if (rc < 0) {
        if (errno == 0) errno = TRPC_EFAILEDSOCKET;
        *todo = r;
        return -1;
      }
      if (rc == 0) {
        *todo = r;  // park; the flush already ran inside WriteMessage
        return 0;
      }
      if (r != last) tbutil::return_object(r);
      r = next;
    }
    *todo = nullptr;
    return 1;
  }
  // TLS records: delegate one request at a time (SSL_write batches records
  // internally anyway).
  if (_ssl_state.load(std::memory_order_acquire) != kSslOff) {
    const int rc = WriteOnce(head);
    if (rc <= 0) return rc;
    *todo = head->next.load(std::memory_order_relaxed);
    if (head != last) tbutil::return_object(head);
    return *todo == nullptr ? 1 : 2;  // 2 = progress, keep going
  }
  const int fd = _fd.load(std::memory_order_acquire);
  if (fd < 0) {
    errno = ENOTCONN;
    return -1;
  }
  constexpr int kMaxIov = 64;
  iovec iov[kMaxIov];
  int niov = 0;
  for (WriteRequest* r = head; r != nullptr && niov < kMaxIov;
       r = r->next.load(std::memory_order_relaxed)) {
    const size_t nblocks = r->data.backing_block_num();
    for (size_t b = 0; b < nblocks && niov < kMaxIov; ++b) {
      const std::string_view blk = r->data.backing_block(b);
      if (blk.empty()) continue;
      iov[niov].iov_base = const_cast<char*>(blk.data());
      iov[niov].iov_len = blk.size();
      ++niov;
    }
  }
  size_t total_iov = 0;
  for (int i = 0; i < niov; ++i) total_iov += iov[i].iov_len;
  ssize_t nw = 0;
  if (niov > 0) {
    do {
      nw = writev(fd, iov, niov);
    } while (nw < 0 && errno == EINTR);
    if (nw < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) return 0;
      return -1;
    }
    _write_queue_bytes.fetch_sub(nw, std::memory_order_relaxed);
    GlobalRpcMetrics::instance().bytes_out << nw;
  }
  const bool kernel_full = static_cast<size_t>(nw) < total_iov;
  // Distribute the written bytes over the chain; release fully-drained
  // requests (keep `last`: it is the retire-CAS detach point).
  size_t remaining = static_cast<size_t>(nw);
  WriteRequest* r = head;
  while (r != nullptr) {
    const size_t sz = r->data.size();
    if (sz > remaining) {
      r->data.pop_front(remaining);
      *todo = r;
      // Park only on kernel backpressure; a chain cut by the iov cap has
      // more writable bytes right now.
      return kernel_full ? 0 : 2;
    }
    remaining -= r->data.pop_front(sz);
    WriteRequest* next = r->next.load(std::memory_order_relaxed);
    if (r != last) {
      tbutil::return_object(r);
    }
    r = next;
  }
  *todo = r;
  if (r == nullptr) return 1;
  return kernel_full ? 0 : 2;  // beyond-cap requests still pending
}

int Socket::WriteOnce(WriteRequest* req) {
  const int fd = _fd.load(std::memory_order_acquire);
  if (fd < 0) {
    errno = ENOTCONN;
    return -1;
  }
  ttpu::IciEndpoint* ici = _ici.load(std::memory_order_acquire);
  if (ici != nullptr && ici->active()) {
    // tpu:// path: payload bytes move into TX segment blocks (the fake-ICI
    // "DMA"), doorbells/credits ride the TCP fd. The reference's zero-copy
    // send branch (socket.cpp:1754-1766) plays the same role.
    const size_t before = req->data.size();
    const int rc = ici->WriteMessage(&req->data, fd);
    _write_queue_bytes.fetch_sub(
        static_cast<int64_t>(before - req->data.size()),
        std::memory_order_relaxed);
    if (rc < 0 && errno == 0) errno = TRPC_EFAILEDSOCKET;
    return rc;
  }
  const int sstate = _ssl_state.load(std::memory_order_acquire);
  if (sstate == kSslSniff || sstate == kSslHandshaking) {
    return 0;  // TLS not up: KeepWrite parks in WaitSslReady
  }
  if (sstate == kSslOn) {
    SslConn* conn = _ssl.load(std::memory_order_acquire);
    while (!req->data.empty()) {
      // Retry-stable: after EAGAIN the SAME block head is offered again
      // (OpenSSL without ENABLE_PARTIAL_WRITE requires the same buffer).
      const std::string_view blk = req->data.backing_block(0);
      const ssize_t nw = conn->Write(blk.data(), blk.size());
      if (nw < 0) {
        if (errno == EAGAIN) return 0;
        return -1;
      }
      req->data.pop_front(static_cast<size_t>(nw));
      _write_queue_bytes.fetch_sub(nw, std::memory_order_relaxed);
      GlobalRpcMetrics::instance().bytes_out << nw;
    }
    return 1;
  }
  while (!req->data.empty()) {
    ssize_t nw = req->data.cut_into_file_descriptor(fd);
    if (nw < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) return 0;
      return -1;
    }
    _write_queue_bytes.fetch_sub(nw, std::memory_order_relaxed);
    GlobalRpcMetrics::instance().bytes_out << nw;
  }
  return 1;
}

// Park until the TLS handshake completes (or the socket fails). Cannot use
// WaitEpollOut: its poll() fast path sees a WRITABLE fd and returns
// immediately, which would busy-spin the writer while the handshake runs.
// Completion paths (DoRead server sniff, ConnectIfNot client, OnFailed)
// bump the epollout butex after publishing the state change.
void Socket::WaitSslReady() {
  const int expected =
      tbthread::butex_value(_epollout_butex)->load(std::memory_order_acquire);
  const int sstate = _ssl_state.load(std::memory_order_acquire);
  if (sstate == kSslOn || sstate == kSslOff || Failed()) return;
  tbthread::butex_wait(_epollout_butex, expected, nullptr);
}

int Socket::WaitEpollOut(int64_t deadline_us) {
  const int fd = _fd.load(std::memory_order_acquire);
  if (fd < 0) return -1;
  const int expected =
      tbthread::butex_value(_epollout_butex)->load(std::memory_order_acquire);
  // Close the missed-edge race: if the fd became writable before we
  // snapshotted the butex, the edge (and its wake) already happened — check
  // writability non-blockingly before parking.
  pollfd pfd{fd, POLLOUT, 0};
  if (poll(&pfd, 1, 0) > 0 && (pfd.revents & (POLLOUT | POLLERR | POLLHUP))) {
    return 0;
  }
  timespec abstime;
  timespec* pabs = nullptr;
  if (deadline_us > 0) {
    abstime.tv_sec = deadline_us / 1000000;
    abstime.tv_nsec = (deadline_us % 1000000) * 1000;
    pabs = &abstime;
  }
  int rc = tbthread::butex_wait(_epollout_butex, expected, pabs);
  if (rc != 0 && errno == ETIMEDOUT) return -1;
  return 0;
}

// Called only by the active writer, which owns the FIFO chain `todo`
// terminating at `last` (the detach point in _write_head). Releases the
// not-yet-claimed suffix hanging off _write_head FIRST — walking newest →
// older with `last` as the terminator, spinning through producers that
// exchanged-but-not-yet-linked — then the claimed chain. `last`'s pointer
// value is needed as the walk terminator, hence this ordering.
void Socket::ReleaseAllWrites(WriteRequest* todo, WriteRequest* last,
                              int error) {
  auto release_one = [this, error](WriteRequest* r) {
    _write_queue_bytes.fetch_sub(static_cast<int64_t>(r->data.size()),
                                 std::memory_order_relaxed);
    if (r->notify_id != 0) {
      tbthread::fiber_id_error(r->notify_id, error);
    }
    r->data.clear();
    tbutil::return_object(r);
  };
  WriteRequest* p = _write_head.exchange(nullptr, std::memory_order_acq_rel);
  while (p != nullptr && p != last) {
    WriteRequest* older = p->next.load(std::memory_order_acquire);
    while (older == nullptr) {
      tbthread::fiber_yield();
      older = p->next.load(std::memory_order_acquire);
    }
    release_one(p);
    p = older;
  }
  // Claimed FIFO chain (includes `last` as its tail).
  while (todo != nullptr) {
    WriteRequest* next = todo->next.load(std::memory_order_relaxed);
    release_one(todo);
    todo = next;
  }
}

void Socket::ListAll(std::vector<SocketId>* out) {
  std::lock_guard<tbthread::FiberMutex> lk(g_live_mu);
  out->assign(g_live_sockets.begin(), g_live_sockets.end());
}

size_t Socket::PendingIdsSnapshot(std::vector<tbthread::fiber_id_t>* out,
                                  size_t cap) {
  std::lock_guard<tbthread::FiberMutex> lk(_pending_mu);
  if (out != nullptr) {
    const size_t n = std::min(cap, _pending_ids.size());
    out->assign(_pending_ids.begin(), _pending_ids.begin() + n);
  }
  return _pending_ids.size();
}

// ---------------- connect path ----------------

int Socket::ConnectIfNot(int64_t deadline_us) {
  // Fast path only when the fd is published AND the connect that published
  // it has completed (the _fd release-store orders the _connecting store
  // before it, so seeing the fd implies seeing _connecting == true until
  // success clears it).
  if (_fd.load(std::memory_order_acquire) >= 0 &&
      !_connecting.load(std::memory_order_acquire)) {
    return 0;
  }
  if (Failed()) {
    errno = TRPC_EFAILEDSOCKET;
    return -1;
  }
  std::lock_guard<tbthread::FiberMutex> lk(_connect_mu);
  if (Failed()) {
    errno = TRPC_EFAILEDSOCKET;
    return -1;
  }
  if (_fd.load(std::memory_order_acquire) >= 0) return 0;
  if (deadline_us <= 0) {
    deadline_us = tbutil::gettimeofday_us() + kDefaultConnectTimeoutUs;
  }
  int fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK | SOCK_CLOEXEC, 0);
  if (fd < 0) return -1;
  set_no_delay(fd);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr = _remote_side.ip;
  addr.sin_port = htons(static_cast<uint16_t>(_remote_side.port));
  int rc = connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr));
  if (rc != 0 && errno != EINPROGRESS) {
    close(fd);
    SetFailed(TRPC_ECONNECT);
    errno = TRPC_ECONNECT;
    return -1;
  }
  // Publish the fd and register before waiting: the EPOLLOUT edge of
  // connect-completion is the wakeup. Writers racing in before completion
  // just queue (WriteOnce returns EAGAIN on an in-progress fd and KeepWrite
  // parks on the same epollout butex).
  _connecting.store(true, std::memory_order_release);
  _fd.store(fd, std::memory_order_release);
  if (EventDispatcher::shard(id()).AddConsumer(id(), fd) != 0) {
    SetFailed(TRPC_ECONNECT);  // OnRecycle closes the fd
    errno = TRPC_ECONNECT;
    return -1;
  }
  if (rc != 0) {
    pollfd pfd{fd, POLLOUT, 0};
    while (true) {
      pfd.revents = 0;
      int pr = poll(&pfd, 1, 0);
      if (pr > 0) break;  // writable or error (revents checked below)
      if (tbutil::gettimeofday_us() >= deadline_us) {
        // SetFailed (not a quiet rollback): queued writers parked on the
        // epollout butex get woken + errored, pending ids are notified.
        SetFailed(TRPC_ERPCTIMEDOUT);
        errno = TRPC_ERPCTIMEDOUT;
        return -1;
      }
      if (Failed()) {
        errno = TRPC_EFAILEDSOCKET;
        return -1;
      }
      WaitEpollOut(deadline_us);
    }
    int err = 0;
    socklen_t len = sizeof(err);
    getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &len);
    // SO_ERROR alone is not enough: the input fiber's read may have already
    // CONSUMED the pending error (readv on a refused connect clears it), so
    // also trust the poll revents.
    if (err != 0 || (pfd.revents & (POLLERR | POLLHUP)) != 0) {
      SetFailed(TRPC_ECONNECT);
      errno = TRPC_ECONNECT;
      return -1;
    }
  }
  // TLS upgrade: handshake right after the TCP connect, inside the connect
  // lock (the reference's SSLConnect seam). Input events back off while
  // _ssl_state is kSslHandshaking; the handshake's own fiber_fd_wait
  // consumes readability.
  if (_ssl_ctx != nullptr && !_server_side &&
      _ssl.load(std::memory_order_acquire) == nullptr) {
    auto* conn = new SslConn(_ssl_ctx.get(), fd, /*server=*/false, _sni_host);
    if (!conn->valid() || conn->Handshake(deadline_us) != 0) {
      delete conn;
      SetFailed(TRPC_ECONNECT);
      errno = TRPC_ECONNECT;
      return -1;
    }
    _ssl.store(conn, std::memory_order_release);
    _ssl_state.store(kSslOn, std::memory_order_release);
    tbthread::butex_increment_and_wake_all(_epollout_butex);
    // App data may already sit decrypted inside the SSL object (it rode in
    // with the final handshake flight); the edge that delivered it was
    // consumed by the handshake — drain explicitly.
    StartInputEvent(id());
  }
  // tpu:// upgrade (the reference's app_connect seam): send HELLO, park
  // until the ACK arrives on the input fiber. _connecting stays true so no
  // caller takes the fast path until the transport is ready.
  if (_tpu_requested && _ici.load(std::memory_order_acquire) == nullptr) {
    ttpu::IciEndpoint* ep = ttpu::IciEndpoint::StartClient(this);
    if (ep == nullptr || ep->WaitActive(deadline_us) != 0) {
      SetFailed(TRPC_ECONNECT);
      errno = TRPC_ECONNECT;
      return -1;
    }
  }
  _connecting.store(false, std::memory_order_release);
  return 0;
}

// ---------------- read path ----------------

ssize_t Socket::DoRead(size_t size_hint) {
  const int fd = _fd.load(std::memory_order_acquire);
  if (fd < 0) {
    errno = ENOTCONN;
    return -1;
  }
  int sstate = _ssl_state.load(std::memory_order_acquire);
  if (sstate == kSslSniff) {
    // Same-port TLS sniffing (reference ssl_helper): a TLS ClientHello
    // starts with content-type 0x16; anything else stays plaintext on the
    // same listener. Runs on the input fiber, which owns the read side.
    unsigned char first;
    const ssize_t np = recv(fd, &first, 1, MSG_PEEK);
    if (np == 0) return 0;  // EOF before any byte
    if (np < 0) return -1;  // EAGAIN et al
    if (first != 0x16) {
      _ssl_state.store(kSslOff, std::memory_order_release);
      sstate = kSslOff;
      tbthread::butex_increment_and_wake_all(_epollout_butex);
    } else {
      auto* conn = new SslConn(_ssl_ctx.get(), fd, /*server=*/true, "");
      if (!conn->valid()) {
        delete conn;
        errno = EPROTO;
        return -1;
      }
      _ssl_state.store(kSslHandshaking, std::memory_order_release);
      const int64_t hs_deadline = tbutil::gettimeofday_us() + 10 * 1000000;
      if (conn->Handshake(hs_deadline) != 0) {
        delete conn;
        if (errno == 0) errno = EPROTO;
        return -1;  // fails the socket via the read-error path
      }
      _ssl.store(conn, std::memory_order_release);
      _ssl_state.store(kSslOn, std::memory_order_release);
      sstate = kSslOn;
      // Writers that queued during the handshake park on epollout.
      tbthread::butex_increment_and_wake_all(_epollout_butex);
    }
  } else if (sstate == kSslHandshaking) {
    // Client handshake in flight (ConnectIfNot drives it): the input
    // event backs off; the handshake's own fd-wait consumes readability.
    errno = EAGAIN;
    return -1;
  }
  if (sstate == kSslOn) {
    SslConn* conn = _ssl.load(std::memory_order_acquire);
    // TLS records decrypt through a bounce buffer (TLS copies internally
    // anyway); semantics mirror append_from_file_descriptor.
    char buf[16 * 1024];
    ssize_t total = 0;
    while (static_cast<size_t>(total) < size_hint) {
      const ssize_t n = conn->Read(buf, sizeof(buf));
      if (n > 0) {
        _read_buf.append(buf, static_cast<size_t>(n));
        total += n;
        continue;
      }
      if (n == 0) return total > 0 ? total : 0;           // EOF
      if (errno == EAGAIN) return total > 0 ? total : -1;  // drained
      // Fatal TLS error AFTER decrypted bytes landed this call: surface
      // the bytes first (a complete response may be among them — the
      // respond-then-close pattern); the error re-raises on the next call.
      return total > 0 ? total : -1;
    }
    return total;
  }
  return _read_buf.append_from_file_descriptor(fd, size_hint);
}

void Socket::WaitDispatchDrain() {
  const int64_t deadline_us = tbutil::monotonic_time_us() + 500 * 1000;
  for (int spins = 0;
       _inflight_dispatch.load(std::memory_order_acquire) > 0; ++spins) {
    if (spins < 64) {
      tbthread::fiber_yield();
    } else {
      if (tbutil::monotonic_time_us() >= deadline_us) {
        // A dispatched handler is parked long-term; proceeding may race a
        // response delivery, but the other pending RPCs on this dead
        // connection need their error sweep more.
        TB_LOG(WARNING) << "dispatch drain timed out on sock " << id();
        return;
      }
      tbthread::fiber_usleep(100);
    }
  }
}

void Socket::StartInputEvent(SocketId sid) {
  SocketUniquePtr s;
  if (Address(sid, &s) != 0) return;
  if (s->_messenger == nullptr) return;
  if (s->_nevent.fetch_add(1, std::memory_order_acq_rel) == 0) {
    // First edge: this fiber owns input processing until the counter
    // returns to 0. The ref moves into the fiber.
    Socket* raw = s.release();
    tbthread::fiber_t tid;
    if (tbthread::fiber_start_urgent(&tid, nullptr, ProcessEventThunk, raw) !=
        0) {
      ProcessEventThunk(raw);  // degrade: process on the dispatcher thread
    }
  }
}

void* Socket::ProcessEventThunk(void* argv) {
  static_cast<Socket*>(argv)->ProcessEvent();
  return nullptr;
}

void Socket::ProcessEvent() {
  InputMessenger* messenger = _messenger;
  InputMessageBase* tail = nullptr;
  int defer_error = 0;
  int n = _nevent.load(std::memory_order_acquire);
  // Inline fast-path requests run DURING OnNewMessages on this fiber;
  // their responses coalesce under this scope and flush once per read
  // event. Pinned to THIS connection: a handler's writes to any other
  // socket go out immediately.
  WriteCoalesceScope coalesce(true, this);
  while (true) {
    if (!Failed() && defer_error == 0 && messenger != nullptr) {
      InputMessageBase* m = messenger->OnNewMessages(this, &defer_error);
      if (m != nullptr) {
        if (tail != nullptr) messenger->ProcessInFiber(this, tail);
        tail = m;
      }
    }
    // Inline responses accumulated during this read pass go out now —
    // once per pass, so sustained inbound traffic (the CAS below failing
    // repeatedly) cannot stretch their latency past one pass. DETACHED:
    // we still hold the read claim, and a synchronous drain that parked
    // on backpressure would block this connection's reads — on tpu://
    // including the credit frames the drain itself might wait for.
    coalesce.FlushDetached();
    // Doorbell-free polling mode (rpc_input_poll_us): with the fd
    // drained, nothing owed to a deferred handler and input seen less
    // than poll_us ago, keep the read claim and re-poll instead of
    // parking back into epoll — consecutive small RPCs skip the
    // doorbell-edge wakeup (epoll_wait + dispatcher hop + fiber spawn)
    // entirely. The budget is measured from the LAST byte that arrived,
    // so a live ping-pong stream stays in the polled regime while an
    // idle connection stops burning its worker after one window. `tail`
    // taking a non-inline message ends the poll: running its handler
    // beats shaving the next wakeup.
    if (tail == nullptr && defer_error == 0 && !Failed() &&
        messenger != nullptr) {
      const int64_t poll_us = input_poll_us();
      const int64_t last = last_input_us();
      if (poll_us > 0 && last != 0 &&
          tbutil::cpuwide_time_us() - last < poll_us) {
        for (int i = 0; i < 64; ++i) {
#if defined(__x86_64__) || defined(__i386__)
          asm volatile("pause" ::: "memory");
#endif
        }
        continue;  // re-run the read pass: the poll IS the next DoRead
      }
    }
    // If no new edges arrived while we read, hand the read claim back.
    if (_nevent.compare_exchange_strong(n, 0, std::memory_order_acq_rel,
                                        std::memory_order_acquire)) {
      break;
    }
    if (Failed() || defer_error != 0) {  // stop spinning on a dead socket
      _nevent.store(0, std::memory_order_release);
      break;
    }
  }
  // The claim is released: new data starts a fresh input fiber. Only now
  // run the trailing handler inline — if it parks (slow service method), it
  // blocks just this fiber, not the connection (no head-of-line blocking).
  if (tail != nullptr && messenger != nullptr) {
    messenger->ProcessInline(this, tail);
    if (!_server_side) EndDispatch();  // counted at parse time
  }
  // The tail handler's response may have re-adopted into the scope: put
  // it on the wire BEFORE the deferred failure below — SetFailed first
  // would make the flush release the response unsent, breaking the
  // respond-then-close delivery contract. (The claim is released; a
  // synchronous drain may park, which is fine here.)
  coalesce.Flush();
  // EOF/read errors fail the socket only AFTER the response that rode in
  // with them was delivered (respond-then-close peers). Same-event tails
  // were just delivered above; responses read by a PREVIOUS input event
  // may still be mid-dispatch on other fibers — wait those out on client
  // sockets, or SetFailed's pending-id sweep errors an RPC whose response
  // is already in hand (server side skips the wait: request handlers may
  // park on this very socket's write queue).
  if (defer_error != 0) {
    if (!_server_side) WaitDispatchDrain();
    SetFailed(defer_error);
  }
  Deref();
}

std::string Socket::DebugString() const {
  char buf[256];
  snprintf(buf, sizeof(buf),
           "sock=%llu fd=%d failed=%d nevent=%d read_buf=%zu wq_bytes=%lld "
           "write_head=%d preferred=%d",
           static_cast<unsigned long long>(id()),
           _fd.load(std::memory_order_acquire), int(Failed()),
           _nevent.load(std::memory_order_acquire), _read_buf.size(),
           static_cast<long long>(
               _write_queue_bytes.load(std::memory_order_relaxed)),
           int(_write_head.load(std::memory_order_acquire) != nullptr),
           preferred_protocol());
  return buf;
}

std::string Socket::DebugReadBufHead() const {
  // _read_buf is a non-atomic multi-word structure owned by the input
  // fiber; walking it concurrently is a use-after-free, not just a torn
  // read. Only touch it when no input processing is active — which is
  // exactly the stuck state this forensics call exists for.
  if (_nevent.load(std::memory_order_acquire) != 0) {
    return "(input fiber active: head withheld)";
  }
  std::string out;
  const size_t n = std::min<size_t>(_read_buf.size(), 96);
  if (n > 0) {
    uint8_t head[96];
    _read_buf.copy_to(head, n);
    out += "head=";
    char hex[4];
    for (size_t i = 0; i < n; ++i) {
      snprintf(hex, sizeof(hex), "%02x", head[i]);
      out += hex;
    }
  }
  return out;
}

void Socket::HandleEpollOut(SocketId sid) {
  SocketUniquePtr s;
  if (Address(sid, &s) != 0) return;
  tbthread::butex_increment_and_wake_all(s->_epollout_butex);
}

}  // namespace trpc
