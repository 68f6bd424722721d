// SocketMap: process-wide cache of client connections keyed by endpoint —
// "single connection" semantics: all Channels to the same server share one
// socket (the reference's default, controller.cpp:1148) — plus per-endpoint
// free-lists backing ConnectionType::kPooled (reference socket_map.h:82
// SocketPool: each RPC borrows an exclusive socket, returns it on success).
// Capability parity: reference src/brpc/socket_map.h:82-150 (SocketMapInsert/
// Find; dead sockets replaced on next acquire).
#pragma once

#include <mutex>
#include <unordered_map>
#include <vector>

#include "tbutil/endpoint.h"
#include "trpc/socket.h"

namespace trpc {

// How a Channel maps RPCs onto connections (reference socket_map.h:82,
// controller.cpp:1148-1160 CONNECTION_TYPE_{SINGLE,POOLED,SHORT}):
//  - kSingle: every Channel to one endpoint multiplexes one shared socket
//    (wait-free write queue + correlation ids make this safe) — lowest fd
//    cost, but one kernel socket serializes the read path.
//  - kPooled: each RPC borrows an exclusive socket from a per-endpoint
//    free-list and returns it on success — N in-flight RPCs ride N sockets,
//    scaling the read path across EventDispatcher threads.
//  - kShort: a fresh connection per RPC, closed at the end — required by
//    protocols whose wire has no correlation id (HTTP/1.x w/o pipelining).
enum class ConnectionType : uint8_t { kSingle = 0, kPooled = 1, kShort = 2 };

// Client transport selection: tpu:// upgrade, TLS, and the SNI hostname.
// tpu/tls are part of the connection-cache key (plain, tpu and tls
// connections to one endpoint are distinct sockets); sni_host is carried to
// the socket but keyed by endpoint. The bool constructor keeps legacy
// call sites (`GetOrCreate(pt, &s, /*tpu=*/true)`) working.
struct ClientTransport {
  bool tpu = false;
  bool tls = false;
  // TLS ALPN policy: gRPC/h2 channels MUST offer h2 (strict gRPC servers
  // refuse without it); HTTP/1.1 and tstd channels must NOT (an
  // ALPN-honoring third-party server would select h2 and then reject
  // their non-h2 bytes). Chosen per channel protocol, part of the pool
  // key so connections with different handshakes never mix.
  bool alpn_h2 = false;
  std::string sni_host;
  ClientTransport() = default;
  ClientTransport(bool tpu_) : tpu(tpu_) {}  // NOLINT: legacy bool-tpu sites
};

// The one way client sockets are made (shared by the single/pooled/short
// paths): fd = -1 (connect on first use), client messenger, optional tpu://
// or TLS transport.
int CreateClientSocket(const tbutil::EndPoint& pt, const ClientTransport& tr,
                       SocketId* sid);

// Acquire a CONNECTED client socket per the connection type (the one
// acquisition path shared by IssueRPC and the backup-request hedge). On
// failure returns -1 with errno set; a failed short/pooled socket is closed,
// a failed shared (single) socket is evicted from the map but NOT SetFailed —
// other RPCs may hold pending ids on it.
int AcquireClientSocket(ConnectionType ctype, const tbutil::EndPoint& pt,
                        const ClientTransport& tr, int64_t deadline_us,
                        SocketUniquePtr* out);

class SocketMap {
 public:
  // Get (or lazily create) the shared socket to `pt`. The returned socket
  // may be unconnected; callers run ConnectIfNot before writing. A cached
  // socket that has died is replaced with a fresh one. `tpu` selects the
  // tpu:// ICI transport — tpu and plain connections to one endpoint are
  // distinct cache entries (a process may use both).
  int GetOrCreate(const tbutil::EndPoint& pt, SocketUniquePtr* out,
                  const ClientTransport& tr = {});

  // Drop the cache entry (e.g. after SetFailed, to force a fresh connect).
  void Remove(const tbutil::EndPoint& pt, SocketId expected);

  // Borrow an exclusive socket from the (pt, transport) pool, creating a
  // fresh one when the free-list is empty. The caller owns it for one RPC;
  // hand it back with ReturnPooled on clean completion or SetFailed it
  // otherwise.
  int GetPooled(const tbutil::EndPoint& pt, SocketUniquePtr* out,
                const ClientTransport& tr = {});

  // Return a healthy borrowed socket for reuse. Failed sockets and overflow
  // past max_connection_pool_size are dropped (closed).
  void ReturnPooled(const tbutil::EndPoint& pt, SocketId sid,
                    const ClientTransport& tr = {});

  // Idle sockets parked in the (pt, transport) free-list (tests/vars).
  size_t PooledIdleCount(const tbutil::EndPoint& pt,
                         const ClientTransport& tr = {});

  static SocketMap& global();

 private:
  struct Key {
    tbutil::EndPoint pt;
    bool tpu;
    bool tls;
    bool alpn_h2;
    bool operator==(const Key& o) const {
      return pt == o.pt && tpu == o.tpu && tls == o.tls &&
             alpn_h2 == o.alpn_h2;
    }
  };
  struct KeyHasher {
    size_t operator()(const Key& k) const {
      return tbutil::EndPointHasher()(k.pt) * 8 + (k.tpu ? 1 : 0) +
             (k.tls ? 2 : 0) + (k.alpn_h2 ? 4 : 0);
    }
  };
  std::mutex _mu;
  std::unordered_map<Key, SocketId, KeyHasher> _map;
  // kPooled free-lists: sockets not currently carrying an RPC. Entries are
  // bare ids — a pooled socket's liveness is its self-ref; Address() on
  // acquire filters any that died while parked.
  std::unordered_map<Key, std::vector<SocketId>, KeyHasher> _pools;
};

}  // namespace trpc
