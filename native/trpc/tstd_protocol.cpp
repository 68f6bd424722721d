#include "trpc/tstd_protocol.h"

#include "tbutil/crc32c.h"

#include "trpc/thrift_protocol.h"

#include <algorithm>
#include <csignal>
#include <bit>
#include <cstring>
#include <mutex>

#include "tbthread/fiber.h"
#include "tbutil/logging.h"
#include "tbutil/object_pool.h"
#include "tbutil/time.h"
#include "tbvar/flight_recorder.h"
#include "trpc/builtin_console.h"
#include "trpc/compress.h"
#include "trpc/controller.h"
#include "trpc/h2_protocol.h"
#include "trpc/http_protocol.h"
#include "trpc/memcache_protocol.h"
#include "trpc/qos.h"
#include "trpc/redis_protocol.h"
#include "trpc/errno.h"
#include "trpc/flags.h"
#include "trpc/rpc_metrics.h"
#include "trpc/server.h"
#include "trpc/socket.h"
#include "trpc/span.h"
#include "trpc/stream_internal.h"
#include "ttpu/ici_endpoint.h"

namespace trpc {

namespace {

constexpr char kMagic[4] = {'T', 'R', 'P', 'C'};
constexpr size_t kHeaderSize = 12;
constexpr size_t kFixedMetaSize = 44;
constexpr size_t kMaxMetaSize = 64 * 1024;
// Body-size sanity cap, hot-reloadable via /flags (reference
// FLAGS_max_body_size).
std::atomic<int64_t>* g_max_body_size = TRPC_DEFINE_FLAG(
    tstd_max_body_size, 2LL * 1024 * 1024 * 1024,
    "Max tstd frame body size accepted by the parser");

// Wire byte order is LITTLE-ENDIAN by definition: header/meta integers are
// memcpy'd raw. All supported deployment targets (x86_64, aarch64 TPU VMs)
// are little-endian; a big-endian peer would need byte-swapping shims here.
template <typename T>
void put(std::string* s, T v) {
  static_assert(std::endian::native == std::endian::little,
                "tstd wire format requires a little-endian host");
  s->append(reinterpret_cast<const char*>(&v), sizeof(T));
}

template <typename T>
T get(const char*& p) {
  T v;
  memcpy(&v, p, sizeof(T));
  p += sizeof(T);
  return v;
}

// 0/1: stamp crc32c of the body into outgoing tstd frames. Costs one pass
// over the payload; worth it on links without end-to-end integrity
// (reference baidu_std has no body checksum — this is a deliberate
// improvement for tensor payloads riding tpu:// shm segments).
const auto* g_tstd_checksum = trpc::FlagRegistry::global().DefineInt(
    "tstd_checksum", 0, "stamp+verify crc32c on tstd bodies (0/1)",
    [](int64_t v) { return v == 0 || v == 1; });

uint32_t crc_of_iobuf(uint32_t crc, const tbutil::IOBuf& buf) {
  const size_t nblocks = buf.backing_block_num();
  for (size_t b = 0; b < nblocks; ++b) {
    const std::string_view blk = buf.backing_block(b);
    crc = tbutil::crc32c_extend(crc, blk.data(), blk.size());
  }
  return crc;
}

bool checksum_enabled() {
  return g_tstd_checksum->load(std::memory_order_relaxed) != 0;
}

}  // namespace

// ---------------- pooled per-RPC state ----------------

// Inbound frames: one pooled object per message instead of new/delete on
// the parse hot path. Destroy is THE teardown everywhere (protocol.h).
TstdInputMessage* GetPooledTstdMessage() {
  return tbutil::get_object<TstdInputMessage>();
}

void TstdInputMessage::Destroy() {
  meta = TstdMeta();
  payload.clear();
  attachment.clear();
  socket_id = 0;
  protocol_index = -1;
  process_in_place = false;
  inline_fast_path = false;
  dispatch_batchable = false;
  batch_next = nullptr;
  tbutil::return_object(this);
}

namespace {

// Server-side per-RPC session: the Controller + response buffer that live
// from request dispatch until done->Run(). Pooled so the small-RPC path
// pays two pointer pops instead of a new/delete pair per request on each
// of them. Reset happens at RETURN time (ReturnServerSession) so pooled
// objects hold no stale RPC state (and no retained buffers) while idle —
// Controller::Reset's completeness is pinned by tests/test_small_rpc.py.
struct ServerSession {
  Controller cntl;
  tbutil::IOBuf response;
};

ServerSession* GetServerSession() {
  return tbutil::get_object<ServerSession>();
}

void ReturnServerSession(ServerSession* sess) {
  sess->cntl.Reset();
  sess->response.clear();
  tbutil::return_object(sess);
}

}  // namespace

void tstd_serialize_meta(tbutil::IOBuf* out, const TstdMeta& meta,
                         size_t body_size) {
  std::string m;
  m.reserve(kFixedMetaSize + meta.service.size() + meta.method.size() +
            meta.error_text.size() + 24);
  uint16_t flags = meta.flags;
  if (meta.stream_id != 0) flags |= kTstdFlagHasStream;
  // QoS fields cost bytes ONLY when stamped: an unmarked request (default
  // priority, no tenant) serializes byte-identically to the pre-QoS wire —
  // pinned by tests/test_overload.py.
  const bool has_qos = meta.priority != PRIORITY_NORMAL ||
                       !meta.tenant.empty();
  if (has_qos) flags |= kTstdFlagHasQos;
  put<uint8_t>(&m, meta.msg_type);
  put<uint8_t>(&m, meta.compress_type);
  put<uint16_t>(&m, flags);
  put<uint64_t>(&m, meta.correlation_id);
  put<uint32_t>(&m, meta.attachment_size);
  put<int32_t>(&m, meta.code_or_timeout);
  put<uint64_t>(&m, meta.trace_id);
  put<uint64_t>(&m, meta.span_id);
  put<uint64_t>(&m, meta.parent_span_id);
  if (meta.stream_id != 0) {
    put<uint64_t>(&m, meta.stream_id);
    put<int64_t>(&m, meta.stream_window);
  }
  if (flags & kTstdFlagHasChecksum) {
    put<uint32_t>(&m, meta.body_crc);
  }
  if (has_qos) {
    put<uint8_t>(&m, meta.priority);
    // Length field is u16: truncate CONSISTENTLY (length AND bytes) so an
    // oversized tenant can never desynchronize the meta walk. The public
    // entry (tbrpc_qos_set) rejects long tenants before they get here.
    const size_t tlen = std::min<size_t>(meta.tenant.size(), 0xFFFF);
    put<uint16_t>(&m, static_cast<uint16_t>(tlen));
    m.append(meta.tenant.data(), tlen);
  }
  if (meta.msg_type == 0) {
    put<uint16_t>(&m, static_cast<uint16_t>(meta.service.size()));
    m.append(meta.service);
    put<uint16_t>(&m, static_cast<uint16_t>(meta.method.size()));
    m.append(meta.method);
  } else {
    put<uint16_t>(&m, static_cast<uint16_t>(meta.error_text.size()));
    m.append(meta.error_text);
  }
  char header[kHeaderSize];
  memcpy(header, kMagic, 4);
  uint32_t meta_size = static_cast<uint32_t>(m.size());
  uint32_t bsz = static_cast<uint32_t>(body_size);
  memcpy(header + 4, &meta_size, 4);
  memcpy(header + 8, &bsz, 4);
  out->append(header, kHeaderSize);
  out->append(m);
}

static bool parse_meta(const std::string& raw, TstdMeta* meta) {
  if (raw.size() < kFixedMetaSize) return false;
  const char* p = raw.data();
  const char* end = raw.data() + raw.size();
  meta->msg_type = get<uint8_t>(p);
  meta->compress_type = get<uint8_t>(p);
  meta->flags = get<uint16_t>(p);
  meta->correlation_id = get<uint64_t>(p);
  meta->attachment_size = get<uint32_t>(p);
  meta->code_or_timeout = get<int32_t>(p);
  meta->trace_id = get<uint64_t>(p);
  meta->span_id = get<uint64_t>(p);
  meta->parent_span_id = get<uint64_t>(p);
  if (meta->flags & kTstdFlagHasStream) {
    if (p + 16 > end) return false;
    meta->stream_id = get<uint64_t>(p);
    meta->stream_window = get<int64_t>(p);
  }
  if (meta->flags & kTstdFlagHasChecksum) {
    if (p + 4 > end) return false;
    meta->body_crc = get<uint32_t>(p);
  }
  auto get_str = [&p, end](std::string* out) {
    if (p + 2 > end) return false;
    uint16_t len = get<uint16_t>(p);
    if (p + len > end) return false;
    out->assign(p, len);
    p += len;
    return true;
  };
  if (meta->flags & kTstdFlagHasQos) {
    if (p + 1 > end) return false;
    meta->priority = static_cast<uint8_t>(
        clamp_priority(get<uint8_t>(p)));
    if (!get_str(&meta->tenant)) return false;
  }
  if (meta->msg_type == 0) {
    if (!get_str(&meta->service) || !get_str(&meta->method)) return false;
  } else {
    if (!get_str(&meta->error_text)) return false;
  }
  return true;
}

ParseResult tstd_parse(tbutil::IOBuf* source, Socket* sock) {
  ParseResult r;
  if (source->size() < kHeaderSize) {
    // Judge the magic on whatever prefix exists before claiming the
    // buffer: a short non-tstd frame (e.g. the 8-byte tici HELLO-NACK)
    // must fall through to its own parser, not be held hostage here
    // waiting for a 12-byte header that will never complete.
    char head[4];
    const size_t n = source->copy_to(head, 4);
    r.error = memcmp(head, kMagic, n) == 0 ? PARSE_ERROR_NOT_ENOUGH_DATA
                                           : PARSE_ERROR_TRY_OTHERS;
    return r;
  }
  char header[kHeaderSize];
  source->copy_to(header, kHeaderSize);
  if (memcmp(header, kMagic, 4) != 0) {
    r.error = PARSE_ERROR_TRY_OTHERS;
    return r;
  }
  uint32_t meta_size, body_size;
  memcpy(&meta_size, header + 4, 4);
  memcpy(&body_size, header + 8, 4);
  if (meta_size < kFixedMetaSize || meta_size > kMaxMetaSize ||
      static_cast<int64_t>(body_size) >
          g_max_body_size->load(std::memory_order_relaxed)) {
    r.error = PARSE_ERROR_ABSOLUTELY_WRONG;
    return r;
  }
  if (source->size() < kHeaderSize + meta_size + body_size) {
    r.error = PARSE_ERROR_NOT_ENOUGH_DATA;
    return r;
  }
  source->pop_front(kHeaderSize);
  std::string raw_meta;
  source->cutn(&raw_meta, meta_size);
  TstdInputMessage* msg = GetPooledTstdMessage();
  if (!parse_meta(raw_meta, &msg->meta) ||
      msg->meta.attachment_size > body_size) {
    msg->Destroy();
    r.error = PARSE_ERROR_ABSOLUTELY_WRONG;
    return r;
  }
  source->cutn(&msg->payload, body_size - msg->meta.attachment_size);
  source->cutn(&msg->attachment, msg->meta.attachment_size);
  if (msg->meta.flags & kTstdFlagHasChecksum) {
    const uint32_t got =
        crc_of_iobuf(crc_of_iobuf(0, msg->payload), msg->attachment);
    if (got != msg->meta.body_crc) {
      // Bytes corrupted in flight (or a buggy peer): nothing later on this
      // connection can be trusted — kill it loudly.
      TB_LOG(ERROR) << "tstd body crc mismatch: got " << got << " want "
                    << msg->meta.body_crc << " (" << body_size << "B body)";
      msg->Destroy();
      r.error = PARSE_ERROR_ABSOLUTELY_WRONG;
      return r;
    }
  }
  msg->process_in_place = msg->meta.msg_type >= 2;  // stream frames: ordered
  // Small-RPC fast path gates, all keyed on ONE size cutoff (the ici
  // control-channel small-message threshold, so "small" means the same
  // thing on both halves of the transport). Batchability is granted only
  // where processing provably never parks the dispatch fiber (protocol.h):
  //   * responses — client-side resolution is a correlation lookup + a
  //     caller wake (the rare small async completion with a Python
  //     callback can park; bounded by the batch cap, and tensor-class
  //     responses are large, hence excluded by size anyway);
  //   * requests to inline_safe services (a declared never-parks
  //     contract) or to no service at all (the ENOSERVICE answer path);
  //     a Python-backed handler parks its fiber on the callback pool, so
  //     those keep fiber-per-message dispatch and their natural
  //     pool-wide concurrency.
  // The same single FindService feeds the inline-execution decision: a
  // small request to an inline-REGISTERED service runs right on the input
  // fiber (process_in_place), skipping the dispatch hop entirely.
  if (sock != nullptr && body_size <= ttpu::ici_small_msg_threshold()) {
    if (msg->meta.msg_type == 1) {
      msg->dispatch_batchable = true;
    } else if (msg->meta.msg_type == 0 && sock->server_side()) {
      auto* server = static_cast<Server*>(sock->user());
      Service* svc = server != nullptr
                         ? server->FindService(msg->meta.service)
                         : nullptr;
      if (svc == nullptr || svc->inline_safe()) {
        msg->dispatch_batchable = true;
      }
      if (svc != nullptr && svc->allow_inline()) {
        msg->process_in_place = true;
        msg->inline_fast_path = true;
      }
    }
  }
  r.error = PARSE_OK;
  r.msg = msg;
  return r;
}

// ---------------- client side: pack + response dispatch ----------------

static void tstd_pack_request(tbutil::IOBuf* out, Controller* cntl,
                              uint64_t correlation_id,
                              const std::string& service_method,
                              const tbutil::IOBuf& payload, Socket*) {
  TstdMeta meta;
  meta.msg_type = 0;
  meta.correlation_id = correlation_id;
  meta.attachment_size =
      static_cast<uint32_t>(cntl->request_attachment().size());
  ControllerPrivateAccessor acc0(cntl);
  // rpcz propagation: the server's span will parent on OUR span id.
  meta.trace_id = acc0.trace_id();
  meta.span_id = acc0.span_id();
  meta.parent_span_id = acc0.parent_span_id();
  if (acc0.request_stream() != 0) {
    meta.stream_id = acc0.request_stream();
    meta.stream_window = stream_internal::AdvertisedWindow(meta.stream_id);
  }
  // QoS stamping (qos.h): priority/tenant resolved in Channel::CallMethod
  // (explicit set > ambient context > defaults). Default priority + no
  // tenant serialize to ZERO extra bytes (kTstdFlagHasQos stays clear).
  meta.priority = static_cast<uint8_t>(clamp_priority(cntl->priority()));
  meta.tenant = cntl->tenant();
  if (cntl->deadline_us() > 0) {
    int64_t remaining_ms =
        (cntl->deadline_us() - tbutil::gettimeofday_us()) / 1000;
    meta.code_or_timeout =
        static_cast<int32_t>(remaining_ms > 0 ? remaining_ms : 1);
  }
  size_t slash = service_method.find('/');
  if (slash == std::string::npos) {
    meta.service = service_method;
  } else {
    meta.service = service_method.substr(0, slash);
    meta.method = service_method.substr(slash + 1);
  }
  // Payload compression (attachments ride raw — compress.h).
  const tbutil::IOBuf* body = &payload;
  tbutil::IOBuf compressed;
  if (MaybeCompress(cntl->compress_type(), payload, &compressed)) {
    body = &compressed;
    meta.compress_type = cntl->compress_type();
  }
  if (checksum_enabled()) {
    meta.flags |= kTstdFlagHasChecksum;
    meta.body_crc =
        crc_of_iobuf(crc_of_iobuf(0, *body), cntl->request_attachment());
  }
  tstd_serialize_meta(out, meta,
                      body->size() + cntl->request_attachment().size());
  out->append(*body);
  out->append(cntl->request_attachment());
}

// Defined in controller.cpp — hands the parsed response to the controller
// under its locked correlation id.
void TstdHandleResponse(TstdInputMessage* msg);

void tstd_process_response(InputMessageBase* base) {
  auto* msg = static_cast<TstdInputMessage*>(base);
  if (msg->meta.msg_type >= 2) {  // stream frame, either side
    stream_internal::OnStreamFrame(msg);
    return;
  }
  TstdHandleResponse(msg);
}

// ---------------- server side: request dispatch ----------------

static void tstd_send_response(SocketId sid, uint64_t correlation_id,
                               Controller* cntl, tbutil::IOBuf* payload) {
  SocketUniquePtr s;
  if (Socket::Address(sid, &s) != 0) return;  // peer is gone
  TstdMeta meta;
  meta.msg_type = 1;
  meta.correlation_id = correlation_id;
  meta.code_or_timeout = cntl->ErrorCode();
  meta.error_text = cntl->ErrorText();
  meta.attachment_size =
      static_cast<uint32_t>(cntl->response_attachment().size());
  ControllerPrivateAccessor acc1(cntl);
  if (acc1.response_stream() != 0) {
    meta.stream_id = acc1.response_stream();
    meta.stream_window = stream_internal::AdvertisedWindow(meta.stream_id);
  }
  // Answer in the request's codec when it shrinks the response.
  {
    tbutil::IOBuf compressed;
    if (MaybeCompress(cntl->compress_type(), *payload, &compressed)) {
      meta.compress_type = cntl->compress_type();
      payload->swap(compressed);
    }
  }
  if (checksum_enabled()) {
    meta.flags |= kTstdFlagHasChecksum;
    meta.body_crc =
        crc_of_iobuf(crc_of_iobuf(0, *payload), cntl->response_attachment());
  }
  tbutil::IOBuf out;
  tstd_serialize_meta(&out, meta,
                      payload->size() + cntl->response_attachment().size());
  out.append(std::move(*payload));
  out.append(cntl->response_attachment());
  s->Write(&out);
}

void tstd_process_request(InputMessageBase* base) {
  auto* msg = static_cast<TstdInputMessage*>(base);
  if (msg->meta.msg_type >= 2) {  // stream frame, either side
    stream_internal::OnStreamFrame(msg);
    return;
  }
  SocketUniquePtr s;
  if (Socket::Address(msg->socket_id, &s) != 0) {
    msg->Destroy();
    return;
  }
  auto* server = static_cast<Server*>(s->user());
  const SocketId sid = msg->socket_id;
  const uint64_t cid = msg->meta.correlation_id;

  // Controller + response live until done->Run() (handlers may be async):
  // pooled as one ServerSession so the per-request new/delete pair is gone
  // from the hot path. Returned — reset — by the single teardown below.
  ServerSession* sess = GetServerSession();
  Controller* cntl = &sess->cntl;
  tbutil::IOBuf* response = &sess->response;
  ControllerPrivateAccessor acc(cntl);
  int64_t deadline_us = 0;
  if (msg->meta.code_or_timeout > 0) {
    deadline_us =
        tbutil::gettimeofday_us() + int64_t(msg->meta.code_or_timeout) * 1000;
  }
  acc.set_server_side(s->remote_side(), deadline_us);
  acc.set_request_attachment(std::move(msg->attachment));
  acc.set_server_socket(sid);
  // Server-side QoS mirror: handlers (and the handler QoS scope below)
  // read the request's lane + tenant off the controller.
  cntl->set_priority(clamp_priority(msg->meta.priority));
  cntl->set_tenant(msg->meta.tenant);
  if (msg->meta.stream_id != 0) {
    acc.set_remote_stream(msg->meta.stream_id, msg->meta.stream_window);
  }
  auto fail_without_gate = [&](int code, const std::string& text) {
    cntl->SetFailed(code, text);
    msg->Destroy();
    tstd_send_response(sid, cid, cntl, response);
    ReturnServerSession(sess);
  };
  if (server == nullptr) {
    fail_without_gate(TRPC_EINTERNAL, "socket has no server");
    return;
  }
  // Layered admission (overload protection, server.h BeginRequest):
  // deadline-expired shed, per-tenant quota, BULK-lane headroom, then the
  // configured limiter. A shed answers WITHOUT running anything further —
  // shed-before-queue — and its error text carries the retry-after hint.
  RequestQos qos;
  qos.priority = msg->meta.priority;
  qos.tenant = msg->meta.tenant;
  qos.deadline_us = deadline_us;
  Admission admit;
  if (!server->BeginRequest(qos, s->remote_side(), &admit)) {
    fail_without_gate(admit.error, admit.text);
    return;
  }
  // Admission time: the latency window opens HERE, so injected queueing
  // below reads as handler time everywhere (method stats, lane
  // recorders, the EMA the retry-after hints derive from) — a slow
  // handler's exact footprint.
  const int64_t received_us = tbutil::gettimeofday_us();
  // TEST-ONLY deterministic queueing (tbrpc_debug_inject_latency): an
  // admitted request holds its gate slot for the injected time.
  const int64_t inject_ms = DebugInjectedLatencyMs(msg->meta.service);
  if (inject_ms > 0) {
    tbthread::fiber_usleep(static_cast<uint64_t>(inject_ms) * 1000);
  }
  Service* svc = server->FindService(msg->meta.service);
  // Per-method stats (reference details/method_status.h): looked up only
  // for REGISTERED services so junk service names can't mint entries.
  std::string full_method = msg->meta.service + "/" + msg->meta.method;
  MethodStatus* ms = nullptr;
  if (svc != nullptr) {
    ms = GetMethodStatus(full_method);
    ms->OnRequested();
  }
  // rpcz: with collection on, every request gets a server span — parenting
  // on the client's span when the request carries one, or starting a fresh
  // self-sampled trace otherwise (a server debugged in isolation must see
  // its own traffic). The handler fiber carries the context so nested
  // calls link up.
  uint64_t server_span_id = 0;
  uint64_t span_trace_id = msg->meta.trace_id;
  // A request carrying a trace_id belongs to a trace its CLIENT already
  // sampled — always record, or the assembled fleet trace loses legs. An
  // untraced inbound self-samples a fresh root at 1-in-N.
  if (rpcz_enabled() && (span_trace_id != 0 || rpcz_sample_root())) {
    server_span_id = new_trace_or_span_id();
    if (span_trace_id == 0) span_trace_id = new_trace_or_span_id();
    acc.set_trace(span_trace_id, server_span_id, msg->meta.span_id);
  }
  const uint64_t span_parent = msg->meta.span_id;
  // Untraced requests carry an empty string into the closure, not a copy.
  const std::string span_method =
      server_span_id != 0 ? full_method : std::string();
  const tbutil::EndPoint span_remote = s->remote_side();
  // From here the gate is released exactly once — by done (the single
  // teardown path for both the error and success branches).
  Closure* done = NewCallback(
      [sid, cid, sess, cntl, response, server, ms, received_us, admit,
       server_span_id, span_trace_id, span_parent, span_method,
       span_remote]() {
        // Clamped: gettimeofday can step backward (NTP), and a negative
        // value here would read as the shed sentinel in EndRequest,
        // leaking a limiter slot.
        const int64_t latency_us =
            std::max<int64_t>(0, tbutil::gettimeofday_us() - received_us);
        if (ms != nullptr) {
          ms->OnResponded(cntl->ErrorCode(), latency_us);
        }
        RecordServerSpan(span_trace_id, server_span_id, span_parent,
                         received_us, latency_us, cntl->ErrorCode(),
                         span_method, span_remote);
        tbvar::flight_record(tbvar::FLIGHT_RPC_PHASE,
                             tbvar::FLIGHT_RPC_SERVER_DONE, cid);
        tstd_send_response(sid, cid, cntl, response);
        // Releases the tenant gate too, and feeds the per-lane recorders.
        server->EndRequest(latency_us, admit);
        ReturnServerSession(sess);
      });
  // Deadline shed-before-handler: the queueing above (injected or real
  // fiber-scheduling delay) may have consumed the whole propagated budget
  // — running the handler now would burn capacity on a response nobody is
  // waiting for. This is THE deadline shed on the tstd path: the wire
  // budget is clamped >= 1ms at pack time and the absolute deadline is
  // reconstructed just above, so BeginRequest's pre-gate check (step 1)
  // cannot fire here — it covers direct native callers only.
  if (deadline_us > 0 && svc != nullptr &&
      tbutil::gettimeofday_us() >= deadline_us) {
    GlobalRpcMetrics::instance().shed_deadline << 1;
    GlobalRpcMetrics::instance().shed_total << 1;
    cntl->SetFailed(TRPC_ERPCTIMEDOUT,
                    "propagated deadline expired before the handler ran; "
                    "shed (retry_after_ms=" +
                        std::to_string(server->ComputeRetryAfterMs(
                            server->concurrency())) +
                        ")");
    msg->Destroy();
    done->Run();
    return;
  }
  if (svc == nullptr) {
    cntl->SetFailed(TRPC_ENOSERVICE,
                    "no such service: " + msg->meta.service);
    msg->Destroy();
    done->Run();
    return;
  }
  tbutil::IOBuf request = std::move(msg->payload);
  std::string method = std::move(msg->meta.method);
  if (msg->meta.compress_type != kCompressNone) {
    // (decompressed below; the interceptor sees plain bytes)
    const Compressor* c = GetCompressor(msg->meta.compress_type);
    tbutil::IOBuf plain;
    const size_t max_out = static_cast<size_t>(
        g_max_body_size->load(std::memory_order_relaxed));
    if (c == nullptr || !c->decompress(request, &plain, max_out)) {
      cntl->SetFailed(TRPC_EREQUEST, "cannot decompress request payload");
      msg->Destroy();
      done->Run();
      return;
    }
    request.swap(plain);
    // The response answers in the request's codec (tstd_send_response).
    cntl->set_compress_type(msg->meta.compress_type);
  }
  msg->Destroy();
  // rpc_dump sampling (post-decompression: replay feeds plain bytes).
  if (RpcDumper* d = server->dumper()) {
    d->MaybeSample(full_method, request, cntl->request_attachment());
  }
  // Pre-dispatch interception (auth, quota, audit — reference server
  // interceptor/authenticator seam).
  if (Interceptor* icept = server->interceptor()) {
    std::string reject_text;
    const int rc =
        icept->OnRequest(cntl, full_method, request, &reject_text);
    if (rc != 0) {
      cntl->SetFailed(rc, reject_text.empty() ? "rejected by interceptor"
                                              : reject_text);
      done->Run();
      return;
    }
  }
  // The context lives for the synchronous part of the handler — where
  // nested client calls are issued. (An async handler that parks `done` on
  // another fiber makes nested calls untraced, same as the reference's
  // bthread-local scope.)
  ScopedTraceContext trace_scope(span_trace_id, server_span_id);
  // Same scope for the request's QoS: nested RPCs the handler issues
  // inherit the caller's tenant + priority, and their deadline clamps to
  // min(own timeout, this request's remaining budget) in
  // Channel::CallMethod — deadline propagation across hops. The Python
  // callback services hand this across their pool thread explicitly
  // (capi.cpp), like the trace context.
  QosContext handler_qos;
  handler_qos.priority = admit.priority;
  handler_qos.tenant = cntl->tenant();
  handler_qos.deadline_us = deadline_us;
  ScopedQosContext qos_scope(handler_qos);
  tbvar::flight_record(tbvar::FLIGHT_RPC_PHASE, tbvar::FLIGHT_RPC_SERVER_IN,
                       cid);
  svc->CallMethod(method, cntl, request, response, done);
}

// ---------------- registration ----------------

void GlobalInitializeOrDie() {
  static std::once_flag once;
  std::call_once(once, [] {
    // A peer closing mid-write must surface as EPIPE from the write call,
    // never as a process-killing signal (reference: brpc ignores SIGPIPE
    // the same way; every network daemon does).
    signal(SIGPIPE, SIG_IGN);
    tbvar::ExposeDefaultVariables();
    RegisterBuiltinCompressors();
    RegisterBuiltinTensorCodecs();  // quantized tensor wire negotiation
    Protocol p;
    p.parse = tstd_parse;
    p.pack_request = tstd_pack_request;
    p.process_request = tstd_process_request;
    p.process_response = tstd_process_response;
    p.name = "tstd";
    TB_CHECK(RegisterProtocol(kTstdProtocolIndex, p) == 0)
        << "tstd protocol slot taken";
    RegisterHttpProtocol();  // same-port multi-protocol serving
    ttpu::ici_internal::RegisterTiciProtocol();  // tpu:// control frames
    RegisterRedisProtocol();
    RegisterMemcacheProtocol();
    RegisterH2Protocol();
    RegisterThriftProtocol();
    RegisterBuiltinConsole();
  });
}

}  // namespace trpc
