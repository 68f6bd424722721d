#include "ttpu/ici_segment.h"

#include <dirent.h>
#include <fcntl.h>
#include <signal.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <cstdio>

#include <atomic>
#include <cstring>
#include <map>

#include "tbutil/logging.h"
#include "trpc/flags.h"
#include "trpc/socket.h"
#include "ttpu/ici_endpoint.h"

namespace ttpu {

namespace {
constexpr uint8_t kHeld = 1;
constexpr uint8_t kInflight = 2;

// Fault injection for the TCP-fallback path (tests flip it via /flags):
// simulates the cross-host case where the peer's shm name can't be mapped.
std::atomic<int64_t>* g_fail_map = TRPC_DEFINE_FLAG(
    ici_fail_map_for_test, 0,
    "fault injection: make tpu:// peer segment mapping fail (0/1)");

std::string next_segment_name() {
  static std::atomic<uint64_t> counter{0};
  return "/brpctpu_" + std::to_string(getpid()) + "_" +
         std::to_string(counter.fetch_add(1));
}
}  // namespace

// Crash debris: a hard-killed process (no destructors) leaks its segment
// files; a later process reusing the pid then collides on O_EXCL. Names
// embed the creator's pid, so any file named with OUR pid belongs to a dead
// process — unlink and retry. A startup sweep also clears other dead pids'
// debris so /dev/shm can't fill up across crash loops.
void sweep_dead_segments() {
  DIR* d = opendir("/dev/shm");
  if (d == nullptr) return;
  while (dirent* e = readdir(d)) {
    long pid = 0;
    if (sscanf(e->d_name, "brpctpu_%ld_", &pid) != 1) continue;
    if (pid > 0 && kill(static_cast<pid_t>(pid), 0) != 0 && errno == ESRCH) {
      std::string name = "/";
      name += e->d_name;
      shm_unlink(name.c_str());
    }
  }
  closedir(d);
}

std::shared_ptr<IciSegment> IciSegment::CreateOwner(uint32_t block_size,
                                                    uint32_t n_blocks) {
  static const bool swept = [] {
    sweep_dead_segments();
    return true;
  }();
  (void)swept;
  auto seg = std::shared_ptr<IciSegment>(new IciSegment);
  seg->_name = next_segment_name();
  seg->_block_size = block_size;
  seg->_n_blocks = n_blocks;
  seg->_owner = true;
  const size_t total = size_t(block_size) * n_blocks;
  int fd = shm_open(seg->_name.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0 && errno == EEXIST) {
    // Same-pid debris from a dead predecessor: reclaim the name.
    shm_unlink(seg->_name.c_str());
    fd = shm_open(seg->_name.c_str(), O_CREAT | O_EXCL | O_RDWR, 0600);
  }
  if (fd < 0) {
    TB_LOG(ERROR) << "shm_open " << seg->_name
                  << " failed: " << strerror(errno);
    return nullptr;
  }
  if (ftruncate(fd, static_cast<off_t>(total)) != 0) {
    close(fd);
    shm_unlink(seg->_name.c_str());
    return nullptr;
  }
  seg->_base = static_cast<char*>(
      mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0));
  close(fd);
  if (seg->_base == MAP_FAILED) {
    seg->_base = nullptr;
    shm_unlink(seg->_name.c_str());
    return nullptr;
  }
  seg->_state.assign(n_blocks, 0);
  seg->_free_list.reserve(n_blocks);
  for (uint32_t i = n_blocks; i > 0; --i) seg->_free_list.push_back(i - 1);
  return seg;
}

std::shared_ptr<IciSegment> IciSegment::MapPeer(const std::string& name,
                                                uint32_t block_size,
                                                uint32_t n_blocks) {
  if (g_fail_map->load(std::memory_order_relaxed) != 0) {
    TB_LOG(WARNING) << "ici_fail_map_for_test: refusing to map " << name;
    return nullptr;
  }
  if (block_size == 0 || n_blocks == 0 ||
      size_t(block_size) * n_blocks > (1ULL << 34)) {
    return nullptr;  // refuse absurd handshake values
  }
  // The name is fully peer-controlled: constrain it to the framework's own
  // namespace so a handshake can't map an unrelated shm object.
  if (name.rfind("/brpctpu_", 0) != 0 ||
      name.find('/', 1) != std::string::npos) {
    TB_LOG(ERROR) << "rejecting peer segment name " << name;
    return nullptr;
  }
  auto seg = std::shared_ptr<IciSegment>(new IciSegment);
  seg->_name = name;
  seg->_block_size = block_size;
  seg->_n_blocks = n_blocks;
  seg->_owner = false;
  const size_t total = size_t(block_size) * n_blocks;
  int fd = shm_open(name.c_str(), O_RDWR, 0600);
  if (fd < 0) {
    TB_LOG(ERROR) << "shm_open peer " << name
                  << " failed: " << strerror(errno);
    return nullptr;
  }
  // A peer that lies about the size in HELLO would make us map short and
  // SIGBUS on first access past the real size: trust the object, not the
  // handshake.
  struct stat st;
  if (fstat(fd, &st) != 0 || st.st_size < static_cast<off_t>(total)) {
    TB_LOG(ERROR) << "peer segment " << name << " is smaller ("
                  << (long long)st.st_size << ") than announced (" << total
                  << ")";
    close(fd);
    return nullptr;
  }
  seg->_base = static_cast<char*>(
      mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0));
  close(fd);
  if (seg->_base == MAP_FAILED) {
    seg->_base = nullptr;
    return nullptr;
  }
  return seg;
}

IciSegment::~IciSegment() {
  if (_base != nullptr) {
    munmap(_base, size_t(_block_size) * _n_blocks);
  }
  if (_owner) {
    shm_unlink(_name.c_str());  // no-op (ENOENT) after UnlinkEarly
  }
}

void IciSegment::UnlinkEarly() {
  // _owner doubles as the once-guard: this is called from the data-frame
  // hot path, and a repeat would pay a failing shm_unlink syscall per
  // message. (The destructor's unlink keys off _owner too — already done.)
  if (_owner) {
    _owner = false;
    shm_unlink(_name.c_str());
  }
}

int IciSegment::Alloc() {
  std::lock_guard<std::mutex> lk(_mu);
  if (_free_list.empty()) return -1;
  uint32_t idx = _free_list.back();
  _free_list.pop_back();
  _state[idx] = kHeld;
  return static_cast<int>(idx);
}

void IciSegment::AllocBatch(uint32_t max, std::vector<uint32_t>* out) {
  std::lock_guard<std::mutex> lk(_mu);
  while (max-- > 0 && !_free_list.empty()) {
    uint32_t idx = _free_list.back();
    _free_list.pop_back();
    _state[idx] = kHeld;
    out->push_back(idx);
  }
}

void IciSegment::Release(uint32_t idx) {
  std::lock_guard<std::mutex> lk(_mu);
  _state[idx] &= ~kHeld;
  if (_state[idx] == 0) _free_list.push_back(idx);
}

void IciSegment::MarkInflight(uint32_t idx) {
  std::lock_guard<std::mutex> lk(_mu);
  _state[idx] |= kInflight;
}

void IciSegment::OnCreditReturned(uint32_t idx) {
  std::lock_guard<std::mutex> lk(_mu);
  if (idx >= _n_blocks || (_state[idx] & kInflight) == 0) return;
  _state[idx] &= ~kInflight;
  if (_state[idx] == 0) _free_list.push_back(idx);
}

uint32_t IciSegment::free_blocks() const {
  std::lock_guard<std::mutex> lk(_mu);
  return static_cast<uint32_t>(_free_list.size());
}

// ---------------- peer registry ----------------

namespace {

struct RegEntry {
  std::shared_ptr<IciSegment> seg;
  uint64_t socket_id = 0;
  int64_t outstanding = 0;  // materialized blocks still held by IOBufs
  bool endpoint_gone = false;
};

struct Registry {
  std::mutex mu;
  // base address -> entry; lookup by containing range.
  std::map<const char*, RegEntry> map;
};
Registry& registry() {
  static Registry* r = new Registry;
  return *r;
}

// Find the entry whose [base, end) contains ptr. Caller holds mu.
std::map<const char*, RegEntry>::iterator find_containing(Registry& r,
                                                          const void* ptr) {
  auto it = r.map.upper_bound(static_cast<const char*>(ptr));
  if (it == r.map.begin()) return r.map.end();
  --it;
  if (!it->second.seg->contains(ptr)) return r.map.end();
  return it;
}

}  // namespace

void PeerSegmentRegistry::Register(std::shared_ptr<IciSegment> seg,
                                   uint64_t socket_id) {
  Registry& r = registry();
  std::lock_guard<std::mutex> lk(r.mu);
  RegEntry e;
  const char* base = seg->base();
  e.seg = std::move(seg);
  e.socket_id = socket_id;
  r.map[base] = std::move(e);
}

void PeerSegmentRegistry::OnMaterialize(const IciSegment* seg) {
  Registry& r = registry();
  std::lock_guard<std::mutex> lk(r.mu);
  auto it = r.map.find(seg->base());
  if (it != r.map.end()) ++it->second.outstanding;
}

void PeerSegmentRegistry::OnRelease(void* ptr) {
  uint64_t socket_id = 0;
  uint32_t idx = 0;
  // Explicit flag, NOT a socket_id==0 sentinel: 0 is a VALID SocketId
  // (INVALID_SOCKET_ID is ~0, and the first socket a client process
  // creates gets id 0). The sentinel silently dropped EVERY credit owed
  // by such a peer — one leaked TX block per response until the sender's
  // pool emptied and its writer parked forever (the long-standing
  // "all threads parked" tpu:// wedge).
  bool notify = false;
  {
    Registry& r = registry();
    std::lock_guard<std::mutex> lk(r.mu);
    auto it = find_containing(r, ptr);
    if (it == r.map.end()) return;
    RegEntry& e = it->second;
    socket_id = e.socket_id;
    idx = e.seg->index_of(ptr);
    notify = !e.endpoint_gone;
    if (--e.outstanding == 0 && e.endpoint_gone) {
      r.map.erase(it);  // drops the last shared_ptr: unmap
    }
  }
  if (notify) {
    ici_internal::SendCreditFrame(socket_id, idx);
  }
}

std::string DebugDumpEndpoints(bool include_read_heads) {
  std::vector<uint64_t> ids;
  std::vector<int64_t> outstanding;
  {
    Registry& r = registry();
    std::lock_guard<std::mutex> lk(r.mu);
    for (const auto& [base, e] : r.map) {
      ids.push_back(e.socket_id);
      outstanding.push_back(e.outstanding);
    }
  }
  std::string out;
  for (size_t i = 0; i < ids.size(); ++i) {
    trpc::SocketUniquePtr s;
    if (trpc::Socket::Address(ids[i], &s) != 0) {
      out += "ici sock=" + std::to_string(ids[i]) + " (socket gone)";
    } else if (s->ici_endpoint() != nullptr) {
      out += s->DebugString();
      if (include_read_heads) {
        out += " ";
        out += s->DebugReadBufHead();
      }
      out += "\n  ";
      out += s->ici_endpoint()->DebugString();
    } else {
      out += "ici sock=" + std::to_string(ids[i]) + " (no endpoint)";
    }
    out += " rx_outstanding=" + std::to_string(outstanding[i]) + "\n";
  }
  return out;
}

void PeerSegmentRegistry::OnEndpointGone(const IciSegment* seg) {
  Registry& r = registry();
  std::lock_guard<std::mutex> lk(r.mu);
  auto it = r.map.find(seg->base());
  if (it == r.map.end()) return;
  if (it->second.outstanding == 0) {
    r.map.erase(it);
  } else {
    it->second.endpoint_gone = true;
  }
}

}  // namespace ttpu
