// brpc_tpu native RPC datapath: framing + dispatch + correlation in C++.
//
// This is the "move framing+dispatch onto the native core" stage promised in
// docs/DESIGN.md §4: the full RPC hot path — client channel, TRPC frame
// codec, epoll server loop, method dispatch, response correlation — runs
// native, with Python only on the control plane (service registration,
// protobuf user payloads).  Reference anchors:
//   * frame shape + server path: src/brpc/policy/baidu_rpc_protocol.cpp
//     (ProcessRpcRequest :312, SendRpcResponse :139) — ours is the TRPC
//     frame of brpc_tpu/policy/tpu_std.py, byte-compatible with the Python
//     stack so native and Python peers interoperate on one wire
//   * meta schema: brpc_tpu/proto/rpc_meta.proto (hand-rolled proto3 wire
//     codec below — no protobuf C++ dep; unknown fields are skipped the way
//     any proto3 parser must)
//   * client correlation: src/brpc/controller.cpp OnVersionedRPCReturned —
//     a cid→slot table; the caller-becomes-reader election mirrors
//     Socket::StartInputEvent's single-reader discipline (socket.cpp:2046)
//
// Build: compiled into libbrpc_tpu_core.so (see native/Makefile).

#include "tsan_compat.h"

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>

#include "flat_map.h"
#include <vector>

#ifdef __linux__
#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>
#include <algorithm>

namespace nrpc {

// ====================================================================
// proto3 wire codec (varint + length-delimited), RpcMeta subset
// ====================================================================

static void put_varint(std::string& out, uint64_t v) {
  while (v >= 0x80) {
    out.push_back((char)((v & 0x7f) | 0x80));
    v >>= 7;
  }
  out.push_back((char)v);
}

static bool get_varint(const uint8_t*& p, const uint8_t* end, uint64_t* v) {
  uint64_t r = 0;
  int shift = 0;
  while (p < end && shift < 64) {
    uint8_t b = *p++;
    r |= (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) {
      *v = r;
      return true;
    }
    shift += 7;
  }
  return false;
}

static void put_tag(std::string& out, int field, int wire) {
  put_varint(out, (uint64_t)((field << 3) | wire));
}

static void put_len_field(std::string& out, int field, const std::string& s) {
  if (s.empty()) return;
  put_tag(out, field, 2);
  put_varint(out, s.size());
  out.append(s);
}

static void put_u64_field(std::string& out, int field, uint64_t v) {
  if (v == 0) return;
  put_tag(out, field, 0);
  put_varint(out, v);
}

static bool skip_field(const uint8_t*& p, const uint8_t* end, int wire) {
  uint64_t tmp;
  switch (wire) {
    case 0: return get_varint(p, end, &tmp);
    case 1: if (end - p < 8) return false; p += 8; return true;
    case 2:
      if (!get_varint(p, end, &tmp) || (uint64_t)(end - p) < tmp) return false;
      p += tmp;
      return true;
    case 5: if (end - p < 4) return false; p += 4; return true;
    default: return false;
  }
}

struct MetaRequest {
  std::string service_name, method_name, auth_token;
  uint64_t log_id = 0, trace_id = 0, span_id = 0, parent_span_id = 0;
  uint64_t timeout_ms = 0;
  // admission-control propagation (rpc_meta.proto fields 9-11):
  // priority is offset-encoded on the wire (0 = unset, 1..N = band
  // 0..N-1); deadline_left_ms is the sender's REMAINING budget.
  uint64_t priority = 0;
  std::string tenant;
  uint64_t deadline_left_ms = 0;
  bool present = false;
};

struct MetaResponse {
  uint64_t error_code = 0;
  std::string error_text;
  uint64_t retry_after_ms = 0;   // admission shed backoff hint (field 3)
  bool present = false;
};

struct RpcMeta {
  MetaRequest request;
  MetaResponse response;
  uint64_t compress_type = 0;
  uint64_t correlation_id = 0;
  uint64_t attachment_size = 0;
  bool has_stream_settings = false;  // parsed-but-skipped (native path
                                     // doesn't own streams; Python does)
};

static std::string encode_request_meta(const MetaRequest& r) {
  std::string out;
  put_len_field(out, 1, r.service_name);
  put_len_field(out, 2, r.method_name);
  put_u64_field(out, 3, r.log_id);
  put_u64_field(out, 4, r.trace_id);
  put_u64_field(out, 5, r.span_id);
  put_u64_field(out, 6, r.parent_span_id);
  put_u64_field(out, 7, r.timeout_ms);
  put_len_field(out, 8, r.auth_token);
  put_u64_field(out, 9, r.priority);
  put_len_field(out, 10, r.tenant);
  put_u64_field(out, 11, r.deadline_left_ms);
  return out;
}

static std::string encode_response_meta(const MetaResponse& r) {
  std::string out;
  put_u64_field(out, 1, r.error_code);
  put_len_field(out, 2, r.error_text);
  put_u64_field(out, 3, r.retry_after_ms);
  return out;
}

static std::string encode_meta(const RpcMeta& m) {
  std::string out;
  if (m.request.present) {
    std::string sub = encode_request_meta(m.request);
    put_tag(out, 1, 2);
    put_varint(out, sub.size());
    out.append(sub);
  }
  if (m.response.present) {
    std::string sub = encode_response_meta(m.response);
    put_tag(out, 2, 2);
    put_varint(out, sub.size());
    out.append(sub);
  }
  put_u64_field(out, 3, m.compress_type);
  put_u64_field(out, 4, m.correlation_id);
  put_u64_field(out, 5, m.attachment_size);
  return out;
}

static bool decode_len(const uint8_t*& p, const uint8_t* end,
                       const uint8_t** sub, const uint8_t** sub_end) {
  uint64_t n;
  if (!get_varint(p, end, &n) || (uint64_t)(end - p) < n) return false;
  *sub = p;
  *sub_end = p + n;
  p += n;
  return true;
}

static bool decode_string(const uint8_t*& p, const uint8_t* end,
                          std::string* s) {
  const uint8_t *sub, *sub_end;
  if (!decode_len(p, end, &sub, &sub_end)) return false;
  s->assign((const char*)sub, sub_end - sub);
  return true;
}

static bool decode_request_meta(const uint8_t* p, const uint8_t* end,
                                MetaRequest* r) {
  r->present = true;
  while (p < end) {
    uint64_t tag;
    if (!get_varint(p, end, &tag)) return false;
    int field = (int)(tag >> 3), wire = (int)(tag & 7);
    uint64_t v;
    switch (field) {
      case 1: if (!decode_string(p, end, &r->service_name)) return false; break;
      case 2: if (!decode_string(p, end, &r->method_name)) return false; break;
      case 3: if (!get_varint(p, end, &r->log_id)) return false; break;
      case 4: if (!get_varint(p, end, &r->trace_id)) return false; break;
      case 5: if (!get_varint(p, end, &r->span_id)) return false; break;
      case 6: if (!get_varint(p, end, &r->parent_span_id)) return false; break;
      case 7: if (!get_varint(p, end, &r->timeout_ms)) return false; break;
      case 8: if (!decode_string(p, end, &r->auth_token)) return false; break;
      case 9: if (!get_varint(p, end, &r->priority)) return false; break;
      case 10: if (!decode_string(p, end, &r->tenant)) return false; break;
      case 11:
        if (!get_varint(p, end, &r->deadline_left_ms)) return false;
        break;
      default: if (!skip_field(p, end, wire)) return false; break;
    }
    (void)v;
  }
  return true;
}

static bool decode_response_meta(const uint8_t* p, const uint8_t* end,
                                 MetaResponse* r) {
  r->present = true;
  while (p < end) {
    uint64_t tag;
    if (!get_varint(p, end, &tag)) return false;
    int field = (int)(tag >> 3), wire = (int)(tag & 7);
    switch (field) {
      case 1: if (!get_varint(p, end, &r->error_code)) return false; break;
      case 2: if (!decode_string(p, end, &r->error_text)) return false; break;
      case 3:
        if (!get_varint(p, end, &r->retry_after_ms)) return false;
        break;
      default: if (!skip_field(p, end, wire)) return false; break;
    }
  }
  return true;
}

static bool decode_meta(const uint8_t* p, const uint8_t* end, RpcMeta* m) {
  while (p < end) {
    uint64_t tag;
    if (!get_varint(p, end, &tag)) return false;
    int field = (int)(tag >> 3), wire = (int)(tag & 7);
    const uint8_t *sub, *sub_end;
    switch (field) {
      case 1:
        if (!decode_len(p, end, &sub, &sub_end) ||
            !decode_request_meta(sub, sub_end, &m->request))
          return false;
        break;
      case 2:
        if (!decode_len(p, end, &sub, &sub_end) ||
            !decode_response_meta(sub, sub_end, &m->response))
          return false;
        break;
      case 3: if (!get_varint(p, end, &m->compress_type)) return false; break;
      case 4: if (!get_varint(p, end, &m->correlation_id)) return false; break;
      case 5: if (!get_varint(p, end, &m->attachment_size)) return false; break;
      case 6:
        m->has_stream_settings = true;
        if (!skip_field(p, end, wire)) return false;
        break;
      default: if (!skip_field(p, end, wire)) return false; break;
    }
  }
  return true;
}

// ====================================================================
// TRPC frame: "TRPC" + u32be meta_size + u32be body_size
// ====================================================================

static const char kMagic[4] = {'T', 'R', 'P', 'C'};
static const size_t kHeaderSize = 12;

static void put_u32be(std::string& out, uint32_t v) {
  out.push_back((char)(v >> 24));
  out.push_back((char)(v >> 16));
  out.push_back((char)(v >> 8));
  out.push_back((char)v);
}

// header + meta only; the payload rides separate iovecs (no copy)
static std::string pack_head(const RpcMeta& meta, size_t body_len) {
  std::string meta_bytes = encode_meta(meta);
  std::string out;
  out.reserve(kHeaderSize + meta_bytes.size() + body_len);
  out.append(kMagic, 4);
  put_u32be(out, (uint32_t)meta_bytes.size());
  put_u32be(out, (uint32_t)body_len);
  out.append(meta_bytes);
  return out;
}

static std::string pack_frame(const RpcMeta& meta, const void* body,
                              size_t body_len) {
  std::string out = pack_head(meta, body_len);
  out.append((const char*)body, body_len);
  return out;
}

// head + up-to-two payload segments as iovecs; returns the entry count
static int build_iov(struct iovec* iov, const std::string& head,
                     const void* data, size_t len, const void* att,
                     size_t att_len) {
  int n = 0;
  iov[n].iov_base = (void*)head.data();
  iov[n++].iov_len = head.size();
  if (len) {
    iov[n].iov_base = (void*)data;
    iov[n++].iov_len = len;
  }
  if (att_len) {
    iov[n].iov_base = (void*)att;
    iov[n++].iov_len = att_len;
  }
  return n;
}

static uint32_t get_u32be(const uint8_t* p) {
  return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
         ((uint32_t)p[2] << 8) | (uint32_t)p[3];
}

// ====================================================================
// fd helpers
// ====================================================================

static void set_nonblock(int fd) {
  fcntl(fd, F_SETFL, fcntl(fd, F_GETFL, 0) | O_NONBLOCK);
}

static void set_nodelay(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  // socket buffer sizes stay kernel-autotuned: explicit 4 MB buffers
  // measured ~45% SLOWER for 1 MB echoes here (cache-cold slabs beat the
  // saved wakeups on a shared core)
}

// Scatter-gather bounded write: one syscall for header+meta+payload+
// attachment with no assembly copy (the zero-copy discipline of
// Socket::DoWrite's writev batching, socket.cpp:1790).  iov entries are
// consumed in place.  Polls through EAGAIN (callers already serialized
// per connection) but bounded: a peer that stops reading must not wedge
// the caller forever (the epoll thread calls this inline, so an
// unbounded loop would starve every connection on the loop and deadlock
// stop()).  ~5 s of refusal = dead.
static bool write_all_iov(int fd, struct iovec* iov, int iovcnt,
                          const std::atomic<bool>* abort_flag = nullptr,
                          int timeout_ms = 5000) {
  int waited_ms = 0;
  int cur = 0;
  while (cur < iovcnt) {
    if (abort_flag != nullptr &&
        abort_flag->load(std::memory_order_relaxed))
      return false;
    ssize_t w = ::writev(fd, iov + cur, iovcnt - cur);
    if (w > 0) {
      size_t n = (size_t)w;
      while (cur < iovcnt && n >= iov[cur].iov_len) {
        n -= iov[cur].iov_len;
        ++cur;
      }
      if (cur < iovcnt && n > 0) {
        iov[cur].iov_base = (char*)iov[cur].iov_base + n;
        iov[cur].iov_len -= n;
      }
    } else if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      if (waited_ms >= timeout_ms) return false;
      struct pollfd pfd{fd, POLLOUT, 0};
      ::poll(&pfd, 1, 100);
      waited_ms += 100;
    } else if (w < 0 && errno == EINTR) {
      continue;
    } else {
      return false;
    }
  }
  return true;
}

static bool write_all(int fd, const char* data, size_t len,
                      const std::atomic<bool>* abort_flag = nullptr,
                      int timeout_ms = 5000) {
  struct iovec iov{(void*)data, len};
  return write_all_iov(fd, &iov, 1, abort_flag, timeout_ms);
}

// Read up to `chunk` bytes straight into the tail of `s` — no intermediate
// stack buffer and no zero-fill (resize_and_overwrite leaves the new tail
// uninitialized for read() to fill).  For multi-chunk frames this halves
// userspace memory traffic vs buf-then-append.  Returns read() semantics.
static ssize_t read_into_string(int fd, std::string& s, size_t chunk) {
  size_t old = s.size();
  ssize_t got = 0;
#if defined(__cpp_lib_string_resize_and_overwrite)
  s.resize_and_overwrite(old + chunk, [&](char* p, size_t) {
    got = ::read(fd, p + old, chunk);
    return old + (got > 0 ? (size_t)got : 0);
  });
#else
  // pre-C++23 fallback: resize zero-fills the tail once per chunk — a
  // memset the reads immediately overwrite, still one copy fewer than
  // the stack-buffer-then-append path
  s.resize(old + chunk);
  got = ::read(fd, &s[old], chunk);
  s.resize(old + (got > 0 ? (size_t)got : 0));
#endif
  return got;
}

// If a frame header is already buffered, reserve the full frame so the
// growth path never re-copies accumulated bytes mid-frame.
static void reserve_for_frame(std::string& rbuf) {
  if (rbuf.size() < kHeaderSize) return;
  const uint8_t* p = (const uint8_t*)rbuf.data();
  if (memcmp(p, kMagic, 4) != 0) return;
  uint32_t meta_size = get_u32be(p + 4);
  uint32_t body_size = get_u32be(p + 8);
  if (meta_size > (1u << 26) || body_size > (1u << 31)) return;
  size_t total = kHeaderSize + (size_t)meta_size + body_size;
  if (total > rbuf.capacity()) rbuf.reserve(total);
}

// Read size for the next chunk: when the head of the buffer is a partial
// frame, read EXACTLY its remainder (capped) — one syscall instead of
// four per MB, and the buffer stays single-frame so bulk responses take
// the zero-copy dispatch path.
static size_t next_read_size(const std::string& rbuf) {
  static const size_t kChunk = 256 * 1024;
  if (rbuf.size() >= kHeaderSize &&
      memcmp(rbuf.data(), kMagic, 4) == 0) {
    uint32_t meta_size = get_u32be((const uint8_t*)rbuf.data() + 4);
    uint32_t body_size = get_u32be((const uint8_t*)rbuf.data() + 8);
    if (meta_size <= (1u << 26) && body_size <= (1u << 31)) {
      size_t total = kHeaderSize + (size_t)meta_size + body_size;
      if (total > rbuf.size())
        return std::min(total - rbuf.size(), (size_t)(8u << 20));
    }
  }
  return kChunk;
}

// ====================================================================
// NativeServer
// ====================================================================

// Python request hook: (token, method, payload, payload_len, att, att_len,
// log_id).  Respond via brpc_tpu_nserver_respond(token, ...) from any
// thread; each token must be answered exactly once.
typedef void (*py_request_fn)(uint64_t token, const char* method,
                              const uint8_t* payload, uint64_t payload_len,
                              const uint8_t* att, uint64_t att_len,
                              uint64_t log_id);

// Conns are shared_ptr-owned: the epoll thread, the conns_ map, and any
// in-flight respond() each hold a reference, so closing a connection can
// never free memory under another thread (the reference gets this from
// Socket's versioned-id ResourcePool; shared_ptr is the C++-idiomatic
// equivalent here).  After close, fd is -1 under wmu — respond() checks it
// so a recycled fd number is never written.
struct Conn {
  int fd = -1;
  std::string rbuf;
  std::mutex wmu;
  uint64_t id = 0;
  int loop = 0;       // owning epoll loop (reads are single-threaded per conn)
};
using ConnPtr = std::shared_ptr<Conn>;

struct PendingReply;

class NativeServer {
 public:
  // nloops: epoll loops (the reference's FLAGS_event_dispatcher_num,
  // event_dispatcher.cpp:30).  Loop 0 owns the listener; accepted conns
  // hash across loops so request processing scales past one core.
  bool start(int port, int nloops = 4) {
    listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
    addr.sin_port = htons((uint16_t)port);
    if (::bind(listen_fd_, (sockaddr*)&addr, sizeof(addr)) != 0) return false;
    socklen_t len = sizeof(addr);
    getsockname(listen_fd_, (sockaddr*)&addr, &len);
    port_ = ntohs(addr.sin_port);
    ::listen(listen_fd_, 128);
    set_nonblock(listen_fd_);
    nloops_ = nloops < 1 ? 1 : nloops;
    epfds_.resize(nloops_);
    for (int i = 0; i < nloops_; ++i) epfds_[i] = epoll_create1(0);
    epoll_event ev{};
    ev.events = EPOLLIN;                 // listen fd: level-triggered accept
    ev.data.u64 = 0;                     // 0 = listener
    epoll_ctl(epfds_[0], EPOLL_CTL_ADD, listen_fd_, &ev);
    for (int i = 0; i < nloops_; ++i)
      threads_.emplace_back([this, i] { run(i); });
    return true;
  }

  void stop();          // defined after the token registry (purges tokens)

  void set_handle(uint64_t h) { handle_ = h; }
  uint64_t handle() const { return handle_; }

  int port() const { return port_; }

  void register_echo(const std::string& full_method) {
    std::lock_guard<std::mutex> g(methods_mu_);
    echo_methods_.insert({full_method, true});
  }

  void set_py_handler(py_request_fn fn) { py_handler_ = fn; }

  uint64_t requests() const { return requests_.load(); }

  bool respond(uint64_t conn_id, uint64_t cid, uint64_t err,
               const std::string& err_text, const void* data, size_t len,
               const void* att, size_t att_len);

 private:
  void run(int loop) {
    epoll_event events[64];
    while (!stop_.load(std::memory_order_relaxed)) {
      int n = epoll_wait(epfds_[loop], events, 64, 50);
      for (int i = 0; i < n; ++i) {
        if (events[i].data.u64 == 0) {
          accept_all();
        } else {
          ConnPtr c = find_conn(events[i].data.u64);
          if (c != nullptr) handle_readable(c);
        }
      }
    }
  }

  void accept_all() {
    for (;;) {
      int fd = ::accept(listen_fd_, nullptr, nullptr);
      if (fd < 0) break;
      set_nonblock(fd);
      set_nodelay(fd);
      ConnPtr c = std::make_shared<Conn>();
      c->fd = fd;
      c->id = next_conn_id_.fetch_add(1) + 1;  // ids start at 1 (0=listener)
      c->loop = (int)(c->id % nloops_);        // conn pinned to one loop
      {
        std::lock_guard<std::mutex> g(conns_mu_);
        conns_[c->id] = c;
      }
      epoll_event ev{};
      ev.events = EPOLLIN | EPOLLET;           // edge-triggered data path
      ev.data.u64 = c->id;
      epoll_ctl(epfds_[c->loop], EPOLL_CTL_ADD, fd, &ev);
    }
  }

  ConnPtr find_conn(uint64_t id) {
    std::lock_guard<std::mutex> g(conns_mu_);
    auto it = conns_.find(id);
    return it == conns_.end() ? nullptr : it->second;
  }

  void close_conn(const ConnPtr& c) {
    {
      std::lock_guard<std::mutex> g(conns_mu_);
      conns_.erase(c->id);
    }
    std::lock_guard<std::mutex> wg(c->wmu);
    if (c->fd >= 0) {
      epoll_ctl(epfds_[c->loop], EPOLL_CTL_DEL, c->fd, nullptr);
      ::close(c->fd);
      c->fd = -1;     // respond() checks under wmu: no write to recycled fd
    }
  }

  void handle_readable(const ConnPtr& c) {
    for (;;) {                       // ET: drain until EAGAIN
      reserve_for_frame(c->rbuf);    // growth never re-copies mid-frame
      size_t chunk = next_read_size(c->rbuf);
      ssize_t r = read_into_string(c->fd, c->rbuf, chunk);
      if (r > 0) {
        // short read = socket buffer drained; data arriving after this
        // read raises a fresh edge, so skipping the EAGAIN round-trip is
        // safe and saves one syscall per request
        if ((size_t)r < chunk) break;
      } else if (r == 0) {
        close_conn(c);
        return;
      } else if (errno == EAGAIN || errno == EWOULDBLOCK) {
        break;
      } else if (errno == EINTR) {
        continue;
      } else {
        close_conn(c);
        return;
      }
    }
    // cut complete frames
    size_t off = 0;
    const std::string& rb = c->rbuf;
    while (rb.size() - off >= kHeaderSize) {
      const uint8_t* p = (const uint8_t*)rb.data() + off;
      if (memcmp(p, kMagic, 4) != 0) {  // protocol error: drop conn
        close_conn(c);
        return;
      }
      uint32_t meta_size = get_u32be(p + 4);
      uint32_t body_size = get_u32be(p + 8);
      if (meta_size > (1u << 26) || body_size > (1u << 31)) {
        close_conn(c);   // absurd frame sizes (tpu_std.py parse guard)
        return;
      }
      size_t total = kHeaderSize + (size_t)meta_size + body_size;
      if (rb.size() - off < total) break;
      process_frame(c, p + kHeaderSize, meta_size,
                    p + kHeaderSize + meta_size, body_size);
      off += total;
    }
    if (off > 0) c->rbuf.erase(0, off);
  }

  void process_frame(const ConnPtr& c, const uint8_t* meta_p,
                     size_t meta_len, const uint8_t* body, size_t body_len);

  int listen_fd_ = -1, port_ = 0;
  int nloops_ = 1;
  std::vector<int> epfds_;
  uint64_t handle_ = 0;
  std::vector<std::thread> threads_;
  std::atomic<bool> stop_{false};
  std::mutex conns_mu_;
  std::unordered_map<uint64_t, ConnPtr> conns_;
  std::atomic<uint64_t> next_conn_id_{0};
  std::mutex methods_mu_;
  std::unordered_map<std::string, bool> echo_methods_;
  py_request_fn py_handler_ = nullptr;
  std::atomic<uint64_t> requests_{0};
};

// Tokens for in-flight Python-handled requests.  A token stores the
// server's registry HANDLE, never a pointer: respond() re-resolves both
// the server (g_servers, shared_ptr) and the conn (conns_, shared_ptr) so
// replies after a disconnect or a server stop are dropped, not crashed —
// the reference's Socket::Address versioned-id discipline.
struct PendingReply {
  uint64_t server_handle;
  uint64_t conn_id;
  uint64_t cid;
};

static std::mutex g_tokens_mu;
// Heap-allocated and intentionally never freed (same discipline as
// fabric.cpp's conn registries): a static destructor would destroy this
// map — and the objects it pins — while server/channel reader threads
// another exiting thread left running may still be mid-access, which is
// the std::terminate-at-exit flake.  The OS reclaims everything.
static auto& g_tokens = *new nbase::FlatMap64<PendingReply>();
static std::atomic<uint64_t> g_next_token{1};

void NativeServer::stop() {
  stop_.store(true);
  for (auto& t : threads_)
    if (t.joinable()) t.join();
  threads_.clear();
  {
    // drop replies parked in Python for this server: their tokens must not
    // resolve once we're gone
    std::lock_guard<std::mutex> g(g_tokens_mu);
    std::vector<uint64_t> purge;
    g_tokens.for_each([&](uint64_t t, PendingReply& pr) {
      if (pr.server_handle == handle_) purge.push_back(t);
    });
    for (uint64_t t : purge) g_tokens.erase(t);
  }
  std::vector<ConnPtr> conns;
  {
    std::lock_guard<std::mutex> g(conns_mu_);
    for (auto& kv : conns_) conns.push_back(kv.second);
    conns_.clear();
  }
  for (auto& c : conns) {
    std::lock_guard<std::mutex> wg(c->wmu);
    if (c->fd >= 0) {
      ::close(c->fd);
      c->fd = -1;
    }
  }
  if (listen_fd_ >= 0) ::close(listen_fd_);
  for (int fd : epfds_)
    if (fd >= 0) ::close(fd);
  epfds_.clear();
  listen_fd_ = -1;
}

bool NativeServer::respond(uint64_t conn_id, uint64_t cid, uint64_t err,
                           const std::string& err_text, const void* data,
                           size_t len, const void* att, size_t att_len) {
  ConnPtr c = find_conn(conn_id);
  if (c == nullptr) return false;
  RpcMeta rmeta;
  rmeta.response.present = true;
  rmeta.response.error_code = err;
  rmeta.response.error_text = err_text;
  rmeta.correlation_id = cid;
  rmeta.attachment_size = att_len;
  std::string head = pack_head(rmeta, len + att_len);
  struct iovec iov[3];
  int iovcnt = build_iov(iov, head, data, len, att, att_len);
  bool ok;
  {
    std::lock_guard<std::mutex> g(c->wmu);
    ok = c->fd >= 0 &&               // closed while the handler ran?
         write_all_iov(c->fd, iov, iovcnt, &stop_);
  }
  // a timed-out/partial write leaves the stream desynced mid-frame: drop
  // the connection now (matching the echo path) instead of letting a
  // later respond() append after the truncation
  if (!ok) close_conn(c);
  return ok;
}

void NativeServer::process_frame(const ConnPtr& c, const uint8_t* meta_p,
                                 size_t meta_len, const uint8_t* body,
                                 size_t body_len) {
  RpcMeta meta;
  if (!decode_meta(meta_p, meta_p + meta_len, &meta)) {
    close_conn(c);
    return;
  }
  requests_.fetch_add(1, std::memory_order_relaxed);
  std::string full = meta.request.service_name + "." +
                     meta.request.method_name;
  bool is_echo;
  {
    std::lock_guard<std::mutex> g(methods_mu_);
    is_echo = echo_methods_.count(full) != 0;
  }  // released before any write: a stalled peer must not hold the
     // server-wide method table against other loops
  if (is_echo) {
    // native echo: response payload = request payload, attachment echoed;
    // payload goes out via writev straight from the read buffer (no copy)
    RpcMeta rmeta;
    rmeta.response.present = true;
    rmeta.correlation_id = meta.correlation_id;
    rmeta.attachment_size = meta.attachment_size;
    std::string head = pack_head(rmeta, body_len);
    struct iovec iov[3];
    int iovcnt = build_iov(iov, head, body, body_len, nullptr, 0);
    bool ok;
    {
      std::lock_guard<std::mutex> wg(c->wmu);
      ok = c->fd >= 0 && write_all_iov(c->fd, iov, iovcnt, &stop_);
    }
    if (!ok) close_conn(c);     // non-reading peer: drop it, free the loop
    return;
  }
  if (py_handler_ != nullptr) {
    uint64_t token = g_next_token.fetch_add(1);
    {
      std::lock_guard<std::mutex> g(g_tokens_mu);
      g_tokens[token] = PendingReply{handle_, c->id, meta.correlation_id};
    }
    size_t att = std::min((size_t)meta.attachment_size, body_len);
    size_t payload_len = body_len - att;
    py_handler_(token, full.c_str(), body, payload_len, body + payload_len,
                att, meta.request.log_id);
    return;
  }
  // ENOMETHOD (brpc_tpu/rpc/errors.py values mirror the reference's)
  RpcMeta rmeta;
  rmeta.response.present = true;
  rmeta.response.error_code = 1002;  // ENOMETHOD (rpc/errors.py)
  rmeta.response.error_text = "no method " + full;
  rmeta.correlation_id = meta.correlation_id;
  std::string frame = pack_frame(rmeta, nullptr, 0);
  bool ok;
  {
    std::lock_guard<std::mutex> wg(c->wmu);
    ok = c->fd >= 0 &&
         write_all(c->fd, frame.data(), frame.size(), &stop_);
  }
  if (!ok) close_conn(c);
}

// ====================================================================
// NativeChannel: correlation table + caller-becomes-reader election
// ====================================================================

// Slots are shared_ptr-owned: the caller, the slots_ map, and a reader
// mid-dispatch each hold a reference, so a timed-out caller erasing its
// slot can never free it under the reader (the review finding this fixes:
// dispatch_frame resolved a raw pointer, released slots_mu_, then locked
// the slot — a deleted slot in between was a use-after-free).
// async completion hook: (user, error_code, err_text, payload,
// payload_len, att, att_len); pointers valid only for the callback
typedef void (*nrpc_async_cb)(void* user, uint64_t error_code,
                              const char* err_text, const uint8_t* resp,
                              uint64_t resp_len, const uint8_t* att,
                              uint64_t att_len);

struct CallSlot {
  std::mutex mu;
  std::condition_variable cv;
  bool done = false;
  uint64_t error_code = 0;
  std::string error_text;
  // response bytes: `storage` owns them (for bulk responses the READER's
  // buffer is MOVED here — zero copy); payload/attachment are spans
  std::string storage;
  size_t p_off = 0, p_len = 0, a_off = 0, a_len = 0;
  // async completion (sync callers leave cb null and wait on cv)
  nrpc_async_cb cb = nullptr;
  void* cb_user = nullptr;
  int64_t deadline_ns = 0;       // async timeout, checked by the reader
};
using SlotPtr = std::shared_ptr<CallSlot>;

// Owning view of one completed call's response.
struct CallResult {
  std::string storage;
  size_t p_off = 0, p_len = 0, a_off = 0, a_len = 0;
  const uint8_t* payload() const {
    return (const uint8_t*)storage.data() + p_off;
  }
  const uint8_t* attachment() const {
    return (const uint8_t*)storage.data() + a_off;
  }
};

class NativeChannel : public std::enable_shared_from_this<NativeChannel> {
 public:
  ~NativeChannel() {
    closing_.store(true, std::memory_order_release);
    join_reader();
    // fd closes only here, once every in-flight call has dropped its
    // shared_ptr to this channel — an fd number is never recycled while a
    // caller could still write it
    if (fd_ >= 0) ::close(fd_);
  }

  bool connect_to(const char* host, int port) {
    fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) return false;
    if (::connect(fd_, (sockaddr*)&addr, sizeof(addr)) != 0) {
      ::close(fd_);
      fd_ = -1;
      return false;
    }
    set_nodelay(fd_);
    set_nonblock(fd_);   // readers use poll(); an exact-64KiB read burst
                         // must hit EAGAIN, not block holding read_mu_
    return true;
  }

  void close_ch() {
    closing_.store(true, std::memory_order_release);
    fail_all_pending();     // fd itself closes in the destructor
    join_reader();
  }

  void fail_all_pending() {
    // O(1) under the hot lock (same discipline as IciChannel::fail_all,
    // review finding: per-slot lock/notify sweeps under slots_mu_
    // stalled concurrent slot registration); the table is processed
    // outside it
    nbase::FlatMap64<SlotPtr> victims;
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      victims.swap(slots_);
    }
    std::vector<std::pair<SlotPtr, uint64_t>> async_victims;
    victims.for_each([&](uint64_t cid, SlotPtr& sp) {
      std::lock_guard<std::mutex> sg(sp->mu);
      if (sp->done) return;             // delivered result stays delivered
      sp->done = true;
      sp->error_code = 1009;  // EFAILEDSOCKET (rpc/errors.py)
      sp->error_text = "channel closed";
      sp->cv.notify_all();
      if (sp->cb != nullptr) async_victims.push_back({sp, cid});
    });
    for (auto& [slot, cid] : async_victims)   // callbacks outside locks
      slot->cb(slot->cb_user, 1009, "channel closed", nullptr, 0, nullptr,
               0);
  }

  bool pack_and_write(const char* service_dot_method, const void* req,
                      size_t req_len, const void* att, size_t att_len,
                      int64_t timeout_us, uint64_t cid) {
    RpcMeta meta;
    meta.request.present = true;
    const char* dot = strrchr(service_dot_method, '.');
    if (dot == nullptr) {
      meta.request.method_name = service_dot_method;
    } else {
      meta.request.service_name.assign(service_dot_method,
                                       dot - service_dot_method);
      meta.request.method_name = dot + 1;
    }
    meta.correlation_id = cid;
    meta.attachment_size = att_len;
    if (timeout_us > 0)
      meta.request.timeout_ms = (uint64_t)(timeout_us / 1000);
    std::string head = pack_head(meta, req_len + att_len);
    struct iovec iov[3];
    int iovcnt = build_iov(iov, head, req, req_len, att, att_len);
    std::lock_guard<std::mutex> g(wmu_);
    return !closing_.load(std::memory_order_acquire) &&
           write_all_iov(fd_, iov, iovcnt);
  }

  // 0 ok; 1008 ERPCTIMEDOUT; 1009 broken socket; else server error code
  uint64_t call(const char* service_dot_method, const void* req,
                size_t req_len, const void* att, size_t att_len,
                int64_t timeout_us, CallResult* out,
                std::string* err_text) {
    if (fd_ < 0 || closing_.load(std::memory_order_acquire)) {
      *err_text = "channel not connected";
      return 1009;
    }
    uint64_t cid = next_cid_.fetch_add(1) + 1;
    SlotPtr slot = std::make_shared<CallSlot>();
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      slots_[cid] = slot;
    }
    if (!pack_and_write(service_dot_method, req, req_len, att, att_len,
                        timeout_us, cid)) {
      erase_slot(cid);
      *err_text = "write failed";
      return 1009;
    }
    // wait: become the reader or wait for the reader to fill our slot
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::microseconds(timeout_us > 0 ? timeout_us
                                                             : (int64_t)1e12);
    uint64_t rc = 0;
    for (;;) {
      {
        std::unique_lock<std::mutex> sl(slot->mu);
        if (slot->done) break;
      }
      if (read_mu_.try_lock()) {
        bool progressed = read_once(200);
        read_mu_.unlock();
        if (!progressed && closing_.load(std::memory_order_acquire)) {
          std::unique_lock<std::mutex> sl(slot->mu);
          if (!slot->done) {
            slot->done = true;
            slot->error_code = 1009;
            slot->error_text = "connection lost";
          }
          break;
        }
      } else {
        std::unique_lock<std::mutex> sl(slot->mu);
        nbase::cv_wait_for(slot->cv, sl, std::chrono::milliseconds(1));
        if (slot->done) break;
      }
      if (std::chrono::steady_clock::now() >= deadline) {
        erase_slot(cid);   // response arriving later finds no slot: dropped,
                           // exactly the stale-version drop of bthread_id
        *err_text = "rpc timeout";
        return 1008;       // ERPCTIMEDOUT (rpc/errors.py)
      }
    }
    rc = slot->error_code;
    *err_text = slot->error_text;
    out->storage = std::move(slot->storage);
    out->p_off = slot->p_off;
    out->p_len = slot->p_len;
    out->a_off = slot->a_off;
    out->a_len = slot->a_len;
    erase_slot(cid);
    return rc;
  }

  // Async completion: fire-and-forget write; `cb` runs on the channel's
  // reader thread when the response (or timeout/conn-death) arrives.
  // The reference's async CallMethod with done closure (client.cpp
  // examples); ours completes from the background reader the same way
  // brpc completes from the event dispatcher thread.
  uint64_t call_async(const char* service_dot_method, const void* req,
                      size_t req_len, const void* att, size_t att_len,
                      int64_t timeout_us, nrpc_async_cb cb, void* user) {
    if (fd_ < 0 || closing_.load(std::memory_order_acquire)) {
      cb(user, 1009, "channel not connected", nullptr, 0, nullptr, 0);
      return 1009;
    }
    uint64_t cid = next_cid_.fetch_add(1) + 1;
    SlotPtr slot = std::make_shared<CallSlot>();
    slot->cb = cb;
    slot->cb_user = user;
    slot->deadline_ns =
        now_steady_ns() + (timeout_us > 0 ? timeout_us * 1000
                                          : (int64_t)1e15);
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      slots_[cid] = slot;
    }
    // pull the sweep forward if this deadline is the nearest (benign
    // race: worst case one 50ms-late sweep)
    int64_t cur = next_sweep_ns_.load(std::memory_order_relaxed);
    if (slot->deadline_ns < cur)
      next_sweep_ns_.store(slot->deadline_ns, std::memory_order_relaxed);
    ensure_reader();
    if (!pack_and_write(service_dot_method, req, req_len, att, att_len,
                        timeout_us, cid)) {
      erase_slot(cid);
      // a racing fail_all_pending / deadline sweep may already have
      // completed this slot: the callback fires EXACTLY once, gated on
      // slot->done like every other completion path
      bool fire = false;
      {
        std::lock_guard<std::mutex> sg(slot->mu);
        if (!slot->done) {
          slot->done = true;
          slot->error_code = 1009;
          fire = true;
        }
      }
      if (fire) cb(user, 1009, "write failed", nullptr, 0, nullptr, 0);
      return 1009;
    }
    return 0;
  }

 private:
  static int64_t now_steady_ns() {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
  }

  void erase_slot(uint64_t cid) {
    std::lock_guard<std::mutex> g(slots_mu_);
    slots_.erase(cid);
  }

  // Background reader for async completions.  Sync callers still use
  // caller-becomes-reader; read_mu_ arbitrates.  Started on the first
  // async call, lives until close.
  void ensure_reader() {
    // reader_ construction and join are serialized by reader_mu_ — a
    // flag-then-assign publication would let a concurrent close_ch read
    // the std::thread object mid-move (UB)
    std::lock_guard<std::mutex> g(reader_mu_);
    if (reader_.joinable()) return;
    // the loop holds a self-reference: the destructor can never run
    // while the reader is mid-iteration (an async callback may drop the
    // last external ref)
    auto self = shared_from_this();
    reader_ = std::thread([self] {
      while (!self->closing_.load(std::memory_order_acquire)) {
        if (self->read_mu_.try_lock()) {
          self->read_once(50);
          self->read_mu_.unlock();
        } else {
          // a sync caller is the reader right now; it fills async slots
          // too, so just yield briefly
          std::this_thread::sleep_for(std::chrono::milliseconds(1));
        }
        // deadline sweep only when something can actually expire — a
        // per-iteration full slot scan would contend the dispatch path
        if (now_steady_ns() >=
            self->next_sweep_ns_.load(std::memory_order_relaxed))
          self->sweep_async_deadlines();
      }
    });
  }

  void join_reader() {
    std::thread t;
    {
      std::lock_guard<std::mutex> g(reader_mu_);
      t = std::move(reader_);
    }
    if (!t.joinable()) return;
    if (t.get_id() == std::this_thread::get_id()) {
      // close() called from inside an async completion callback (which
      // runs ON the reader thread): self-join would abort the process.
      // Detach — the loop exits right after the callback returns
      // (closing_ is set), and it holds its own shared_ptr, so no
      // use-after-free.
      t.detach();
      return;
    }
    t.join();
  }

  void sweep_async_deadlines() {
    int64_t now = now_steady_ns();
    int64_t next = now + 50 * 1000 * 1000;    // idle: re-check in 50ms
    std::vector<std::pair<uint64_t, SlotPtr>> expired;
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      slots_.for_each([&](uint64_t cid, SlotPtr& sp) {
        if (sp->cb == nullptr) return;
        if (sp->deadline_ns <= now)
          expired.push_back({cid, sp});
        else
          next = std::min(next, sp->deadline_ns);
      });
      for (auto& kv : expired) slots_.erase(kv.first);
    }
    next_sweep_ns_.store(next, std::memory_order_relaxed);
    for (auto& [cid, slot] : expired) {
      bool fire = false;
      {
        std::lock_guard<std::mutex> sg(slot->mu);
        if (!slot->done) {
          slot->done = true;
          slot->error_code = 1008;
          fire = true;
        }
      }
      if (fire)
        slot->cb(slot->cb_user, 1008, "rpc timeout", nullptr, 0, nullptr,
                 0);
    }
  }

  // drain the socket into rbuf_ until EAGAIN/short read; sets *eof on
  // peer close (handled by the caller AFTER buffered frames dispatch, so
  // a response sharing a segment with FIN still reaches its slot);
  // returns the number of bytes read
  ssize_t drain_fd(bool* eof) {
    ssize_t got = 0;
    for (;;) {
      reserve_for_frame(rbuf_);
      size_t chunk = next_read_size(rbuf_);
      ssize_t r = read_into_string(fd_, rbuf_, chunk);
      if (r > 0) {
        got += r;
        if ((size_t)r < chunk) break;   // socket buffer drained
      } else if (r == 0) {
        *eof = true;
        break;
      } else {
        break;  // EAGAIN (fd is nonblocking)
      }
    }
    return got;
  }

  // Read whatever is available (one optimistic drain, else poll up to
  // timeout_ms and drain), dispatch complete frames into slots; returns
  // true if bytes were read.
  bool read_once(int timeout_ms) {
    // optimistic drain first: under pipelining/1-core scheduling the
    // response is often already buffered, making poll() a wasted syscall
    bool eof = false;
    ssize_t got = drain_fd(&eof);
    if (got == 0 && !eof) {
      struct pollfd pfd{fd_, POLLIN, 0};
      if (::poll(&pfd, 1, timeout_ms) <= 0) return false;
      got = drain_fd(&eof);
    }
    bool any = got > 0;
    size_t off = 0;
    while (rbuf_.size() - off >= kHeaderSize) {
      const uint8_t* p = (const uint8_t*)rbuf_.data() + off;
      uint32_t meta_size = get_u32be(p + 4);
      uint32_t body_size = get_u32be(p + 8);
      if (memcmp(p, kMagic, 4) != 0 || meta_size > (1u << 26) ||
          body_size > (1u << 31)) {
        // mid-frame desync is unrecoverable on a byte stream: fail the
        // channel so callers get 1009 now instead of timing out forever
        ::shutdown(fd_, SHUT_RDWR);
        closing_.store(true, std::memory_order_release);
        fail_all_pending();
        rbuf_.clear();
        return any;
      }
      size_t total = kHeaderSize + (size_t)meta_size + body_size;
      if (rbuf_.size() - off < total) break;
      if (off == 0 && total == rbuf_.size()) {
        // exactly one frame in the buffer: move it into the slot instead
        // of copying the body (bulk responses land here — the read
        // buffer was pre-reserved to the frame size)
        std::string whole;
        whole.swap(rbuf_);
        const uint8_t* wp = (const uint8_t*)whole.data();
        dispatch_frame(wp + kHeaderSize, meta_size,
                       wp + kHeaderSize + meta_size, body_size, &whole);
        off = 0;
        break;
      }
      dispatch_frame(p + kHeaderSize, meta_size, p + kHeaderSize + meta_size,
                     body_size);
      off += total;
    }
    if (off > 0) rbuf_.erase(0, off);
    if (eof) {
      // peer EOF — processed only after the dispatch loop above, so
      // responses riding the final segment were delivered.  shutdown
      // (not close) so the fd number cannot be recycled while concurrent
      // writers still reference it; the destructor does the close
      ::shutdown(fd_, SHUT_RDWR);
      closing_.store(true, std::memory_order_release);
      fail_all_pending();
    }
    return any;
  }

  // Fill a slot from a complete frame.  `owned` non-null hands the WHOLE
  // buffer to the slot (zero-copy: the reader's rbuf is moved when it
  // holds exactly one frame — the common shape for bulk responses, and
  // ~20% of per-byte CPU on the large-request path); otherwise the body
  // is copied out of the shared read buffer.
  void dispatch_frame(const uint8_t* meta_p, size_t meta_len,
                      const uint8_t* body, size_t body_len,
                      std::string* owned = nullptr) {
    RpcMeta meta;
    if (!decode_meta(meta_p, meta_p + meta_len, &meta)) return;
    SlotPtr slot;
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      SlotPtr* p = slots_.seek(meta.correlation_id);
      if (p != nullptr) {
        slot = *p;                    // shared ref held past mu
        if (slot->cb != nullptr)
          slots_.erase(meta.correlation_id);         // async: done here
      }
    }
    if (slot == nullptr) return;  // timed out / stale: drop
    size_t att = std::min((size_t)meta.attachment_size, body_len);
    size_t payload_len = body_len - att;
    nrpc_async_cb cb = nullptr;
    void* cb_user = nullptr;
    {
      std::lock_guard<std::mutex> sg(slot->mu);
      if (slot->done) return;       // async timeout sweep beat us
      slot->error_code = meta.response.error_code;
      slot->error_text = meta.response.error_text;
      if (owned != nullptr) {
        size_t body_off = (const char*)body - owned->data();
        slot->storage = std::move(*owned);
        slot->p_off = body_off;
      } else {
        slot->storage.assign((const char*)body, body_len);
        slot->p_off = 0;
      }
      slot->p_len = payload_len;
      slot->a_off = slot->p_off + payload_len;
      slot->a_len = att;
      slot->done = true;
      slot->cv.notify_all();
      cb = slot->cb;
      cb_user = slot->cb_user;
    }
    if (cb != nullptr)              // async completion, outside slot->mu
      cb(cb_user, slot->error_code, slot->error_text.c_str(),
         (const uint8_t*)slot->storage.data() + slot->p_off, slot->p_len,
         (const uint8_t*)slot->storage.data() + slot->a_off, slot->a_len);
  }

  int fd_ = -1;
  std::atomic<bool> closing_{false};
  std::atomic<uint64_t> next_cid_{0};
  std::mutex wmu_;
  std::mutex read_mu_;
  std::string rbuf_;
  std::mutex slots_mu_;
  nbase::FlatMap64<SlotPtr> slots_;   // correlation hot path (flat_map.h)
  std::mutex reader_mu_;
  std::thread reader_;
  std::atomic<int64_t> next_sweep_ns_{0};
};

// Pooled multi-connection channel (reference: pooled sockets,
// src/brpc/socket.h:256-262) — N connections round-robined per call so
// large requests overlap in the kernel instead of serializing on one
// stream.  This is the reference's 2.3 GB/s "pooled large messages"
// deployment shape (docs/cn/benchmark.md:104).
class NativePool {
 public:
  bool connect_to(const char* host, int port, int nconns) {
    for (int i = 0; i < (nconns < 1 ? 1 : nconns); ++i) {
      auto c = std::make_shared<NativeChannel>();
      if (!c->connect_to(host, port)) return false;
      conns_.push_back(std::move(c));
    }
    return true;
  }

  std::shared_ptr<NativeChannel> pick() {
    return conns_[rr_.fetch_add(1, std::memory_order_relaxed)
                  % conns_.size()];
  }

  void close_all() {
    for (auto& c : conns_) c->close_ch();
  }

  size_t size() const { return conns_.size(); }

 private:
  std::vector<std::shared_ptr<NativeChannel>> conns_;
  std::atomic<uint64_t> rr_{0};
};

// ====================================================================
// ici:// in-process plane: the native device-endpoint datapath.
//
// Analogue of the reference's RDMA endpoint (rdma_endpoint.cpp): control
// frames (TRPC header+meta+payload+host-attachment bytes) move through
// the native codec above; bulk device payloads ride a sidecar of
// "device refs" — {key, nbytes, resident-device} descriptors naming
// arrays held alive by a Python-side registry (the SGE list of a
// zero-copy post, rdma_endpoint.cpp:771 CutFromIOBufList).  The ONLY
// Python on the datapath is the relocation upcall, and only when a ref
// is not already resident on the target device (the HBM→HBM ICI
// device_put); a resident ref passes through with zero upcalls.
//
// Custody discipline for refs (mirrors the completion-driven _sbuf free,
// rdma_endpoint.cpp:926): a key entering native custody (call/respond)
// leaves it either INTO Python (an upcall or a returned response — the
// Python side takes it from the registry) or by an explicit release
// upcall on drop paths (timeout, dead peer, relocation).  Exactly one
// exit per key: the registry can never leak or free-under-use.
// ====================================================================

struct IciSegC {
  uint64_t key;      // registry key for device segs; unused for host segs
  uint64_t nbytes;   // logical byte length of this attachment segment
  int32_t dev;       // resident device id (device segs)
  int32_t is_dev;    // 1 = device ref, 0 = host bytes (span of att_host)
};

typedef uint64_t (*py_relocate_fn)(uint64_t key, int32_t target_dev);
typedef void (*py_release_fn)(uint64_t key);
// (token, method, payload, len, att_host, att_host_len, segs, nsegs,
//  log_id, peer_dev); answer exactly once via brpc_tpu_ici_respond
typedef void (*py_ici_request_fn)(uint64_t token, const char* method,
                                  const uint8_t* payload,
                                  uint64_t payload_len,
                                  const uint8_t* att_host,
                                  uint64_t att_host_len,
                                  const IciSegC* segs, uint64_t nsegs,
                                  uint64_t log_id, int32_t peer_dev);

// ---- one-struct batched upcall ABI -------------------------------------
// The Python-handler tier's request boundary: ONE ctypes crossing hands
// the handler tier an array of packed request structs (method id,
// correlation token, deadline metadata, payload views), and one crossing
// takes an array of packed response structs back
// (brpc_tpu_ici_respond_batch).  Replaces the per-request 10-argument
// upcall + 9-argument respond chatter: under load the GIL acquisition
// and argument marshalling amortize over the whole batch.
struct IciReqC {
  uint64_t token;          // respond exactly once with this token
  const char* method;      // "Service.Method"
  const uint8_t* payload;  // request body (borrowed for the upcall)
  uint64_t payload_len;
  const uint8_t* att_host; // host-attachment bytes (borrowed)
  uint64_t att_host_len;
  const IciSegC* segs;     // device-ref sidecar; Python TAKES the keys
  uint64_t nsegs;
  uint64_t log_id;
  int64_t recv_ns;         // steady-clock enqueue stamp (queue stage)
  int32_t peer_dev;
  int32_t _pad;
  // admission-control propagation (appended: earlier fields keep their
  // offsets for the ctypes mirror).  priority stays WIRE-encoded
  // (0 = unset, 1..N = band 0..N-1); tenant is borrowed for the upcall.
  const char* tenant;
  uint64_t deadline_left_ms;
  int32_t priority;
  int32_t _pad2;
  // native attachment custody (appended, ISSUE 12): nonzero means the
  // device-seg list is PARKED in the native att table under this
  // handle instead of being taken by Python during the upcall.  Python
  // wraps it lazily and exits custody exactly once — pass the handle
  // back in IciRespC.att_handle (echo pass-through), take the keys via
  // brpc_tpu_ici_att_take at materialization, or dispose it
  // (brpc_tpu_ici_att_dispose) at Controller pool-recycle.  segs/nsegs
  // still point at the parked list (heap-stable while the handle
  // lives) for callers that need the full walk; seg0_* mirrors
  // segs[0] inline so the dominant one-seg shape is readable with
  // plain struct field loads instead of a ctypes pointer deref.
  uint64_t att_handle;
  uint64_t seg0_key;
  uint64_t seg0_nbytes;
  int32_t seg0_dev;
  int32_t _pad3;
};
// (reqs, n): process each request; every token answered exactly once
typedef void (*py_ici_batch_fn)(const IciReqC* reqs, uint64_t n);

struct IciRespC {
  uint64_t token;
  uint64_t err;            // 0 = success
  const char* err_text;    // may be null
  const uint8_t* data;     // response payload (borrowed for the call)
  uint64_t len;
  const uint8_t* att_host;
  uint64_t att_host_len;
  const IciSegC* segs;     // custody of device keys transfers to native
  uint64_t nsegs;
  uint64_t retry_after_ms; // admission shed hint, 0 = none
  // native custody pass-through (appended, ISSUE 12): nonzero names a
  // parked att-table entry whose seg list IS this response's device
  // attachment — the echo shape never walks segs in Python.  segs/
  // nsegs are ignored when set.
  uint64_t att_handle;
};

static inline int64_t ici_now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

static std::atomic<py_relocate_fn> g_ici_relocate{nullptr};
static std::atomic<py_release_fn> g_ici_release{nullptr};

static void ici_release_segs(const std::vector<IciSegC>& segs) {
  py_release_fn rel = g_ici_release.load(std::memory_order_acquire);
  if (rel == nullptr) return;
  for (const auto& s : segs)
    if (s.is_dev) rel(s.key);
}

// ---- native-owned attachment custody table (ISSUE 12) ----------------
// One entry parks a whole device-seg list under an opaque handle, so
// the Python handler tier never walks segs or touches its device-ref
// registry on the hot path: the handle moves with the structs
// (IciReqC.att_handle in, IciRespC.att_handle back out on the echo
// pass-through) and exits custody EXACTLY once — pass-back, take
// (Python assumed the keys), or dispose (keys released via the release
// upcall).  Entries are heap-allocated so IciReqC.segs pointers into
// them stay stable across table rehashes.
struct IciAttEntry {
  std::vector<IciSegC> segs;
};
static std::mutex g_ici_atts_mu;
// Leaked like the other registries (see g_ici_listeners): static
// teardown must never race live holders at exit.
// fablint: guarded-by(g_ici_atts_mu): g_ici_atts
static auto& g_ici_atts = *new nbase::FlatMap64<IciAttEntry*>();
static std::atomic<uint64_t> g_ici_next_att{1};

// Register a parked entry; `out_e` (optional) receives the heap entry
// so callers can point borrowed views (IciReqC.segs) at its stable
// seg storage.  EVERY registration goes through here — the protocol
// (alloc, counter, publish under the lock) has exactly one home.
static uint64_t ici_att_register(std::vector<IciSegC>&& segs,
                                 IciAttEntry** out_e = nullptr) {
  auto* e = new IciAttEntry{std::move(segs)};
  uint64_t h = g_ici_next_att.fetch_add(1);
  {
    std::lock_guard<std::mutex> g(g_ici_atts_mu);
    g_ici_atts[h] = e;
  }
  if (out_e != nullptr) *out_e = e;
  return h;
}

static IciAttEntry* ici_att_pop(uint64_t h) {
  std::lock_guard<std::mutex> g(g_ici_atts_mu);
  IciAttEntry* e = nullptr;
  if (!g_ici_atts.take(h, &e)) return nullptr;
  return e;
}

// Move every non-resident device ref to target_dev via the Python/JAX
// upcall (jax.device_put = the ICI transfer).  Returns false when the
// device plane can't relocate (caller fails the RPC).  The replaced key
// is released — its custody ends here.
static bool ici_relocate_segs(std::vector<IciSegC>& segs,
                              int32_t target_dev) {
  py_relocate_fn rf = g_ici_relocate.load(std::memory_order_acquire);
  py_release_fn rel = g_ici_release.load(std::memory_order_acquire);
  for (auto& s : segs) {
    if (!s.is_dev || s.dev == target_dev) continue;
    if (rf == nullptr) return false;
    uint64_t nk = rf(s.key, target_dev);
    if (nk == 0) return false;
    if (nk != s.key && rel != nullptr) rel(s.key);
    s.key = nk;
    s.dev = target_dev;
  }
  return true;
}

struct IciSlot {
  std::mutex mu;
  std::condition_variable cv;
  // lock-free fast-path check: the native echo tier delivers inline
  // before the caller ever reaches its wait, so `done` is usually
  // already true and the mutex/condvar is skipped entirely
  std::atomic<bool> done{false};
  bool abandoned = false;   // waiter timed out; deliver() must release
  uint64_t error_code = 0;
  std::string error_text;
  std::string payload, att_host;
  std::vector<IciSegC> segs;
  uint64_t retry_after_ms = 0;   // admission shed hint
};
using IciSlotPtr = std::shared_ptr<IciSlot>;

class IciServer;

class IciChannel {
 public:
  IciChannel(int32_t local_dev, int32_t remote_dev)
      : local_dev_(local_dev), remote_dev_(remote_dev) {}

  int32_t local_dev() const { return local_dev_; }
  int32_t remote_dev() const { return remote_dev_; }

  IciSlotPtr make_slot(uint64_t* cid) {
    *cid = next_cid_.fetch_add(1) + 1;
    auto slot = std::make_shared<IciSlot>();
    std::lock_guard<std::mutex> g(slots_mu_);
    slots_[*cid] = slot;
    return slot;
  }

  void erase_slot(uint64_t cid) {
    std::lock_guard<std::mutex> g(slots_mu_);
    slots_.erase(cid);
  }

  // Response delivery from the server worker (or respond()).  The slot
  // stays in the map — the WAITER erases it after consuming, so a
  // deliver/timeout race can never strand segs in a slot nobody reads
  // (review finding r4: erase-before-fill leaked device-ref custody and
  // turned an arrived response into a spurious timeout).  A missing or
  // abandoned slot drops the payload and releases ref custody.
  void deliver(uint64_t cid, uint64_t err, std::string err_text,
               std::string payload, std::string att_host,
               std::vector<IciSegC> segs, uint64_t retry_after_ms = 0) {
    IciSlotPtr slot;
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      IciSlotPtr* p = slots_.seek(cid);
      if (p != nullptr) slot = *p;
    }
    if (slot == nullptr) {
      ici_release_segs(segs);
      return;
    }
    {
      std::lock_guard<std::mutex> g(slot->mu);
      if (slot->abandoned) {
        ici_release_segs(segs);
        return;
      }
      slot->error_code = err;
      slot->error_text = std::move(err_text);
      slot->payload = std::move(payload);
      slot->att_host = std::move(att_host);
      slot->segs = std::move(segs);
      slot->retry_after_ms = retry_after_ms;
      slot->done.store(true, std::memory_order_release);
    }
    slot->cv.notify_all();
  }

  void fail_all(uint64_t err, const char* text) {
    // O(1) under the hot lock (review finding: per-entry shared_ptr
    // copies stalled concurrent make_slot/deliver for the copy's
    // duration); the table is processed outside it
    nbase::FlatMap64<IciSlotPtr> victims;
    {
      std::lock_guard<std::mutex> g(slots_mu_);
      victims.swap(slots_);
    }
    // victims is private to this frame: process in place, no staging
    victims.for_each([&](uint64_t, IciSlotPtr& sp) {
      {
        std::lock_guard<std::mutex> g(sp->mu);
        if (sp->done.load(std::memory_order_acquire)) return;
        sp->error_code = err;
        sp->error_text = text;
        sp->done.store(true, std::memory_order_release);
      }
      sp->cv.notify_all();
    });
  }

 private:
  int32_t local_dev_, remote_dev_;
  std::atomic<uint64_t> next_cid_{0};
  std::mutex slots_mu_;
  // correlation table on the sub-microsecond path: contiguous
  // open-addressing slots, no per-node allocation (see flat_map.h)
  nbase::FlatMap64<IciSlotPtr> slots_;
};
using IciChannelPtr = std::shared_ptr<IciChannel>;

// One accepted connection: the client→server credit window lives here
// (requests are windowed; responses deliver into a waiting slot, so the
// reverse direction cannot queue unboundedly in-process).
struct IciConn {
  uint64_t id = 0;
  int32_t client_dev = 0;
  std::weak_ptr<IciChannel> client;
  std::shared_ptr<IciServer> server;
  std::mutex wmu;
  std::condition_variable wcv;
  int64_t window_left = 0;
  int64_t window_bytes = 0;
  std::atomic<bool> closed{false};

  void return_credits(int64_t n) {
    {
      std::lock_guard<std::mutex> g(wmu);
      window_left = std::min(window_bytes, window_left + n);
    }
    wcv.notify_all();
  }
};
using IciConnPtr = std::shared_ptr<IciConn>;

struct IciMsg {
  IciConnPtr conn;
  uint64_t cid = 0;
  std::string bytes;             // full TRPC frame (header+meta+payload+att)
  std::vector<IciSegC> segs;
  int64_t wire_bytes = 0;        // credits returned when consumed
};

// A Python-tier request parked in the server's batch queue: owns the
// frame bytes (the IciReqC views point into them) until the upcall
// consumes it.  Credits return when the upcall does.
struct IciBatchItem {
  uint64_t token = 0;
  std::string method;
  std::string bytes;             // full frame; payload/att are spans of it
  size_t payload_off = 0, payload_len = 0, att_len = 0;
  std::vector<IciSegC> segs;
  uint64_t log_id = 0;
  int32_t peer_dev = 0;
  int64_t enq_ns = 0;
  IciConnPtr conn;
  int64_t wire_bytes = 0;
  // admission-control metadata (wire-encoded priority: 0 = unset)
  uint64_t priority = 0;
  std::string tenant;
  uint64_t deadline_left_ms = 0;
};

// Dispatch discipline: the in-process transport's "IO thread" is the
// CALLER — ici_do_call runs the server's frame processing inline on the
// client thread (the reference's usercode-in-IO-thread default,
// baidu_rpc_protocol.cpp:312, specialized to a loopback transport; this
// box may have ONE core, where any thread-hop design serializes both
// sides' wakeups and loses ~100 µs/round).  Python-tier handlers keep
// their isolation anyway: the ServerBinding upcall parks user code on a
// tasklet unless the server opted into usercode_inline.
class IciServer : public std::enable_shared_from_this<IciServer> {
 public:
  // handler arrives at construction so the listener is never visible in
  // a half-initialized state (a racing call between listen and a later
  // set_handler would ENOMETHOD a method that exists)
  explicit IciServer(int32_t dev, py_ici_request_fn handler)
      : dev_(dev), handler_(handler) {}

  void start() {}

  void stop() {
    stop_.store(true, std::memory_order_release);
    // fail queued-but-undelivered Python-tier batch items first: their
    // device refs release and their callers get a specific error instead
    // of a parked request that nothing will ever drain
    std::deque<IciBatchItem> leftover;
    {
      std::lock_guard<std::mutex> g(bq_mu_);
      bq_stopped_ = true;
      leftover.swap(bq_);
    }
    for (auto& it : leftover)
      fail_batch_item(it, 1009, "ici server stopped");
    std::vector<IciConnPtr> conns;
    {
      std::lock_guard<std::mutex> g(conns_mu_);
      for (auto& kv : conns_) conns.push_back(kv.second);
      conns_.clear();
    }
    for (auto& c : conns) {
      c->closed.store(true, std::memory_order_release);
      c->wcv.notify_all();
      if (auto ch = c->client.lock())
        ch->fail_all(1009, "ici server stopped");
    }
  }

  int32_t dev() const { return dev_; }
  void set_handle(uint64_t h) { handle_ = h; }
  uint64_t handle() const { return handle_; }
  uint64_t requests() const { return requests_.load(); }

  void register_echo(const std::string& m) {
    std::lock_guard<std::mutex> g(mmu_);
    echo_methods_.insert({m, true});
  }

  void set_handler(py_ici_request_fn fn) {
    handler_.store(fn, std::memory_order_release);
  }

  void set_batch_handler(py_ici_batch_fn fn) {
    batch_handler_.store(fn, std::memory_order_release);
  }

  void set_batch_params(uint64_t max_batch, int64_t age_us) {
    if (max_batch > 0)
      batch_max_.store(max_batch, std::memory_order_relaxed);
    if (age_us >= 0)
      batch_age_ns_.store(age_us * 1000, std::memory_order_relaxed);
  }

  void batch_stats(uint64_t* upcalls, uint64_t* requests,
                   uint64_t* max_batch) const {
    *upcalls = upcalls_.load(std::memory_order_relaxed);
    *requests = upcall_reqs_.load(std::memory_order_relaxed);
    *max_batch = batch_max_seen_.load(std::memory_order_relaxed);
  }

  IciConnPtr accept(const IciChannelPtr& ch, int32_t client_dev,
                    int64_t window_bytes) {
    auto c = std::make_shared<IciConn>();
    c->id = next_conn_id_.fetch_add(1) + 1;
    c->client_dev = client_dev;
    c->client = ch;
    c->server = shared_from_this();
    c->window_bytes = window_bytes;
    c->window_left = window_bytes;
    std::lock_guard<std::mutex> g(conns_mu_);
    conns_[c->id] = c;
    return c;
  }

  void drop_conn(uint64_t id) {
    std::lock_guard<std::mutex> g(conns_mu_);
    conns_.erase(id);
  }

  bool stopped() const { return stop_.load(std::memory_order_acquire); }

  // Inline dispatch entry: runs on the caller's thread; returns the
  // frame's credits to the connection when the frame is consumed.
  void dispatch(IciMsg&& m) {
    IciConnPtr conn = m.conn;
    int64_t credits = m.wire_bytes;
    // request frame consumed: return its credits (the piggybacked-ACK
    // of the RDMA window; the reference replenishes on completion).
    // process() returns false when the frame moved into the Python
    // batch queue — the batch upcall returns the credits then.
    if (process(m)) conn->return_credits(credits);
  }

 private:
  void reply_error(const IciMsg& msg, uint64_t cid, uint64_t err,
                   const std::string& text) {
    if (auto ch = msg.conn->client.lock())
      ch->deliver(cid, err, text, "", "", {});
  }

  // Returns true when the frame's credits may be returned by the caller
  // (consumed inline); false when the frame moved into the batch queue.
  bool process(IciMsg& msg) {
    const uint8_t* p = (const uint8_t*)msg.bytes.data();
    size_t sz = msg.bytes.size();
    if (sz < kHeaderSize || memcmp(p, kMagic, 4) != 0) {
      ici_release_segs(msg.segs);
      return true;                    // malformed: drop (framing guard)
    }
    uint32_t meta_size = get_u32be(p + 4);
    uint32_t body_size = get_u32be(p + 8);
    if (kHeaderSize + (size_t)meta_size + body_size != sz) {
      ici_release_segs(msg.segs);
      return true;
    }
    RpcMeta meta;
    if (!decode_meta(p + kHeaderSize, p + kHeaderSize + meta_size, &meta)) {
      ici_release_segs(msg.segs);
      return true;
    }
    requests_.fetch_add(1, std::memory_order_relaxed);
    const uint8_t* body = p + kHeaderSize + meta_size;
    // body = payload + host-attachment bytes; attachment_size in the meta
    // counts host attachment bytes only (device bytes ride the sidecar)
    size_t att = std::min((size_t)meta.attachment_size, (size_t)body_size);
    size_t payload_len = body_size - att;
    std::string full = meta.request.service_name + "." +
                       meta.request.method_name;
    uint64_t cid = meta.correlation_id;
    bool is_echo;
    {
      std::lock_guard<std::mutex> g(mmu_);
      is_echo = echo_methods_.count(full) != 0;
    }
    if (is_echo) {
      // native echo tier: refs pass through toward the client (resident
      // refs = zero upcalls, the pure-HBM round trip)
      if (!ici_relocate_segs(msg.segs, msg.conn->client_dev)) {
        ici_release_segs(msg.segs);
        reply_error(msg, cid, 1009, "ici relocation failed");
        return true;
      }
      if (auto ch = msg.conn->client.lock()) {
        ch->deliver(cid, 0, "",
                    std::string((const char*)body, payload_len),
                    std::string((const char*)body + payload_len, att),
                    std::move(msg.segs));
      } else {
        ici_release_segs(msg.segs);
      }
      return true;
    }
    py_ici_batch_fn bh = batch_handler_.load(std::memory_order_acquire);
    py_ici_request_fn h = handler_.load(std::memory_order_acquire);
    if (bh != nullptr || h != nullptr) {
      // user-code tier: refs land resident on the SERVER device before
      // the handler sees them (the test contract: a handler observes its
      // attachment in local HBM)
      if (!ici_relocate_segs(msg.segs, dev_)) {
        ici_release_segs(msg.segs);
        reply_error(msg, cid, 1009, "ici relocation failed");
        return true;
      }
      uint64_t token = register_token(msg.conn, cid);
      if (bh != nullptr) {
        IciBatchItem item;
        item.token = token;
        item.method = std::move(full);
        item.payload_off = kHeaderSize + meta_size;
        item.payload_len = payload_len;
        item.att_len = att;
        item.log_id = meta.request.log_id;
        item.peer_dev = msg.conn->client_dev;
        item.priority = meta.request.priority;
        item.tenant = std::move(meta.request.tenant);
        item.deadline_left_ms = meta.request.deadline_left_ms;
        item.enq_ns = ici_now_ns();
        item.conn = msg.conn;
        item.wire_bytes = msg.wire_bytes;
        item.bytes = std::move(msg.bytes);
        item.segs = std::move(msg.segs);
        enqueue_batch(std::move(item));
        return false;
      }
      // legacy single-request upcall ABI (no batch handler installed)
      h(token, full.c_str(), body, payload_len, body + payload_len, att,
        msg.segs.data(), msg.segs.size(), meta.request.log_id,
        msg.conn->client_dev);
      // the upcall TOOK the refs (Python popped them into its IOBuf):
      // native custody ends without release
      msg.segs.clear();
      return true;
    }
    ici_release_segs(msg.segs);
    reply_error(msg, cid, 1002, "no method " + full);
    return true;
  }

  // ---- Python batch queue (the batched-GIL-crossing core) ------------
  // Arrival discipline: the first enqueuer becomes the DRAINER and loops
  // delivering batches until the queue is empty; later arrivals just
  // enqueue (their requests ride the drainer's next batch — that is the
  // amortization) unless the oldest queued request has aged past
  // batch_age_ns_, in which case the arrival STEALS the whole queue and
  // delivers it concurrently — p99 never pays more than the age bound
  // for batching, even with a drainer stuck in a slow inline handler.
  // An idle arrival is a batch of 1 delivered immediately: p50 pays no
  // batching delay at all.
  void enqueue_batch(IciBatchItem&& item) {
    std::vector<IciBatchItem> batch;
    bool owner = false;
    {
      std::lock_guard<std::mutex> g(bq_mu_);
      if (!bq_stopped_) {
        bq_.push_back(std::move(item));
        if (!bq_draining_) {
          bq_draining_ = true;
          owner = true;
          take_batch_locked(&batch);
        } else if (ici_now_ns() - bq_.front().enq_ns >=
                   batch_age_ns_.load(std::memory_order_relaxed)) {
          take_batch_locked(&batch);   // steal: concurrent delivery
        } else {
          return;                      // the active drainer will take it
        }
      }
    }
    if (!owner && batch.empty()) {
      // enqueued after stop: fail it here (stop's sweep already ran)
      fail_batch_item(item, 1009, "ici server stopped");
      return;
    }
    for (;;) {
      deliver_batch(batch);
      if (!owner) return;
      {
        std::lock_guard<std::mutex> g(bq_mu_);
        if (bq_.empty() || bq_stopped_) {
          bq_draining_ = false;
          return;
        }
        batch.clear();
        take_batch_locked(&batch);
      }
    }
  }

  // fablint: lock-held(bq_mu_)
  void take_batch_locked(std::vector<IciBatchItem>* out) {
    uint64_t max_n = batch_max_.load(std::memory_order_relaxed);
    while (!bq_.empty() && out->size() < max_n) {
      out->push_back(std::move(bq_.front()));
      bq_.pop_front();
    }
  }

  void deliver_batch(std::vector<IciBatchItem>& batch) {
    py_ici_batch_fn bh = batch_handler_.load(std::memory_order_acquire);
    if (bh == nullptr) {               // detached mid-flight
      for (auto& it : batch)
        fail_batch_item(it, 1009, "ici batch handler detached");
      return;
    }
    std::vector<IciReqC> reqs;
    reqs.reserve(batch.size());
    for (auto& it : batch) {
      const uint8_t* base = (const uint8_t*)it.bytes.data();
      IciReqC r;
      r.token = it.token;
      r.method = it.method.c_str();
      r.payload = base + it.payload_off;
      r.payload_len = it.payload_len;
      r.att_host = base + it.payload_off + it.payload_len;
      r.att_host_len = it.att_len;
      r.log_id = it.log_id;
      r.recv_ns = it.enq_ns;
      r.peer_dev = it.peer_dev;
      r._pad = 0;
      r.tenant = it.tenant.empty() ? nullptr : it.tenant.c_str();
      r.deadline_left_ms = it.deadline_left_ms;
      r.priority = (int32_t)it.priority;
      r._pad2 = 0;
      r.att_handle = 0;
      r.seg0_key = 0;
      r.seg0_nbytes = 0;
      r.seg0_dev = 0;
      r._pad3 = 0;
      if (it.att_len == 0 && !it.segs.empty()) {
        // native custody: the seg list PARKS in the att table; Python
        // receives a ready handle + an inline mirror of segs[0] and
        // never walks the list on the hot path.  Host-mixed
        // attachments keep the legacy take-during-upcall walk (the
        // host spans interleave with device segs positionally).
        IciAttEntry* e = nullptr;
        r.att_handle = ici_att_register(std::move(it.segs), &e);
        r.segs = e->segs.data();     // heap-stable while the handle lives
        r.nsegs = e->segs.size();
        r.seg0_key = e->segs[0].key;
        r.seg0_nbytes = e->segs[0].nbytes;
        r.seg0_dev = e->segs[0].dev;
      } else {
        r.segs = it.segs.data();
        r.nsegs = it.segs.size();
        if (!it.segs.empty()) {
          r.seg0_key = it.segs[0].key;
          r.seg0_nbytes = it.segs[0].nbytes;
          r.seg0_dev = it.segs[0].dev;
        }
      }
      reqs.push_back(r);
    }
    upcalls_.fetch_add(1, std::memory_order_relaxed);
    upcall_reqs_.fetch_add(batch.size(), std::memory_order_relaxed);
    uint64_t n = batch.size();
    uint64_t seen = batch_max_seen_.load(std::memory_order_relaxed);
    while (n > seen && !batch_max_seen_.compare_exchange_weak(
                           seen, n, std::memory_order_relaxed)) {
    }
    bh(reqs.data(), reqs.size());
    // the upcall TOOK every request's seg keys (Python popped them into
    // its IOBufs): native custody ends without release.  Credits return
    // now — the frames are consumed.
    for (auto& it : batch) {
      it.segs.clear();
      it.conn->return_credits(it.wire_bytes);
    }
  }

  void fail_batch_item(IciBatchItem& it, uint64_t err, const char* text);

  uint64_t register_token(const IciConnPtr& conn, uint64_t cid);

  int32_t dev_;
  uint64_t handle_ = 0;
  std::atomic<bool> stop_{false};
  std::mutex conns_mu_;
  std::unordered_map<uint64_t, IciConnPtr> conns_;
  std::atomic<uint64_t> next_conn_id_{0};
  std::mutex mmu_;
  std::unordered_map<std::string, bool> echo_methods_;
  std::atomic<py_ici_request_fn> handler_{nullptr};
  std::atomic<py_ici_batch_fn> batch_handler_{nullptr};
  std::atomic<uint64_t> requests_{0};
  // batch queue state (guarded by bq_mu_; see enqueue_batch)
  std::mutex bq_mu_;
  std::deque<IciBatchItem> bq_;
  bool bq_draining_ = false;
  bool bq_stopped_ = false;
  std::atomic<uint64_t> batch_max_{64};
  std::atomic<int64_t> batch_age_ns_{50 * 1000};   // ~50 us steal bound
  std::atomic<uint64_t> upcalls_{0};
  std::atomic<uint64_t> upcall_reqs_{0};
  std::atomic<uint64_t> batch_max_seen_{0};
};
using IciServerPtr = std::shared_ptr<IciServer>;

struct IciPending {
  std::weak_ptr<IciConn> conn;
  uint64_t cid = 0;
};

static std::mutex g_ici_mu;
// Leaked on purpose: these registries own IciServer/IciChannel objects
// whose destructors join (or abort on) live dispatcher threads — running
// them from static teardown races whatever threads exit() left alive
// (the abort-at-exit flake in the cross-process streaming test).  See
// fabric.cpp's g_conns note; brpc_tpu_fab_quiesce / Python's atexit
// quiesce provide the DETERMINISTIC shutdown path instead.
static auto& g_ici_listeners =
    *new std::unordered_map<int32_t, IciServerPtr>();
static auto& g_ici_servers =
    *new std::unordered_map<uint64_t, IciServerPtr>();  // by handle
static auto& g_ici_channels =
    *new std::unordered_map<uint64_t, std::pair<IciChannelPtr, IciConnPtr>>();
static std::mutex g_ici_tokens_mu;
static auto& g_ici_tokens = *new nbase::FlatMap64<IciPending>();
static std::atomic<uint64_t> g_ici_next_token{1};

uint64_t IciServer::register_token(const IciConnPtr& conn, uint64_t cid) {
  uint64_t token = g_ici_next_token.fetch_add(1);
  std::lock_guard<std::mutex> g(g_ici_tokens_mu);
  g_ici_tokens[token] = IciPending{conn, cid};
  return token;
}

// Drop path for a queued Python-tier request that will never reach the
// upcall (server stopped / handler detached): release ref custody, take
// the token so a late respond can't double-deliver, error the caller,
// and return the frame's credits.
void IciServer::fail_batch_item(IciBatchItem& it, uint64_t err,
                                const char* text) {
  ici_release_segs(it.segs);
  it.segs.clear();
  IciPending pr;
  bool had = false;
  {
    std::lock_guard<std::mutex> g(g_ici_tokens_mu);
    had = g_ici_tokens.take(it.token, &pr);
  }
  if (had) {
    if (auto conn = pr.conn.lock()) {
      if (auto ch = conn->client.lock())
        ch->deliver(pr.cid, err, text, "", "", {});
    }
  }
  if (it.conn != nullptr) it.conn->return_credits(it.wire_bytes);
}

// The client-side unary call: window reservation → TRPC frame encode →
// relocation toward the server → queue hop → slot wait (spin, then park).
static uint64_t ici_do_call(const IciChannelPtr& ch, const IciConnPtr& conn,
                            const char* service_dot_method,
                            const uint8_t* req, uint64_t req_len,
                            const uint8_t* att_host, uint64_t att_host_len,
                            std::vector<IciSegC> segs, int64_t timeout_us,
                            IciSlot* out, std::string* err_text,
                            int64_t priority_wire = 0,
                            const char* tenant = nullptr,
                            int64_t deadline_left_ms = 0) {
  IciServerPtr srv = conn->server;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::microseconds(timeout_us > 0 ? timeout_us
                                                           : (int64_t)1e12);
  // ---- encode the frame (the same codec the TCP path uses) ----
  RpcMeta meta;
  meta.request.present = true;
  const char* dot = strrchr(service_dot_method, '.');
  if (dot == nullptr) {
    meta.request.method_name = service_dot_method;
  } else {
    meta.request.service_name.assign(service_dot_method,
                                     dot - service_dot_method);
    meta.request.method_name = dot + 1;
  }
  uint64_t cid;
  IciSlotPtr slot = ch->make_slot(&cid);
  meta.correlation_id = cid;
  meta.attachment_size = att_host_len;
  if (timeout_us > 0) meta.request.timeout_ms = (uint64_t)(timeout_us / 1000);
  if (priority_wire > 0) meta.request.priority = (uint64_t)priority_wire;
  if (tenant != nullptr && tenant[0] != '\0') meta.request.tenant = tenant;
  if (deadline_left_ms > 0)
    meta.request.deadline_left_ms = (uint64_t)deadline_left_ms;
  std::string frame = pack_head(meta, req_len + att_host_len);
  if (req_len) frame.append((const char*)req, req_len);
  if (att_host_len) frame.append((const char*)att_host, att_host_len);
  int64_t dev_bytes = 0;
  for (const auto& s : segs)
    if (s.is_dev) dev_bytes += (int64_t)s.nbytes;
  int64_t wire = (int64_t)frame.size() + dev_bytes;

  // ---- window reservation (check-and-reserve under one lock — the
  // AppendIfNotFull discipline, stream.cpp:274) ----
  if (wire > conn->window_bytes) {
    // can NEVER fit: fail now instead of burning the whole rpc deadline
    ch->erase_slot(cid);
    ici_release_segs(segs);
    *err_text = "frame larger than the ici send window";
    return 1011;  // EOVERCROWDED (rpc/errors.py)
  }
  {
    std::unique_lock<std::mutex> g(conn->wmu);
    while (conn->window_left < wire) {
      if (conn->closed.load(std::memory_order_acquire) || srv->stopped()) {
        g.unlock();
        ch->erase_slot(cid);
        ici_release_segs(segs);
        *err_text = "ici peer closed while window full";
        return 1009;
      }
      if (nbase::cv_wait_until(conn->wcv, g, deadline)
              == std::cv_status::timeout) {
        g.unlock();
        ch->erase_slot(cid);
        ici_release_segs(segs);
        *err_text = "ici send window stalled (peer not consuming)";
        return 1011;  // EOVERCROWDED (rpc/errors.py)
      }
    }
    conn->window_left -= wire;
  }
  if (conn->closed.load(std::memory_order_acquire) || srv->stopped()) {
    ch->erase_slot(cid);
    ici_release_segs(segs);
    conn->return_credits(wire);
    *err_text = "ici peer closed";
    return 1009;
  }
  // ---- relocate toward the server's device (HBM→HBM; resident = noop),
  // then hand the frame to the server queue ----
  if (!ici_relocate_segs(segs, srv->dev())) {
    ch->erase_slot(cid);
    ici_release_segs(segs);
    conn->return_credits(wire);
    *err_text = "ici relocation failed";
    return 1009;
  }
  IciMsg msg;
  msg.conn = conn;
  msg.cid = cid;
  msg.bytes = std::move(frame);
  msg.segs = std::move(segs);
  msg.wire_bytes = wire;
  srv->dispatch(std::move(msg));   // inline: caller is the IO thread

  // ---- wait.  The native echo tier already delivered synchronously
  // (the common case: done before we get here, zero parks).  A Python
  // handler completes from its tasklet thread → park on the condvar.
  if (!slot->done.load(std::memory_order_acquire)) {
    std::unique_lock<std::mutex> g(slot->mu);
    while (!slot->done.load(std::memory_order_acquire)) {
      if (nbase::cv_wait_until(slot->cv, g, deadline)
              == std::cv_status::timeout) {
        // the deadline and the response can race: `done` is the truth,
        // re-checked under the lock.  Abandoning under the SAME lock
        // guarantees a later deliver() sees it and releases custody.
        if (slot->done.load(std::memory_order_acquire)) break;
        slot->abandoned = true;
        g.unlock();
        ch->erase_slot(cid);
        *err_text = "rpc timeout";
        return 1008;
      }
    }
  }
  {
    std::lock_guard<std::mutex> g(slot->mu);
    out->error_code = slot->error_code;
    out->error_text = std::move(slot->error_text);
    out->payload = std::move(slot->payload);
    out->att_host = std::move(slot->att_host);
    out->segs = std::move(slot->segs);
    out->retry_after_ms = slot->retry_after_ms;
  }
  ch->erase_slot(cid);       // waiter owns slot lifetime (see deliver)
  *err_text = out->error_text;
  return out->error_code;
}

// ====================================================================
// handle registries.  shared_ptr ownership: a stop/close erases the map
// entry, but callers that already resolved the handle keep the object
// alive until they return — no free-under-caller (the registry is the
// versioned-id check; the shared_ptr is the reference count the C ABI
// can't express).
// ====================================================================

static std::mutex g_handles_mu;
// Leaked on purpose — see the g_ici_listeners note above: destructing
// NativeServer/NativeChannel from static teardown joins epoll/reader
// threads concurrently with process exit, the abort-at-exit flake.
static auto& g_servers =
    *new std::unordered_map<uint64_t, std::shared_ptr<NativeServer>>();
static auto& g_channels =
    *new std::unordered_map<uint64_t, std::shared_ptr<NativeChannel>>();
static auto& g_pools =
    *new std::unordered_map<uint64_t, std::shared_ptr<NativePool>>();
static std::atomic<uint64_t> g_next_handle{1};

static std::shared_ptr<NativeServer> find_server(uint64_t h) {
  std::lock_guard<std::mutex> g(g_handles_mu);
  auto it = g_servers.find(h);
  return it == g_servers.end() ? nullptr : it->second;
}

static std::shared_ptr<NativeChannel> find_channel(uint64_t h) {
  std::lock_guard<std::mutex> g(g_handles_mu);
  auto it = g_channels.find(h);
  return it == g_channels.end() ? nullptr : it->second;
}

static std::shared_ptr<NativePool> find_pool(uint64_t h) {
  std::lock_guard<std::mutex> g(g_handles_mu);
  auto it = g_pools.find(h);
  return it == g_pools.end() ? nullptr : it->second;
}

// Shared sync-call → C-ABI-outputs marshalling (channel and pool paths).
static uint64_t call_and_fill_outputs(
    const std::shared_ptr<NativeChannel>& c, const char* method,
    const uint8_t* req, uint64_t req_len, const uint8_t* att,
    uint64_t att_len, int64_t timeout_us, uint8_t** resp_out,
    uint64_t* resp_len, uint8_t** att_out, uint64_t* att_out_len,
    char** err_text_out) {
  CallResult out;
  std::string err_text;
  uint64_t rc = c->call(method, req, req_len, att, att_len, timeout_us,
                        &out, &err_text);
  if (out.p_len) {
    *resp_out = (uint8_t*)malloc(out.p_len);
    memcpy(*resp_out, out.payload(), out.p_len);
    *resp_len = out.p_len;
  }
  if (out.a_len) {
    *att_out = (uint8_t*)malloc(out.a_len);
    memcpy(*att_out, out.attachment(), out.a_len);
    *att_out_len = out.a_len;
  }
  if (!err_text.empty()) {
    *err_text_out = (char*)malloc(err_text.size() + 1);
    memcpy(*err_text_out, err_text.c_str(), err_text.size() + 1);
  }
  return rc;
}

}  // namespace nrpc

// ====================================================================
// C ABI
// ====================================================================

extern "C" {

uint64_t brpc_tpu_nserver_start(int port) {
  auto s = std::make_shared<nrpc::NativeServer>();
  if (!s->start(port)) return 0;
  uint64_t h = nrpc::g_next_handle.fetch_add(1);
  s->set_handle(h);
  std::lock_guard<std::mutex> g(nrpc::g_handles_mu);
  nrpc::g_servers[h] = s;
  return h;
}

int brpc_tpu_nserver_port(uint64_t h) {
  auto s = nrpc::find_server(h);
  return s == nullptr ? -1 : s->port();
}

int brpc_tpu_nserver_register_echo(uint64_t h, const char* full_method) {
  auto s = nrpc::find_server(h);
  if (s == nullptr) return -1;
  s->register_echo(full_method);
  return 0;
}

int brpc_tpu_nserver_set_handler(uint64_t h, nrpc::py_request_fn fn) {
  auto s = nrpc::find_server(h);
  if (s == nullptr) return -1;
  s->set_py_handler(fn);
  return 0;
}

uint64_t brpc_tpu_nserver_requests(uint64_t h) {
  auto s = nrpc::find_server(h);
  return s == nullptr ? 0 : s->requests();
}

int brpc_tpu_nserver_respond(uint64_t token, uint64_t err,
                             const char* err_text, const uint8_t* data,
                             uint64_t len, const uint8_t* att,
                             uint64_t att_len) {
  nrpc::PendingReply pr;
  {
    std::lock_guard<std::mutex> g(nrpc::g_tokens_mu);
    if (!nrpc::g_tokens.take(token, &pr)) return -1;
  }
  // resolve by handle: a stopped server no longer resolves (its tokens
  // were purged too; this is belt-and-braces for the in-between window)
  auto s = nrpc::find_server(pr.server_handle);
  if (s == nullptr) return -1;
  bool ok = s->respond(pr.conn_id, pr.cid, err, err_text ? err_text : "",
                       data, len, att, att_len);
  return ok ? 0 : -2;
}

void brpc_tpu_nserver_stop(uint64_t h) {
  std::shared_ptr<nrpc::NativeServer> s;
  {
    std::lock_guard<std::mutex> g(nrpc::g_handles_mu);
    auto it = nrpc::g_servers.find(h);
    if (it == nrpc::g_servers.end()) return;
    s = it->second;
    nrpc::g_servers.erase(it);
  }
  s->stop();   // frees when the last concurrent resolver drops its ref
}

uint64_t brpc_tpu_nchannel_connect(const char* host, int port) {
  auto c = std::make_shared<nrpc::NativeChannel>();
  if (!c->connect_to(host, port)) return 0;
  uint64_t h = nrpc::g_next_handle.fetch_add(1);
  std::lock_guard<std::mutex> g(nrpc::g_handles_mu);
  nrpc::g_channels[h] = c;
  return h;
}

// Returns error code (0 ok).  Response/attachment/error-text returned as
// malloc'd buffers the caller frees with brpc_tpu_buf_free.
uint64_t brpc_tpu_nchannel_call(uint64_t h, const char* method,
                                const uint8_t* req, uint64_t req_len,
                                const uint8_t* att, uint64_t att_len,
                                int64_t timeout_us, uint8_t** resp_out,
                                uint64_t* resp_len, uint8_t** att_out,
                                uint64_t* att_out_len, char** err_text_out) {
  *resp_out = nullptr; *resp_len = 0;
  *att_out = nullptr; *att_out_len = 0;
  *err_text_out = nullptr;
  auto c = nrpc::find_channel(h);    // shared ref: close can't free mid-call
  if (c == nullptr) return 1009;
  return nrpc::call_and_fill_outputs(c, method, req, req_len, att, att_len,
                                     timeout_us, resp_out, resp_len,
                                     att_out, att_out_len, err_text_out);
}

// Async call: `cb` fires exactly once from the channel's reader thread
// (response, timeout, or failure).  Returns 0 when the request was
// written; on synchronous failure the callback has already fired.
uint64_t brpc_tpu_nchannel_call_async(uint64_t h, const char* method,
                                      const uint8_t* req, uint64_t req_len,
                                      const uint8_t* att, uint64_t att_len,
                                      int64_t timeout_us,
                                      nrpc::nrpc_async_cb cb, void* user) {
  auto c = nrpc::find_channel(h);
  if (c == nullptr) {
    cb(user, 1009, "channel not found", nullptr, 0, nullptr, 0);
    return 1009;
  }
  return c->call_async(method, req, req_len, att, att_len, timeout_us, cb,
                       user);
}

// ---- pooled multi-connection channel ----

uint64_t brpc_tpu_npool_connect(const char* host, int port, int nconns) {
  auto p = std::make_shared<nrpc::NativePool>();
  if (!p->connect_to(host, port, nconns)) return 0;
  uint64_t h = nrpc::g_next_handle.fetch_add(1);
  std::lock_guard<std::mutex> g(nrpc::g_handles_mu);
  nrpc::g_pools[h] = p;
  return h;
}

uint64_t brpc_tpu_npool_call(uint64_t h, const char* method,
                             const uint8_t* req, uint64_t req_len,
                             const uint8_t* att, uint64_t att_len,
                             int64_t timeout_us, uint8_t** resp_out,
                             uint64_t* resp_len, uint8_t** att_out,
                             uint64_t* att_out_len, char** err_text_out) {
  *resp_out = nullptr; *resp_len = 0;
  *att_out = nullptr; *att_out_len = 0;
  *err_text_out = nullptr;
  auto p = nrpc::find_pool(h);
  if (p == nullptr) return 1009;
  return nrpc::call_and_fill_outputs(p->pick(), method, req, req_len, att,
                                     att_len, timeout_us, resp_out,
                                     resp_len, att_out, att_out_len,
                                     err_text_out);
}

void brpc_tpu_npool_close(uint64_t h) {
  std::shared_ptr<nrpc::NativePool> p;
  {
    std::lock_guard<std::mutex> g(nrpc::g_handles_mu);
    auto it = nrpc::g_pools.find(h);
    if (it == nrpc::g_pools.end()) return;
    p = it->second;
    nrpc::g_pools.erase(it);
  }
  p->close_all();
}

void brpc_tpu_buf_free(void* p) { free(p); }

void brpc_tpu_nchannel_close(uint64_t h) {
  std::shared_ptr<nrpc::NativeChannel> c;
  {
    std::lock_guard<std::mutex> g(nrpc::g_handles_mu);
    auto it = nrpc::g_channels.find(h);
    if (it == nrpc::g_channels.end()) return;
    c = it->second;
    nrpc::g_channels.erase(it);
  }
  c->close_ch();   // destructor (and the fd close) runs when the last
                   // in-flight call drops its reference
}

// Full-native-stack echo benchmark: channel → frame → epoll server →
// dispatch → response → correlation wake, all in this library.  Measures
// per-call round trips the way example/echo_c++'s client does.  Returns
// p50 ns (-1 failure).
int64_t brpc_tpu_native_rpc_echo_p50_ns(int iters, int payload_len) {
  uint64_t sh = brpc_tpu_nserver_start(0);
  if (sh == 0) return -1;
  brpc_tpu_nserver_register_echo(sh, "EchoService.Echo");
  int port = brpc_tpu_nserver_port(sh);
  uint64_t ch = brpc_tpu_nchannel_connect("127.0.0.1", port);
  if (ch == 0) {
    brpc_tpu_nserver_stop(sh);
    return -1;
  }
  std::string payload(payload_len, 'x');
  std::vector<int64_t> lat;
  lat.reserve(iters);
  auto now_ns = [] {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
  };
  auto c = nrpc::find_channel(ch);
  for (int i = 0; i < iters + 50; ++i) {
    nrpc::CallResult out;
    std::string err;
    int64_t t0 = now_ns();
    uint64_t rc = c->call("EchoService.Echo", payload.data(), payload.size(),
                          nullptr, 0, 5 * 1000 * 1000, &out, &err);
    int64_t t1 = now_ns();
    if (rc != 0 || out.p_len != payload.size()) {
      brpc_tpu_nchannel_close(ch);
      brpc_tpu_nserver_stop(sh);
      return -1;
    }
    if (i >= 50) lat.push_back(t1 - t0);
  }
  brpc_tpu_nchannel_close(ch);
  brpc_tpu_nserver_stop(sh);
  std::sort(lat.begin(), lat.end());
  return lat[lat.size() / 2];
}

// Multi-threaded native QPS benchmark (the multi_threaded_echo_c++ config):
// `threads` client threads, one connection each, run for duration_ms.
double brpc_tpu_native_rpc_qps(int threads, int duration_ms,
                               int payload_len) {
  uint64_t sh = brpc_tpu_nserver_start(0);
  if (sh == 0) return -1.0;
  brpc_tpu_nserver_register_echo(sh, "EchoService.Echo");
  int port = brpc_tpu_nserver_port(sh);
  std::atomic<uint64_t> count{0};
  std::atomic<bool> stop{false};
  std::vector<std::thread> ts;
  for (int t = 0; t < threads; ++t) {
    ts.emplace_back([&, t] {
      uint64_t ch = brpc_tpu_nchannel_connect("127.0.0.1", port);
      if (ch == 0) return;
      auto c = nrpc::find_channel(ch);
      std::string payload(payload_len, 'x');
      while (!stop.load(std::memory_order_relaxed)) {
        nrpc::CallResult out;
        std::string err;
        uint64_t rc = c->call("EchoService.Echo", payload.data(),
                              payload.size(), nullptr, 0, 5 * 1000 * 1000,
                              &out, &err);
        if (rc == 0) count.fetch_add(1, std::memory_order_relaxed);
      }
      brpc_tpu_nchannel_close(ch);
    });
  }
  auto t0 = std::chrono::steady_clock::now();
  std::this_thread::sleep_for(std::chrono::milliseconds(duration_ms));
  stop.store(true);
  for (auto& th : ts) th.join();
  double secs = std::chrono::duration<double>(
                    std::chrono::steady_clock::now() - t0)
                    .count();
  brpc_tpu_nserver_stop(sh);
  return count.load() / secs;
}

// ---- ici:// plane ----

void brpc_tpu_ici_set_hooks(nrpc::py_relocate_fn relocate,
                            nrpc::py_release_fn release) {
  nrpc::g_ici_relocate.store(relocate, std::memory_order_release);
  nrpc::g_ici_release.store(release, std::memory_order_release);
}

// Returns a server handle; 0 when the device id is already listening.
// The Python handler (may be null for echo-only servers) is installed
// BEFORE the listener becomes visible — no half-initialized window.
uint64_t brpc_tpu_ici_listen(int32_t dev, nrpc::py_ici_request_fn handler) {
  auto s = std::make_shared<nrpc::IciServer>(dev, handler);
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
    if (nrpc::g_ici_listeners.count(dev)) return 0;
    uint64_t h = nrpc::g_next_handle.fetch_add(1);
    s->set_handle(h);
    nrpc::g_ici_listeners[dev] = s;
    nrpc::g_ici_servers[h] = s;
  }
  s->start();
  return s->handle();
}

int brpc_tpu_ici_register_echo(uint64_t h, const char* full_method) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_servers.find(h);
  if (it == nrpc::g_ici_servers.end()) return -1;
  it->second->register_echo(full_method);
  return 0;
}

int brpc_tpu_ici_set_handler(uint64_t h, nrpc::py_ici_request_fn fn) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_servers.find(h);
  if (it == nrpc::g_ici_servers.end()) return -1;
  it->second->set_handler(fn);
  return 0;
}

// Batched one-struct upcall variant of brpc_tpu_ici_listen: the handler
// receives (IciReqC*, n) — see the ABI comment at IciReqC.
uint64_t brpc_tpu_ici_listen_batch(int32_t dev, nrpc::py_ici_batch_fn fn) {
  uint64_t h = brpc_tpu_ici_listen(dev, nullptr);
  if (h == 0) return 0;
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_servers.find(h);
  if (it != nrpc::g_ici_servers.end()) it->second->set_batch_handler(fn);
  return h;
}

// max_batch <= 0 keeps the current cap; age_us < 0 keeps the current
// steal bound (age_us == 0 means steal-always: every arrival delivers
// concurrently, i.e. batching effectively off past the first drainer).
int brpc_tpu_ici_set_batch_params(uint64_t h, int64_t max_batch,
                                  int64_t age_us) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_servers.find(h);
  if (it == nrpc::g_ici_servers.end()) return -1;
  it->second->set_batch_params(max_batch > 0 ? (uint64_t)max_batch : 0,
                               age_us);
  return 0;
}

int brpc_tpu_ici_batch_stats(uint64_t h, uint64_t* upcalls,
                             uint64_t* requests, uint64_t* max_batch) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_servers.find(h);
  if (it == nrpc::g_ici_servers.end()) return -1;
  it->second->batch_stats(upcalls, requests, max_batch);
  return 0;
}

uint64_t brpc_tpu_ici_requests(uint64_t h) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_servers.find(h);
  return it == nrpc::g_ici_servers.end() ? 0 : it->second->requests();
}

// 1 when a native listener exists for this device id.
int brpc_tpu_ici_has_listener(int32_t dev) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  return nrpc::g_ici_listeners.count(dev) ? 1 : 0;
}

void brpc_tpu_ici_unlisten(uint64_t h) {
  nrpc::IciServerPtr s;
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
    auto it = nrpc::g_ici_servers.find(h);
    if (it == nrpc::g_ici_servers.end()) return;
    s = it->second;
    nrpc::g_ici_servers.erase(it);
    nrpc::g_ici_listeners.erase(s->dev());
  }
  {
    // purge this server's in-flight Python-handler tokens
    std::lock_guard<std::mutex> g(nrpc::g_ici_tokens_mu);
    std::vector<uint64_t> purge;
    nrpc::g_ici_tokens.for_each([&](uint64_t t, nrpc::IciPending& pr) {
      auto conn = pr.conn.lock();
      if (conn == nullptr || conn->server == s) purge.push_back(t);
    });
    for (uint64_t t : purge) nrpc::g_ici_tokens.erase(t);
  }
  s->stop();
}

// Connect local_dev → the native listener at remote_dev; returns a
// channel handle (0 = no listener).
uint64_t brpc_tpu_ici_connect(int32_t local_dev, int32_t remote_dev,
                              int64_t window_bytes) {
  nrpc::IciServerPtr srv;
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
    auto it = nrpc::g_ici_listeners.find(remote_dev);
    if (it == nrpc::g_ici_listeners.end()) return 0;
    srv = it->second;
  }
  auto ch = std::make_shared<nrpc::IciChannel>(local_dev, remote_dev);
  auto conn = srv->accept(ch, local_dev,
                          window_bytes > 0 ? window_bytes : (4 << 20));
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  uint64_t h = nrpc::g_next_handle.fetch_add(1);
  nrpc::g_ici_channels[h] = {ch, conn};
  return h;
}

void brpc_tpu_ici_close(uint64_t h) {
  std::pair<nrpc::IciChannelPtr, nrpc::IciConnPtr> entry;
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
    auto it = nrpc::g_ici_channels.find(h);
    if (it == nrpc::g_ici_channels.end()) return;
    entry = it->second;
    nrpc::g_ici_channels.erase(it);
  }
  entry.second->closed.store(true, std::memory_order_release);
  entry.second->server->drop_conn(entry.second->id);
  entry.first->fail_all(1009, "channel closed");
}

int64_t brpc_tpu_ici_window_left(uint64_t h) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
  auto it = nrpc::g_ici_channels.find(h);
  if (it == nrpc::g_ici_channels.end()) return -1;
  std::lock_guard<std::mutex> wg(it->second.second->wmu);
  return it->second.second->window_left;
}

// Single-output-struct out-block for the unary ici call (see call2/call4):
// one reusable pointer instead of seven byref temporaries.
struct IciCallOut {
  uint8_t* resp;
  uint64_t resp_len;
  uint8_t* att;
  uint64_t att_len;
  nrpc::IciSegC* segs;
  uint64_t nsegs;
  char* err_text;
  uint64_t retry_after_ms;   // admission shed hint on ELIMIT rejections
  // native custody outputs (appended, ISSUE 12; filled by call4 only):
  // nonzero att_handle parks the response seg list in the att table —
  // the caller wraps it lazily and exits custody exactly once (take at
  // materialization / dispose when the view dies).  seg0_* mirrors the
  // first seg inline; for the dominant 1-seg shape segs stays NULL
  // (nothing to free), >1 segs are additionally malloc'd into segs so
  // the caller can read metadata without another crossing.
  uint64_t att_handle;
  uint64_t seg0_key;
  uint64_t seg0_nbytes;
  int32_t seg0_dev;
  int32_t _pad;
};

// Shared unary-call body: outputs are malloc'd (brpc_tpu_buf_free);
// response device refs land in out->segs (caller takes their keys).
static uint64_t ici_call_fill(uint64_t h, const char* method,
                              const uint8_t* req, uint64_t req_len,
                              const uint8_t* att_host,
                              uint64_t att_host_len,
                              const nrpc::IciSegC* segs, uint64_t nsegs,
                              int64_t timeout_us, int64_t priority_wire,
                              const char* tenant, int64_t deadline_left_ms,
                              IciCallOut* o, int want_handle = 0) {
  memset(o, 0, sizeof(*o));
  std::pair<nrpc::IciChannelPtr, nrpc::IciConnPtr> entry;
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
    auto it = nrpc::g_ici_channels.find(h);
    if (it != nrpc::g_ici_channels.end()) entry = it->second;
  }
  std::vector<nrpc::IciSegC> seg_vec(segs, segs + nsegs);
  if (entry.first == nullptr) {
    nrpc::ici_release_segs(seg_vec);
    return 1009;
  }
  nrpc::IciSlot out;
  std::string err_text;
  uint64_t rc = nrpc::ici_do_call(entry.first, entry.second, method, req,
                                  req_len, att_host, att_host_len,
                                  std::move(seg_vec), timeout_us, &out,
                                  &err_text, priority_wire, tenant,
                                  deadline_left_ms);
  if (!out.payload.empty()) {
    o->resp = (uint8_t*)malloc(out.payload.size());
    memcpy(o->resp, out.payload.data(), out.payload.size());
    o->resp_len = out.payload.size();
  }
  if (!out.att_host.empty()) {
    o->att = (uint8_t*)malloc(out.att_host.size());
    memcpy(o->att, out.att_host.data(), out.att_host.size());
    o->att_len = out.att_host.size();
  }
  if (want_handle && rc != 0 && !out.segs.empty()) {
    // handle-mode error path: a handler that failed the RPC may still
    // have shipped response segs — release them HERE so the Python
    // caller's error path needs no custody walk at all
    nrpc::ici_release_segs(out.segs);
    out.segs.clear();
  }
  if (!out.segs.empty()) {
    if (want_handle && out.att_host.empty()) {
      // native custody: park the seg list under a handle; the caller
      // builds a lazy view.  seg0 rides inline; >1 segs additionally
      // get the malloc'd metadata copy (the caller reads it during
      // THIS call — it is freed with the other outputs).
      o->seg0_key = out.segs[0].key;
      o->seg0_nbytes = out.segs[0].nbytes;
      o->seg0_dev = out.segs[0].dev;
      o->nsegs = out.segs.size();
      if (out.segs.size() > 1) {
        o->segs = (nrpc::IciSegC*)malloc(out.segs.size() *
                                         sizeof(nrpc::IciSegC));
        memcpy(o->segs, out.segs.data(),
               out.segs.size() * sizeof(nrpc::IciSegC));
      }
      o->att_handle = nrpc::ici_att_register(std::move(out.segs));
    } else {
      o->segs = (nrpc::IciSegC*)malloc(out.segs.size() *
                                       sizeof(nrpc::IciSegC));
      memcpy(o->segs, out.segs.data(),
             out.segs.size() * sizeof(nrpc::IciSegC));
      o->nsegs = out.segs.size();
    }
  }
  if (!err_text.empty()) {
    o->err_text = (char*)malloc(err_text.size() + 1);
    memcpy(o->err_text, err_text.c_str(), err_text.size() + 1);
  }
  o->retry_after_ms = out.retry_after_ms;
  return rc;
}

// The smoke's entry (native/ici_smoke.cpp): no admission meta, owned seg
// copies on the response.
uint64_t brpc_tpu_ici_call2(uint64_t h, const char* method,
                            const uint8_t* req, uint64_t req_len,
                            const uint8_t* att_host, uint64_t att_host_len,
                            const nrpc::IciSegC* segs, uint64_t nsegs,
                            int64_t timeout_us, IciCallOut* out) {
  return ici_call_fill(h, method, req, req_len, att_host, att_host_len,
                       segs, nsegs, timeout_us, 0, nullptr, 0, out);
}

// call2 + admission-control metadata — wire-encoded priority (0 = unset,
// 1..N = band 0..N-1), tenant, and the sender's remaining deadline
// budget; out->retry_after_ms carries the shed hint back on ELIMIT — and
// native att custody on the RESPONSE: device-only response attachments
// come back as out->att_handle (+ seg0 inline; >1 segs also malloc'd as
// metadata) instead of owned seg copies the caller must walk and take.
// Error-path response segs are released natively.
uint64_t brpc_tpu_ici_call4(uint64_t h, const char* method,
                            const uint8_t* req, uint64_t req_len,
                            const uint8_t* att_host, uint64_t att_host_len,
                            const nrpc::IciSegC* segs, uint64_t nsegs,
                            int64_t timeout_us, int64_t priority_wire,
                            const char* tenant, int64_t deadline_left_ms,
                            IciCallOut* out) {
  return ici_call_fill(h, method, req, req_len, att_host, att_host_len,
                       segs, nsegs, timeout_us, priority_wire, tenant,
                       deadline_left_ms, out, /*want_handle=*/1);
}

// ---- native att custody handle ops (ISSUE 12) ----
// Exactly-one-exit per handle: pass-back (IciRespC.att_handle), take,
// or dispose.  Each op consumes the handle.

// Python assumed custody of every key in the entry (it pulled them
// from its registry itself): drop the entry WITHOUT releasing.
// Returns the seg count, -1 for an unknown handle.
int64_t brpc_tpu_ici_att_take(uint64_t handle) {
  nrpc::IciAttEntry* e = nrpc::ici_att_pop(handle);
  if (e == nullptr) return -1;
  int64_t n = (int64_t)e->segs.size();
  delete e;
  return n;
}

// Drop path: release every parked key via the release upcall (the
// registry forgets them) and free the entry.  -1 unknown handle.
int brpc_tpu_ici_att_dispose(uint64_t handle) {
  nrpc::IciAttEntry* e = nrpc::ici_att_pop(handle);
  if (e == nullptr) return -1;
  nrpc::ici_release_segs(e->segs);
  delete e;
  return 0;
}

// Copy out up to `cap` seg descriptors WITHOUT consuming the handle
// (materialization reads metadata here when it outlived the upcall's
// borrowed pointers).  Returns the full seg count, -1 unknown.
int64_t brpc_tpu_ici_att_peek(uint64_t handle, nrpc::IciSegC* out,
                              uint64_t cap) {
  std::lock_guard<std::mutex> g(nrpc::g_ici_atts_mu);
  nrpc::IciAttEntry** ep = nrpc::g_ici_atts.seek(handle);
  if (ep == nullptr) return -1;
  const auto& segs = (*ep)->segs;
  uint64_t n = segs.size() < cap ? segs.size() : cap;
  for (uint64_t i = 0; i < n; ++i) out[i] = segs[i];
  return (int64_t)segs.size();
}

// Live parked entries — the census/leak-detection surface.
uint64_t brpc_tpu_ici_att_count() {
  std::lock_guard<std::mutex> g(nrpc::g_ici_atts_mu);
  return nrpc::g_ici_atts.size();
}

// Respond to a Python-handled ici request.  Custody of `segs` keys
// transfers to native here; they exit into the client's take (or are
// released on drop paths).
int brpc_tpu_ici_respond(uint64_t token, uint64_t err, const char* err_text,
                         const uint8_t* data, uint64_t len,
                         const uint8_t* att_host, uint64_t att_host_len,
                         const nrpc::IciSegC* segs, uint64_t nsegs) {
  nrpc::IciPending pr;
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_tokens_mu);
    if (!nrpc::g_ici_tokens.take(token, &pr)) return -1;
  }
  std::vector<nrpc::IciSegC> seg_vec(segs, segs + nsegs);
  auto conn = pr.conn.lock();
  if (conn == nullptr) {
    nrpc::ici_release_segs(seg_vec);
    return -2;
  }
  if (!nrpc::ici_relocate_segs(seg_vec, conn->client_dev)) {
    nrpc::ici_release_segs(seg_vec);
    if (auto ch = conn->client.lock())
      ch->deliver(pr.cid, 1009, "ici relocation failed", "", "", {});
    return -3;
  }
  auto ch = conn->client.lock();
  if (ch == nullptr) {
    nrpc::ici_release_segs(seg_vec);
    return -2;
  }
  // empty buffers arrive as NULL pointers from ctypes; std::string(ptr,
  // n) requires a valid pointer even for n==0
  ch->deliver(pr.cid, err, err_text ? err_text : "",
              len ? std::string((const char*)data, len) : std::string(),
              att_host_len
                  ? std::string((const char*)att_host, att_host_len)
                  : std::string(),
              std::move(seg_vec));
  return 0;
}

// Batched write-back half of the one-struct ABI: one ctypes crossing
// delivers every ready response the Python side accumulated (symmetric
// with the batched request upcall).  Per-item custody/drop semantics are
// brpc_tpu_ici_respond's, EXCEPT that native releases seg custody on
// every failure path (including a vanished token) — the batch caller
// gets no per-item return code, so it must never need one.
int brpc_tpu_ici_respond_batch(const nrpc::IciRespC* rs, uint64_t n) {
  for (uint64_t i = 0; i < n; ++i) {
    const nrpc::IciRespC& r = rs[i];
    nrpc::IciPending pr;
    bool had;
    {
      std::lock_guard<std::mutex> g(nrpc::g_ici_tokens_mu);
      had = nrpc::g_ici_tokens.take(r.token, &pr);
    }
    std::vector<nrpc::IciSegC> seg_vec;
    if (r.att_handle != 0) {
      // native-custody pass-through: the parked request att IS the
      // response attachment — custody continues into delivery without
      // Python ever walking the segs.  A vanished handle (double
      // pass-back would be a caller bug) degrades to an empty att.
      nrpc::IciAttEntry* e = nrpc::ici_att_pop(r.att_handle);
      if (e != nullptr) {
        seg_vec = std::move(e->segs);
        delete e;
      }
    } else {
      seg_vec.assign(r.segs, r.segs + r.nsegs);
    }
    if (!had) {
      nrpc::ici_release_segs(seg_vec);
      continue;
    }
    auto conn = pr.conn.lock();
    if (conn == nullptr) {
      nrpc::ici_release_segs(seg_vec);
      continue;
    }
    if (!nrpc::ici_relocate_segs(seg_vec, conn->client_dev)) {
      nrpc::ici_release_segs(seg_vec);
      if (auto ch = conn->client.lock())
        ch->deliver(pr.cid, 1009, "ici relocation failed", "", "", {});
      continue;
    }
    auto ch = conn->client.lock();
    if (ch == nullptr) {
      nrpc::ici_release_segs(seg_vec);
      continue;
    }
    ch->deliver(pr.cid, r.err, r.err_text ? r.err_text : "",
                r.len ? std::string((const char*)r.data, r.len)
                      : std::string(),
                r.att_host_len
                    ? std::string((const char*)r.att_host, r.att_host_len)
                    : std::string(),
                std::move(seg_vec), r.retry_after_ms);
  }
  return 0;
}

// Native-loop ici echo benchmark: the C++ client loop of the reference's
// rdma_performance client.  dev_key names a pre-registered device array
// (borrowed for the duration — never released here); dev_nbytes 0 runs
// the host-only frame.  Returns p50 ns (-1 on failure).
int64_t brpc_tpu_ici_echo_p50_ns(int iters, int payload_len,
                                 uint64_t dev_key, uint64_t dev_nbytes,
                                 int32_t dev) {
  uint64_t sh = brpc_tpu_ici_listen(dev, nullptr);
  if (sh == 0) return -1;
  brpc_tpu_ici_register_echo(sh, "EchoService.Echo");
  uint64_t ch = brpc_tpu_ici_connect(dev, dev, 0);
  if (ch == 0) {
    brpc_tpu_ici_unlisten(sh);
    return -1;
  }
  std::pair<nrpc::IciChannelPtr, nrpc::IciConnPtr> entry;
  {
    std::lock_guard<std::mutex> g(nrpc::g_ici_mu);
    entry = nrpc::g_ici_channels[ch];
  }
  std::string payload(payload_len, 'x');
  std::vector<int64_t> lat;
  lat.reserve(iters);
  auto now_ns = [] {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
  };
  bool ok = true;
  for (int i = 0; i < iters + 50 && ok; ++i) {
    std::vector<nrpc::IciSegC> segs;
    if (dev_nbytes > 0)
      segs.push_back(nrpc::IciSegC{dev_key, dev_nbytes, dev, 1});
    nrpc::IciSlot out;
    std::string err;
    int64_t t0 = now_ns();
    uint64_t rc = nrpc::ici_do_call(
        entry.first, entry.second, "EchoService.Echo",
        (const uint8_t*)payload.data(), payload.size(), nullptr, 0,
        std::move(segs), 5 * 1000 * 1000, &out, &err);
    int64_t t1 = now_ns();
    ok = (rc == 0 && out.payload.size() == payload.size() &&
          out.segs.size() == (dev_nbytes > 0 ? 1u : 0u));
    if (ok && i >= 50) lat.push_back(t1 - t0);
  }
  brpc_tpu_ici_close(ch);
  brpc_tpu_ici_unlisten(sh);
  if (!ok || lat.empty()) return -1;
  std::sort(lat.begin(), lat.end());
  return lat[lat.size() / 2];
}

}  // extern "C"

#else  // !__linux__

// Full stub set: every symbol the Python bindings reference must exist so
// _bind() succeeds and the rest of the native core (pools, butex, fibers,
// timers) stays usable even where the epoll datapath is unavailable.
#include <cstdint>
extern "C" {
uint64_t brpc_tpu_nserver_start(int) { return 0; }
int brpc_tpu_nserver_port(uint64_t) { return -1; }
int brpc_tpu_nserver_register_echo(uint64_t, const char*) { return -1; }
int brpc_tpu_nserver_set_handler(uint64_t, void*) { return -1; }
uint64_t brpc_tpu_nserver_requests(uint64_t) { return 0; }
int brpc_tpu_nserver_respond(uint64_t, uint64_t, const char*,
                             const uint8_t*, uint64_t, const uint8_t*,
                             uint64_t) { return -1; }
void brpc_tpu_nserver_stop(uint64_t) {}
uint64_t brpc_tpu_nchannel_connect(const char*, int) { return 0; }
uint64_t brpc_tpu_nchannel_call(uint64_t, const char*, const uint8_t*,
                                uint64_t, const uint8_t*, uint64_t, int64_t,
                                uint8_t**, uint64_t*, uint8_t**, uint64_t*,
                                char**) { return 1009; }
void brpc_tpu_buf_free(void* p) { free(p); }
void brpc_tpu_nchannel_close(uint64_t) {}
int64_t brpc_tpu_native_rpc_echo_p50_ns(int, int) { return -1; }
double brpc_tpu_native_rpc_qps(int, int, int) { return -1.0; }
void brpc_tpu_ici_set_hooks(void*, void*) {}
uint64_t brpc_tpu_ici_listen(int32_t, void*) { return 0; }
int brpc_tpu_ici_register_echo(uint64_t, const char*) { return -1; }
int brpc_tpu_ici_set_handler(uint64_t, void*) { return -1; }
uint64_t brpc_tpu_ici_requests(uint64_t) { return 0; }
int brpc_tpu_ici_has_listener(int32_t) { return 0; }
void brpc_tpu_ici_unlisten(uint64_t) {}
uint64_t brpc_tpu_ici_connect(int32_t, int32_t, int64_t) { return 0; }
void brpc_tpu_ici_close(uint64_t) {}
int64_t brpc_tpu_ici_window_left(uint64_t) { return -1; }
uint64_t brpc_tpu_ici_call2(uint64_t, const char*, const uint8_t*,
                            uint64_t, const uint8_t*, uint64_t,
                            const void*, uint64_t, int64_t, void*) {
  return 1009;
}
uint64_t brpc_tpu_ici_call4(uint64_t, const char*, const uint8_t*,
                            uint64_t, const uint8_t*, uint64_t,
                            const void*, uint64_t, int64_t, int64_t,
                            const char*, int64_t, void*) {
  return 1009;
}
int64_t brpc_tpu_ici_att_take(uint64_t) { return -1; }
int brpc_tpu_ici_att_dispose(uint64_t) { return -1; }
int64_t brpc_tpu_ici_att_peek(uint64_t, void*, uint64_t) { return -1; }
uint64_t brpc_tpu_ici_att_count() { return 0; }
int brpc_tpu_ici_respond(uint64_t, uint64_t, const char*, const uint8_t*,
                         uint64_t, const uint8_t*, uint64_t, const void*,
                         uint64_t) { return -1; }
uint64_t brpc_tpu_ici_listen_batch(int32_t, void*) { return 0; }
int brpc_tpu_ici_set_batch_params(uint64_t, int64_t, int64_t) { return -1; }
int brpc_tpu_ici_batch_stats(uint64_t, uint64_t*, uint64_t*, uint64_t*) {
  return -1;
}
int brpc_tpu_ici_respond_batch(const void*, uint64_t) { return -1; }
int64_t brpc_tpu_ici_echo_p50_ns(int, int, uint64_t, uint64_t, int32_t) {
  return -1;
}
uint64_t brpc_tpu_nchannel_call_async(uint64_t, const char*,
                                      const uint8_t*, uint64_t,
                                      const uint8_t*, uint64_t, int64_t,
                                      void*, void*) { return 1009; }
uint64_t brpc_tpu_npool_connect(const char*, int, int) { return 0; }
uint64_t brpc_tpu_npool_call(uint64_t, const char*, const uint8_t*,
                             uint64_t, const uint8_t*, uint64_t, int64_t,
                             uint8_t**, uint64_t*, uint8_t**, uint64_t*,
                             char**) { return 1009; }
void brpc_tpu_npool_close(uint64_t) {}
}

#endif
