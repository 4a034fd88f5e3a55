// Tensor RPC transport for the parameter-server runtime.
//
// TPU-native analog of the reference's RPC layer
// (paddle/fluid/operators/distributed/: grpc_client.cc / grpc_server.cc /
// variable_response.cc wire format, request_handler_impl.cc Send/Get
// handlers).  gRPC/BRPC are replaced by a framed TCP protocol; the server is
// dumb transport + tensor store + event queue, and the pserver's optimizer
// blocks run in Python against the normal executor (mirroring the reference,
// where listen_and_serv_op.cc executes optimizer sub-blocks per received
// grad while the transport lives in C++).
//
// Wire frame: [u8 type][u32 name_len][name][u8 dtype][u8 ndim][i64 dims...]
//             [u64 payload_len][payload]
// types: 1=SEND_VAR 2=GET_VAR 3=BARRIER 4=COMPLETE 5=REPLY_VAR 6=ACK
//
// C ABI (ctypes): rpcs_* = server, rpcc_* = client.

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <sys/time.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

constexpr uint8_t kSendVar = 1, kGetVar = 2, kBarrier = 3, kComplete = 4,
                  kReplyVar = 5, kAck = 6;

struct Tensor {
  uint8_t dtype = 0;  // opaque to the transport (numpy dtype enum on the py side)
  std::vector<int64_t> dims;
  std::string data;
  int64_t stored_ns = 0;  // when its transaction entered the store
};

// CLOCK_MONOTONIC, the clock of Python's time.monotonic() on this host.
int64_t now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// One timed GET reply (rpcs_time_gets): when the variable was stored, when
// the request's frame was read, when the reply was written, and when the
// reply before it on the same connection was written (0 if that was not a
// timed one).
struct GetRecord {
  int64_t stored_ns, get_ns, sent_ns, prev_sent_ns;
};
constexpr size_t kGetRing = 4096;

struct Event {  // delivered to the Python pserver loop
  uint8_t type;  // kSendVar | kBarrier | kComplete
  std::string name;
  Tensor tensor;  // valid for kSendVar
};

bool read_full(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n > 0) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool write_full(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n > 0) {
    ssize_t r = ::send(fd, p, n, MSG_NOSIGNAL);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

struct Frame {
  uint8_t type = 0;
  std::string name;
  Tensor tensor;
};

bool read_frame(int fd, Frame* f) {
  uint8_t type;
  if (!read_full(fd, &type, 1)) return false;
  uint32_t name_len;
  if (!read_full(fd, &name_len, 4)) return false;
  if (name_len > (1u << 20)) return false;
  f->name.resize(name_len);
  if (name_len && !read_full(fd, f->name.data(), name_len)) return false;
  uint8_t dtype, ndim;
  if (!read_full(fd, &dtype, 1) || !read_full(fd, &ndim, 1)) return false;
  f->tensor.dtype = dtype;
  f->tensor.dims.resize(ndim);
  if (ndim && !read_full(fd, f->tensor.dims.data(), 8ull * ndim)) return false;
  uint64_t payload;
  if (!read_full(fd, &payload, 8)) return false;
  if (payload > (1ull << 33)) return false;
  f->tensor.data.resize(payload);
  if (payload && !read_full(fd, f->tensor.data.data(), payload)) return false;
  f->type = type;
  return true;
}

bool write_frame(int fd, uint8_t type, const std::string& name,
                 const Tensor* t) {
  std::string head;
  head.push_back(static_cast<char>(type));
  uint32_t name_len = static_cast<uint32_t>(name.size());
  head.append(reinterpret_cast<char*>(&name_len), 4);
  head += name;
  uint8_t dtype = t ? t->dtype : 0;
  uint8_t ndim = t ? static_cast<uint8_t>(t->dims.size()) : 0;
  head.push_back(static_cast<char>(dtype));
  head.push_back(static_cast<char>(ndim));
  if (t && ndim)
    head.append(reinterpret_cast<const char*>(t->dims.data()), 8ull * ndim);
  uint64_t payload = t ? t->data.size() : 0;
  head.append(reinterpret_cast<char*>(&payload), 8);
  if (!write_full(fd, head.data(), head.size())) return false;
  if (t && payload) return write_full(fd, t->data.data(), payload);
  return true;
}

struct Server {
  int listen_fd = -1;
  int port = 0;
  std::thread accept_thread;
  std::vector<std::thread> conns;
  std::vector<int> conn_fds;  // so destroy can unblock idle recv()s
  std::mutex mu;
  std::condition_variable events_cv;   // Python waits for inbound events
  std::deque<Event> events;
  std::map<std::string, Tensor> store;
  // a GET handler whose variable is not there yet parks on a condition
  // variable of its own, registered here under the name it wants, so that
  // a store wakes the readers of the names it wrote and nobody else (one
  // shared condition variable costs parked readers x stores wake-ups, each
  // a round of ``mu``: 1,024 a decode step at 32 streaming lanes).
  std::multimap<std::string, std::condition_variable*> waiters;
  long long wakeups = 0;  // times a parked GET handler woke (tests read it)
  bool serving = false;  // GETs blocked until Python publishes + enables
  bool stop = false;
  // Timed GETs (rpcs_time_gets).  ``timed_gen`` is odd while the switch is
  // on and moves at every call of the switch, so a GET that read its frame
  // under one setting leaves no record under another; a GET with the switch
  // off pays this one load.  The rest is under ``timed_mu``, never ``mu``:
  // the ring holds the newest kGetRing records, oldest at ``ring_head``.
  std::atomic<uint32_t> timed_gen{0};
  std::mutex timed_mu;
  std::string timed_prefix;
  std::vector<GetRecord> ring;
  size_t ring_head = 0, ring_count = 0;
  long long ring_dropped = 0;

  // Erase ``gone`` and store ``items`` as ONE transaction: a reader sees
  // all of it or none of it, and each waiter of a stored name is woken
  // once.  (Call without ``mu``; the tensors are built before it is taken.
  // The notify happens under ``mu``: a waiter's condition variable lives on
  // its stack and it cannot leave wait() before ``mu`` is released.)
  void publish(std::vector<std::pair<std::string, Tensor>>* items,
               const std::vector<std::string>& gone) {
    std::lock_guard<std::mutex> lk(mu);
    const int64_t stored_ns = now_ns();
    for (const auto& name : gone) store.erase(name);
    for (auto& kv : *items) {
      kv.second.stored_ns = stored_ns;
      store[kv.first] = std::move(kv.second);
    }
    if (!serving) return;  // parked GETs stay parked until rpcs_serve(1)
    for (const auto& kv : *items) {
      auto range = waiters.equal_range(kv.first);
      for (auto it = range.first; it != range.second; ++it)
        it->second->notify_one();
    }
  }

  // The ``serving`` gate opened or the server stops: every parked GET
  // handler looks again.  (Call with ``mu`` held.)
  void wake_all_locked() {
    for (auto& kv : waiters) kv.second->notify_one();
  }

  void forget_fd(int fd) {
    std::lock_guard<std::mutex> lk(mu);
    for (auto it = conn_fds.begin(); it != conn_fds.end(); ++it) {
      if (*it == fd) {
        conn_fds.erase(it);
        break;
      }
    }
  }

  // After a timed GET's reply is written: one record, unless the switch
  // has moved since the frame was read or the name is not under the
  // prefix.  -> whether it was recorded.
  bool record_get(uint32_t gen, const std::string& name, const GetRecord& r) {
    std::lock_guard<std::mutex> lk(timed_mu);
    if (timed_gen.load(std::memory_order_relaxed) != gen ||
        name.compare(0, timed_prefix.size(), timed_prefix) != 0)
      return false;
    if (ring_count == kGetRing) {  // full: the oldest falls out
      ring[ring_head] = r;
      ring_head = (ring_head + 1) % kGetRing;
      ++ring_dropped;
    } else {
      ring[(ring_head + ring_count++) % kGetRing] = r;
    }
    return true;
  }

  void handle_conn(int fd) {
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    Frame f;
    // this connection's last reply, if it was a timed one, and the setting
    // of the switch it was timed under
    int64_t prev_sent_ns = 0;
    uint32_t prev_gen = 0;
    while (read_frame(fd, &f)) {
      if (f.type == kSendVar || f.type == kBarrier || f.type == kComplete) {
        prev_sent_ns = 0;
        {
          std::lock_guard<std::mutex> lk(mu);
          events.push_back({f.type, f.name, std::move(f.tensor)});
        }
        events_cv.notify_all();
        if (!write_frame(fd, kAck, "", nullptr)) break;
      } else if (f.type == kGetVar) {
        const uint32_t gen = timed_gen.load(std::memory_order_relaxed);
        const bool timed = gen & 1;
        const int64_t get_ns = timed ? now_ns() : 0;
        Tensor t;
        {
          std::unique_lock<std::mutex> lk(mu);
          auto ready = [&] {
            return stop || (serving && store.count(f.name));
          };
          if (!ready()) {
            std::condition_variable cv;
            auto self = waiters.emplace(f.name, &cv);
            while (!ready()) {
              cv.wait(lk);
              ++wakeups;
            }
            waiters.erase(self);
          }
          if (stop) break;
          t = store[f.name];
        }
        if (!write_frame(fd, kReplyVar, f.name, &t)) break;
        if (timed) {
          const int64_t sent_ns = now_ns();
          const bool kept = record_get(
              gen, f.name,
              {t.stored_ns, get_ns, sent_ns,
               prev_gen == gen ? prev_sent_ns : 0});
          prev_sent_ns = kept ? sent_ns : 0;
          prev_gen = gen;
        }
      }
    }
    // drop from conn_fds BEFORE closing: destroy() must never shutdown()
    // a number the OS may have already reassigned to an unrelated socket
    forget_fd(fd);
    ::close(fd);
  }

  void accept_loop() {
    while (true) {
      int fd = ::accept(listen_fd, nullptr, nullptr);
      if (fd < 0) {
        std::lock_guard<std::mutex> lk(mu);
        if (stop) return;
        continue;
      }
      {
        std::lock_guard<std::mutex> lk(mu);
        if (stop) {
          ::close(fd);
          return;
        }
        conn_fds.push_back(fd);
        conns.emplace_back(&Server::handle_conn, this, fd);
      }
    }
  }
};

struct Client {
  int fd = -1;
};

}  // namespace

extern "C" {

// -- server ------------------------------------------------------------------

void* rpcs_create(int port) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return nullptr;
  int one = 1;
  ::setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::bind(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
      ::listen(fd, 64) != 0) {
    ::close(fd);
    return nullptr;
  }
  auto* s = new Server();
  s->listen_fd = fd;
  if (port == 0) {
    socklen_t len = sizeof(addr);
    ::getsockname(fd, reinterpret_cast<sockaddr*>(&addr), &len);
  }
  s->port = ntohs(addr.sin_port);
  s->accept_thread = std::thread(&Server::accept_loop, s);
  return s;
}

int rpcs_port(void* h) { return static_cast<Server*>(h)->port; }

// Blocking poll for the next inbound event.  Returns the event type (0 on
// shutdown).  Name is copied into name_buf; SEND_VAR tensors are held until
// the next rpcs_poll call via *data/*dims outputs.
int rpcs_poll(void* h, char* name_buf, int name_cap, unsigned char* dtype,
              long long* dims, int dims_cap, int* ndim,
              const void** data, long long* data_len) {
  auto* s = static_cast<Server*>(h);
  static thread_local Event current;  // keeps tensor alive for the caller
  std::unique_lock<std::mutex> lk(s->mu);
  s->events_cv.wait(lk, [&] { return s->stop || !s->events.empty(); });
  if (s->stop && s->events.empty()) return 0;
  current = std::move(s->events.front());
  s->events.pop_front();
  lk.unlock();
  std::snprintf(name_buf, name_cap, "%s", current.name.c_str());
  *dtype = current.tensor.dtype;
  *ndim = static_cast<int>(current.tensor.dims.size());
  for (int i = 0; i < *ndim && i < dims_cap; ++i)
    dims[i] = current.tensor.dims[i];
  *data = current.tensor.data.data();
  *data_len = static_cast<long long>(current.tensor.data.size());
  return current.type;
}

// Store ``n`` variables and erase ``n_gone`` names in one acquisition of
// the store's mutex.  ``dims`` holds every variable's dims one after
// another (``ndims[i]`` of them each).  A name both erased and stored ends
// up stored.
void rpcs_set_vars(void* h, int n, const char* const* names,
                   const unsigned char* dtypes, const int* ndims,
                   const long long* dims, const void* const* data,
                   const long long* lens, int n_gone,
                   const char* const* gone) {
  auto* s = static_cast<Server*>(h);
  std::vector<std::pair<std::string, Tensor>> items(n);
  for (int i = 0; i < n; ++i) {
    items[i].first = names[i];
    Tensor& t = items[i].second;
    t.dtype = dtypes[i];
    t.dims.assign(dims, dims + ndims[i]);
    dims += ndims[i];
    t.data.assign(static_cast<const char*>(data[i]),
                  static_cast<size_t>(lens[i]));
  }
  s->publish(&items, std::vector<std::string>(gone, gone + n_gone));
}

void rpcs_set_var(void* h, const char* name, unsigned char dtype,
                  const long long* dims, int ndim, const void* data,
                  long long len) {
  rpcs_set_vars(h, 1, &name, &dtype, &ndim, dims, &data, &len, 0, nullptr);
}

void rpcs_del_var(void* h, const char* name) {
  rpcs_set_vars(h, 0, nullptr, nullptr, nullptr, nullptr, nullptr, nullptr,
                1, &name);
}

void rpcs_serve(void* h, int enable) {
  auto* s = static_cast<Server*>(h);
  std::lock_guard<std::mutex> lk(s->mu);
  s->serving = enable != 0;
  s->wake_all_locked();
}

// out[0]: GET handlers parked now; out[1]: times a parked handler has woken
// since the server started (one a reader where a store wakes only the
// readers of what it wrote).
void rpcs_wait_stats(void* h, long long* out) {
  auto* s = static_cast<Server*>(h);
  std::lock_guard<std::mutex> lk(s->mu);
  out[0] = static_cast<long long>(s->waiters.size());
  out[1] = s->wakeups;
}

// Time the replies to GETs of names that start with ``prefix``; a null
// prefix turns the timing off (the default).  Either way the ring is
// emptied, and a GET whose frame was read before the call leaves no record.
void rpcs_time_gets(void* h, const char* prefix) {
  auto* s = static_cast<Server*>(h);
  std::lock_guard<std::mutex> lk(s->timed_mu);
  // the next value of the wanted parity: odd is on
  uint32_t gen = s->timed_gen.load(std::memory_order_relaxed) + 1;
  if ((gen & 1) != (prefix != nullptr)) ++gen;
  s->timed_gen.store(gen, std::memory_order_relaxed);
  s->timed_prefix = prefix ? prefix : "";
  if (prefix && s->ring.empty()) s->ring.resize(kGetRing);
  s->ring_head = s->ring_count = 0;
  s->ring_dropped = 0;
}

// What the oldest records (at most ``cap``) say, in microseconds, and take
// them off the ring.  out[0]: records that fell out of the full ring since
// the last drain; out[1], out[2]: how many ``late`` and ``turnaround``
// values follow.  Then, ``n`` being the count returned: at out + 3 ``n``
// times ``deliver`` (from chunk and request both there to the reply
// written: sent - max(stored, get)); at out + 3 + n ``late`` (get - stored
// where the request came after its chunk); at out + 3 + 2n ``turnaround``
// (get - the connection's reply before, where that one was timed).  The
// arithmetic is here because the caller is a decode loop that pays for every
// call it makes.  ``out`` holds 3 + 3 * cap values.
long long rpcs_drain_gets(void* h, long long* out, long long cap) {
  auto* s = static_cast<Server*>(h);
  std::lock_guard<std::mutex> lk(s->timed_mu);
  out[0] = s->ring_dropped;
  s->ring_dropped = 0;
  const long long n =
      std::min<long long>(cap, static_cast<long long>(s->ring_count));
  long long* deliver = out + 3;
  long long* late = deliver + n;
  long long* turnaround = late + n;
  long long n_late = 0, n_turn = 0;
  for (long long i = 0; i < n; ++i) {
    const GetRecord& r = s->ring[s->ring_head];
    s->ring_head = (s->ring_head + 1) % kGetRing;
    deliver[i] = (r.sent_ns - std::max(r.stored_ns, r.get_ns)) / 1000;
    if (r.get_ns > r.stored_ns)
      late[n_late++] = (r.get_ns - r.stored_ns) / 1000;
    if (r.prev_sent_ns)
      turnaround[n_turn++] = (r.get_ns - r.prev_sent_ns) / 1000;
  }
  s->ring_count -= static_cast<size_t>(n);
  out[1] = n_late;
  out[2] = n_turn;
  return n;
}

void rpcs_destroy(void* h) {
  auto* s = static_cast<Server*>(h);
  {
    std::lock_guard<std::mutex> lk(s->mu);
    s->stop = true;
    // unblock handler threads parked in recv() on idle connections —
    // joining without this deadlocks when a client is mid-compute
    for (int fd : s->conn_fds) ::shutdown(fd, SHUT_RDWR);
    s->wake_all_locked();
  }
  s->events_cv.notify_all();
  ::shutdown(s->listen_fd, SHUT_RDWR);
  ::close(s->listen_fd);
  if (s->accept_thread.joinable()) s->accept_thread.join();
  for (auto& t : s->conns)
    if (t.joinable()) t.join();
  delete s;
}

// -- client ------------------------------------------------------------------

void* rpcc_connect(const char* host, int port) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return nullptr;
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    ::close(fd);
    return nullptr;
  }
  if (::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0) {
    ::close(fd);
    return nullptr;
  }
  int one = 1;
  ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  auto* c = new Client();
  c->fd = fd;
  return c;
}

// Per-request deadline (reference FLAGS_rpc_deadline,
// paddle/fluid/operators/distributed/grpc/grpc_client.cc): a pserver that
// hangs mid-round must surface as an error on the trainer, not block its
// recv() forever.  seconds <= 0 restores fully-blocking behavior.
void rpcc_set_deadline(void* h, double seconds) {
  auto* c = static_cast<Client*>(h);
  timeval tv{};
  if (seconds > 0) {
    tv.tv_sec = static_cast<time_t>(seconds);
    tv.tv_usec =
        static_cast<suseconds_t>((seconds - static_cast<double>(tv.tv_sec)) * 1e6);
  }
  ::setsockopt(c->fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
  ::setsockopt(c->fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
}

int rpcc_send_var(void* h, const char* name, unsigned char dtype,
                  const long long* dims, int ndim, const void* data,
                  long long len) {
  auto* c = static_cast<Client*>(h);
  Tensor t;
  t.dtype = dtype;
  t.dims.assign(dims, dims + ndim);
  t.data.assign(static_cast<const char*>(data), static_cast<size_t>(len));
  if (!write_frame(c->fd, kSendVar, name, &t)) return -1;
  Frame ack;
  if (!read_frame(c->fd, &ack) || ack.type != kAck) return -1;
  return 0;
}

int rpcc_barrier(void* h, const char* kind) {
  auto* c = static_cast<Client*>(h);
  if (!write_frame(c->fd, kBarrier, kind, nullptr)) return -1;
  Frame ack;
  if (!read_frame(c->fd, &ack) || ack.type != kAck) return -1;
  return 0;
}

int rpcc_complete(void* h) {
  auto* c = static_cast<Client*>(h);
  if (!write_frame(c->fd, kComplete, "", nullptr)) return -1;
  Frame ack;
  if (!read_frame(c->fd, &ack) || ack.type != kAck) return -1;
  return 0;
}

// Blocking GET: fills dtype/dims/ndim, returns a malloc'd payload pointer in
// *data (caller frees with rpc_free) and the byte length (<0 on error).
long long rpcc_get_var(void* h, const char* name, unsigned char* dtype,
                       long long* dims, int dims_cap, int* ndim,
                       void** data) {
  auto* c = static_cast<Client*>(h);
  if (!write_frame(c->fd, kGetVar, name, nullptr)) return -1;
  Frame f;
  if (!read_frame(c->fd, &f) || f.type != kReplyVar) return -1;
  *dtype = f.tensor.dtype;
  *ndim = static_cast<int>(f.tensor.dims.size());
  for (int i = 0; i < *ndim && i < dims_cap; ++i) dims[i] = f.tensor.dims[i];
  void* buf = ::malloc(f.tensor.data.size() ? f.tensor.data.size() : 1);
  std::memcpy(buf, f.tensor.data.data(), f.tensor.data.size());
  *data = buf;
  return static_cast<long long>(f.tensor.data.size());
}

void rpc_free(void* p) { ::free(p); }

void rpcc_close(void* h) {
  auto* c = static_cast<Client*>(h);
  ::close(c->fd);
  delete c;
}

}  // extern "C"
