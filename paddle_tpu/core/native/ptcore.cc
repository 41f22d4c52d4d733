// paddle_tpu native runtime core (C ABI, loaded via ctypes).
//
// Reference-parity note: the reference implements these subsystems in C++
// inside the framework —
//   * rendezvous KV store: paddle/phi/core/distributed/store/tcp_store.*
//     [— verify]
//   * DataLoader shared-memory transport: paddle/fluid/memory +
//     python/paddle/io worker shm path [— verify]
// This file provides the TPU-framework equivalents as a small C library:
// the compute path is XLA's business, but multi-process rendezvous and
// zero-pickle batch transport are genuine native-runtime concerns on TPU
// hosts too. (Host spans are Python's: observability/tracing.py.)
//
// Build: g++ -std=c++17 -O2 -shared -fPIC -pthread ptcore.cc -o libptcore.so

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <pthread.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

extern "C" {

// ===========================================================================
// 1. TCPStore: rendezvous KV over TCP (rank0 hosts the server)
// ===========================================================================
//
// Wire protocol (little endian):
//   request:  u8 op | u32 klen | key | u32 vlen | value
//     op: 0=SET 1=GET 2=ADD(value = i64 delta) 3=WAIT 4=DELETE 5=CHECK
//   response: u32 vlen | value            (GET/ADD; ADD returns i64)
//             u8 status                   (SET/WAIT/DELETE/CHECK)
// GET and WAIT block server-side until the key exists.

namespace {

struct StoreServer {
  int listen_fd = -1;
  std::thread accept_thread;
  std::vector<std::thread> workers;
  std::vector<int> client_fds;   // guarded by mu; for shutdown wakeup
  std::map<std::string, std::string> kv;
  std::mutex mu;
  std::condition_variable cv;
  std::atomic<bool> stop{false};
};

bool read_full(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n) {
    ssize_t r = recv(fd, p, n, 0);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool write_full(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n) {
    ssize_t r = send(fd, p, n, MSG_NOSIGNAL);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

void serve_client(StoreServer* s, int fd) {
  for (;;) {
    uint8_t op;
    uint32_t klen, vlen;
    if (!read_full(fd, &op, 1) || !read_full(fd, &klen, 4)) break;
    if (klen > (1u << 20)) break;
    std::string key(klen, '\0');
    if (!read_full(fd, key.data(), klen) || !read_full(fd, &vlen, 4)) break;
    if (vlen > (1u << 28)) break;
    std::string val(vlen, '\0');
    if (vlen && !read_full(fd, val.data(), vlen)) break;

    if (op == 0) {  // SET
      {
        std::lock_guard<std::mutex> lk(s->mu);
        s->kv[key] = val;
      }
      s->cv.notify_all();
      uint8_t ok = 0;
      if (!write_full(fd, &ok, 1)) break;
    } else if (op == 1 || op == 3) {  // GET / WAIT
      std::unique_lock<std::mutex> lk(s->mu);
      s->cv.wait(lk, [&] {
        return s->stop.load() || s->kv.count(key) > 0;
      });
      if (s->stop.load()) break;
      if (op == 1) {
        std::string v = s->kv[key];
        lk.unlock();
        uint32_t n = static_cast<uint32_t>(v.size());
        if (!write_full(fd, &n, 4) || !write_full(fd, v.data(), n)) break;
      } else {
        lk.unlock();
        uint8_t ok = 0;
        if (!write_full(fd, &ok, 1)) break;
      }
    } else if (op == 2) {  // ADD
      int64_t delta = 0;
      memcpy(&delta, val.data(), std::min<size_t>(8, val.size()));
      int64_t result;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        int64_t cur = 0;
        auto it = s->kv.find(key);
        if (it != s->kv.end() && it->second.size() == 8)
          memcpy(&cur, it->second.data(), 8);
        result = cur + delta;
        std::string enc(8, '\0');
        memcpy(enc.data(), &result, 8);
        s->kv[key] = enc;
      }
      s->cv.notify_all();
      uint32_t n = 8;
      if (!write_full(fd, &n, 4) || !write_full(fd, &result, 8)) break;
    } else if (op == 4) {  // DELETE
      {
        std::lock_guard<std::mutex> lk(s->mu);
        s->kv.erase(key);
      }
      uint8_t ok = 0;
      if (!write_full(fd, &ok, 1)) break;
    } else if (op == 5) {  // CHECK (non-blocking existence)
      uint8_t exists;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        exists = s->kv.count(key) ? 1 : 0;
      }
      if (!write_full(fd, &exists, 1)) break;
    } else {
      break;
    }
  }
  {
    // deregister before closing so stop() never shutdown()s a reused fd
    std::lock_guard<std::mutex> lk(s->mu);
    auto it = std::find(s->client_fds.begin(), s->client_fds.end(), fd);
    if (it != s->client_fds.end()) s->client_fds.erase(it);
  }
  close(fd);
}

}  // namespace

void* pt_store_server_start(int port) {
  auto* s = new StoreServer();
  s->listen_fd = socket(AF_INET, SOCK_STREAM, 0);
  if (s->listen_fd < 0) {
    delete s;
    return nullptr;
  }
  int one = 1;
  setsockopt(s->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (bind(s->listen_fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) <
          0 ||
      listen(s->listen_fd, 128) < 0) {
    close(s->listen_fd);
    delete s;
    return nullptr;
  }
  s->accept_thread = std::thread([s] {
    for (;;) {
      int fd = accept(s->listen_fd, nullptr, nullptr);
      if (fd < 0) break;  // listen_fd closed => shutdown
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      std::lock_guard<std::mutex> lk(s->mu);
      if (s->stop.load()) {
        close(fd);
        break;
      }
      s->client_fds.push_back(fd);
      s->workers.emplace_back(serve_client, s, fd);
    }
  });
  return s;
}

// Bound port (for port=0 auto-assign).
int pt_store_server_port(void* handle) {
  auto* s = static_cast<StoreServer*>(handle);
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  if (getsockname(s->listen_fd, reinterpret_cast<sockaddr*>(&addr), &len) <
      0)
    return -1;
  return ntohs(addr.sin_port);
}

void pt_store_server_stop(void* handle) {
  auto* s = static_cast<StoreServer*>(handle);
  s->stop.store(true);
  s->cv.notify_all();
  shutdown(s->listen_fd, SHUT_RDWR);
  close(s->listen_fd);
  if (s->accept_thread.joinable()) s->accept_thread.join();
  {
    // wake workers blocked in recv() on live client sockets
    std::lock_guard<std::mutex> lk(s->mu);
    for (int fd : s->client_fds) shutdown(fd, SHUT_RDWR);
  }
  for (auto& w : s->workers)
    if (w.joinable()) w.join();  // must all exit before s is freed
  delete s;
}

struct StoreClient {
  int fd = -1;
  std::mutex mu;
};

void* pt_store_client_connect(const char* host, int port, int timeout_ms) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return nullptr;
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    close(fd);
    return nullptr;
  }
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  while (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0) {
    close(fd);
    if (std::chrono::steady_clock::now() > deadline) return nullptr;
    std::this_thread::sleep_for(std::chrono::milliseconds(50));
    fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return nullptr;
  }
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  auto* c = new StoreClient();
  c->fd = fd;
  return c;
}

namespace {
bool send_req(StoreClient* c, uint8_t op, const char* key, const void* val,
              uint32_t vlen) {
  uint32_t klen = static_cast<uint32_t>(strlen(key));
  return write_full(c->fd, &op, 1) && write_full(c->fd, &klen, 4) &&
         write_full(c->fd, key, klen) && write_full(c->fd, &vlen, 4) &&
         (vlen == 0 || write_full(c->fd, val, vlen));
}
}  // namespace

int pt_store_set(void* handle, const char* key, const void* val, int len) {
  auto* c = static_cast<StoreClient*>(handle);
  std::lock_guard<std::mutex> lk(c->mu);
  if (!send_req(c, 0, key, val, static_cast<uint32_t>(len))) return -1;
  uint8_t ok;
  return read_full(c->fd, &ok, 1) ? 0 : -1;
}

// Returns value length (may exceed buf_len: caller re-calls with bigger
// buffer — value re-fetched), or -1 on error.
int pt_store_get(void* handle, const char* key, void* buf, int buf_len) {
  auto* c = static_cast<StoreClient*>(handle);
  std::lock_guard<std::mutex> lk(c->mu);
  if (!send_req(c, 1, key, nullptr, 0)) return -1;
  uint32_t n;
  if (!read_full(c->fd, &n, 4)) return -1;
  std::string v(n, '\0');
  if (n && !read_full(c->fd, v.data(), n)) return -1;
  if (static_cast<int>(n) <= buf_len && buf) memcpy(buf, v.data(), n);
  return static_cast<int>(n);
}

int64_t pt_store_add(void* handle, const char* key, int64_t delta) {
  auto* c = static_cast<StoreClient*>(handle);
  std::lock_guard<std::mutex> lk(c->mu);
  if (!send_req(c, 2, key, &delta, 8)) return INT64_MIN;
  uint32_t n;
  int64_t result;
  if (!read_full(c->fd, &n, 4) || n != 8 || !read_full(c->fd, &result, 8))
    return INT64_MIN;
  return result;
}

int pt_store_wait(void* handle, const char* key) {
  auto* c = static_cast<StoreClient*>(handle);
  std::lock_guard<std::mutex> lk(c->mu);
  if (!send_req(c, 3, key, nullptr, 0)) return -1;
  uint8_t ok;
  return read_full(c->fd, &ok, 1) ? 0 : -1;
}

int pt_store_delete(void* handle, const char* key) {
  auto* c = static_cast<StoreClient*>(handle);
  std::lock_guard<std::mutex> lk(c->mu);
  if (!send_req(c, 4, key, nullptr, 0)) return -1;
  uint8_t ok;
  return read_full(c->fd, &ok, 1) ? 0 : -1;
}

int pt_store_check(void* handle, const char* key) {
  auto* c = static_cast<StoreClient*>(handle);
  std::lock_guard<std::mutex> lk(c->mu);
  if (!send_req(c, 5, key, nullptr, 0)) return -1;
  uint8_t exists;
  return read_full(c->fd, &exists, 1) ? exists : -1;
}

void pt_store_client_close(void* handle) {
  auto* c = static_cast<StoreClient*>(handle);
  close(c->fd);
  delete c;
}

// ===========================================================================
// 2. Shared-memory ring queue: DataLoader worker -> main batch transport
// ===========================================================================
//
// Layout in the shm segment:
//   Header { pthread_mutex_t mu; pthread_cond_t not_full, not_empty;
//            u64 capacity, head, tail, count; }   (process-shared)
//   data[capacity]  byte ring; each message is u64 length + payload.

namespace {

struct ShmHeader {
  pthread_mutex_t mu;
  pthread_cond_t not_full;
  pthread_cond_t not_empty;
  uint64_t capacity;
  uint64_t head;   // read offset
  uint64_t tail;   // write offset
  uint64_t used;   // bytes in ring
};

struct ShmQueue {
  ShmHeader* h;
  char* data;
  size_t total;
  std::string name;
  bool owner;
};

void ring_write(ShmQueue* q, const char* src, uint64_t n) {
  uint64_t cap = q->h->capacity;
  uint64_t tail = q->h->tail;
  uint64_t first = std::min(n, cap - tail);
  memcpy(q->data + tail, src, first);
  if (n > first) memcpy(q->data, src + first, n - first);
  q->h->tail = (tail + n) % cap;
  q->h->used += n;
}

void ring_read(ShmQueue* q, char* dst, uint64_t n) {
  uint64_t cap = q->h->capacity;
  uint64_t head = q->h->head;
  uint64_t first = std::min(n, cap - head);
  memcpy(dst, q->data + head, first);
  if (n > first) memcpy(dst + first, q->data, n - first);
  q->h->head = (head + n) % cap;
  q->h->used -= n;
}

int wait_ms(pthread_cond_t* cv, pthread_mutex_t* mu, int timeout_ms) {
  if (timeout_ms < 0) return pthread_cond_wait(cv, mu);
  timespec ts;
  clock_gettime(CLOCK_REALTIME, &ts);
  ts.tv_sec += timeout_ms / 1000;
  ts.tv_nsec += (timeout_ms % 1000) * 1000000L;
  if (ts.tv_nsec >= 1000000000L) {
    ts.tv_sec += 1;
    ts.tv_nsec -= 1000000000L;
  }
  return pthread_cond_timedwait(cv, mu, &ts);
}

}  // namespace

void* pt_shmq_create(const char* name, uint64_t capacity) {
  size_t total = sizeof(ShmHeader) + capacity;
  shm_unlink(name);  // stale segment from a crashed run
  int fd = shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0) return nullptr;
  if (ftruncate(fd, static_cast<off_t>(total)) != 0) {
    close(fd);
    shm_unlink(name);
    return nullptr;
  }
  void* mem = mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED, fd,
                   0);
  close(fd);
  if (mem == MAP_FAILED) {
    shm_unlink(name);
    return nullptr;
  }
  auto* h = static_cast<ShmHeader*>(mem);
  pthread_mutexattr_t ma;
  pthread_mutexattr_init(&ma);
  pthread_mutexattr_setpshared(&ma, PTHREAD_PROCESS_SHARED);
  pthread_mutexattr_setrobust(&ma, PTHREAD_MUTEX_ROBUST);
  pthread_mutex_init(&h->mu, &ma);
  pthread_condattr_t ca;
  pthread_condattr_init(&ca);
  pthread_condattr_setpshared(&ca, PTHREAD_PROCESS_SHARED);
  pthread_cond_init(&h->not_full, &ca);
  pthread_cond_init(&h->not_empty, &ca);
  h->capacity = capacity;
  h->head = h->tail = h->used = 0;
  auto* q = new ShmQueue{h, static_cast<char*>(mem) + sizeof(ShmHeader),
                         total, name, true};
  return q;
}

void* pt_shmq_open(const char* name) {
  int fd = shm_open(name, O_RDWR, 0600);
  if (fd < 0) return nullptr;
  struct stat st;
  if (fstat(fd, &st) != 0) {
    close(fd);
    return nullptr;
  }
  void* mem = mmap(nullptr, static_cast<size_t>(st.st_size),
                   PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (mem == MAP_FAILED) return nullptr;
  auto* h = static_cast<ShmHeader*>(mem);
  auto* q = new ShmQueue{h, static_cast<char*>(mem) + sizeof(ShmHeader),
                         static_cast<size_t>(st.st_size), name, false};
  return q;
}

namespace {
int lock_robust(pthread_mutex_t* mu) {
  int rc = pthread_mutex_lock(mu);
  if (rc == EOWNERDEAD) {  // a worker died holding the lock
    pthread_mutex_consistent(mu);
    rc = 0;
  }
  return rc;
}
}  // namespace

// Push one message. Returns 0 ok, -1 timeout/error, -2 message too big.
int pt_shmq_push(void* handle, const void* buf, uint64_t len,
                 int timeout_ms) {
  auto* q = static_cast<ShmQueue*>(handle);
  uint64_t need = len + 8;
  if (need > q->h->capacity) return -2;
  if (lock_robust(&q->h->mu) != 0) return -1;
  while (q->h->capacity - q->h->used < need) {
    if (wait_ms(&q->h->not_full, &q->h->mu, timeout_ms) != 0) {
      pthread_mutex_unlock(&q->h->mu);
      return -1;
    }
  }
  ring_write(q, reinterpret_cast<const char*>(&len), 8);
  ring_write(q, static_cast<const char*>(buf), len);
  pthread_cond_signal(&q->h->not_empty);
  pthread_mutex_unlock(&q->h->mu);
  return 0;
}

// Pop one message into buf. Returns message length; if it exceeds
// buf_len the message is dropped and -2 returned; -1 on timeout.
int64_t pt_shmq_pop(void* handle, void* buf, uint64_t buf_len,
                    int timeout_ms) {
  auto* q = static_cast<ShmQueue*>(handle);
  if (lock_robust(&q->h->mu) != 0) return -1;
  while (q->h->used < 8) {
    if (wait_ms(&q->h->not_empty, &q->h->mu, timeout_ms) != 0) {
      pthread_mutex_unlock(&q->h->mu);
      return -1;
    }
  }
  uint64_t len;
  ring_read(q, reinterpret_cast<char*>(&len), 8);
  int64_t result;
  if (len > buf_len) {
    // drain and drop
    uint64_t remaining = len;
    char scratch[4096];
    while (remaining) {
      uint64_t chunk = std::min<uint64_t>(remaining, sizeof(scratch));
      ring_read(q, scratch, chunk);
      remaining -= chunk;
    }
    result = -2;
  } else {
    ring_read(q, static_cast<char*>(buf), len);
    result = static_cast<int64_t>(len);
  }
  pthread_cond_signal(&q->h->not_full);
  pthread_mutex_unlock(&q->h->mu);
  return result;
}

uint64_t pt_shmq_size(void* handle) {
  auto* q = static_cast<ShmQueue*>(handle);
  return q->h->used;
}

void pt_shmq_close(void* handle) {
  auto* q = static_cast<ShmQueue*>(handle);
  bool owner = q->owner;
  std::string name = q->name;
  munmap(q->h, q->total);
  if (owner) shm_unlink(name.c_str());
  delete q;
}

}  // extern "C"
