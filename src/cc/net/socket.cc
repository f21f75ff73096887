#include "net/socket.h"

#include <arpa/inet.h>
#include <errno.h>
#include <sys/un.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <stddef.h>
#include <string.h>
#include <sys/stat.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <unistd.h>

#include "bthread/execution_queue.h"
#include "bthread/executor.h"
#include "bthread/fiber.h"
#include "butil/flight.h"
#include "bvar/combiner.h"
#include "net/event_dispatcher.h"
#include "net/h2.h"

namespace brpc {

using butil::ResourcePool;

static ResourcePool<Socket>* pool() { return ResourcePool<Socket>::singleton(); }

static std::atomic<int64_t> g_active_sockets{0};
// Process-wide traffic totals as bvar combiners (per-thread cells,
// bvar/combiner.h): dispatcher and drainer threads each write their own
// cell instead of bouncing one shared cacheline per read/write/message
// (reference SocketVarsCollector, socket.h:126-157).
static bvar::Adder g_total_read_bytes;
static bvar::Adder g_total_written_bytes;
static bvar::Adder g_total_messages;

void Socket::GlobalTraffic(int64_t* nread, int64_t* nwritten, int64_t* nmsg) {
  if (nread) *nread = g_total_read_bytes.get();
  if (nwritten) *nwritten = g_total_written_bytes.get();
  if (nmsg) *nmsg = g_total_messages.get();
}

// Syscall attribution (ISSUE 15 / ROADMAP 1(e)): on this class of box a
// 64-byte loopback send costs the same ~260us as a 16KB one — syscall
// COUNT, not bytes, is the floor — so the frame-coalescing work needs
// these as its before/after metric.
static bvar::Adder g_read_syscalls;
static bvar::Adder g_write_syscalls;
static bvar::Adder g_batch_hits;    // writes coalesced into the TLS batch
static bvar::Adder g_batch_misses;  // writes that had to take their own path
// log2-bucketed bytes-per-write histogram; exact atomics, not combiner
// cells — 16 counters bumped once per SYSCALL are not a hot cacheline.
static std::atomic<int64_t> g_write_size_hist[Socket::kWriteHistBuckets];

static void note_write_syscall(ssize_t nw) {
  g_write_syscalls.add(1);
  if (nw <= 0) return;
  int idx = 0;
  uint64_t bound = 64;
  while (idx < Socket::kWriteHistBuckets - 1 && (uint64_t)nw > bound) {
    bound <<= 1;
    ++idx;
  }
  g_write_size_hist[idx].fetch_add(1, std::memory_order_relaxed);
}

void Socket::SyscallCounters(int64_t* read_sys, int64_t* write_sys,
                             int64_t* batch_hits, int64_t* batch_misses) {
  if (read_sys) *read_sys = g_read_syscalls.get();
  if (write_sys) *write_sys = g_write_syscalls.get();
  if (batch_hits) *batch_hits = g_batch_hits.get();
  if (batch_misses) *batch_misses = g_batch_misses.get();
}

int Socket::WriteSizeHist(int64_t* out, int n) {
  const int m = n < kWriteHistBuckets ? n : kWriteHistBuckets;
  for (int i = 0; i < m; ++i) {
    out[i] = g_write_size_hist[i].load(std::memory_order_relaxed);
  }
  return m;
}
// Per-socket unwritten-byte cap (reference FLAGS_socket_max_unwritten_bytes;
// EOVERCROWDED backpressure, socket.h:326-380).
static std::atomic<int64_t> g_overcrowded_limit{64 << 20};
// errno surfaced to on_failed when a backlog bound closes the socket
// (errors.py EOVERCROWDED).
constexpr int EOVERCROWDED_ERRNO = 1011;

int64_t Socket::active_count() { return g_active_sockets.load(std::memory_order_relaxed); }

void Socket::set_overcrowded_limit(int64_t bytes) {
  g_overcrowded_limit.store(bytes, std::memory_order_relaxed);
}
int64_t Socket::overcrowded_limit() {
  return g_overcrowded_limit.load(std::memory_order_relaxed);
}

static int make_nonblocking(int fd) {
  const int flags = fcntl(fd, F_GETFL, 0);
  return fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

// ---- versioned lifecycle ----

int Socket::Create(const SocketOptions& opts, SocketId* id_out) {
  uint32_t slot = 0;
  Socket* s = pool()->get_resource(&slot);
  if (s == nullptr) {
    BLOG(ERROR, "socket pool exhausted");
    return -1;
  }
  const uint64_t v = s->_vref.load(std::memory_order_acquire);
  const uint32_t version = (uint32_t)(v >> 32);  // even for a recycled slot
  s->_id = ((uint64_t)version << 32) | slot;
  s->_fd = opts.fd;
  s->_error_code = 0;
  s->_opts = opts;
  s->_out_buf.clear();
  s->_read_buf.clear();
  s->_parse = ParseState();
  s->_forced_protocol.store(-1, std::memory_order_relaxed);
  s->_filter_mode.store(false, std::memory_order_relaxed);  // recycled slot
  s->_write_stack.store(nullptr, std::memory_order_relaxed);
  s->_write_busy.store(false, std::memory_order_relaxed);
  s->_waiting_epollout.store(false, std::memory_order_relaxed);
  s->_pending_write.store(0, std::memory_order_relaxed);
  s->_fifo_q.store(nullptr, std::memory_order_relaxed);  // detached in cleanup
  s->_fifo_pending_bytes.store(0, std::memory_order_relaxed);
  s->_nread.store(0, std::memory_order_relaxed);
  s->_nwritten.store(0, std::memory_order_relaxed);
  s->_nmsg.store(0, std::memory_order_relaxed);
  s->_read_sys.store(0, std::memory_order_relaxed);
  s->_write_sys.store(0, std::memory_order_relaxed);
  s->FillRemoteAddr();
  if (opts.on_response != nullptr && !opts.response_inline) {
    // rpc client socket: responses ride the FIFO lane; create it HERE,
    // before the fd is armed, so SetFailed can never observe a missing
    // lane and deliver on_failed ahead of queued responses
    s->EnsureFifoLane();
  }
  // Publish with one "registration" ref (dropped by SetFailed).
  s->_vref.store(((uint64_t)version << 32) | 1, std::memory_order_release);
  g_active_sockets.fetch_add(1, std::memory_order_relaxed);
  *id_out = s->_id;
  butil::flight::record(butil::flight::EV_SOCK_CREATE, s->_id, opts.fd);
  if (opts.fd >= 0) {
    make_nonblocking(opts.fd);
    if (!opts.is_listener) {
      const int one = 1;
      setsockopt(opts.fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    }
    if (!opts.defer_register &&
        EventDispatcher::GetDispatcher(opts.fd)->AddConsumer(s->_id, opts.fd) != 0) {
      SetFailed(s->_id, errno);
      return -1;
    }
  }
  return 0;
}

Socket* Socket::Address(SocketId id) {
  Socket* s = pool()->address((uint32_t)id);
  if (s == nullptr) return nullptr;
  uint64_t v = s->_vref.load(std::memory_order_acquire);
  const uint32_t want = (uint32_t)(id >> 32);
  while (true) {
    if ((uint32_t)(v >> 32) != want) return nullptr;
    if (s->_vref.compare_exchange_weak(v, v + 1, std::memory_order_acq_rel,
                                       std::memory_order_acquire)) {
      return s;
    }
  }
}

bool Socket::failed() const {
  // Alive iff the packed version still equals this socket's id version
  // (SetFailed bumps it to id_version+1, recycle to id_version+2).
  return (uint32_t)(_id >> 32) !=
         (uint32_t)(_vref.load(std::memory_order_acquire) >> 32);
}

int Socket::SetFailed(SocketId id, int error_code) {
  Socket* s = Socket::Address(id);
  if (s == nullptr) return -1;
  const uint32_t want = (uint32_t)(id >> 32);
  uint64_t v = s->_vref.load(std::memory_order_acquire);
  bool won = false;
  while ((uint32_t)(v >> 32) == want) {
    const uint64_t nv = ((uint64_t)(want + 1) << 32) | (uint32_t)v;
    if (s->_vref.compare_exchange_weak(v, nv, std::memory_order_acq_rel,
                                       std::memory_order_acquire)) {
      won = true;
      break;
    }
  }
  if (won) {
    s->_error_code = error_code;
    butil::flight::record(butil::flight::EV_SOCK_FAILED, id, error_code);
    // a KeepWrite fiber parked on writability must not sleep through the
    // failure (the dispatcher is being detached; no EPOLLOUT will come)
    s->_epollout_butex.value.fetch_add(1, std::memory_order_acq_rel);
    s->_epollout_butex.wake_all();
    if (s->_fd >= 0) EventDispatcher::GetDispatcher(s->_fd)->RemoveConsumer(s->_fd);
    if (s->_opts.on_failed != nullptr) {
      auto* q = s->_fifo_q.load(std::memory_order_acquire);
      if (q != nullptr) {
        // The failure notification must be delivered AFTER messages
        // already queued on the FIFO lane: a server that replies and
        // closes must not make the client see EFAILEDSOCKET before the
        // reply it already received (inline delivery used to give this
        // ordering for free).  We still hold the Address reference, so
        // cleanup's destroy() cannot have run: execute() is safe.
        struct FailNote {
          SocketFailedCallback cb;
          SocketId id;
          int err;
          void* user;
        };
        auto* note = new FailNote{s->_opts.on_failed, id, error_code,
                                  s->_opts.user};
        q->execute(bthread::TaskNode{
            [](void* arg) {
              auto* n = (FailNote*)arg;
              n->cb(n->id, n->err, n->user);
              delete n;
            },
            note});
      } else {
        s->_opts.on_failed(id, error_code, s->_opts.user);
      }
    }
    s->Dereference();  // drop the registration ref
  }
  s->Dereference();  // drop the Address ref
  return won ? 0 : -1;
}

void Socket::CloseFd() {
  if (_fd >= 0) {
    butil::flight::record(butil::flight::EV_SOCK_CLOSE, _id, _fd);
    close(_fd);
    _fd = -1;
  }
}

void Socket::Dereference() {
  const uint64_t v = _vref.fetch_sub(1, std::memory_order_acq_rel);
  if ((uint32_t)v != 1) return;
  // Last ref: recycle.  Version is odd (SetFailed ran); make it even for the
  // next Create so the slot can be reused with a fresh id.
  const uint32_t ver = (uint32_t)(v >> 32);
  CloseFd();
  WriteRequest* head = _write_stack.exchange(nullptr, std::memory_order_acquire);
  while (head != nullptr) {
    WriteRequest* next = head->next;
    delete head;
    head = next;
  }
  _out_buf.clear();
  _read_buf.clear();
  // no references can exist here (last deref): the session dies with us
  h2::H2Session* sess = _h2_session.exchange(nullptr,
                                             std::memory_order_acq_rel);
  delete sess;
  auto* q = _fifo_q.exchange(nullptr, std::memory_order_acq_rel);
  if (q != nullptr) {
    // destroy(): the (possibly currently-running) drainer consumes every
    // leftover message, then the queue deletes itself — no blocking, no
    // spinning, safe even when this Dereference is running INSIDE one of
    // the queue's own callbacks.
    q->destroy();
  }
  g_active_sockets.fetch_sub(1, std::memory_order_relaxed);
  const uint32_t slot = (uint32_t)_id;
  _vref.store((uint64_t)(ver + 1) << 32, std::memory_order_release);
  pool()->return_resource(slot);
}

void Socket::FillRemoteAddr() {
  _remote_ip[0] = 0;
  _remote_port = 0;
  if (_fd < 0) return;
  sockaddr_storage ss;
  socklen_t len = sizeof(ss);
  if (getpeername(_fd, (sockaddr*)&ss, &len) == 0) {
    if (ss.ss_family == AF_INET) {
      auto* a = (sockaddr_in*)&ss;
      inet_ntop(AF_INET, &a->sin_addr, _remote_ip, sizeof(_remote_ip));
      _remote_port = ntohs(a->sin_port);
    } else if (ss.ss_family == AF_INET6) {
      auto* a = (sockaddr_in6*)&ss;
      inet_ntop(AF_INET6, &a->sin6_addr, _remote_ip, sizeof(_remote_ip));
      _remote_port = ntohs(a->sin6_port);
    }
  }
}

// ---- write path (wait-free producers, single drainer) ----

// Dispatch-loop write batching: while DispatchMessages drains one read
// buffer, writes issued from that same thread to that same socket (inline
// native handlers' responses; inline response callbacks sending the next
// pipelined request) coalesce into one buffer flushed with a single
// syscall after the loop.  On a pipelined connection this turns K
// responses = K writev calls into 1, which is the difference between
// syscall-bound and memory-bound on small frames.
static thread_local Socket* tls_batch_socket = nullptr;
static thread_local butil::IOBuf* tls_batch_buf = nullptr;

butil::IOBuf* Socket::CurrentBatchFor(SocketId sid, size_t more) {
  Socket* s = tls_batch_socket;
  if (s == nullptr || s->_id != sid || s->failed()) return nullptr;
  const int64_t limit = g_overcrowded_limit.load(std::memory_order_relaxed);
  if (limit > 0 &&
      s->_pending_write.load(std::memory_order_relaxed) +
              (int64_t)tls_batch_buf->size() + (int64_t)more > limit) {
    return nullptr;  // stalled peer: Write path drops with EOVERCROWDED
  }
  g_batch_hits.add(1);
  return tls_batch_buf;
}

int Socket::Write(butil::IOBuf&& data, bool admitted) {
  const int64_t limit =
      admitted ? 0 : g_overcrowded_limit.load(std::memory_order_relaxed);
  if (tls_batch_socket == this) {
    // same failed() contract as the direct path; enqueued-then-failed
    // still drops data with only on_failed as the signal (identical to
    // the MPSC-stack path and the reference's WriteRequest semantics)
    if (failed()) return -1;
    // batch bytes are accounted when the guard flushes through Write;
    // the check here includes them so a stalled peer can't hide behind
    // the thread-local batch
    if (limit > 0 &&
        _pending_write.load(std::memory_order_relaxed) +
                (int64_t)tls_batch_buf->size() + (int64_t)data.size() > limit) {
      return -2;  // EOVERCROWDED
    }
    tls_batch_buf->append(std::move(data));
    g_batch_hits.add(1);
    return 0;
  }
  if (failed()) return -1;
  if (limit > 0 &&
      _pending_write.load(std::memory_order_relaxed) + (int64_t)data.size() >
          limit) {
    return -2;  // EOVERCROWDED
  }
  // `admitted` writes are the batch's own deferred flush — one write
  // carrying many coalesced frames — so only unadmitted direct writes
  // count as coalescing misses.
  if (!admitted) g_batch_misses.add(1);
  _pending_write.fetch_add((int64_t)data.size(), std::memory_order_relaxed);
  auto* req = new WriteRequest{std::move(data), nullptr};
  WriteRequest* old = _write_stack.load(std::memory_order_relaxed);
  do {
    req->next = old;
  } while (!_write_stack.compare_exchange_weak(old, req,
                                               std::memory_order_seq_cst,
                                               std::memory_order_relaxed));
  if (!_write_busy.exchange(true, std::memory_order_seq_cst)) {
    // We own the drain: write inline once on the caller thread (the wait-free
    // fast path — one syscall in caller context, reference socket.cpp:1748).
    DrainWriteQueue(false);
  }
  return 0;
}

void Socket::DrainWriteQueue(bool from_keepwrite) {
  while (true) {
    if (failed()) {
      int64_t dropped = (int64_t)_out_buf.size();
      WriteRequest* head = _write_stack.exchange(nullptr, std::memory_order_acquire);
      while (head != nullptr) {
        WriteRequest* next = head->next;
        dropped += (int64_t)head->data.size();
        delete head;
        head = next;
      }
      _out_buf.clear();
      _pending_write.fetch_sub(dropped, std::memory_order_relaxed);
      _write_busy.store(false, std::memory_order_seq_cst);
      return;
    }
    // Move queued requests into _out_buf in FIFO order (zero-copy).
    WriteRequest* head = _write_stack.exchange(nullptr, std::memory_order_seq_cst);
    WriteRequest* prev = nullptr;
    while (head != nullptr) {
      WriteRequest* next = head->next;
      head->next = prev;
      prev = head;
      head = next;
    }
    while (prev != nullptr) {
      _out_buf.append(std::move(prev->data));
      WriteRequest* next = prev->next;
      delete prev;
      prev = next;
    }
    if (_out_buf.empty()) {
      // Release with recheck (single-drainer protocol, see execution_queue.h).
      _write_busy.store(false, std::memory_order_seq_cst);
      if (_write_stack.load(std::memory_order_seq_cst) != nullptr &&
          !_write_busy.exchange(true, std::memory_order_seq_cst)) {
        continue;
      }
      return;
    }
    while (!_out_buf.empty()) {
      butil::flight::record(butil::flight::EV_WRITE_ENTER, _id,
                            (int64_t)_out_buf.size());
      const ssize_t nw = _out_buf.cut_into_file_descriptor(_fd);
      note_write_syscall(nw);
      _write_sys.fetch_add(1, std::memory_order_relaxed);
      butil::flight::record(butil::flight::EV_WRITE_EXIT, _id,
                            nw >= 0 ? (int64_t)nw : (int64_t)-errno);
      if (nw >= 0) {
        _nwritten.fetch_add(nw, std::memory_order_relaxed);
        _pending_write.fetch_sub(nw, std::memory_order_relaxed);
        g_total_written_bytes.add(nw);
        continue;
      }
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        // Hand the remainder to the KeepWrite FIBER: it parks on the
        // writability butex until OnWritable (or SetFailed) wakes it,
        // then continues draining — the reference's KeepWrite bthread
        // (socket.cpp:1800-1920) on the coroutine runtime.  Snapshot the
        // butex word BEFORE the epoll re-arm so an EPOLLOUT edge firing
        // between the re-arm and the fiber's park is never missed (the
        // wake bumps the word; the park's expected-value check fails and
        // the fiber proceeds immediately).
        Socket* self = Socket::Address(_id);
        if (self == nullptr) {
          // lost a race with SetFailed: the failed() branch on the next
          // KeepWrite pass would clean up, but there is no next pass —
          // drop the leftovers now
          int64_t dropped = (int64_t)_out_buf.size();
          _out_buf.clear();
          _pending_write.fetch_sub(dropped, std::memory_order_relaxed);
          _write_busy.store(false, std::memory_order_seq_cst);
          return;
        }
        const int32_t seq =
            _epollout_butex.value.load(std::memory_order_acquire);
        _waiting_epollout.store(true, std::memory_order_seq_cst);
        EventDispatcher::GetDispatcher(_fd)->Rearm(_id, _fd);
        KeepWriteFiber(self, seq).spawn();
        return;
      }
      SetFailed(_id, errno);
      break;  // failed() branch cleans up on the next loop
    }
  }
}

void Socket::OnWritable() {
  if (_waiting_epollout.exchange(false, std::memory_order_seq_cst)) {
    // Wake the parked KeepWrite fiber (resumes on the executor).
    _epollout_butex.value.fetch_add(1, std::memory_order_acq_rel);
    _epollout_butex.wake_all();
  }
}

// KeepWrite: park until writable (or failed), then resume the drain.
// Holds a socket reference for its whole life, so the slot cannot recycle
// under the parked frame; the 500ms timeout is a safety net that rechecks
// failed() even if a wake was somehow lost.
bthread::Fiber Socket::KeepWriteFiber(Socket* self, int32_t seq) {
  co_await self->_epollout_butex.wait(seq, 500 * 1000);
  self->DrainWriteQueue(true);
  self->Dereference();
}

// ---- read path ----

struct PendingMessage {
  SocketId sid;
  int kind;
  std::string meta;
  butil::IOBuf* body;
  MessageCallback cb;
  void* user;
  UpcallTicket ticket;  // stamped here, at the cut (net/rpc.h)
};

static void run_message_task(void* arg) {
  auto* m = (PendingMessage*)arg;
  UpcallScope scope(&m->ticket);
  m->cb(m->sid, m->kind, m->meta.data(), m->meta.size(), m->body, m->user);
  delete m;  // callback owns *body (freed via C ABI)
}

void Socket::OnReadable() {
  if (_opts.is_listener) {
    DoAcceptLoop();
    return;
  }
  const bool filtered = _filter_mode.load(std::memory_order_acquire);
  while (true) {
    // Filter mode (in-socket TLS): ciphertext reads go into a LOCAL
    // portal and straight to the filter callback — _read_buf holds ONLY
    // injected plaintext, so split plaintext frames can never
    // interleave with later ciphertext reads.
    butil::IOPortal local;
    butil::IOPortal& buf = filtered ? local : _read_buf;
    butil::flight::record(butil::flight::EV_READ_ENTER, _id);
    const ssize_t nr = buf.append_from_file_descriptor(_fd, 256 * 1024);
    g_read_syscalls.add(1);
    _read_sys.fetch_add(1, std::memory_order_relaxed);
    butil::flight::record(butil::flight::EV_READ_EXIT, _id,
                          nr >= 0 ? (int64_t)nr : (int64_t)-errno);
    if (nr > 0) {
      _nread.fetch_add(nr, std::memory_order_relaxed);
      g_total_read_bytes.add(nr);
      if (filtered) {
        DeliverFiltered(&local);
      } else {
        DispatchMessages();
      }
      // Edge-triggered: must keep reading until EAGAIN.
      continue;
    }
    if (nr == 0) {
      SetFailed(_id, 0);  // clean EOF
      return;
    }
    if (errno == EAGAIN || errno == EWOULDBLOCK) return;
    if (errno == EINTR) continue;
    SetFailed(_id, errno);
    return;
  }
}

void Socket::DeliverFiltered(butil::IOPortal* cipher) {
  if (_opts.on_message == nullptr) {
    cipher->clear();
    return;
  }
  const int64_t bytes = (int64_t)cipher->size() + 256;
  auto* pm = new PendingMessage{_id, MSG_FILTERED, std::string(),
                                new butil::IOBuf(std::move(*cipher)),
                                _opts.on_message, _opts.user};
  // the FIFO lane keeps ciphertext chunks ordered for the TLS engine
  // (and orders them ahead of the failure notification)
  if (!FifoSubmit(run_message_task, pm, bytes)) {
    delete pm->body;
    delete pm;
  }
}

void Socket::InjectBytes(butil::IOBuf&& data) {
  // dispatcher-loop thread only (EventDispatcher::RunOnLoop): append the
  // filter's plaintext and run the normal parse/dispatch over it
  _read_buf.append(std::move(data));
  DispatchMessages();
}

struct FifoTask {
  Socket* owner;
  int64_t bytes;
  bthread::TaskFn fn;
  void* arg;
};

static void run_fifo_task(void* a) {
  auto* w = (FifoTask*)a;
  // release backlog credit BEFORE the callback (its work is the
  // consumer's cost, not queued bytes) — same discipline as
  // run_message_task
  w->owner->fifo_release(w->bytes);
  w->fn(w->arg);
  delete w;
}

bthread::ExecutionQueue<bthread::TaskNode>* Socket::EnsureFifoLane() {
  auto* q = _fifo_q.load(std::memory_order_acquire);
  if (q == nullptr) {
    // Creation sites: Create() (before the fd is armed — no concurrent
    // SetFailed can exist yet) and the dispatcher thread.  Without the
    // eager Create()-time lane for response sockets, a cross-thread
    // SetFailed racing the FIRST response's lazy creation could read
    // nullptr and deliver on_failed inline, overtaking that response.
    q = new bthread::ExecutionQueue<bthread::TaskNode>(
        bthread::Executor::global(),
        [](bthread::TaskNode& t) { t.fn(t.arg); });
    _fifo_q.store(q, std::memory_order_release);
  }
  return q;
}

bool Socket::FifoSubmit(bthread::TaskFn fn, void* arg, int64_t bytes) {
  auto* q = EnsureFifoLane();
  const int64_t limit = g_overcrowded_limit.load(std::memory_order_relaxed);
  if (bytes > 0 && limit > 0 &&
      _fifo_pending_bytes.load(std::memory_order_relaxed) + bytes > limit) {
    BLOG(WARNING, "socket %llu FIFO backlog over %lld bytes, closing",
         (unsigned long long)_id, (long long)limit);
    SetFailed(_id, EOVERCROWDED_ERRNO);
    return false;
  }
  if (bytes == 0) {
    // no accounting to release: skip the wrapper allocation entirely
    // (the rpc response hot path runs here once per call)
    q->execute(bthread::TaskNode{fn, arg});
    return true;
  }
  _fifo_pending_bytes.fetch_add(bytes, std::memory_order_relaxed);
  q->execute(bthread::TaskNode{run_fifo_task,
                               new FifoTask{this, bytes, fn, arg}});
  return true;
}

// Consecutive MSG_H2 frames coalesced into ONE FIFO delivery: at ~6
// frames per unary gRPC call, per-frame lane tasks + Python upcalls +
// GIL cycles were a visible slice of the h2 floor.  meta = the 9-byte
// frame headers concatenated (self-describing: payload length is the
// first 3 bytes of each header), body = payloads in order; h2.py
// feed_frames() walks them.
struct H2Accum {
  Socket* s = nullptr;
  std::string meta;
  butil::IOBuf body;
  int count = 0;

  void add(ParsedMessage& m) {
    meta.append(m.meta);
    body.append(std::move(m.body));
    ++count;
  }
  // Returns false when the socket failed (delivery impossible).
  bool flush() {
    if (count == 0) return true;
    const int64_t bytes = (int64_t)(meta.size() + body.size() + 256);
    auto* pm = new PendingMessage{s->id(), MSG_H2, std::move(meta),
                                  new butil::IOBuf(std::move(body)),
                                  s->_opts.on_message, s->_opts.user};
    meta.clear();
    body.clear();
    count = 0;
    if (!s->FifoSubmit(run_message_task, pm, bytes)) {
      delete pm->body;
      delete pm;
      return false;
    }
    return true;
  }
};

void Socket::DispatchMessages() {
  ParsedMessage msg;
  H2Accum h2acc;
  h2acc.s = this;
  if (_parse.detected == -1) {
    const int forced = _forced_protocol.load(std::memory_order_acquire);
    if (forced >= 0) _parse.detected = forced;
  }
  // arm the write batch for the duration of this drain (flushed by the
  // RAII guard on every exit path)
  butil::IOBuf batch_out;
  struct BatchGuard {
    Socket* s;
    butil::IOBuf* buf;
    ~BatchGuard() {
      tls_batch_socket = nullptr;
      tls_batch_buf = nullptr;
      if (!buf->empty()) s->Write(std::move(*buf), /*admitted=*/true);
    }
  } guard{this, &batch_out};
  tls_batch_socket = this;
  tls_batch_buf = &batch_out;
  while (true) {
    // TRPC in-place fast path: header+meta viewed in the read block —
    // no meta copy, no ParsedMessage round (a top-3 hot-path cost).
    // Falls back to the generic parser for split frames / other
    // protocols with nothing consumed.
    if (_parse.detected == MSG_TRPC && !_opts.native_echo &&
        (_opts.enable_rpc_dispatch || _opts.on_response != nullptr ||
         _opts.on_response_flat != nullptr)) {
      const char* mview = nullptr;
      size_t mlen = 0;
      const char* bview = nullptr;
      uint64_t blen = 0;
      uint64_t total = 0;
      const ParseResult r = parse_trpc_peek(&_read_buf, &mview, &mlen,
                                            &bview, &blen, &total);
      if (r == PARSE_NEED_MORE) {
        h2acc.flush();
        return;
      }
      if (r == PARSE_ERROR) {
        BLOG(WARNING, "parse error on socket %llu, closing",
             (unsigned long long)_id);
        h2acc.flush();  // frames parsed before the error stay ordered
        SetFailed(_id, EPROTO);  // ...ahead of the failure notification
        return;
      }
      if (mview != nullptr) {
        _nmsg.fetch_add(1, std::memory_order_relaxed);
        g_total_messages.add(1);
        // body also contiguous (the common case for small frames):
        // zero-ref flat dispatch — no pops yet, no body IOBuf, no block
        // refs; the response is staged flat into the write batch
        if (bview != nullptr || blen == 0) {
          if (TryDispatchTrpcFlat(_id, _opts, mview, mlen,
                                  bview != nullptr ? bview : "",
                                  (size_t)blen)) {
            _read_buf.pop_front(total);
            continue;
          }
        }
        // IOBuf path: take ONE guard ref so the meta view survives the
        // pops, then cut the body out
        butil::IOBuf meta_guard;  // NOT the write-batch RAII guard above
        meta_guard.add_block_ref(_read_buf.backing_block(0));
        _read_buf.pop_front(kTrpcHeaderLen + mlen);
        msg.body.clear();
        _read_buf.cutn(&msg.body, blen);
        if (TryDispatchTrpc(_id, _opts, mview, mlen, &msg.body)) {
          continue;
        }
        // not fast-dispatchable (stream frame, unknown method, generic
        // Python path): materialize the meta and take generic delivery
        msg.kind = MSG_TRPC;
        msg.meta.assign(mview, mlen);
        meta_guard.clear();
        goto generic_delivery;
      }
      // mview==nullptr: split frame or protocol re-detection — fall
      // through to the full parser
    }
    {
    const ParseResult r = parse_message(&_read_buf, &_parse, &msg);
    if (r == PARSE_NEED_MORE) {
      h2acc.flush();
      return;
    }
    if (r == PARSE_ERROR) {
      BLOG(WARNING, "parse error on socket %llu, closing",
           (unsigned long long)_id);
      h2acc.flush();
      SetFailed(_id, EPROTO);
      return;
    }
    }
    _nmsg.fetch_add(1, std::memory_order_relaxed);
    g_total_messages.add(1);
    if (_opts.native_echo && msg.kind == MSG_TRPC) {
      // Native echo service: reflect the frame without leaving C++.
      butil::IOBuf out;
      char hdr[kTrpcHeaderLen];
      make_trpc_header(hdr, (uint32_t)msg.meta.size(), msg.body.size());
      out.append(hdr, sizeof(hdr));
      out.append(msg.meta);
      out.append(std::move(msg.body));
      Write(std::move(out));
      msg.body.clear();
      continue;
    }
    if (msg.kind == MSG_TRPC &&
        (_opts.enable_rpc_dispatch || _opts.on_response != nullptr ||
         _opts.on_response_flat != nullptr)) {
      // Native unary hot path (net/rpc.h): parse meta, method lookup and
      // response packing in C++; Python sees pre-parsed requests only.
      // The gate must match the peek-path gate above: a flat-only client
      // still needs split-frame responses delivered (rpc.cc's flat-only
      // to_string branch), not dropped at generic_delivery.
      if (TryDispatchTrpc(_id, _opts, msg.meta.data(), msg.meta.size(),
                          &msg.body)) {
        continue;
      }
      // false: body untouched, fall through to the generic path
    }
  generic_delivery:
    if (msg.kind == MSG_H2 && _opts.h2_native) {
      // native h2 data plane: frames feed the in-socket session
      // (framing/HPACK/flow control/gRPC dispatch in C++; Python is
      // upcalled per message, not per frame)
      h2::H2Session* sess = _h2_session.load(std::memory_order_relaxed);
      if (sess == nullptr) {
        sess = new h2::H2Session(_id);
        _h2_session.store(sess, std::memory_order_release);
      }
      if (!sess->OnFrames(msg.meta.data(), msg.meta.size(), &msg.body)) {
        BLOG(WARNING, "h2 session error on socket %llu, closing",
             (unsigned long long)_id);
        msg.body.clear();
        // flush the batch NOW (it holds the session's GOAWAY): the
        // guard's exit-path flush would be rejected once the socket is
        // failed, and the peer would never learn why it died.  Clear
        // the TLS batch pointers FIRST (the guard's order): Write's
        // drain can re-enter dispatch-adjacent code that must not see
        // a half-flushed batch as current.
        tls_batch_socket = nullptr;
        tls_batch_buf = nullptr;
        if (!batch_out.empty()) Write(std::move(batch_out), true);
        SetFailed(_id, EPROTO);
        return;
      }
      msg.body.clear();
      continue;
    }
    if (_opts.on_message == nullptr) {
      msg.body.clear();
      continue;
    }
    if (kind_requires_fifo(msg.kind)) {
      if (msg.kind == MSG_H2) {
        // coalesce consecutive h2 frames; bounded so one drain can't
        // build an unbounded delivery
        h2acc.add(msg);
        if (h2acc.count >= 64 || h2acc.body.size() > (256 << 10)) {
          if (!h2acc.flush()) return;
        }
        continue;
      }
      // a different FIFO kind: deliver pending h2 frames FIRST so the
      // lane preserves arrival order
      if (!h2acc.flush()) return;
      // RESP/memcache pipelining, h2 HPACK + stream state, thrift/mongo
      // reply order and raw streaming all make per-connection FIFO part
      // of the protocol contract.  Deliver through this socket's
      // ExecutionQueue: order is preserved (serialized drain) but the
      // GIL-bound Python callback runs on an executor worker, not the
      // dispatcher thread — one slow connection can no longer stall the
      // whole event loop (the reference's per-stream ExecutionQueue,
      // stream_impl.h:133, in the socket's FIFO slot).
      // read-side EOVERCROWDED: inline delivery used to throttle reads
      // naturally; a queued lane needs an explicit bound or a fast peer
      // with a slow consumer grows memory without limit (same limit as
      // the write side)
      const int64_t msg_bytes =
          (int64_t)(msg.meta.size() + msg.body.size() + 256);
      auto* pm = new PendingMessage{_id, msg.kind, std::move(msg.meta),
                                    new butil::IOBuf(std::move(msg.body)),
                                    _opts.on_message, _opts.user};
      if (!FifoSubmit(run_message_task, pm, msg_bytes)) {
        delete pm->body;   // overcrowded: socket failed, task not queued
        delete pm;
        return;
      }
      continue;
    }
    if (!h2acc.flush()) return;   // order vs non-FIFO deliveries too
    auto* pm = new PendingMessage{_id, msg.kind, std::move(msg.meta),
                                  new butil::IOBuf(std::move(msg.body)),
                                  _opts.on_message, _opts.user};
    bthread::Executor::global()->submit(run_message_task, pm);
  }
}

void Socket::DoAcceptLoop() {
  while (true) {
    sockaddr_storage ss;
    socklen_t len = sizeof(ss);
    const int fd = accept4(_fd, (sockaddr*)&ss, &len, SOCK_NONBLOCK | SOCK_CLOEXEC);
    if (fd < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) return;
      if (errno == EINTR || errno == ECONNABORTED) continue;
      BLOG(WARNING, "accept4 failed: %d", errno);
      return;
    }
    SocketOptions copts = _opts;
    copts.fd = fd;
    copts.is_listener = false;
    copts.defer_register = true;
    SocketId cid;
    if (Socket::Create(copts, &cid) != 0) continue;
    // Callback BEFORE the fd can generate events: the consumer registers
    // its handler for cid here, so the first message can't outrun it.
    if (_opts.on_accepted != nullptr) {
      _opts.on_accepted(_id, cid, _opts.user);
    }
    if (EventDispatcher::GetDispatcher(fd)->AddConsumer(cid, fd) != 0) {
      Socket::SetFailed(cid, errno);
    }
  }
}

// ---- connect / listen ----

// "unix:/path" addresses select AF_UNIX (reference butil/unix_socket.*;
// EndPoint UDS support, SURVEY §2.1) — same Socket machinery, different
// address family.
static socklen_t fill_sockaddr_un(const char* path, sockaddr_un* sa) {
  memset(sa, 0, sizeof(*sa));
  sa->sun_family = AF_UNIX;
  const size_t n = strlen(path);
  if (n >= sizeof(sa->sun_path)) return 0;  // overlong path
  memcpy(sa->sun_path, path, n);
  return (socklen_t)(offsetof(sockaddr_un, sun_path) + n + 1);
}

static int connect_unix(const char* path, const SocketOptions& opts,
                        SocketId* id) {
  sockaddr_un sa;
  const socklen_t len = fill_sockaddr_un(path, &sa);
  if (len == 0) return -1;
  const int fd = socket(AF_UNIX, SOCK_STREAM | SOCK_CLOEXEC, 0);
  if (fd < 0) return -1;
  if (connect(fd, (sockaddr*)&sa, len) != 0) {
    close(fd);
    return -1;
  }
  SocketOptions o = opts;
  o.fd = fd;
  return Socket::Create(o, id);
}

static int listen_unix(const char* path, const SocketOptions& opts,
                       SocketId* id, int* bound_port) {
  sockaddr_un sa;
  const socklen_t len = fill_sockaddr_un(path, &sa);
  if (len == 0) return -1;
  // Remove ONLY a stale socket file: unlinking whatever happens to live
  // at a typo'd path (a regular file, a directory) would destroy user
  // data before bind even fails.
  struct stat st;
  if (lstat(path, &st) == 0) {
    if (!S_ISSOCK(st.st_mode)) {
      errno = EEXIST;
      return -1;
    }
    unlink(path);
  }
  const int fd = socket(AF_UNIX, SOCK_STREAM | SOCK_CLOEXEC, 0);
  if (fd < 0) return -1;
  if (bind(fd, (sockaddr*)&sa, len) != 0 || listen(fd, 1024) != 0) {
    close(fd);
    return -1;
  }
  if (bound_port != nullptr) *bound_port = 0;  // no port space on UDS
  SocketOptions o = opts;
  o.fd = fd;
  o.is_listener = true;
  return Socket::Create(o, id);
}

int Connect(const char* host, int port, const SocketOptions& opts, SocketId* id) {
  if (strncmp(host, "unix:", 5) == 0) {
    return connect_unix(host + 5, opts, id);
  }
  addrinfo hints = {};
  hints.ai_family = AF_UNSPEC;
  hints.ai_socktype = SOCK_STREAM;
  addrinfo* res = nullptr;
  char portstr[16];
  snprintf(portstr, sizeof(portstr), "%d", port);
  if (getaddrinfo(host, portstr, &hints, &res) != 0 || res == nullptr) {
    return -1;
  }
  int fd = -1;
  for (addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
    fd = socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
    if (fd < 0) continue;
    if (connect(fd, ai->ai_addr, ai->ai_addrlen) == 0) break;
    close(fd);
    fd = -1;
  }
  freeaddrinfo(res);
  if (fd < 0) return -1;
  SocketOptions o = opts;
  o.fd = fd;
  return Socket::Create(o, id);
}

int Listen(const char* addr, int port, const SocketOptions& opts, SocketId* id,
           int* bound_port) {
  if (addr != nullptr && strncmp(addr, "unix:", 5) == 0) {
    return listen_unix(addr + 5, opts, id, bound_port);
  }
  const int fd = socket(AF_INET, SOCK_STREAM | SOCK_CLOEXEC, 0);
  if (fd < 0) return -1;
  const int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in sa = {};
  sa.sin_family = AF_INET;
  sa.sin_port = htons((uint16_t)port);
  if (addr == nullptr || addr[0] == 0) {
    sa.sin_addr.s_addr = htonl(INADDR_ANY);
  } else if (inet_pton(AF_INET, addr, &sa.sin_addr) != 1) {
    close(fd);
    return -1;
  }
  if (bind(fd, (sockaddr*)&sa, sizeof(sa)) != 0 || listen(fd, 1024) != 0) {
    close(fd);
    return -1;
  }
  if (bound_port != nullptr) {
    socklen_t len = sizeof(sa);
    getsockname(fd, (sockaddr*)&sa, &len);
    *bound_port = ntohs(sa.sin_port);
  }
  SocketOptions o = opts;
  o.fd = fd;
  o.is_listener = true;
  return Socket::Create(o, id);
}

}  // namespace brpc
