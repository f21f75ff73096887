// Native CPU profiler — SIGPROF stack sampling with pprof-compatible
// output (VERDICT r2 task 10; reference builtin/hotspots_service.cpp:36
// drives gperftools' ProfilerStart the same way).
//
// The Python-frame profiler (builtin/profiler.py) cannot see the
// dispatcher/executor/drainer threads where the hot path actually runs.
// This sampler can: ITIMER_PROF delivers SIGPROF on whichever thread is
// burning CPU; the handler captures a backtrace into a fixed ring.
// Output formats:
//   - legacy pprof CPU profile binary (header/sample/trailer words +
//     /proc/self/maps), readable by `pprof ./binary profile` and modern
//     `pprof -http` alike;
//   - folded stacks text ("sym1;sym2;sym3 count"), flamegraph input and
//     human-greppable.
//
// backtrace(3) in a signal handler: formally unsafe (first call may
// allocate inside the unwinder), standard profiler practice regardless —
// we force that initialization in prof_start before arming the timer,
// exactly like gperftools.
#include "butil/common.h"

#include <dlfcn.h>
#include <execinfo.h>
#include <signal.h>
#include <stdio.h>
#include <string.h>
#include <sys/time.h>

#include <atomic>
#include <cstdint>
#include <map>
#include <string>
#include <vector>

namespace butil {

namespace {

constexpr int kMaxDepth = 48;
constexpr int kMaxSamples = 65536;

struct Sample {
  std::atomic<bool> ready{false};  // slot fully written (handler races stop)
  int depth;
  void* pcs[kMaxDepth];
};

Sample* g_samples = nullptr;            // allocated at first start
std::atomic<int> g_count{0};
std::atomic<bool> g_running{false};
int g_period_us = 10000;
struct sigaction g_old_action;

void prof_handler(int, siginfo_t*, void*) {
  if (!g_running.load(std::memory_order_relaxed)) return;
  const int i = g_count.fetch_add(1, std::memory_order_relaxed);
  if (i >= kMaxSamples) {
    g_count.store(kMaxSamples, std::memory_order_relaxed);
    return;
  }
  Sample& s = g_samples[i];
  const int n = backtrace(s.pcs, kMaxDepth);
  // drop the top frames (this handler + the signal trampoline)
  const int skip = n > 2 ? 2 : 0;
  s.depth = n - skip;
  if (skip > 0) {
    memmove(s.pcs, s.pcs + skip, sizeof(void*) * (size_t)s.depth);
  }
  // publish LAST: readers after prof_stop skip slots whose fill was
  // preempted mid-write (the index was claimed before the data landed)
  s.ready.store(true, std::memory_order_release);
}

}  // namespace

int prof_start(int hz) {
  if (hz <= 0 || hz > 1000) hz = 100;
  bool expected = false;
  if (!g_running.compare_exchange_strong(expected, true)) return -1;
  if (g_samples == nullptr) {
    g_samples = new Sample[kMaxSamples]();  // value-init: depth 0, !ready
  }
  for (int i = 0; i < kMaxSamples; ++i) {
    g_samples[i].ready.store(false, std::memory_order_relaxed);
  }
  g_count.store(0, std::memory_order_relaxed);
  g_period_us = 1000000 / hz;
  // force-load the unwinder outside signal context (gperftools dance)
  void* warm[4];
  backtrace(warm, 4);
  struct sigaction sa;
  memset(&sa, 0, sizeof(sa));
  sa.sa_sigaction = prof_handler;
  sa.sa_flags = SA_SIGINFO | SA_RESTART;
  sigemptyset(&sa.sa_mask);
  if (sigaction(SIGPROF, &sa, &g_old_action) != 0) {
    g_running.store(false);
    return -1;
  }
  itimerval tv;
  tv.it_interval.tv_sec = 0;
  tv.it_interval.tv_usec = g_period_us;
  tv.it_value = tv.it_interval;
  if (setitimer(ITIMER_PROF, &tv, nullptr) != 0) {
    sigaction(SIGPROF, &g_old_action, nullptr);
    g_running.store(false);
    return -1;
  }
  return 0;
}

int prof_stop() {
  if (!g_running.load(std::memory_order_acquire)) return -1;
  itimerval off;
  memset(&off, 0, sizeof(off));
  setitimer(ITIMER_PROF, &off, nullptr);
  // Deliberately do NOT restore the old SIGPROF disposition: a SIGPROF
  // generated before the timer was disarmed can still be pending, and
  // restoring SIG_DFL (default: terminate) would kill the process on
  // delivery.  Our handler stays installed and no-ops via g_running —
  // the gperftools approach.
  g_running.store(false, std::memory_order_release);
  const int n = g_count.load(std::memory_order_acquire);
  return n > kMaxSamples ? kMaxSamples : n;
}

long long prof_sample_count() {
  const int n = g_count.load(std::memory_order_acquire);
  return n > kMaxSamples ? kMaxSamples : n;
}

// Legacy pprof CPU profile: words are uintptr_t.
// header: [0, 3, 0, period_us, 0]; per sample: [count, depth, pcs...];
// trailer: [0, 1, 0]; then the text of /proc/self/maps.
int prof_dump(const char* path) {
  if (g_running.load(std::memory_order_acquire)) return -1;  // stop first
  const int n = (int)prof_sample_count();
  FILE* f = fopen(path, "wb");
  if (f == nullptr) return -1;
  const uintptr_t header[5] = {0, 3, 0, (uintptr_t)g_period_us, 0};
  fwrite(header, sizeof(uintptr_t), 5, f);
  for (int i = 0; i < n; ++i) {
    const Sample& s = g_samples[i];
    if (!s.ready.load(std::memory_order_acquire) || s.depth <= 0) continue;
    const uintptr_t rec[2] = {1, (uintptr_t)s.depth};
    fwrite(rec, sizeof(uintptr_t), 2, f);
    fwrite(s.pcs, sizeof(void*), (size_t)s.depth, f);
  }
  const uintptr_t trailer[3] = {0, 1, 0};
  fwrite(trailer, sizeof(uintptr_t), 3, f);
  // address->binary mapping so pprof can symbolize
  FILE* maps = fopen("/proc/self/maps", "r");
  if (maps != nullptr) {
    char buf[4096];
    size_t got;
    while ((got = fread(buf, 1, sizeof(buf), maps)) > 0) {
      fwrite(buf, 1, got, f);
    }
    fclose(maps);
  }
  fclose(f);
  return n;
}

// Folded stacks ("leaf-last;..;root count" per flamegraph convention is
// root-first — we emit root;..;leaf).  Aggregates identical stacks.
int prof_folded(char* out, unsigned long cap) {
  if (g_running.load(std::memory_order_acquire)) return -1;
  const int n = (int)prof_sample_count();
  std::map<std::string, int> folded;
  for (int i = 0; i < n; ++i) {
    const Sample& s = g_samples[i];
    if (!s.ready.load(std::memory_order_acquire) || s.depth <= 0) continue;
    char** syms = backtrace_symbols(s.pcs, s.depth);
    if (syms == nullptr) continue;
    std::string key;
    for (int d = s.depth - 1; d >= 0; --d) {  // root first
      // backtrace_symbols gives "module(function+0x..) [addr]"; keep the
      // function token when present, else the module
      const char* t = syms[d];
      const char* lp = strchr(t, '(');
      std::string frame;
      if (lp != nullptr && lp[1] != ')' && lp[1] != '+') {
        const char* e = strpbrk(lp + 1, "+)");
        frame.assign(lp + 1, e ? (size_t)(e - lp - 1) : strlen(lp + 1));
      } else {
        const char* sl = strrchr(t, '/');
        const char* base = sl ? sl + 1 : t;
        const char* e = strchr(base, '(');
        frame.assign(base, e ? (size_t)(e - base) : strlen(base));
      }
      if (!key.empty()) key += ';';
      key += frame;
    }
    free(syms);
    folded[key] += 1;
  }
  std::string text;
  for (const auto& [k, c] : folded) {
    text += k;
    text += ' ';
    text += std::to_string(c);
    text += '\n';
  }
  if (cap == 0) return -1;
  if (text.size() + 1 > cap) {
    static const char kMark[] = "\n...truncated\n";
    if (cap <= sizeof(kMark)) {
      text.clear();             // too small for data + marker: just NUL
    } else {
      text.resize(cap - sizeof(kMark));
      text += kMark;            // sizeof includes the NUL slot
    }
  }
  memcpy(out, text.data(), text.size());
  out[text.size()] = 0;
  return (int)text.size();
}

// ---- event samplers (contention + IOBuf alloc sites) ----
//
// Shared shape: event-driven (not time-driven) stack capture into a
// seqlock-protected ring, rate-bounded by a token bucket so a hot path
// costs one relaxed atomic per event in steady state.  Two instances:
//  * contention (VERDICT r4 #8): like the reference ContentionProfiler
//    (src/bthread/mutex.cpp:66,122-145) capture happens on the
//    contended UNLOCK; the caller stack there is usually the executor's
//    resume loop (coroutine symmetric transfer is tail-called), so the
//    LOCK'S OWN ADDRESS rides each sample as the leaf frame.
//  * iobuf_alloc (reference butil/iobuf_profiler.h): block allocation
//    sites, sampled in iobuf.cc create_block — answers WHERE buffer
//    memory is being minted when /sockets' live-block count grows.
namespace {

constexpr int kCMaxDepth = 32;
constexpr int kCMaxSamples = 8192;
constexpr int64_t kCSamplePeriodNs = 1000000;  // >= 1ms apart => <=1k/s

struct CSample {
  std::atomic<uint64_t> seq{0};  // even = stable, odd = being written
  int depth;
  const void* leaf;  // event identity (lock address; null for allocs)
  void* pcs[kCMaxDepth];
};

struct EventSampler {
  CSample ring[kCMaxSamples];
  std::atomic<int64_t> events{0};    // every event, sampled or not
  std::atomic<int64_t> captured{0};
  std::atomic<int64_t> last_ns{0};   // token bucket

  void note(const void* leaf_addr, int skip_frames, int64_t clock_every) {
    const int64_t ev = events.fetch_add(1, std::memory_order_relaxed);
    // While the SIGPROF sampler is armed, take no stack here: backtrace()
    // walks libgcc's registered-frame list under its object_mutex (long,
    // once XLA's CPU JIT has registered hundreds of modules), and a
    // SIGPROF that lands on this thread inside it runs prof_handler's own
    // backtrace() against the mutex this thread holds.  The thread never
    // returns, every later backtrace() in the process queues behind it,
    // and one of those is a Python thread holding the interpreter lock
    // (seen as a whole-suite wedge in test_native_profiler: 8 threads on
    // libgcc_s's mutex, 130 on the GIL).
    if (g_running.load(std::memory_order_relaxed)) return;
    // hot-event instances (block allocs) only consult the clock every
    // Nth event, keeping steady-state cost at one relaxed atomic; rare-
    // event instances (contention) pass 1 and check every time
    if (clock_every > 1 && (ev % clock_every) != 0) return;
    const int64_t now = monotonic_time_ns();
    int64_t last = last_ns.load(std::memory_order_relaxed);
    if (now - last < kCSamplePeriodNs) return;
    if (!last_ns.compare_exchange_strong(last, now,
                                         std::memory_order_relaxed)) {
      return;  // another thread took this token
    }
    const int64_t i = captured.fetch_add(1, std::memory_order_relaxed);
    CSample& s = ring[i % kCMaxSamples];
    const uint64_t seq = s.seq.load(std::memory_order_relaxed) | 1;
    s.seq.store(seq, std::memory_order_release);     // mark mid-write
    std::atomic_thread_fence(std::memory_order_release);
    s.leaf = leaf_addr;
    const int n = backtrace(s.pcs, kCMaxDepth);
    const int skip = n > skip_frames ? skip_frames : 0;
    s.depth = n - skip;
    if (skip > 0) {
      memmove(s.pcs, s.pcs + skip, sizeof(void*) * (size_t)s.depth);
    }
    // fences pair with the reader's acquire fence: payload writes cannot
    // sink below the stable-marking store, and the reader's copies
    // cannot hoist above its seq check (the seqlock protocol)
    std::atomic_thread_fence(std::memory_order_release);
    s.seq.store(seq + 1, std::memory_order_release);  // stable
  }

  int64_t sample_count() const {
    const int64_t n = captured.load(std::memory_order_relaxed);
    return n > kCMaxSamples ? kCMaxSamples : n;
  }

  void reset() {
    captured.store(0, std::memory_order_relaxed);
    events.store(0, std::memory_order_relaxed);
    for (auto& s : ring) s.seq.store(0, std::memory_order_relaxed);
  }
};

EventSampler g_contention;
EventSampler g_iobuf_alloc;

// dladdr-based naming: exported functions get their symbol; local/
// coroutine-clone frames (not in dynsym) get "module+0xoffset", which
// `addr2line -e module 0xoffset` resolves to the exact site — without
// this every local frame collapsed into one opaque "libbrpc_core.so"
// bucket.
std::string symbolize_pc(const void* pc, const char* prefix) {
  Dl_info info;
  char buf[160];
  if (pc != nullptr && dladdr(pc, &info) != 0 && info.dli_fname != nullptr) {
    if (info.dli_sname != nullptr) {
      snprintf(buf, sizeof(buf), "%s%s", prefix, info.dli_sname);
    } else {
      const char* sl = strrchr(info.dli_fname, '/');
      snprintf(buf, sizeof(buf), "%s%s+0x%zx", prefix,
               sl ? sl + 1 : info.dli_fname,
               (size_t)((const char*)pc - (char*)info.dli_fbase));
    }
  } else {
    snprintf(buf, sizeof(buf), "%s%p", prefix, pc);
  }
  return buf;
}

int render_ring(EventSampler& es, const char* what, bool leaf_is_identity,
                const char* leaf_prefix, char* out, unsigned long cap) {
  const int n = (int)es.sample_count();
  std::map<std::string, int> folded;
  for (int i = 0; i < n; ++i) {
    CSample& s = es.ring[i];
    const uint64_t seq0 = s.seq.load(std::memory_order_acquire);
    if (seq0 == 0 || (seq0 & 1)) continue;  // empty or mid-write
    std::atomic_thread_fence(std::memory_order_acquire);
    int depth = s.depth;
    const void* leaf = s.leaf;
    void* pcs[kCMaxDepth];
    if (depth <= 0 || depth > kCMaxDepth) continue;
    memcpy(pcs, s.pcs, sizeof(void*) * (size_t)depth);
    std::atomic_thread_fence(std::memory_order_acquire);
    if (s.seq.load(std::memory_order_relaxed) != seq0) continue;  // torn
    std::string key;
    for (int d = depth - 1; d >= 0; --d) {  // root first
      if (!key.empty()) key += ';';
      key += symbolize_pc(pcs[d], "");
    }
    if (leaf_is_identity) {
      // e.g. a mutex address as the site identity: a global/static
      // object resolves to its symbol via dladdr; heap ones print raw
      if (!key.empty()) key += ';';
      key += symbolize_pc(leaf, leaf_prefix);
    }
    folded[key] += 1;
  }
  std::string text;
  text += "# ";
  text += what;
  text += " events: " +
          std::to_string(es.events.load(std::memory_order_relaxed)) +
          ", stacks sampled: " + std::to_string(n) +
          " (rate-bounded 1/ms)\n";
  for (const auto& [k, c] : folded) {
    text += k;
    text += ' ';
    text += std::to_string(c);
    text += '\n';
  }
  if (cap == 0) return -1;
  if (text.size() + 1 > cap) {
    static const char kMark[] = "\n...truncated\n";
    if (cap <= sizeof(kMark)) {
      text.clear();
    } else {
      text.resize(cap - sizeof(kMark));
      text += kMark;
    }
  }
  memcpy(out, text.data(), text.size());
  out[text.size()] = 0;
  return (int)text.size();
}

}  // namespace

void contention_note(const void* lock_addr) {
  g_contention.note(lock_addr, /*skip=*/1, /*clock_every=*/1);
}
int64_t contention_event_count() {
  return g_contention.events.load(std::memory_order_relaxed);
}
int64_t contention_sample_count() { return g_contention.sample_count(); }
void contention_reset() { g_contention.reset(); }
int contention_folded(char* out, unsigned long cap) {
  return render_ring(g_contention, "contention", /*leaf=*/true, "lock:",
                     out, cap);
}

void iobuf_alloc_note() {
  // skip 2: this function + create_block (the caller IS the site)
  g_iobuf_alloc.note(nullptr, /*skip=*/2, /*clock_every=*/64);
}
int64_t iobuf_alloc_event_count() {
  return g_iobuf_alloc.events.load(std::memory_order_relaxed);
}
int64_t iobuf_alloc_sample_count() { return g_iobuf_alloc.sample_count(); }
void iobuf_alloc_reset() { g_iobuf_alloc.reset(); }
int iobuf_alloc_folded(char* out, unsigned long cap) {
  return render_ring(g_iobuf_alloc, "iobuf block alloc", /*leaf=*/false,
                     "", out, cap);
}

}  // namespace butil
