// Native flight recorder (ISSUE 15) — always-on per-thread event rings.
//
// PR 14 proved the intermittent tier-1 wedge is NOT a Python lock cycle
// (the runtime witness saw zero nesting edges under the full native
// modules), which leaves the root cause in the one layer the repo could
// not see: the native executor / butex / socket core.  rpcz spans, the
// /hotspots sampler and the lockprof ledger all stop at the ctypes
// boundary.  This is the in-core answer, in the bvar tradition: every
// load-bearing transition (executor task begin/end, steal, park/unpark,
// butex wait/wake/timeout, timer fire/cancel, socket lifecycle + read/
// write syscalls, TokenRing batch push/pop/terminal) records one
// fixed-size 32-byte event into the calling thread's bounded ring.
//
// Design constraints, in order:
//   * Always-on: rings overwrite-oldest, so there is nothing to arm and
//     nothing to leak — the last ~2048 transitions per thread are
//     simply always there when a wedge autopsy needs them.  Rings of
//     EXITED threads go onto a recycle list and are reused by the next
//     registering thread (per-request emitter threads must not leak a
//     64KB ring each at serving scale); until reuse they keep their
//     events, so a dead thread's tail is still dumpable.
//   * Near-zero hot-path cost: one relaxed enabled-flag load, one TLS
//     pointer read, four relaxed atomic stores and a vDSO clock read —
//     no locks, no allocation, no syscalls.
//   * Torn-read-proof dumps: each slot carries a seqlock version word
//     (odd while the owner writes, even when complete), so a dump
//     taken WHILE every thread keeps writing returns only consistent
//     events — a slot overwritten mid-copy either fails the version
//     double-check and is dropped, or yields the complete newer event.
//     All fields are relaxed atomics, which also keeps `make tsan`'s
//     ring stress sound (no seqlock false positives).
//
// Granularity note: TokenRing events are recorded per CALL (push_many /
// pop_many / terminal / full-ring push failure), not per token — the
// per-token single-push path is the emit_fanout hot loop and a per-token
// event would blow the <2% overhead gate while adding nothing a
// per-batch event does not show.
#pragma once

#include <atomic>
#include <cstddef>
#include <cstdint>

namespace butil {
namespace flight {

// Event kinds.  Append-only: the dump format names them, and tools
// parse the names, not the values.
enum EventKind : uint16_t {
  EV_NONE = 0,
  // executor worker loop
  EV_TASK_BEGIN,      // a = task fn ptr
  EV_TASK_END,        // a = task fn ptr
  EV_STEAL,           // a = victim worker index
  EV_PARK,            // a = parking-lot state snapshot
  EV_UNPARK,          //
  // butex
  EV_BUTEX_WAIT,      // a = butex ptr, b = timeout_us (clamped, -1 none)
  EV_BUTEX_WAKE,      // a = butex ptr, b = waiters woken
  EV_BUTEX_TIMEOUT,   // a = butex ptr
  // timer thread
  EV_TIMER_FIRE,      // a = timer id
  EV_TIMER_CANCEL,    // a = timer id
  // socket lifecycle + syscalls
  EV_SOCK_CREATE,     // a = socket id, b = fd
  EV_SOCK_EPOLLIN,    // a = socket id, b = epoll event bits
  EV_READ_ENTER,      // a = socket id
  EV_READ_EXIT,       // a = socket id, b = bytes read (or -errno)
  EV_WRITE_ENTER,     // a = socket id, b = bytes attempted
  EV_WRITE_EXIT,      // a = socket id, b = bytes written (or -errno)
  EV_SOCK_FAILED,     // a = socket id, b = error code
  EV_SOCK_CLOSE,      // a = socket id, b = fd
  // serving TokenRing (batch granularity — see header comment)
  EV_RING_PUSH,       // a = first ring handle, b = rings pushed OK
  EV_RING_FULL,       // a = ring handle (single-push hit a full ring)
  EV_RING_POP,        // a = ring handle, b = tokens drained
  EV_RING_TERMINAL,   // a = ring handle, b = error code
  // rpcz native span queue (fastrpc_module.cc)
  EV_SPANQ_DRAIN,     // b = spans drained
  // test/self-probe marker (brpc_flight_selftest_* in capi.cc)
  EV_PROBE,           // a = caller tag, b = sequence
  EV_KIND_MAX,
};

const char* kind_name(uint16_t k);  // "task_begin", "butex_wait", ...

// Per-thread ring capacity (power of two).  2048 x 32B = 64KB/thread.
constexpr uint64_t kRingCap = 2048;

// One recorded transition.  32 bytes; all fields relaxed atomics so
// concurrent dumps are data-race-free (see header comment).
struct Event {
  std::atomic<uint64_t> ver;    // seq*2+1 writing, seq*2+2 complete
  std::atomic<int64_t> ts_us;   // monotonic
  std::atomic<uint64_t> a;      // primary id (socket id, ptr, index)
  std::atomic<int32_t> b;       // small arg (bytes, errno, count)
  std::atomic<uint16_t> kind;
  uint16_t _pad;
};
static_assert(sizeof(Event) == 32, "event must stay ~32 bytes");

struct ThreadRing {
  Event buf[kRingCap];
  std::atomic<uint64_t> head{0};   // next sequence to write (owner only)
  std::atomic<uint64_t> tid{0};
  // thread role, 15 chars + NUL packed into two atomic words so the
  // owner can (re)name itself while a dump reads concurrently
  std::atomic<uint64_t> name_lo{0}, name_hi{0};
  std::atomic<bool> live{true};
  ThreadRing* next = nullptr;      // registration list, push-front once
  ThreadRing* free_next = nullptr; // recycle list (under its mutex)
};

// ---- recording (hot path) ----

bool enabled();
void set_enabled(bool on);

// Record one event on the calling thread's ring (registering the ring
// on first use).  No-op while disabled.
void record(uint16_t kind, uint64_t a = 0, int64_t b = 0);

// Name the calling thread's ring ("worker/3", "timer", "epoll/0").
// Threads that never call this show up as "ext".
void set_thread_name(const char* fmt, ...)
    __attribute__((format(printf, 1, 2)));

// ---- introspection (cold path) ----

// Merged time-ordered tail of every thread's ring: up to max_events
// consistent events, oldest first, one per line:
//   <ts_us> <tid> <name> <kind> a=<hex> b=<dec>
// Returns bytes written (0 terminated, truncating at cap).
int dump(char* out, size_t cap, int max_events);

// Per-thread state table ("what is every native thread doing RIGHT
// NOW"), one line per ring:
//   <tid> <name> <live|exited> events=<n> dropped=<n> last=<kind> age_us=<n>
int threads_table(char* out, size_t cap);

// events = total recorded, threads = rings registered,
// dropped = events overwritten before any dump could see them.
void stats(int64_t* events, int64_t* threads, int64_t* dropped);

}  // namespace flight
}  // namespace butil
