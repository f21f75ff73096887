// Native serving hot path (ISSUE 9) — the per-token and per-frame work
// the Python serving stack pushes down into the core so the GIL stops
// being the ceiling:
//
//   * TokenRing — bounded emit ring between the shared decode step loop
//     and one request's emitter.  The step loop pushes ONE batch call
//     per step across every active slot (brpc_tokring_push_many: ctypes
//     releases the GIL for the call's duration), and the emitter drains
//     MANY tokens per wakeup (brpc_tokring_pop_many) instead of paying a
//     Python lock round-trip per token; the engine's one emit drainer
//     takes what EVERY ring holds in one call (brpc_tokring_pop_each).  The PR 3 contract is preserved
//     natively: push never blocks (a full ring returns 0 and the engine
//     cuts the consumer with EOVERCROWDED), the terminal marker is
//     always accepted and only surfaces after every buffered token, and
//     a global live-ring counter keeps the chaos suite's leak baselines
//     honest.
//   * brpc_batch_pad — DynamicBatcher formation's zero-fill + row
//     gather/pad as one GIL-released memset/memcpy pass (bucket choice,
//     EDF lanes and shed policy stay in Python where policy lives).
//   * brpc_page_table_fill — the engine's fixed-shape per-slot page
//     table gather, same discipline.
//
// Everything here is standalone (mutex + condvar, no Executor
// dependency) so the ring also works before brpc_core_init and inside
// forked bench subprocesses.
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <mutex>

#include "butil/flight.h"

namespace {

struct TokenRing {
  explicit TokenRing(int cap_) : cap(cap_ > 0 ? cap_ : 1) {
    buf = new int32_t[cap];
  }
  ~TokenRing() { delete[] buf; }

  std::mutex mu;
  std::condition_variable cv;
  int32_t* buf;
  int cap;
  int head = 0;   // next pop index
  int count = 0;  // tokens buffered
  bool terminal = false;
  int32_t terminal_err = 0;  // 0 = clean completion
  // flight-recorder sampling counters (ISSUE 15): pop and full-ring
  // events record 1-in-64 — the autopsy needs "is this ring still
  // advancing, roughly when did it last", not a per-token ledger, and
  // a per-token event would blow the <2% emit_fanout overhead gate.
  std::atomic<uint64_t> pops{0};
  std::atomic<uint64_t> fulls{0};

  // push under mu; returns false when full (never blocks, never grows)
  bool push_locked(int32_t tok) {
    if (count >= cap) return false;
    buf[(head + count) % cap] = tok;
    ++count;
    return true;
  }
};

std::atomic<int64_t> g_live_rings{0};

}  // namespace

extern "C" {

void* brpc_tokring_new(int cap) {
  g_live_rings.fetch_add(1, std::memory_order_relaxed);
  return new TokenRing(cap);
}

void brpc_tokring_free(void* h) {
  if (h == nullptr) return;
  g_live_rings.fetch_sub(1, std::memory_order_relaxed);
  delete (TokenRing*)h;
}

int64_t brpc_tokring_live() {
  return g_live_rings.load(std::memory_order_relaxed);
}

int brpc_tokring_push(void* h, int32_t tok) {
  auto* r = (TokenRing*)h;
  bool ok;
  {
    std::lock_guard<std::mutex> g(r->mu);
    ok = r->push_locked(tok);
  }
  if (ok) {
    r->cv.notify_one();
  } else if ((r->fulls.fetch_add(1, std::memory_order_relaxed) & 63) ==
             0) {
    // flight granularity (butil/flight.h): the per-token success path
    // records nothing — only the interesting transition (ring full,
    // the engine is about to cut this consumer) leaves an event, and
    // sampled at that, since a spinning producer hits full per token
    butil::flight::record(butil::flight::EV_RING_FULL,
                          (uint64_t)(uintptr_t)h);
  }
  return ok ? 1 : 0;
}

// One call per decode step: push toks[i] onto rings[i] for every active
// slot.  ok_out[i] = 1 on success, 0 when that ring is full (the caller
// cuts that consumer with EOVERCROWDED).  Returns the success count.
// The step loop holds Python references to every ring's wrapper while
// this runs, so the raw handles cannot be freed under us.
int brpc_tokring_push_many(void** rings, const int32_t* toks, int n,
                           uint8_t* ok_out) {
  int ok = 0;
  for (int i = 0; i < n; ++i) {
    auto* r = (TokenRing*)rings[i];
    bool pushed;
    {
      std::lock_guard<std::mutex> g(r->mu);
      pushed = r->push_locked(toks[i]);
    }
    if (pushed) {
      r->cv.notify_one();
      ++ok;
    }
    if (ok_out != nullptr) ok_out[i] = pushed ? 1 : 0;
  }
  // one event per STEP CALL, not per ring — what the wedge autopsy
  // needs ("did the step loop keep advancing?") at batch cost
  butil::flight::record(butil::flight::EV_RING_PUSH,
                        n > 0 ? (uint64_t)(uintptr_t)rings[0] : 0, ok);
  return ok;
}

// Always accepted (a cut/finished request must be able to flush and
// notify); first terminal wins.  Returns 1 when THIS call installed the
// terminal, 0 when one was already present — the Python wrapper uses
// the same exactly-once decision for its error-object slot.
int brpc_tokring_push_terminal(void* h, int32_t err_code) {
  auto* r = (TokenRing*)h;
  bool first;
  {
    std::lock_guard<std::mutex> g(r->mu);
    first = !r->terminal;
    if (first) {
      r->terminal = true;
      r->terminal_err = err_code;
    }
  }
  r->cv.notify_all();
  butil::flight::record(butil::flight::EV_RING_TERMINAL,
                        (uint64_t)(uintptr_t)h, err_code);
  return first ? 1 : 0;
}

// Drain up to `cap` tokens into `out`; blocks up to timeout_us when the
// ring is empty and no terminal is set.  *terminal_out becomes 1 only
// once the ring is EMPTY and the terminal marker is present (tokens
// always flush before the terminal — the exactly-once contract's
// ordering half); *err_out then carries the terminal code.
int brpc_tokring_pop_many(void* h, int32_t* out, int cap,
                          int64_t timeout_us, int* terminal_out,
                          int32_t* err_out) {
  auto* r = (TokenRing*)h;
  if (terminal_out != nullptr) *terminal_out = 0;
  std::unique_lock<std::mutex> g(r->mu);
  if (r->count == 0 && !r->terminal && timeout_us > 0) {
    r->cv.wait_for(g, std::chrono::microseconds(timeout_us), [r] {
      return r->count > 0 || r->terminal;
    });
  }
  int n = 0;
  while (n < cap && r->count > 0) {
    out[n++] = r->buf[r->head];
    r->head = (r->head + 1) % r->cap;
    --r->count;
  }
  bool saw_term = false;
  if (r->count == 0 && r->terminal && terminal_out != nullptr) {
    *terminal_out = 1;
    if (err_out != nullptr) *err_out = r->terminal_err;
    saw_term = true;
  }
  g.unlock();  // the record below must not stretch the ring mutex
  if (n > 0 || saw_term) {
    const uint64_t k = r->pops.fetch_add(1, std::memory_order_relaxed);
    if (saw_term || (k & 63) == 0) {
      butil::flight::record(butil::flight::EV_RING_POP,
                            (uint64_t)(uintptr_t)h, n);
    }
  }
  return n;
}

// One call a drain (the engine's emit drainer): pop up to `per_cap`
// tokens of EACH of `n` rings, ring i's into out[i * per_cap ...].
// counts_out[i] = tokens popped; term_out[i] = 1 only once ring i is
// EMPTY and carries its terminal marker (pop_many's rule: tokens
// always flush before the terminal).  A null handle is skipped (count
// 0, no terminal).  Never blocks.  Returns the tokens popped in all.
// The drainer holds a Python reference to every ring's wrapper while
// this runs, so the raw handles cannot be freed under us.
int brpc_tokring_pop_each(void** rings, int n, int32_t* out, int per_cap,
                          int32_t* counts_out, uint8_t* term_out) {
  int total = 0, terms = 0;
  for (int i = 0; i < n; ++i) {
    counts_out[i] = 0;
    term_out[i] = 0;
    auto* r = (TokenRing*)rings[i];
    if (r == nullptr) continue;
    int32_t* dst = out + (size_t)i * per_cap;
    int k = 0;
    {
      std::lock_guard<std::mutex> g(r->mu);
      while (k < per_cap && r->count > 0) {
        dst[k++] = r->buf[r->head];
        r->head = (r->head + 1) % r->cap;
        --r->count;
      }
      if (r->count == 0 && r->terminal) term_out[i] = 1;
    }
    counts_out[i] = k;
    total += k;
    terms += term_out[i];
  }
  // one event per DRAIN, not per ring (push_many's granularity)
  if (total > 0 || terms > 0) {
    butil::flight::record(butil::flight::EV_RING_POP,
                          n > 0 ? (uint64_t)(uintptr_t)rings[0] : 0, total);
  }
  return total;
}

int64_t brpc_tokring_size(void* h) {
  auto* r = (TokenRing*)h;
  std::lock_guard<std::mutex> g(r->mu);
  return r->count;
}

// ---- batch assembly (DynamicBatcher._execute's gather/pad) ----

// Zero-fill `out` (rows * stride_bytes) then copy row i's row_bytes[i]
// payload to out + i*stride_bytes.  One GIL-released pass replaces the
// np.zeros + per-row slice-assign loop that serialized formation
// against every other Python thread.
void brpc_batch_pad(const void** rows, const int64_t* row_bytes, int n,
                    void* out, int64_t stride_bytes, int64_t total_bytes) {
  memset(out, 0, (size_t)total_bytes);
  char* base = (char*)out;
  for (int i = 0; i < n; ++i) {
    int64_t m = row_bytes[i];
    // defensive truncate to the bucket width, same contract as the
    // fastrpc entry and brpc_page_table_fill: an oversized row must
    // not memcpy past its stride (or past total_bytes on the last row)
    if (m > stride_bytes) m = stride_bytes;
    if (m > 0) {
      memcpy(base + (int64_t)i * stride_bytes, rows[i], (size_t)m);
    }
  }
}

// ---- page-table gather (DecodeEngine._gather_page_tables) ----

// Fill the fixed-shape [num_slots, max_pages] int32 table with -1, then
// copy each active slot's page-id list into its row (truncated to
// max_pages).  lists[i] points at slot slot_idx[i]'s contiguous int32
// page ids.
void brpc_page_table_fill(const int32_t** lists, const int64_t* lens,
                          const int32_t* slot_idx, int n, int32_t* table,
                          int num_slots, int max_pages) {
  const int64_t total = (int64_t)num_slots * max_pages;
  for (int64_t i = 0; i < total; ++i) table[i] = -1;
  for (int i = 0; i < n; ++i) {
    int64_t m = lens[i];
    if (m > max_pages) m = max_pages;
    if (m > 0) {
      memcpy(table + (int64_t)slot_idx[i] * max_pages, lists[i],
             (size_t)m * sizeof(int32_t));
    }
  }
}

}  // extern "C"
