// Native unary-RPC hot path: meta codec, method map, dispatch.
//
// The reference parses baidu_std meta, finds the method and serializes the
// response entirely in C++ (baidu_rpc_protocol.cpp:97-137 parse, :398
// ProcessRpcRequest, server.h:399,432 FlatMap method maps) — the Python
// round-trip per request was round 1's architectural QPS cap.  This layer
// mirrors that: TRPC meta (meta.py layout: fixed <BBHQH> + u8/u32le TLVs)
// is parsed natively; methods registered in a FlatMap behind
// DoublyBufferedData are dispatched either to a pure-native handler (the
// request never surfaces to Python) or to Python through a pre-parsed
// request callback; responses are packed natively.
#pragma once

#include <cstddef>
#include <cstdint>

#include "butil/iobuf.h"

namespace brpc {

typedef uint64_t SocketId;

// ---- meta codec (mirrors brpc_tpu/rpc/meta.py) ----

enum MetaMsgType {
  META_REQUEST = 0,
  META_RESPONSE = 1,
  // stream frame types 2..4 are not handled natively
};

enum MetaTag {
  TAG_SERVICE = 1,
  TAG_METHOD = 2,
  TAG_ERROR_CODE = 3,
  TAG_ERROR_TEXT = 4,
  TAG_COMPRESS = 5,
  TAG_ATTACHMENT_SIZE = 6,
  TAG_TIMEOUT_MS = 7,
  TAG_CONTENT_TYPE = 12,
};

constexpr size_t kMetaFixedLen = 14;  // <BBHQH>

struct ParsedMeta {
  uint8_t version = 0;
  uint8_t msg_type = 0;
  uint16_t flags = 0;
  uint64_t cid = 0;
  uint16_t attempt = 0;
  // string fields point into the raw meta buffer
  const char* service = nullptr;
  uint32_t service_len = 0;
  const char* method = nullptr;
  uint32_t method_len = 0;
  const char* error_text = nullptr;
  uint32_t error_text_len = 0;
  const char* content_type = nullptr;
  uint32_t content_type_len = 0;
  int32_t error_code = 0;
  uint8_t compress = 0;
  uint64_t attachment_size = 0;
  uint32_t timeout_ms = 0;
  uint32_t present_mask = 0;  // bit (1<<tag) for every TLV seen, tag<32
};

// Parse; returns false on malformed meta.  String fields alias `p`.
bool ParseMeta(const char* p, size_t n, ParsedMeta* out);

// Tags the native fast path fully understands; metas with any other tag
// (auth, trace ids, stream state, tensor headers, user fields) fall back
// to the Python decoder so nothing is silently dropped.
constexpr uint32_t kFastPathTags =
    (1u << TAG_SERVICE) | (1u << TAG_METHOD) | (1u << TAG_ERROR_CODE) |
    (1u << TAG_ERROR_TEXT) | (1u << TAG_COMPRESS) |
    (1u << TAG_ATTACHMENT_SIZE) | (1u << TAG_TIMEOUT_MS) |
    (1u << TAG_CONTENT_TYPE);

inline bool MetaIsFastPath(const ParsedMeta& m) {
  return (m.present_mask & ~kFastPathTags) == 0;
}

// Build a complete TRPC response frame (header + response meta + body)
// into *out.  Consumes body.
void PackResponseFrame(butil::IOBuf* out, uint64_t cid, uint16_t attempt,
                       int32_t error_code, const char* error_text,
                       size_t error_text_len, const char* content_type,
                       size_t content_type_len, butil::IOBuf&& body);

// Build a complete TRPC request frame natively (client-side fast path).
void PackRequestFrame(butil::IOBuf* out, uint64_t cid, uint16_t attempt,
                      const char* service, size_t service_len,
                      const char* method, size_t method_len,
                      uint32_t timeout_ms, uint8_t compress,
                      const char* content_type, size_t content_type_len,
                      butil::IOBuf&& body);

// Same, but the body is raw bytes staged through the one appender — for
// small payloads this skips the body IOBuf's block-ref round entirely.
void PackRequestFrameFlat(butil::IOBuf* out, uint64_t cid, uint16_t attempt,
                          const char* service, size_t service_len,
                          const char* method, size_t method_len,
                          uint32_t timeout_ms, uint8_t compress,
                          const char* content_type, size_t content_type_len,
                          const void* body, size_t body_len);

// ---- method registry ----

// Pure-native handler: fills *resp_body, returns an error code (0 = ok).
// body ownership stays with the caller.
typedef int32_t (*NativeMethodFn)(SocketId sid, butil::IOBuf* body,
                                  butil::IOBuf* resp_body, void* user);

// Flat inline handler (the zero-ref hot path): the request body is a VIEW
// into the socket's read block (valid only for the duration of the call)
// and the response body is written straight into a stack stage that lands
// in the dispatch write batch as ONE contiguous span — no IOBuf, no
// block refs, no extra iovecs on either side.  Returns the response
// length (>= 0, rc 0 implied), or -1 to fall back to the IOBuf handler
// `fn` (only allowed BEFORE any side effect: the request is re-delivered).
typedef int32_t (*NativeMethodFlatFn)(SocketId sid, const char* req,
                                      size_t req_len, char* resp,
                                      size_t resp_cap, void* user);

// Response stage capacity offered to flat handlers (stack-allocated in
// the dispatch loop; responses above this take the IOBuf path).
constexpr size_t kFlatRespCap = 4096;

// Pre-parsed request surfaced to Python.  hdr fields alias raw_meta, which
// is only valid during the call; body ownership transfers to the callee.
struct RequestHeader {
  uint64_t cid;
  uint32_t timeout_ms;
  uint32_t present_mask;
  const char* service;
  uint32_t service_len;
  const char* method;
  uint32_t method_len;
  uint16_t attempt;
  uint8_t compress;
  uint8_t msg_type;
  const char* content_type;
  uint32_t content_type_len;
  int32_t error_code;
  const char* error_text;
  uint32_t error_text_len;
  uint64_t attachment_size;
};

typedef void (*RequestCallback)(SocketId sid, const RequestHeader* hdr,
                                butil::IOBuf* body, void* user);
// Client side: pre-parsed response.  Same aliasing rules.
typedef void (*ResponseCallback)(SocketId sid, const RequestHeader* hdr,
                                 butil::IOBuf* body, void* user);
// Flat inline response: body is a view into the read block, valid only
// for the duration of the call (zero-ref client hot path).
typedef void (*ResponseFlatCallback)(SocketId sid, const RequestHeader* hdr,
                                     const char* body, size_t body_len,
                                     void* user);

class MethodRegistry {
 public:
  static MethodRegistry* global();

  // kind: 0 = native handler, 1 = python (dispatched via RequestCallback).
  // inline_run: run the native handler on the dispatcher thread instead of
  // an executor task (only for handlers that never block).
  void Register(const char* service, const char* method, NativeMethodFn fn,
                void* user, bool inline_run);
  // Register both forms: `flat` runs when the request body is contiguous
  // in the read block and the response fits kFlatRespCap; `fn` is the
  // fallback for split/oversized frames (and MUST be provided).
  void RegisterFlat(const char* service, const char* method,
                    NativeMethodFn fn, NativeMethodFlatFn flat, void* user,
                    bool inline_run);
  void RegisterPython(const char* service, const char* method);
  bool Unregister(const char* service, const char* method);

  struct Entry {
    NativeMethodFn fn = nullptr;  // null => python
    NativeMethodFlatFn fn_flat = nullptr;
    void* user = nullptr;
    bool inline_run = false;
  };
  // Returns true and fills *out when (service, method) is registered.
  bool Lookup(const char* service, size_t service_len, const char* method,
              size_t method_len, Entry* out);

  int64_t native_calls() const;
  int64_t dropped_responses() const;
  // Count a reply whose socket Write was rejected (callers outside this
  // TU: fastrpc extension, capi response paths).
  static void NoteDroppedResponse();
  int64_t python_fast_calls() const;
};

// Install the process-wide Python-side request callback for the fast path
// (server role; responses are per-socket via SocketOptions.on_response).
void SetRequestCallback(RequestCallback cb, void* user);

// Usercode admission control (reference ELIMIT fail-fast semantics with a
// time-denominated bound): when a budget is set, more than two upcalls
// are pending and the lane's measured queue wait (an EMA of frame cut to
// upcall start, UpcallTicket::cut_us; inline mode: position in the sweep
// x an EMA of handler time) exceeds it, new requests are answered ELIMIT
// natively instead of queueing.  The estimate is the process's: every
// queued upcall feeds it, with or without a budget.
void SetUsercodeLatencyBudgetUs(int64_t us);  // 0 disables (default)
int64_t UsercodeLatencyBudgetUs();
int64_t UsercodeShedCount();
int64_t UsercodePending();
double UsercodeEmaUs();

// Inline usercode mode (single-threaded event loop): Python upcalls run
// synchronously on the dispatcher thread.  Lowest possible latency
// variance on core-starved hosts; STRICTLY for non-blocking handlers.
void SetUsercodeInline(bool on);
bool UsercodeInline();
// Called by the event dispatcher at the top of each epoll sweep: resets
// the per-sweep inline-upcall counter that the inline admission control
// uses to estimate how long a request sat behind this sweep's handlers.
void NoteDispatchSweepStart();

// ---- upcall lane sample (rpcz stages: queue_wait_us / queue_depth) ----
// Taken when a frame is cut from the socket's read buffer, carried with
// the queued task, and published to the worker thread for the length of
// the Python upcall: the upcall can then ask how long its frame waited
// (executor or FIFO lane, the hop onto the worker, the wait for the
// interpreter lock up to the asking line) and how many upcalls were
// queued when it was cut.  One clock (cpuwide_time_us), so the interval
// needs no mapping onto any other.
struct UpcallTicket {
  int64_t cut_us;
  int64_t depth;   // upcalls queued, this one not counted, at the cut
  bool queued;
  UpcallTicket();
  ~UpcallTicket();  // a task dropped before it ran leaves the queue too
  UpcallTicket(const UpcallTicket&) = delete;
  UpcallTicket& operator=(const UpcallTicket&) = delete;
};

// Around the callback, on the thread that runs it.  Nests: an upcall
// made from inside another restores the outer sample when it ends.
class UpcallScope {
 public:
  explicit UpcallScope(UpcallTicket* t);
  ~UpcallScope();

 private:
  int64_t _prev_cut_us;
  int64_t _prev_depth;
};

// From inside an upcall; false when this thread runs none.
bool CurrentUpcallWait(int64_t* wait_us, int64_t* depth);

struct SocketOptions;

// Socket::DispatchMessages hook for MSG_TRPC.  Returns true if the message
// was fully handled natively (or handed to the fast-path callbacks) — the
// callee then owns *body (heap).  false => caller falls back to the
// generic on_message path and still owns body.
bool TryDispatchTrpc(SocketId sid, const SocketOptions& opts,
                     const char* meta, size_t meta_len, butil::IOBuf* body);

// Zero-ref variant: meta AND body are views into the read block.  Returns
// true when fully handled (caller pops the body bytes); false => caller
// takes the IOBuf path (cutn + TryDispatchTrpc) with NOTHING consumed —
// flat handlers must not have had side effects before falling back.
bool TryDispatchTrpcFlat(SocketId sid, const SocketOptions& opts,
                         const char* meta, size_t meta_len, const char* body,
                         size_t body_len);

}  // namespace brpc
