// _fastrpc — CPython C-extension for the RPC hot boundary.
//
// ctypes marshalling costs ~10-20us per crossing (measured via cProfile:
// send_request alone ~20us tottime) and CFUNCTYPE trampolines are similar
// on the way back — at ~170us/request end-to-end that is the single
// largest removable cost.  This module replaces the hot crossings with
// direct C API calls: request/response frames are packed and written in
// one call, and natively pre-parsed requests/responses are delivered to
// Python as plain argument tuples (strings + bytes), with the IOBuf
// consumed C-side.  The ctypes surface (lib.py) remains for everything
// cold (listen/connect, timers, stats, streams).
//
// Reference analog: the generated pb stub layer sitting directly on the
// C++ core (baidu_rpc_protocol.cpp pack/process), with no FFI toll booth.
#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <atomic>
#include <cstring>

#include "butil/flight.h"
#include "butil/iobuf.h"
#include "net/rpc.h"
#include "net/socket.h"
#include "spanq.h"

namespace {

PyObject* g_request_handler = nullptr;   // called with 10-tuple args
PyObject* g_response_handler = nullptr;  // called with 9-tuple args

// ---- FastBody: IOBuf-backed buffer object (zero-copy boundary) ----
//
// VERDICT r2 task 9: fast-path bodies used to be memcpy'd into Python
// bytes.  FastBody owns the native IOBuf and exposes its bytes through
// the buffer protocol: single-block bodies (every body <= one 8KB block
// — the common case) are exposed IN PLACE; multi-block bodies coalesce
// once on first access.  Python sees a standard memoryview over it, so
// slicing (payload/attachment split) stays zero-copy and the IOBuf block
// refs live exactly as long as Python references do — the SURVEY §2.1
// splice semantics carried across the language boundary.

struct FastBodyObject {
  PyObject_HEAD
  butil::IOBuf* buf;
  char* flat;     // coalesced copy for multi-block bodies (lazy)
  size_t size;
};

int fastbody_getbuffer(PyObject* self, Py_buffer* view, int flags) {
  auto* fb = (FastBodyObject*)self;
  void* ptr = nullptr;
  if (fb->flat != nullptr) {
    ptr = fb->flat;
  } else if (fb->size == 0) {
    ptr = (void*)"";  // zero-length: any non-null pointer is fine
  } else if (fb->buf->backing_block_num() == 1) {
    const butil::BlockRef& r = fb->buf->backing_block(0);
    ptr = butil::iobuf::block_data(r.block) + r.offset;
  } else {
    fb->flat = (char*)PyMem_Malloc(fb->size);
    if (fb->flat == nullptr) {
      PyErr_NoMemory();
      return -1;
    }
    fb->buf->copy_to(fb->flat, fb->size, 0);
    // the flat copy fully replaces the blocks: release them now rather
    // than doubling memory for the view's lifetime (dealloc handles null)
    delete fb->buf;
    fb->buf = nullptr;
    ptr = fb->flat;
  }
  return PyBuffer_FillInfo(view, self, ptr, (Py_ssize_t)fb->size,
                           /*readonly=*/1, flags);
}

void fastbody_dealloc(PyObject* self) {
  auto* fb = (FastBodyObject*)self;
  delete fb->buf;
  if (fb->flat != nullptr) PyMem_Free(fb->flat);
  Py_TYPE(self)->tp_free(self);
}

Py_ssize_t fastbody_length(PyObject* self) {
  return (Py_ssize_t)((FastBodyObject*)self)->size;
}

PyBufferProcs fastbody_as_buffer = {fastbody_getbuffer, nullptr};
PySequenceMethods fastbody_as_sequence = {fastbody_length};

PyTypeObject FastBodyType = {
    PyVarObject_HEAD_INIT(nullptr, 0)
    "_fastrpc.FastBody",            /* tp_name */
    sizeof(FastBodyObject),         /* tp_basicsize */
};

// Wrap `b` (ownership taken) as a read-only memoryview whose lifetime
// keeps the IOBuf blocks alive.  Returns nullptr with an exception set.
PyObject* iobuf_to_memoryview(butil::IOBuf* b) {
  auto* fb = PyObject_New(FastBodyObject, &FastBodyType);
  if (fb == nullptr) {
    delete b;
    return nullptr;
  }
  fb->buf = b;
  fb->flat = nullptr;
  fb->size = b->size();
  PyObject* mv = PyMemoryView_FromObject((PyObject*)fb);
  Py_DECREF(fb);  // the memoryview holds the buffer reference
  return mv;
}

// ---- native -> Python trampolines (run on executor/dispatcher threads) ----

// If the Python handler raises (or the payload can't be materialized), the
// peer must still get a reply — a silently dropped frame hangs the caller
// until its RPC deadline.  Pack a native EINTERNAL response instead.
constexpr int32_t kEInternal = 2001;  // errors.py EINTERNAL

void send_error_response(brpc::SocketId sid, const brpc::RequestHeader* hdr) {
  static const char kMsg[] = "python handler raised";
  butil::IOBuf frame;
  brpc::PackResponseFrame(&frame, hdr->cid, hdr->attempt, kEInternal, kMsg,
                          sizeof(kMsg) - 1, "", 0, butil::IOBuf());
  brpc::Socket* s = brpc::Socket::Address(sid);
  if (s != nullptr) {
    if (s->Write(std::move(frame)) != 0) {
      brpc::MethodRegistry::NoteDroppedResponse();
    }
    s->Dereference();
  }
}

void fast_request_cb(brpc::SocketId sid, const brpc::RequestHeader* hdr,
                     butil::IOBuf* body, void* /*user*/) {
  PyGILState_STATE g = PyGILState_Ensure();
  PyObject* handler = g_request_handler;
  bool handled = false;
  if (handler != nullptr) {
    PyObject* payload = iobuf_to_memoryview(body);  // takes ownership
    if (payload != nullptr) {
      PyObject* r = PyObject_CallFunction(
          handler, "KKHs#s#BIs#KN", (unsigned long long)sid,
          (unsigned long long)hdr->cid, (unsigned short)hdr->attempt,
          hdr->service ? hdr->service : "", (Py_ssize_t)hdr->service_len,
          hdr->method ? hdr->method : "", (Py_ssize_t)hdr->method_len,
          hdr->compress, hdr->timeout_ms,
          hdr->content_type ? hdr->content_type : "",
          (Py_ssize_t)hdr->content_type_len,
          (unsigned long long)hdr->attachment_size, payload);
      if (r == nullptr) {
        PyErr_Print();
      } else {
        Py_DECREF(r);
        handled = true;
      }
    } else {
      PyErr_Print();
    }
  } else {
    delete body;
  }
  if (!handled) send_error_response(sid, hdr);
  PyGILState_Release(g);
}

void fast_response_cb(brpc::SocketId sid, const brpc::RequestHeader* hdr,
                      butil::IOBuf* body, void* /*user*/) {
  PyGILState_STATE g = PyGILState_Ensure();
  PyObject* handler = g_response_handler;
  if (handler != nullptr) {
    PyObject* payload = iobuf_to_memoryview(body);  // takes ownership
    if (payload != nullptr) {
      PyObject* r = PyObject_CallFunction(
          handler, "KKHis#Bs#KN", (unsigned long long)sid,
          (unsigned long long)hdr->cid, (unsigned short)hdr->attempt,
          (int)hdr->error_code, hdr->error_text ? hdr->error_text : "",
          (Py_ssize_t)hdr->error_text_len, hdr->compress,
          hdr->content_type ? hdr->content_type : "",
          (Py_ssize_t)hdr->content_type_len,
          (unsigned long long)hdr->attachment_size, payload);
      if (r == nullptr) PyErr_Print();
      else Py_DECREF(r);
    } else {
      PyErr_Print();
    }
  } else {
    delete body;
  }
  PyGILState_Release(g);
}

// ---- Python -> native ----

// Zero-copy send threshold: below it a memcpy into the IOBuf beats the
// Py_buffer bookkeeping + GIL reacquisition in the deleter.
constexpr Py_ssize_t kZeroCopySendBytes = 4096;

struct PyBufHolder { Py_buffer view; };

void release_pybuf(void* /*data*/, void* arg) {
  // Runs when the last block ref drops (usually the writer thread after
  // the bytes hit the fd) — must retake the GIL to release the exporter.
  PyGILState_STATE g = PyGILState_Ensure();
  auto* h = (PyBufHolder*)arg;
  PyBuffer_Release(&h->view);
  delete h;
  PyGILState_Release(g);
}

// Move `view`'s bytes into b: small payloads copy; large ones wrap the
// Python buffer as a user block that pins the exporter until written.
void append_pybuffer(butil::IOBuf* b, Py_buffer* view) {
  if (view->len <= 0) {
    PyBuffer_Release(view);
    return;
  }
  if (view->len < kZeroCopySendBytes || !view->readonly) {
    // writable exporters (bytearray, numpy) must be copied: the caller is
    // free to mutate after we return, and a pinned mutable buffer would
    // silently corrupt the queued frame if the write queue is backlogged
    b->append(view->buf, (size_t)view->len);
    PyBuffer_Release(view);
    return;
  }
  auto* h = new PyBufHolder{*view};
  b->append_user_data(h->view.buf, (size_t)h->view.len, release_pybuf, h);
}

// Write one framed buffer to a socket, deciding whether to yield the
// GIL: Socket::Write is wait-free-producer + nonblocking inline drain,
// so a SMALL frame onto a SMALL backlog finishes in microseconds and
// dropping the GIL around it costs a full handoff cycle per call under
// load (measured ~17us/req at 64 concurrent on 1 core).  Yield when this
// frame is big OR the socket's backlog is — winning _write_busy there
// can inline-drain the whole multi-thread backlog, and that must not run
// with the GIL held.
static int write_frame_gil_aware(unsigned long long sid,
                                 butil::IOBuf&& frame) {
  brpc::Socket* s = brpc::Socket::Address(sid);
  if (s == nullptr) return -1;
  const bool yield_gil = frame.size() > 64 * 1024 ||
                         s->pending_write_bytes() > 256 * 1024;
  int rc;
  if (yield_gil) {
    Py_BEGIN_ALLOW_THREADS
    rc = s->Write(std::move(frame));
    s->Dereference();
    Py_END_ALLOW_THREADS
  } else {
    rc = s->Write(std::move(frame));
    s->Dereference();
  }
  return rc;
}

PyObject* py_send_request(PyObject*, PyObject* args) {
  unsigned long long sid, cid;
  unsigned short attempt;
  const char *service, *method, *content_type;
  Py_ssize_t service_len, method_len, ct_len;
  unsigned int timeout_ms;
  unsigned char compress;
  Py_buffer body;
  if (!PyArg_ParseTuple(args, "KKHs#s#IBs#y*", &sid, &cid, &attempt, &service,
                        &service_len, &method, &method_len, &timeout_ms,
                        &compress, &content_type, &ct_len, &body))
    return nullptr;
  butil::IOBuf b;
  append_pybuffer(&b, &body);
  butil::IOBuf frame;
  brpc::PackRequestFrame(&frame, cid, attempt, service, (size_t)service_len,
                         method, (size_t)method_len, timeout_ms, compress,
                         content_type, (size_t)ct_len, std::move(b));
  return PyLong_FromLong(write_frame_gil_aware(sid, std::move(frame)));
}

PyObject* py_send_response(PyObject*, PyObject* args) {
  unsigned long long sid, cid;
  unsigned short attempt;
  int error_code;
  const char *error_text, *content_type;
  Py_ssize_t et_len, ct_len;
  Py_buffer body;
  if (!PyArg_ParseTuple(args, "KKHis#s#y*", &sid, &cid, &attempt, &error_code,
                        &error_text, &et_len, &content_type, &ct_len, &body))
    return nullptr;
  butil::IOBuf b;
  append_pybuffer(&b, &body);
  butil::IOBuf frame;
  brpc::PackResponseFrame(&frame, cid, attempt, error_code, error_text,
                          (size_t)et_len, content_type, (size_t)ct_len,
                          std::move(b));
  return PyLong_FromLong(write_frame_gil_aware(sid, std::move(frame)));
}

PyObject* py_set_request_handler(PyObject*, PyObject* arg) {
  if (arg != Py_None && !PyCallable_Check(arg)) {
    PyErr_SetString(PyExc_TypeError, "request handler must be callable");
    return nullptr;
  }
  PyObject* next = (arg == Py_None) ? nullptr : arg;
  Py_XINCREF(next);
  PyObject* old = g_request_handler;
  g_request_handler = next;
  Py_XDECREF(old);
  brpc::SetRequestCallback(fast_request_cb, nullptr);
  Py_RETURN_NONE;
}

PyObject* py_set_response_handler(PyObject*, PyObject* arg) {
  if (arg != Py_None && !PyCallable_Check(arg)) {
    PyErr_SetString(PyExc_TypeError, "response handler must be callable");
    return nullptr;
  }
  PyObject* next = (arg == Py_None) ? nullptr : arg;
  Py_XINCREF(next);
  PyObject* old = g_response_handler;
  g_response_handler = next;
  Py_XDECREF(old);
  Py_RETURN_NONE;
}

// ctypes casts this integer to RESPONSE_CB when calling brpc_connect_rpc,
// so client sockets get the C trampoline with zero ctypes on the hot path.
PyObject* py_response_cb_ptr(PyObject*, PyObject*) {
  return PyLong_FromVoidPtr((void*)fast_response_cb);
}

// Single-copy IOBuf -> bytes (lib.py IOBuf.to_bytes rode
// create_string_buffer + .raw slice: two copies plus a zero-init per
// call — visible on the h2 frame path at 6 frames/unary-call).
PyObject* py_iobuf_bytes(PyObject*, PyObject* args) {
  unsigned long long handle;
  Py_ssize_t pos = 0;
  Py_ssize_t n = -1;
  if (!PyArg_ParseTuple(args, "K|nn", &handle, &pos, &n)) return nullptr;
  auto* b = (butil::IOBuf*)(uintptr_t)handle;
  const Py_ssize_t size = (Py_ssize_t)b->size();
  if (pos < 0 || pos > size) pos = size;
  Py_ssize_t avail = size - pos;
  if (n < 0 || n > avail) n = avail;
  PyObject* out = PyBytes_FromStringAndSize(nullptr, n);
  if (out == nullptr) return nullptr;
  if (n > 0) {
    const size_t got = b->copy_to(PyBytes_AS_STRING(out), (size_t)n,
                                  (size_t)pos);
    if ((Py_ssize_t)got != n && _PyBytes_Resize(&out, (Py_ssize_t)got) < 0)
      return nullptr;
  }
  return out;
}

// upcall_wait() -> (wait_us, queue_depth) of the upcall this thread is
// running, or None outside one (net/rpc.h, UpcallTicket).
PyObject* py_upcall_wait(PyObject*, PyObject*) {
  int64_t wait_us = 0;
  int64_t depth = 0;
  if (!brpc::CurrentUpcallWait(&wait_us, &depth)) Py_RETURN_NONE;
  return Py_BuildValue("(LL)", (long long)wait_us, (long long)depth);
}

// ---- native span queue (ISSUE 9: off-thread rpcz recording) ----
//
// rpcz.submit used to pay two Python lock acquisitions (speed-limit
// grab + collector pending append) plus a wrapper allocation per span,
// ON the token path.  Now the hot side is ONE lock-free Treiber push of
// the span object (incref under the GIL we already hold, CAS, done);
// the collector thread drains the stack in FIFO order and does the
// rate-limiting, store append and SpanDB IO there.  Same shape as
// bthread's ExecutionQueue producer half — a drain-side-serialized MPSC
// stack — holding PyObject* instead of nodes on an Executor.

// The stack itself lives in spanq.h (ISSUE 14) so `make tsan`'s ring
// stress exercises the exact producer/drain algorithm without Python.
brpc_spanq::Stack g_spanq;

PyObject* py_spanq_push(PyObject*, PyObject* arg) {
  Py_INCREF(arg);
  g_spanq.push(arg);
  Py_RETURN_NONE;
}

PyObject* py_spanq_drain(PyObject*, PyObject*) {
  int64_t count = 0;
  brpc_spanq::Node* chain = g_spanq.drain_fifo(&count);
  if (count > 0) {
    // drain cadence on the collector thread (one event per BATCH; the
    // per-span push stays event-free, same discipline as TokenRing)
    butil::flight::record(butil::flight::EV_SPANQ_DRAIN, 0, count);
  }
  PyObject* out = PyList_New((Py_ssize_t)count);
  if (out == nullptr) {
    // push the chain back so the spans are not lost (order within
    // this failed batch is preserved relative to itself)
    while (chain != nullptr) {
      brpc_spanq::Node* next = chain->next;
      g_spanq.push_node(chain);
      chain = next;
    }
    return nullptr;
  }
  Py_ssize_t i = 0;
  while (chain != nullptr) {
    PyList_SET_ITEM(out, i++, (PyObject*)chain->obj);  // steals the ref
    brpc_spanq::Node* next = chain->next;
    delete chain;
    chain = next;
  }
  return out;
}

PyObject* py_spanq_pending(PyObject*, PyObject*) {
  return PyLong_FromLongLong(g_spanq.count());
}

// ---- native batch assembly + token-ring fast entries (ISSUE 9) ----
//
// The ctypes bindings in _core/lib.py pay ~25us of marshalling per
// call (a .ctypes view object per numpy row) and ALWAYS drop the GIL —
// right for a bulk or blocking call, fatally wrong for the per-token
// and per-formation hot path.  These entries parse via the buffer
// protocol (no per-row Python objects) and choose per call whether the
// GIL is worth releasing: batch_pad/page_table_fill release it for the
// memset+memcpy pass only; tokring_push HOLDS it — a sub-microsecond
// mutex push is cheaper than a GIL handoff convoy.

extern "C" int brpc_tokring_push(void* h, int32_t tok);  // serving_hotpath.cc

// batch_pad(out2d, rows) -> None.  Zero-fill the C-contiguous 2-D
// buffer `out2d`, then copy rows[i]'s bytes into row i (truncated to
// the row stride).  Rows must be C-contiguous 1-D buffers of out's
// dtype (the batcher's enqueue coercion guarantees this).
PyObject* py_batch_pad(PyObject*, PyObject* args) {
  PyObject* out_obj;
  PyObject* rows_obj;
  if (!PyArg_ParseTuple(args, "OO", &out_obj, &rows_obj)) return nullptr;
  Py_buffer out;
  if (PyObject_GetBuffer(out_obj, &out,
                         PyBUF_WRITABLE | PyBUF_STRIDES) != 0) {
    return nullptr;
  }
  if (out.ndim != 2 || !PyBuffer_IsContiguous(&out, 'C')) {
    PyBuffer_Release(&out);
    PyErr_SetString(PyExc_ValueError, "out must be C-contiguous 2-D");
    return nullptr;
  }
  PyObject* fast = PySequence_Fast(rows_obj, "rows must be a sequence");
  if (fast == nullptr) {
    PyBuffer_Release(&out);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
  if (n > out.shape[0]) {
    Py_DECREF(fast);
    PyBuffer_Release(&out);
    PyErr_SetString(PyExc_ValueError, "more rows than out has");
    return nullptr;
  }
  // collect every row buffer under the GIL, then copy without it
  Py_buffer* rows = (Py_buffer*)PyMem_Malloc(sizeof(Py_buffer) * (n ? n : 1));
  if (rows == nullptr) {
    Py_DECREF(fast);
    PyBuffer_Release(&out);
    return PyErr_NoMemory();
  }
  Py_ssize_t got = 0;
  for (; got < n; ++got) {
    if (PyObject_GetBuffer(PySequence_Fast_GET_ITEM(fast, got),
                           &rows[got], PyBUF_SIMPLE) != 0) {
      break;
    }
  }
  if (got < n) {
    for (Py_ssize_t i = 0; i < got; ++i) PyBuffer_Release(&rows[i]);
    PyMem_Free(rows);
    Py_DECREF(fast);
    PyBuffer_Release(&out);
    return nullptr;
  }
  const Py_ssize_t stride = out.strides[0];
  Py_BEGIN_ALLOW_THREADS
  memset(out.buf, 0, (size_t)out.len);
  for (Py_ssize_t i = 0; i < n; ++i) {
    Py_ssize_t m = rows[i].len < stride ? rows[i].len : stride;
    if (m > 0) memcpy((char*)out.buf + i * stride, rows[i].buf, (size_t)m);
  }
  Py_END_ALLOW_THREADS
  for (Py_ssize_t i = 0; i < n; ++i) PyBuffer_Release(&rows[i]);
  PyMem_Free(rows);
  Py_DECREF(fast);
  PyBuffer_Release(&out);
  Py_RETURN_NONE;
}

// page_table_fill(table2d_int32, lists, slot_idx) -> None.  Fill the
// C-contiguous int32 table with -1, then copy int32 buffer lists[k]
// into row slot_idx[k] (truncated to the table width).
PyObject* py_page_table_fill(PyObject*, PyObject* args) {
  PyObject* table_obj;
  PyObject* lists_obj;
  PyObject* idx_obj;
  if (!PyArg_ParseTuple(args, "OOO", &table_obj, &lists_obj, &idx_obj)) {
    return nullptr;
  }
  Py_buffer table;
  if (PyObject_GetBuffer(table_obj, &table,
                         PyBUF_WRITABLE | PyBUF_STRIDES) != 0) {
    return nullptr;
  }
  if (table.ndim != 2 || !PyBuffer_IsContiguous(&table, 'C') ||
      table.itemsize != 4) {
    PyBuffer_Release(&table);
    PyErr_SetString(PyExc_ValueError,
                    "table must be C-contiguous 2-D int32");
    return nullptr;
  }
  PyObject* lists = PySequence_Fast(lists_obj, "lists must be a sequence");
  PyObject* idx = lists ? PySequence_Fast(idx_obj,
                                          "slot_idx must be a sequence")
                        : nullptr;
  if (idx == nullptr) {
    Py_XDECREF(lists);
    PyBuffer_Release(&table);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(lists);
  const Py_ssize_t rows = table.shape[0];
  const Py_ssize_t width_bytes = table.strides[0];
  if (PySequence_Fast_GET_SIZE(idx) != n) {
    Py_DECREF(lists);
    Py_DECREF(idx);
    PyBuffer_Release(&table);
    PyErr_SetString(PyExc_ValueError, "lists/slot_idx length mismatch");
    return nullptr;
  }
  // collect every row index and id buffer under the GIL, then do the
  // -1 fill + row copies without it (same discipline as batch_pad —
  // the module header and the engine call site both promise it)
  Py_buffer* ids =
      (Py_buffer*)PyMem_Malloc(sizeof(Py_buffer) * (n ? n : 1));
  long* rowidx = (long*)PyMem_Malloc(sizeof(long) * (n ? n : 1));
  if (ids == nullptr || rowidx == nullptr) {
    PyMem_Free(ids);
    PyMem_Free(rowidx);
    Py_DECREF(lists);
    Py_DECREF(idx);
    PyBuffer_Release(&table);
    return PyErr_NoMemory();
  }
  Py_ssize_t got = 0;
  for (; got < n; ++got) {
    long row = PyLong_AsLong(PySequence_Fast_GET_ITEM(idx, got));
    if ((row == -1 && PyErr_Occurred()) || row < 0 || row >= rows) {
      if (!PyErr_Occurred()) {
        PyErr_SetString(PyExc_ValueError, "slot index out of range");
      }
      break;
    }
    rowidx[got] = row;
    if (PyObject_GetBuffer(PySequence_Fast_GET_ITEM(lists, got),
                           &ids[got], PyBUF_SIMPLE) != 0) {
      break;
    }
  }
  if (got < n) {
    for (Py_ssize_t i = 0; i < got; ++i) PyBuffer_Release(&ids[i]);
    PyMem_Free(ids);
    PyMem_Free(rowidx);
    Py_DECREF(lists);
    Py_DECREF(idx);
    PyBuffer_Release(&table);
    return nullptr;
  }
  Py_BEGIN_ALLOW_THREADS
  int32_t* base = (int32_t*)table.buf;
  const Py_ssize_t total = table.len / 4;
  for (Py_ssize_t i = 0; i < total; ++i) base[i] = -1;
  for (Py_ssize_t k = 0; k < n; ++k) {
    Py_ssize_t m = ids[k].len < width_bytes ? ids[k].len : width_bytes;
    if (m > 0) {
      memcpy((char*)table.buf + rowidx[k] * width_bytes, ids[k].buf,
             (size_t)m);
    }
  }
  Py_END_ALLOW_THREADS
  for (Py_ssize_t i = 0; i < n; ++i) PyBuffer_Release(&ids[i]);
  PyMem_Free(ids);
  PyMem_Free(rowidx);
  Py_DECREF(lists);
  Py_DECREF(idx);
  PyBuffer_Release(&table);
  Py_RETURN_NONE;
}

// tokring_push(handle, tok) -> 1 pushed / 0 full.  Deliberately HOLDS
// the GIL: the ring mutex is held for nanoseconds and never blocks, so
// a GIL release/reacquire per token would cost more than the push (and
// under N producer threads becomes a handoff convoy).
PyObject* py_tokring_push(PyObject*, PyObject* args) {
  unsigned long long handle;
  int tok;
  if (!PyArg_ParseTuple(args, "Ki", &handle, &tok)) return nullptr;
  return PyLong_FromLong(
      brpc_tokring_push((void*)(uintptr_t)handle, (int32_t)tok));
}

PyMethodDef kMethods[] = {
    {"spanq_push", py_spanq_push, METH_O,
     "Push one span object onto the native MPSC queue (lock-free)."},
    {"spanq_drain", py_spanq_drain, METH_NOARGS,
     "Drain every queued span, FIFO order -> list."},
    {"spanq_pending", py_spanq_pending, METH_NOARGS,
     "Spans pushed but not yet drained."},
    {"batch_pad", py_batch_pad, METH_VARARGS,
     "batch_pad(out2d, rows): zero-fill + row gather, GIL released."},
    {"page_table_fill", py_page_table_fill, METH_VARARGS,
     "page_table_fill(table2d, lists, slot_idx): -1 fill + row copy."},
    {"tokring_push", py_tokring_push, METH_VARARGS,
     "tokring_push(handle, tok) -> 1 pushed / 0 full (GIL held)."},
    {"send_request", py_send_request, METH_VARARGS,
     "send_request(sid, cid, attempt, service, method, timeout_ms, "
     "compress, content_type, body) -> rc"},
    {"send_response", py_send_response, METH_VARARGS,
     "send_response(sid, cid, attempt, error_code, error_text, "
     "content_type, body) -> rc"},
    {"set_request_handler", py_set_request_handler, METH_O,
     "Install the process-wide pre-parsed request handler."},
    {"set_response_handler", py_set_response_handler, METH_O,
     "Install the process-wide pre-parsed response handler."},
    {"response_cb_ptr", py_response_cb_ptr, METH_NOARGS,
     "Address of the C response trampoline (for brpc_connect_rpc)."},
    {"iobuf_bytes", py_iobuf_bytes, METH_VARARGS,
     "iobuf_bytes(handle, pos=0, n=-1) -> bytes (single copy)"},
    {"upcall_wait", py_upcall_wait, METH_NOARGS,
     "(wait_us, queue_depth) of the running upcall's frame, or None."},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef kModule = {PyModuleDef_HEAD_INIT, "_fastrpc",
                       "Zero-ctypes RPC hot boundary", -1, kMethods};

}  // namespace

PyMODINIT_FUNC PyInit__fastrpc() {
  FastBodyType.tp_dealloc = fastbody_dealloc;
  FastBodyType.tp_flags = Py_TPFLAGS_DEFAULT;
  FastBodyType.tp_as_buffer = &fastbody_as_buffer;
  FastBodyType.tp_as_sequence = &fastbody_as_sequence;
  FastBodyType.tp_doc = "IOBuf-backed read-only buffer (zero-copy body)";
  FastBodyType.tp_new = nullptr;  // only created from C
  if (PyType_Ready(&FastBodyType) < 0) return nullptr;
  return PyModule_Create(&kModule);
}
