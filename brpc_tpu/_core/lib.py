"""ctypes bindings to libbrpc_core.so — the native host core.

The native core owns the transport hot path (epoll dispatchers, wait-free
socket writes, frame parsing, IOBuf block management, work-stealing executor,
timer thread); Python is the protocol/API layer above it, mirroring how the
reference layers generated protobuf stubs over its C++ core.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

MSG_TRPC = 0
MSG_HTTP = 1
MSG_REDIS = 2
MSG_MEMCACHE = 3
MSG_THRIFT = 4
MSG_MONGO = 5
MSG_H2 = 6
MSG_RAW = 7
MSG_NSHEAD = 8
MSG_FILTERED = 9   # transport-filter ciphertext (in-socket TLS)

_here = os.path.dirname(os.path.abspath(__file__))
_libpath = os.path.join(_here, "libbrpc_core.so")


def _build_if_needed() -> None:
    """Build the native core where the checkout has none (the .so
    files are git-ignored, so a fresh copy always builds, ~10 s).  The
    compiler's output goes to stderr, and a failed build raises HERE:
    loading whatever half of the pair an earlier or interrupted build
    left behind would run a library that does not match the sources."""
    built = (_libpath, os.path.join(_here, "_fastrpc.so"))
    if all(os.path.exists(p) for p in built):
        return
    repo = os.path.dirname(os.path.dirname(_here))
    # make's stdout joins its stderr on OUR stderr (fd 2): a program's
    # stdout may be a result someone parses
    proc = subprocess.run(["make", "-j8"], cwd=repo, stdout=2)
    if proc.returncode != 0 or not all(os.path.exists(p) for p in built):
        raise ImportError(
            f"building the native core failed (`make -j8` in {repo} "
            f"exited {proc.returncode}); the compiler's output is above")


_build_if_needed()
core = ctypes.CDLL(_libpath)

# Callback signatures (see src/cc/capi.cc).
# meta is c_void_p, NOT c_char_p: meta is opaque binary (may contain NULs) and
# ctypes would strlen-truncate a c_char_p argument.  Read it with
# ctypes.string_at(meta, meta_len).
MESSAGE_CB = ctypes.CFUNCTYPE(None, ctypes.c_uint64, ctypes.c_int,
                              ctypes.c_void_p, ctypes.c_size_t,
                              ctypes.c_void_p, ctypes.c_void_p)
FAILED_CB = ctypes.CFUNCTYPE(None, ctypes.c_uint64, ctypes.c_int,
                             ctypes.c_void_p)
ACCEPTED_CB = ctypes.CFUNCTYPE(None, ctypes.c_uint64, ctypes.c_uint64,
                               ctypes.c_void_p)
TASK_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
DELETER_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p)


class RequestHeader(ctypes.Structure):
    """Mirror of brpc::RequestHeader (src/cc/net/rpc.h) — a natively
    pre-parsed TRPC meta.  Pointer fields alias the native meta buffer and
    are only valid during the callback."""
    _fields_ = [
        ("cid", ctypes.c_uint64),
        ("timeout_ms", ctypes.c_uint32),
        ("present_mask", ctypes.c_uint32),
        ("service", ctypes.c_void_p),
        ("service_len", ctypes.c_uint32),
        ("method", ctypes.c_void_p),
        ("method_len", ctypes.c_uint32),
        ("attempt", ctypes.c_uint16),
        ("compress", ctypes.c_uint8),
        ("msg_type", ctypes.c_uint8),
        ("content_type", ctypes.c_void_p),
        ("content_type_len", ctypes.c_uint32),
        ("error_code", ctypes.c_int32),
        ("error_text", ctypes.c_void_p),
        ("error_text_len", ctypes.c_uint32),
        ("attachment_size", ctypes.c_uint64),
    ]


REQUEST_CB = ctypes.CFUNCTYPE(None, ctypes.c_uint64,
                              ctypes.POINTER(RequestHeader), ctypes.c_void_p,
                              ctypes.c_void_p)
RESPONSE_CB = ctypes.CFUNCTYPE(None, ctypes.c_uint64,
                               ctypes.POINTER(RequestHeader), ctypes.c_void_p,
                               ctypes.c_void_p)
NATIVE_METHOD_FN = ctypes.CFUNCTYPE(ctypes.c_int32, ctypes.c_uint64,
                                    ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_void_p)
# Native h2 session event (src/cc/net/h2.h H2EventCallback): sid,
# stream_id, kind, service/len, method/len, headers/len ("k\0v\0" pairs),
# body IOBuf* (owned by callee; may be NULL), grpc message flags, user.
H2_EVENT_CB = ctypes.CFUNCTYPE(None, ctypes.c_uint64, ctypes.c_uint32,
                               ctypes.c_int, ctypes.c_void_p,
                               ctypes.c_size_t, ctypes.c_void_p,
                               ctypes.c_size_t, ctypes.c_void_p,
                               ctypes.c_size_t, ctypes.c_void_p,
                               ctypes.c_int, ctypes.c_void_p)

_sigs = {
    "brpc_core_init": (None, [ctypes.c_int, ctypes.c_int]),
    "brpc_core_shutdown": (None, []),
    "brpc_set_min_log_level": (None, [ctypes.c_int]),
    "brpc_crc32c": (ctypes.c_uint32, [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_uint32]),
    # snappy block-format codec (butil/snappy.cc)
    "brpc_snappy_max_compressed_length": (ctypes.c_size_t,
                                          [ctypes.c_size_t]),
    "brpc_snappy_compress": (ctypes.c_size_t,
                             [ctypes.c_char_p, ctypes.c_size_t,
                              ctypes.c_void_p]),
    "brpc_snappy_uncompressed_length": (ctypes.c_int64,
                                        [ctypes.c_char_p, ctypes.c_size_t]),
    "brpc_snappy_decompress": (ctypes.c_int,
                               [ctypes.c_char_p, ctypes.c_size_t,
                                ctypes.c_void_p, ctypes.c_size_t]),
    # native CPU profiler (butil/profiler.cc)
    "brpc_prof_start": (ctypes.c_int, [ctypes.c_int]),
    "brpc_prof_stop": (ctypes.c_int, []),
    "brpc_prof_dump": (ctypes.c_int, [ctypes.c_char_p]),
    "brpc_prof_folded": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_size_t]),
    "brpc_prof_samples": (ctypes.c_int64, []),
    "brpc_iobuf_new": (ctypes.c_void_p, []),
    "brpc_iobuf_free": (None, [ctypes.c_void_p]),
    "brpc_iobuf_clear": (None, [ctypes.c_void_p]),
    "brpc_iobuf_size": (ctypes.c_size_t, [ctypes.c_void_p]),
    "brpc_iobuf_block_num": (ctypes.c_size_t, [ctypes.c_void_p]),
    "brpc_iobuf_append": (None, [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]),
    "brpc_iobuf_append_iobuf": (None, [ctypes.c_void_p, ctypes.c_void_p]),
    "brpc_iobuf_copy_to": (ctypes.c_size_t, [ctypes.c_void_p, ctypes.c_void_p,
                                             ctypes.c_size_t, ctypes.c_size_t]),
    "brpc_iobuf_cutn": (ctypes.c_size_t, [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_size_t]),
    "brpc_iobuf_pop_front": (ctypes.c_size_t, [ctypes.c_void_p, ctypes.c_size_t]),
    "brpc_iobuf_append_user_data": (None, [ctypes.c_void_p, ctypes.c_void_p,
                                           ctypes.c_size_t, DELETER_CB,
                                           ctypes.c_void_p]),
    "brpc_iobuf_live_blocks": (ctypes.c_int64, []),
    "brpc_executor_submit": (None, [TASK_CB, ctypes.c_void_p]),
    "brpc_executor_tasks_executed": (ctypes.c_int64, []),
    "brpc_executor_steals": (ctypes.c_int64, []),
    "brpc_fiber_counters": (None, [ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64)]),
    "brpc_executor_num_workers": (ctypes.c_int, []),
    "brpc_timer_add": (ctypes.c_uint64, [TASK_CB, ctypes.c_void_p, ctypes.c_int64]),
    "brpc_timer_cancel": (ctypes.c_int, [ctypes.c_uint64]),
    "brpc_timer_fired": (ctypes.c_int64, []),
    "brpc_now_us": (ctypes.c_int64, []),
    "brpc_listen": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_int, MESSAGE_CB,
                                   FAILED_CB, ACCEPTED_CB, ctypes.c_void_p,
                                   ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
                                   ctypes.POINTER(ctypes.c_int)]),
    "brpc_connect": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_int, MESSAGE_CB,
                                    FAILED_CB, ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint64)]),
    "brpc_socket_write_frame": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_char_p,
                                               ctypes.c_size_t, ctypes.c_char_p,
                                               ctypes.c_size_t, ctypes.c_void_p]),
    "brpc_socket_write_raw": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_char_p,
                                             ctypes.c_size_t, ctypes.c_void_p]),
    "brpc_socket_set_protocol": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_int]),
    # transport filter (in-socket TLS)
    "brpc_socket_set_filter": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_int]),
    "brpc_socket_inject": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_char_p,
                                          ctypes.c_size_t]),
    "brpc_socket_set_failed": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_int]),
    "brpc_socket_alive": (ctypes.c_int, [ctypes.c_uint64]),
    "brpc_socket_stats": (ctypes.c_int, [ctypes.c_uint64,
                                         ctypes.POINTER(ctypes.c_int64),
                                         ctypes.POINTER(ctypes.c_int64),
                                         ctypes.POINTER(ctypes.c_int64),
                                         ctypes.c_char_p, ctypes.c_int,
                                         ctypes.POINTER(ctypes.c_int)]),
    "brpc_socket_active_count": (ctypes.c_int64, []),
    "brpc_socket_traffic": (None, [ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64)]),
    # bvar combiners (per-thread cells, src/cc/bvar/combiner.h)
    "brpc_atomic_new": (ctypes.c_void_p, []),
    "brpc_atomic_free": (None, [ctypes.c_void_p]),
    "brpc_atomic_incr": (ctypes.c_int64, [ctypes.c_void_p, ctypes.c_int64]),
    "brpc_atomic_get": (ctypes.c_int64, [ctypes.c_void_p]),
    "brpc_adder_new": (ctypes.c_void_p, []),
    "brpc_adder_free": (None, [ctypes.c_void_p]),
    "brpc_adder_add": (None, [ctypes.c_void_p, ctypes.c_int64]),
    "brpc_adder_get": (ctypes.c_int64, [ctypes.c_void_p]),
    "brpc_latency_new": (ctypes.c_void_p, []),
    "brpc_latency_free": (None, [ctypes.c_void_p]),
    "brpc_latency_record": (None, [ctypes.c_void_p, ctypes.c_int64]),
    "brpc_latency_stats": (None, [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.POINTER(ctypes.c_int64)]),
    "brpc_latency_percentile": (ctypes.c_double, [ctypes.c_void_p,
                                                  ctypes.c_double]),
    "brpc_socket_set_overcrowded_limit": (None, [ctypes.c_int64]),
    "brpc_socket_overcrowded_limit": (ctypes.c_int64, []),
    "brpc_socket_pending_write": (ctypes.c_int64, [ctypes.c_uint64]),
    # native unary RPC hot path
    "brpc_register_python_method": (None, [ctypes.c_char_p, ctypes.c_char_p]),
    "brpc_register_native_method": (None, [ctypes.c_char_p, ctypes.c_char_p,
                                           NATIVE_METHOD_FN, ctypes.c_void_p,
                                           ctypes.c_int]),
    "brpc_unregister_method": (ctypes.c_int, [ctypes.c_char_p,
                                              ctypes.c_char_p]),
    "brpc_set_request_callback": (None, [REQUEST_CB, ctypes.c_void_p]),
    "brpc_rpc_dropped_responses": (ctypes.c_int64, []),
    "brpc_rpc_counters": (None, [ctypes.POINTER(ctypes.c_int64),
                                 ctypes.POINTER(ctypes.c_int64)]),
    "brpc_send_response": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_uint64,
                                          ctypes.c_uint16, ctypes.c_int32,
                                          ctypes.c_char_p, ctypes.c_char_p,
                                          ctypes.c_char_p, ctypes.c_size_t,
                                          ctypes.c_void_p]),
    "brpc_send_request": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_uint64,
                                         ctypes.c_uint16, ctypes.c_char_p,
                                         ctypes.c_char_p, ctypes.c_uint32,
                                         ctypes.c_uint8, ctypes.c_char_p,
                                         ctypes.c_char_p, ctypes.c_size_t,
                                         ctypes.c_void_p]),
    "brpc_listen_rpc": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_int,
                                       MESSAGE_CB, FAILED_CB, ACCEPTED_CB,
                                       ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64),
                                       ctypes.POINTER(ctypes.c_int)]),
    "brpc_connect_rpc": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_int,
                                        MESSAGE_CB, FAILED_CB, RESPONSE_CB,
                                        ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_uint64)]),
    # native h2/gRPC server data plane (src/cc/net/h2.h)
    "brpc_listen_rpc_h2": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_int,
                                          MESSAGE_CB, FAILED_CB, ACCEPTED_CB,
                                          ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_uint64),
                                          ctypes.POINTER(ctypes.c_int)]),
    "brpc_h2_set_event_cb": (None, [H2_EVENT_CB, ctypes.c_void_p]),
    "brpc_h2_respond_unary": (ctypes.c_int, [ctypes.c_uint64,
                                             ctypes.c_uint32, ctypes.c_int,
                                             ctypes.c_char_p,
                                             ctypes.c_size_t,
                                             ctypes.c_char_p,
                                             ctypes.c_size_t,
                                             ctypes.c_char_p,
                                             ctypes.c_size_t]),
    "brpc_h2_send_response_headers": (ctypes.c_int, [ctypes.c_uint64,
                                                     ctypes.c_uint32,
                                                     ctypes.c_char_p,
                                                     ctypes.c_size_t]),
    "brpc_h2_send_message": (ctypes.c_int, [ctypes.c_uint64,
                                            ctypes.c_uint32,
                                            ctypes.c_char_p, ctypes.c_size_t,
                                            ctypes.c_int]),
    "brpc_h2_send_trailers": (ctypes.c_int, [ctypes.c_uint64,
                                             ctypes.c_uint32, ctypes.c_int,
                                             ctypes.c_char_p,
                                             ctypes.c_size_t,
                                             ctypes.c_char_p,
                                             ctypes.c_size_t]),
    "brpc_h2_native_stats": (None, [ctypes.POINTER(ctypes.c_int64),
                                    ctypes.POINTER(ctypes.c_int64),
                                    ctypes.POINTER(ctypes.c_int64)]),
    # gRPC unary pump against an existing server's NATIVE h2 plane
    "brpc_bench_register_native_echo": (None, [ctypes.c_char_p,
                                               ctypes.c_char_p,
                                               ctypes.c_int]),
    "brpc_bench_pump_h2": (ctypes.c_int, [ctypes.c_int, ctypes.c_char_p,
                                          ctypes.c_int, ctypes.c_int,
                                          ctypes.c_uint64, ctypes.c_int,
                                          ctypes.POINTER(ctypes.c_double),
                                          ctypes.POINTER(ctypes.c_double),
                                          ctypes.POINTER(ctypes.c_double)]),
    "brpc_bench_echo": (ctypes.c_int, [ctypes.c_int, ctypes.c_int,
                                       ctypes.c_uint64, ctypes.c_int,
                                       ctypes.c_int,
                                       ctypes.POINTER(ctypes.c_double),
                                       ctypes.POINTER(ctypes.c_double),
                                       ctypes.POINTER(ctypes.c_double)]),
    # usercode admission control (net/rpc.h; latency-budget ELIMIT sheds)
    "brpc_set_usercode_budget_us": (None, [ctypes.c_int64]),
    "brpc_usercode_budget_us": (ctypes.c_int64, []),
    "brpc_usercode_shed_count": (ctypes.c_int64, []),
    "brpc_usercode_pending": (ctypes.c_int64, []),
    "brpc_usercode_ema_us": (ctypes.c_double, []),
    "brpc_set_usercode_inline": (None, [ctypes.c_int]),
    "brpc_usercode_inline": (ctypes.c_int, []),
    # contention sampler (per-site stacks on contended FiberMutex locks)
    "brpc_contention_folded": (ctypes.c_int, [ctypes.c_char_p,
                                              ctypes.c_size_t]),
    "brpc_contention_events": (ctypes.c_int64, []),
    "brpc_contention_samples": (ctypes.c_int64, []),
    "brpc_contention_reset": (None, []),
    "brpc_contention_selftest": (ctypes.c_int, [ctypes.c_int, ctypes.c_int,
                                                ctypes.c_int]),
    # IOBuf block-allocation-site sampler (/memory)
    "brpc_iobuf_alloc_folded": (ctypes.c_int, [ctypes.c_char_p,
                                               ctypes.c_size_t]),
    "brpc_iobuf_alloc_events": (ctypes.c_int64, []),
    "brpc_iobuf_alloc_reset": (None, []),
    # fiber / butex (coroutine M:N runtime, src/cc/bthread/fiber.h)
    "brpc_fiber_demo_start": (ctypes.c_void_p, [ctypes.c_int]),
    "brpc_fiber_demo_blocked": (ctypes.c_int, [ctypes.c_void_p]),
    "brpc_fiber_demo_started": (ctypes.c_int64, [ctypes.c_void_p]),
    "brpc_fiber_demo_release": (None, [ctypes.c_void_p]),
    "brpc_fiber_demo_join": (ctypes.c_int, [ctypes.c_void_p, ctypes.c_int]),
    "brpc_fiber_demo_free": (None, [ctypes.c_void_p]),
    "brpc_fiber_pingpong": (ctypes.c_int, [ctypes.c_int, ctypes.c_int]),
    "brpc_fiber_mutex_stress": (ctypes.c_int64, [ctypes.c_int, ctypes.c_int,
                                                 ctypes.c_int]),
    "brpc_fiber_sleep_probe": (ctypes.c_int64, [ctypes.c_int64,
                                                ctypes.c_int]),
    "brpc_fiber_cond_stress": (ctypes.c_int64, [ctypes.c_int64,
                                                ctypes.c_int]),
    # CallId (bthread_id analog, src/cc/bthread/id.h)
    # fd wait (net/fd_wait.h): events bit1=read, bit2=write
    "brpc_fd_wait": (ctypes.c_int, [ctypes.c_int, ctypes.c_uint32,
                                    ctypes.c_int]),
    "brpc_fiber_fd_wait_probe": (ctypes.c_int, [ctypes.c_int,
                                                ctypes.c_uint32,
                                                ctypes.c_int]),
    "brpc_id_create": (ctypes.c_uint64, [ctypes.c_uint32]),
    "brpc_id_valid": (ctypes.c_int, [ctypes.c_uint64]),
    "brpc_id_trylock": (ctypes.c_int, [ctypes.c_uint64]),
    "brpc_id_unlock": (ctypes.c_int, [ctypes.c_uint64]),
    "brpc_id_unlock_and_destroy": (ctypes.c_int, [ctypes.c_uint64]),
    "brpc_id_join": (ctypes.c_int, [ctypes.c_uint64, ctypes.c_int]),
    "brpc_id_live_count": (ctypes.c_int64, []),
    "brpc_id_lock_stress": (ctypes.c_int64, [ctypes.c_int, ctypes.c_int,
                                             ctypes.c_int]),
    "brpc_id_destroy_stress": (ctypes.c_int64, [ctypes.c_int,
                                                ctypes.c_int]),
    "brpc_fiber_sem_stress": (ctypes.c_int, [ctypes.c_int, ctypes.c_int,
                                             ctypes.c_int, ctypes.c_int]),
    "brpc_fiber_rw_stress": (ctypes.c_int64, [ctypes.c_int, ctypes.c_int,
                                              ctypes.c_int]),
    # native serving hot path (ISSUE 9; src/cc/serving_hotpath.cc):
    # bounded emit token rings with batch push/pop, batch-formation
    # pad, page-table gather — ctypes releases the GIL for each call
    "brpc_tokring_new": (ctypes.c_void_p, [ctypes.c_int]),
    "brpc_tokring_free": (None, [ctypes.c_void_p]),
    "brpc_tokring_live": (ctypes.c_int64, []),
    "brpc_tokring_push": (ctypes.c_int, [ctypes.c_void_p, ctypes.c_int32]),
    "brpc_tokring_push_many": (ctypes.c_int,
                               [ctypes.POINTER(ctypes.c_void_p),
                                ctypes.POINTER(ctypes.c_int32),
                                ctypes.c_int,
                                ctypes.POINTER(ctypes.c_uint8)]),
    "brpc_tokring_push_terminal": (ctypes.c_int, [ctypes.c_void_p,
                                                  ctypes.c_int32]),
    "brpc_tokring_pop_many": (ctypes.c_int,
                              [ctypes.c_void_p,
                               ctypes.POINTER(ctypes.c_int32),
                               ctypes.c_int, ctypes.c_int64,
                               ctypes.POINTER(ctypes.c_int),
                               ctypes.POINTER(ctypes.c_int32)]),
    "brpc_tokring_pop_each": (ctypes.c_int,
                              [ctypes.POINTER(ctypes.c_void_p),
                               ctypes.c_int,
                               ctypes.POINTER(ctypes.c_int32),
                               ctypes.c_int,
                               ctypes.POINTER(ctypes.c_int32),
                               ctypes.POINTER(ctypes.c_uint8)]),
    "brpc_tokring_size": (ctypes.c_int64, [ctypes.c_void_p]),
    # native flight recorder (ISSUE 15; src/cc/butil/flight.h):
    # always-on per-thread event rings in the C++ core — merged dump,
    # per-thread last-event table, stats, and the forced-stall probe
    "brpc_flight_enable": (None, [ctypes.c_int]),
    "brpc_flight_enabled": (ctypes.c_int, []),
    "brpc_flight_dump": (ctypes.c_int, [ctypes.c_char_p, ctypes.c_size_t,
                                        ctypes.c_int]),
    "brpc_flight_threads": (ctypes.c_int, [ctypes.c_char_p,
                                           ctypes.c_size_t]),
    "brpc_flight_stats": (None, [ctypes.POINTER(ctypes.c_int64),
                                 ctypes.POINTER(ctypes.c_int64),
                                 ctypes.POINTER(ctypes.c_int64)]),
    "brpc_flight_selftest_emit": (None, [ctypes.c_int, ctypes.c_uint64]),
    "brpc_flight_stall_probe": (ctypes.c_int, [ctypes.c_int]),
    # syscall attribution (ISSUE 15 satellite; ROADMAP 1(e))
    "brpc_syscall_counters": (None, [ctypes.POINTER(ctypes.c_int64),
                                     ctypes.POINTER(ctypes.c_int64),
                                     ctypes.POINTER(ctypes.c_int64),
                                     ctypes.POINTER(ctypes.c_int64)]),
    "brpc_write_size_hist": (ctypes.c_int, [ctypes.POINTER(ctypes.c_int64),
                                            ctypes.c_int]),
    "brpc_socket_syscalls": (ctypes.c_int, [ctypes.c_uint64,
                                            ctypes.POINTER(ctypes.c_int64),
                                            ctypes.POINTER(ctypes.c_int64)]),
    "brpc_batch_pad": (None, [ctypes.POINTER(ctypes.c_void_p),
                              ctypes.POINTER(ctypes.c_int64),
                              ctypes.c_int, ctypes.c_void_p,
                              ctypes.c_int64, ctypes.c_int64]),
    "brpc_page_table_fill": (None, [ctypes.POINTER(ctypes.c_void_p),
                                    ctypes.POINTER(ctypes.c_int64),
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.c_int, ctypes.c_void_p,
                                    ctypes.c_int, ctypes.c_int]),
}
for _name, (_res, _args) in _sigs.items():
    fn = getattr(core, _name)
    fn.restype = _res
    fn.argtypes = _args

_init_lock = threading.Lock()
_initialized = False


_fastrpc_cache = None
_fastrpc_attempts = 0


def _fastrpc_mod():
    """The _fastrpc C extension, or None while it is still being built
    (lazy: importing it at module scope would recurse through the
    build-on-import path).  Permanent failure is cached after a few
    tries — failed imports aren't in sys.modules, and paying the import
    machinery + ImportError on every to_bytes would tax the very hot
    path this accelerates."""
    global _fastrpc_cache, _fastrpc_attempts
    if _fastrpc_cache is None and _fastrpc_attempts < 3:
        _fastrpc_attempts += 1
        try:
            from brpc_tpu._core import _fastrpc as fb
            _fastrpc_cache = fb
        except Exception:
            return None
    return _fastrpc_cache


def core_init(num_workers: int = 0, num_dispatchers: int = 0) -> None:
    """Start the native executor, dispatchers and timer thread (idempotent).
    num_dispatchers=0 lets the native core size the epoll pool by CPU
    count (1 on small hosts — extra epoll threads only time-slice and
    inflate the p99 tail by whole scheduler quanta)."""
    global _initialized
    with _init_lock:
        if not _initialized:
            core.brpc_core_init(num_workers, num_dispatchers)
            _initialized = True


def core_shutdown() -> None:
    global _initialized
    with _init_lock:
        if _initialized:
            core.brpc_core_shutdown()
            _initialized = False


class IOBuf:
    """Python view of a native zero-copy chained buffer.

    Wraps the native butil::IOBuf (src/cc/butil/iobuf.h).  Appending shares
    or copies into refcounted 8KB blocks; moving data between IOBufs
    (``append_iobuf``, ``cutn``) never copies payload bytes.
    """

    __slots__ = ("handle", "_owned")

    def __init__(self, data: bytes | None = None, *, handle: int | None = None):
        if handle is not None:
            self.handle = handle
            self._owned = True
        else:
            self.handle = core.brpc_iobuf_new()
            self._owned = True
        if data:
            self.append(data)

    def __del__(self):
        h = getattr(self, "handle", None)
        if h and self._owned:
            core.brpc_iobuf_free(h)
            self.handle = None

    def __len__(self) -> int:
        return core.brpc_iobuf_size(self.handle)

    @property
    def block_count(self) -> int:
        return core.brpc_iobuf_block_num(self.handle)

    def append(self, data: bytes) -> None:
        core.brpc_iobuf_append(self.handle, data, len(data))

    def append_iobuf(self, other: "IOBuf") -> None:
        core.brpc_iobuf_append_iobuf(self.handle, other.handle)

    def cutn(self, n: int) -> "IOBuf":
        out = IOBuf()
        core.brpc_iobuf_cutn(self.handle, out.handle, n)
        return out

    def pop_front(self, n: int) -> int:
        return core.brpc_iobuf_pop_front(self.handle, n)

    def to_bytes(self, n: int | None = None, pos: int = 0) -> bytes:
        fb = _fastrpc_mod()
        if fb is not None:
            # single copy straight into the bytes object (the ctypes
            # fallback below pays two copies plus a zero-init)
            return fb.iobuf_bytes(self.handle, pos, -1 if n is None else n)
        size = len(self)
        if n is None:
            n = size - pos
        n = max(0, min(n, size - pos))
        buf = ctypes.create_string_buffer(n)
        got = core.brpc_iobuf_copy_to(self.handle, buf, n, pos)
        return buf.raw[:got]

    def clear(self) -> None:
        core.brpc_iobuf_clear(self.handle)


class TokenRing:
    """Python handle on one native bounded emit ring (ISSUE 9;
    src/cc/serving_hotpath.cc).  The hot calls — batch push from the
    decode step loop, batch pop from the emitter — run with the GIL
    released for the call's duration; the terminal marker's Python
    error OBJECT rides a wrapper slot whose exactly-once owner is
    decided by the native ring (first push_terminal wins), so native
    and Python state can never disagree about which error a consumer
    observes."""

    __slots__ = ("handle", "cap", "_terminal_obj", "_terminal_set",
                 "_tmu")

    def __init__(self, cap: int):
        self.cap = int(cap)
        self.handle = core.brpc_tokring_new(self.cap)
        self._terminal_obj = None
        self._terminal_set = False
        # terminal is once-per-request (cold): a tiny Python lock keeps
        # the error OBJECT slot and the native marker exactly-once
        # together; the per-token path never touches it
        self._tmu = threading.Lock()

    def __del__(self):
        h = getattr(self, "handle", None)
        if h:
            core.brpc_tokring_free(h)
            self.handle = None

    def push(self, tok: int) -> bool:
        # prefer the C-extension entry: it HOLDS the GIL (the ring
        # mutex is held for nanoseconds, so a ctypes GIL drop/reacquire
        # per token costs more than the push — and under N producer
        # threads becomes a handoff convoy)
        fb = _fastrpc_mod()
        if fb is not None:
            return bool(fb.tokring_push(self.handle, tok))
        return bool(core.brpc_tokring_push(self.handle, tok))

    def push_terminal(self, err) -> None:
        with self._tmu:
            if self._terminal_set:
                return
            # object BEFORE the native marker: a consumer that observes
            # the native terminal must find the winner's object in place
            self._terminal_obj = err
            self._terminal_set = True
            core.brpc_tokring_push_terminal(
                self.handle, getattr(err, "code", 0) or 0)

    def pop_many(self, out, timeout_s: float):
        """Drain into the caller's ctypes int32 array `out`; returns
        ``(count, terminal_seen, err_obj)``."""
        term = ctypes.c_int(0)
        errc = ctypes.c_int32(0)
        n = core.brpc_tokring_pop_many(
            self.handle, out, len(out), int(timeout_s * 1e6),
            ctypes.byref(term), ctypes.byref(errc))
        return n, bool(term.value), self._terminal_obj

    def __len__(self) -> int:
        return core.brpc_tokring_size(self.handle)


def tokring_live() -> int:
    """Globally live native emit rings (chaos-suite leak baseline)."""
    return core.brpc_tokring_live()
