"""Coverage-guided fuzzer for the h2 frame state machine + HPACK
(VERDICT r4 #7; reference analog: test/fuzzing/fuzz_hpack.cpp +
oss-fuzz.sh libFuzzer targets).

Neither atheris nor coverage.py exists in this image, so the feedback
loop is built on ``sys.monitoring`` (PEP 669): LINE events over every
code object in ``brpc_tpu.rpc.h2`` and ``brpc_tpu.rpc.hpack``, with the
callback returning ``sys.monitoring.DISABLE`` after the first hit of
each line — so steady-state overhead is near zero and anything the
callback reports IS new global coverage.  An input that lights up a
new line joins the corpus; mutations are the classic menu (bit flips,
byte splices, truncations, frame-header-aware length/type/flag
smashing, cross-member splices).

Input format: a byte string interpreted as a sequence of h2 frames
(9-byte header + payload, lengths clamped) fed straight into
``H2Connection.on_frame`` on a socketless connection — the same entry
the native parser feeds after frame reassembly.  The state machine must
never raise or hang; protocol errors must surface as GOAWAY/fatal.

Usage:
  python tools/fuzz_h2_cov.py --execs 1000000 [--seed 7]
      [--corpus-out /tmp/h2corpus]       # save the grown corpus
      [--replay-native PORT]             # replay corpus at a live port
"""
from __future__ import annotations

import argparse
import os
import random
import struct
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

TOOL_ID = 3  # sys.monitoring tool slot (0-5 free-form; 3 unused by std tools)


def _iter_code_objects(module):
    import types
    seen = set()

    def walk(code):
        if code in seen:
            return
        seen.add(code)
        yield code
        for const in code.co_consts:
            if isinstance(const, types.CodeType):
                yield from walk(const)

    for name in dir(module):
        obj = getattr(module, name)
        fn = None
        if isinstance(obj, types.FunctionType):
            fn = obj
        elif isinstance(obj, type):
            for m in vars(obj).values():
                f = getattr(m, "__func__", m)
                if isinstance(f, types.FunctionType):
                    yield from walk(f.__code__)
            continue
        if fn is not None:
            yield from walk(fn.__code__)


class CoverageTracker:
    """PEP 669 line tracker over a fixed set of code objects.  Lines
    auto-disable after their first report, so `hits` after a run holds
    exactly the NEW coverage."""

    BACKEND = "monitoring"

    def __init__(self, modules):
        self.hits: set = set()
        self.total_lines = 0
        mon = sys.monitoring
        mon.use_tool_id(TOOL_ID, "h2fuzz")
        mon.register_callback(TOOL_ID, mon.events.LINE, self._on_line)
        for module in modules:
            for code in _iter_code_objects(module):
                try:
                    mon.set_local_events(TOOL_ID, code, mon.events.LINE)
                    self.total_lines += len(set(
                        ln for _, _, ln in code.co_lines() if ln))
                except Exception:
                    pass

    def _on_line(self, code, line):
        self.hits.add((id(code), line))
        return sys.monitoring.DISABLE

    def take_new(self) -> int:
        n = len(self.hits)
        self.hits.clear()
        return n

    def close(self):
        mon = sys.monitoring
        mon.register_callback(TOOL_ID, mon.events.LINE, None)
        mon.free_tool_id(TOOL_ID)


def make_conn():
    """Socketless server-side H2Connection (mirrors the stub in
    tests/test_fuzz_parsers.py — kept separate so the tool runs without
    pytest)."""
    import threading

    from brpc_tpu.rpc import h2 as h2m
    from brpc_tpu.rpc.hpack import HpackDecoder, HpackEncoder

    class _Sink:
        def write_raw(self, sid, data):
            return 0

        def alive(self, sid):
            return True

    class _Conn(h2m.H2Connection):
        def __init__(self):
            self.sid = 1
            self.is_server = True
            self._tp = _Sink()
            self._enc = HpackEncoder()
            self._dec = HpackDecoder()
            self._send_lock = threading.Lock()
            self._fc = threading.Condition(threading.Lock())
            self.remote_conn_window = h2m.DEFAULT_WINDOW
            self.remote_initial_window = h2m.DEFAULT_WINDOW
            self.remote_max_frame = 16384
            self._recv_conn_consumed = 0
            self._streams = {}
            self._sent_settings = True
            self._goaway = False
            self._fatal = False
            self._cont_stream = None

        def on_stream_complete(self, st):
            self.close_stream(st.id)

    return _Conn()


MAX_FRAMES_PER_INPUT = 64
MAX_PAYLOAD = 4096


def run_input(data: bytes) -> None:
    """Interpret `data` as h2 frames and feed the state machine.  Any
    exception = a finding."""
    conn = make_conn()
    pos = 0
    frames = 0
    n = len(data)
    while pos + 9 <= n and frames < MAX_FRAMES_PER_INPUT:
        hdr9 = bytearray(data[pos:pos + 9])
        want = (hdr9[0] << 16) | (hdr9[1] << 8) | hdr9[2]
        take = min(want, MAX_PAYLOAD, n - pos - 9)
        # keep the header's declared length consistent with the slice so
        # length-vs-payload mismatches come from MUTATION of inner
        # structure, not from the driver's own slicing
        hdr9[0] = (take >> 16) & 0xFF
        hdr9[1] = (take >> 8) & 0xFF
        hdr9[2] = take & 0xFF
        payload = data[pos + 9:pos + 9 + take]
        conn.on_frame(bytes(hdr9), payload)
        pos += 9 + take
        frames += 1


def seeds(base_only: bool = False) -> list[bytes]:
    """Valid-ish conversations: real HPACK blocks, DATA with grpc
    framing, SETTINGS churn, CONTINUATION splits — mutation starts from
    structure, not noise.  base_only=True returns just the synthetic
    seeds (no evolved corpus) — the CI feedback-wiring check starts from
    these so corpus growth is actually expected within a short slice."""
    from brpc_tpu.rpc import h2 as h2m
    from brpc_tpu.rpc.hpack import HpackEncoder

    out = []
    enc = HpackEncoder()
    block = enc.encode([(":method", "POST"), (":path", "/svc/Method"),
                        ("content-type", "application/grpc"),
                        ("grpc-encoding", "gzip"), ("te", "trailers")])
    body = b"\x00" + struct.pack(">I", 16) + b"p" * 16
    out.append(h2m.build_frame(h2m.HEADERS, h2m.FLAG_END_HEADERS, 1, block)
               + h2m.build_frame(h2m.DATA, h2m.FLAG_END_STREAM, 1, body))
    half = len(block) // 2
    out.append(h2m.build_frame(h2m.HEADERS, 0, 3, block[:half])
               + h2m.build_frame(h2m.CONTINUATION, h2m.FLAG_END_HEADERS, 3,
                                 block[half:])
               + h2m.build_frame(h2m.DATA, h2m.FLAG_END_STREAM, 3, body))
    out.append(h2m.build_frame(h2m.SETTINGS, 0, 0,
                               struct.pack(">HI", 1, 0)
                               + struct.pack(">HI", 4, 1 << 20))
               + h2m.build_frame(h2m.PING, 0, 0, b"12345678")
               + h2m.build_frame(h2m.WINDOW_UPDATE, 0, 0,
                                 struct.pack(">I", 1 << 20)))
    out.append(h2m.build_frame(h2m.HEADERS,
                               h2m.FLAG_END_HEADERS | 0x08, 5,
                               b"\x04" + block + b"\x00" * 4))  # PADDED
    out.append(h2m.build_frame(h2m.RST_STREAM, 0, 1, struct.pack(">I", 8))
               + h2m.build_frame(h2m.GOAWAY, 0, 0, struct.pack(">II", 0, 2)))
    # evolved corpus from past campaigns (tests/fuzz_corpus/h2): inputs
    # that earned their place by lighting up new coverage — checked in
    # like the reference's OSS-Fuzz corpora so every later campaign and
    # the CI replay start from the deepest known frontier
    cdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "tests", "fuzz_corpus", "h2")
    if not base_only and os.path.isdir(cdir):
        for name in sorted(os.listdir(cdir)):
            if name.endswith(".bin"):
                with open(os.path.join(cdir, name), "rb") as f:
                    out.append(f.read())
    return out


def mutate(rng: random.Random, corpus: list[bytes]) -> bytes:
    data = bytearray(rng.choice(corpus))
    for _ in range(rng.randrange(1, 4)):
        op = rng.randrange(6)
        if not data:
            data = bytearray(rng.randbytes(16))
        if op == 0:      # bit flip
            i = rng.randrange(len(data))
            data[i] ^= 1 << rng.randrange(8)
        elif op == 1:    # byte splice from another member
            other = rng.choice(corpus)
            if other:
                i = rng.randrange(len(data) + 1)
                j = rng.randrange(len(other))
                k = rng.randrange(j, min(len(other), j + 64) + 1)
                data[i:i] = other[j:k]
        elif op == 2:    # truncate
            data = data[:rng.randrange(len(data) + 1)]
        elif op == 3 and len(data) >= 9:  # smash a frame header
            base = 9 * rng.randrange(max(1, len(data) // 9))
            if base + 9 <= len(data):
                field = rng.randrange(3)
                if field == 0:
                    data[base + 3] = rng.randrange(256)   # type
                elif field == 1:
                    data[base + 4] = rng.randrange(256)   # flags
                else:
                    struct.pack_into(">I", data, base + 5,
                                     rng.getrandbits(31))  # stream id
        elif op == 4:    # random byte run
            i = rng.randrange(len(data) + 1)
            data[i:i] = rng.randbytes(rng.randrange(1, 16))
        else:            # duplicate a window
            i = rng.randrange(len(data))
            k = min(len(data), i + rng.randrange(1, 32))
            data[i:i] = data[i:k]
    return bytes(data[:8192])


def fuzz(execs: int, seed: int = 7, log=print,
         base_seeds_only: bool = False) -> dict:
    from brpc_tpu.rpc import h2 as h2m
    from brpc_tpu.rpc import hpack as hpack_m

    tracker = CoverageTracker([h2m, hpack_m])
    rng = random.Random(seed)
    corpus = list(seeds(base_only=base_seeds_only))
    covered = 0
    # seed pass: baseline coverage
    for s in corpus:
        run_input(s)
    covered += tracker.take_new()
    t0 = time.monotonic()
    crashes = []
    i = -1  # execs=0: the loop never binds i; the result math still needs it
    for i in range(execs):
        data = mutate(rng, corpus)
        try:
            run_input(data)
        except Exception as e:  # a finding: the machine must never raise
            crashes.append((repr(e), data[:256].hex()))
            if len(crashes) >= 5:
                break
        new = tracker.take_new()
        if new:
            covered += new
            corpus.append(data)
        if (i + 1) % 50_000 == 0:
            r = (i + 1) / (time.monotonic() - t0)
            log(f"  {i + 1} execs, {covered} lines covered, "
                f"corpus {len(corpus)}, {r:.0f}/s")
    tracker.close()
    return {"execs": min(execs, i + 1 if execs else 0),
            "coverage_backend": tracker.BACKEND,
            "covered_lines": covered,
            "total_lines": tracker.total_lines,
            "corpus_size": len(corpus),
            "corpus": corpus,
            "crashes": crashes,
            "execs_per_s": round((i + 1) / max(time.monotonic() - t0, 1e-9))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--execs", type=int, default=200_000)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--corpus-out")
    ap.add_argument("--replay-native", type=int, metavar="PORT",
                    help="replay the final corpus as MSG_H2 bytes at a "
                         "live server port (cross-pollination into the "
                         "native parser)")
    args = ap.parse_args()
    r = fuzz(args.execs, args.seed)
    corpus = r.pop("corpus")
    print(r)
    if args.corpus_out:
        os.makedirs(args.corpus_out, exist_ok=True)
        for i, c in enumerate(corpus):
            with open(os.path.join(args.corpus_out, f"c{i:05d}.bin"),
                      "wb") as f:
                f.write(c)
    if args.replay_native:
        import socket
        ok = 0
        for c in corpus:
            try:
                s = socket.create_connection(("127.0.0.1",
                                              args.replay_native), timeout=5)
                s.sendall(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n" + c)
                s.close()
                ok += 1
            except OSError:
                pass
        print({"replayed": ok, "of": len(corpus)})
    if r["crashes"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
