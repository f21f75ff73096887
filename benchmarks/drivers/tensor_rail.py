"""Driver of the tensor-rail deployments: ``brpc.Server(ici_device=)``
with a tensor echo method and an accept-stream method, ``brpc.Channel``
clients in the same process, payloads resident on the caller's chip.

Traffic generators (``traffic["generator"]``):

``closed_loop_unary``  N callers, each a ``call_sync(serializer="tensor")``
                       echo of a payload drawn from a seeded resident
                       pool; optional ``targets`` (server chips, taken in
                       turn) and ``fanout_every`` (every k-th call a
                       ``ParallelChannel`` fan-out lowered to a collective)
``stream_echo``        N streams, each writing fixed-size device chunks
                       back to back under a credit window, the server
                       echoing each chunk on the same stream

From the program it takes only the public entry points and counters.
"""
from __future__ import annotations

import collections
import sys
import threading
import time

import numpy as np

from benchmarks.harness import generators as gen
from benchmarks.harness import reference_tensor as ref

SERVICE = "BenchTensor"
HORIZON = 1 << 16              # calls per caller drawn ahead of the window
KEEP_FIRST, KEEP_LAST = 2, 2   # sampled replies kept per caller, each end
CONTROLS = ("identity", "altered_reply", "no_exchange", "misplaced_server")


def device_payload(key, n_words: int):
    """The payload of ``reference_tensor.payload_numpy``, written for
    the device (traced under jit; ``key`` is a uint32 scalar)."""
    import jax.numpy as jnp
    x = jnp.arange(n_words, dtype=jnp.uint32) * jnp.uint32(2654435761) + key
    x = x ^ (x >> 15)
    x = x * jnp.uint32(2246822519)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(3266489917)
    return x ^ (x >> 16)


def pool_layout(pool_bytes: int, rungs: list) -> dict:
    """How many resident tensors each rung gets: the largest rung half
    of the pool, the next a quarter, ..., the last two the same."""
    rungs = sorted(rungs, reverse=True)
    counts = {}
    for i, r in enumerate(rungs):
        share = pool_bytes >> min(i + 1, len(rungs) - 1)
        counts[r] = max(4, share // r)
    return counts


class Driver:
    def __init__(self, cell, *, seed: int, devices: list, control=None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}; {CONTROLS}")
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seed32 = gen.fold_seed(seed)
        self.devices = devices
        self.control = control
        self.client_device = devices[self.cfg.get("client_chip", 0)]
        self.servers: list = []
        self.channels: dict = {}
        self.pool: dict = {}
        self.samples: list = []      # (record, reply array) to verify
        self._calls: list = []
        self._fanout = None
        self._closed = False

    # ---- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax
        import brpc_tpu as brpc
        self.jax = jax
        self.brpc = brpc
        control = self.control
        window = int(self.traffic.get("window_bytes", 16 << 20))

        class BenchTensor(brpc.Service):
            NAME = SERVICE

            @brpc.method(request="tensor", response="tensor")
            def Echo(self, cntl, req):
                if control == "altered_reply":
                    return req.at[req.shape[0] // 2].add(1)
                return req

            @brpc.method(request="json", response="json")
            def OpenTensor(svc, cntl, req):
                dev = self.devices[int(req["chip"])]
                if control == "altered_reply":
                    def echo(stream, payload):
                        stream.write(payload.at[payload.shape[0] // 2]
                                     .add(1))
                else:
                    def echo(stream, payload):
                        stream.write(payload)
                cntl.accept_stream(echo, max_buf_size=window, device=dev)
                return {"accepted": True}

        server_chips = self.cfg["server_chips"]
        for chip in server_chips:
            # the fault "the exchange between chips left out": every
            # server sits on the callers' own chip ("no_exchange"), or
            # every server but the first does ("misplaced_server")
            here = control == "no_exchange" or (
                control == "misplaced_server" and chip != server_chips[0])
            on = self.cfg.get("client_chip", 0) if here else chip
            s = brpc.Server(ici_device=self.devices[on])
            s.add_service(BenchTensor())
            s.start("127.0.0.1", 0)
            self.servers.append(s)
            self.channels[chip] = brpc.Channel(
                f"127.0.0.1:{s.port}",
                timeout_ms=int(self.traffic.get("timeout_ms", 120_000)),
                connection_type=self.traffic.get("connection_type",
                                                 "pooled"),
                max_retry=0)
        t_pool = time.monotonic()
        self._make_pool()
        print(f"  tensor_rail: servers up, {self.pool_bytes / 2**30:.2f} GiB "
              f"pool of {sum(self.counts.values())} tensors made in "
              f"{time.monotonic() - t_pool:.2f} s", file=sys.stderr,
              flush=True)
        kind = self.traffic["generator"]
        if kind == "closed_loop_unary":
            self._plan_unary()
            if self.traffic.get("fanout_every"):
                self._setup_fanout()
            self._warm_unary()
        elif kind == "stream_echo":
            self._warm_streams()
        else:
            raise ValueError(f"tensor_rail has no generator {kind!r}")

    def _make_pool(self) -> None:
        """The resident set: ``pool_bytes`` of seeded uint32 tensors on
        the caller's chip, made there (one small jitted program per
        rung, one launch per tensor; nothing comes from the host)."""
        jax = self.jax
        import jax.numpy as jnp
        rungs = self.traffic.get("rungs_bytes") \
            or [self.traffic["chunk_bytes"]]
        self.rungs = sorted(rungs)
        self.counts = pool_layout(int(self.cfg["pool_bytes"]), rungs)

        maker = jax.jit(device_payload, static_argnums=(1,))
        self.tid_base = {}
        tid = 0
        with jax.default_device(self.client_device):
            for r in self.rungs:
                self.tid_base[r] = tid
                self.pool[r] = [
                    maker(jnp.uint32(ref.payload_key(self.seed32, tid + i)),
                          r // 4) for i in range(self.counts[r])]
                tid += self.counts[r]
        jax.block_until_ready(self.pool)
        self.pool_bytes = sum(r * self.counts[r] for r in self.rungs)

    def _plan_unary(self) -> None:
        """Per caller: rung order (seeded permutations of the ladder laid
        end to end), which resident tensor, and which calls are kept
        for the byte comparison."""
        n = int(self.traffic["callers"])
        k = len(self.rungs)
        self.plan = []
        for c in range(n):
            rng = gen.rng_for(self.seed, 1, c)
            order = gen.block_permutations(range(k), HORIZON, rng)
            pick = rng.integers(0, 1 << 30, HORIZON)
            flag = rng.random(HORIZON) < float(
                self.traffic.get("sample_share", 0.125))
            self.plan.append((order, pick, flag))
        self.targets = self.traffic.get("targets") \
            or [self.cfg["server_chips"][0]]
        self.fanout_every = int(self.traffic.get("fanout_every", 0))

    def _setup_fanout(self) -> None:
        """A ``ParallelChannel`` over one ``IciChannel`` per chip to a
        registered device service, lowered to one compiled program."""
        import jax.numpy as jnp
        brpc = self.brpc
        from brpc_tpu.ici import IciChannel, register_device_service

        def fn(x):
            return (x ^ (x >> 3)) + jnp.uint32(1)

        register_device_service(SERVICE, "Apply", fn)

        self.fan_fn = fn
        lowered = brpc.ParallelChannel()
        for i in range(len(self.devices)):
            lowered.add_channel(IciChannel(f"ici://slice0/{i}"))
        self._fanout = lowered
        self.fanout_bytes = int(self.traffic["fanout_bytes"])
        self.fan_x = self.pool[self.fanout_bytes]

    def _echo(self, chip: int, x):
        if self.control == "identity":
            return x                  # the reference in the program's place
        return self.channels[chip].call_sync(SERVICE, "Echo", x,
                                             serializer="tensor")

    def _one_call(self, caller: int, i: int) -> dict:
        jax = self.jax
        order, pick, flag = self.plan[caller]
        j = i % HORIZON
        seq = caller + i * len(self.plan)      # global turn, for targets
        if self.fanout_every and i % self.fanout_every \
                == self.fanout_every - 1:
            return self._fanout_call(caller, i)
        r = self.rungs[order[j]]
        idx = int(pick[j]) % self.counts[r]
        x = self.pool[r][idx]
        chip = self.targets[seq % len(self.targets)]
        rec = {"caller": caller, "i": i, "kind": "echo", "bytes": r,
               "tid": self.tid_base[r] + idx, "chip": chip, "ok": False}
        rec["t_issue"] = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation("bench.echo_call"):
                out = self._echo(chip, x)
            with jax.profiler.TraceAnnotation("bench.wait_reply"):
                jax.block_until_ready(out)
            rec["t_done"] = time.monotonic()
            rec["ok"] = True
        except self.brpc.errors.RpcError as e:
            rec["t_done"] = time.monotonic()
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            return rec
        self._inspect(rec, x, out)
        first_big = r == self.rungs[-1] and not self._kept_big[caller]
        if flag[j] or first_big:
            if first_big:
                self._kept_big[caller] = True
            self._keep(caller, rec, out)
        return rec

    def _inspect(self, rec: dict, x, out) -> None:
        """What every reply is held to, without reading its bytes."""
        jax = self.jax
        rec["is_array"] = isinstance(out, jax.Array)
        if not rec["is_array"]:
            return
        rec["on_device"] = out.devices() == {self.client_device}
        rec["shape_ok"] = out.shape == x.shape and out.dtype == x.dtype
        rec["own_buffer"] = (out.unsafe_buffer_pointer()
                             != x.unsafe_buffer_pointer())

    def _keep(self, caller: int, rec: dict, out) -> None:
        first, last = self._kept[caller]
        (first if len(first) < KEEP_FIRST else last).append((rec, out))

    def _fanout_call(self, caller: int, i: int) -> dict:
        jax = self.jax
        idx = (caller + i) % len(self.fan_x)
        x = self.fan_x[idx]
        rec = {"caller": caller, "i": i, "kind": "fanout", "ok": False,
               "tid": self.tid_base[self.fanout_bytes] + idx,
               "bytes": self.fanout_bytes * len(self.devices)}
        rec["t_issue"] = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation("bench.fanout_call"):
                if self.control == "no_exchange":
                    out = [self.fan_fn(x) for _ in self.devices]
                else:
                    out = self._fanout.call_sync(SERVICE, "Apply", x)
                jax.block_until_ready(out)
            rec["t_done"] = time.monotonic()
            rec["ok"] = len(out) == len(self.devices)
        except self.brpc.errors.RpcError as e:
            rec["t_done"] = time.monotonic()
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            return rec
        if not self._kept_fan[caller]:
            self._kept_fan[caller].append((rec, out))
        return rec

    def _reset_kept(self) -> None:
        n = int(self.traffic.get("callers", self.traffic.get("streams", 1)))
        self._kept = [([], collections.deque(maxlen=KEEP_LAST))
                      for _ in range(n)]
        self._kept_big = [False] * n
        self._kept_fan = [[] for _ in range(n)]

    def _warm_unary(self) -> None:
        """Every caller echoes every rung once to every target (and makes
        one fan-out): each copy program compiles, each pooled connection
        opens."""
        n = int(self.traffic["callers"])
        errs = []

        def warm(c):
            try:
                for r in self.rungs:
                    for chip in self.targets:
                        out = self._echo(chip, self.pool[r][c % self.counts[r]])
                        self.jax.block_until_ready(out)
                if self._fanout is not None:
                    out = self._fanout.call_sync(SERVICE, "Apply",
                                                 self.fan_x[0])
                    self.jax.block_until_ready(out)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=warm, args=(c,)) for c in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        self._reset_kept()

    # ---- streams ----------------------------------------------------------

    def _open_stream(self, s: int):
        brpc = self.brpc
        state = {"sent": [], "back": [], "cv": threading.Condition(),
                 "first": [], "last": collections.deque(maxlen=KEEP_LAST)}
        window = int(self.traffic["window_bytes"])
        jax = self.jax

        def on_chunk(_stream, payload):
            # a chunk has arrived when its bytes have: the consumer
            # waits for them, as one that reads the data would
            if isinstance(payload, jax.Array):
                payload.block_until_ready()
            t = time.monotonic()
            with state["cv"]:
                k = len(state["back"])
                state["back"].append(t)
                if k < KEEP_FIRST:
                    state["first"].append((k, payload))
                else:
                    state["last"].append((k, payload))
                state["cv"].notify_all()

        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, on_chunk, max_buf_size=window,
                                    device=self.client_device)
        chip = self.cfg["server_chips"][s % len(self.cfg["server_chips"])]
        self.channels[chip].call_sync(SERVICE, "OpenTensor", {"chip": chip},
                                      serializer="json", cntl=cntl)
        state["stream"] = stream
        state["on_chunk"] = on_chunk
        return state

    def _warm_streams(self) -> None:
        n = int(self.traffic["streams"])
        self._reset_kept()
        self.chunk = int(self.traffic["chunk_bytes"])
        self.stream_states = [self._open_stream(s) for s in range(n)]
        for st in self.stream_states:
            for k in range(4):
                st["stream"].write(self.pool[self.chunk][k], timeout_s=120.0)
            with st["cv"]:
                ok = st["cv"].wait_for(lambda: len(st["back"]) >= 4, 120.0)
            if not ok:
                raise RuntimeError("stream warm-up: echoes did not return")
            st["back"].clear()
            st["first"].clear()
            st["last"].clear()

    def _stream_loop(self, s: int, stop: threading.Event) -> None:
        """Write chunks back to back; the credit window is the only
        brake.  Which resident chunk goes out is the seed's choice."""
        jax = self.jax
        st = self.stream_states[s]
        rng = gen.rng_for(self.seed, 2, s)
        pick = rng.integers(0, self.counts[self.chunk], HORIZON)
        st["tids"] = []
        i = 0
        while not stop.is_set():
            idx = int(pick[i % HORIZON])
            t = time.monotonic()
            try:
                with jax.profiler.TraceAnnotation("bench.stream_write"):
                    if self.control == "identity":
                        # the reference in the program's place: the
                        # chunk handed straight back
                        st["on_chunk"](None, self.pool[self.chunk][idx])
                    else:
                        st["stream"].write(self.pool[self.chunk][idx],
                                           timeout_s=60.0)
            except self.brpc.errors.RpcError as e:
                st["error"] = f"{type(e).__name__}: {e}"[:200]
                break
            st["sent"].append(t)
            st["tids"].append(self.tid_base[self.chunk] + idx)
            i += 1

    def _run_streams(self, seconds: float, during):
        stop = threading.Event()
        threads = [threading.Thread(target=self._stream_loop, args=(s, stop),
                                    daemon=True, name=f"bench-stream-{s}")
                   for s in range(len(self.stream_states))]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        if during is not None:
            during(t0)
        left = t0 + seconds - time.monotonic()
        if left > 0:
            time.sleep(left)
        t1 = time.monotonic()
        stop.set()
        for t in threads:
            t.join(90.0)
        # every chunk written is due: wait for its echo, a minute at most
        for st in self.stream_states:
            with st["cv"]:
                st["cv"].wait_for(
                    lambda: len(st["back"]) >= len(st["sent"]), 60.0)
        for s, st in enumerate(self.stream_states):
            back = st["back"]
            base = len(self._calls)
            for k, t_sent in enumerate(st["sent"]):
                ok = k < len(back)
                self._calls.append({
                    "caller": s, "i": k, "kind": "chunk", "bytes": self.chunk,
                    "tid": st["tids"][k], "t_issue": t_sent,
                    "t_done": back[k] if ok else t1 + 3600.0, "ok": ok})
            if "error" in st:
                self._calls.append({
                    "caller": s, "i": len(st["sent"]), "kind": "chunk",
                    "bytes": self.chunk, "tid": -1, "t_issue": t1,
                    "t_done": t1, "ok": False, "error": st["error"]})
            # the first and the last echoes of each stream are compared
            # byte for byte; the others were let go as they came
            for k, out in list(st["first"]) + list(st["last"]):
                if k >= len(st["sent"]):
                    continue
                rec = self._calls[base + k]
                x = self.pool[self.chunk][rec["tid"]
                                          - self.tid_base[self.chunk]]
                self._inspect(rec, x, out)
                self.samples.append((rec, out))
        return t0, t1

    # ---- the window -------------------------------------------------------

    def run(self, seconds: float, during=None):
        self._c_start = self.counters()
        if self.traffic["generator"] == "stream_echo":
            return self._run_streams(seconds, during)
        loop = gen.ClosedLoop(int(self.traffic["callers"]), self._one_call)
        t0, t1 = loop.run(seconds, during)
        self._calls = loop.all_records()
        self._stuck = loop.stuck
        for first, last in self._kept:
            self.samples.extend(first + list(last))
        return t0, t1

    def counters(self) -> dict:
        from brpc_tpu.ici import endpoint, rail
        links = endpoint.link_stats()
        from brpc_tpu.bvar import find_exposed
        lowered = find_exposed("ici_collective_calls")
        return {"lowered_calls":
                lowered.get_value() if lowered is not None else 0,"host_copies": rail.host_copy_count(),
                "rail_fallbacks": rail.rail_fallbacks.get_value(),
                "rail_bytes": rail.rail_bytes.get_value(),
                "rail_payloads": rail.rail_payloads.get_value(),
                "same_device_copies": links["same_device_copies"],
                "cross_device_moves": links["cross_device_moves"],
                "t": time.monotonic()}

    def records(self) -> dict:
        return {"calls": self._calls}

    def attempted_failed(self) -> tuple:
        return len(self._calls), sum(1 for c in self._calls if not c["ok"])

    # ---- after the window -------------------------------------------------

    def release(self) -> None:
        """Stop the servers and drop the resident pool: the reference
        runs with the program's state gone."""
        self._c_end = self.counters()
        for st in getattr(self, "stream_states", []):
            try:
                st["stream"].close()
            except Exception:
                pass
        for s in self.servers:
            s.stop()
            s.join()
        self.servers = []
        self.fan_samples = [k for ks in getattr(self, "_kept_fan", [])
                            for k in ks]
        self.pool = {}
        self._kept = []

    def check(self) -> list:
        """The configuration's guarantees, on what the window returned.
        Every comparison is exact, so every limit is 0."""
        calls = [c for c in self._calls if c["kind"] != "fanout"]
        done = [c for c in calls if c["ok"]]
        bad_words = 0
        for rec, out in self.samples:
            bad_words += ref.mismatched_words(out, self.seed32, rec["tid"],
                                              rec["bytes"] // 4)
        n_samples = len(self.samples)
        self.samples = []
        c0, c1 = self._c_start, self._c_end
        out = [
            ("failed_calls", sum(1 for c in self._calls if not c["ok"])
             + getattr(self, "_stuck", 0), 0),
            ("mismatched_words", bad_words, 0),
            ("replies_not_compared", 0 if n_samples else 1, 0),
            ("not_device_arrays",
             sum(1 for c in done if not c.get("is_array", True)), 0),
            ("off_caller_device",
             sum(1 for c in done if not c.get("on_device", True)), 0),
            ("wrong_shape", sum(1 for c in done
                                if not c.get("shape_ok", True)), 0),
            ("aliased_replies",
             sum(1 for c in done if not c.get("own_buffer", True)), 0),
            ("host_copies", c1["host_copies"] - c0["host_copies"], 0),
            ("rail_fallbacks", c1["rail_fallbacks"] - c0["rail_fallbacks"],
             0),
        ]
        per_echo = self.cfg.get("guarantees", {}).get(
            "cross_device_moves_per_remote_echo")
        if per_echo:
            # every echo to a server on another chip moves its request
            # there and its reply back: that many moves at the least, and
            # no copy on the callers' chip but those of the local echoes
            home = self.cfg.get("client_chip", 0)
            remote = sum(1 for c in done if c.get("chip", home) != home)
            moved = c1["cross_device_moves"] - c0["cross_device_moves"]
            copied = c1["same_device_copies"] - c0["same_device_copies"]
            out.append(("echo_moves_missing",
                        max(0, per_echo * remote - moved), 0))
            out.append(("unexpected_same_chip_copies",
                        max(0, copied - per_echo * (len(done) - remote)), 0))
        if self.fan_samples:
            out.append(("fanout_mismatched_words", self._check_fanout(), 0))
            fans = sum(1 for c in self._calls
                       if c["kind"] == "fanout" and c["ok"])
            lowered = c1["lowered_calls"] - c0["lowered_calls"]
            out.append(("fanouts_not_lowered", max(0, fans - lowered), 0))
        return out

    def _check_fanout(self) -> int:
        """The lowered fan-out against the plain function of the request
        as the reference makes it from the seed (integers: exact)."""
        bad = 0
        for rec, outs in self.fan_samples:
            xs = ref.payload_numpy(self.seed32, rec["tid"],
                                   self.fanout_bytes // 4)
            want = (xs ^ (xs >> np.uint32(3))) + np.uint32(1)
            for o in outs:
                got = np.asarray(o)
                bad += int(np.count_nonzero(got != want)) \
                    if got.shape == want.shape else want.size
        return bad

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for s in self.servers:
            try:
                s.stop()
                s.join()
            except Exception:
                pass
        from brpc_tpu.ici import rail
        rail.close_endpoints()
