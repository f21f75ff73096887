"""Driver of the served-model deployments: a ``TransformerRunner`` behind
``register_serving`` / ``Serving.Generate`` on one chip, chat sessions
over loopback in the same process.

The weights are the BENCHMARK's (``reference_llm.make_params``, one
jitted call on the device from the seed) and are handed to the program;
after the window the program's state is freed and the plain reference
makes them again from the seed and runs once over a sample of the
finished requests.
"""
from __future__ import annotations

import gc
import json
import sys
import threading
import time

import numpy as np

from benchmarks.harness import generators as gen
from benchmarks.harness import reference_llm as ref

CONTROLS = ("altered_token", "low_precision")
ROUNDS = 1024                 # turns per session planned ahead
# the widest gap by which a served token's logit may lie below the
# reference's best (PERF.md, "How correct is decided", gives the readings)
SERVED_GAP_LIMIT = 1e-4


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Collector:
    """Stream handler of one generation: tokens with the time each one
    reached the client, and the terminal."""

    def __init__(self):
        self.tokens: list = []
        self.times: list = []
        self.terminal = None
        self.done = threading.Event()

    def on_received_messages(self, stream, messages):
        t = time.monotonic()
        for m in messages:
            d = json.loads(m)
            if "token" in d:
                self.tokens.append(int(d["token"]))
                self.times.append(t)
            if d.get("done"):
                self.terminal = d
                self.done.set()

    def on_idle_timeout(self, stream):
        pass

    def on_closed(self, stream):
        self.done.set()


class Driver:
    def __init__(self, cell, *, seed: int, devices: list, control=None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}; {CONTROLS}")
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seed32 = gen.fold_seed(seed)
        self.device = devices[0]
        self.control = control
        self.requests: list = []
        self.server = self.engine = self.store = self.runner = None
        self.params = None
        self._closed = False

    # ---- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax
        import brpc_tpu as brpc
        from brpc_tpu.models.runner import (TransformerConfig,
                                            TransformerRunner, make_store_for)
        from brpc_tpu.serving import DecodeEngine, register_serving
        self.jax, self.brpc = jax, brpc
        c = self.cfg
        t0 = time.monotonic()
        self.params = ref.make_params(c, self.seed32, self.device)
        jax.block_until_ready(self.params)
        log(f"  llm_serving: weights "
            f"{sum(v.nbytes for v in self.params.values()) / 2**30:.2f} GiB "
            f"made on the device in {time.monotonic() - t0:.2f} s")
        tcfg = TransformerConfig(
            vocab=c["vocab"], d_model=c["d_model"], n_layers=c["n_layers"],
            n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"],
            head_dim=c["head_dim"], d_ff=c["d_ff"])
        self.store = make_store_for(tcfg, page_tokens=c["page_tokens"],
                                    max_blocks=c["cache_pages"],
                                    device=self.device, name="bench_kv")
        self.runner = TransformerRunner(self.params, tcfg, store=self.store,
                                        attn_backend=c.get("attn_backend"),
                                        name="bench_llm")
        self.engine = DecodeEngine(
            runner=self.runner, num_slots=c["num_slots"], store=self.store,
            max_pages_per_slot=c["max_pages_per_slot"],
            prefill_buckets=tuple(c["prefill_buckets"]), name="bench_llm")
        self.server = brpc.Server()
        register_serving(self.server, engine=self.engine)
        self.server.start("127.0.0.1", 0)
        self.channel = brpc.Channel(
            f"127.0.0.1:{self.server.port}",
            timeout_ms=int(self.traffic.get("timeout_s", 120)) * 1000,
            max_retry=0)
        self._plan()
        self._warm()

    def _plan(self) -> None:
        """One fixed block of (prompt, output) lengths per mix, drawn from
        the mix's own ``length_seed``.  Turn ``t`` of the sessions is a
        ROUND: the sessions take, between them, one slice of the block
        (as many pairs as there are sessions), in an order the run's
        seed decides.  So every round of every seed holds the same
        requests, and the seed only says which session sends which and
        draws the token ids."""
        t = self.traffic
        fixed = np.random.default_rng(int(t["length_seed"]))
        n_s = int(t["sessions"])
        n = int(t["turns_block"])
        if n % n_s:
            raise ValueError("turns_block must be a multiple of sessions")
        p, o = t["prompt_tokens"], t["output_tokens"]
        q = int(p.get("round_to", 1))
        self.block = list(zip(
            [max(p["min"], min(p["max"], q * round(v / q)))
             for v in gen.lognormal_lengths(n, p["median"], p["sigma"],
                                            p["min"], p["max"], fixed)],
            gen.lognormal_lengths(n, o["median"], o["sigma"], o["min"],
                                  o["max"], fixed)))
        rng = gen.rng_for(self.seed, 7)
        vocab = self.cfg["vocab"]
        self.prefixes = [rng.integers(1, vocab, int(
            t["shared_prefix_tokens"])).tolist()
            for _ in range(int(t["shared_prefixes"]))]
        self.session_rng = [gen.rng_for(self.seed, 8, s) for s in range(n_s)]
        self.rounds = []
        for r in range(ROUNDS):
            k = (r * n_s) % n
            who = rng.permutation(n_s)
            self.rounds.append([self.block[k + int(who[s])]
                                for s in range(n_s)])

    def _prompt(self, session: int, turn: int) -> tuple:
        plen, olen = self.rounds[turn % ROUNDS][session]
        rng = self.session_rng[session]
        vocab = self.cfg["vocab"]
        if session < int(self.traffic["sessions_with_prefix"]):
            pre = self.prefixes[session % len(self.prefixes)]
            body = rng.integers(1, vocab, max(1, plen - len(pre))).tolist()
            return (pre + body)[:max(plen, len(pre) + 1)], olen
        return rng.integers(1, vocab, plen).tolist(), olen

    def _generate(self, prompt, max_new: int, session: int = -1) -> dict:
        brpc = self.brpc
        col = _Collector()
        cntl = brpc.Controller()
        brpc.stream_create(cntl, col)
        rec = {"session": session, "prompt": prompt, "asked": max_new,
               "ok": False, "kind": "generate"}
        rec["t_issue"] = time.monotonic()
        try:
            with self.jax.profiler.TraceAnnotation("bench.generate_call"):
                resp = self.channel.call_sync(
                    "Serving", "Generate",
                    {"prompt": prompt, "max_new_tokens": int(max_new),
                     "speculative": False},
                    serializer="json", cntl=cntl)
            rec["prefix_hit"] = int(resp.get("prefix_hit", 0))
            with self.jax.profiler.TraceAnnotation("bench.await_tokens"):
                finished = col.done.wait(
                    float(self.traffic.get("timeout_s", 120)))
        except brpc.errors.RpcError as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            finished = False
        rec["t_done"] = time.monotonic()
        rec["tokens"] = list(col.tokens)
        rec["times"] = list(col.times)
        term = col.terminal
        if finished and term is not None and "error" not in term \
                and len(col.tokens) == max_new:
            rec["ok"] = True
        elif "error" not in rec:
            rec["error"] = (f"terminal {term}, {len(col.tokens)}/{max_new} "
                            f"tokens, finished={finished}")[:200]
        if self.control == "altered_token" and rec["tokens"]:
            # a token altered where it is produced (the fault of the
            # contract's list): the served stream says another token
            k = len(rec["tokens"]) // 2
            rec["tokens"][k] = (rec["tokens"][k] + 1) % self.cfg["vocab"]
        return rec

    def _warm(self) -> None:
        """One generation per prefill bucket (cold, so the whole prompt
        is prefilled in that bucket), then all slots at once: the
        decode step's one shape and every prefill shape compile here."""
        t0 = time.monotonic()
        rng = gen.rng_for(self.seed, 9)
        vocab = self.cfg["vocab"]
        p = self.traffic["prompt_tokens"]
        # the program compiles a page-write program per suffix length:
        # one cold prefill of every length the mix can send
        for n in range(int(p["min"]), int(p["max"]) + 1,
                       int(p.get("round_to", 1))):
            r = self._generate(rng.integers(1, vocab, n).tolist(), 2)
            if not r["ok"]:
                raise RuntimeError(f"warm-up generation failed: {r['error']}")
        log(f"  llm_serving: one prefill of each length: "
            f"{time.monotonic() - t0:.1f} s")
        # ... and a batched write per number of live slots: all sessions
        # at once, with outputs that end one after the other
        recs = []
        n_s = int(self.traffic["sessions"])
        threads = [threading.Thread(target=lambda s=s: recs.append(
            self._generate(self._prompt(s, ROUNDS - 1)[0],
                           int(self.traffic.get("warm_tokens", 24)) + 3 * s,
                           session=s)))
            for s in range(n_s)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        bad = [r for r in recs if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up generation failed: {bad[0]['error']}")
        # a serving cache in steady state is full: fill it, so that the
        # window measures eviction and reuse and not the first fill
        pages = self.store.pagepool.pages_in_use
        full = int(self.cfg["cache_pages"]) - int(p["max"]) \
            // int(self.cfg["page_tokens"])
        for _ in range(2 * int(self.cfg["cache_pages"])):
            if pages() >= full:
                break
            r = self._generate(rng.integers(1, vocab, int(p["max"])).tolist(),
                               2)
            if not r["ok"]:
                raise RuntimeError(f"warm-up generation failed: {r['error']}")
        log(f"  llm_serving: cache filled to {pages()} of "
            f"{self.cfg['cache_pages']} pages")
        log(f"  llm_serving: warm-up {time.monotonic() - t0:.1f} s")

    # ---- the window -------------------------------------------------------

    def _one_turn(self, session: int, turn: int) -> dict:
        prompt, olen = self._prompt(session, turn)
        return self._generate(prompt, olen, session=session)

    def run(self, seconds: float, during=None):
        self._c_start = self.counters()
        loop = gen.ClosedLoop(int(self.traffic["sessions"]), self._one_turn)
        t0, t1 = loop.run(seconds, during, drain_s=90.0)
        self.requests = loop.all_records()
        self._stuck = loop.stuck
        return t0, t1

    def counters(self) -> dict:
        from brpc_tpu.serving import engine as engine_mod
        e, s = self.engine, self.store
        return {"steps": e.steps.get_value(),
                "tokens": e.tokens_out.get_value(),
                "retired": e.retired.get_value(),
                "hit_tokens": s.hit_tokens.get_value(),
                "prompt_tokens": s.prompt_tokens.get_value(),
                "prefill_us_sum": _lat_sum(engine_mod.STAGE_PREFILL_REC),
                "prefill_count": _lat_count(engine_mod.STAGE_PREFILL_REC),
                "t": time.monotonic()}

    def records(self) -> dict:
        return {"calls": self.requests,
                "streams": [r["times"] for r in self.requests]}

    def attempted_failed(self) -> tuple:
        return (len(self.requests),
                sum(1 for r in self.requests if not r["ok"]))

    # ---- after the window -------------------------------------------------

    def release(self) -> None:
        """Stop serving and free the weights, the cache and the engine:
        the reference runs on an empty chip."""
        self.server.stop()
        self.server.join()
        self.engine.close()
        self.store.clear()
        self.store.close()
        self.server = self.engine = self.store = self.runner = None
        self.params = None
        gc.collect()

    def sample(self) -> list:
        """The finished requests the reference runs over: the longest,
        the one that took most of its prompt from the prefix cache, and
        others drawn from the seed."""
        done = [r for r in self.requests if r["ok"]]
        if not done:
            return []
        k = int(self.traffic.get("check_requests", 8))
        longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        cached = max(done, key=lambda r: r.get("prefix_hit", 0))
        must = [longest] + ([cached] if cached is not longest else [])
        rest = [r for r in done if not any(r is m for m in must)]
        rng = gen.rng_for(self.seed, 10)
        pick = rng.permutation(len(rest))[:max(0, k - len(must))]
        return must + [rest[i] for i in pick]

    def rows_of(self, reqs: list) -> np.ndarray:
        p, o = self.traffic["prompt_tokens"], self.traffic["output_tokens"]
        width = int(p["max"]) + int(o["max"])
        rows = np.zeros((len(reqs), width), np.int32)
        for i, r in enumerate(reqs):
            seq = (r["prompt"] + r["tokens"])[:width]
            rows[i, :len(seq)] = seq
        return rows

    def served_gaps(self, reqs: list, params) -> list:
        """Per served token of ``reqs``: how far its logit lies below the
        reference's best at its position (0 where it IS the best).  Under
        the control ``low_precision`` the reference at the precision
        below the stated one stands in the program's place: at the SAME
        positions of the same rows, the gap of the token it puts first."""
        rows = self.rows_of(reqs)
        if self.control == "low_precision":
            gap = np.asarray(ref.control_gaps(params, self.cfg, rows, "high"))
        else:
            best, served = ref.position_gaps(params, self.cfg, rows)
            gap = np.asarray(best) - np.asarray(served)
        gaps = []
        for i, r in enumerate(reqs):
            n = len(r["prompt"])
            for j in range(len(r["tokens"])):
                gaps.append(float(gap[i, n + j - 1]))  # the one predicting it
        return gaps

    def check(self) -> list:
        reqs = self.sample()
        t0 = time.monotonic()
        gap_max, n_tokens = 0.0, 0
        if reqs:
            params = ref.make_params(self.cfg, self.seed32, self.device)
            gaps = self.served_gaps(reqs, params)
            gap_max, n_tokens = max(gaps), len(gaps)
            del params
        log(f"  llm_serving: reference over {len(reqs)} requests, "
            f"{n_tokens} served tokens, in {time.monotonic() - t0:.1f} s")
        short = sum(1 for r in self.requests
                    if not r["ok"] and "error" in r)
        return [
            ("failed_requests", short + getattr(self, "_stuck", 0), 0),
            ("requests_not_compared", 0 if n_tokens >= 16 else 1, 0),
            ("served_logit_gap_max", gap_max, SERVED_GAP_LIMIT),
        ]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.server is not None:
            try:
                self.release()
            except Exception as e:
                log(f"  llm_serving: close: {type(e).__name__}: {e}")
        from brpc_tpu.ici import rail
        rail.close_endpoints()


def _lat_count(rec) -> int:
    return int(rec.snapshot()[0])


def _lat_sum(rec) -> float:
    return float(rec.snapshot()[1])
