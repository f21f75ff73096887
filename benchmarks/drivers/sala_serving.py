"""Driver of the described-architecture serving deployments (MiniCPM-SALA):
a ``HybridRunner`` behind ``register_serving`` / ``Serving.Generate`` on
one chip over a layered ``KVCacheStore``, document sessions over loopback
in the same process.

The weights are the BENCHMARK's (``reference_sala.make_params``, from the
seed, on the device) and are handed to the program.  Set-up prefills
every document once through the normal path (a ``Generate`` request:
chunked prefill, state carried on the device, a snapshot at the
document's last page boundary), so the window holds radix hits only.
After the window the program's state is freed, the plain reference makes
the weights again, runs each document once and then every finished
request's suffix teacher-forced on the served tokens.

``--control low_precision`` serves with everything the configuration
states in float32 and the program accumulates (every matmul's sum, the
residual stream, the lightning state) at bfloat16 values and the K/V
pages at an int8 cache's values: the precision below the stated one
throughout.  ``--control altered_token`` alters one served token a
request where the client receives it.  Both must read ``correct:
false``.
"""
from __future__ import annotations

import gc
import json
import sys
import threading
import time

import numpy as np

from benchmarks.harness import generators as gen
from benchmarks.harness import reference_sala as ref

CONTROLS = ("altered_token", "low_precision")
ROUNDS = 1024                 # turns per session planned ahead


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Collector:
    """Stream handler of one generation: tokens and their
    log-probabilities with the time each reached the client, and the
    terminal."""

    def __init__(self):
        self.tokens: list = []
        self.logprobs: list = []
        self.times: list = []
        self.terminal = None
        self.done = threading.Event()

    def on_received_messages(self, stream, messages):
        t = time.monotonic()
        for m in messages:
            d = json.loads(m)
            if "token" in d:
                self.tokens.append(int(d["token"]))
                self.logprobs.append(d.get("logprob"))
                self.times.append(t)
            if d.get("done"):
                self.terminal = d
                self.done.set()

    def on_idle_timeout(self, stream):
        pass

    def on_closed(self, stream):
        self.done.set()


def uniform_pairs(n: int, q: dict, o: dict, rng_fixed) -> list:
    """``n`` (question, output) length pairs, uniform over the closed
    ranges, from a generator the run's seed does not reach."""
    return list(zip(
        (int(v) for v in rng_fixed.integers(q["min"], q["max"] + 1, n)),
        (int(v) for v in rng_fixed.integers(o["min"], o["max"] + 1, n))))


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
        self._c_start = self._c_end = None

    # ---- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax
        import brpc_tpu as brpc
        from brpc_tpu.models.hybrid import HybridRunner, make_layered_store
        from brpc_tpu.models.runner import from_hf_config
        from brpc_tpu.serving import DecodeEngine, register_serving
        self.jax, self.brpc = jax, brpc
        c = self.cfg
        t0 = time.monotonic()
        self.params = ref.make_params(c, self.seed32, self.device)
        jax.block_until_ready(self.params)
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        log(f"  sala_serving: weights {nbytes / 1e9:.2f} GB made on the "
            f"device in {time.monotonic() - t0:.2f} s")
        tcfg = from_hf_config(
            dict(c, num_hidden_layers=c["published_num_hidden_layers"]),
            layers=(c["first_published_layer"], c["num_hidden_layers"]),
            sparse=c["assumed"]["sparse_config"]["value"],
            param_dtype=c["param_dtype"])
        self.store = make_layered_store(
            tcfg, cache_pages=c["cache_pages"], state_rows=c["state_rows"],
            device=self.device, name="bench_kv")
        self.runner = HybridRunner(
            self.params, tcfg, store=self.store,
            control="low" if self.control == "low_precision" else "",
            name="bench_sala")
        self.engine = DecodeEngine(
            runner=self.runner, num_slots=c["num_slots"], store=self.store,
            max_pages_per_slot=c["max_pages_per_slot"],
            prefill_buckets=tuple(c["prefill_buckets"]), name="bench_sala")
        self.server = brpc.Server()
        register_serving(self.server, engine=self.engine)
        self.server.start("127.0.0.1", 0)
        self.channel = brpc.Channel(
            f"127.0.0.1:{self.server.port}",
            timeout_ms=int(self.traffic.get("timeout_s", 300)) * 1000,
            max_retry=0)
        self._plan()
        self._warm()

    def _plan(self) -> None:
        """Documents and token ids from the seed; ONE fixed block of
        (question, output) lengths from the mix's own ``length_seed``,
        of which every round of turns takes one slice (a pair a
        session) in an order the seed decides."""
        t = self.traffic
        n_s, n = int(t["sessions"]), int(t["turns_block"])
        if n % n_s:
            raise ValueError("turns_block must be a multiple of sessions")
        self.block = uniform_pairs(
            n, t["question_tokens"], t["output_tokens"],
            np.random.default_rng(int(t["length_seed"])))
        rng = gen.rng_for(self.seed, 7)
        vocab = int(self.cfg["vocab_size"])
        self.documents = [
            rng.integers(1, vocab, int(t["document_tokens"])).tolist()
            for _ in range(int(t["documents"]))]
        self.session_rng = [gen.rng_for(self.seed, 8, s) for s in range(n_s)]
        self.rounds = []
        for r in range(ROUNDS):
            k = (r * n_s) % n
            who = rng.permutation(n_s)
            self.rounds.append([self.block[k + int(who[s])]
                                for s in range(n_s)])

    def _doc_of(self, session: int) -> int:
        return (session // int(self.traffic["sessions_per_document"])) \
            % len(self.documents)

    def _generate(self, doc: int, question: list, max_new: int,
                  session: int = -1) -> dict:
        brpc = self.brpc
        col = _Collector()
        cntl = brpc.Controller()
        brpc.stream_create(cntl, col)
        prompt = self.documents[doc] + question
        rec = {"session": session, "doc": doc, "question": question,
               "prompt_len": len(prompt), "asked": max_new, "ok": False,
               "kind": "generate", "bytes": 0}
        rec["t_issue"] = time.monotonic()
        try:
            with self.jax.profiler.TraceAnnotation("bench.generate_call"):
                resp = self.channel.call_sync(
                    "Serving", "Generate",
                    {"prompt": prompt, "max_new_tokens": int(max_new),
                     "speculative": False, "logprobs": True},
                    serializer="json", cntl=cntl)
            rec["prefix_hit"] = int(resp.get("prefix_hit", 0))
            with self.jax.profiler.TraceAnnotation("bench.await_tokens"):
                finished = col.done.wait(
                    float(self.traffic.get("timeout_s", 300)))
        except brpc.errors.RpcError as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            finished = False
        rec["t_done"] = time.monotonic()
        rec["tokens"] = list(col.tokens)
        rec["logprobs"] = list(col.logprobs)
        rec["times"] = list(col.times)
        term = col.terminal
        if finished and term is not None and "error" not in term \
                and len(col.tokens) == max_new \
                and all(x is not None for x in col.logprobs):
            rec["ok"] = True
        elif "error" not in rec:
            rec["error"] = (f"terminal {term}, {len(col.tokens)}/{max_new} "
                            f"tokens, finished={finished}")[:200]
        if self.control == "altered_token" and rec["tokens"]:
            # a token altered where the client receives it: the stream
            # says another token than the one the log-probability is of
            k = len(rec["tokens"]) // 2
            rec["tokens"][k] = (rec["tokens"][k] + 1) \
                % int(self.cfg["vocab_size"])
        return rec

    def _question(self, session: int, n: int) -> list:
        return self.session_rng[session].integers(
            1, int(self.cfg["vocab_size"]), n).tolist()

    def _warm(self) -> None:
        """Each document once through the normal path (this IS the long
        prefill: chunks of the largest bucket, a state snapshot at the
        document's end), then every session at once with a question of
        its own: the decode step and both prefill buckets compile
        here."""
        t0 = time.monotonic()
        rng = gen.rng_for(self.seed, 9)
        vocab = int(self.cfg["vocab_size"])
        q = self.traffic["question_tokens"]
        for d in range(len(self.documents)):
            r = self._generate(
                d, rng.integers(1, vocab, int(q["min"])).tolist(), 2)
            if not r["ok"]:
                raise RuntimeError(f"document prefill failed: {r['error']}")
            log(f"  sala_serving: document {d} "
                f"({len(self.documents[d])} tokens) prefilled and decoded "
                f"2 tokens {time.monotonic() - t0:.1f} s into the warm-up")
        recs = []
        n_s = int(self.traffic["sessions"])
        threads = [threading.Thread(target=lambda s=s: recs.append(
            self._generate(self._doc_of(s),
                           self._question(s, int(q["max"]) - s),
                           4 + 2 * s, session=s)))
            for s in range(n_s)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        bad = [r for r in recs if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up generation failed: {bad[0]['error']}")
        cold = [r for r in recs if r["prefix_hit"]
                < len(self.documents[0])]
        if cold:
            raise RuntimeError(
                f"{len(cold)} warm-up requests missed the document's "
                f"prefix (hit {cold[0]['prefix_hit']})")
        log(f"  sala_serving: warm-up {time.monotonic() - t0:.1f} s; cache "
            f"{self.store.stats().get('layers')}")

    # ---- the window -------------------------------------------------------

    def _one_turn(self, session: int, turn: int) -> dict:
        qlen, olen = self.rounds[turn % ROUNDS][session]
        return self._generate(self._doc_of(session),
                              self._question(session, qlen), olen,
                              session=session)

    def run(self, seconds: float, during=None):
        self._c_start = self.counters()
        loop = gen.ClosedLoop(int(self.traffic["sessions"]), self._one_turn)
        t0, t1 = loop.run(seconds, during, drain_s=120.0)
        self._c_end = self.counters()
        self.requests = loop.all_records()
        self._stuck = loop.stuck
        return t0, t1

    def counters(self) -> dict:
        from brpc_tpu.serving import engine as engine_mod
        e, s, r = self.engine, self.store, self.runner
        lay = s.layers
        return {"steps": e.steps.get_value(),
                "tokens": e.tokens_out.get_value(),
                "retired": e.retired.get_value(),
                "hit_tokens": s.hit_tokens.get_value(),
                "prompt_tokens": s.prompt_tokens.get_value(),
                "prefill_us_sum": float(
                    engine_mod.STAGE_PREFILL_REC.snapshot()[1]),
                "prefill_count": int(
                    engine_mod.STAGE_PREFILL_REC.snapshot()[0]),
                "state_snapshots": lay.snapshots.get_value(),
                "state_restores": lay.restores.get_value(),
                "state_restore_misses": lay.restore_misses.get_value(),
                "sparse_selected_blocks": r.sparse_selected.get_value(),
                "sparse_positions": r.sparse_positions.get_value(),
                "dense_positions": r.dense_positions.get_value(),
                "lightning_tokens": r.lightning_tokens.get_value(),
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
        self.runner.close()
        self.store.clear()
        self.store.close()
        self.server = self.engine = self.store = self.runner = None
        self.params = None
        gc.collect()

    def reference_document(self, params, tokens: list) -> dict:
        """The reference's context after one document: a block of
        positions at a time."""
        b = int(self.cfg["reference_block"])
        if len(tokens) % b:
            raise ValueError("document_tokens must be a multiple of the "
                             "reference's block")
        ctx = ref.new_context(self.cfg, len(tokens) + b)
        for at in range(0, len(tokens), b):
            _, ctx = ref.block_forward(params, self.cfg, ctx,
                                       tokens[at:at + b], at, b)
        return ctx

    def compare(self, params, ctx: dict, doc_len: int, r: dict) -> tuple:
        """One finished request against the reference, teacher-forced on
        the served tokens: (largest |served logprob - reference's|,
        largest gap of a served token below the reference's best, every
        token's |served logprob - reference's|)."""
        b = int(self.cfg["reference_block"])
        row = r["question"] + r["tokens"]
        n = len(row) - 1              # the last served token is no input
        toks = np.zeros((b,), np.int32)
        toks[:n] = row[:n]
        targets = np.zeros((b,), np.int32)
        targets[:n] = row[1:]
        (lp, gap), _ = ref.block_forward(params, self.cfg, ctx, toks,
                                         doc_len, n, targets=targets)
        lp, gap = np.asarray(lp), np.asarray(gap)
        first = len(r["question"]) - 1   # the position predicting token 0
        idx = first + np.arange(len(r["tokens"]))
        served = np.asarray(r["logprobs"], np.float64)
        err = np.abs(served - lp[idx])
        return float(err.max()), float(gap[idx].max()), err

    def check(self) -> list:
        done = [r for r in self.requests if r["ok"]]
        tol = self.cfg["assumed"]["tolerances"]
        t0 = time.monotonic()
        lp_err = gaps = 0.0
        compared = 0
        errs: list = []
        if done:
            params = ref.make_params(self.cfg, self.seed32, self.device)
            for d, doc in enumerate(self.documents):
                mine = [r for r in done if r["doc"] == d]
                if not mine:
                    continue
                ctx = self.reference_document(params, doc)
                log(f"  sala_serving: reference ran document {d} "
                    f"{time.monotonic() - t0:.1f} s into the check")
                for r in mine:
                    e, g, each = self.compare(params, ctx, len(doc), r)
                    lp_err, gaps = max(lp_err, e), max(gaps, g)
                    errs.append(each)
                    compared += 1
                del ctx
            del params
        errs = np.concatenate(errs) if errs else np.zeros((0,))
        lp_mean = float(errs.mean()) if len(errs) else 0.0
        log(f"  sala_serving: reference over {compared} requests, "
            f"{len(errs)} served tokens, in {time.monotonic() - t0:.1f} s: "
            f"|served logprob - reference| mean {lp_mean:.6g}, median "
            f"{float(np.median(errs)) if len(errs) else 0.0:.6g}, largest "
            f"{lp_err:.6g}; largest served gap {gaps:.6g}")
        short = sum(1 for r in self.requests
                    if not r["ok"] and "error" in r)
        dense = (self._c_end["dense_positions"]
                 - self._c_start["dense_positions"]) \
            if self._c_end and self._c_start else 0
        return [
            ("failed_requests", short + getattr(self, "_stuck", 0), 0),
            ("requests_not_compared",
             (len(done) - compared) + (0 if compared else 1), 0),
            ("served_logprob_abs_err_max", lp_err, tol["logprob_abs"]),
            ("served_logprob_abs_err_mean", lp_mean,
             tol["logprob_abs_mean"]),
            ("served_logit_gap_max", gaps, tol["served_gap"]),
            ("dense_positions_in_window", dense, 0),
        ]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.server is not None:
            try:
                self.release()
            except Exception as e:
                log(f"  sala_serving: close: {type(e).__name__}: {e}")
        from brpc_tpu.ici import rail
        rail.close_endpoints()
