"""Driver of one chip's share of NVIDIA-Nemotron-3-Super-120B-A12B's serving
deployment (``nemotron3_super_l11_ep4_1chip``): a ``HybridRunner`` (5
Mamba-2 blocks, 5 latent-expert blocks holding experts 0-127 of 512, one
attention block; embedding and head over vocabulary rows 0-32,767)
behind ``register_serving`` / ``Serving.Generate`` on one chip over a
layered ``KVCacheStore``, single-turn sessions over loopback in the same
process.

The weights are the BENCHMARK's (``reference_nemotron.make_params``,
from the seed, on the device) and are handed to the program.  Set-up
prefills the shared system prompt once through the normal path (a
``Generate`` request: one chunk, whole pages to the radix tree, a
snapshot of the state row at its end), then sends one request a prefill
bucket and one round of every session at once, so that every shape of
the window is compiled; those records feed no metric.  Every request of
the window is a NEW question: system prompt + a message of its own,
token ids from the held slice of the vocabulary, nothing kept after it.
After the window the program's state is freed, the plain reference
makes the weights again, runs the system prompt once and then, for the
sessions with ``id mod compare_every == 0``, every request of the
window teacher-forced on the served tokens.

Controls (each must read ``correct: false``): ``low_precision`` serves
with everything the configuration states in float32 at bfloat16 values
(every matmul's sum, the residual stream, the router, the scan state
and the convolution's tail) and the K/V pages at an int8 cache's values;
``stale_state`` admits a request into its state row as the row's last
holder left it (neither the hit's snapshot restored nor zeros);
``dropped_expert`` leaves the last chosen expert's share out of every
routed sum (where it is held here); ``altered_token`` alters one served
token a request where the client receives it.
"""
from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

from benchmarks.drivers.sala_serving import _Collector
from benchmarks.harness import generators as gen
from benchmarks.drivers.jamba_serving import closed_loop_chat_churn
from benchmarks.harness import reference_nemotron as ref

CONTROLS = {"altered_token": "", "low_precision": "low", "stale_state": "",
            "dropped_expert": "drop"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Driver:
    def __init__(self, cell, *, seed: int, devices: list, control=None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}; "
                             f"{sorted(CONTROLS)}")
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
        self._stuck = 0

    # ---- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax
        import brpc_tpu as brpc
        from brpc_tpu.models.hybrid import HybridRunner, make_layered_store
        from brpc_tpu.models.runner import from_hf_config
        from brpc_tpu.serving import DecodeEngine, register_serving
        self.jax, self.brpc = jax, brpc
        c = self.cfg
        # first of all: a program that cannot describe this family says
        # so here, before anything is made on the device
        tcfg = from_hf_config(
            dict(c, num_hidden_layers=c["published_num_hidden_layers"],
                 n_routed_experts=c["published_n_routed_experts"],
                 vocab_size=c["published_vocab_size"]),
            layers=(c["first_published_layer"], c["num_hidden_layers"]),
            experts=(c["first_expert_held"], c["n_routed_experts"]),
            vocab=(c["first_vocab_row"], c["vocab_size"]),
            param_dtype=c["param_dtype"])
        t0 = time.monotonic()
        self.params = ref.make_params(c, self.seed32, self.device)
        jax.block_until_ready(self.params)
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        log(f"  nemotron_serving: weights {nbytes / 1e9:.2f} GB made on the "
            f"device in {time.monotonic() - t0:.2f} s")
        self.store = make_layered_store(
            tcfg, cache_pages=c["cache_pages"], state_rows=c["state_rows"],
            page_tokens=c["page_tokens"], device=self.device,
            name="bench_kv")
        if self.control == "stale_state":
            # the control: an admission neither restores nor zeroes its
            # row (the counters still move: the readers read them)
            lay = self.store.layers
            lay.restore = lambda row, snapshot: lay.restores.add(1)
            lay.reset_row = lambda row: None
        self.runner = HybridRunner(
            self.params, tcfg, store=self.store,
            control=CONTROLS.get(self.control, ""), name="bench_nemotron")
        self.engine = DecodeEngine(
            runner=self.runner, num_slots=c["num_slots"], store=self.store,
            max_pages_per_slot=c["max_pages_per_slot"],
            prefill_buckets=tuple(c["prefill_buckets"]), name="bench_nemotron")
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
        t = self.traffic
        n_s = int(t["sessions"])
        self.block, self.pairs = closed_loop_chat_churn(t, self.seed)
        vocab = int(self.cfg["vocab_size"])
        self.system = gen.rng_for(self.seed, 7).integers(
            1, vocab, int(t["system_prompt_tokens"])).tolist()
        self.session_rng = [gen.rng_for(self.seed, 8, s) for s in range(n_s)]
        self.turns_done = [0] * n_s

    def _generate(self, message: list, max_new: int, **about) -> dict:
        brpc = self.brpc
        col = _Collector()
        cntl = brpc.Controller()
        brpc.stream_create(cntl, col)
        prompt = self.system + message
        rec = dict(about, message=message, prompt_len=len(prompt),
                   asked=max_new, ok=False, kind="generate", bytes=0)
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

    def _message(self, s: int, n: int) -> list:
        return self.session_rng[s].integers(
            1, int(self.cfg["vocab_size"]), n).tolist()

    def _turn(self, s: int) -> dict:
        """Session ``s``'s next conversation: one request, nothing kept."""
        j = self.turns_done[s]
        n_msg, n_out = self.pairs[j * len(self.turns_done) + s]
        self.turns_done[s] = j + 1
        return self._generate(self._message(s, n_msg), n_out, session=s,
                              turn=j)

    def _warm(self) -> None:
        """The system prompt once through the normal path (chunks of
        the largest bucket, whole pages to the radix tree and a snapshot
        of the row at its end; the decode step compiles on its first
        token), a request whose message fills each smaller bucket, then
        one round of every session at once (these turns are the plan's
        first and are not played again)."""
        t0 = time.monotonic()
        page = int(self.cfg["page_tokens"])
        n_sys = len(self.system)
        if n_sys % page:
            raise ValueError("the system prompt is whole pages")
        rng = gen.rng_for(self.seed, 10)
        vocab = int(self.cfg["vocab_size"])

        def once(n_msg):
            r = self._generate(rng.integers(1, vocab, n_msg).tolist(), 2,
                               session=-1, turn=-1)
            if not r["ok"]:
                raise RuntimeError(f"warm-up request failed: {r['error']}")
            return r
        once(page // 2)
        log(f"  nemotron_serving: system prompt ({n_sys} tokens) prefilled and "
            f"2 tokens decoded {time.monotonic() - t0:.1f} s into the "
            f"warm-up")
        for bucket in self.cfg["prefill_buckets"]:
            # a message of bucket + half a page: a chunk that fills the
            # bucket up to the message's last page boundary, then its tail
            hit = once(bucket + page // 2)
            if hit["prefix_hit"] < n_sys:
                raise RuntimeError(
                    f"a warm-up request missed the system prompt (hit "
                    f"{hit['prefix_hit']})")
        recs: list = []
        threads = [threading.Thread(
            target=lambda s=s: recs.append(self._turn(s)))
            for s in range(len(self.turns_done))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        bad = [r for r in recs if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up turn failed: {bad[0]['error']}")
        log(f"  nemotron_serving: warm-up {time.monotonic() - t0:.1f} s, "
            f"{len(recs)} turns; cache {self.store.stats().get('layers')}")

    # ---- the window -------------------------------------------------------

    def run(self, seconds: float, during=None):
        loop = gen.ClosedLoop(len(self.turns_done),
                              lambda s, _i: self._turn(s))
        t0, t1 = loop.run(seconds, during, drain_s=120.0)
        self.requests = loop.all_records()
        self._stuck = loop.stuck
        return t0, t1

    def counters(self) -> dict:
        from brpc_tpu.serving import engine as engine_mod
        e, s, r = self.engine, self.store, self.runner
        lay = s.layers
        n, us = engine_mod.STAGE_PREFILL_REC.snapshot()[:2]
        return {"steps": e.steps.get_value(),
                "tokens": e.tokens_out.get_value(),
                "retired": e.retired.get_value(),
                "hit_tokens": s.hit_tokens.get_value(),
                "prompt_tokens": s.prompt_tokens.get_value(),
                "prefill_us_sum": float(us), "prefill_count": int(n),
                "evictions": s.evictions.get_value(),
                "state_snapshots": lay.snapshots.get_value(),
                "state_restores": lay.restores.get_value(),
                "state_restore_misses": lay.restore_misses.get_value(),
                "state_snapshot_no_row": lay.snapshot_no_row.get_value(),
                "ssd_tokens": r.ssd_tokens.get_value(),
                "ssd_steps": r.ssd_steps.get_value(),
                "moe_assignments": r.moe_assignments.get_value(),
                "moe_assignments_held": r.moe_assignments_held.get_value(),
                "moe_experts_hit": r.moe_experts_hit.get_value(),
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
        log(f"  nemotron_serving: at the window's end {self.counters()}")
        self.server.stop()
        self.server.join()
        self.engine.close()
        self.runner.close()
        self.store.clear()
        self.store.close()
        self.server = self.engine = self.store = self.runner = None
        self.params = None
        gc.collect()

    def compare(self, params, ctx: dict, r: dict) -> np.ndarray:
        """One request against the reference, teacher-forced on the
        served tokens, a block of positions at a time after the system
        prompt's context: for every served token ``(|served logprob -
        reference's log-softmax|, reference's best logit - its logit)``
        ``[n, 2]``."""
        b = int(self.cfg["reference_block"])
        start = len(self.system)
        row = r["message"] + r["tokens"]
        n = len(row) - 1              # the last served token is no input
        lps, gaps = [], []
        for at in range(0, n, b):
            k = min(b, n - at)
            toks = np.zeros((b,), np.int32)
            targets = np.zeros((b,), np.int32)
            toks[:k] = row[at:at + k]
            targets[:k] = row[at + 1:at + 1 + k]
            (lp, gap), ctx = ref.block_forward(
                params, self.cfg, ctx, toks, start + at, k, targets=targets)
            lps.append(np.asarray(lp)[:k])
            gaps.append(np.asarray(gap)[:k])
        lp, gap = np.concatenate(lps), np.concatenate(gaps)
        first = len(r["message"]) - 1    # the position predicting token 0
        idx = first + np.arange(len(r["tokens"]))
        got = np.asarray(r["logprobs"], np.float64)
        return np.stack([np.abs(got - lp[idx]), gap[idx]], axis=1)

    def check(self) -> list:
        tol = self.cfg["assumed"]["tolerances"]
        t0 = time.monotonic()
        every = int(self.traffic["compare_every"])
        wanted = [r for r in self.requests
                  if r["ok"] and r["session"] % every == 0]
        each: list = []
        if wanted:
            b = int(self.cfg["reference_block"])
            if len(self.system) % b:
                raise ValueError("system_prompt_tokens must be a multiple "
                                 "of the reference's block")
            longest = max(len(r["message"]) + len(r["tokens"])
                          for r in wanted)
            s_max = -(-(len(self.system) + longest) // 1024) * 1024
            params = ref.make_params(self.cfg, self.seed32, self.device)
            ctx = ref.new_context(self.cfg, s_max)
            for at in range(0, len(self.system), b):
                _, ctx = ref.block_forward(params, self.cfg, ctx,
                                           self.system[at:at + b], at, b)
            self.jax.block_until_ready(ctx)
            log(f"  nemotron_serving: reference ran the system prompt "
                f"{time.monotonic() - t0:.1f} s into the check (context "
                f"of {s_max} positions)")
            each = [self.compare(params, ctx, r) for r in wanted]
            del params, ctx
        all_ = np.concatenate(each) if each else np.zeros((0, 2))
        firsts = np.stack([e[0] for e in each]) if each else np.zeros((0, 2))
        n = len(all_)
        err, gap = all_[:, 0], all_[:, 1]

        far = float(tol["far_gap"])

        def spread(x):
            if not len(x):
                return "-"
            qs = np.quantile(x, [0.5, 0.9, 0.99, 0.999, 1.0])
            over = [int((x > t).sum()) for t in (0.5, 1.0, 2.0, 3.0, 4.0)]
            return (f"p50 {qs[0]:.4g} p90 {qs[1]:.4g} p99 {qs[2]:.4g} "
                    f"p99.9 {qs[3]:.4g} max {qs[4]:.4g}, mean "
                    f"{x.mean():.4g}; over 0.5/1/2/3/4: {over}")
        log(f"  nemotron_serving: reference over {len(each)} requests, {n} "
            f"served tokens, in {time.monotonic() - t0:.1f} s\n"
            f"    |served logprob - reference|: {spread(err)}\n"
            f"    the same of each request's FIRST token: "
            f"{spread(firsts[:, 0])}\n"
            f"    reference's best logit - served token's: {spread(gap)}")
        failed = sum(1 for r in self.requests if not r["ok"])
        return [
            ("failed_requests", failed + self._stuck, 0),
            ("requests_not_compared",
             len(wanted) - len(each) + (0 if each else 1), 0),
            ("served_logprob_abs_err_median",
             float(np.median(err)) if n else 0.0, tol["logprob_abs_median"]),
            ("served_logprob_abs_err_mean",
             float(err.mean()) if n else 0.0, tol["logprob_abs_mean"]),
            ("first_token_logprob_abs_err_median",
             float(np.median(firsts[:, 0])) if n else 0.0,
             tol["first_token_logprob_abs_median"]),
            ("served_tokens_far_from_best_per_1000",
             1e3 * float((gap > far).mean()) if n else 0.0,
             tol["far_gap_per_1000"]),
        ]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.server is not None:
            try:
                self.release()
            except Exception as e:
                log(f"  nemotron_serving: close: {type(e).__name__}: {e}")
        from brpc_tpu.ici import rail
        rail.close_endpoints()
