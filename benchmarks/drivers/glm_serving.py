"""Driver of GLM-4.7-Flash's serving deployment (``glm47_flash_l8_1chip``):
a ``HybridRunner`` (latent attention over latent pages, routed experts)
behind ``register_serving`` / ``Serving.Generate`` on one chip over a
layered ``KVCacheStore``, agent sessions over loopback in the same
process.

The weights are the BENCHMARK's (``reference_glm.make_params``, from the
seed, on the device) and are handed to the program.  Set-up prefills the
shared system prompt once through the normal path (a ``Generate``
request: chunked prefill, whole pages to the radix tree) and then plays
session ``i`` forward to turn ``i mod turns_per_episode`` through
``Generate`` itself, so that the mix of context lengths is stationary
from the window's first second; those records feed the reference and no
metric.  After the window the program's state is freed, the plain
reference makes the weights again, runs the system prompt once and then,
for the sessions with ``id mod compare_every == 0``, every episode that
touched the window WHOLE from its first turn, teacher-forced on the
served tokens.

Controls (each must read ``correct: false``): ``low_precision`` serves
with everything the configuration states in float32 at bfloat16 values
(every matmul's sum, the residual stream, the router) and the latent
pages at an int8 cache's values; ``dropped_expert`` leaves the fourth
chosen expert's share out of every routed sum; ``altered_token`` alters
one served token a request where the client receives it.
"""
from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

from benchmarks.drivers.sala_serving import _Collector
from benchmarks.harness import generators as gen
from benchmarks.harness import reference_glm as ref

CONTROLS = {"altered_token": "", "low_precision": "low",
            "dropped_expert": "drop"}
TURNS = 4096                  # turns per session planned ahead


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Session:
    """One agent: the episode it is in and that episode's history (every
    tool result and served answer so far).  One thread drives it."""

    def __init__(self):
        self.episode = 0
        self.turn = 0             # within the episode
        self.turns_done = 0       # of all its episodes
        self.history: list = []


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
        self.setup_records: list = []
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
            dict(c, num_hidden_layers=c["published_num_hidden_layers"]),
            layers=(c["first_published_layer"], c["num_hidden_layers"]),
            param_dtype=c["param_dtype"])
        t0 = time.monotonic()
        self.params = ref.make_params(c, self.seed32, self.device)
        jax.block_until_ready(self.params)
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        log(f"  glm_serving: weights {nbytes / 1e9:.2f} GB made on the "
            f"device in {time.monotonic() - t0:.2f} s")
        self.store = make_layered_store(
            tcfg, cache_pages=c["cache_pages"],
            page_tokens=c["page_tokens"], device=self.device,
            name="bench_kv")
        self.runner = HybridRunner(
            self.params, tcfg, store=self.store,
            control=CONTROLS.get(self.control, ""), name="bench_glm")
        self.engine = DecodeEngine(
            runner=self.runner, num_slots=c["num_slots"], store=self.store,
            max_pages_per_slot=c["max_pages_per_slot"],
            prefill_buckets=tuple(c["prefill_buckets"]), name="bench_glm")
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
        """The system prompt and token ids from the seed; ONE fixed block
        of (tool result, output) lengths from the mix's own
        ``length_seed``, laid end to end in permutations the seed
        decides and dealt over sessions and turns."""
        t = self.traffic
        n_s, n = int(t["sessions"]), int(t["turns_block"])
        fixed = np.random.default_rng(int(t["length_seed"]))
        tool, out = t["tool_result_tokens"], t["output_tokens"]
        self.block = list(zip(
            gen.lognormal_lengths(n, tool["median"], tool["sigma"],
                                  tool["min"], tool["max"], fixed),
            gen.lognormal_lengths(n, out["median"], out["sigma"],
                                  out["min"], out["max"], fixed)))
        vocab = int(self.cfg["vocab_size"])
        self.system = gen.rng_for(self.seed, 7).integers(
            1, vocab, int(t["system_prompt_tokens"])).tolist()
        self.session_rng = [gen.rng_for(self.seed, 8, s) for s in range(n_s)]
        self.pairs = gen.block_permutations(self.block, n_s * TURNS,
                                            gen.rng_for(self.seed, 9))
        self.sessions = [_Session() for _ in range(n_s)]

    def _generate(self, prompt: list, max_new: int, **about) -> dict:
        brpc = self.brpc
        col = _Collector()
        cntl = brpc.Controller()
        brpc.stream_create(cntl, col)
        rec = dict(about, prompt_len=len(prompt), asked=max_new, ok=False,
                   kind="generate", bytes=0)
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
        return rec

    def _turn(self, s: int) -> dict:
        """Session ``s``'s next turn: the request, and the history it
        leaves."""
        ses = self.sessions[s]
        n_tool, n_out = self.pairs[
            ses.turns_done * len(self.sessions) + s]
        tool = self.session_rng[s].integers(
            1, int(self.cfg["vocab_size"]), n_tool).tolist()
        rec = self._generate(self.system + ses.history + tool, n_out,
                             session=s, episode=ses.episode, turn=ses.turn,
                             tool=tool)
        ses.turns_done += 1
        # the history keeps what was SERVED; the control alters what the
        # client is told it received
        ses.history += tool + rec["tokens"]
        ses.turn += 1
        if ses.turn == int(self.traffic["turns_per_episode"]) \
                or not rec["ok"]:
            ses.episode, ses.turn, ses.history = ses.episode + 1, 0, []
        if self.control == "altered_token" and rec["tokens"]:
            k = len(rec["tokens"]) // 2
            rec["tokens"] = list(rec["tokens"])
            rec["tokens"][k] = (rec["tokens"][k] + 1) \
                % int(self.cfg["vocab_size"])
        return rec

    def _warm(self) -> None:
        """The system prompt once through the normal path (this IS the
        long prefill: chunks of the largest bucket, then the smallest for
        what is left; the decode step compiles on its first token), then
        every session forward to its starting turn, all at once."""
        t0 = time.monotonic()
        vocab = int(self.cfg["vocab_size"])
        opener = gen.rng_for(self.seed, 10).integers(1, vocab, 40).tolist()
        r = self._generate(self.system + opener, 2)
        if not r["ok"]:
            raise RuntimeError(f"system prompt prefill failed: {r['error']}")
        log(f"  glm_serving: system prompt ({len(self.system)} tokens) "
            f"prefilled and 2 tokens decoded {time.monotonic() - t0:.1f} s "
            f"into the warm-up")
        per = int(self.traffic["turns_per_episode"])
        recs: list = [[] for _ in self.sessions]

        def forward(s):
            for _ in range(s % per):
                recs[s].append(self._turn(s))
        threads = [threading.Thread(target=forward, args=(s,))
                   for s in range(len(self.sessions))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        self.setup_records = [r for rs in recs for r in rs]
        bad = [r for r in self.setup_records if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up turn failed: {bad[0]['error']}")
        cold = [r for r in self.setup_records
                if r["prefix_hit"] < len(self.system)]
        if cold:
            raise RuntimeError(
                f"{len(cold)} warm-up requests missed the system prompt "
                f"(hit {cold[0]['prefix_hit']})")
        log(f"  glm_serving: warm-up {time.monotonic() - t0:.1f} s, "
            f"{len(self.setup_records)} turns played forward; cache "
            f"{self.store.stats().get('layers')}")

    # ---- the window -------------------------------------------------------

    def run(self, seconds: float, during=None):
        loop = gen.ClosedLoop(len(self.sessions),
                              lambda s, _i: self._turn(s))
        t0, t1 = loop.run(seconds, during, drain_s=120.0)
        self.requests = loop.all_records()
        self._stuck = loop.stuck
        return t0, t1

    def counters(self) -> dict:
        from brpc_tpu.serving import engine as engine_mod
        e, s, r = self.engine, self.store, self.runner
        n, us = engine_mod.STAGE_PREFILL_REC.snapshot()[:2]
        return {"steps": e.steps.get_value(),
                "tokens": e.tokens_out.get_value(),
                "retired": e.retired.get_value(),
                "hit_tokens": s.hit_tokens.get_value(),
                "prompt_tokens": s.prompt_tokens.get_value(),
                "prefill_us_sum": float(us), "prefill_count": int(n),
                "evictions": s.evictions.get_value(),
                "moe_assignments": r.moe_assignments.get_value(),
                "moe_experts_hit": r.moe_experts_hit.get_value(),
                "latent_tokens_read": r.latent_tokens_read.get_value(),
                "latent_pages_distinct":
                    r.latent_pages_distinct.get_value(),
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

    def episodes(self) -> list:
        """The compared sessions' episodes that touched the window, each
        WHOLE from its first turn: ``[records in turn order]``, the
        set-up's turns of an episode the window went on with included.
        An episode that a failed request cut is left out (the failure is
        counted on its own)."""
        every = int(self.traffic["compare_every"])
        by: dict = {}
        in_window = set()
        for r in self.setup_records + self.requests:
            if r["session"] % every == 0:
                by.setdefault((r["session"], r["episode"]), []).append(r)
        for r in self.requests:
            in_window.add((r["session"], r["episode"]))
        out = []
        for key in sorted(in_window & set(by)):
            turns = sorted(by[key], key=lambda r: r["turn"])
            if all(r["ok"] for r in turns) \
                    and [r["turn"] for r in turns] == list(range(len(turns))):
                out.append(turns)
        return out

    def compare(self, params, ctx: dict, turns: list) -> np.ndarray:
        """One episode against the reference, teacher-forced on the
        served tokens, a block of positions at a time after the system
        prompt's context: for every served token ``(|served logprob -
        reference's log-softmax|, reference's best logit - its logit)``
        ``[n, 2]``."""
        b = int(self.cfg["reference_block"])
        start = len(self.system)
        row, served = [], []          # served: (index in row, logprob)
        for r in turns:
            row += r["tool"]
            for tok, lp in zip(r["tokens"], r["logprobs"]):
                served.append((len(row), lp))
                row.append(tok)
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
        idx = np.asarray([i - 1 for i, _ in served])   # who predicts it
        got = np.asarray([x for _, x in served], np.float64)
        return np.stack([np.abs(got - lp[idx]), gap[idx]], axis=1)

    def check(self) -> list:
        tol = self.cfg["assumed"]["tolerances"]
        t0 = time.monotonic()
        episodes = self.episodes()
        wanted = {(r["session"], r["episode"]) for r in self.requests
                  if r["session"] % int(self.traffic["compare_every"]) == 0
                  and r["ok"]}
        each = np.zeros((0, 2))
        if episodes:
            b = int(self.cfg["reference_block"])
            if len(self.system) % b:
                raise ValueError("system_prompt_tokens must be a multiple "
                                 "of the reference's block")
            longest = max(sum(len(r["tool"]) + len(r["tokens"])
                              for r in turns) for turns in episodes)
            s_max = -(-(len(self.system) + longest) // 1024) * 1024
            params = ref.make_params(self.cfg, self.seed32, self.device)
            ctx = ref.new_context(self.cfg, s_max)
            for at in range(0, len(self.system), b):
                _, ctx = ref.block_forward(params, self.cfg, ctx,
                                           self.system[at:at + b], at, b)
            self.jax.block_until_ready(ctx)
            log(f"  glm_serving: reference ran the system prompt "
                f"{time.monotonic() - t0:.1f} s into the check (context "
                f"of {s_max} positions)")
            each = np.concatenate([self.compare(params, ctx, turns)
                                   for turns in episodes])
            del params, ctx
        n = len(each)
        err, gap = each[:, 0], each[:, 1]
        far = float(tol["far_gap"])
        lp_median = float(np.median(err)) if n else 0.0
        lp_mean = float(err.mean()) if n else 0.0
        far_per_1000 = 1e3 * float((gap > far).mean()) if n else 0.0

        def spread(x):
            if not n:
                return "-"
            qs = np.quantile(x, [0.5, 0.9, 0.99, 0.999, 1.0])
            over = [int((x > t).sum()) for t in (0.5, 1.0, 2.0, 3.0, 4.0)]
            return (f"p50 {qs[0]:.4g} p90 {qs[1]:.4g} p99 {qs[2]:.4g} "
                    f"p99.9 {qs[3]:.4g} max {qs[4]:.4g}, mean "
                    f"{x.mean():.4g}; over 0.5/1/2/3/4: {over}")
        log(f"  glm_serving: reference over {len(episodes)} episodes "
            f"({sum(len(t) for t in episodes)} requests), {n} served "
            f"tokens, in {time.monotonic() - t0:.1f} s\n"
            f"    |served logprob - reference|: {spread(err)}\n"
            f"    reference's best logit - served token's: {spread(gap)}")
        failed = sum(1 for r in self.requests if not r["ok"])
        return [
            ("failed_requests", failed + self._stuck, 0),
            ("episodes_not_compared",
             len(wanted) - len(episodes) + (0 if episodes else 1), 0),
            ("served_logprob_abs_err_median", lp_median,
             tol["logprob_abs_median"]),
            ("served_logprob_abs_err_mean", lp_mean,
             tol["logprob_abs_mean"]),
            ("served_tokens_far_from_best_per_1000", far_per_1000,
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
                log(f"  glm_serving: close: {type(e).__name__}: {e}")
        from brpc_tpu.ici import rail
        rail.close_endpoints()
