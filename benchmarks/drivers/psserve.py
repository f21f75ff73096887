"""Driver of the parameter-server deployments: one ``brpc.Server`` bound
to a chip with one ``EmbeddingShardServer`` behind ``register_psserve``
(its default batchers), ``PSClient`` callers in the same process, each
over a ``PartitionChannel`` of one partition on loopback, on the binary
tensorframe wire (``PS.LookupT`` / ``PS.UpdateT``).  No local lowered
table is registered, so ``ici="auto"`` finds none and the RPC path
serves: what the cell calls is what ``examples/embedding_server.py`` and
``train/`` call.

Traffic generator (``traffic["generator"]``): ``closed_loop_keyed``
(``psserve_traffic.py``, beside this file).

What ``benchmarks/README.md``'s ``psserve`` example left open:

**Records of two kinds.**  ``records()["calls"]`` holds one record a
call: ``kind`` ``lookup`` (bytes: rows delivered to the caller, ``n`` x
the record width), ``update`` (bytes: gradient bytes acknowledged) or
``resend`` (an update sent a second time with the same
``update_token`` right after its ack; 0 bytes).  Every record carries
``n``, ``version`` (the version the shard answered or acknowledged
with) and, a lookup, ``v_floor``: the highest version acknowledged to
any caller before it was issued.  A failed call counts no bytes.

**The version-ordered replay.**  ``correct`` compares what the timed
window itself returned.  The acknowledged versions give the order of
the updates; after the program's state is freed the plain reference
(``harness/reference_ps.py``: numpy float32 Adam, the table and the
gradients made again from the seed) replays them in that order, and at
each version the sampled replies that carry it (per caller the first
and last four lookups and the flagged share of the rest) are held to
the snapshot guarantee: every returned row equals the reference's at
exactly that version (``rows_gap_max`` under the configuration's
``row_tolerance``, ``rows_off_snapshot`` 0).  Over the window: the
shard's final version equals the distinct tokens acknowledged and no
two acks share a version (``updates_lost_or_doubled``); every resend is
acknowledged with its first version (``replays_not_deduped``) and the
shard's ``n_dup_updates`` rose by exactly the resends
(``dup_counter_off``); no lookup reports a version under its
``v_floor``, nor does the client's own count (``stale_reads``); after
the window every touched row and 4,096 untouched ones are read back
through the served path and compared (``final_rows_off``).

Controls (``--control``; each must read ``correct: false``):
``lost_update`` (every fourth update acknowledged and not applied),
``double_apply`` (the shard forgets update ids, so a resend applies
again), ``stale_read`` (lookups served from a set-up snapshot),
``low_precision`` (the table rounded to bfloat16).  They wrap the
shard's public entry points from here; the program has no such option.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.drivers import psserve_traffic as keyed
from benchmarks.harness import generators as gen
from benchmarks.harness import reference_ps as ref

HORIZON = 1 << 15              # calls per caller drawn ahead of the window
KEY_POOL = 1 << 20             # keys per caller drawn ahead of the window
GRAD_POOL_ROWS = 1 << 16       # gradient rows shared by all callers
KEEP_FIRST, KEEP_LAST = 4, 4   # sampled lookups kept per caller, each end
UNTOUCHED_READ_BACK = 4096
TABLE_CHUNK_ROWS = 1 << 14
GAP_WRONG_SHAPE = 1e30         # a reply of another shape: over any limit
SLOW_CALL_S = 0.25             # a call this long is logged (p95 is 45 ms)
CONTROLS = ("lost_update", "double_apply", "stale_read", "low_precision")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_table(seed32: int, vocab: int, dim: int) -> np.ndarray:
    """The whole table on the host, in bulk: ``reference_ps.table_rows``
    over chunks of rows on a few threads (numpy gives up the
    interpreter lock inside the large operations)."""
    table = np.empty((vocab, dim), np.float32)

    def fill(lo: int) -> None:
        hi = min(lo + TABLE_CHUNK_ROWS, vocab)
        table[lo:hi] = ref.table_rows(seed32, np.arange(lo, hi), dim)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(0, vocab, TABLE_CHUNK_ROWS)))
    return table


class Driver:
    def __init__(self, cell, *, seed: int, devices: list, control=None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}; {CONTROLS}")
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        if self.traffic["generator"] != "closed_loop_keyed":
            raise ValueError(f"psserve has no generator "
                             f"{self.traffic['generator']!r}")
        self.seed = int(seed)
        self.seed32 = gen.fold_seed(seed)
        self.devices = devices
        self.control = control
        self.vocab, self.dim = int(self.cfg["vocab"]), int(self.cfg["dim"])
        self.row_bytes = self.dim * 4
        self.n_callers = int(self.traffic["callers"])
        self.server = self.svc = self.shard = None
        self.clients: list = []
        self._calls: list = []
        self._warm_calls: list = []
        self._ack_mu = threading.Lock()
        self._acked_max = 0
        self._stuck = 0
        self._closed = False

    # ---- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax
        import brpc_tpu as brpc
        from brpc_tpu import psserve
        from brpc_tpu.rpc.combo_channels import PartitionChannel
        from brpc_tpu.train.optimizer import OptimizerSpec
        self.jax, self.brpc, self.psserve = jax, brpc, psserve
        if not (hasattr(psserve.PSService, "warm")
                and hasattr(psserve.PSClient, "lookup_versioned")):
            # a program from before this cell: fail at once, make nothing
            raise RuntimeError(
                "this program has no PSService.warm / "
                "PSClient.lookup_versioned: the cell cannot run on it")
        opt = self.cfg["optimizer"]
        self.spec = OptimizerSpec(opt["kind"], lr=opt["lr"],
                                  beta1=opt["beta1"], beta2=opt["beta2"],
                                  eps=opt["eps"])
        t = time.monotonic()
        table = make_table(self.seed32, self.vocab, self.dim)
        if self.control == "low_precision":
            table = ref.to_bfloat16(table)
        log(f"  psserve: table {self.vocab} x {self.dim} float32 "
            f"({table.nbytes / 1e9:.2f} GB) made on the host in "
            f"{time.monotonic() - t:.2f} s")
        t = time.monotonic()
        device = self.devices[int(self.cfg.get("server_chip", 0))]
        self.shard = psserve.EmbeddingShardServer(
            0, 1, self.vocab, self.dim, table=table, device=device)
        self._break_shard(table)
        del table
        self.server = brpc.Server(ici_device=device)
        self.svc = psserve.register_psserve(self.server, self.shard)
        self.server.start("127.0.0.1", 0)
        self.svc.warm(self.spec)
        log(f"  psserve: shard on {device}, slots allocated and every "
            f"bucket warm in {time.monotonic() - t:.2f} s")
        for c in range(self.n_callers):
            pc = PartitionChannel(1)
            pc.add_partition(0, brpc.Channel(
                f"127.0.0.1:{self.server.port}",
                timeout_ms=int(self.traffic["timeout_ms"])))
            self.clients.append(psserve.PSClient(
                pc, vocab=self.vocab, dim=self.dim,
                timeout_ms=int(self.traffic["timeout_ms"]),
                max_retry=int(self.traffic["max_retry"]),
                name=f"bench_{c}"))
        t = time.monotonic()
        self.grad_pool = ref.gradient_pool(
            self.seed32, GRAD_POOL_ROWS, self.dim,
            float(self.cfg["gradient_scale"]))
        self.plans = self._make_plans()
        log(f"  psserve: {self.n_callers} callers' keys (scrambled Zipfian "
            f"{self.cfg['zipfian_constant']}) and the gradient pool drawn "
            f"in {time.monotonic() - t:.2f} s")
        self._dups0 = self.shard.n_dup_updates
        self._warm_rpc()

    def _make_plans(self) -> list:
        zipf = keyed.Zipfian(self.vocab, float(self.cfg["zipfian_constant"]))
        with ThreadPoolExecutor(8) as pool:
            return list(pool.map(
                lambda c: keyed.CallerPlan(
                    self.traffic, self.seed, c, zipf, GRAD_POOL_ROWS,
                    HORIZON, KEY_POOL), range(self.n_callers)))

    def _break_shard(self, table: np.ndarray) -> None:
        """The controls: the shard's public entry points wrapped from
        outside, before the service takes hold of them."""
        shard, control = self.shard, self.control
        if control == "lost_update":
            apply = shard.update_opt
            count = [0]

            def lossy(keys, grads, spec, update_id=None):
                count[0] += 1
                if count[0] % 4 == 0:
                    return shard.version, False
                return apply(keys, grads, spec, update_id=update_id)
            shard.update_opt = lossy
        elif control == "double_apply":
            apply = shard.update_opt
            shard.update_opt = lambda keys, grads, spec, update_id=None: \
                apply(keys, grads, spec, update_id=None)
        elif control == "stale_read":
            snapshot = table.copy()
            shard.lookup = lambda keys: (
                snapshot[np.asarray(keys, np.int64)], 0)
            shard.lookup_batch_fn = lambda padded: snapshot[
                np.asarray(padded, np.int64)]
            shard.gathered_version = lambda: 0

    def _warm_rpc(self) -> None:
        """Every caller opens its connection and makes one call of each
        kind through the served path (the device programs are warm
        already).  The updates change the table, so they are kept for
        the replay like the window's."""
        errs = []

        def warm(c: int) -> None:
            try:
                plan = self.plans[c]
                seen = set()
                for j in range(HORIZON):
                    kind, _keys, resend, _g, _f = plan.call(j)
                    tag = (kind, resend)
                    if tag in seen:
                        continue
                    seen.add(tag)
                    self._warm_calls.extend(self._do_call(c, j, warm=True))
                    if len(seen) == 3:
                        return
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=warm, args=(c,))
                   for c in range(self.n_callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        failed = [r for r in self._warm_calls if not r["ok"]]
        if failed:
            raise RuntimeError(f"warm-up call failed: {failed[0]}")
        n = self.n_callers
        self._first = [[] for _ in range(n)]
        self._last = [collections.deque(maxlen=KEEP_LAST) for _ in range(n)]
        self._flagged = [[] for _ in range(n)]
        self._resends = [[] for _ in range(n)]

    # ---- one call ---------------------------------------------------------

    def _token(self, caller: int, i: int, warm: bool) -> int:
        """A fresh update token: (caller, turn) packed under 2**53."""
        return ((caller + 1) << 33) | (int(warm) << 32) | (i + 1)

    def _do_call(self, caller: int, i: int, warm: bool = False) -> list:
        """Call ``i`` of ``caller``; its record, and a resend's after it."""
        kind, keys, resend, goff, flagged = self.plans[caller].call(i)
        n = int(keys.shape[0])
        cli = self.clients[caller]
        rec = {"caller": caller, "i": i, "kind": kind, "n": n, "ok": False,
               "bytes": n * self.row_bytes, "warm": warm}
        if kind == "lookup":
            rec["v_floor"] = self._acked_max
            rows = self._timed(rec, lambda: cli.lookup_versioned(keys))
            if rows is None:
                return [rec]
            rows, versions = rows
            rec["version"] = versions[0]
            rec["shape_ok"] = (isinstance(rows, np.ndarray)
                               and rows.shape == (n, self.dim)
                               and rows.dtype == np.float32)
            if not warm:
                self._keep(caller, rec, rows, flagged)
            return [rec]
        grads = self.grad_pool[goff:goff + n]
        rec["grad_off"] = goff
        rec["token"] = token = self._token(caller, i, warm)

        def send(r: dict) -> bool:
            acks = self._timed(r, lambda: cli.update(
                keys, grads, update_token=token, optimizer=self.spec))
            if acks is None:
                return False
            r["version"] = acks[0]
            with self._ack_mu:
                self._acked_max = max(self._acked_max, acks[0])
            return True

        if not (send(rec) and resend):
            return [rec]
        again = dict(rec, kind="resend", bytes=0, ok=False)
        send(again)
        return [rec, again]

    def _timed(self, rec: dict, call):
        """Run ``call`` on the host clock into ``rec``; None if it
        failed."""
        rec["t_issue"] = time.monotonic()
        try:
            with self.jax.profiler.TraceAnnotation("bench.ps_call"):
                out = call()
        except self.brpc.errors.RpcError as e:
            rec["t_done"] = time.monotonic()
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            return None
        rec["t_done"] = time.monotonic()
        rec["ok"] = True
        return out

    def _keep(self, caller: int, rec: dict, rows, flagged: bool) -> None:
        if len(self._first[caller]) < KEEP_FIRST:
            self._first[caller].append((rec, rows))
        elif flagged:
            self._flagged[caller].append((rec, rows))
        else:
            self._last[caller].append((rec, rows))

    def _one_call(self, caller: int, i: int) -> dict:
        recs = self._do_call(caller, i)
        self._resends[caller].extend(recs[1:])
        return recs[0]

    # ---- the window -------------------------------------------------------

    def run(self, seconds: float, during=None):
        loop = gen.ClosedLoop(self.n_callers, self._one_call)
        t0, t1 = loop.run(seconds, during)
        self._calls = loop.all_records() \
            + [r for rs in self._resends for r in rs]
        self._stuck = loop.stuck
        per_s = np.bincount(
            [int(c["t_done"] - t0) for c in self._calls
             if t0 <= c["t_done"] < t1], minlength=int(seconds))
        log(f"  psserve: calls completed in each second of the window: "
            f"{per_s.tolist()}")
        slow = sorted((c for c in self._calls
                       if c["t_done"] - c["t_issue"] >= SLOW_CALL_S),
                      key=lambda c: c["t_done"])
        if slow:
            # a stall of the whole process shows as one such call a caller
            log(f"  psserve: {len(slow)} calls took {SLOW_CALL_S} s or "
                f"more; (done at, took, kind, caller, keys) of the first: "
                + ", ".join(f"({c['t_done'] - t0:.2f}, "
                            f"{c['t_done'] - c['t_issue']:.2f}, {c['kind']}, "
                            f"{c['caller']}, {c['n']})" for c in slow[:20]))
        return t0, t1

    def counters(self) -> dict:
        from brpc_tpu.butil import flight
        from brpc_tpu.bvar import dump_exposed
        out = {k: v for pattern in ("psserve_*", "serving_ps_lookup_*")
               for k, v in dump_exposed(pattern).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
        b = f"serving_ps_lookup_{self.shard.name}_{self.shard.shard_index}"
        out["lookup_programs"] = out.get(f"{b}_batches", 0) \
            + out.get(f"{b}_bypassed", 0)
        out["write_syscalls"] = flight.syscall_counters()["write_syscalls"]
        out["t"] = time.monotonic()
        return out

    def records(self) -> dict:
        for c in self._calls:
            if c["kind"] == "update" and "distinct" not in c:
                keys = self.plans[c["caller"]].call(c["i"])[1]
                c["distinct"] = int(np.unique(keys).size)
        return {"calls": self._calls}

    def attempted_failed(self) -> tuple:
        return len(self._calls), sum(1 for c in self._calls if not c["ok"])

    # ---- after the window -------------------------------------------------

    def _read_back(self) -> None:
        """Every row an update touched and a seeded draw of untouched
        ones, through the served path, outside the window."""
        touched = np.unique(np.concatenate(
            [self.plans[c["caller"]].call(c["i"])[1]
             for c in self._warm_calls + self._calls
             if c["kind"] == "update"] or [np.zeros((0,), np.int64)]))
        rng = gen.rng_for(self.seed, 7)
        extra = np.setdiff1d(
            rng.integers(0, self.vocab, 2 * UNTOUCHED_READ_BACK), touched
        )[:UNTOUCHED_READ_BACK]
        keys = np.concatenate([touched, extra])
        self._final = []
        step = max(self.shard.key_buckets)
        try:
            for lo in range(0, keys.shape[0], step):
                rows, versions = self.clients[0].lookup_versioned(
                    keys[lo:lo + step])
                self._final.append((keys[lo:lo + step], rows, versions[0]))
        except self.brpc.errors.RpcError as e:
            log(f"  psserve: read-back failed: {e}")
            self._final = None

    def release(self) -> None:
        """Read the final table back, stop the program and free its
        state: the reference runs with all of it gone."""
        self._read_back()
        self._final_version = self.shard.version
        self._dups = self.shard.n_dup_updates - self._dups0
        self._client_stale = sum(c.n_stale_reads for c in self.clients)
        self.samples = [s for per_caller in (self._first, self._flagged,
                                             self._last)
                        for kept in per_caller for s in kept]
        self._first = self._flagged = self._last = []
        self._stop_program()
        self.grad_pool = self.plans = None

    def _stop_program(self) -> None:
        for cli in self.clients:
            cli.close()
        self.clients = []
        if self.svc is not None:
            self.psserve.unregister_psserve(self.svc)
        if self.server is not None:
            self.server.stop()
            self.server.join()
        self.server = self.svc = self.shard = None

    def check(self) -> list:
        """The configuration's guarantees, on what the window returned
        (the module docstring says how)."""
        opt = self.cfg["optimizer"]
        tol = float(self.cfg["row_tolerance"])
        table = ref.AdamTable(self.seed32, self.dim, lr=opt["lr"],
                              beta1=opt["beta1"], beta2=opt["beta2"],
                              eps=opt["eps"])
        pool = ref.gradient_pool(self.seed32, GRAD_POOL_ROWS, self.dim,
                                 float(self.cfg["gradient_scale"]))
        plans = self._make_plans()
        calls = self._warm_calls + self._calls
        done = [c for c in calls if c["ok"]]
        updates = [c for c in done if c["kind"] == "update"]
        by_version = {c["version"]: c for c in updates}
        shared = len(updates) - len(by_version)
        lost_or_doubled = shared + abs(self._final_version - len(updates))
        first = {c["token"]: c["version"] for c in updates}
        resends = [c for c in done if c["kind"] == "resend"]
        not_deduped = sum(1 for c in resends
                          if c["version"] != first.get(c["token"]))
        lookups = [c for c in done if c["kind"] == "lookup"]
        stale = sum(1 for c in lookups if c["version"] < c["v_floor"]) \
            + self._client_stale

        samples = sorted(self.samples, key=lambda s: s[0]["version"])
        self.samples = []
        gap_max, off, k = 0.0, 0, 0
        last = max([self._final_version, *by_version,
                    *(s[0]["version"] for s in samples)], default=0)
        for v in range(last + 1):
            u = by_version.get(v)
            if u is not None:
                keys = plans[u["caller"]].call(u["i"])[1]
                table.apply(keys, pool[u["grad_off"]:u["grad_off"] + u["n"]])
            while k < len(samples) and samples[k][0]["version"] <= v:
                rec, rows = samples[k]
                keys = plans[rec["caller"]].call(rec["i"])[1]
                gaps = ref.row_gaps(rows, table.rows(keys), GAP_WRONG_SHAPE)
                gap_max = max(gap_max, float(gaps.max(initial=0.0)))
                off += int(np.count_nonzero(gaps > tol))
                k += 1
        final_off = UNTOUCHED_READ_BACK     # a read-back that failed
        if self._final is not None:
            final_off = 0
            for keys, rows, _version in self._final:
                gaps = ref.row_gaps(rows, table.rows(keys), GAP_WRONG_SHAPE)
                gap_max = max(gap_max, float(gaps.max(initial=0.0)))
                final_off += int(np.count_nonzero(gaps > tol))
        log(f"  psserve: replayed {len(updates)} updates over "
            f"{len(table.touched_keys())} rows; compared {len(samples)} "
            f"replies at their versions and "
            f"{sum(len(f[0]) for f in self._final or ())} rows read back")
        return [
            ("failed_calls", sum(1 for c in calls if not c["ok"])
             + self._stuck, 0),
            ("replies_not_compared", 0 if samples else 1, 0),
            ("wrong_shape", sum(1 for c in lookups
                                if not c.get("shape_ok", True)), 0),
            ("rows_gap_max", gap_max, tol),
            ("rows_off_snapshot", off, 0),
            ("final_rows_off", final_off, 0),
            ("updates_lost_or_doubled", lost_or_doubled, 0),
            ("replays_not_deduped", not_deduped, 0),
            ("dup_counter_off", abs(self._dups - len(resends)), 0),
            ("stale_reads", stale, 0),
        ]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._stop_program()
        except Exception as e:      # closing after a failure: report it
            log(f"  psserve: close: {type(e).__name__}: {e}")
