"""DataParallelTrainer — N workers training THROUGH the PS wire
(ISSUE 17).

The training loop is the seed example (examples/embedding_server.py)
grown into a real multi-worker trainer: every gather rides
``PS.Lookup`` (batched, tensorframe wire), every sparse gradient rides
``PS.Update`` carrying an :class:`~brpc_tpu.train.OptimizerSpec` so
the scatter AND the momentum/Adam slot step run fused ON the shard,
dense parameters live in the service (``Pull``/``Push`` per step), and
a periodic Pull-based eval proves loss decreases
through the service — the model the trainer ever sees is the one the
shards hold.

Worker coordination is BOUNDED STALENESS: worker w may start step s
only while ``s - min(steps completed by any worker) <= max_lag`` —
``max_lag=0`` is synchronous lockstep (a barrier per step), larger
lags trade gradient staleness for stall immunity.  The gate is a
condition variable over the progress vector, so a dead worker is
excused (marked complete) rather than wedging the fleet.

Update waves heal like any PS client: a failed wave re-issues with its
``update_token``, so partitions that already applied DEDUP — the fused
optimizer's applied-id discipline means a retried wave can never
double-step momentum.  Fault site ``train.update_wave`` injects wave
failures (chaos scenario 18 kills a live shard instead).
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from brpc_tpu import errors, fault
from brpc_tpu.bvar import Adder
from brpc_tpu.butil.lockprof import InstrumentedLock
from brpc_tpu.train.optimizer import OptimizerSpec

WAVES = Adder("train_waves")
WAVE_RETRIES = Adder("train_wave_retries")
EVALS = Adder("train_evals")


class DataParallelTrainer:
    """N worker threads pulling minibatches, computing grads locally,
    and streaming PS.Update waves under bounded-staleness gating."""

    def __init__(self, client, cfg=None, *, n_workers: int = 2,
                 steps: int = 8,
                 optimizer: Optional[OptimizerSpec] = None,
                 max_lag: int = 1,
                 sync: bool = False, lr_dense: float = 0.5,
                 eval_every: int = 0, wave_max_retry: int = 4,
                 retry_backoff_s: float = 0.05, arbiter=None,
                 seed: int = 0, name: str = "trainer"):
        import jax
        import jax.numpy as jnp
        from brpc_tpu.models.parameter_server import (PSConfig, _block,
                                                      make_example_batch)
        self.client = client
        self.cfg = cfg or PSConfig(
            vocab=client.vocab, d_model=client.dim,
            d_ff=2 * client.dim, n_layers=2, seq=8, batch=4)
        if self.cfg.vocab != client.vocab or \
                self.cfg.d_model != client.dim:
            raise ValueError(
                f"cfg ({self.cfg.vocab}x{self.cfg.d_model}) does not "
                f"match the client's table "
                f"({client.vocab}x{client.dim})")
        self.n_workers = int(n_workers)
        self.steps = int(steps)
        self.optimizer = optimizer or OptimizerSpec(
            "sgdm", lr=0.5, momentum=0.5)
        self.max_lag = 0 if sync else int(max_lag)
        self.sync = bool(sync) or self.max_lag == 0
        self.lr_dense = float(lr_dense)
        self.eval_every = int(eval_every)
        self.wave_max_retry = int(wave_max_retry)
        self.retry_backoff_s = float(retry_backoff_s)
        self.arbiter = arbiter
        self.seed = int(seed)
        self.name = str(name)
        self._jax, self._jnp = jax, jnp
        self._make_batch = make_example_batch

        # bounded-staleness gate state
        self._cv = threading.Condition()
        self._progress = [0] * self.n_workers
        self._stop = False
        self._errors: list = []
        self._mu = InstrumentedLock("train.trainer")
        self.n_waves = 0
        self.n_wave_retries = 0
        self.n_io_retries = 0
        self.n_paced = 0
        self.loss_history: list = []
        self.step_losses: list = []

        # the seed model's loss over gathered rows + dense params —
        # jitted ONCE here (never per call)
        def loss_from_rows(rows, dense, targets):
            x = rows.astype(jnp.bfloat16)

            def body(x, layer):
                wqk, wup, wdown = layer
                return _block(x, wqk, wup, wdown), None

            d = {k: v.astype(jnp.bfloat16) for k, v in dense.items()}
            x, _ = jax.lax.scan(body, x,
                                (d["w_qk"], d["w_up"], d["w_down"]))
            logits = (x @ d["w_out"]).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return -jnp.mean(ll)

        self._loss_fn = jax.jit(loss_from_rows)
        self._grad_fn = jax.jit(
            jax.value_and_grad(loss_from_rows, argnums=(0, 1)))
        self._dense_names: list = []
        # fixed held-out eval batch (its own key, never trained on)
        self._eval_tokens, self._eval_targets = make_example_batch(
            self.cfg, key=jax.random.PRNGKey(self.seed + 104729))

    # ---- model seeding (the fleet holds the model; seed it first) ----

    @staticmethod
    def model_init(cfg, seed: int = 0) -> tuple:
        """(embed0, dense0) for seeding the shard fleet: build shards
        with ``table=embed0`` and let :meth:`seed_dense` push dense."""
        import jax
        from brpc_tpu.models.parameter_server import init_params
        params = init_params(cfg, key=jax.random.PRNGKey(seed))
        embed = np.asarray(params["embed"], np.float32)
        dense = {k: np.asarray(v, np.float32)
                 for k, v in params.items() if k != "embed"}
        return embed, dense

    def seed_dense(self, dense: dict) -> None:
        """Push the dense (non-embedding) params into the service —
        after this the trainer has NO local copy of the model."""
        for k, v in dense.items():
            self.client.push(k, np.asarray(v, np.float32))
        self._dense_names = sorted(dense)

    def _clone_client(self, w: int):
        """One PSClient per worker: read-your-writes is a PER-CLIENT
        contract (a lookup must observe every update THIS client got
        acked), so workers sharing one client would count each other's
        in-flight writes as stale reads.  update_ids come from a
        module-global sequence, so clones never collide."""
        c = self.client
        if getattr(c, "_pc", None) is None:
            return c        # lowered/ICI backend: no wire, no clone
        from brpc_tpu.psserve import PSClient
        return PSClient(c._pc, vocab=c.vocab, dim=c.dim,
                        n_shards=c.n_shards, timeout_ms=c.timeout_ms,
                        max_retry=c.max_retry, serializer=c.serializer,
                        ici=c._ici_mode, table_name=c.table_name,
                        name=f"{c.name}_w{w}")

    # ---- bounded-staleness gate ----

    def _gate(self, w: int, s: int) -> None:
        with self._cv:
            while not self._stop and \
                    s - min(self._progress) > self.max_lag:
                self._cv.wait(0.2)

    def _advance(self, w: int) -> None:
        with self._cv:
            self._progress[w] += 1
            self._cv.notify_all()

    def _excuse(self, w: int) -> None:
        """A dead worker must not wedge the gate: mark it complete."""
        with self._cv:
            self._progress[w] = self.steps
            self._cv.notify_all()

    # ---- the update wave ----

    def _io_retry(self, fn):
        """Bounded retry with backoff for the worker's NON-wave I/O
        (pull/lookup/push).  The wave already heals itself via
        update_token replay; the read path needs the same patience so a
        shard restart mid-run (chaos scenario 18) costs a few retries,
        not a dead worker.  Reads are idempotent and pushes carry their
        own update_id through the partition channel's retry, so a
        replay here never double-applies."""
        for attempt in range(self.wave_max_retry + 1):
            try:
                return fn()
            except errors.RpcError:
                with self._mu:
                    self.n_io_retries += 1
                if attempt >= self.wave_max_retry:
                    raise
                time.sleep(self.retry_backoff_s * (attempt + 1))

    def _send_wave(self, cli, w: int, s: int, keys: np.ndarray,
                   grads: np.ndarray) -> None:
        """One PS.Update wave with partition-retry healing: a failed
        fan-out replays the SAME logical update via its update_token,
        so partitions that already applied dedup instead of
        double-stepping momentum."""
        tok = None
        for attempt in range(self.wave_max_retry + 1):
            if self.arbiter is not None:
                paced = self.arbiter.admit_wave()
                if paced:
                    with self._mu:
                        self.n_paced += 1
            try:
                if fault.ENABLED and fault.hit(
                        "train.update_wave", worker=w, step=s,
                        attempt=attempt) is not None:
                    raise errors.RpcError(
                        errors.EINTERNAL,
                        "injected train.update_wave fault")
                cli.update(keys, grads, update_token=tok,
                           optimizer=self.optimizer)
                with self._mu:
                    self.n_waves += 1
                WAVES.add(1)
                return
            except errors.RpcError as e:
                # keep (or adopt) the token: partitions that acked the
                # failed attempt will dedup the replay
                tok = getattr(e, "update_token", tok)
                with self._mu:
                    self.n_wave_retries += 1
                WAVE_RETRIES.add(1)
                if attempt >= self.wave_max_retry:
                    raise
                time.sleep(self.retry_backoff_s * (attempt + 1))

    # ---- eval (Pull-based: the model scored is the SERVICE's) ----

    def eval_loss(self) -> float:
        jnp = self._jnp
        dense = {k: jnp.asarray(self.client.pull(k))
                 for k in self._dense_names}
        keys = np.asarray(self._eval_tokens).reshape(-1).astype(np.int64)
        rows = self.client.lookup(keys).reshape(
            self.cfg.batch, self.cfg.seq, self.cfg.d_model)
        loss = float(self._loss_fn(jnp.asarray(rows), dense,
                                   self._eval_targets))
        with self._mu:
            self.loss_history.append(loss)
        EVALS.add(1)
        return loss

    # ---- the worker loop ----

    def _worker(self, w: int) -> None:
        jax, jnp = self._jax, self._jnp
        cli = self._worker_clients[w]
        try:
            for s in range(self.steps):
                self._gate(w, s)
                if self._stop:
                    return
                tokens, targets = self._make_batch(
                    self.cfg, key=jax.random.PRNGKey(
                        self.seed * 7919 + w * 104729 + s))
                keys = np.asarray(tokens).reshape(-1).astype(np.int64)
                dense = {k: jnp.asarray(self._io_retry(
                    lambda k=k: cli.pull(k)))
                    for k in self._dense_names}
                rows = self._io_retry(lambda: cli.lookup(keys)).reshape(
                    self.cfg.batch, self.cfg.seq, self.cfg.d_model)
                loss, (g_rows, g_dense) = self._grad_fn(
                    jnp.asarray(rows), dense, targets)
                self._send_wave(
                    cli, w, s, keys,
                    np.asarray(g_rows, np.float32).reshape(
                        -1, self.cfg.d_model))
                for k in self._dense_names:
                    self._io_retry(lambda k=k: cli.push(
                        k, np.asarray(-self.lr_dense * g_dense[k],
                                      np.float32)))
                with self._mu:
                    self.step_losses.append((w, s, float(loss)))
                self._advance(w)
                if self.eval_every and w == 0 and \
                        (s + 1) % self.eval_every == 0:
                    self.eval_loss()
        except BaseException as e:
            with self._mu:
                self._errors.append((w, e))
            self._excuse(w)

    def run(self) -> dict:
        """Train to completion; returns the report.  Raises the first
        worker error AFTER every worker has stopped (the gate excuses
        dead workers, so the rest drain normally)."""
        if not self._dense_names:
            raise RuntimeError("call seed_dense() before run() — the "
                               "service must hold the dense params")
        t0 = time.monotonic()
        self.eval_loss()        # the "before" point of the loss proof
        self._worker_clients = [self._clone_client(w)
                                for w in range(self.n_workers)]
        threads = [threading.Thread(
            target=self._worker, args=(w,),
            name=f"{self.name}_w{w}", daemon=True)
            for w in range(self.n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.eval_loss()
        elapsed = time.monotonic() - t0
        with self._mu:
            if self._errors:
                raise self._errors[0][1]
            return {
                "optimizer": self.optimizer.to_wire(),
                "workers": self.n_workers,
                "steps": self.steps,
                "steps_done": int(sum(self._progress)),
                "waves": self.n_waves,
                "wave_retries": self.n_wave_retries,
                "io_retries": self.n_io_retries,
                "paced_waves": self.n_paced,
                "max_lag": self.max_lag,
                "sync": self.sync,
                "elapsed_s": elapsed,
                "updates_per_s": self.n_waves / max(elapsed, 1e-9),
                "loss_first": self.loss_history[0],
                "loss_final": self.loss_history[-1],
                "loss_history": list(self.loss_history),
                "stale_reads": self.stale_reads(),
            }

    def stale_reads(self) -> int:
        """RYW violations summed across the shared client and every
        per-worker clone (the chaos-18 invariant reads this)."""
        clis = {id(self.client): self.client}
        for c in getattr(self, "_worker_clients", ()):
            clis[id(c)] = c
        return sum(c.n_stale_reads for c in clis.values())

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()

    def stats(self) -> dict:
        with self._mu:
            return {
                "name": self.name,
                "waves": self.n_waves,
                "wave_retries": self.n_wave_retries,
                "io_retries": self.n_io_retries,
                "paced_waves": self.n_paced,
                "progress": list(self._progress),
                "evals": len(self.loss_history),
            }
