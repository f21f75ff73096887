"""What ``test_hybrid_runner.py`` and ``test_glm_runner.py`` share: a
``HybridRunner`` over its store driven by hand as the engine drives it,
seeded tokens, and three requests through a ``DecodeEngine``."""
import threading

import jax
import jax.monitoring
import numpy as np

from brpc_tpu.models.hybrid import (HybridRunner, _programs,
                                    init_hybrid_params, make_layered_store)
from brpc_tpu.serving import DecodeEngine

T = 16               # tokens a page (MiniCPM-SALA's toy selection block)
MAX_PAGES = 16


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def as_drawn(params, cfg):
    """A float32 draw at ``cfg``'s types: the seeded draw is float32 and
    cast leaf by leaf, so this IS its draw, not compiled again."""
    return jax.tree_util.tree_map(
        lambda x, t: x.astype(t.dtype), params,
        jax.eval_shape(lambda: init_hybrid_params(cfg)))


def draw_compiles(cfg) -> list:
    """The backend compiles of a fresh seeded draw of ``cfg``."""
    names = []

    def on(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name", "?"))
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        jax.block_until_ready(init_hybrid_params(cfg, jax.random.PRNGKey(5)))
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    return names


def program_entries(before=None) -> dict:
    """Compiled entries of the two programs (every model's), or what was
    added to them since ``before``."""
    now = {k: f._cache_size() for k, f in _programs().items()}
    return {k: n - (before or {}).get(k, 0) for k, n in now.items()}


class Rig:
    def __init__(self, cfg, params, name, pages=64, rows=6, backend=None):
        self.store = make_layered_store(cfg, cache_pages=pages,
                                        state_rows=rows, page_tokens=T,
                                        name=name)
        self.runner = HybridRunner(params, cfg, store=self.store, name=name,
                                   backend=backend)

    def table(self, seq):
        out = np.full((MAX_PAGES,), -1, np.int32)
        ids = seq.page_ids()
        out[:len(ids)] = ids
        return out

    def prefill(self, seq, tokens, chunk=32):
        """Positions prefill_from .. len - 2, as the engine cuts them;
        returns the logits of those positions."""
        out = []
        at, end = seq.prefill_from, len(tokens) - 1
        cuts = [c for c in self.runner.prefill_cuts(seq) if at < c < end]
        for cut in cuts + [end]:
            while at < cut:
                k = min(chunk, cut - at)
                pad = np.zeros((chunk,), np.int32)
                pad[:k] = tokens[at:at + k]
                lg = self.runner.prefill(pad, at + np.arange(chunk),
                                         self.table(seq), seq=seq,
                                         n_valid=k, logits=True)
                out.append(np.asarray(lg)[:k])
                at += k
        return np.concatenate(out) if out else np.zeros((0, 256))

    def decode(self, seq, tokens, upto, slot=1):
        """Teacher-forced steps for positions len(seq) - 1 .. upto - 1;
        returns their logits."""
        out = []
        for pos in range(len(seq.tokens), upto + 1):
            tok = np.zeros((4,), np.int32)
            p = np.zeros((4,), np.int32)
            tok[slot], p[slot] = tokens[pos - 1], pos
            tabs = np.full((4, MAX_PAGES), -1, np.int32)
            tabs[slot] = self.table(seq)
            seqs = [None] * 4
            seqs[slot] = seq
            lg = self.runner.step_logits(tok, p, tabs, seqs=seqs)
            out.append(np.asarray(lg)[slot])
            if pos < upto:
                self.store.extend(seq, tokens[pos])
        return np.stack(out)

    def close(self):
        self.runner.close()
        self.store.close()


class Take:
    """Where a ``DecodeEngine`` request's tokens go."""

    def __init__(self):
        self.tokens, self.logprobs, self.err = [], [], "UNSET"
        self.done = threading.Event()

    def emit(self, tok, lp):
        self.tokens.append(tok)
        self.logprobs.append(lp)

    def on_done(self, err):
        self.err = err
        self.done.set()


class Gated(HybridRunner):
    """Holds the engine inside the first admission until the test has
    queued every request: who rides which step is then the same in every
    run, and so is the order in which pages are taken."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.gate, self.parked = threading.Event(), threading.Event()

    def prefill(self, *a, **kw):
        self.parked.set()
        assert self.gate.wait(60)
        return super().prefill(*a, **kw)


class InARow(Gated):
    feeds_tokens = False      # the engine completes each step it dispatches


def serve(model, cls, name, compiles):
    """Three requests through a ``DecodeEngine``: prompts of 40, 44 and 47
    tokens that decode across position 48, a page boundary (16-token
    pages) and MiniCPM-SALA's switch from the dense to the sparse branch
    (``dense_len`` 48), ending by count on different steps; ``compiles``
    the caller's growing list of programs compiled."""
    cfg, _ref_cfg, params = model
    store = make_layered_store(cfg, cache_pages=64, state_rows=8,
                               page_tokens=T, name=name)
    runner = cls(params, cfg, store=store, name=name)
    engine = DecodeEngine(runner=runner, num_slots=4, store=store,
                          max_pages_per_slot=MAX_PAGES,
                          prefill_buckets=(16, 32), name=name)
    try:
        takes = [Take() for _ in range(3)]
        for k, (n, new, t) in enumerate(zip((40, 44, 47), (14, 9, 12),
                                            takes)):
            engine.submit(tokens_of(n, seed=70 + k), new, t.emit, t.on_done,
                          logprobs=True)
            if k == 0:      # the engine stays inside this admission
                assert runner.parked.wait(20)
        # what compiles from here on compiles inside the steps
        seen = len(compiles)
        runner.gate.set()
        for t in takes:
            assert t.done.wait(120) and t.err is None
        assert engine.join_idle(20)
        stats = engine.stats()
        lay = store.layers
        with lay.lock:
            arrays = [np.asarray(x) for x in (lay.kv, lay.kc, lay.state)]
        return {"tokens": [t.tokens for t in takes],
                "logprobs": [t.logprobs for t in takes],
                "arrays": arrays, "steps": stats["steps"],
                "ahead": stats["steps_ahead"],
                "compiled": compiles[seen:],
                "counters": (runner.sparse_positions.get_value(),
                             runner.dense_positions.get_value(),
                             runner.sparse_selected.get_value())}
    finally:
        engine.close()
        runner.close()
        store.close()
