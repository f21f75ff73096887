"""HybridRunner — a model described by its configuration (ISSUE 32).

Serves a :class:`~brpc_tpu.models.runner.TransformerConfig` whose
``mixer_types`` and ``ffn_types`` name each layer's mixer and
feed-forward (MiniCPM-SALA's, GLM-4.7-Flash's, Jamba's and Nemotron 3's
kinds today); what a kind draws, computes, keeps and counts is its
record in :mod:`brpc_tpu.models.layers`.  Around them learned RMS norms,
per-head q/k norms, rotary positions, output gates, the muP scalings
where the family has them and an untied head, or the embedding as the
head where the family ties them; the vocabulary may be the chip's slice
of it.  The equations are those of
``benchmarks/harness/reference_sala.py``, ``reference_glm.py``,
``reference_jamba.py`` and ``reference_nemotron.py`` (the plain
references; the tier-1 tests hold this runner to them).

The cache is the store's :class:`~brpc_tpu.kvcache.layered.LayeredCache`:
persistent device arrays, one a kind of state (a kind the model has no
layer of is an array with no element), that the two jitted programs
here (``jit_runner_hybrid_step``, ``jit_runner_hybrid_prefill``) take
DONATED and return updated, fixed shapes throughout.

Position contract (the engine's): ``step(tok, pos)`` computes position
``pos - 1`` (the token newest in the sequence) and WRITES its K/V and
state; so prefill covers positions ``prefill_from .. len(prompt) - 2``
only: a recurrent state must see every position exactly once.

Precision: with ``param_dtype="bfloat16"`` weights and matmul inputs are
bfloat16 (one MXU pass), accumulation, norms, softmax, selection scores
and the recurrent state float32, cached K/V and compressed keys
bfloat16.  ``param_dtype="float32"`` (the CPU tests) multiplies at
``highest``.  ``control="low"`` is the benchmark's low-precision
control and nothing a deployment sets: everything the configuration
states in float32 and the program accumulates (every matmul's sum, the
residual stream, the lightning state, either scan state and the
convolution's tail) then holds bfloat16 values, and
the K/V and latent pages the values of an int8 cache (scale 1/16), the
router bfloat16.  ``control="drop"`` leaves the last chosen expert's
share out of every routed sum.
"""
from __future__ import annotations

import functools
import math
import threading
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from brpc_tpu import fault
from brpc_tpu.bvar import Adder
# ``layer_kinds``, ``layer_shapes``, ``moe_share`` and ``ffn_kernel_blocks``
# are this module's names too: callers import them from here
from brpc_tpu.models.layers import (  # noqa: F401
    _TRACING, A_LOG, A_LOG_U, BIAS, DT_BIAS, DT_RANGE, FFNS, MIXERS, ONES,
    ROUTER, SMALL_BIAS, _acc, _rms, _to_bf16, ffn_kernel_blocks, held,
    keeping, layer_kinds, layer_records, layer_shapes, moe_share,
    state_block)
from brpc_tpu.models.runner import ModelRunner, TransformerConfig

# a slot's "live" column of the step program's operand: its token comes
# from the host, or from the result of the step before on the device
LIVE, FED = 1, 2


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def top_shapes(cfg: TransformerConfig, head_gain: float = 1.0) -> dict:
    """The embedding, the final norm and, where the family does not tie
    it to the embedding, the head; in the order the init draws them."""
    out = {"emb": ((cfg.vocab, cfg.d_model), cfg.d_model)}
    if not cfg.tie_embeddings:
        out["head"] = ((cfg.vocab, cfg.d_model),
                       cfg.d_model / head_gain ** 2)
    out["norm_f"] = ((cfg.d_model,), None)
    return out


def init_hybrid_params(cfg: TransformerConfig, key=None) -> dict:
    """Seeded parameters: matrices normal(0, 1/fan_in) in
    ``cfg.param_dtype``, norm weights ``1 + 0.1 normal`` in float32,
    the head scaled by ``d_model / dim_model_base`` so that the muP
    division leaves logits of order one.  One jitted call a layer (the
    float32 draw of a whole stacked tensor would not fit beside 10 GB
    of weights)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    dt = jnp.dtype(cfg.param_dtype)

    def draw(key, shapes):
        ks = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, fan_in)) in zip(ks, shapes.items()):
            x = jax.random.normal(k, shape, jnp.float32)
            if fan_in is None:
                out[name] = 1.0 + 0.1 * x
            elif fan_in == A_LOG:
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
            elif fan_in == A_LOG_U:
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif fan_in == ONES:
                out[name] = jnp.ones(shape, jnp.float32)
            elif fan_in == DT_BIAS:
                lo, hi = (math.log(v) for v in DT_RANGE)
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                  lo, hi))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif fan_in == BIAS:
                out[name] = 0.1 * x
            elif fan_in == SMALL_BIAS:
                out[name] = 0.01 * x
            elif fan_in == ROUTER:
                out[name] = x / math.sqrt(shape[0])
            else:
                out[name] = (x / math.sqrt(fan_in)).astype(dt)
        return out

    ks = jax.random.split(key, cfg.n_layers + 1)
    head_gain = cfg.d_model / cfg.dim_model_base if cfg.dim_model_base \
        else 1.0
    kinds = layer_kinds(cfg)
    # built once a model at start-up, one program a kind of layer
    # brpc-check: allow(jit-hot-path)
    init = {kind: jax.jit(functools.partial(draw, shapes=shapes))
            for kind, shapes in [
        ("top", top_shapes(cfg, head_gain))]
            + [(k, layer_shapes(cfg, *k)) for k in sorted(set(kinds))]}
    top = init["top"](ks[0])
    top["layers"] = [init[kind](k) for kind, k in zip(kinds, ks[1:])]
    return top


def _logits(params, h, cfg):
    x = _rms(h, params["norm_f"], cfg.rms_eps)
    if cfg.dim_model_base:
        x = x / (cfg.d_model / cfg.dim_model_base)
    # the head is kept [vocab, d_model] like the embedding: the
    # contraction then runs over the minor dimension of both operands,
    # which is the layout the chip's compiler wants (a [d_model, vocab]
    # head is copied transposed, 0.6 GB, every step)
    w = params["emb"] if cfg.tie_embeddings else params["head"]
    if w.dtype == x.dtype:
        return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                   precision="highest")
    return jax.lax.dot_general(_to_bf16(x), w,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=x.dtype,
                               precision=jax.lax.Precision.DEFAULT)


@functools.cache
def _programs():
    """The two jitted programs, built at the first runner.  Neither
    reads or writes the K/V or the latent arena through XLA operations:
    on the chip ``cache_write``, ``page_keys``, ``latent_write`` and the
    attention kernels are Pallas calls (``ops.sparse_attention`` says
    why).  Each computes what its positions need before the layers, then
    goes through the held layers' records (``layers.layer_records``):
    the norm, the mixer's function, the residual add, the
    feed-forward's."""
    from brpc_tpu.ops.moe import ROW_TILE
    from brpc_tpu.ops.sparse_attention import KERNELS_PER_PAGE

    # ---- one decode position a slot --------------------------------------

    def step(params, kv, kc, state, packed, prev, latent=None, *, cfg,
             backend, control, logits_out):
        """``packed [S, 4 + MPs]`` int32: a slot's token, position, state
        row, whether it is live (0 no, ``LIVE``, or ``FED``: live, and
        its token is row 0 of ``prev``, the step before's result, not
        ``packed[:, 0]``), then its page table; a model with latent
        layers has two columns more ahead of the table, the keys at the
        head of it that the slot shares with the leader row and that
        row (``ops.latent_attention.shared_run``).  ONE operand made on the
        host and one result (``[3, S]`` float32: next token, its
        log-probability, blocks selected; a model with expert layers
        adds two rows whose first value is the held experts its layers
        hit, and the assignments that fell to them) a
        step: every device array a
        step makes and drops costs the engine thread a hand-off of the
        interpreter lock under load (PERF.md section 7, entry 15).
        ``FED`` is data: a step dispatched before the one ahead of it
        was fetched is the same program as any other."""
        _TRACING.control, _TRACING.backend = control, backend
        positions, rows = packed[:, 1], packed[:, 2]
        tokens = jnp.where(packed[:, 3] == FED,
                           prev[0].astype(jnp.int32), packed[:, 0])
        active = packed[:, 3] > 0
        tables = packed[:, 4 + 2 * bool(cfg.n_latent):]
        t_page = kv.shape[4]
        n_arena = kv.shape[3]
        stride = t_page // KERNELS_PER_PAGE
        s_n = tokens.shape[0]
        rs = cfg.residual_scale
        qpos = jnp.maximum(positions - 1, 0)
        page = jnp.take_along_axis(tables, (qpos // t_page)[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(active & (page >= 0), page, n_arena)
        # the kernel this position completes, if it does; it lives in
        # the page its LAST key lies in (this position's): the page
        # where it starts may be a shared prefix page, and what follows
        # that differs by sequence
        j = (qpos + 1) // stride - 2
        done = active & ((qpos + 1) % stride == 0) & (j >= 0)
        jc = jnp.maximum(j, 0)
        page_a = jnp.take_along_axis(
            tables, (jc // KERNELS_PER_PAGE)[:, None], axis=1)[:, 0]
        win_at = (jc % KERNELS_PER_PAGE) * stride
        h = cfg.scale_emb * params["emb"][tokens].astype(jnp.float32)
        # where the expert blocks take the ``expert_ffn`` kernel, the
        # row tiles it visits for them
        kernel = bool(ffn_kernel_blocks(cfg, backend))
        c = SimpleNamespace(
            cfg=cfg, control=control, backend=backend, rs=rs,
            round_state="bfloat16" if control == "low" else None,
            kv=kv, kc=kc, state=state, latent=latent, qpos=qpos,
            live=active, rows=rows, tables=tables, packed=packed, page=page,
            done=done, jc=jc, page_a=page_a, win_at=win_at, t_page=t_page,
            n_arena=n_arena, stride=stride,
            n_sel=jnp.zeros((s_n,), jnp.float32), kernel=kernel,
            n_moe=jnp.zeros((2,), jnp.int32), n_tile=0, hits=lambda hit: hit)
        for p, (mixer, ffn, i) in zip(params["layers"], layer_records(cfg)):
            if mixer.decode:
                x = _rms(h, p["norm1"], cfg.rms_eps)
                h = _acc(control, h + rs * mixer.decode(p, x, i, c))
            if ffn.apply:
                h = ffn.apply(p, h, c)
        logits = _logits(params, h, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logprob = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), nxt[:, None], axis=1)[:, 0]
        out = [nxt.astype(jnp.float32), logprob,
               c.n_sel / max(1, cfg.n_sparse)]
        if cfg.n_moe:
            out += [jnp.zeros((s_n,), jnp.float32).at[0].set(v)
                    for v in c.n_moe.astype(jnp.float32)]
        if kernel and s_n > 1:
            # the kernel's rows ride a spare column of the experts' row
            out[3] = out[3].at[1].set(
                (c.n_tile * ROW_TILE).astype(jnp.float32))
        return (jnp.stack(out), logits if logits_out else None, c.kv, c.kc,
                c.state, c.latent)

    # ---- one prefill chunk of one sequence -------------------------------

    def prefill(params, kv, kc, state, packed, latent=None, *, cfg, backend,
                control, logits_out, max_pages):
        """``packed`` int32: the chunk's start, its valid length, the
        sequence's state row, its page table ``[MPs]``, then the
        bucket's tokens (``MPs`` is the store's, static)."""
        _TRACING.control, _TRACING.backend = control, backend
        mps = max_pages
        start, n_valid, row = packed[0], packed[1], packed[2]
        table, tokens = packed[3:3 + mps], packed[3 + mps:]
        t_page = kv.shape[4]
        n_arena = kv.shape[3]
        stride = t_page // KERNELS_PER_PAGE
        n = tokens.shape[0]
        rs = cfg.residual_scale
        off = jnp.arange(n, dtype=jnp.int32)
        qpos = start + off
        valid = off < n_valid
        # the chunk's pages: it starts at a page boundary
        n_pg = n // t_page
        pg_l = start // t_page + jnp.arange(n_pg, dtype=jnp.int32)
        page = table[jnp.clip(pg_l, 0, mps - 1)]
        page = jnp.where((jnp.arange(n_pg) * t_page < n_valid)
                         & (page >= 0), page, n_arena)
        page_before = table[jnp.clip(start // t_page - 1, 0, mps - 1)]
        # kernels this chunk completes: j = start/stride - 1 + i, each
        # kept in the page its last key lies in
        n_k = n // stride
        jk = start // stride - 1 + jnp.arange(n_k, dtype=jnp.int32)
        k_done = (jk >= 0) & (stride * jk + 2 * stride - 1
                              < start + n_valid)
        jkc = jnp.maximum(jk, 0)
        k_page = table[jnp.clip((jkc + 1) // KERNELS_PER_PAGE, 0, mps - 1)]
        k_page = jnp.where(k_done & (k_page >= 0), k_page, n_arena)
        h = cfg.scale_emb * params["emb"][tokens].astype(jnp.float32)
        kernel = bool(ffn_kernel_blocks(cfg, backend))
        c = SimpleNamespace(
            cfg=cfg, control=control, backend=backend, rs=rs,
            round_state="bfloat16" if control == "low" else None,
            kv=kv, kc=kc, state=state, latent=latent, qpos=qpos,
            live=valid, row=row, n_valid=n_valid, table=table, page=page,
            n_pg=n_pg, page_before=page_before, n_k=n_k, jkc=jkc,
            k_page=k_page, t_page=t_page, n_arena=n_arena, stride=stride,
            n_sel=jnp.zeros((n,), jnp.float32), kernel=kernel,
            n_moe=jnp.zeros((), jnp.int32), n_tile=0,
            hits=lambda hit: hit[1])
        for p, (mixer, ffn, i) in zip(params["layers"], layer_records(cfg)):
            if mixer.prefill:
                x = _rms(h, p["norm1"], cfg.rms_eps)
                h = _acc(control, h + rs * mixer.prefill(p, x, i, c))
            if ffn.apply:
                h = ffn.apply(p, h, c)
        # blocks selected a sparse layer; a model with expert layers
        # adds the assignments that fell to the held experts (and, where
        # they take the ``expert_ffn`` kernel, the rows it multiplied)
        counts = c.n_sel.sum() / max(1, cfg.n_sparse)
        if cfg.n_moe:
            counts = [counts, c.n_moe.astype(jnp.float32)]
            if kernel:
                counts.append((c.n_tile * ROW_TILE).astype(jnp.float32))
            counts = jnp.stack(counts)
        return (counts, _logits(params, h, cfg) if logits_out else None,
                c.kv, c.kc, c.state, c.latent)

    def named(fn, scope, *more_statics):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(scope), \
                    jax.default_matmul_precision("highest"):
                return fn(*args, **kw)
        scoped.__name__ = scoped.__qualname__ = scope.replace(".", "_")
        return jax.jit(scoped, static_argnames=(
            "cfg", "backend", "control", "logits_out") + more_statics,
            donate_argnames=("kv", "kc", "state", "latent"))
    return {"step": named(step, "runner.hybrid_step"),
            "prefill": named(prefill, "runner.hybrid_prefill", "max_pages")}


# ---------------------------------------------------------------------------
# the store and the runner
# ---------------------------------------------------------------------------

def layered_spec(cfg: TransformerConfig, state_rows: int):
    from brpc_tpu.kvcache.layered import LayeredSpec
    recurrent = held(cfg.mixer_types, *keeping("state"))
    return LayeredSpec(
        n_sparse=cfg.n_kv_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_linear=recurrent,
        state_rows=int(state_rows) if recurrent else 0,
        state_layer_shape=state_block(cfg), n_latent=cfg.n_latent,
        latent_dim=cfg.latent_dim, compressed=bool(cfg.n_sparse))


def make_layered_store(cfg: TransformerConfig, *, cache_pages: int,
                       state_rows: int = 0, page_tokens: int = 0,
                       device=None, name: str = "kv"):
    """A :class:`KVCacheStore` for a described architecture: page ids,
    refcounts and the radix tree as ever, ``cache_pages`` pages whose
    K/V (sparse- and full-attention layers), compressed keys, latent
    rows (latent-attention layers) and ``state_rows`` recurrent-state
    rows (lightning, Mamba or Mamba-2 layers) live in the store's
    :class:`LayeredCache`.  A page
    holds ``cfg.sparse_block`` tokens where the model selects blocks
    (one selection block a page), else ``page_tokens``.  The pool's own
    blocks hold the 4-byte token-id stand-in only."""
    from brpc_tpu.ici.block_pool import BlockPool
    from brpc_tpu.kvcache import KVCacheStore
    pt = cfg.sparse_block if cfg.n_sparse else int(page_tokens)
    if pt <= 0 or (page_tokens and pt != page_tokens):
        raise ValueError(
            f"page_tokens {page_tokens}: a model with sparse-attention "
            f"layers pages by its selection block ({cfg.sparse_block}), "
            f"any other states its page")
    if cfg.n_sparse and (cfg.sparse_kernel != 2 * cfg.sparse_stride
                         or pt != 4 * cfg.sparse_stride):
        raise ValueError(
            "the cache's compressed-key index needs kernel_size = 2 x "
            "kernel_stride and block_size = 4 x kernel_stride")
    per_block = 64
    blocks = -(-int(cache_pages) // per_block)
    pool = BlockPool(device, classes=(per_block * pt * 4,),
                     blocks_per_class=blocks)
    return KVCacheStore(pool, device, page_bytes=pt * 4, page_tokens=pt,
                        max_blocks=blocks, vector_kv=True,
                        layers=layered_spec(cfg, state_rows), name=name)


class HybridRunner(ModelRunner):
    """See the module docstring.  ``control`` is the benchmark's
    low-precision control (``"low"``) and otherwise empty."""

    wants_pages = True
    has_prefill = True
    feeds_tokens = True        # a step can take tokens from the one before
    chunked_prefill = True     # the engine cuts a long suffix to buckets
    kv_bytes_per_token = 0     # the engine writes no K/V rows for it

    def __init__(self, params: dict, cfg: TransformerConfig, *, store=None,
                 backend: Optional[str] = None, control: str = "",
                 name: str = "model"):
        from brpc_tpu.ici.mesh import ensure_compile_cache
        ensure_compile_cache()
        if not cfg.mixer_types:
            raise ValueError("HybridRunner serves a described "
                             "architecture (cfg.mixer_types)")
        records = layer_records(cfg)     # a kind with no record raises
        self.cfg = cfg
        self.params = params
        self.name = name
        self.store = None
        self._backend = backend
        self._control = control
        self._mu = threading.Lock()
        self._fns = _programs()
        self._kernel_blocks = ffn_kernel_blocks(cfg, backend)
        self._table_cache: dict = {}  # seq id -> (table's key, arena indices)
        self._rows: list = []         # the last step's rows of it, a slot
        self._run = None              # (leader, keys shared a slot) of them
        self._no_prev: dict = {}      # slots -> zeros [3, S] on the device
        # prefill chunks dispatched and not counted yet: the engine
        # thread does not wait for a prefill to run (see prefill)
        self._uncounted: list = []
        safe = "".join(c if c.isalnum() else "_" for c in name)
        # every kind's counters (``layers``: what each counts), those of
        # a kind the model has no layer of left at zero
        names = dict(n for rec in (*MIXERS.values(), *FFNS.values())
                     for n in rec.counters)
        for attr, suffix in names.items():
            setattr(self, attr, Adder(f"runner_{safe}_{suffix}"))
        self._bvar_names = [f"runner_{safe}_{n}" for n in names.values()]
        # the count function of each kind the model has, once
        self._counts = list(dict.fromkeys(
            rec.count for *pair, _ in records for rec in pair if rec.count))
        if store is not None:
            self.bind(store)

    def _statics(self) -> dict:
        return {"cfg": self.cfg, "backend": self._backend,
                "control": self._control}

    def bind(self, store) -> None:
        if store is None or getattr(store, "layers", None) is None:
            raise ValueError("HybridRunner needs a layered KVCacheStore "
                             "(make_layered_store)")
        with self._mu:
            if self.store is store:
                return
            if self.store is not None:
                raise ValueError("runner already bound to a store")
            if self.cfg.n_sparse \
                    and store.page_tokens != self.cfg.sparse_block:
                raise ValueError(
                    f"store pages hold {store.page_tokens} tokens, a "
                    f"selection block {self.cfg.sparse_block}")
            self.store = store

    # ---- helpers ----

    def _flat_tables(self, pages) -> np.ndarray:
        pages = np.asarray(pages, np.int32)
        flat = self.store.pagepool.flat_ids(pages.ravel().tolist())
        return np.asarray(flat, np.int32).reshape(pages.shape)

    def _slot_tables(self, pages, seqs) -> np.ndarray:
        """The step's page tables as arena indices.  A sequence's table
        only grows at its end while it decodes, so the translation of a
        slot's row is kept until the row's page count changes: 8 x 520
        lookups under the pool's lock every step were a millisecond of
        the engine thread, a step in 64 a page long is not."""
        pages = np.asarray(pages, np.int32)
        out = np.full(pages.shape, -1, np.int32)
        kept = self._table_cache
        live = {}
        for i, s in enumerate(seqs or ()):
            if s is None:
                continue
            # what a table changes by while it decodes: a page more, or
            # its tail page copied
            key = (len(s.pages), s.pages[-1].pid if s.pages else -1)
            hit = kept.get(s.seq_id)
            if hit is None or hit[0] != key:
                hit = (key, self._flat_tables(pages[i]))
            live[s.seq_id] = hit
            out[i] = hit[1]
        # the run the slots share is read off these rows (dispatch_step):
        # it stands while every slot holds the row it held
        rows = [live[s.seq_id][1] if s is not None else None
                for s in seqs or ()]
        if len(rows) != len(self._rows) or any(
                a is not b for a, b in zip(rows, self._rows)):
            self._rows, self._run = rows, None
        self._table_cache = live
        return out

    def _count(self, qpos, counts, dead: int, step=None) -> None:
        """Counters of the positions a program just computed, by the
        count function of each kind the model has (``layers``: ``step``
        is None for a prefill chunk, ``(handle, result)`` for a decode
        step)."""
        counts = np.atleast_1d(np.asarray(counts))
        for count in self._counts:
            count(self, qpos, counts, dead, step)

    # ---- the ModelRunner surface ----

    def prefill_cuts(self, seq) -> list:
        """Positions a prefill chunk must END at: where this sequence's
        state is to be snapshot."""
        b = self.store.snapshot_boundary(seq)
        return [b] if b else []

    def prefill(self, tokens, positions, pages, seq=None, n_valid=None,
                logits: bool = False):
        """One chunk: ``tokens`` bucket-padded, ``positions[0]`` its
        start (a whole number of pages), ``n_valid`` its real length.
        Never the prompt's last position: the first step computes
        it.  ``logits=True`` (tests) returns the chunk's logits."""
        if seq is None:
            raise ValueError("HybridRunner.prefill needs the KVSeq")
        start = int(positions[0])
        limit = len(seq.tokens) - 1 - start
        n = limit if n_valid is None else min(int(n_valid), limit)
        if n <= 0:
            return None
        if start % self.store.page_tokens:
            raise ValueError("a prefill chunk starts at a page boundary")
        lay = self.store.layers
        table = self._flat_tables(pages)
        packed = np.concatenate([
            np.asarray([start, n, seq.state_row], np.int32), table,
            np.asarray(tokens, np.int32)])
        with lay.lock:
            n_sel, out, lay.kv, lay.kc, lay.state, lay.latent = \
                self._fns["prefill"](
                    self.params, lay.kv, lay.kc, lay.state,
                    jnp.asarray(packed), lay.latent,
                    logits_out=bool(logits), max_pages=len(table),
                    **self._statics())
        self.store.mark_filled(seq, start + n)
        # counted when the next step dispatched completes: the chunk has
        # run by then, and ``float(n_sel)`` here would hold the engine
        # thread until the device has caught up with everything queued
        self._uncounted.append((start + np.arange(n), n_sel, len(tokens) - n))
        if start + n == self.store.snapshot_boundary(seq):
            self.store.take_snapshot(seq, start + n)
        return out

    def dispatch_step(self, tokens, positions, pages, seqs=None, prev=None,
                      fed=None, logits: bool = False):
        """Dispatch one step and return its handle (for
        :meth:`complete_step`) without waiting for the device.  Where
        ``fed[i]``, slot ``i``'s token is not ``tokens[i]`` but the one
        the step ``prev`` (a handle, not yet completed perhaps) made for
        it, read on the device."""
        if fault.ENABLED and fault.hit(
                "model.step_compute", runner=self.name) is not None:
            raise RuntimeError("injected model step-compute failure")
        lay = self.store.layers
        n = len(tokens)
        head = 4 + 2 * bool(self.cfg.n_latent)
        packed = np.empty((n, head + np.shape(pages)[1]), np.int32)
        packed[:, 0], packed[:, 1] = tokens, positions
        packed[:, 2], packed[:, 3] = lay.scratch_row, 0
        for i, s in enumerate(seqs or ()):
            if s is not None and s.state_row is not None:
                packed[i, 2], packed[i, 3] = s.state_row, LIVE
        live = packed[:, 3] > 0
        if fed is not None:
            packed[live & np.asarray(fed, bool), 3] = FED
        tables = packed[:, head:] = self._slot_tables(pages, seqs)
        if self.cfg.n_latent:
            from brpc_tpu.ops.latent_attention import page_visits, shared_run
            t = self.store.page_tokens
            seen = np.where(live, np.maximum(packed[:, 1], 1), 0)
            # found again only when a table row changed: between those a
            # slot's ``seen`` only grows, so what it shares stays shared
            if self._run is None:
                self._run = shared_run(tables, seen, t)
            packed[:, 5], packed[:, 4] = self._run
        if prev is not None:
            before = prev["out"]
        else:
            before = self._no_prev.get(n)
            if before is None:
                # placed as a step's own result is (committed, beside the
                # cache): the same program whether a step came before
                before = self._no_prev[n] = jax.device_put(
                    np.zeros((3 + 2 * bool(self.cfg.n_moe), n), np.float32),
                    lay.kv.sharding)
        with lay.lock:
            out, lg, lay.kv, lay.kc, lay.state, lay.latent = \
                self._fns["step"](
                    self.params, lay.kv, lay.kc, lay.state,
                    jnp.asarray(packed), before, lay.latent,
                    logits_out=bool(logits), **self._statics())
        owed, self._uncounted = self._uncounted, []
        handle = {"out": out, "logits": lg, "live": live, "seqs": seqs,
                  "positions": np.asarray(positions), "prefills": owed}
        if self.cfg.n_latent:
            # the pages the live slots' rows lie in, each once however
            # many slots share it: what a step must read of the cache
            used = [tables[i, :-(-int(seen[i]) // t)]
                    for i in np.flatnonzero(live)]
            handle["latent_pages"] = len(np.unique(np.concatenate(used))) \
                if used else 0
            handle["latent_visits"] = page_visits(seen, packed[:, 4], t)
        return handle

    def complete_step(self, handle):
        """Fetch a dispatched step's result and book its positions
        (materialised, counted) and the prefill chunks dispatched before
        it: ``(next tokens, None, their log-probabilities)``."""
        out = np.asarray(handle["out"])
        live, positions = handle["live"], handle["positions"]
        for i, s in enumerate(handle["seqs"] or ()):
            if live[i] and not s.retired:
                self.store.mark_filled(s, int(positions[i]))
        for chunk in handle["prefills"]:
            self._count(*chunk)
        counts = [out[2][live].sum()]
        if self.cfg.n_moe:
            counts.append(out[4][0])
            if self._kernel_blocks and out.shape[1] > 1:
                counts.append(out[3][1])
        self._count(positions[live] - 1, counts,
                    len(live) - int(live.sum()), (handle, out))
        return out[0].astype(np.int32), None, out[1]

    def step(self, tokens, positions, pages, seqs=None):
        nxt, rows, _ = self.complete_step(
            self.dispatch_step(tokens, positions, pages, seqs))
        return nxt, rows

    def step_logits(self, tokens, positions, pages, seqs=None):
        """The step's logits ``[slots, vocab]`` (it advances the cache
        as :meth:`step` does)."""
        handle = self.dispatch_step(tokens, positions, pages, seqs,
                                    logits=True)
        self.complete_step(handle)
        return handle["logits"]

    def verify(self, tokens, positions, tables, base_len, mask):
        raise NotImplementedError(
            "speculative verify needs a copy of the recurrent state a "
            "draft branch (ROADMAP R8)")

    def close(self) -> None:
        from brpc_tpu.bvar.variable import find_exposed
        for n in self._bvar_names:
            v = find_exposed(n)
            if v is not None:
                v.hide()
