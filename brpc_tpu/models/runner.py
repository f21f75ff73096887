"""ModelRunner — real model serving over the paged KV cache (ISSUE 10).

PRs 2–9 built the serving stack around two ad-hoc model protocols: the
engine's 2-arg/3-arg ``step_fn``/``prefill_fn`` and the batcher's
1-arg/2-arg ``batch_fn``, all driven with token ids standing in for KV.
This module replaces them with ONE interface and ships the first model
that actually uses the paged HBM layout:

  :class:`ModelRunner`       the interface: ``prefill(tokens, positions,
                             pages)`` / ``step(tokens, positions, pages)``
                             — fixed shapes, one compile per bucket, the
                             engine's trace-counter discipline unchanged;
  :class:`LegacyFnRunner`    the adapter wrapping the old fn protocols
                             byte-for-byte (required-positional
                             detection, jnp conversion, pass_page_table
                             override), so every existing test and the
                             pure-token harness keep passing unmodified;
  :class:`TransformerRunner` a small real transformer (GQA attention +
                             gelu MLP, RMS-norm, tied embeddings, greedy
                             decode) whose K/V live IN the KV cache's
                             pages: prefill writes each layer's suffix
                             K/V through ``KVCacheStore.write_kv`` (the
                             PagePool splice path — COW and refcounts
                             apply) then attends over the page table
                             with :func:`~brpc_tpu.ops.paged_attention`;
                             decode steps attend over the arena plus the
                             position's in-flight K/V (the self key) and
                             return packed K/V rows the engine splices
                             back — so prefix reuse, COW forks, radix
                             eviction and crash recovery all operate on
                             REAL attention state.

Position/materialization contract (the whole stack hinges on it):

  * a sequence at ``position p`` has tokens 0..p-1 appended and REAL
    K/V materialized for positions 0..p-2 at minimum (``seq.kv_filled``);
  * ``step(tok=t_{p-1}, pos=p)`` recomputes position p-1's hidden state
    (embedding + per-layer q/k/v), attends over arena keys 0..p-2 PLUS
    its own in-flight k/v, and returns (next token, position p-1's
    packed K/V rows) — the engine writes the rows before extending, so
    the NEXT step reads them from the arena;
  * prefill covers suffix positions f..n-1 write-then-attend per layer:
    layer l's K/V are spliced into the pages FIRST, then the layer
    attends over the page table (cached prefix pages + just-written
    suffix) with per-row causal lengths.  Cold (f=0) and warm (f>0)
    prefill therefore run the SAME kernel over the SAME fixed arena
    shapes — prefix reuse changes which pages already hold bytes, not
    the compute path — which is what makes prefill-skip produce
    identical tokens to cold prefill.

Sharding: parameters place over an ICI ``tp`` mesh axis with
``NamedSharding`` (:func:`place_runner_params` — q/k/v/o projections and
the MLP shard on the head/ff dim, embeddings replicate) and the jitted
step partitions under GSPMD exactly like the pjit pattern in
SNIPPETS.md [1]/[3]; a 1-device mesh (the CPU tier-1 path) is the
degenerate case of the same code.
"""
from __future__ import annotations

import functools
import math
import threading
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import numpy as np

from brpc_tpu import fault
from brpc_tpu.models.layers import (ATTN, LINEAR, MAMBA, MAMBA2, MIXERS, MLA,
                                    ROUTED, SPARSE, held, keeping)

DEFAULT_PREFILL_BUCKETS = (16, 64, 256, 1024, 4096)


# ---------------------------------------------------------------------------
# the interface + legacy adapter
# ---------------------------------------------------------------------------

class ModelRunner:
    """The model interface the serving stack drives (see module
    docstring).  ``wants_pages`` tells the engine to gather per-slot
    page tables; ``kv_bytes_per_token`` > 0 means the runner produces
    REAL packed K/V rows (the engine writes step rows via
    ``KVCacheStore.write_kv``; prefill writes its own, layer by layer);
    ``has_prefill`` gates the engine's prefill stage."""

    wants_pages: bool = False
    kv_bytes_per_token: int = 0
    has_prefill: bool = False
    # the runner's step can take a slot's token on the device from the
    # step dispatched before it (``dispatch_step(prev=, fed=)``), so the
    # engine may dispatch a step before it has fetched the one ahead
    feeds_tokens: bool = False
    name: str = "runner"

    def bind(self, store) -> None:
        """Called by the engine at construction with its KV store (may
        be None for raw-block engines).  Idempotent."""

    def prefill(self, tokens, positions, pages, seq=None):
        """Prefill one sequence's uncached suffix: ``tokens`` is the
        bucket-padded suffix (int32), ``positions`` the matching global
        positions, ``pages`` the slot's page-id table (-1 padded),
        ``seq`` the owning KVSeq (vector runners write K/V through
        it).  Returns nothing; K/V lands in the pages."""
        raise NotImplementedError

    def step(self, tokens, positions, pages):
        """One decode step across every slot: fixed-shape ``tokens`` /
        ``positions`` ``[num_slots]`` plus the gathered page table
        ``[num_slots, max_pages_per_slot]`` (None unless
        ``wants_pages``).  Returns ``(next_tokens, kv_rows)`` — int32
        per-slot next tokens and the query positions' packed K/V rows
        (``[num_slots, kv_bytes_per_token]`` uint8, or None for
        token-harness runners)."""
        raise NotImplementedError

    def dispatch_step(self, tokens, positions, pages, seqs=None, prev=None,
                      fed=None):
        """The engine's way into :meth:`step`, in two halves: start one
        step and return a handle; :meth:`complete_step` turns the handle
        into the step's result.  ``seqs`` are the slots' KVSeqs (None
        where a slot is idle).  A runner that ``feeds_tokens`` returns
        without waiting for the device and takes slot ``i``'s token,
        where ``fed[i]``, from the step ``prev`` (a handle) on the
        device; any other runs the whole step here."""
        return self.step(tokens, positions, pages)

    def complete_step(self, handle):
        """``(next_tokens, kv_rows, logprobs)`` of a dispatched step:
        :meth:`step`'s pair and the next tokens' log-probabilities
        (None from a runner that computes none)."""
        nxt, rows = handle
        return nxt, rows, None

    def verify(self, tokens, positions, tables, base_len, mask):
        """Speculative-verify (ISSUE 11): score a whole draft tree in
        ONE call.  ``tokens``/``positions`` are ``[num_slots, K1]`` —
        per slot, row 0 is the normal decode query (the last real
        token) and rows 1.. are draft positions (engine position
        convention: a token at sequence index p rides position p+1,
        exactly what :meth:`step` would have been handed when that
        token was newest).  ``tables`` ``[num_slots*K1,
        max_pages_per_slot]`` is the PER-ROW page-id table (tree side
        branches ride their fork's table), ``base_len``
        ``[num_slots*K1]`` the per-row count of MATERIALIZED arena
        keys, and ``mask`` ``[num_slots, K1, K1]`` the draft-tree
        ancestry mask (row i sees local row j's in-call K/V iff
        ``mask[s, i, j]``; always includes self).  Returns
        ``(out_tokens, kv_rows)`` — per-ROW greedy next tokens
        ``[num_slots, K1]`` (the accept rule is greedy match against
        these) and the rows' packed K/V ``[num_slots, K1,
        kv_bytes_per_token]`` uint8 (None for token-harness runners);
        only the ACCEPTED rows' K/V should ever be spliced."""
        raise NotImplementedError

    def close(self) -> None:
        pass


class LegacyFnRunner(ModelRunner):
    """Adapter for the PR 2/3 fn protocols: a 2-arg
    ``step_fn(tokens, positions)`` or 3-arg ``step_fn(tokens,
    positions, pages)`` plus an optional ``prefill_fn(padded_suffix,
    prefill_from)``.  Behavior is byte-for-byte the engine's old
    inline calls — required-positional detection included — so the
    pure-token harness and every existing test ride through
    unchanged."""

    def __init__(self, step_fn: Callable,
                 prefill_fn: Optional[Callable] = None, *,
                 store=None, pass_page_table: Optional[bool] = None,
                 name: str = "legacy"):
        self.step_fn = step_fn
        self.prefill_fn = prefill_fn
        self.has_prefill = prefill_fn is not None
        self.name = name
        # pass the gathered page tables only to a step_fn built for
        # them — a 2-arg step_fn keeps the PR 2 contract unchanged.
        # Detection counts REQUIRED positionals (an optional third
        # parameter like rng=None must not silently receive the
        # table); pass_page_table overrides for *args step functions
        if pass_page_table is not None:
            self.wants_pages = bool(pass_page_table)
        else:
            from brpc_tpu.serving.batcher import required_positional_args
            self.wants_pages = (store is not None and
                                required_positional_args(step_fn) >= 3)

    def prefill(self, tokens, positions, pages, seq=None):
        import jax.numpy as jnp
        self.prefill_fn(jnp.asarray(tokens),
                        jnp.int32(int(positions[0])))

    def step(self, tokens, positions, pages):
        import jax.numpy as jnp
        if pages is not None:
            out = self.step_fn(jnp.asarray(tokens),
                               jnp.asarray(positions),
                               jnp.asarray(pages))
        else:
            out = self.step_fn(jnp.asarray(tokens),
                               jnp.asarray(positions))
        return np.asarray(out), None

    def verify(self, tokens, positions, tables, base_len, mask):
        """Speculative-verify for the fn protocols: the PR 2 step_fn
        contract is elementwise over its slot axis (each slot is an
        independent (token, position) query — that independence is
        what lets requests share a fixed-shape batch at all), so a
        draft tree verifies as ONE step_fn call with the rows flattened
        onto the slot axis.  kv_rows is None — token-harness pages
        materialize at append time."""
        import jax.numpy as jnp
        tokens = np.asarray(tokens, np.int32)
        s, k1 = tokens.shape
        flat_t = jnp.asarray(tokens.reshape(-1))
        flat_p = jnp.asarray(np.asarray(positions,
                                        np.int32).reshape(-1))
        if self.wants_pages and tables is not None:
            out = self.step_fn(flat_t, flat_p, jnp.asarray(tables))
        else:
            out = self.step_fn(flat_t, flat_p)
        return np.asarray(out).reshape(s, k1), None


def as_runner(step_fn=None, prefill_fn=None, *, runner=None, store=None,
              pass_page_table=None) -> ModelRunner:
    """The engine's construction shim: hand back ``runner`` as-is, or
    wrap legacy fns in a :class:`LegacyFnRunner`."""
    if runner is not None:
        if step_fn is not None or prefill_fn is not None:
            raise ValueError("pass either runner= or step_fn/prefill_fn,"
                             " not both")
        return runner
    if step_fn is None:
        raise ValueError("a step_fn or a runner is required")
    return LegacyFnRunner(step_fn, prefill_fn, store=store,
                          pass_page_table=pass_page_table)


# ---------------------------------------------------------------------------
# the real transformer
# ---------------------------------------------------------------------------

def _layers_of(*kinds, of: str = "mixer_types") -> property:
    """A configuration's count of its held layers of ``kinds``."""
    return property(lambda cfg: held(getattr(cfg, of), *kinds))


@dataclass(frozen=True)
class TransformerConfig:
    """What a served model is made of.  The defaults are the dense
    stand-in (:class:`TransformerRunner`: GQA softmax attention in
    every layer, gelu MLP, weightless norms, sinusoidal positions, tied
    head, float32).  A published architecture fills the rest through
    :func:`from_hf_config`: ``mixer_types`` names each held layer's
    mixer and ``ffn_types`` its feed-forward (the kinds and what each
    is: :mod:`brpc_tpu.models.layers`), and
    :class:`~brpc_tpu.models.hybrid.HybridRunner` serves it."""
    vocab: int = 128
    d_model: int = 32
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    d_ff: int = 64
    # ---- a described architecture (ISSUE 32); () is the stand-in ----
    mixer_types: tuple = ()
    depth_published: int = 0        # layers of the published model
    layer_offset: int = 0           # published index of the first held
    lin_heads: int = 0              # lightning heads (q, k and v alike)
    lin_head_dim: int = 0
    qk_norm: bool = False
    attn_rope: bool = False
    lin_rope: bool = True
    rope_theta: float = 10000.0
    attn_output_gate: bool = False
    lin_output_gate: bool = False
    lin_output_norm: bool = False
    rms_eps: float = 1e-6
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 0         # logits / (d_model / dim_model_base)
    tie_embeddings: bool = True
    param_dtype: str = "float32"    # weights; "bfloat16": one MXU pass
    # learned sparse attention (one selection block = one cache page)
    sparse_block: int = 64
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # latent attention (ISSUE 34): the cache holds [c_kv; k_rope] a token
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # the state-space mixer (ISSUE 38, Mamba-1): ``ssm_expand x
    # d_model`` channels, ``ssm_state`` state values a channel, a causal
    # depthwise convolution of ``ssm_conv`` taps, a step size projected
    # up from ``ssm_dt_rank``
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_dt_rank: int = 0
    ssm_expand: int = 0
    # the Mamba-2 mixer (ISSUE 40): ``ssm_heads`` heads of
    # ``ssm_head_dim``, a scalar decay a head over a ``[ssm_head_dim,
    # ssm_state]`` state, ``B`` and ``C`` shared by ``ssm_groups`` groups
    # of heads (the group norm's groups too), ``ssm_conv`` taps over the
    # heads' channels + B + C, the dual form in blocks of ``ssm_chunk``
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_chunk: int = 0
    # feed-forward kind a held layer; () is the gated MLP everywhere
    ffn_types: tuple = ()
    n_experts: int = 0              # routed experts the router scores
    experts_per_tok: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0               # width of one expert, shared alike
    moe_latent: int = 0             # "latent_moe": the experts' own width
    shared_d_ff: int = 0            # "latent_moe": the shared expert's
    routed_scale: float = 1.0
    norm_topk: bool = True
    experts_held: tuple = ()        # (first, count) computed here

    @property
    def kv_bytes_per_token(self) -> int:
        """One token slot of the stand-in's cache: all layers' K then V
        vectors, f32, the token-major layout ``[n_layers, 2,
        n_kv_heads, head_dim]`` (``ops.paged_attention.arena_kv_view``).
        A described architecture's cache allocates by layer kind
        (``kvcache.layered``): bf16 K/V for its attention layers only,
        and this is then that figure."""
        if self.mixer_types:
            return (self.n_kv_layers * 2 * self.n_kv_heads * self.head_dim
                    + self.n_latent * self.latent_dim) * 2
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * 4

    @property
    def ssm_inner(self) -> int:
        """The state-space mixer's channels."""
        return self.ssm_expand * self.d_model

    @property
    def ssd_inner(self) -> int:
        """The Mamba-2 mixer's channels: its heads' values."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssd_channels(self) -> int:
        """What its convolution runs over: the heads' values, B and C."""
        return self.ssd_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def latent_dim(self) -> int:
        """One cached row: the compressed K/V and the shared rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    # held layers of a kind, by the records: K/V pages are kept by both
    # attention kinds, routed experts by either expert kind
    n_sparse = _layers_of(SPARSE)
    n_attention = _layers_of(ATTN)
    n_kv_layers = _layers_of(*keeping("kv"))
    n_linear = _layers_of(LINEAR)
    n_latent = _layers_of(MLA)
    n_mamba = _layers_of(MAMBA)
    n_mamba2 = _layers_of(MAMBA2)
    n_moe = _layers_of(*ROUTED, of="ffn_types")

    @property
    def residual_scale(self) -> float:
        """muP's depth scaling, where the family has it (it states
        ``dim_model_base``)."""
        if not (self.depth_published and self.dim_model_base):
            return 1.0
        return self.scale_depth / math.sqrt(self.depth_published)

    def layer_param_counts(self) -> dict:
        """Parameters of one MLP, one mixer of each kind (norm weights
        left out; of the state-space mixer everything its published
        count has: the convolution and its bias, ``dt_proj``'s bias,
        ``A_log``, ``D`` and the dt/B/C norms), one expert layer's
        feed-forward (every routed expert, the shared ones and the
        router), the Mamba-2 mixer (its convolution and bias, ``A_log``,
        ``D``, ``dt_bias`` and the gated norm with it), a latent expert
        layer (every routed expert in the latent width, the shared one,
        the two latent projections, the router and its correction bias)
        and the embedding."""
        dm = self.d_model
        di, r, n = self.ssm_inner, self.ssm_dt_rank, self.ssm_state
        hd = self.n_heads * self.head_dim
        kvd = self.n_kv_heads * self.head_dim
        lin = self.lin_heads * self.lin_head_dim
        h = self.n_heads
        return {"mlp": 3 * dm * self.d_ff,
                "minicpm4": dm * hd * (2 + int(self.attn_output_gate))
                + 2 * dm * kvd,
                "lightning-attn": dm * lin * (4
                                              + int(self.lin_output_gate)),
                "mla": dm * self.q_lora_rank + self.q_lora_rank * h
                * (self.qk_nope_dim + self.qk_rope_dim)
                + dm * self.latent_dim + self.kv_lora_rank * h
                * (self.qk_nope_dim + self.v_head_dim)
                + h * self.v_head_dim * dm,
                "mamba": dm * 2 * di + di * (self.ssm_conv + 1)
                + di * (r + 2 * n) + r * di + di + di * n + di
                + r + 2 * n + di * dm,
                "mamba2": dm * (self.ssd_inner + self.ssd_channels
                                + self.ssm_heads)
                + self.ssd_channels * (self.ssm_conv + 1)
                + 3 * self.ssm_heads + self.ssd_inner + self.ssd_inner * dm,
                "attention": 2 * dm * hd + 2 * dm * kvd,
                "moe": 3 * dm * self.moe_d_ff
                * (self.n_experts + self.n_shared_experts)
                + dm * self.n_experts,
                "latent_moe": self.n_experts
                * (2 * self.moe_latent * self.moe_d_ff + dm + 1)
                + 2 * dm * self.shared_d_ff + 2 * dm * self.moe_latent,
                "embedding": self.vocab * dm}


MIXER_KINDS = tuple(MIXERS)


def from_hf_config(hf: dict, *, layers: Optional[tuple] = None,
                   sparse: Optional[dict] = None,
                   experts: Optional[tuple] = None,
                   vocab: Optional[tuple] = None,
                   param_dtype: str = "bfloat16") -> TransformerConfig:
    """A :class:`TransformerConfig` from a published ``config.json``'s
    keys, taken verbatim: a file that names its ``mixer_types``
    (MiniCPM-SALA's), ``model_type`` ``glm4_moe_lite`` (latent
    attention in every layer, ``first_k_dense_replace`` gated MLPs and
    then routed experts), ``model_type`` ``jamba`` (state-space
    mixers with full attention where ``attn_layer_period`` /
    ``attn_layer_offset`` put it), or ``model_type`` ``nemotron_h``
    (blocks of ONE sublayer by ``hybrid_override_pattern``: ``M``
    Mamba-2, ``*`` attention, ``E`` latent experts); any other raises.
    ``layers`` =
    ``(first, count)`` holds a contiguous slice of the published
    layers (a pipeline stage); ``sparse`` gives what the published
    file does not carry (``kernel_size``, ``kernel_stride``,
    ``block_size``, ``topk``, ``init_blocks``, ``window_size``,
    ``dense_len``); ``experts`` = ``(first, count)`` are the routed
    experts this chip computes (all of them where it is None), and
    ``vocab`` = ``(first, count)`` the rows of the vocabulary it holds
    (a sliced vocabulary is a smaller vocabulary: embedding, head,
    logits and sampling are over the slice)."""
    if "mixer_types" not in hf:
        family = hf.get("model_type")
        if family not in ("glm4_moe_lite", "jamba", "nemotron_h"):
            raise ValueError(
                f"no description of model_type {family!r}: a config names "
                f"its mixer_types or is glm4_moe_lite, jamba or nemotron_h")
        if sparse:
            raise ValueError(f"{family} has no sparse settings")
        if family == "nemotron_h":
            return _from_nemotron_h(hf, layers, experts, vocab, param_dtype)
        if vocab is not None:
            raise ValueError("a slice of the vocabulary is described for "
                             "nemotron_h only")
        if family == "jamba":
            if experts is not None:
                raise ValueError("this family's experts are not described")
            return _from_jamba(hf, layers, param_dtype)
        return _from_glm4_moe_lite(hf, layers, experts, param_dtype)
    if experts is not None or vocab is not None:
        raise ValueError("this family has no routed experts and holds "
                         "its whole vocabulary")
    mixers = tuple(hf["mixer_types"])
    unknown = sorted(set(mixers) - set(MIXER_KINDS))
    if unknown:
        raise ValueError(f"mixer kinds this runner has not: {unknown}")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("only the silu gated MLP is described")
    first, count = layers if layers is not None else (0, len(mixers))
    held = mixers[first:first + count]
    if len(held) != count:
        raise ValueError(f"layers {first}+{count} exceed {len(mixers)}")
    sp = dict(sparse or {})
    fields = {}
    for key, name in (("block_size", "sparse_block"),
                      ("kernel_size", "sparse_kernel"),
                      ("kernel_stride", "sparse_stride"),
                      ("topk", "sparse_topk"),
                      ("init_blocks", "sparse_init_blocks"),
                      ("window_size", "sparse_window"),
                      ("dense_len", "sparse_dense_len")):
        if key in sp:
            fields[name] = int(sp.pop(key))
    if sp:
        raise ValueError(f"unknown sparse settings {sorted(sp)}")
    return TransformerConfig(
        vocab=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
        n_layers=count, n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]), d_ff=int(hf["intermediate_size"]),
        mixer_types=held, depth_published=int(hf["num_hidden_layers"]),
        layer_offset=first, lin_heads=int(hf["lightning_nh"]),
        lin_head_dim=int(hf["lightning_head_dim"]),
        qk_norm=bool(hf["qk_norm"]), attn_rope=bool(hf["attn_use_rope"]),
        lin_rope=bool(hf["lightning_use_rope"]),
        rope_theta=float(hf["rope_theta"]),
        attn_output_gate=bool(hf["attn_use_output_gate"]),
        lin_output_gate=bool(hf["use_output_gate"]),
        lin_output_norm=bool(hf["use_output_norm"]),
        rms_eps=float(hf["rms_norm_eps"]),
        scale_emb=float(hf["scale_emb"]),
        scale_depth=float(hf["scale_depth"]),
        dim_model_base=int(hf["dim_model_base"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=param_dtype, **fields)


def _held(what: str, held, whole: int) -> tuple:
    """``(first, count)`` of ``whole``, all of it where None."""
    first, count = held if held is not None else (0, whole)
    if first < 0 or count < 1 or first + count > whole:
        raise ValueError(f"{what} {first}+{count} exceed {whole}")
    return int(first), int(count)


def _from_glm4_moe_lite(hf: dict, layers, experts,
                        param_dtype: str) -> TransformerConfig:
    """GLM-4.7-Flash's keys.  What this runner does not compute raises:
    grouped routing (``n_group`` / ``topk_group`` other than 1 are not
    the identity), scaled rotary positions, biases, a partial rotary
    factor."""
    for key, want in (("hidden_act", "silu"), ("topk_method", "noaux_tc"),
                      ("n_group", 1), ("topk_group", 1),
                      ("rope_scaling", None), ("attention_bias", False),
                      ("partial_rotary_factor", 1)):
        if hf.get(key, want) != want:
            raise ValueError(f"glm4_moe_lite with {key}={hf[key]!r} is "
                             f"not described (only {want!r})")
    if int(hf["num_key_value_heads"]) != int(hf["num_attention_heads"]):
        raise ValueError("latent attention has one K/V head a query head")
    depth = int(hf["num_hidden_layers"])
    first, count = _held("layers", layers, depth)
    dense = int(hf["first_k_dense_replace"])
    n_exp = int(hf["n_routed_experts"])
    e_first, e_count = _held("experts", experts, n_exp)
    rope = int(hf["qk_rope_head_dim"])
    return TransformerConfig(
        vocab=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
        n_layers=count, n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["qk_nope_head_dim"]) + rope,
        d_ff=int(hf["intermediate_size"]),
        mixer_types=("mla",) * count,
        ffn_types=tuple("dense" if first + i < dense else "moe"
                        for i in range(count)),
        depth_published=depth, layer_offset=first,
        rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        q_lora_rank=int(hf["q_lora_rank"]),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_dim=int(hf["qk_nope_head_dim"]), qk_rope_dim=rope,
        v_head_dim=int(hf["v_head_dim"]), n_experts=n_exp,
        experts_per_tok=int(hf["num_experts_per_tok"]),
        n_shared_experts=int(hf["n_shared_experts"]),
        moe_d_ff=int(hf["moe_intermediate_size"]),
        routed_scale=float(hf["routed_scaling_factor"]),
        norm_topk=bool(hf["norm_topk_prob"]),
        experts_held=(e_first, e_count), param_dtype=param_dtype)


def _from_jamba(hf: dict, layers, param_dtype: str) -> TransformerConfig:
    """Jamba's keys: layer ``i`` mixes by attention where ``i %
    attn_layer_period == attn_layer_offset`` and by Mamba-1 elsewhere
    (the family's convention; the published file does not list the
    order).  What this runner does not compute raises: routed experts
    (``num_experts`` over 1), a sliding window, another activation than
    silu, biases on the state-space mixer's projections, a convolution
    without its bias.  The family has no rotary positions, no q/k norms
    and no output gate; heads are ``hidden_size / num_attention_heads``
    wide."""
    for key, want in (("hidden_act", "silu"), ("num_experts", 1),
                      ("sliding_window", None), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True)):
        if hf.get(key, want) != want:
            raise ValueError(f"jamba with {key}={hf[key]!r} is not "
                             f"described (only {want!r})")
    depth = int(hf["num_hidden_layers"])
    first, count = _held("layers", layers, depth)
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    dm, heads = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    if dm % heads:
        raise ValueError(f"hidden_size {dm} is not whole heads of {heads}")
    return TransformerConfig(
        vocab=int(hf["vocab_size"]), d_model=dm, n_layers=count,
        n_heads=heads, n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=dm // heads, d_ff=int(hf["intermediate_size"]),
        mixer_types=tuple("attention" if (first + i) % period == offset
                          else "mamba" for i in range(count)),
        depth_published=depth, layer_offset=first,
        rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        ssm_state=int(hf["mamba_d_state"]), ssm_conv=int(hf["mamba_d_conv"]),
        ssm_dt_rank=int(hf["mamba_dt_rank"]),
        ssm_expand=int(hf["mamba_expand"]), param_dtype=param_dtype)


def _from_nemotron_h(hf: dict, layers, experts, vocab,
                     param_dtype: str) -> TransformerConfig:
    """Nemotron-H's keys as Nemotron 3 publishes them: block ``i`` is
    ONE sublayer, ``hybrid_override_pattern[i]``: ``M`` the Mamba-2
    mixer, ``*`` attention (no rotary or other position signal, no q/k
    norm), ``E`` the latent mixture of ``relu(.)^2`` experts with its
    shared expert.  What this runner does not compute raises: a dense
    feed-forward block (``-``), a sliding window, grouped routing
    (``n_group`` / ``topk_group`` other than 1), the shared expert
    overlapped, a bias on any projection, a convolution without its
    bias, another activation, Mamba-2 channels that are not ``expand x
    hidden_size``.  The multi-token-prediction module
    (``num_nextn_predict_layers``) adds nothing to the next token's
    logits and is not built."""
    for key, want in (("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("sliding_window", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("moe_shared_expert_overlap", False),
                      ("n_shared_experts", 1), ("use_bias", False),
                      ("mlp_bias", False), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("use_conv_bias", True)):
        if hf.get(key, want) != want:
            raise ValueError(f"nemotron_h with {key}={hf[key]!r} is not "
                             f"described (only {want!r})")
    pattern = str(hf["hybrid_override_pattern"])
    depth = int(hf["num_hidden_layers"])
    if len(pattern) != depth:
        raise ValueError(f"hybrid_override_pattern names {len(pattern)} "
                         f"blocks of {depth}")
    first, count = _held("layers", layers, depth)
    kinds = {"M": ("mamba2", "none"), "*": ("attention", "none"),
             "E": ("none", "latent_moe")}
    unknown = sorted(set(pattern[first:first + count]) - set(kinds))
    if unknown:
        raise ValueError(
            f"nemotron_h blocks {unknown} are not described (only M, * and "
            f"E; '-' is a dense feed-forward block nothing here computes)")
    held = [kinds[c] for c in pattern[first:first + count]]
    dm = int(hf["hidden_size"])
    heads, hd = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    if heads * hd != int(hf["expand"]) * dm:
        raise ValueError(f"{heads} Mamba-2 heads of {hd} are not expand "
                         f"{hf['expand']} x hidden_size {dm}")
    n_exp = int(hf["n_routed_experts"])
    return TransformerConfig(
        vocab=_held("vocabulary rows", vocab, int(hf["vocab_size"]))[1],
        d_model=dm, n_layers=count, n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]), d_ff=int(hf["intermediate_size"]),
        mixer_types=tuple(m for m, _ in held),
        ffn_types=tuple(f for _, f in held),
        depth_published=depth, layer_offset=first,
        rms_eps=float(hf["layer_norm_epsilon"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        ssm_state=int(hf["ssm_state_size"]), ssm_conv=int(hf["conv_kernel"]),
        ssm_heads=heads, ssm_head_dim=hd, ssm_groups=int(hf["n_groups"]),
        ssm_chunk=int(hf["chunk_size"]), n_experts=n_exp,
        experts_per_tok=int(hf["num_experts_per_tok"]),
        n_shared_experts=int(hf["n_shared_experts"]),
        moe_d_ff=int(hf["moe_intermediate_size"]),
        moe_latent=int(hf["moe_latent_size"]),
        shared_d_ff=int(hf["moe_shared_expert_intermediate_size"]),
        routed_scale=float(hf["routed_scaling_factor"]),
        norm_topk=bool(hf["norm_topk_prob"]),
        experts_held=_held("experts", experts, n_exp),
        param_dtype=param_dtype)


def init_runner_params(cfg: TransformerConfig, key=None) -> dict:
    """Seeded random parameters, stacked per layer (every layer shares
    one compiled step: params index by layer inside the jit)."""
    import jax
    import jax.numpy as jnp
    key = key if key is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(key, 7)
    dm, h, hkv, d, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.d_ff)
    L = cfg.n_layers

    def init(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) \
            / math.sqrt(fan_in)

    return {
        "emb": init(ks[0], (cfg.vocab, dm), dm),
        "wq": init(ks[1], (L, dm, h * d), dm),
        "wk": init(ks[2], (L, dm, hkv * d), dm),
        "wv": init(ks[3], (L, dm, hkv * d), dm),
        "wo": init(ks[4], (L, h * d, dm), h * d),
        "w1": init(ks[5], (L, dm, ff), dm),
        "w2": init(ks[6], (L, ff, dm), ff),
    }


def make_tp_mesh(n_devices: Optional[int] = None):
    """A 1-D ``tp`` (tensor-parallel) ICI mesh — the moe.py ``ep``
    pattern applied to attention heads."""
    import jax
    from jax.sharding import Mesh
    n = n_devices or len(jax.devices())
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


def place_runner_params(params: dict, mesh) -> dict:
    """Shard the parameter tree over the ``tp`` axis with
    NamedSharding (the SNIPPETS.md [1]/[3] pjit partitioning applied
    here under GSPMD): q/k/v projections and the MLP up-projection
    shard their OUTPUT (head/ff) dim, the o/down projections their
    INPUT dim, embeddings replicate.  The jitted step inherits the
    layout — no per-call resharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = {
        "emb": P(),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "w1": P(None, None, "tp"),
        "w2": P(None, "tp", None),
    }
    tp = mesh.shape["tp"]
    for name, dim in (("wq", params["wq"].shape[2]),
                      ("wk", params["wk"].shape[2]),
                      ("wv", params["wv"].shape[2]),
                      ("w1", params["w1"].shape[2])):
        if dim % tp:
            raise ValueError(f"{name} dim {dim} must divide tp={tp}")
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def _float32_matmuls(fn):
    """Trace ``fn`` with every matmul at full float32 precision.  The
    weights and the cache are float32, but a TPU's default for float32
    operands is ONE bfloat16 pass (~3 significant digits): the paged
    path and the dense reference then round differently wherever their
    shapes differ, and a greedy argmax over a 50k vocabulary flips at
    the first near-tie.  This is the one place the model's precision
    is decided — the jitted paged programs and the dense reference
    both trace under it, kernels and reference einsums included."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


def _posenc(pos, dm: int):
    """Parameter-free sinusoidal position encoding (deterministic, so
    the dense reference and the paged path agree by construction)."""
    import jax.numpy as jnp
    half = dm // 2
    freq = jnp.exp(-math.log(10000.0)
                   * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _rms(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _mlp(x, w1, w2):
    import jax
    import jax.numpy as jnp
    return jax.nn.gelu(x @ w1) @ w2


@_float32_matmuls
def dense_forward(params: dict, cfg: TransformerConfig, tokens,
                  positions, use_flash: bool = True):
    """The DENSE reference forward: full causal self-attention over the
    whole sequence, no cache — the oracle the paged path is validated
    against, and the batcher's scoring path.  ``tokens``/``positions``
    are ``[B, S]``; returns per-position logits ``[B, S, vocab]``.
    Attention runs through the ops/attention.py flash kernel (the
    pallas TPU path with its CPU fallback) — the prefill-compute reuse
    the ISSUE names."""
    import jax.numpy as jnp

    from brpc_tpu.ops.attention import flash_attention, local_attention
    b, s = tokens.shape
    h_, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = params["emb"][tokens] + _posenc(positions, cfg.d_model)
    for l in range(cfg.n_layers):
        x = _rms(h)
        q = (x @ params["wq"][l]).reshape(b, s, h_, d)
        k = (x @ params["wk"][l]).reshape(b, s, hkv, d)
        v = (x @ params["wv"][l]).reshape(b, s, hkv, d)
        attn = flash_attention if use_flash else local_attention
        o = attn(q, k, v, causal=True)
        h = h + o.reshape(b, s, h_ * d) @ params["wo"][l]
        h = h + _mlp(_rms(h), params["w1"][l], params["w2"][l])
    return _rms(h) @ params["emb"].T


_DENSE_PAD = 128


@functools.cache
def _dense_last_logits():
    import jax

    def last(params, tokens, positions, n, *, cfg):
        return dense_forward(params, cfg, tokens, positions)[0, n - 1]
    return jax.jit(last, static_argnames=("cfg",))


def dense_logits(params: dict, cfg: TransformerConfig,
                 tokens: Sequence[int]):
    """The dense reference's logits ``[vocab]`` for the token that
    follows ``tokens``.  The sequence is padded to a multiple of
    ``_DENSE_PAD``: attention is causal, so padding behind the last
    real position cannot reach it, and a generation compiles once per
    128 tokens of length instead of once per token."""
    n = len(tokens)
    s = -(-n // _DENSE_PAD) * _DENSE_PAD
    toks = np.zeros((1, s), np.int32)
    toks[0, :n] = tokens
    pos = np.arange(s, dtype=np.int32)[None]
    return _dense_last_logits()(params, toks, pos, np.int32(n), cfg=cfg)


def dense_generate(params: dict, cfg: TransformerConfig,
                   prompt: Sequence[int], max_new_tokens: int) -> list:
    """Greedy decode with NO cache: the full sequence recomputes every
    step through :func:`dense_forward`.  The equivalence oracle for
    the paged runner — same math, none of the paging machinery."""
    import jax.numpy as jnp
    out = [int(t) for t in prompt]
    for _ in range(max_new_tokens):
        out.append(int(jnp.argmax(dense_logits(params, cfg, out))))
    return out[len(prompt):]


# ---- jitted compute (module level, cfg static: the compile cache is
# shared by every runner instance with the same config — a supervisor
# rebuilding engines, the chaos seeds, and the bench trials all reuse
# one trace per bucket shape) ----

def _kv_view(arena_u8, cfg: TransformerConfig, page_tokens: int):
    from brpc_tpu.ops.paged_attention import arena_kv_view
    return arena_kv_view(arena_u8, page_tokens, cfg.n_layers,
                         cfg.n_kv_heads, cfg.head_dim)


def _jit(fn, scope: str):
    """``fn`` jitted under a stable name: the program is
    ``jit_<scope, dots as underscores>`` and every operation in it is
    named under ``jax.named_scope(scope)``, so a trace's reduction finds
    the decode step and each prefill stage after a refactor."""
    import jax
    inner = _float32_matmuls(fn)

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(scope):
            return inner(*args, **kwargs)
    scoped.__name__ = scoped.__qualname__ = scope.replace(".", "_")
    return jax.jit(scoped,
                   static_argnames=("cfg", "page_tokens", "backend",
                                    "mesh"))


def _attention(mesh, backend):
    """``paged_attention`` for the jitted programs: as it is on one
    device, and HEAD-PARALLEL under an explicit ``shard_map`` over a
    ``tp`` mesh — each chip attends its own query heads over its own
    K/V heads of the (replicated) arena, which is the layout the
    sharded q/k/v projections already produce.  GSPMD cannot do this
    by itself: the chip's compiler refuses to partition a Mosaic
    kernel automatically."""
    from jax.sharding import PartitionSpec as P

    from brpc_tpu.ici.collective import shard_map
    from brpc_tpu.ops.paged_attention import paged_attention
    attn = functools.partial(paged_attention, backend=backend)
    if mesh is None or mesh.shape["tp"] == 1:
        return attn
    heads = P(None, "tp", None)          # q, extra_k/v, out: [N, H, D]
    kv_heads = P(None, None, "tp", None)  # pages, local_k/v: [.., Hkv, D]
    optional = {"extra_k": heads, "extra_v": heads, "local_k": kv_heads,
                "local_v": kv_heads, "local_mask": P()}

    def sharded(q, k_pages, v_pages, tables, lengths, **kw):
        names = [n for n in optional if kw.get(n) is not None]

        def per_chip(q, k_pages, v_pages, tables, lengths, *rest):
            return attn(q, k_pages, v_pages, tables, lengths,
                        **dict(zip(names, rest)))
        # runs while the jitted program TRACES (once per compile), never
        # per call
        # brpc-check: allow(jit-hot-path)
        return shard_map(
            per_chip, mesh,
            in_specs=(heads, kv_heads, kv_heads, P(), P(),
                      *(optional[n] for n in names)),
            out_specs=heads)(q, k_pages, v_pages, tables, lengths,
                             *(kw[n] for n in names))
    return sharded


@functools.cache
def _jits():
    """Build the jitted kernels lazily (first runner construction), so
    importing brpc_tpu.models costs no jax tracing."""
    import jax
    import jax.numpy as jnp

    def embed(params, tokens, positions, *, cfg, page_tokens, backend,
              mesh):
        return params["emb"][tokens] + _posenc(positions, cfg.d_model)

    def proj(params, h, l, *, cfg, page_tokens, backend, mesh):
        n = h.shape[0]
        x = _rms(h)
        q = (x @ params["wq"][l]).reshape(n, cfg.n_heads, cfg.head_dim)
        k = (x @ params["wk"][l]).reshape(n, cfg.n_kv_heads,
                                          cfg.head_dim)
        v = (x @ params["wv"][l]).reshape(n, cfg.n_kv_heads,
                                          cfg.head_dim)
        return q, k, v

    def attend(params, h, q, arena_u8, tables, lengths, l, *,
               cfg, page_tokens, backend, mesh):
        kv = _kv_view(arena_u8, cfg, page_tokens)
        o = _attention(mesh, backend)(q, kv[:, :, l, 0], kv[:, :, l, 1],
                                      tables, lengths)
        h = h + o.reshape(h.shape[0], cfg.n_heads * cfg.head_dim) \
            @ params["wo"][l]
        return h + _mlp(_rms(h), params["w1"][l], params["w2"][l])

    def step(params, tokens, positions, tables, arena_u8, *,
             cfg, page_tokens, backend, mesh):
        paged_attention = _attention(mesh, backend)
        s = tokens.shape[0]
        qpos = positions - 1      # the query position (see contract)
        kv = _kv_view(arena_u8, cfg, page_tokens)
        h = params["emb"][tokens] + _posenc(qpos, cfg.d_model)
        new_k, new_v = [], []
        for l in range(cfg.n_layers):
            x = _rms(h)
            q = (x @ params["wq"][l]).reshape(s, cfg.n_heads,
                                              cfg.head_dim)
            k = (x @ params["wk"][l]).reshape(s, cfg.n_kv_heads,
                                              cfg.head_dim)
            v = (x @ params["wv"][l]).reshape(s, cfg.n_kv_heads,
                                              cfg.head_dim)
            new_k.append(k)
            new_v.append(v)
            # arena keys 0..qpos-1 plus the in-flight self key: the
            # query position's slot may hold stale bytes (it is
            # written only after this step returns), so lengths
            # EXCLUDE it and extra_k/extra_v supply the value computed
            # right here
            o = paged_attention(q, kv[:, :, l, 0], kv[:, :, l, 1],
                                tables, qpos, extra_k=k, extra_v=v)
            h = h + o.reshape(s, cfg.n_heads * cfg.head_dim) \
                @ params["wo"][l]
            h = h + _mlp(_rms(h), params["w1"][l], params["w2"][l])
        logits = _rms(h) @ params["emb"].T
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # pack this position's K/V rows in the token-major slot layout
        kv_rows = jnp.stack(
            [jnp.stack(new_k, axis=1), jnp.stack(new_v, axis=1)],
            axis=2)                     # [S, L, 2, Hkv, D]
        rows_u8 = jax.lax.bitcast_convert_type(
            kv_rows, jnp.uint8).reshape(s, cfg.kv_bytes_per_token)
        return nxt, rows_u8, logits

    def verify(params, tokens, positions, tables, base_len, mask,
               arena_u8, *, cfg, page_tokens, backend, mesh):
        """Draft-tree verify (ISSUE 11): every row of every slot in ONE
        paged-attention call.  The arena part covers each slot's
        MATERIALIZED keys (per-row ``base_len`` — draft pages in the
        table hold nothing attendable and stay masked); the draft
        positions' K/V, computed right here, fold in as the kernel's
        LOCAL BLOCK under the ancestry ``mask`` — the multi-key
        generalization of the decode step's self-key merge, so a slot
        with zero drafts reduces exactly to a plain step row."""
        paged_attention = _attention(mesh, backend)
        s, k1 = tokens.shape
        r = s * k1
        qpos = positions.reshape(r) - 1    # engine position convention
        kv = _kv_view(arena_u8, cfg, page_tokens)
        h = params["emb"][tokens.reshape(r)] \
            + _posenc(qpos, cfg.d_model)
        new_k, new_v = [], []
        for l in range(cfg.n_layers):
            x = _rms(h)
            q = (x @ params["wq"][l]).reshape(r, cfg.n_heads,
                                              cfg.head_dim)
            k = (x @ params["wk"][l]).reshape(r, cfg.n_kv_heads,
                                              cfg.head_dim)
            v = (x @ params["wv"][l]).reshape(r, cfg.n_kv_heads,
                                              cfg.head_dim)
            new_k.append(k)
            new_v.append(v)
            o = paged_attention(
                q, kv[:, :, l, 0], kv[:, :, l, 1], tables, base_len,
                local_k=k.reshape(s, k1, cfg.n_kv_heads, cfg.head_dim),
                local_v=v.reshape(s, k1, cfg.n_kv_heads, cfg.head_dim),
                local_mask=mask)
            h = h + o.reshape(r, cfg.n_heads * cfg.head_dim) \
                @ params["wo"][l]
            h = h + _mlp(_rms(h), params["w1"][l], params["w2"][l])
        logits = _rms(h) @ params["emb"].T
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        kv_rows = jnp.stack(
            [jnp.stack(new_k, axis=1), jnp.stack(new_v, axis=1)],
            axis=2)                     # [R, L, 2, Hkv, D]
        rows_u8 = jax.lax.bitcast_convert_type(
            kv_rows, jnp.uint8).reshape(s, k1, cfg.kv_bytes_per_token)
        return nxt.reshape(s, k1), rows_u8

    return {"embed": _jit(embed, "runner.prefill.embed"),
            "proj": _jit(proj, "runner.prefill.proj"),
            "attend": _jit(attend, "runner.prefill.attend"),
            "step": _jit(step, "runner.decode_step"),
            "verify": _jit(verify, "runner.verify")}


def make_store_for(cfg: TransformerConfig, *, page_tokens: int = 8,
                   max_blocks: int = 8, pool=None, device=None,
                   commit_live_pages: bool = False, name: str = "kv"):
    """A KVCacheStore whose page geometry matches ``cfg``'s packed
    K/V slots (``vector_kv=True`` — the runner owns materialization).

    At real widths one token slot is ``L*2*Hkv*D*4`` bytes (256 KiB at
    16 layers of 16x128), so a page outgrows the rail's largest block
    class; the cache then leases from a pool of its own whose one
    class is a page: ``max_blocks`` pages, ``max_blocks * page_bytes``
    of cache."""
    from brpc_tpu.ici.block_pool import BLOCK_CLASSES, BlockPool
    from brpc_tpu.kvcache import KVCacheStore
    page_bytes = page_tokens * cfg.kv_bytes_per_token
    if pool is None and page_bytes > BLOCK_CLASSES[-1]:
        pool = BlockPool(device, classes=(page_bytes,),
                         blocks_per_class=max_blocks)
    return KVCacheStore(
        pool, device, page_bytes=page_bytes,
        page_tokens=page_tokens, max_blocks=max_blocks,
        commit_live_pages=commit_live_pages, vector_kv=True, name=name)


class TransformerRunner(ModelRunner):
    """The real model (see module docstring).  One instance may serve
    any number of engine incarnations (the supervisor's factory reuses
    it across restarts — parameters and jit caches survive the
    rebuild)."""

    wants_pages = True
    has_prefill = True

    def __init__(self, params: dict, cfg: TransformerConfig, *,
                 store=None, mesh=None,
                 attn_backend: Optional[str] = None,
                 name: str = "model"):
        from brpc_tpu.ici.mesh import ensure_compile_cache
        ensure_compile_cache()
        self.cfg = cfg
        self.kv_bytes_per_token = cfg.kv_bytes_per_token
        self.name = name
        if mesh is not None:
            self.mesh = mesh
            self.params = place_runner_params(params, mesh)
        else:
            # params already placed by the caller (place_runner_params)
            # carry their mesh — the runner must know it to place the
            # arena consistently (below)
            sh = getattr(params.get("wq"), "sharding", None)
            self.mesh = getattr(sh, "mesh", None)
            self.params = params
        tp = 1 if self.mesh is None else self.mesh.shape["tp"]
        if cfg.n_heads % tp or cfg.n_kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide n_heads ({cfg.n_heads}) and "
                f"n_kv_heads ({cfg.n_kv_heads}): attention runs "
                f"head-parallel over the mesh")
        self.store = None
        self._mu = threading.Lock()
        # backend=None lets ops/paged_attention pick (the kernel on a
        # TPU, gather elsewhere) at TRACE time, inside the shared jits
        self._backend = attn_backend
        self._fns = _jits()
        if store is not None:
            self.bind(store)

    def _statics(self) -> dict:
        return {"cfg": self.cfg, "page_tokens": self.store.page_tokens,
                "backend": self._backend, "mesh": self.mesh}

    # ---- binding / validation ----

    def bind(self, store) -> None:
        if store is None:
            raise ValueError(
                "TransformerRunner needs a paged KVCacheStore "
                "(store=) — raw-block engines have no page layout "
                "for the kernel to read")
        with self._mu:
            if self.store is store:
                return
            if self.store is not None:
                raise ValueError("runner already bound to a store")
            if not getattr(store, "vector_kv", False):
                raise ValueError(
                    "store must be vector_kv=True (make_store_for) — "
                    "token-id stand-in pages are not attendable KV")
            kbpt = store.pagepool.kv_bytes_per_token
            if kbpt != self.cfg.kv_bytes_per_token:
                raise ValueError(
                    f"store kv_bytes_per_token={kbpt} != model slot "
                    f"{self.cfg.kv_bytes_per_token} "
                    f"(page_bytes/page_tokens must match the packed "
                    f"[L, 2, Hkv, D] f32 layout)")
            self.store = store

    # ---- the ModelRunner surface ----

    def _flat_tables(self, pages) -> np.ndarray:
        """pid page tables -> flat arena indices (fixed shape)."""
        pages = np.asarray(pages, np.int32)
        flat = self.store.pagepool.flat_ids(pages.ravel().tolist())
        return np.asarray(flat, np.int32).reshape(pages.shape)

    def _arena(self):
        """The pool arena, placed CONSISTENTLY with the params: page
        buffers are committed to the pool's single device, and a jit
        whose params shard over a tp mesh rejects mixed placements —
        replicate the arena over the mesh (plain single-device serving
        returns it untouched).  Sharding the K/V pages themselves over
        the mesh heads is the ROADMAP follow-on; replication is the
        correct-if-wasteful tensor-parallel baseline."""
        import jax
        arena = self.store.pagepool.arena()
        if self.mesh is None:
            return arena
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(arena, NamedSharding(self.mesh, P()))

    def _step_args(self, tokens, positions, pages) -> tuple:
        import jax.numpy as jnp
        return (self.params, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(self._flat_tables(pages)), self._arena())

    def _step(self, tokens, positions, pages):
        return self._fns["step"](
            *self._step_args(tokens, positions, pages), **self._statics())

    def compile_step(self, num_slots: int, max_pages_per_slot: int):
        """Compile the decode step ahead of time at an engine's fixed
        shapes, against the bound store's arena, and return the
        compiled program: its ``memory_analysis()`` is what a
        deployment sizes its cache from, and with the persistent
        compile cache the first real step finds the program there."""
        slots = np.zeros((num_slots,), np.int32)
        pages = np.full((num_slots, max_pages_per_slot), -1, np.int32)
        return self._fns["step"].lower(
            *self._step_args(slots, slots, pages),
            **self._statics()).compile()

    def step(self, tokens, positions, pages):
        if fault.ENABLED and fault.hit(
                "model.step_compute", runner=self.name) is not None:
            raise RuntimeError("injected model step-compute failure")
        nxt, rows, _ = self._step(tokens, positions, pages)
        return np.asarray(nxt), np.asarray(rows)

    def step_logits(self, tokens, positions, pages):
        """The decode step's logits ``[num_slots, vocab]`` before the
        argmax, from the SAME compiled program :meth:`step` runs —
        what a check against :func:`dense_logits` compares."""
        return self._step(tokens, positions, pages)[2]

    def verify(self, tokens, positions, tables, base_len, mask):
        import jax.numpy as jnp
        flat = self._flat_tables(tables)
        arena = self._arena()
        nxt, rows = self._fns["verify"](
            self.params,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(flat),
            jnp.asarray(base_len, jnp.int32),
            jnp.asarray(mask, bool),
            arena, **self._statics())
        return np.asarray(nxt), np.asarray(rows)

    def prefill(self, tokens, positions, pages, seq=None):
        """Write-then-attend per layer (see module docstring): layer
        l's suffix K/V splice into the pages BEFORE the layer attends,
        so every query reads every key — its own included — from the
        ONE arena layout, cold and warm alike."""
        import jax.numpy as jnp
        if seq is None:
            raise ValueError("TransformerRunner.prefill needs the "
                             "owning KVSeq (seq=)")
        cfg = self.cfg
        start = int(positions[0])
        n = len(seq.tokens) - start       # valid (un-padded) rows
        if n <= 0:
            return
        b = len(tokens)
        toks = jnp.asarray(tokens, jnp.int32)
        pos = jnp.asarray(positions, jnp.int32)
        lengths = np.asarray(positions, np.int32) + 1   # causal: 0..i
        statics = self._statics()
        h = self._fns["embed"](self.params, toks, pos, **statics)
        # host-side running slot buffer: after layer l, each valid
        # row's slot holds layers 0..l — layers above are zeros, which
        # layer l never reads
        kvbuf = np.zeros((b, cfg.n_layers, 2, cfg.n_kv_heads,
                          cfg.head_dim), np.float32)
        for l in range(cfg.n_layers):
            q, k, v = self._fns["proj"](self.params, h, l, **statics)
            kvbuf[:, l, 0] = np.asarray(k)
            kvbuf[:, l, 1] = np.asarray(v)
            rows = kvbuf[:n].reshape(n, -1).view(np.uint8)
            # only the LAST layer's pass completes the slots: advancing
            # kv_filled (or live-committing) earlier would publish
            # pages whose upper layers are still zeros
            self.store.write_kv(seq, start, rows,
                                final=(l == cfg.n_layers - 1))
            # re-gather after the write: a COW inside write_kv swaps
            # page identities, and the arena must reflect the splice
            tab_row = self._flat_tables(seq.page_ids())
            mp = len(pages) if pages is not None else len(tab_row)
            padded = np.full((mp,), -1, np.int32)
            padded[:min(len(tab_row), mp)] = tab_row[:mp]
            tables = np.broadcast_to(padded, (b, mp))
            arena = self._arena()
            h = self._fns["attend"](self.params, h, q, arena,
                                    jnp.asarray(np.ascontiguousarray(
                                        tables)),
                                    jnp.asarray(lengths), l, **statics)

    # ---- the batcher surface (Serving.Score over the real model) ----

    def score(self, padded):
        """1-arg batch_fn: per-position greedy next-token ids
        ``[B, L]`` over the dense forward (flash-kernel prefill
        compute) — the batcher trims row i back to the request's raw
        length."""
        return self._score(padded, None)

    def score_with_offsets(self, padded, offsets):
        """2-arg batch_fn for prefix-trimmed batchers: rows are
        suffixes, ``offsets`` their global start positions."""
        return self._score(padded, offsets)

    def _score(self, padded, offsets):
        import jax.numpy as jnp
        toks = np.asarray(padded)
        if toks.dtype != np.int32:
            toks = toks.astype(np.int32)
        b, s = toks.shape
        pos = np.tile(np.arange(s, dtype=np.int32), (b, 1))
        if offsets is not None:
            pos = pos + np.asarray(offsets, np.int32)[:b, None]
        logits = dense_forward(self.params, self.cfg,
                               jnp.asarray(toks), jnp.asarray(pos))
        return np.asarray(jnp.argmax(logits, axis=-1),
                          dtype=np.float32)


def run_prefill(runner: ModelRunner, seq, prompt: Sequence[int], *,
                buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS,
                max_pages: int = 64) -> int:
    """Standalone prefill driver for callers OUTSIDE the engine (the
    disagg PrefillReplica): bucket-pad the uncached suffix and run
    ``runner.prefill`` against the admitted ``seq``.  Returns the
    suffix length prefilled."""
    suffix = [int(t) for t in prompt[seq.prefill_from:]]
    if not suffix or not runner.has_prefill:
        return 0
    n = len(suffix)
    bucket = next((x for x in sorted(buckets) if n <= x), n)
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = suffix
    positions = seq.prefill_from + np.arange(bucket, dtype=np.int32)
    ids = seq.page_ids()
    pages = np.full((max(max_pages, len(ids)),), -1, np.int32)
    pages[:len(ids)] = ids
    runner.prefill(padded, positions, pages, seq=seq)
    return n
