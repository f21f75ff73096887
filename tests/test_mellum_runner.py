"""Mellum 2's layer kinds on the normal serving path: sliding-window
attention whose keys live in the sequence's ring of the layered cache,
full attention with YaRN-scaled rotary over the pages, and routed
experts under a softmax router with no bias and no shared expert, in
the SAME layer loop as the other served models, at toy widths on the
CPU, float32, seeded weights, held to the plain reference
(``benchmarks/harness/reference_mellum.py``: the tests import the
benchmark's copy, there is no second one)."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_mellum as ref
from brpc_tpu.models import hybrid, layers
from brpc_tpu.models.hybrid import init_hybrid_params
from brpc_tpu.models.runner import from_hf_config
from hybrid_rig import Rig, T, draw_compiles, program_entries, tokens_of

S, F = "sliding_attention", "full_attention"
# the published keys (``config.json`` of the configuration's source)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [S, S, S, F] * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        F: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        S: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
# ... at toy widths, five layers S S S F S (the window layer after the
# full one takes the ring's fourth index, the full one the pages' first);
# 4 query heads on 2 K/V heads of 16; 8 experts top-2 of 24; a window of
# 24 positions over pages of 16 (a decode query spans 3 pages, the ring
# holds 2 + a 32-token chunk's 2: four pages, so 90 positions wrap it
# more than once); YaRN at a toy original length, so that its ramp lies
# inside the head's 8 frequency pairs
HF = dict(PUBLISHED, hidden_size=64, head_dim=16, num_attention_heads=4,
          num_key_value_heads=2, intermediate_size=96,
          moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
          vocab_size=256, num_hidden_layers=5, layer_types=[S, S, S, F, S],
          mlp_layer_types=["sparse"] * 5, sliding_window=24,
          rope_parameters={
              F: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                  "original_max_position_embeddings": 256, "beta_fast": 32,
                  "beta_slow": 1, "attention_factor": 1.1386294361119891},
              S: {"rope_type": "default", "rope_theta": 1000}})
REF = dict(HF, param_dtype="float32")
# float32 weights over a bfloat16 K/V cache: a key or value that the two
# sides round to neighbouring bfloat16s (float32 sums in another order)
# moves every later logit by about 1e-4; the full layer of 90 positions
# here meets a dozen such, from position 35 on, and reads up to 1e-3.
# The kernel (interpreted) multiplies a float32 query as two bfloat16
# terms (to 2^-17) and meets such a flip in nearly every position: up
# to 3e-3.  A key one past the window or a full layer without YaRN
# moves a logit by 1e-2 and more.
TOL = {None: 2e-3, "pallas": 5e-3}


@pytest.fixture(scope="module")
def model():
    # the file's one seeded draw: every call compiles its programs anew
    cfg = from_hf_config(HF, param_dtype="float32")
    return cfg, init_hybrid_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module", autouse=True)
def entries_before():
    # the programs' compiled entries before this file's first run
    return program_entries()


def test_from_hf_config_gives_the_published_kinds_and_counts():
    """The published keys: layers 0-11 are ``SSSF`` three times, and the
    parameter arithmetic of the WHOLE published model (12.15 B) and of a
    layer (417.7 M, 835.8 MB at bfloat16 with the router in float32)."""
    hf = PUBLISHED
    cfg = from_hf_config(hf, layers=(0, 12))
    letters = {("window", "moe"): "S", ("attention", "moe"): "F"}
    assert "".join(letters[k] for k in hybrid.layer_kinds(cfg)) \
        == "SSSF" * 3
    assert (cfg.n_window, cfg.n_attention, cfg.n_moe) == (9, 3, 12)
    assert (cfg.window, cfg.window_rope_theta, cfg.rope_theta) \
        == (1024, 500000.0, 500000.0)
    assert cfg.rope_yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert (cfg.router_scoring, cfg.n_shared_experts, cfg.experts_held) \
        == ("softmax", 0, (0, 64))
    c = cfg.layer_param_counts()
    assert c["window"] == c["attention"] == 21_233_664
    assert c["moe"] == 64 * 6_193_152 + 147_456
    layer = hybrid.layer_shapes(cfg, "window", "moe")
    assert "router_bias" not in layer and "ws_gate" not in layer
    n = sum(int(np.prod(s)) for s, _ in layer.values())
    assert n == 417_747_456 == c["window"] + c["moe"] + 2 * 2304
    whole = from_hf_config(hf)
    total = sum(int(np.prod(s)) for kind in hybrid.layer_kinds(whole)
                for s, _ in hybrid.layer_shapes(whole, *kind).values()) \
        + sum(int(np.prod(s)) for s, _ in hybrid.top_shapes(whole).values())
    assert round(total / 1e9, 2) == 12.15
    # the cache counts the full layers only: 3 x K and V x 4 x 128 x 2 B
    assert cfg.kv_bytes_per_token == 6144
    spec = hybrid.layered_spec(cfg, 48, 24)
    assert (spec.n_sparse, spec.n_window, spec.ring_pages, spec.n_linear) \
        == (3, 9, 24, 0)
    assert spec.has_state and not spec.compressed


def test_an_undescribed_setting_raises():
    for key, value in (("attention_bias", True), ("max_window_layers", 4),
                       ("use_sliding_window", False),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            from_hf_config(dict(PUBLISHED, **{key: value}), layers=(0, 12))
    longrope = json.loads(json.dumps(PUBLISHED["rope_parameters"]))
    longrope[F]["rope_type"] = "longrope"
    with pytest.raises(ValueError, match="rope_type"):
        from_hf_config(dict(PUBLISHED, rope_parameters=longrope))
    with pytest.raises(ValueError, match="layer_types"):
        from_hf_config(dict(PUBLISHED, layer_types=["chunked"] * 28))
    with pytest.raises(ValueError, match="vocabulary"):
        from_hf_config(PUBLISHED, vocab=(0, 1024))


def test_yarn_keeps_the_low_pairs_and_divides_the_high_ones():
    """``low`` 18 and ``high`` 35 at the published numbers: pairs 0-18
    keep theta's frequency, 35-63 are divided by 16, a ramp between; the
    reference computes the same float32 table."""
    yarn = from_hf_config(PUBLISHED).rope_yarn
    inv, factor = layers.rope_table(128, 500000.0, yarn)
    plain, one = layers.rope_table(128, 500000.0)
    assert factor == 1.2772588722239782 and one == 1.0
    ratio = plain.astype(np.float64) / inv
    assert np.allclose(ratio[:19], 1.0, rtol=1e-6)
    assert np.allclose(ratio[35:], 16.0, rtol=1e-6)
    assert (np.diff(ratio[18:36]) > 0).all()
    assert math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(500000))) == 18
    r_inv, r_factor = ref.inverse_frequencies(128, 500000.0, yarn)
    assert (r_inv == inv).all() and r_factor == factor


def test_seeded_weights_are_the_references(model):
    cfg, params = model
    again = ref.make_params(REF, 5)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params, again))
    assert params["layers"][0]["router"].shape == (64, 8)
    assert params["layers"][3]["we_gate"].shape == (8, 64, 24)
    assert "router_bias" not in params["layers"][0]


def test_the_softmax_router_chooses_and_weighs_as_the_equations_say(model):
    """``p = softmax(x W_r)``, the top 2 of ``p``, weights ``p_e`` over
    their sum, no bias, no shared expert: ``moe_share`` against the
    equations written out here, and against the reference's loop."""
    cfg, params = model
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(12, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, shared, sizes = hybrid.moe_share(p, x, cfg, jnp.ones((12,), bool))
        probs = np.asarray(jax.nn.softmax(x @ p["router"], axis=-1))
        want = np.zeros((12, 64))
        for n in range(12):
            top = np.argsort(-probs[n])[:2]
            for e in top:
                h = jax.nn.silu(x[n] @ p["we_gate"][e]) * (x[n] @ p["we_up"][e])
                want[n] += probs[n, e] / probs[n, top].sum() \
                    * np.asarray(h @ p["we_down"][e])
        loop = ref.experts_ffn(ref.model_cfg(REF), p, x)
    assert shared == 0.0 and int(sizes.sum()) == 24
    assert np.abs(np.asarray(y) - want).max() < 2e-5
    assert np.abs(np.asarray(loop) - want).max() < 2e-5


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_prefill_then_decode_equals_the_full_forward_pass(model, backend):
    """Chunked prefill (a cut at the snapshot boundary, the last chunk
    padded) then decode through the cache, logit for logit the
    reference's one forward pass: the window layers' rings wrap (90
    positions, 4 pages of 16 a ring) and the YaRN-scaled full layer
    reads every page; with the kernels interpreted too."""
    cfg, params = model
    toks = tokens_of(90)
    want, _ = ref.full_logits(params, REF, toks, block=16, s_max=96)
    rig = Rig(cfg, params, f"m_full_{backend}", backend=backend)
    assert rig.store.layers.spec.ring_pages == 4
    seq = rig.store.admit(toks[:60])
    got = np.concatenate([rig.prefill(seq, toks[:60]),
                          rig.decode(seq, toks, 90)])
    assert got.shape == (90, 256)
    assert np.abs(got - want[:90]).max() < TOL[backend]
    r = rig.runner
    # decode positions 59 .. 89: a window query spans at most 3 pages
    pos = np.arange(59, 90)
    pages = pos // T - np.maximum(pos - 23, 0) // T + 1
    assert r.window_positions.get_value() == 31 * 4
    assert r.window_pages_read.get_value() == pages.sum() * 4
    assert r.full_pages_read.get_value() == (pos // T + 1).sum()
    assert r.moe_assignments.get_value() == 90 * 2 * 5
    assert rig.store.layers.snapshots.get_value() == 1      # at 48
    rig.store.retire(seq, cache=False)
    rig.close()


def test_a_block_of_positions_a_row_serves_what_a_row_a_position_does(
        model, monkeypatch):
    """Where a full layer's prefill rows would name more table entries
    than ``layers.TABLE_ENTRIES`` (the cell's 816-page tables), a kernel
    row is a block of positions; forced here at toy size, the logits
    are the reference's as before."""
    cfg, params = model
    monkeypatch.setattr(layers, "TABLE_ENTRIES", 0)
    traced, block = [], layers._attend_block
    monkeypatch.setattr(layers, "_attend_block", lambda *a, **kw: (
        traced.append(kw.get("name", a[-1] if len(a) > 8 else "")),
        block(*a, **kw))[1])
    toks = tokens_of(70, seed=11)
    want, _ = ref.full_logits(params, REF, toks, block=16, s_max=80)
    # another cache size: the programs are traced anew under the limit
    rig = Rig(cfg, params, "m_grouped", pages=128)
    seq = rig.store.admit(toks[:60])
    got = np.concatenate([rig.prefill(seq, toks[:60]),
                          rig.decode(seq, toks, 70)])
    assert np.abs(got - want[:70]).max() < TOL[None]
    # the window layers' blocks, and the full layer's
    assert traced.count("window_attend") == 4 and traced.count("") == 1
    rig.store.retire(seq, cache=False)
    rig.close()


def test_a_hit_on_a_ring_snapshot_equals_a_cold_prefill(model):
    """A radix hit shares the full layer's pages and restores the
    window layers' ring from the snapshot at the prompt's boundary: the
    second request on a shared prompt reads the logits of a cold one,
    from its suffix on."""
    cfg, params = model
    shared = tokens_of(64, seed=3)
    q1, q2 = tokens_of(9, seed=4), tokens_of(23, seed=5)
    rig = Rig(cfg, params, "m_warm")
    first = rig.store.admit(shared + q1)
    rig.prefill(first, shared + q1)
    rig.decode(first, shared + q1 + [7] * 4, len(shared + q1) + 3)
    rig.store.retire(first)
    lay = rig.store.layers
    assert lay.snapshots.get_value() == 1
    cold = Rig(cfg, params, "m_cold")
    prompt = shared + q2
    seq_c = cold.store.admit(prompt)
    logits_c = np.concatenate([
        cold.prefill(seq_c, prompt)[64:],
        cold.decode(seq_c, prompt + [9] * 5, len(prompt) + 4)])
    seq_w = rig.store.admit(prompt)
    assert seq_w.prefill_from == 64 and lay.restores.get_value() == 1
    logits_w = np.concatenate([
        rig.prefill(seq_w, prompt),
        rig.decode(seq_w, prompt + [9] * 5, len(prompt) + 4)])
    assert logits_w.shape == logits_c.shape
    assert np.abs(logits_w - logits_c).max() < 2e-5
    cold.close()
    rig.store.retire(seq_w)
    rig.close()


def test_a_sequence_in_a_row_another_just_left_equals_the_same_alone(model):
    """A sequence admitted into the row (and so the ring) another just
    left, with no prefix to hit, reads the logits it reads in an
    untouched cache."""
    cfg, params = model
    rig = Rig(cfg, params, "m_reuse", rows=1)
    other = rig.store.admit(tokens_of(40, seed=6))
    rig.prefill(other, other.tokens)
    rig.decode(other, other.tokens + [3] * 3, 42)
    row = other.state_row
    rig.store.retire(other, cache=False)
    r = rig.store.layers.spec.ring_pages
    assert np.abs(np.asarray(rig.store.layers.ring, np.float32)[
        :, :, :, row * r:(row + 1) * r]).max() > 0
    prompt = tokens_of(30, seed=7)
    seq = rig.store.admit(prompt)
    assert seq.state_row == row and seq.prefill_from == 0
    got = np.concatenate([rig.prefill(seq, prompt),
                          rig.decode(seq, prompt + [5] * 4, 33)])
    alone = Rig(cfg, params, "m_alone", rows=1)
    seq_a = alone.store.admit(prompt)
    want = np.concatenate([alone.prefill(seq_a, prompt),
                           alone.decode(seq_a, prompt + [5] * 4, 33)])
    assert (got == want).all()
    alone.close()
    rig.store.retire(seq, cache=False)
    rig.close()


def test_a_chunk_longer_than_the_ring_holds_raises(model):
    """A ring holds the window beside ONE chunk of ``chunk_tokens``: a
    longer chunk would overwrite keys its own first queries read."""
    cfg, params = model
    rig = Rig(cfg, params, "m_long_chunk")
    toks = tokens_of(70, seed=9)
    seq = rig.store.admit(toks)
    with pytest.raises(ValueError, match="overruns a window ring"):
        rig.prefill(seq, toks, chunk=64)
    rig.store.retire(seq, cache=False)
    rig.close()
    with pytest.raises(ValueError, match="chunk_tokens"):
        hybrid.make_layered_store(cfg, cache_pages=8, state_rows=2,
                                  page_tokens=T)


def test_set_up_compiles_a_draw_a_kind_of_block_and_a_program_a_shape(
        model, entries_before):
    """What set-up compiles: ONE seeded draw a distinct (mixer,
    feed-forward) pair and one of the embedding and head, whatever the
    depth; and each of the two programs once a shape and static value
    this file ran.  The file's LAST test: it counts what the tests
    before it compiled."""
    cfg = model[0]
    names = draw_compiles(cfg)
    assert len(names) == 3 == len(set(hybrid.layer_kinds(cfg))) + 1, names
    # the two backends, the one-row store's arrays and the 128-page
    # store's; the prefill's fifth is the refused chunk's shape
    assert program_entries(entries_before) == {"step": 4, "prefill": 5}
