"""GLM-4.7-Flash's layer kinds on the normal serving path (ISSUE 34):
latent attention over latent pages and routed experts in the SAME layer
loop as MiniCPM-SALA's mixers, at toy widths on the CPU, float32, seeded
weights, held to the plain reference (``benchmarks/harness/
reference_glm.py``: the tests import the benchmark's copy, there is no
second one)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_glm as ref
from brpc_tpu.models import hybrid
from brpc_tpu.models.hybrid import init_hybrid_params
from brpc_tpu.models.runner import from_hf_config
from brpc_tpu.ops.moe import route
from hybrid_rig import (Gated, InARow, Rig, T, as_drawn, serve,
                        tokens_of)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the published keys (the catalog row's, verbatim)
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880}
# ... at toy widths: 64 experts still, so that 8 shares of 8 exist
HF = dict(PUBLISHED, hidden_size=64, intermediate_size=128,
          moe_intermediate_size=16, num_attention_heads=4,
          num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32,
          qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
          vocab_size=256)
# the dense layer and one expert layer, every kind once; two of a kind are
# the toy cell's three layers (benchmarks/tests/test_glm_cell.py, tier-1)
HELD = 2
# float32 weights over a bfloat16 cache: a latent value that the two
# sides round to neighbouring bfloat16s moves a logit by about 1e-4
TOL = 5e-4


def configs(dtype="float32"):
    cfg = from_hf_config(HF, layers=(0, HELD), param_dtype=dtype)
    ref_cfg = dict(HF, num_hidden_layers=HELD,
                   published_num_hidden_layers=47, first_published_layer=0,
                   param_dtype=dtype)
    return cfg, ref_cfg


@pytest.fixture(scope="module")
def model():
    # the file's one seeded draw: every call compiles its programs anew
    cfg, ref_cfg = configs()
    return cfg, ref_cfg, init_hybrid_params(cfg, jax.random.PRNGKey(5))


def test_from_hf_config_gives_the_published_parameter_counts():
    """The catalog row's keys, verbatim: the issue's arithmetic."""
    hf = PUBLISHED
    if os.path.exists(CATALOG):
        hf = json.loads(next(line for line in open(CATALOG)
                             if '"GLM-4.7-Flash"' in line))["config"]
        assert hf == PUBLISHED
    cfg = from_hf_config(hf, layers=(0, 8))
    c = cfg.layer_param_counts()
    assert c["mla"] == 21_757_952                       # 21.76 M
    assert c["mla"] + c["mlp"] == 84_672_512            # layer 0: 84.7 M
    assert c["mla"] + c["moe"] == 635_305_984           # 635.3 M
    assert 2 * c["embedding"] == 634_388_480            # 634.4 M
    assert c["moe"] - 64 * 3 * 2048 * 1536 + c["mla"] == 31_326_208
    assert cfg.ffn_types == ("dense",) + ("moe",) * 7
    assert cfg.mixer_types == ("mla",) * 8 and cfg.n_latent == 8
    assert cfg.kv_bytes_per_token == 8 * 576 * 2 == 9216
    assert cfg.residual_scale == 1.0 and cfg.experts_held == (0, 64)
    assert from_hf_config(hf, layers=(1, 7)).ffn_types == ("moe",) * 7
    shapes = hybrid.layer_shapes(cfg, "mla", "moe")
    n = sum(int(np.prod(s)) for s, fan in shapes.values() if fan is not None)
    assert n == c["mla"] + c["moe"] + 64          # + the correction bias


def test_an_undescribed_family_or_setting_raises():
    with pytest.raises(ValueError, match="model_type"):
        from_hf_config(dict(PUBLISHED, model_type="glm5"))
    with pytest.raises(ValueError, match="n_group"):
        from_hf_config(dict(PUBLISHED, n_group=8))
    with pytest.raises(ValueError, match="experts"):
        from_hf_config(PUBLISHED, experts=(60, 8))


def test_seeded_weights_are_the_references(model):
    cfg, ref_cfg, params = model
    again = ref.make_params(ref_cfg, 5)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params, again))


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_prefill_then_decode_equals_the_full_forward_pass(model, backend):
    """Chunked prefill (two chunks, the second padded) then decode
    through the latent pages, logit for logit the reference's one forward
    pass with expanded keys and values; with the kernels interpreted
    too (they multiply queries and probabilities at the pages' bfloat16)."""
    cfg, ref_cfg, params = model
    toks = tokens_of(90)
    want, _ = ref.full_logits(params, ref_cfg, toks, block=16, s_max=96)
    rig = Rig(cfg, params, f"g_full_{backend}", backend=backend)
    seq = rig.store.admit(toks[:60])
    got = np.concatenate([rig.prefill(seq, toks[:60]),
                          rig.decode(seq, toks, 90)])
    tol = TOL if backend is None else 6e-2
    assert np.abs(got - want[:90]).max() < tol
    r = rig.runner
    assert r.moe_assignments.get_value() == 90 * 4 * cfg.n_moe
    assert 31 * 1 <= r.moe_experts_hit.get_value() / cfg.n_moe <= 31 * 4
    assert r.latent_tokens_read.get_value() == sum(range(60, 91)) * HELD
    assert r.latent_pages_distinct.get_value() \
        == sum(-(-n // T) for n in range(60, 91)) * HELD
    rig.store.retire(seq, cache=False)
    rig.close()


def test_the_gated_experts_stay_off_the_expert_ffn_kernel(model):
    """ISSUE 41: the ``expert_ffn`` Pallas kernel is the two-matrix
    body's; the gated experts of this family go through
    ``lax.ragged_dot`` whatever the backend, and the kernel's counters
    say so."""
    from brpc_tpu.models.hybrid import ffn_kernel_blocks
    cfg, _, params = model
    assert cfg.n_moe and ffn_kernel_blocks(cfg, "pallas") == 0
    toks = tokens_of(50, seed=8)
    rig = Rig(cfg, params, "g_calls", backend="pallas")
    seq = rig.store.admit(toks[:40])
    rig.prefill(seq, toks[:40])
    rig.decode(seq, toks, 42)
    r = rig.runner
    assert r.moe_assignments.get_value() == 42 * 4 * cfg.n_moe
    assert r.moe_kernel_calls.get_value() == 0
    assert r.moe_tile_rows.get_value() == 0
    rig.store.retire(seq, cache=False)
    rig.close()


def test_absorbed_attention_equals_expanded_attention(model):
    """One layer's attention alone: queries absorbed into the latent
    space over the latent pages against the reference's per-head keys
    and values expanded from the same rows."""
    cfg, ref_cfg, params = model
    m = ref.model_cfg(ref_cfg)
    p = params["layers"][1]
    rng = np.random.default_rng(1)
    n = 40
    x = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)
    want, rows = jax.jit(lambda p, x: ref.attention(
        m, p, x, jnp.zeros((48, 40), jnp.float32), pos, n))(p, x)
    lanes = 128
    from brpc_tpu.ops.latent_attention import latent_attend, latent_write

    @jax.jit            # one program, not one an operation
    def absorbed(p, x):
        with jax.default_matmul_precision("highest"):
            qq, row = hybrid._mla_project(p, x, pos, cfg, lanes, "")
            lat = latent_write(
                jnp.zeros((1, 4, T, lanes), jnp.bfloat16), 0,
                jnp.asarray([2, 0, 3]), jnp.zeros((3,), jnp.int32),
                jnp.pad(row, ((0, 8), (0, 0))).reshape(3, T, lanes))
            o = latent_attend(
                qq, jnp.broadcast_to((pos + 1)[:, None, None], (n, 4, 1)),
                lat, 0, jnp.zeros((n,), jnp.int32),
                jnp.asarray([[2, 0, 3, -1]], jnp.int32))
            return row, hybrid._mla_out(p, o, cfg)
    row, got = absorbed(p, x)
    assert np.array_equal(np.asarray(row[:, :40], np.float32),
                          np.asarray(rows[:n]))
    assert not np.asarray(row[:, 40:]).any()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_the_bias_chooses_and_the_scores_weigh(model):
    """``noaux_tc``: the top 4 of ``s + b``, weighted by ``s`` alone.
    The seeded bias is large enough that both ways of getting it wrong
    show: leaving it out of the choice and letting it into the
    weights."""
    cfg, ref_cfg, params = model
    m = ref.model_cfg(ref_cfg)
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(50, 64)),
                    jnp.float32)
    idx_r, w_r, s = ref.route(m, p, x)
    idx, w = route(s, p["router_bias"], 4, norm=True, scale=1.8)
    assert np.array_equal(np.asarray(idx), np.asarray(idx_r))
    assert np.allclose(np.asarray(w), np.asarray(w_r), atol=1e-7)
    s_np, b = np.asarray(s), np.asarray(p["router_bias"])
    chosen = np.argsort(-(s_np + b), axis=1)[:, :4]
    assert np.array_equal(np.sort(chosen), np.sort(np.asarray(idx)))
    no_bias = np.argsort(-s_np, axis=1)[:, :4]
    assert (np.sort(no_bias) != np.sort(chosen)).any(axis=1).sum() >= 10
    picked = np.take_along_axis(s_np, np.asarray(idx), axis=1)
    assert np.allclose(np.asarray(w),
                       1.8 * picked / picked.sum(1, keepdims=True),
                       atol=1e-6)
    leaked = np.take_along_axis(s_np + b, np.asarray(idx), axis=1)
    leaked = 1.8 * leaked / leaked.sum(1, keepdims=True)
    assert np.abs(leaked - np.asarray(w)).max() > 0.02
    # and the layer the program computes is the reference's
    with jax.default_matmul_precision("highest"):
        y, shared, _ = hybrid.moe_share(p, x, cfg, jnp.ones((50,), bool))
        want = ref.experts_ffn(m, p, x)
    assert np.abs(np.asarray(y + shared) - np.asarray(want)).max() < 2e-5


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer(model):
    """A chip that holds experts ``[8 i, 8 i + 8)`` routes over all 64
    and computes its own experts' part; the eight parts and the shared
    expert, counted ONCE, are the uncut reference's layer."""
    cfg, ref_cfg, params = model
    m = ref.model_cfg(ref_cfg)
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, 64)),
                    jnp.float32)
    valid = jnp.ones((24,), bool)
    total, seen = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for i in range(8):
            share_cfg = from_hf_config(HF, layers=(0, HELD),
                                       experts=(8 * i, 8),
                                       param_dtype="float32")
            mine = dict(p, **{k: p[k][8 * i:8 * i + 8]
                              for k in ("we_gate", "we_up", "we_down")})
            y, shared, sizes = hybrid.moe_share(mine, x, share_cfg, valid)
            assert sizes.shape == (8,)
            total, seen = total + y, seen + int(sizes.sum())
        want = ref.experts_ffn(m, p, x)
    assert seen == 24 * 4                       # every assignment, once
    assert np.abs(np.asarray(total + shared) - np.asarray(want)).max() < 2e-5
    # a share alone is not the layer
    assert np.abs(np.asarray(y + shared) - np.asarray(want)).max() > 1e-3


def test_no_token_is_dropped_when_all_choose_one_expert(model):
    """No capacity: a correction bias that sends EVERY token to expert 5
    (and three more of its own choosing) still computes every token
    there.  Rows that are no token are routed nowhere."""
    cfg, ref_cfg, params = model
    m = ref.model_cfg(ref_cfg)
    p = dict(params["layers"][1])
    p["router_bias"] = p["router_bias"].at[5].set(10.0)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(48, 64)),
                    jnp.float32)
    valid = jnp.arange(48) < 40
    with jax.default_matmul_precision("highest"):
        y, shared, sizes = hybrid.moe_share(p, x, cfg, valid)
        want = ref.experts_ffn(m, p, x)
    assert int(sizes[5]) == 40 and int(sizes.sum()) == 160
    assert np.abs(np.asarray(y + shared)[:40]
                  - np.asarray(want)[:40]).max() < 2e-5
    assert not np.asarray(y)[40:].any()


def test_a_warm_request_equals_a_cold_one_logit_for_logit(model):
    """A radix hit is whole latent pages, with no state beside them to
    restore: the second request of a shared prompt reads the same logits
    as a cold one, from its suffix on."""
    cfg, _, params = model
    doc = tokens_of(70, seed=3)
    q1, q2 = tokens_of(9, seed=4), tokens_of(7, seed=5)
    rig = Rig(cfg, params, "g_warm")
    first = rig.store.admit(doc + q1)
    assert first.prefill_from == 0
    rig.prefill(first, doc + q1)
    rig.decode(first, doc + q1 + [7] * 4, len(doc + q1) + 3)
    rig.store.retire(first)
    assert rig.store.layers.rows_free() == 0        # there are no rows
    cold = Rig(cfg, params, "g_cold")
    prompt = doc + q2
    seq_c = cold.store.admit(prompt)
    logits_c = np.concatenate([
        cold.prefill(seq_c, prompt)[64:],
        cold.decode(seq_c, prompt + [9] * 5, len(prompt) + 4)])
    seq_w = rig.store.admit(prompt)
    assert seq_w.prefill_from == 64                 # four whole pages
    logits_w = np.concatenate([
        rig.prefill(seq_w, prompt),
        rig.decode(seq_w, prompt + [9] * 5, len(prompt) + 4)])
    assert logits_w.shape == logits_c.shape
    assert np.abs(logits_w - logits_c).max() < TOL
    cold.close()
    rig.store.retire(seq_w)
    rig.close()


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_slots_on_one_prompt_decode_as_on_private_copies(model, backend):
    """Four slots admitted through the radix tree on one prompt: the
    step reads the nine pages they share once, all their heads in one
    pass, then each slot's own tail (``ops.latent_attention``), and gives
    the logits and tokens of the same four sequences on private copies
    of every page; the counters read what the tables say."""
    from brpc_tpu.ops.latent_attention import PAGES_PER_STEP, page_visits
    cfg, _, params = model
    doc = tokens_of(150, seed=11)               # 9 whole pages and 6 over
    asks = [tokens_of(n, seed=12 + i) for i, n in enumerate((5, 23, 2, 40))]

    def four_slots(rig):
        seqs = [rig.store.admit(doc + a) for a in asks]
        for seq, a in zip(seqs, asks):
            rig.prefill(seq, doc + a)
        pos = np.asarray([len(doc + a) for a in asks], np.int32)
        tabs = np.stack([rig.table(seq) for seq in seqs])
        r = rig.runner
        before = (r.latent_page_visits.get_value(),
                  r.latent_page_visits_shared.get_value())
        logits = np.asarray(r.step_logits(
            np.asarray([a[-1] for a in asks], np.int32), pos, tabs,
            seqs=seqs))
        after = (r.latent_page_visits.get_value(),
                 r.latent_page_visits_shared.get_value())
        # and the position after it, no table row changed: the run the
        # runner found for the first step stands
        found = r._run
        nxt = logits.argmax(axis=-1).astype(np.int32)
        for seq, tok in zip(seqs, nxt):
            rig.store.extend(seq, int(tok))
        assert np.array_equal(
            np.stack([rig.table(seq) for seq in seqs]), tabs)
        again = np.asarray(r.step_logits(nxt, pos + 1, tabs, seqs=seqs))
        assert r._run is found
        return (seqs, pos, np.stack([logits, again]),
                (after[0] - before[0], after[1] - before[1]))

    shared = Rig(cfg, params, f"g_one_prompt_{backend}", backend=backend)
    first = shared.store.admit(doc + [3, 4])
    shared.prefill(first, doc + [3, 4])
    shared.store.retire(first)
    seqs, pos, got, counted = four_slots(shared)
    assert [seq.prefill_from for seq in seqs] == [9 * T] * 4
    assert len({seq.pages[8].pid for seq in seqs}) == 1
    private = Rig(cfg, params, f"g_private_{backend}", backend=backend)
    seqs_p, _, want, counted_p = four_slots(private)
    assert [seq.prefill_from for seq in seqs_p] == [0] * 4
    assert np.abs(got - want).max() < (TOL if backend is None else 6e-2)
    assert got.argmax(axis=-1).tolist() == want.argmax(axis=-1).tolist()
    assert got.shape == (2, 4, 256)
    # a key block is 8 pages of 16: one block of the nine shared pages
    # is read once for all four slots, the ninth page with each tail
    block = PAGES_PER_STEP * T
    own = sum(-(-int(n) // block) for n in pos)
    assert counted == (((own - 4) + 1) * PAGES_PER_STEP * HELD,
                       4 * PAGES_PER_STEP * HELD)
    assert counted == tuple(HELD * v for v in page_visits(
        pos, np.full((4,), block, np.int32), T))
    assert counted_p == ((own + 1) * PAGES_PER_STEP * HELD, 0)
    for rig, held in ((shared, seqs), (private, seqs_p)):
        for seq in held:
            rig.store.retire(seq, cache=False)
        rig.close()


def test_a_shared_tail_page_is_copied_before_it_is_written(model):
    """Copy-on-write over the latent array: a fork shares a half-full
    tail page; each side's next position copies it first, and both then
    read what a sequence of their own reads."""
    cfg, ref_cfg, params = model
    base = tokens_of(40, seed=8)
    a_toks, b_toks = base + tokens_of(6, seed=9), base + tokens_of(6, seed=10)
    rig = Rig(cfg, params, "g_cow")
    seq = rig.store.admit(base)
    rig.prefill(seq, base)
    rig.decode(seq, base, 40)                  # position 39: all 40 filled
    child = rig.store.fork(seq)
    assert child.pages[-1] is seq.pages[-1] and seq.pages[-1].refs == 2
    rig.store.extend(seq, a_toks[40])
    rig.store.extend(child, b_toks[40])
    assert rig.store.cow.get_value() == 1
    assert child.pages[-1] is not seq.pages[-1]
    got_a = rig.decode(seq, a_toks, 46)
    got_b = rig.decode(child, b_toks, 46, slot=2)
    for got, toks in ((got_a, a_toks), (got_b, b_toks)):
        want, _ = ref.full_logits(params, ref_cfg, toks, block=16, s_max=48)
        assert np.abs(got - want[40:46]).max() < TOL
    rig.close()


def test_bfloat16_weights_agree_with_the_reference_at_the_stated_precision(
        model):
    """``param_dtype="bfloat16"`` (what the chip serves; the toy cell is
    float32): the reference takes every weight's input at bfloat16
    values too, so the two differ by where the absorbed form rounds (the
    query after ``W_UK``, not before) and by an expert choice where that
    moves a near tie."""
    cfg, ref_cfg = configs("bfloat16")
    params = as_drawn(model[2], cfg)
    assert params["layers"][1]["we_gate"].dtype == jnp.bfloat16
    assert params["layers"][1]["router"].dtype == jnp.float32
    toks = tokens_of(80)
    want, _ = ref.full_logits(params, ref_cfg, toks, block=16, s_max=80)
    rig = Rig(cfg, params, "g_bf16")
    seq = rig.store.admit(toks[:50])
    got = np.concatenate([rig.prefill(seq, toks[:50]),
                          rig.decode(seq, toks, 80)])
    err = np.abs(got - want[:80]).max(axis=-1)
    # at these widths (12 to 64 values a sum) one bfloat16 rounding is
    # 0.4 % of a logit, and an expert chosen otherwise at a near tie
    # moves a position by about 1: most positions agree closely
    assert np.median(err) < 5e-2 and (err < 0.2).mean() > 0.85, (
        np.median(err), np.sort(err)[-8:])
    rig.close()


def test_a_step_in_flight_serves_what_steps_in_a_row_serve(model):
    """Through the ``DecodeEngine`` (the path ``Serving.Generate``
    takes): with a step in flight (the runner feeds tokens on the
    device) and with every step completed before the next, the same
    tokens with log-probabilities the reference gives."""
    cfg, ref_cfg, params = model
    row = serve(model, InARow, "g_inrow", [])
    fly = serve(model, Gated, "g_inflight", [])
    assert row["ahead"] == 0 and fly["ahead"] > 0
    assert fly["tokens"] == row["tokens"]
    assert [len(t) for t in fly["tokens"]] == [14, 9, 12]
    assert np.allclose(fly["logprobs"][0], row["logprobs"][0], atol=1e-5)
    for k, n in enumerate((40, 44, 47)):
        seq = tokens_of(n, seed=70 + k) + fly["tokens"][k]
        want, _ = ref.full_logits(params, ref_cfg, seq[:-1], block=16,
                                  s_max=64)
        lp = np.asarray(jax.nn.log_softmax(want, axis=-1))
        for j, tok in enumerate(fly["tokens"][k]):
            assert int(np.argmax(want[n - 1 + j])) == tok
            assert abs(fly["logprobs"][k][j] - lp[n - 1 + j, tok]) < TOL
