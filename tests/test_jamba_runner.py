"""Jamba's layer kinds on the normal serving path (ISSUE 38): the Mamba-1
state-space mixer (scan state and convolution tail in the sequence's
state row) and full attention over the layered cache in the SAME layer
loop as the other served models' mixers, at toy widths on the CPU,
float32, seeded weights, held to the plain reference
(``benchmarks/harness/reference_jamba.py``: the tests import the
benchmark's copy, there is no second one)."""
import json
import os

import jax
import numpy as np
import pytest

from benchmarks.harness import reference_jamba as ref
from brpc_tpu.models import hybrid
from brpc_tpu.models.hybrid import init_hybrid_params
from brpc_tpu.models.runner import from_hf_config
from hybrid_rig import Rig, T, tokens_of

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the published keys (the catalog row's, verbatim)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
# ... at toy widths, three layers: Mamba, attention, Mamba (the second
# Mamba layer's index into the state row, every kind at least once); 128
# channels (one lane tile), 8 state values, 4 query heads on ONE K/V head
HF = dict(PUBLISHED, hidden_size=64, intermediate_size=128,
          num_attention_heads=4, num_key_value_heads=1, mamba_d_state=8,
          mamba_dt_rank=8, vocab_size=256, num_hidden_layers=3,
          attn_layer_period=2, attn_layer_offset=1)
# float32 weights over a bfloat16 K/V cache: a key that the two sides
# round to neighbouring bfloat16s moves a logit by about 1e-4
TOL = 5e-4


@pytest.fixture(scope="module")
def model():
    # the file's one seeded draw: every call compiles its programs anew
    cfg = from_hf_config(HF, param_dtype="float32")
    ref_cfg = dict(HF, param_dtype="float32")
    return cfg, ref_cfg, init_hybrid_params(cfg, jax.random.PRNGKey(5))


def test_from_hf_config_gives_the_published_kinds_and_counts():
    """The catalog row's keys, verbatim: attention at published layers 7
    and 21, and the issue's arithmetic (3,029 M)."""
    hf = PUBLISHED
    if os.path.exists(CATALOG):
        hf = json.loads(next(line for line in open(CATALOG)
                             if '"AI21-Jamba2-3B"' in line))["config"]
        assert hf == PUBLISHED
    cfg = from_hf_config(hf)
    assert [i for i, m in enumerate(cfg.mixer_types)
            if m == "attention"] == [7, 21]
    assert (cfg.n_mamba, cfg.n_attention, cfg.n_kv_layers) == (26, 2, 2)
    assert (cfg.head_dim, cfg.ssm_inner, cfg.tie_embeddings) \
        == (128, 5120, True)
    c = cfg.layer_param_counts()
    assert c["mamba"] == 41_241_792                     # 41.24 M
    assert c["mlp"] == 62_914_560                       # 62.91 M
    assert c["attention"] + c["mlp"] == 76_677_120      # 76.68 M
    assert c["embedding"] == 167_772_160                # 167.8 M, once
    assert 26 * (c["mamba"] + c["mlp"]) + 2 * (c["attention"] + c["mlp"]) \
        + c["embedding"] == 3_029_191_552               # 6.06 GB bf16
    assert cfg.kv_bytes_per_token == 2 * 2 * 128 * 2 == 1024
    shapes = hybrid.layer_shapes(cfg, "mamba")
    n = sum(int(np.prod(s)) for name, (s, _) in shapes.items()
            if not name.startswith(("norm", "w_gate", "w_up", "w_down")))
    assert n == c["mamba"]
    spec = hybrid.layered_spec(cfg, 96)
    assert spec.state_layer_shape == (24, 5120)
    assert spec.state_row_bytes() == 26 * 24 * 5120 * 4
    assert (spec.n_sparse, spec.compressed) == (2, False)
    assert "head" not in hybrid.top_shapes(cfg)


def test_an_undescribed_setting_raises():
    for key, value in (("num_experts", 2), ("sliding_window", 4096),
                       ("hidden_act", "gelu"), ("mamba_proj_bias", True)):
        with pytest.raises(ValueError, match=key):
            from_hf_config(dict(PUBLISHED, **{key: value}))
    with pytest.raises(ValueError, match="layers"):
        from_hf_config(PUBLISHED, layers=(20, 9))
    with pytest.raises(ValueError, match="model_type"):
        from_hf_config(dict(PUBLISHED, model_type="jamba2"))


def test_seeded_weights_are_the_references(model):
    cfg, ref_cfg, params = model
    again = ref.make_params(ref_cfg, 5)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params, again))
    mixer = params["layers"][0]
    assert np.allclose(np.asarray(mixer["a_log"])[:, 7],
                       np.log(np.arange(1, 9)), rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(mixer["b_dt"], np.float64)))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_prefill_then_decode_equals_the_full_forward_pass(model, backend):
    """Chunked prefill (a cut at the snapshot boundary, the last chunk
    padded) then decode through the cache, logit for logit the
    reference's one forward pass; with the kernels interpreted too."""
    cfg, ref_cfg, params = model
    toks = tokens_of(90)
    want, _ = ref.full_logits(params, ref_cfg, toks, block=16, s_max=96)
    rig = Rig(cfg, params, f"j_full_{backend}", backend=backend)
    seq = rig.store.admit(toks[:60])
    got = np.concatenate([rig.prefill(seq, toks[:60]),
                          rig.decode(seq, toks, 90)])
    assert np.abs(got - want[:90]).max() < TOL
    r = rig.runner
    assert r.mamba_tokens.get_value() == 59
    assert r.mamba_steps.get_value() == 31
    assert rig.store.layers.snapshots.get_value() == 1      # at 48
    rig.store.retire(seq, cache=False)
    rig.close()


def test_a_hit_on_a_snapshot_equals_a_cold_prefill(model):
    """A radix hit restores pages AND the row (scan state and tail of
    both Mamba layers): the second request on a shared prompt reads the
    logits of a cold one, from its suffix on."""
    cfg, _, params = model
    shared = tokens_of(64, seed=3)
    q1, q2 = tokens_of(9, seed=4), tokens_of(23, seed=5)
    rig = Rig(cfg, params, "j_warm")
    first = rig.store.admit(shared + q1)
    rig.prefill(first, shared + q1)
    rig.decode(first, shared + q1 + [7] * 4, len(shared + q1) + 3)
    rig.store.retire(first)
    lay = rig.store.layers
    assert lay.snapshots.get_value() == 1
    cold = Rig(cfg, params, "j_cold")
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
    assert lay.snapshots.get_value() == 2                   # q2's, at 80
    cold.close()
    rig.store.retire(seq_w)
    rig.close()


def test_a_sequence_in_a_row_another_just_left_equals_the_same_alone(model):
    """``fresh_state``: a request's first token depends on its own
    prompt only.  One row: a sequence is admitted into the row another
    just left, with no prefix to hit, and reads the logits it reads in
    an untouched cache."""
    cfg, _, params = model
    rig = Rig(cfg, params, "j_reuse", rows=1)
    other = rig.store.admit(tokens_of(40, seed=6))
    rig.prefill(other, other.tokens)
    rig.decode(other, other.tokens + [3] * 3, 42)
    row = other.state_row
    rig.store.retire(other, cache=False)
    assert np.abs(np.asarray(rig.store.layers.state)[row]).max() > 0
    prompt = tokens_of(30, seed=7)
    seq = rig.store.admit(prompt)
    assert seq.state_row == row and seq.prefill_from == 0
    got = np.concatenate([rig.prefill(seq, prompt),
                          rig.decode(seq, prompt + [5] * 4, 33)])
    alone = Rig(cfg, params, "j_alone", rows=1)
    seq_a = alone.store.admit(prompt)
    want = np.concatenate([alone.prefill(seq_a, prompt),
                           alone.decode(seq_a, prompt + [5] * 4, 33)])
    assert (got == want).all()
    alone.close()
    rig.store.retire(seq, cache=False)
    rig.close()


def test_a_live_sequence_takes_a_row_before_a_snapshot_does(model):
    """Three rows: two retired requests leave their snapshots with the
    radix tree, a third prefill finds no row to snapshot into (counted,
    its prefix is not cached), and an admission that finds every row
    held by snapshots evicts cached prefixes until one is free."""
    cfg, _, params = model
    rig = Rig(cfg, params, "j_rows", rows=3)
    lay = rig.store.layers
    for seed in (10, 11):
        seq = rig.store.admit(tokens_of(20, seed=seed))
        rig.prefill(seq, seq.tokens)
        rig.store.retire(seq)
    assert lay.rows_free() == 1 and lay.snapshots.get_value() == 2
    third = rig.store.admit(tokens_of(20, seed=12))
    rig.prefill(third, third.tokens)
    assert lay.snapshot_no_row.get_value() == 1 and lay.rows_free() == 0
    fourth = rig.store.admit(tokens_of(20, seed=13))    # evicts to admit
    assert fourth.state_row is not None
    assert rig.store.evictions.get_value() > 0
    for s in (third, fourth):
        rig.store.retire(s, cache=False)
    rig.store.clear()
    assert lay.rows_free() == 3 and rig.store.pagepool.pages_in_use() == 0
    rig.close()


def test_verify_keeps_raising(model):
    cfg, _, params = model
    rig = Rig(cfg, params, "j_verify")
    with pytest.raises(NotImplementedError, match="recurrent state"):
        rig.runner.verify(None, None, None, None, None)
    assert T == 16
    rig.close()
