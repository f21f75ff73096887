"""Nemotron 3's block kinds on the normal serving path (ISSUE 40): the
Mamba-2 mixer (packed scan state and convolution tail in the sequence's
state row), attention with two K/V heads, latent experts of which a
SHARE is held, blocks of ONE sublayer and a sliced vocabulary, in the
SAME layer loop as the other served models, at toy widths on the CPU,
float32, seeded weights, held to the plain reference
(``benchmarks/harness/reference_nemotron.py``: the tests import the
benchmark's copy, there is no second one)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_nemotron as ref
from brpc_tpu.models import hybrid
from brpc_tpu.models.hybrid import init_hybrid_params
from brpc_tpu.models.runner import from_hf_config
from hybrid_rig import Rig, tokens_of

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the published keys (the catalog row's, verbatim)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
# ... at toy widths, five blocks ``ME*ME`` (the second Mamba-2 block's
# index into the state row, every kind at least once): 4 Mamba-2 heads of
# 64 in 2 groups (a tile of two heads a group), 32 state values, blocks of
# 16 positions; 4 query heads on 2 K/V heads; 16 experts top-4 in a
# latent width of 32, of which this chip holds experts 4-7; a quarter of
# a vocabulary of 1,024
HF = dict(PUBLISHED, hidden_size=128, head_dim=32, num_attention_heads=4,
          mamba_num_heads=4, n_groups=2, ssm_state_size=32, chunk_size=16,
          hybrid_override_pattern="ME*ME", num_hidden_layers=5,
          intermediate_size=48, moe_intermediate_size=48, moe_latent_size=32,
          moe_shared_expert_intermediate_size=96, n_routed_experts=16,
          num_experts_per_tok=4, vocab_size=1024)
CUT = dict(experts=(4, 4), vocab=(0, 256))
# what the reference reads of the same cut (the configuration file's form)
REF = dict(HF, n_routed_experts=4, first_expert_held=4,
           published_n_routed_experts=16, vocab_size=256,
           param_dtype="float32")
# float32 weights over a bfloat16 K/V cache: a key that the two sides
# round to neighbouring bfloat16s moves a logit by about 1e-4
TOL = 5e-4


@pytest.fixture(scope="module")
def model():
    # the file's one seeded draw: every call compiles its programs anew
    cfg = from_hf_config(HF, param_dtype="float32", **CUT)
    return cfg, init_hybrid_params(cfg, jax.random.PRNGKey(5))


def test_from_hf_config_gives_the_published_kinds_and_counts():
    """The catalog row's keys, verbatim: blocks 0-10 are ``MEMEMEM*EME``,
    the issue's arithmetic of the WHOLE published model (120.67 B, 12.77
    B a token) and of this chip's share (4,648 M)."""
    hf = PUBLISHED
    if os.path.exists(CATALOG):
        hf = json.loads(next(
            line for line in open(CATALOG)
            if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line))["config"]
        assert hf == PUBLISHED
    whole = from_hf_config(hf)
    kinds = hybrid.layer_kinds(whole)
    assert (whole.n_mamba2, whole.n_attention, whole.n_moe) == (40, 8, 40)
    c = whole.layer_param_counts()
    assert c["mamba2"] == 109_635_968                   # 109.64 M less norm
    assert c["attention"] == 35_651_584                 # 35.66 M less norm
    assert c["latent_moe"] - 512 * 2 * 1024 * 2688 == 54_526_464  # 54.53 M
    total = sum(c[m] for m, _ in kinds if m != "none") \
        + sum(c[f] for _, f in kinds if f != "none") \
        + 2 * c["embedding"] + (len(kinds) + 1) * 4096
    assert round(total / 1e9, 2) == 120.67
    active = total - 40 * (512 - 22) * 2 * 1024 * 2688
    assert round(active / 1e9, 2) == 12.77
    cfg = from_hf_config(hf, layers=(0, 11), experts=(0, 128),
                         vocab=(0, 32768))
    letters = {("mamba2", "none"): "M", ("attention", "none"): "*",
               ("none", "latent_moe"): "E"}
    assert "".join(letters[k] for k in hybrid.layer_kinds(cfg)) \
        == "MEMEMEM*EME"
    assert (cfg.vocab, cfg.experts_held, cfg.n_experts) \
        == (32768, (0, 128), 512)
    assert (cfg.ssd_inner, cfg.ssd_channels, cfg.tie_embeddings) \
        == (8192, 10240, False)
    held = sum(int(np.prod(s)) for kind in hybrid.layer_kinds(cfg)
               for s, _ in hybrid.layer_shapes(cfg, *kind).values()) \
        + sum(int(np.prod(s)) for s, _ in hybrid.top_shapes(cfg).values())
    assert round(held / 1e6) == 4648                    # 9.30 GB bf16
    shapes = hybrid.layer_shapes(cfg, "mamba2", "none")
    assert "norm2" not in shapes and shapes["w_in"][0] == (4096, 18560)
    assert sum(int(np.prod(s)) for name, (s, _) in shapes.items()
               if name != "norm1") == c["mamba2"]
    assert "norm1" not in hybrid.layer_shapes(cfg, "none", "latent_moe")
    assert cfg.kv_bytes_per_token == 1 * 2 * 2 * 128 * 2 == 1024
    spec = hybrid.layered_spec(cfg, 144)
    assert spec.state_layer_shape == (8192 + 256, 128)
    assert spec.state_row_bytes() == 5 * 8448 * 128 * 4     # 21.6 MB
    assert (spec.n_sparse, spec.n_linear, spec.compressed) == (1, 5, False)


def test_an_undescribed_setting_raises():
    dense = PUBLISHED["hybrid_override_pattern"].replace("E", "-", 1)
    with pytest.raises(ValueError, match="dense feed-forward"):
        from_hf_config(dict(PUBLISHED, hybrid_override_pattern=dense),
                       layers=(0, 11))
    for key, value in (("sliding_window", 4096), ("n_group", 2),
                       ("moe_shared_expert_overlap", True),
                       ("mamba_proj_bias", True), ("mlp_bias", True),
                       ("mlp_hidden_act", "silu")):
        with pytest.raises(ValueError, match=key):
            from_hf_config(dict(PUBLISHED, **{key: value}))
    with pytest.raises(ValueError, match="experts"):
        from_hf_config(PUBLISHED, experts=(500, 128))
    with pytest.raises(ValueError, match="vocabulary"):
        from_hf_config(PUBLISHED, vocab=(0, 200000))
    with pytest.raises(ValueError, match="model_type"):
        from_hf_config(dict(PUBLISHED, model_type="nemotron"))


def test_seeded_weights_are_the_references(model):
    cfg, params = model
    again = ref.make_params(REF, 5)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params, again))
    mixer = params["layers"][0]
    a = np.exp(np.asarray(mixer["a_log"], np.float64))
    assert a.shape == (4,) and 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(mixer["b_dt"], np.float64)))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001
    assert params["head"].shape == (256, 128)
    assert params["layers"][1]["we_up"].shape == (4, 32, 48)
    assert params["layers"][1]["router"].shape == (128, 16)


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_prefill_then_decode_equals_the_full_forward_pass(model, backend):
    """Chunked prefill (a cut at the snapshot boundary, the last chunk
    padded, two blocks of the dual form a chunk) then decode through the
    cache, logit for logit the reference's one forward pass over the
    held vocabulary; with the kernels interpreted too."""
    cfg, params = model
    toks = tokens_of(90)
    want, _ = ref.full_logits(params, REF, toks, block=16, s_max=96)
    rig = Rig(cfg, params, f"n_full_{backend}", backend=backend)
    seq = rig.store.admit(toks[:60])
    got = np.concatenate([rig.prefill(seq, toks[:60]),
                          rig.decode(seq, toks, 90)])
    assert got.shape == (90, 256)
    assert np.abs(got - want[:90]).max() < TOL
    r = rig.runner
    assert r.ssd_tokens.get_value() == 59
    assert r.ssd_steps.get_value() == 31
    # two expert blocks, four choices a position; a quarter of the
    # experts are held, and about that share of the routing falls here
    assert r.moe_assignments.get_value() == 90 * 4 * 2
    assert 0.1 < r.moe_assignments_held.get_value() / (90 * 4 * 2) < 0.4
    assert 0 < r.moe_experts_hit.get_value() <= 31 * 2 * 4
    assert rig.store.layers.snapshots.get_value() == 1      # at 48
    rig.store.retire(seq, cache=False)
    rig.close()


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_kernel_calls_counts_a_block_a_step_and_a_block_a_chunk(model,
                                                                backend):
    """``runner_*_moe_kernel_calls``: the expert-block calls that went
    through the ``expert_ffn`` kernel, two blocks here a decode step and
    a prefill chunk alike where the kernels run (interpreted), none over
    ``lax.ragged_dot``; ``_moe_tile_rows`` the rows it multiplied for
    them: whole row tiles, at least the assignments that were held."""
    from brpc_tpu.ops.moe import ROW_TILE
    cfg, params = model
    toks = tokens_of(50, seed=8)
    rig = Rig(cfg, params, f"n_calls_{backend}", backend=backend)
    r = rig.runner
    chunks, run = [], r.prefill
    r.prefill = lambda *a, **kw: chunks.append(1) or run(*a, **kw)
    seq = rig.store.admit(toks[:40])
    rig.prefill(seq, toks[:40])             # 0-31, the cut at 32, 32-38
    rig.decode(seq, toks, 45)               # positions 39 .. 44
    assert len(chunks) == 2
    calls, rows = r.moe_kernel_calls.get_value(), r.moe_tile_rows.get_value()
    held = r.moe_assignments_held.get_value()
    assert 0 < held < 45 * 4 * 2
    if backend is None:
        assert (calls, rows) == (0, 0)
    else:
        assert calls == cfg.n_moe * (6 + 2) == 16
        assert rows % ROW_TILE == 0 and held <= rows
        # a group of a toy chunk never fills two tiles: at most two
        # visits an expert held (4) a block a program
        assert rows <= calls * 4 * 2 * ROW_TILE
    rig.store.retire(seq, cache=False)
    rig.close()


def test_a_hit_on_a_snapshot_equals_a_cold_prefill(model):
    """A radix hit restores pages AND the row (packed scan state and
    tail of both Mamba-2 blocks): the second request on a shared prompt
    reads the logits of a cold one, from its suffix on."""
    cfg, params = model
    shared = tokens_of(64, seed=3)
    q1, q2 = tokens_of(9, seed=4), tokens_of(23, seed=5)
    rig = Rig(cfg, params, "n_warm")
    first = rig.store.admit(shared + q1)
    rig.prefill(first, shared + q1)
    rig.decode(first, shared + q1 + [7] * 4, len(shared + q1) + 3)
    rig.store.retire(first)
    lay = rig.store.layers
    assert lay.snapshots.get_value() == 1
    cold = Rig(cfg, params, "n_cold")
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
    """``fresh_state``: a request's first token depends on its own
    prompt only.  One row: a sequence is admitted into the row another
    just left, with no prefix to hit, and reads the logits it reads in
    an untouched cache."""
    cfg, params = model
    rig = Rig(cfg, params, "n_reuse", rows=1)
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
    alone = Rig(cfg, params, "n_alone", rows=1)
    seq_a = alone.store.admit(prompt)
    want = np.concatenate([alone.prefill(seq_a, prompt),
                           alone.decode(seq_a, prompt + [5] * 4, 33)])
    assert (got == want).all()
    alone.close()
    rig.store.retire(seq, cache=False)
    rig.close()


def test_four_shares_of_an_expert_block_add_up_to_the_uncut_block():
    """A chip that holds experts ``[4 i, 4 i + 4)`` routes over all 16
    and computes its own experts' part in the latent width, through the
    projection up; the four parts, with the shared expert (which every
    chip computes alike) counted ONCE, are the uncut reference's block."""
    whole_cfg = dict(HF, param_dtype="float32")
    m = ref.model_cfg(whole_cfg)
    p = ref.make_params(whole_cfg, 11)["layers"][1]
    assert p["we_up"].shape == (16, 32, 48)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(24, 128)),
                    jnp.float32)
    valid = jnp.ones((24,), bool)
    total, seen = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            share_cfg = from_hf_config(HF, experts=(4 * i, 4),
                                       param_dtype="float32")
            mine = dict(p, **{k: p[k][4 * i:4 * i + 4]
                              for k in ("we_up", "we_down")})
            y, shared, sizes = hybrid.moe_share(mine, x, share_cfg, valid,
                                                "latent_moe")
            assert sizes.shape == (4,)
            total, seen = total + y, seen + int(sizes.sum())
        want = ref.experts_ffn(m, p, x)
    assert seen == 24 * 4                       # every assignment, once
    assert np.abs(np.asarray(total + shared) - np.asarray(want)).max() < 2e-5
    # a share alone is not the block
    assert np.abs(np.asarray(y + shared) - np.asarray(want)).max() > 1e-3
