"""A described architecture on the normal serving path (ISSUE 32):
MiniCPM-SALA's layer kinds at toy size on the CPU, float32, seeded
weights, held to the plain reference (``benchmarks/harness/
reference_sala.py``: the tests import the benchmark's copy, there is no
second one)."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import brpc_tpu as brpc
from benchmarks.harness import reference_sala as ref
from brpc_tpu.models import hybrid
from brpc_tpu.models.hybrid import (HybridRunner, init_hybrid_params,
                                    make_layered_store)
from brpc_tpu.models.runner import from_hf_config
from brpc_tpu.ops.sparse_attention import select_blocks
from brpc_tpu.serving import DecodeEngine, register_serving
from hybrid_rig import (MAX_PAGES, Gated, InARow, Rig, T, as_drawn,
                        draw_compiles, program_entries, serve, tokens_of)

MIXERS = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] \
    + ["lightning-attn"] * 6 + ["minicpm4", "minicpm4"] \
    + ["lightning-attn"] * 4 + ["minicpm4"] + ["lightning-attn"] * 6 \
    + ["minicpm4"] * 3
# the published keys, at toy widths
HF = {"attention_bias": False, "attn_use_rope": False, "head_dim": 16,
      "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
      "lightning_head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
      "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
      "mixer_types": MIXERS, "num_attention_heads": 4,
      "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
      "rms_norm_eps": 1e-6, "vocab_size": 256, "rope_theta": 10000,
      "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
      "tie_word_embeddings": False, "use_output_gate": True,
      "use_output_norm": True, "attn_use_output_gate": True}
SPARSE = dict(block_size=T, kernel_size=8, kernel_stride=4, topk=6,
              init_blocks=1, window_size=24, dense_len=48)
# lightning, minicpm4: every kind once.  Two of a kind (a second layer's
# index into the K/V and the state) is the toy cell's, at four layers
# (benchmarks/tests/test_sala_cell.py, run from tier-1)
FIRST, HELD = 15, 2


def configs(dtype="float32", **sparse):
    sparse = dict(SPARSE, **sparse)
    cfg = from_hf_config(HF, layers=(FIRST, HELD), sparse=sparse,
                         param_dtype=dtype)
    ref_cfg = dict(HF, num_hidden_layers=HELD,
                   published_num_hidden_layers=32,
                   first_published_layer=FIRST, param_dtype=dtype,
                   assumed={"sparse_config": {"value": sparse}})
    return cfg, ref_cfg


@pytest.fixture(scope="module")
def model():
    # the file's one seeded draw: every call compiles its programs anew
    cfg, ref_cfg = configs()
    return cfg, ref_cfg, init_hybrid_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module", autouse=True)
def entries_before():
    # the programs' compiled entries before this file's first run
    return program_entries()


def test_from_hf_config_gives_the_published_parameter_counts():
    """The catalog row's keys, verbatim: Tentpole 1's counts."""
    row = json.loads(next(
        line for line in open("/opt/skills/guides/model-configs/"
                              "architectures.jsonl")
        if '"MiniCPM-SALA"' in line)) if _catalog() else None
    hf = row["config"] if row else dict(
        HF, hidden_size=4096, num_attention_heads=32, head_dim=128,
        intermediate_size=16384, vocab_size=73448, lightning_nh=32,
        lightning_head_dim=128, dim_model_base=256)
    cfg = from_hf_config(hf, layers=(9, 16))
    counts = cfg.layer_param_counts()
    assert counts["mlp"] == 201_326_592
    assert counts["minicpm4"] == 52_428_800
    assert counts["lightning-attn"] == 83_886_080
    assert counts["embedding"] == 300_843_008
    assert (cfg.n_sparse, cfg.n_linear) == (4, 12)
    assert [i + 9 for i, m in enumerate(cfg.mixer_types)
            if m == "minicpm4"] == [9, 16, 17, 22]
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert cfg.kv_bytes_per_token == 4 * 1024


def _catalog():
    import os
    return os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl")


def test_seeded_weights_are_the_references(model):
    cfg, ref_cfg, params = model
    again = ref.make_params(ref_cfg, 5)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params, again))


def test_prefill_then_decode_equals_the_full_forward_pass(model):
    """A sequence that crosses the toy ``dense_len``: chunked prefill
    (dense and sparse positions) then decode through the cache, logit
    for logit the reference's one forward pass."""
    cfg, ref_cfg, params = model
    toks = tokens_of(150)
    want, _, _ = ref.full_logits(params, ref_cfg, toks, block=16, s_max=160)
    rig = Rig(cfg, params, "t_full")
    seq = rig.store.admit(toks[:100])
    got = rig.prefill(seq, toks[:100])
    assert np.abs(got - want[:99]).max() < 2e-5
    got = rig.decode(seq, toks, 150)
    assert np.abs(got - want[99:150]).max() < 2e-5
    assert rig.runner.dense_positions.get_value() == 48
    assert rig.runner.sparse_positions.get_value() == 150 - 48
    rig.store.retire(seq, cache=False)
    rig.close()


def test_bfloat16_weights_agree_with_the_reference_at_the_stated_precision(
        model):
    """``param_dtype="bfloat16"`` (what the chip serves; the toy cell is
    float32): weights and matmul inputs bfloat16, everything else
    float32.  The reference takes every weight's input at bfloat16
    values too (its departure 2), so the two differ by float32 summation
    order only, and by a block selection where that moves a near tie."""
    cfg, ref_cfg = configs("bfloat16")
    params = as_drawn(model[2], cfg)
    assert params["layers"][0]["wq"].dtype == jnp.bfloat16
    toks = tokens_of(120)
    want, _, _ = ref.full_logits(params, ref_cfg, toks, block=16, s_max=128)
    rig = Rig(cfg, params, "t_bf16")
    seq = rig.store.admit(toks[:80])
    got = np.concatenate([rig.prefill(seq, toks[:80]),
                          rig.decode(seq, toks, 120)])
    err = np.abs(got - want[:120]).max(axis=-1)
    assert np.median(err) < 1e-4 and err.max() < 2e-2, (np.median(err),
                                                        err.max())
    rig.close()


def test_a_warm_request_equals_a_cold_one_logit_for_logit(model):
    """A radix hit restores pages AND state: the second request of a
    document reads the same logits as the first, from its suffix on."""
    cfg, _, params = model
    doc = tokens_of(96, seed=3)
    q1, q2 = tokens_of(9, seed=4), tokens_of(7, seed=5)
    rig = Rig(cfg, params, "t_warm")
    first = rig.store.admit(doc + q1)
    assert first.prefill_from == 0
    rig.prefill(first, doc + q1)
    rig.decode(first, doc + q1 + [7] * 4, len(doc + q1) + 3)
    rig.store.retire(first)
    lay = rig.store.layers
    assert lay.snapshots.get_value() == 1
    cold = Rig(cfg, params, "t_cold")
    prompt = doc + q2
    seq_c = cold.store.admit(prompt)
    logits_c = np.concatenate([
        cold.prefill(seq_c, prompt)[96:],
        cold.decode(seq_c, prompt + [9] * 5, len(prompt) + 4)])
    seq_w = rig.store.admit(prompt)
    assert seq_w.prefill_from == 96 and lay.restores.get_value() == 1
    logits_w = np.concatenate([
        rig.prefill(seq_w, prompt),
        rig.decode(seq_w, prompt + [9] * 5, len(prompt) + 4)])
    assert logits_w.shape == logits_c.shape
    assert np.abs(logits_w - logits_c).max() < 2e-5
    cold.close()
    rig.store.retire(seq_w)
    rig.close()


def test_chunked_prefill_equals_one_chunk(model):
    """32 positions: the file's chunk whole (a page boundary inside it)
    against two of a page."""
    cfg, _, params = model
    toks = tokens_of(33, seed=6)
    one, many = Rig(cfg, params, "t_one"), Rig(cfg, params, "t_many")
    a = one.prefill(one.store.admit(toks), toks)
    seq = many.store.admit(toks)
    b = many.prefill(seq, toks, chunk=16)
    assert np.abs(a - b).max() < 2e-5
    nxt_a = one.decode(one.store.admit(toks[:1]), toks, 1)  # other seq
    assert nxt_a.shape == (1, 256)
    one.close()
    many.close()


def test_sparse_branch_with_topk_over_all_blocks_equals_dense(model):
    """With ``topk`` >= every block the selection is everything: the
    sparse branch must read what the dense branch reads.  90 tokens are
    six blocks, the file's ``topk``: its configuration from position 48
    on against one that never leaves the dense branch."""
    cfg_s, _, params = model
    assert -(-90 // T) <= SPARSE["topk"]
    toks = tokens_of(90, seed=7)
    out, sparse = [], []
    for cfg, name in ((cfg_s, "t_sp"),
                      (configs(dense_len=4096)[0], "t_de")):
        rig = Rig(cfg, params, name)
        seq = rig.store.admit(toks[:60])
        out.append(np.concatenate([rig.prefill(seq, toks[:60]),
                                   rig.decode(seq, toks, 90)]))
        sparse.append(rig.runner.sparse_positions.get_value())
        rig.close()
    assert sparse == [90 - 48, 0]
    assert np.abs(out[0] - out[1]).max() < 2e-5


def test_sparse_steps_counts_the_grid_steps_the_kernel_takes(model,
                                                            monkeypatch):
    """``runner_*_sparse_steps`` is derived on the host from positions
    alone; held here to the length of every work list the device built
    (the kernel interpreted, its grid bound read out through a callback)
    over a prefill with a padded bucket and decode steps with idle
    slots: ``sum max(1, ceil(live pages / 8))`` over the live rows and
    one step a dead row.  ``topk`` 12, so a full row is two steps."""
    from brpc_tpu.ops import sparse_attention as sa
    cfg = configs(topk=12)[0]
    built, real = [], sa.flat_work_list

    def spy(first, count, n_blocks):
        rows, blocks, n = real(first, count, n_blocks)
        jax.debug.callback(lambda v: built.append(int(v)), n)
        return rows, blocks, n
    monkeypatch.setattr(sa, "flat_work_list", spy)
    toks = tokens_of(190, seed=3)
    rig = Rig(cfg, model[2], "t_steps", backend="pallas")
    seq = rig.store.admit(toks[:170])
    rig.prefill(seq, toks[:170])
    rig.decode(seq, toks, 190)
    jax.effects_barrier()
    got = rig.runner.sparse_steps.get_value()
    selected = rig.runner.sparse_selected.get_value()
    rig.close()
    hkv = cfg.n_kv_heads * cfg.n_sparse
    # 169 prefilled positions in six buckets of 32 (23 of padding), then
    # positions 169-189 a step each beside three idle slots
    live = [min(p // T + 1, 12) if p >= 48 else p // T + 1
            for p in range(190)]
    want = hkv * (sum(max(1, -(-n // sa.PAGES_PER_STEP)) for n in live)
                  + 23 + 3 * 21)
    assert got == sum(built) == want
    # pages stepped a page read: whole steps of 8, and the dead rows'
    assert 1.0 < got * sa.PAGES_PER_STEP / (hkv * sum(live)) < 3.0
    assert selected == sum(n for p, n in enumerate(live) if p >= 48)


M = ref.model_cfg(configs()[1])      # the toy as the reference reads it


@jax.jit                    # one program for both seeds, not one an operation
def selections(q, k_all, pos):
    """The reference's selection over the whole context's keys, and the
    system's over the compressed keys the cache would hold of them."""
    want = ref.selected_blocks(M, q, k_all, pos)
    k16 = k_all.reshape(-1, M["stride"], M["hkv"], M["d"]).mean(axis=1)
    kc = (0.5 * (k16[:-1] + k16[1:])).astype(jnp.bfloat16)
    kc = jnp.concatenate([kc, jnp.zeros_like(kc[:1])])       # J = 4 pages
    return want, select_blocks(
        q.reshape(4, M["hkv"], -1, M["d"]),
        jnp.broadcast_to(kc[None], (4,) + kc.shape), pos, page_tokens=T,
        topk=M["topk"], init_blocks=M["init_blocks"], window=M["window"])


@pytest.mark.parametrize("seed", [11, 12])
def test_selected_blocks_equal_the_references(seed):
    """The page tables the system attends to are the reference's
    selection (seeds with no near tie of block scores)."""
    rng = np.random.default_rng(seed)
    k_all = jnp.asarray(rng.normal(size=(128, M["hkv"], M["d"])), jnp.float32)
    k_all = k_all.astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, M["h"], M["d"])), jnp.float32)
    want, got = map(np.asarray, selections(
        q, k_all, jnp.asarray([55, 77, 100, 127])))
    for i in range(4):
        for g in range(M["hkv"]):
            assert set(got[i, g][got[i, g] >= 0].tolist()) \
                == set(np.flatnonzero(want[i, g]).tolist())


def test_the_cache_frees_a_sequences_pages_and_state_and_keeps_a_documents(
        model):
    """Two kinds of state under one manager: retiring a request frees
    its own pages and its state row; the shared document's pages and
    snapshot stay, and clearing the store returns everything."""
    cfg, _, params = model
    doc = tokens_of(64, seed=8)
    rig = Rig(cfg, params, "t_free", rows=5)
    lay, pool = rig.store.layers, rig.store.pagepool
    first = rig.store.admit(doc + tokens_of(6, seed=9))
    rig.prefill(first, first.tokens)
    rig.store.retire(first)
    assert pool.pages_in_use() == 4 and lay.rows_free() == 4  # the snapshot
    seqs = [rig.store.admit(doc + tokens_of(5 + i, seed=20 + i))
            for i in range(3)]
    assert [s.prefill_from for s in seqs] == [64, 64, 64]
    assert pool.pages_in_use() == 4 + 3 and lay.rows_free() == 1
    assert len({s.state_row for s in seqs}) == 3
    for s in seqs:
        rig.prefill(s, s.tokens)
        rig.store.retire(s)
    assert pool.pages_in_use() == 4 and lay.rows_free() == 4
    with pytest.raises(NotImplementedError):
        rig.store.fork(live := rig.store.admit(doc + [1, 2, 3]))
    rig.store.clear()                  # the live sequence pins the document
    assert pool.pages_in_use() == 5 and lay.rows_free() == 3
    rig.store.retire(live, cache=False)
    rig.store.clear()
    assert pool.pages_in_use() == 0 and lay.rows_free() == 5
    rig.close()


def test_a_snapshot_that_was_evicted_counts_and_falls_back_to_prefill(model):
    cfg, _, params = model
    doc = tokens_of(64, seed=30)
    rig = Rig(cfg, params, "t_evict", rows=3)
    first = rig.store.admit(doc + [5, 6, 7])
    rig.prefill(first, first.tokens)
    rig.store.retire(first)
    assert rig.store.probe(doc + [1, 2]) == 64
    rig.store.clear()                       # snapshot goes with its node
    assert rig.store.layers.rows_free() == 3
    again = rig.store.admit(doc + [5, 6, 7])
    assert again.prefill_from == 0
    rig.store.retire(again, cache=False)
    rig.close()


class _Collect:
    def __init__(self):
        self.msgs, self.done = [], threading.Event()

    def on_received_messages(self, stream, messages):
        for m in messages:
            d = json.loads(m)
            self.msgs.append(d)
            if d.get("done"):
                self.done.set()

    def on_idle_timeout(self, stream):
        pass

    def on_closed(self, stream):
        self.done.set()


def test_generate_with_and_without_logprobs(model):
    """``Serving.Generate`` through ``register_serving`` and the engine:
    with ``"logprobs": true`` every message carries the served token's
    log-probability (the reference's, teacher-forced); without the key
    the stream is as it was.  The second request is a radix hit."""
    cfg, ref_cfg, params = model
    store = make_layered_store(cfg, cache_pages=64, state_rows=8,
                               name="t_gen")
    runner = HybridRunner(params, cfg, store=store, name="t_gen")
    engine = DecodeEngine(runner=runner, num_slots=4, store=store,
                          max_pages_per_slot=MAX_PAGES,
                          prefill_buckets=(16, 32), name="t_gen")
    server = brpc.Server()
    register_serving(server, engine=engine)
    server.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=60000,
                      max_retry=0)
    doc = tokens_of(80, seed=40)

    def generate(prompt, **extra):
        col, cntl = _Collect(), brpc.Controller()
        brpc.stream_create(cntl, col)
        resp = ch.call_sync("Serving", "Generate",
                            {"prompt": prompt, "max_new_tokens": 6,
                             **extra}, serializer="json", cntl=cntl)
        assert col.done.wait(60)
        assert "error" not in col.msgs[-1], col.msgs[-1]
        return resp, col.msgs[:-1]

    try:
        _, plain = generate(doc + [3, 4, 5])
        assert all(set(m) == {"token"} for m in plain) and len(plain) == 6
        resp, with_lp = generate(doc + [3, 4, 5], logprobs=True)
        assert resp["prefix_hit"] == 80
        assert [m["token"] for m in with_lp] == [m["token"] for m in plain]
        assert all(set(m) == {"token", "logprob"} for m in with_lp)
        row = doc + [3, 4, 5] + [m["token"] for m in with_lp]
        want, _, _ = ref.full_logits(params, ref_cfg, row[:-1], block=16,
                                     s_max=96)
        lp = np.asarray(jax.nn.log_softmax(want, axis=-1))
        for j, m in enumerate(with_lp):
            at = len(doc) + 3 + j - 1
            assert abs(m["logprob"] - lp[at, m["token"]]) < 2e-5
            assert int(np.argmax(want[at])) == m["token"]
    finally:
        server.stop()
        server.join()
        engine.close()
        runner.close()
        store.close()


def test_a_step_in_flight_serves_what_steps_in_a_row_serve(model):
    """The engine over a ``HybridRunner`` keeps one step in flight (the
    runner feeds tokens on the device); over the same runner saying it
    cannot, it runs ``step()``'s two halves in a row.  Same tokens and
    log-probabilities to the bit, same cache arrays, same counters; and
    the second run, in which the "take the token from the step before"
    flag takes both values, compiles nothing: one program."""
    import jax.monitoring
    compiles = []
    on = [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(kw.get("fun_name"))
        if on[0] and event == "/jax/core/compile/backend_compile_duration"
        else None)
    try:
        row = serve(model, InARow, "t_inrow", compiles)
        fly = serve(model, Gated, "t_inflight", compiles)
    finally:
        on[0] = False
    assert row["ahead"] == 0 and fly["ahead"] >= fly["steps"] - 2 > 0
    assert row["steps"] == fly["steps"]         # no surplus step
    assert fly["tokens"] == row["tokens"]
    assert [len(t) for t in fly["tokens"]] == [14, 9, 12]
    assert fly["logprobs"] == row["logprobs"]       # bit for bit
    for a, b in zip(fly["arrays"], row["arrays"]):
        assert np.array_equal(a, b)
    assert fly["counters"] == row["counters"]
    assert fly["counters"][0] > 0 and fly["counters"][1] > 0
    assert fly["compiled"] == [], fly["compiled"]


def test_set_up_compiles_a_draw_a_kind_of_block_and_a_program_a_shape(
        model, entries_before):
    """What set-up compiles: ONE seeded draw a distinct (mixer,
    feed-forward) pair and one of the embedding and head, whatever the
    depth; and each of the two programs once a shape and static value
    this file ran.  A program a layer, or a static argument that hashes
    anew each call, shows here first.  The file's LAST test: it counts
    what the tests before it compiled."""
    cfg = model[0]
    names = draw_compiles(cfg)
    assert len(names) == 3 == len(set(hybrid.layer_kinds(cfg))) + 1, \
        names
    assert program_entries(entries_before) == {"step": 5, "prefill": 9}
