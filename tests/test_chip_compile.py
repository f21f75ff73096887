"""The main path's device programs, compiled for a v5e that is described
and not attached (guide on-chip-measurement, section 2, rehearsal 3).

Interpret mode hides what the chip's compiler refuses: the paged kernel
passed every interpret-mode test for eleven PRs and was refused by
Mosaic at every shape.  These compiles cost 75 s alone (the five whole
programs 8 to 22 s each, the ten kernel-only tests 8 s together)
and no chip time, and they guard every later PR.  The file runs LAST
(``conftest.pytest_collection_modifyitems``): it is what a run that
outgrows the driver's limit loses first.  A compile that passes is not
a chip run: results and times come from ``chip_smoke.py`` on the chip.

The topology is described inside a fixture, after a test of this file
has started — never at import, in a ``skipif`` or in a ``parametrize``
argument — because only one process may load the TPU's library.  All
of these tests live in this one file for the same reason.
"""
import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from brpc_tpu.models.runner import _jits, init_runner_params
from brpc_tpu.ops.attention import flash_attention
from brpc_tpu.ops.paged_attention import paged_attention_pallas

PAGE_TOKENS = chip_smoke.SERVING_SIZES["page_tokens"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct on the first described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    return make


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and compiles again): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _calls(text: str, kernel: str) -> int:
    """Mosaic calls of the kernel named ``kernel`` in a compiled
    program's text."""
    import re
    return len(re.findall(
        rf"%{kernel}[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))


@pytest.mark.parametrize("n_kv_heads", [16, 4])
def test_paged_kernel_lowers_at_serving_widths(sds, n_kv_heads):
    """(H 16, Hkv 16, D 128) is what the smoke serves; (16, 4, 128) is
    the GQA shape.  Both lower, so the dispatcher needs no refusal."""
    n, h, d, pages, max_pages = 8, 16, 128, 256, 64
    kv = sds((pages, PAGE_TOKENS, n_kv_heads, d), jnp.float32)
    compiled = jax.jit(functools.partial(
        paged_attention_pallas, interpret=False)).lower(
            sds((n, h, d), jnp.float32), kv, kv,
            sds((n, max_pages), jnp.int32), sds((n,), jnp.int32)).compile()
    assert _has_kernel(compiled)


def test_paged_kernel_lowers_with_local_block(sds):
    """The speculative-verify form: 8 slots x 5 rows, a local key block
    merged outside the kernel."""
    s, k1, h, d, pages, max_pages = 8, 5, 16, 128, 256, 64
    kv = sds((pages, PAGE_TOKENS, h, d), jnp.float32)
    local = sds((s, k1, h, d), jnp.float32)

    def verify(q, k, v, tables, lengths, lk, lv, mask):
        return paged_attention_pallas(q, k, v, tables, lengths,
                                      local_k=lk, local_v=lv,
                                      local_mask=mask, interpret=False)
    compiled = jax.jit(verify).lower(
        sds((s * k1, h, d), jnp.float32), kv, kv,
        sds((s * k1, max_pages), jnp.int32), sds((s * k1,), jnp.int32),
        local, local, sds((s, k1, k1), jnp.bool_)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_lowers_at_2048(sds, dtype):
    x = sds((1, 2048, 16, 128), dtype)
    compiled = jax.jit(functools.partial(
        flash_attention, causal=True, interpret=False)).lower(
            x, x, x).compile()
    assert _has_kernel(compiled)


def test_flash_kernel_lowers_padded_and_at_full_precision(sds):
    """The dense reference's shape: one padded 128-token block, traced
    under the model's float32 matmul precision."""
    x = sds((1, 100, 16, 128), jnp.float32)

    def attend(q, k, v):
        with jax.default_matmul_precision("highest"):
            return flash_attention(q, k, v, causal=True, interpret=False)
    compiled = jax.jit(attend).lower(x, x, x).compile()
    assert _has_kernel(compiled)


def test_rail_programs_compile_at_a_4mb_block(sds):
    """The rail's stage / unstage / slice / splice / copy programs at the
    stream's chunk size (ici/block_pool.py, ici/endpoint.py, rail.py)."""
    from brpc_tpu.ici import block_pool, endpoint, rail
    nbytes = 4 * 1024 * 1024
    chunk = sds((nbytes // 4,), jnp.float32)
    raw = sds((nbytes,), jnp.uint8)
    offset = sds((), jnp.int32)
    block_pool._stage.lower(chunk, nbytes).compile()
    block_pool._unstage.lower(raw, "float32", (nbytes // 4,)).compile()
    block_pool._slice_bytes.lower(raw, offset, 64 * 1024).compile()
    block_pool._splice_bytes.lower(
        raw, sds((64 * 1024,), jnp.uint8), offset).compile()
    endpoint._device_copy.lower(chunk).compile()
    endpoint._multi_copy.lower(*[chunk] * 4).compile()
    rail._slice_chunk.lower(raw, offset, 2 * 1024 * 1024).compile()
    rail._cat.lower([raw, raw]).compile()


def test_full_width_decode_step_compiles_and_fits(sds, monkeypatch):
    """The runner's ``step`` at the smoke's shapes, kernel included.  Its
    memory analysis is what ``chip_smoke.CACHE_BLOCKS`` was sized from
    (the temporaries are ~5x the cache, ROADMAP S1).

    Three quarters of the 16-layer compile are the six bfloat16 passes
    of each float32 product, arithmetic and no buffer (temporaries 2.690
    GB at one pass against 2.692, PR 36; arguments and outputs are the
    same).  So the 16 layers are sized at one pass, and the kernel as the
    chip traces it (``highest`` reaches its dots) compiles at two."""
    # the dispatcher asks jax which backend is live; steer it to the
    # branch the chip takes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots = chip_smoke.SERVING_SIZES["num_slots"]
    max_pages = chip_smoke.SERVING_SIZES["max_pages_per_slot"]

    def step(cfg):
        page_bytes = PAGE_TOKENS * cfg.kv_bytes_per_token
        params = {k: sds(v.shape, v.dtype) for k, v in jax.eval_shape(
            lambda: init_runner_params(cfg)).items()}
        return _jits()["step"].lower(
            params, sds((slots,), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, max_pages), jnp.int32),
            sds((chip_smoke.CACHE_BLOCKS, page_bytes), jnp.uint8),
            cfg=cfg, page_tokens=PAGE_TOKENS, backend=None,
            mesh=None).compile(), chip_smoke.CACHE_BLOCKS * page_bytes

    cfg = chip_smoke.full_width_config()
    compiled, _ = step(dataclasses.replace(cfg, n_layers=2))
    assert _has_kernel(compiled)
    with monkeypatch.context() as one_pass:
        one_pass.setattr(jax, "default_matmul_precision",
                         lambda _: contextlib.nullcontext())
        compiled, cache = step(cfg)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes + cache
    assert need <= (1.0 - chip_smoke.MEMORY_HEADROOM) * 16 * 2**30, \
        f"decode step needs {need / 1e9:.2f} GB of a 16 GiB chip"


# ---- MiniCPM-SALA (ISSUE 32): the new kernels and the hybrid step -------

def test_sparse_attend_and_state_kernels_lower_at_published_widths(sds):
    """16 query heads a K/V head over 64 selected pages of 64 x 128
    bf16, the two shapes the programs call it at (decode: 16 rows; a
    prefill call: 128), eight pages a grid step over a work list whose
    length is the grid's DYNAMIC bound (ISSUE 37): one Mosaic kernel a
    call, the list built by plain XLA beside it; and the lightning state
    of 8 slots updated in place."""
    from brpc_tpu.ops.lightning import lightning_decode_pallas
    from brpc_tpu.ops.sparse_attention import sparse_attend_pallas
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    kv = sds((4, 2, 2, 256, 64, 128), bf16)
    for n in (16, 128):
        compiled = jax.jit(
            lambda q, kv, hd, tab, bid, ln: sparse_attend_pallas(
                q, kv, 2, hd, tab, bid, ln, interpret=False)).lower(
            sds((n, 16, 128), f32), kv, sds((n,), i32), sds((n, 64), i32),
            sds((n, 64), i32), sds((n,), i32)).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        assert "sparse_attend" in text
    qkv = sds((8, 32, 128), f32)
    compiled = jax.jit(
        lambda st, rows, q, k, v, ld: lightning_decode_pallas(
            st, rows, 3, q, k, v, ld, scale=0.088, interpret=False),
        donate_argnums=0).lower(
        sds((10, 12, 32, 128, 128), f32), sds((8,), i32), qkv, qkv, qkv,
        sds((32,), f32)).compile()
    assert _has_kernel(compiled)


def test_cache_write_and_page_keys_lower(sds):
    from brpc_tpu.ops.sparse_attention import cache_write, page_keys
    bf16, i32 = jnp.bfloat16, jnp.int32
    kv = sds((4, 2, 2, 256, 64, 128), bf16)
    for n, w in ((8, 1), (64, 64)):
        blk = sds((n, 2, w, 128), bf16)
        compiled = jax.jit(
            lambda kv, pg, sl, k, v: cache_write(kv, 1, pg, sl, k, v,
                                                 backend="mosaic"),
            donate_argnums=0).lower(
            kv, sds((n,), i32), sds((n,), i32), blk, blk).compile()
        assert _has_kernel(compiled)
    compiled = jax.jit(lambda kv, pg: page_keys(
        kv, 1, pg, backend="mosaic")).lower(kv, sds((16,), i32)).compile()
    assert _has_kernel(compiled)


def _served(name, sds):
    """A benchmark configuration: its file, the two programs, the shapes
    of its weights (a matrix bfloat16, what the seeded init keeps float32
    float32) and the programs' static arguments on the chip."""
    import json
    import os
    from brpc_tpu.models import hybrid
    from brpc_tpu.models.runner import from_hf_config
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs", name)) as f:
        c = json.load(f)
    held = c["num_hidden_layers"]     # a whole model names no slice
    # a slice of the published layers' kinds holds them all beside it
    c = dict(c, **{k: c["published_" + k] for k in
                   ("layer_types", "mlp_layer_types") if "published_" + k in c})
    cuts = {}
    if "published_n_routed_experts" in c:   # a chip's share of a layer
        cuts = {"experts": (c["first_expert_held"], c["n_routed_experts"]),
                "vocab": (c["first_vocab_row"], c["vocab_size"])}
        c = dict(c, n_routed_experts=c["published_n_routed_experts"],
                 vocab_size=c["published_vocab_size"])
    cfg = from_hf_config(
        dict(c, num_hidden_layers=c.get("published_num_hidden_layers", held)),
        layers=(c.get("first_published_layer", 0), held),
        sparse=c["assumed"].get("sparse_config", {}).get("value"),
        param_dtype=c["param_dtype"], **cuts)

    def shaped(shapes):
        return {n: sds(s, jnp.bfloat16 if isinstance(fan, (int, float))
                       else jnp.float32) for n, (s, fan) in shapes.items()}
    params = shaped(hybrid.top_shapes(cfg))
    params["layers"] = [shaped(hybrid.layer_shapes(cfg, *kinds))
                        for kinds in hybrid.layer_kinds(cfg)]
    return (c, hybrid._programs(), params,
            dict(cfg=cfg, backend="mosaic", control=""))


@pytest.fixture(scope="module")
def sala(sds):
    c, fns, params, statics = _served("minicpm_sala_l16_1chip.json", sds)
    pages, rows = c["cache_pages"], c["state_rows"] + 2
    caches = (sds((4, 2, 2, pages, 64, 128), jnp.bfloat16),
              sds((4, pages, 4, 2, 128), jnp.bfloat16),
              sds((rows, 12, 32, 128, 128), jnp.float32))
    return c, fns, params, caches, statics


def _copies(compiled, arena) -> list:
    """Copies of a whole cache array in a compiled program.  The K/V
    arena: what an XLA gather, scatter or dynamic-update-slice over it
    costs on the chip (0.3 GB each way; found in PR 32's first compile).
    The latent arena: a row of 576 lanes, not whole tiles, got it another
    layout and 1 GB copied each way around every kernel call (found
    before the first chip run of PR 34)."""
    kind = "bf16" if arena.dtype == jnp.bfloat16 else "f32"
    shape = kind + "[" + ",".join(str(d) for d in arena.shape) + "]"
    return [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " copy(" in line and f"= {shape}" in line]


def test_sala_decode_step_compiles_at_published_widths_and_fits(sds, sala):
    """``minicpm_sala_l16_1chip``'s decode step for a described v5e:
    every kernel a custom call (12 state updates, the attention over
    selected pages in both branches, the cache's writes and reads), no
    copy of the arena, and weights + cache + temporaries inside the
    chip."""
    c, fns, params, caches, statics = sala
    s, mp = c["num_slots"], c["max_pages_per_slot"]
    i32 = jnp.int32
    compiled = fns["step"].lower(
        params, *caches, sds((s, 4 + mp), i32), sds((3, s), jnp.float32),
        logits_out=False, **statics).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 12 + 4 * 4
    assert _copies(compiled, caches[0]) == []
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 10e9 < need <= 0.9 * 16 * 2**30, \
        f"the step needs {need / 1e9:.2f} GB of a 16 GiB chip"


def test_sala_question_prefill_compiles_without_copying_the_arena(sds, sala):
    """The 64-token bucket: the one prefill shape inside the measured
    window of ``sala_doc_turns``."""
    c, fns, params, caches, statics = sala
    i32 = jnp.int32
    mp = c["max_pages_per_slot"]
    compiled = fns["prefill"].lower(
        params, *caches, sds((3 + mp + 64,), i32), logits_out=False,
        max_pages=mp, **statics).compile()
    assert _has_kernel(compiled)
    assert _copies(compiled, caches[0]) == []


@pytest.fixture(scope="module")
def glm(sds):
    from brpc_tpu.kvcache.layered import LayeredSpec
    c, fns, params, statics = _served("glm47_flash_l8_1chip.json", sds)
    cfg = statics["cfg"]
    pages, t = c["cache_pages"], c["page_tokens"]
    lanes = LayeredSpec(0, 0, 0, 0, 0, n_latent=cfg.n_latent,
                        latent_dim=cfg.latent_dim).latent_lanes
    # the kinds this model has no layer of: arrays with no element
    caches = (sds((0, 2, 20, pages, t, 256), jnp.bfloat16),
              sds((0, pages, 4, 20, 256), jnp.bfloat16),
              sds((2, 0, 0, 0, 0), jnp.float32))
    latent = sds((cfg.n_latent, pages, t, lanes), jnp.bfloat16)
    return c, fns, params, caches, latent, statics


def test_glm_decode_step_compiles_at_published_widths_and_fits(sds, glm):
    """``glm47_flash_l8_1chip``'s decode step for a described v5e: the
    latent write and the two passes of attention (the shared run, each
    slot's own tail: ISSUE 35) of 8 layers and the three ragged products
    of 7 expert layers are custom calls, the latent arena is copied
    nowhere, its rows are whole tiles, and weights + cache + temporaries
    (the stacked ``[320, 640]`` queries and three float32 partials a
    layer among them) fit the chip."""
    c, fns, params, caches, latent, statics = glm
    assert latent.shape == (8, 1536, 64, 640)
    s, mp = c["num_slots"], c["max_pages_per_slot"]
    compiled = fns["step"].lower(
        params, *caches, sds((s, 6 + mp), jnp.int32),
        sds((5, s), jnp.float32), latent, logits_out=False,
        **statics).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 8 * 3 + 7 * 3
    assert _copies(compiled, latent) == []
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 11e9 < need <= 0.9 * 16 * 2**30, \
        f"the step needs {need / 1e9:.2f} GB of a 16 GiB chip"
    assert mem.temp_size_in_bytes < 0.2e9


def test_glm_prefill_compiles_without_copying_the_arena(sds, glm):
    """Both prefill buckets of ``glm_agent_turns`` (every request of the
    window runs one of them through the experts)."""
    c, fns, params, caches, latent, statics = glm
    mp = c["max_pages_per_slot"]
    for bucket in c["prefill_buckets"]:
        compiled = fns["prefill"].lower(
            params, *caches, sds((3 + mp + bucket,), jnp.int32), latent,
            logits_out=False, max_pages=mp, **statics).compile()
        assert compiled.as_text().count("tpu_custom_call") >= 8 * 2 + 7 * 3
        assert _copies(compiled, latent) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_glm_latent_rows_of_the_published_width_would_be_copied(sds):
    """Why the arena's rows are 640 lanes and not the 576 the model
    caches: at 576 the chip's compiler gives the array another layout
    than the kernel reads and copies it around the call."""
    from brpc_tpu.ops.latent_attention import latent_write
    for lanes, copied in ((576, True), (640, False)):
        latent = sds((8, 1536, 64, lanes), jnp.bfloat16)
        compiled = jax.jit(
            lambda lat, pg, sl, rows: latent_write(lat, 3, pg, sl, rows,
                                                   backend="mosaic"),
            donate_argnums=0).lower(
            latent, sds((16,), jnp.int32), sds((16,), jnp.int32),
            sds((16, 1, lanes), jnp.float32)).compile()
        assert _has_kernel(compiled)
        assert bool(_copies(compiled, latent)) is copied


# ---- Jamba (ISSUE 38): the state-space kernels and both programs --------

def test_jamba_scan_and_step_kernels_lower_at_published_widths(sds):
    """5,120 channels, 16 state values: the chunk scan at the smallest
    and the largest prefill bucket (the state of ten channel blocks in
    VMEM across the chunk), and the convolution's and the scan's slot
    updates of 32 slots on a 98-row, 26-layer state array, in place:
    one Mosaic kernel each and no copy of the array."""
    from brpc_tpu.ops import mamba
    f32, i32 = jnp.float32, jnp.int32
    ch, n = 5120, 16
    for c in (64, 512):
        wide, thin = sds((c, ch), f32), sds((c, n), f32)
        compiled = jax.jit(
            lambda xc, dl, z, b, cm, h0, a, d, nv: mamba.mamba_scan(
                xc, dl, z, b, cm, h0, a, d, nv, backend="mosaic")).lower(
            wide, wide, wide, thin, thin, sds((n, ch), f32),
            sds((n, ch), f32), sds((ch,), f32), sds((), i32)).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        assert "mamba_scan" in text
    state = sds((98, 26, 24, ch), f32)
    wide, thin = sds((32, ch), f32), sds((32, n), f32)
    conv = jax.jit(
        lambda st, rows, xs, w, b: mamba.conv_step(
            st, rows, 3, xs, w, b, d_state=n, backend="mosaic"),
        donate_argnums=0).lower(
        state, sds((32,), i32), wide, sds((4, ch), f32),
        sds((ch,), f32)).compile()
    step = jax.jit(
        lambda st, rows, xc, dl, z, b, c, a, d: mamba.mamba_step(
            st, rows, 3, xc, dl, z, b, c, a, d, backend="mosaic"),
        donate_argnums=0).lower(
        state, sds((32,), i32), wide, wide, wide, thin, thin,
        sds((n, ch), f32), sds((ch,), f32)).compile()
    for compiled, name in ((conv, "mamba_conv"), (step, "mamba_step")):
        text = compiled.as_text()
        assert _has_kernel(compiled) and name in text
        assert "f32[98,26,24,5120]" in text
        assert _copies(compiled, state) == []


@pytest.fixture(scope="module")
def jamba(sds):
    from brpc_tpu.models.hybrid import layered_spec
    c, fns, params, statics = _served("jamba2_3b_1chip.json", sds)
    spec = layered_spec(statics["cfg"], c["state_rows"])
    pages, t = c["cache_pages"], c["page_tokens"]
    caches = (sds((2, 2, 1, pages, t, 128), jnp.bfloat16),
              sds((0, pages, 4, 1, 128), jnp.bfloat16),
              sds((c["state_rows"] + 2, 26) + spec.state_layer_shape,
                  jnp.float32))
    return c, fns, params, caches, statics


def test_jamba_decode_step_compiles_at_published_widths_and_fits(sds, jamba):
    """``jamba2_3b_1chip``'s decode step for a described v5e, the whole
    model: two kernels a Mamba layer (the convolution's and the scan's
    slot updates, 52), the cache's write and the attention over every
    page of the 2 attention layers, neither the K/V arena nor the 1.25
    GB state array copied, weights + cache + temporaries inside the
    chip."""
    c, fns, params, caches, statics = jamba
    assert caches[2].shape == (98, 26, 24, 5120)
    s, mp = c["num_slots"], c["max_pages_per_slot"]
    compiled = fns["step"].lower(
        params, *caches, sds((s, 4 + mp), jnp.int32),
        sds((3, s), jnp.float32), logits_out=False, **statics).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 26 * 2 + 2 * 2
    assert _copies(compiled, caches[0]) == []
    assert _copies(compiled, caches[2]) == []
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 7e9 < need <= 0.9 * 16 * 2**30, \
        f"the step needs {need / 1e9:.2f} GB of a 16 GiB chip"
    assert mem.temp_size_in_bytes < 0.2e9


def test_jamba_prefill_compiles_without_copying_the_caches(sds, jamba):
    """The largest prefill bucket of ``jamba_chat_churn`` (every request
    of the window runs a chunk through the scan; the kernel alone at the
    smallest is above): 26 ``mamba_scan`` kernels, the attention layers'
    writes and reads, no copy of the arena or of the state array."""
    c, fns, params, caches, statics = jamba
    mp = c["max_pages_per_slot"]
    for bucket in c["prefill_buckets"][-1:]:
        compiled = fns["prefill"].lower(
            params, *caches, sds((3 + mp + bucket,), jnp.int32),
            logits_out=False, max_pages=mp, **statics).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 26 + 2 * 2
        assert "mamba_scan" in text
        assert _copies(compiled, caches[0]) == []
        assert _copies(compiled, caches[2]) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


# ---- Nemotron 3 Super (ISSUE 40): the Mamba-2 kernels and both programs ---

def test_nemotron_ssd_kernels_lower_at_published_widths(sds):
    """128 heads of 64, state 128, 8 groups, 10,240 convolved channels:
    the chunk scan at the smallest and the largest prefill bucket (a
    group's state in VMEM across the chunk's blocks of 128 positions,
    float32 products), and the convolution's and the recurrence's slot
    updates of 64 slots on a 146-row, 5-layer state array, in place: one
    Mosaic kernel each and no copy of the array."""
    from brpc_tpu.ops import ssd
    f32, i32 = jnp.float32, jnp.int32
    h, p, g, n, k = 128, 64, 8, 128, 4
    di, ch = h * p, h * p + 2 * g * n
    rows = ssd.state_block_rows(h, p, g, n, k)
    n_state = ssd.state_rows(h, p, n)
    for c in (64, 512):
        compiled = jax.jit(
            lambda xs, dl, b, cm, h0, a, nv: ssd.ssd_scan(
                xs, dl, b, cm, h0, a, nv, groups=g, chunk=128,
                backend="mosaic")).lower(
            sds((c, di), f32), sds((c, h), f32), sds((c, g * n), f32),
            sds((c, g * n), f32), sds((n_state, 128), f32), sds((h,), f32),
            sds((), i32)).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        assert "ssd_scan" in text
    state = sds((146, 5, rows, 128), f32)
    conv = jax.jit(
        lambda st, r, xs, w, b: ssd.conv_step(
            st, r, 3, xs, w, b, n_state=n_state, backend="mosaic"),
        donate_argnums=0).lower(
        state, sds((64,), i32), sds((64, ch), f32), sds((k, ch), f32),
        sds((ch,), f32)).compile()
    step = jax.jit(
        lambda st, r, xs, dl, b, c, a: ssd.ssd_step(
            st, r, 3, xs, dl, b, c, a, groups=g, backend="mosaic"),
        donate_argnums=0).lower(
        state, sds((64,), i32), sds((64, di), f32), sds((64, h), f32),
        sds((64, g * n), f32), sds((64, g * n), f32),
        sds((h,), f32)).compile()
    for compiled, name in ((conv, "ssd_conv"), (step, "ssd_step")):
        text = compiled.as_text()
        assert _has_kernel(compiled) and name in text
        assert "f32[146,5,8448,128]" in text
        assert _copies(compiled, state) == []


@pytest.mark.parametrize("rows", [1408, 11264])
def test_nemotron_expert_ffn_kernel_lowers_at_published_widths(sds, rows):
    """The held experts' two products as one kernel (ISSUE 41): 128
    experts of 1,024 x 2,688 and 2,688 x 1,024 bfloat16 where they lie,
    two experts' pairs resident in VMEM (22 MB: over a kernel's default
    16 MiB, so the call states its limit), at a decode step's 64 x 22
    sorted rows and the largest prefill bucket's 512 x 22; under the
    low-precision control too.  The work list's few XLA operations are
    all that stands beside the one Mosaic call: no ragged product."""
    from brpc_tpu.ops import moe
    bf16 = jnp.bfloat16
    for round_acc in (None, "bfloat16"):
        compiled = jax.jit(functools.partial(
            moe.expert_ffn_pallas, round_acc=round_acc,
            interpret=False)).lower(
            sds((rows, 1024), jnp.float32), sds((128, 1024, 2688), bf16),
            sds((128, 2688, 1024), bf16), sds((128,), jnp.int32)).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        assert "expert_ffn" in text and "ragged" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 64e6


@pytest.fixture(scope="module")
def nemotron(sds):
    from brpc_tpu.models.hybrid import layered_spec
    c, fns, params, statics = _served("nemotron3_super_l11_ep4_1chip.json",
                                      sds)
    spec = layered_spec(statics["cfg"], c["state_rows"])
    pages, t = c["cache_pages"], c["page_tokens"]
    caches = (sds((1, 2, 2, pages, t, 128), jnp.bfloat16),
              sds((0, pages, 4, 2, 128), jnp.bfloat16),
              sds((c["state_rows"] + 2, 5) + spec.state_layer_shape,
                  jnp.float32))
    return c, fns, params, caches, statics


def test_nemotron_decode_step_compiles_at_published_widths_and_fits(
        sds, nemotron):
    """``nemotron3_super_l11_ep4_1chip``'s decode step for a described
    v5e: two kernels a Mamba-2 block (the convolution's and the
    recurrence's slot updates, 10), the cache's write and the attention
    over every page of the attention block, the held experts' two
    products as one ``expert_ffn`` kernel a block (5; no ragged product
    is left), neither the K/V arena nor the 3.16 GB state array copied,
    weights + cache + temporaries inside the chip."""
    c, fns, params, caches, statics = nemotron
    assert caches[2].shape == (146, 5, 8448, 128)
    assert params["head"].shape == (32768, 4096)
    assert params["layers"][1]["we_up"].shape == (128, 1024, 2688)
    assert params["layers"][1]["router"].shape == (4096, 512)
    s, mp = c["num_slots"], c["max_pages_per_slot"]
    compiled = fns["step"].lower(
        params, *caches, sds((s, 4 + mp), jnp.int32),
        sds((5, s), jnp.float32), logits_out=False, **statics).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5 * 2 + 2 + 5
    assert "ssd_step" in text and "ssd_conv" in text
    assert _calls(text, "expert_ffn") == 5 and "ragged-dot" not in text
    assert _copies(compiled, caches[0]) == []
    assert _copies(compiled, caches[2]) == []
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 12e9 < need <= 0.9 * 16 * 2**30, \
        f"the step needs {need / 1e9:.2f} GB of a 16 GiB chip"
    assert mem.temp_size_in_bytes < 0.5e9


def test_nemotron_prefill_compiles_without_copying_the_caches(sds, nemotron):
    """The largest prefill bucket of ``nemotron_reason_decode``: 5
    ``ssd_scan`` kernels, the attention block's write and read, the held
    experts' products over 512 x 22 assignments (``expert_ffn``
    kernels), no copy of the arena or of the state array."""
    c, fns, params, caches, statics = nemotron
    mp = c["max_pages_per_slot"]
    for bucket in c["prefill_buckets"][-1:]:
        compiled = fns["prefill"].lower(
            params, *caches, sds((3 + mp + bucket,), jnp.int32),
            logits_out=False, max_pages=mp, **statics).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 5 + 2 + 4
        assert "ssd_scan" in text
        # the last block's rows feed nothing where no logits are asked
        # for: its kernel is dead code, its group sizes are still counted
        assert _calls(text, "expert_ffn") == 4 and "ragged-dot" not in text
        assert _copies(compiled, caches[0]) == []
        assert _copies(compiled, caches[2]) == []
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 1.5e9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            <= 0.9 * 16 * 2**30


# ---- Mellum 2: the window layers' ring and kernel, both programs ----------

def test_mellum_window_attend_lowers_at_published_widths(sds):
    """The window layers' decode attention at ``mellum_code_mixed``'s
    shape: 32 slots x 4 K/V heads of 8 query heads of 128, a table of the
    17 ring pages a 1,024-position window can span, a lower bound a row,
    over the ring of 9 layers x 74 rows x 24 pages as it lies: one
    Mosaic kernel named ``window_attend``, no copy of the ring."""
    from brpc_tpu.ops.sparse_attention import sparse_attend_pallas
    i32 = jnp.int32
    ring = sds((9, 2, 4, 74 * 24, 64, 128), jnp.bfloat16)
    n = 32 * 4
    compiled = jax.jit(
        lambda q, kv, hd, tab, bid, ln, st: sparse_attend_pallas(
            q, kv, 3, hd, tab, bid, ln, starts=st, interpret=False,
            name="window_attend")).lower(
        sds((n, 8, 128), jnp.float32), ring, sds((n,), i32),
        sds((n, 17), i32), sds((n, 17), i32), sds((n,), i32),
        sds((n,), i32)).compile()
    text = compiled.as_text()
    assert _calls(text, "window_attend") == 1
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert _copies(compiled, ring) == []


@pytest.fixture(scope="module")
def mellum(sds):
    from brpc_tpu.models.hybrid import layered_spec
    from brpc_tpu.models.layers import ring_pages
    c, fns, params, statics = _served("mellum2_12b_l12_1chip.json", sds)
    cfg = statics["cfg"]
    pages, t, rows = c["cache_pages"], c["page_tokens"], c["state_rows"] + 2
    r = ring_pages(cfg.window, t, max(c["prefill_buckets"]))
    spec = layered_spec(cfg, c["state_rows"], r)
    bf16 = jnp.bfloat16
    caches = (sds((3, 2, 4, pages, t, 128), bf16),
              sds((0, pages, 4, 4, 128), bf16),
              sds((rows, 0) + spec.state_layer_shape, jnp.float32),
              sds((0, pages, t, 0), bf16),
              sds((9, 2, 4, rows * r, t, 128), bf16))
    return c, fns, params, caches, statics


def test_mellum_decode_step_compiles_at_published_widths_and_fits(
        sds, mellum):
    """``mellum2_12b_l12_1chip``'s decode step for a described v5e: a
    ring write and a ``window_attend`` kernel a window layer (9 each), a
    page write and a ``sparse_attend`` over every page a full layer (3
    each), the experts as ragged products; neither the K/V pages nor the
    2.09 GB ring copied; weights + cache + temporaries inside the
    chip."""
    c, fns, params, caches, statics = mellum
    assert caches[4].shape == (9, 2, 4, 74 * 24, 64, 128)
    assert params["head"].shape == (98304, 2304)
    assert params["layers"][0]["we_gate"].shape == (64, 2304, 896)
    assert "router_bias" not in params["layers"][0]
    s, mp = c["num_slots"], c["max_pages_per_slot"]
    kv, kc, state, latent, ring = caches
    compiled = fns["step"].lower(
        params, kv, kc, state, sds((s, 4 + mp), jnp.int32),
        sds((5, s), jnp.float32), latent, ring, logits_out=False,
        **statics).compile()
    text = compiled.as_text()
    assert _calls(text, "window_attend") == 9
    assert _calls(text, "sparse_attend") == 3
    assert _calls(text, "cache_write") == 12
    assert _copies(compiled, kv) == [] and _copies(compiled, ring) == []
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 13e9 < need <= 0.9 * 16 * 2**30, \
        f"the step needs {need / 1e9:.2f} GB of a 16 GiB chip"
    assert mem.temp_size_in_bytes < 0.5e9


def test_mellum_prefill_compiles_without_copying_the_caches(sds, mellum):
    """The largest prefill bucket of ``mellum_code_mixed`` (512
    positions, 8 pages into a 24-page ring): each window layer writes
    its ring and reads it in 8 blocks of 64 positions, a kernel row a
    K/V head and block; each full layer its pages the same way (a row a
    position would name 64 x 4 x 816 table entries, twice the scalar
    memory); no copy of the pages or of the ring."""
    c, fns, params, caches, statics = mellum
    mp = c["max_pages_per_slot"]
    kv, kc, state, latent, ring = caches
    compiled = fns["prefill"].lower(
        params, kv, kc, state, sds((3 + mp + 512,), jnp.int32), latent,
        ring, logits_out=False, max_pages=mp, **statics).compile()
    text = compiled.as_text()
    assert _calls(text, "window_attend") >= 1
    assert _calls(text, "sparse_attend") >= 1
    assert _copies(compiled, kv) == [] and _copies(compiled, ring) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= 0.9 * 16 * 2**30
