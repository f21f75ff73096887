"""The layer kinds' records (``brpc_tpu.models.layers``): each kind's
record is whole, a layer indexes its kind's cache array, and a kind
with no record is refused when the runner is built.  No program is
traced here: the four runner files run every record's programs."""
import pytest

from brpc_tpu.models import layers
from brpc_tpu.models.hybrid import HybridRunner
from brpc_tpu.models.runner import MIXER_KINDS, TransformerConfig

# every width a record's shapes read, small
CFG = TransformerConfig(
    lin_heads=2, lin_head_dim=8, q_lora_rank=8, kv_lora_rank=16,
    qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, ssm_state=4, ssm_conv=4,
    ssm_dt_rank=4, ssm_expand=2, ssm_heads=2, ssm_head_dim=8, ssm_groups=1,
    ssm_chunk=8, n_experts=4, experts_per_tok=2, n_shared_experts=1,
    moe_d_ff=8, moe_latent=8, shared_d_ff=16, experts_held=(0, 4))


@pytest.mark.parametrize("table,kind", [("mixer", k) for k in MIXER_KINDS]
                         + [("ffn", k) for k in layers.FFNS])
def test_a_kind_has_a_whole_record(table, kind):
    """Its shapes (the layer's own, after a norm a sublayer), its program
    functions, the cache array it indexes and its counters, each of
    which a runner of that kind has and counts by the record's
    function."""
    mixer = kind if table == "mixer" else layers.ATTN
    ffn = kind if table == "ffn" else layers.DENSE
    rec = (layers.MIXERS if table == "mixer" else layers.FFNS)[kind]
    shapes = rec.shapes(CFG)
    assert all(len(shape) == 2 and min(shape[0]) > 0
               for shape in shapes.values())
    fns = (rec.decode, rec.prefill) if table == "mixer" else (rec.apply,)
    if kind == layers.NONE:
        assert shapes == {} and fns == (None,) * len(fns)
    else:
        assert shapes and all(callable(f) for f in fns)
    if table == "mixer":
        assert rec.store in ((None,) if kind == layers.NONE
                             else ("kv", "latent", "state"))
        assert rec.state_block is None or rec.store == "state"
    else:
        assert rec.routed == (kind in layers.ROUTED) == bool(rec.counters)
    drawn = layers.layer_shapes(CFG, mixer, ffn)
    norms = [n for n, has in (("norm1", mixer != layers.NONE),
                              ("norm2", ffn != layers.NONE)) if has]
    assert list(drawn)[:len(norms)] == norms
    assert all(drawn[n] == s for n, s in shapes.items())
    assert (rec.count is None) == (not rec.counters)
    runner = HybridRunner({}, TransformerConfig(mixer_types=(mixer,),
                                                ffn_types=(ffn,)),
                          name=f"records_{table}_{kind}")
    try:
        assert all(hasattr(runner, attr) for attr, _ in rec.counters)
        assert (rec.count in runner._counts) == bool(rec.counters)
    finally:
        runner.close()


def test_each_layer_indexes_its_kind_s_cache_array():
    """Both attention kinds share the K/V array, the latent layers the
    latent one, the recurrent kinds the state rows; a block with no
    mixer indexes nothing."""
    cfg = TransformerConfig(mixer_types=(
        "minicpm4", "lightning-attn", "attention", "mla", "lightning-attn",
        "none", "minicpm4", "mla"))
    assert [(m.store, i) for m, _, i in layers.layer_records(cfg)] == [
        ("kv", 0), ("state", 0), ("kv", 1), ("latent", 0), ("state", 1),
        (None, 0), ("kv", 2), ("latent", 1)]
    assert (cfg.n_kv_layers, cfg.n_latent, cfg.n_linear) == (3, 2, 2)


@pytest.mark.parametrize("mixers,ffns,named", [
    (("attention", "window"), (), "window"),
    (("attention",), ("dense_moe",), "dense_moe")])
def test_a_kind_without_a_record_is_refused_when_the_runner_is_built(
        mixers, ffns, named):
    with pytest.raises(ValueError, match=f"kind '{named}'"):
        HybridRunner({}, TransformerConfig(mixer_types=mixers,
                                           ffn_types=ffns), name="records_no")
