"""chip_smoke.py's phases at toy size on the virtual CPU mesh.

The script itself runs only on a TPU.  What can rot without one — the
phase functions' control flow, the entry points they drive, the checks
they make — runs here at sizes the CPU finishes in seconds, with the
gather backend NAMED (off a TPU the dispatcher would pick it anyway;
naming it keeps this file honest about what it covers).  The kernels'
lowering for the chip is tests/test_chip_compile.py's job.
"""
import json

import jax
import pytest

import chip_smoke
from brpc_tpu import fault
from brpc_tpu.models.runner import TransformerConfig, make_tp_mesh

# 18 tokens: two shared pages of 4, a run of 8 repeated for the drafts, 2
# over; a bucket each for the warm, the shared and the cold suffix
TOY_SERVING = dict(cfg=TransformerConfig(), seed=0, page_tokens=4,
                   num_slots=2, max_pages_per_slot=24, cache_blocks=16,
                   prompt_len=18, new_tokens=6,
                   prefill_buckets=(8, 16, 32), attn_backend="gather")
# the last key count is the largest key bucket: the updates' union of
# keys then outgrows one request, as it does at the real size
TOY_PS = dict(vocab=4096, dim=16, seed=0, key_counts=(8, 100, 512))
TOY_ECHO = dict(unary_bytes=64 * 1024, n_chunks=4, chunk_bytes=16 * 1024)


def test_tensor_echo_same_device():
    d0 = jax.devices()[0]
    r = chip_smoke.phase_tensor_echo(client_device=d0, server_device=d0,
                                     **TOY_ECHO)
    assert r["host_copies"] == 0
    assert r["same_device_copies"] > 0 and r["cross_device_moves"] == 0
    assert set(r["fence"]) == {"dispatch_s", "block_until_ready_s",
                               "readback_after_s"}


def test_tensor_echo_between_two_devices():
    d0, d1 = jax.devices()[:2]
    r = chip_smoke.phase_tensor_echo(client_device=d0, server_device=d1,
                                     **TOY_ECHO)
    assert r["host_copies"] == 0 and r["cross_device_moves"] > 0


def test_llm_serving_four_requests_match_dense():
    r = chip_smoke.phase_llm_serving(**TOY_SERVING)
    assert r["backend"] == "gather"
    gens = r["generations"]
    assert list(gens) == ["cold", "warm", "shared", "spec"]
    assert gens["cold"]["prefix_hit"] == 0
    assert gens["warm"]["prefix_hit"] > 0
    assert gens["shared"]["prefix_hit"] == 8
    assert gens["spec"]["draft_tokens_proposed"] > 0
    assert r["logits_max_abs_diff"] <= chip_smoke.LOGITS_ATOL
    assert r["kernel_vs_gather_max_abs_diff"] <= chip_smoke.KERNEL_ATOL


def test_llm_serving_tensor_parallel_equals_one_device():
    # attention runs head-parallel: tp must divide the K/V heads
    sizes = dict(TOY_SERVING, cfg=TransformerConfig(n_kv_heads=4),
                 requests=("cold",))
    one = chip_smoke.phase_llm_serving(**sizes)
    tp = chip_smoke.phase_llm_serving(mesh=make_tp_mesh(4), **sizes)
    assert tp["tokens"] == one["tokens"]


def test_error_terminal_fails_the_serving_phase():
    """A decode step that raises reaches the client as a terminal with
    "error" and no tokens; a caller that read only tokens would see an
    empty generation and pass.  The collector must not."""
    plan = fault.FaultPlan(5).on("serving.step", fault.ERROR, times=-1)
    with fault.injected(plan):
        with pytest.raises(chip_smoke.SmokeError, match="error terminal"):
            chip_smoke.phase_llm_serving(**TOY_SERVING)
    assert plan.injected["serving.step"] >= 1


def test_parameter_server_one_shard():
    r = chip_smoke.phase_parameter_server(devices=jax.devices()[:1],
                                          **TOY_PS)
    assert r["adam_max_abs_err"] <= 1e-6


def test_parameter_server_four_shards_one_per_device_and_lowered():
    devs = jax.devices()[:4]
    r = chip_smoke.phase_parameter_server(
        devices=devs, lowered_mesh=make_tp_mesh(4), **TOY_PS)
    assert r["devices"] == [str(d) for d in devs]
    assert r["lowered_max_abs_err"] <= 1e-6


def test_collective_fanout_equals_socket_fanout():
    r = chip_smoke.phase_collective_fanout(devices=jax.devices()[:4],
                                           n_elems=1024)
    assert r == {"chips": 4, "lowered_calls": 3, "elements": 1024}


def test_smoke_prompts_share_two_pages_and_repeat():
    p = chip_smoke.smoke_prompts(128, 18, 4, seed=3)
    assert p["shared"][:8] == p["cold"][:8] and p["shared"] != p["cold"]
    assert p["spec"][:8] == p["spec"][8:16]
    assert {len(v) for v in p.values()} == {18}
    assert p == chip_smoke.smoke_prompts(128, 18, 4, seed=3)


def test_main_refuses_a_platform_that_is_not_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert '"ok"' not in out and out.strip() == ""
    assert "needs a TPU" in err


@pytest.mark.parametrize("chips", [1, 4])
def test_main_on_a_tpu_runs_the_asked_run_and_reports_it(monkeypatch,
                                                         capsys, chips):
    """main()'s own logic with the phases stubbed: which run it picks,
    and that the last stdout line is the result and nothing else."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 1, "bytes_in_use": 1}

    ran = []
    monkeypatch.setattr(jax, "devices", lambda: [Dev()] * chips)
    monkeypatch.setattr(chip_smoke, "run_one_chip",
                        lambda seed, timed: ran.append(1))
    monkeypatch.setattr(chip_smoke, "run_four_chips",
                        lambda seed, timed: ran.append(4))
    assert chip_smoke.main(["--chips", str(chips)]) == 0
    assert ran == [chips]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips}}
    assert last == json.dumps(json.loads(last))


def test_main_fails_when_a_phase_raises(monkeypatch, capsys):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    def boom(seed, timed):
        raise chip_smoke.SmokeError("tokens differ")

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(chip_smoke, "run_one_chip", boom)
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "tokens differ" in err
