"""The latent-attention MoE configuration's benchmark files: FLOPs and
bytes checked by hand, the counts read from a trace's names, the new
readers on a run that holds nothing for them, and a whole run of a tiny
such decoder on the CPU through the harness (paged latent pool, prefix
hits, chunked prefill, fused windows), correct, and not correct as the
float8 control."""

import json
import pathlib

import pytest

from bench import counts, flops_mla, run, trace

DATA = pathlib.Path(__file__).parent / "data"
TINY = {"d_model": 4, "num_heads": 2, "kv_lora_rank": 2, "qk_nope_dim": 1,
        "qk_rope_dim": 2, "v_head_dim": 1, "vocab_size": 10,
        "num_layers": 2, "first_dense": 1, "d_ff": 3, "num_experts": 4,
        "experts_per_token": 2, "experts_held": 2, "moe_d_ff": 5,
        "shared_d_ff": 6}
BENCH = {
    "configs": [{"name": "tiny-mla", "file": "bench/tests/data/tiny-mla.json"}],
    "workloads": [{"name": "tiny-mla.docqa", "config": "tiny-mla",
                   "traffic": "tiny-docqa", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "latency_p50_ms", "unit": "ms"}],
    "per_layer": [{"name": "prefix_hit_share.docqa", "unit": "%"},
                  {"name": "mla_mfu", "unit": "%"},
                  {"name": "moe_expert_load", "unit": "pairs"},
                  {"name": "mla_decode_roofline", "unit": "%"}],
}


def test_flops_by_hand():
    # attention 4*2*3 + 4*4 + 2*2*2 + 2*1*4 = 56 a layer, two layers; the
    # dense layer's MLP 3*4*3; one MoE layer's router 4*4 and shared
    # expert 3*4*6; the head 4*10.
    assert flops_mla.dense_matmul_params(TINY) == 112 + 36 + 88 + 40
    # routed pairs a token: 2 * 2 / 4 = 1, each 3*4*5 weights.
    assert flops_mla.routed_pairs_per_token(TINY) == 1.0
    assert flops_mla.token_flops(TINY) == 2 * (276 + 60)
    # positions 0..2 see 1 + 2 + 3 keys; a key costs 2*2*(1+2+1) a layer.
    assert flops_mla.sequence_flops(TINY, 0, 3) == 3 * 672 + 16 * 2 * 6
    assert flops_mla.request_flops(TINY, 1, 2, 2) == \
        flops_mla.sequence_flops(TINY, 1, 3)
    # an entry is 2 + 2 bf16 values in each of 2 layers; per head a score
    # over 4 and a sum over 2, two operations each.
    assert flops_mla.latent_bytes(TINY, 5) == 5 * 2 * 4 * 2
    assert flops_mla.latent_flops(TINY, 5) == 5 * 2 * 2 * 2 * 6


def test_counts_are_summed_from_names():
    ev = [{"plane": trace.HOST_PLANE, "name": n, "start_ns": 0, "dur_ns": 1}
          for n in ("engine.count.x.3", "engine.count.x.4",
                    "engine.count.xy.5", "engine.window.k8")]
    assert counts.total(ev, "engine.count.x") == 7
    assert counts.total(ev, "engine.count.z") is None


@pytest.mark.parametrize("name", ["moe_expert_load", "mla_decode_roofline",
                                  "mla_mfu"])
def test_new_readers_read_nothing_from_a_run_without_them(name):
    """A program without the counts and kernel (the parent's) reads None,
    and does not raise."""
    r = run.Run(cell={}, model={"d_model": 8}, mix={}, chips=1, num_slots=1,
                peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                window_s=1.0)
    r.events = [{"plane": trace.HOST_PLANE, "name": "engine.window.k8",
                 "start_ns": 0, "dur_ns": 5}]
    assert run.reader(name)(r) is None


def test_configuration_names_its_reference_and_weights():
    cfg = json.loads((run.BENCH / "configs" /
                      "deepseek-v2-lite.json").read_text())
    ref = run.config_module(cfg, "reference")
    maker = run.config_module(cfg, "weights")
    assert callable(ref.logits_at) and ref.Q_BLOCK > 0
    assert 3.0e9 < maker.param_count(cfg["model"]) < 3.2e9
    assert cfg["model"]["experts_held"] == cfg["n_routed_experts"] == 8
    assert run.model_config(cfg).num_experts == 64


def _run(capsys, control=0, seed=2 ** 33 + 5):
    rc = run.main(["--workload", "tiny-mla.docqa", "--seed", str(seed),
                   "--seconds", "1.5", "--trace", "0",
                   "--control", str(control)],
                  bench=BENCH, mixes_dir=DATA, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_tiny_mla_run_is_correct(capsys, no_compile_cache):
    res, _ = _run(capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["latency_p50_ms"]["value"] > 0


def test_tiny_mla_float8_control_is_not_correct(capsys, no_compile_cache):
    res, err = _run(capsys, control=1, seed=11)
    assert res["correct"] is False and res["control"] == "float8"
    check = res["checks"]["logit_gap"]
    assert check["value"] > check["limit"]
    prog = float(err.split("reading program logit_gap: ")[1].split()[0])
    assert prog <= check["limit"]
