"""A whole benchmark run on the CPU at a tiny size: the harness past its
look for a chip, the served program, the window and the comparison with
the reference; then the same with the served tokens broken underneath,
and the float8 control, which must both come out not correct."""

import json
import pathlib

import pytest

from bench import run

DATA = pathlib.Path(__file__).parent / "data"
BENCH = {
    "configs": [{"name": "tiny", "file": "bench/tests/data/tiny.json"}],
    "workloads": [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
         "chips": 1},
        {"name": "tiny.batch", "config": "tiny", "traffic": "tiny-batch",
         "chips": 1}],
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "latency_p90_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "output_tokens_per_s", "unit": "tokens/s",
         "workloads": ["tiny.batch"]}],
    "per_layer": [
        {"name": "compiles_in_window.x", "unit": "count"},
        {"name": "router_calls_per_frame.x", "unit": "calls"},
        {"name": "prefix_hit_share", "unit": "%",
         "workloads": ["tiny.chat"]},
        {"name": "mean_occupancy", "unit": "%",
         "workloads": ["tiny.batch"]}],
}


def _run(capsys, workload, trace=0, seed=2 ** 33 + 5, control=0):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1.5", "--trace", str(trace),
                   "--control", str(control)],
                  bench=BENCH, mixes_dir=DATA, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("workload,metric", [
    ("tiny.chat", "latency_p90_ms"), ("tiny.batch", "output_tokens_per_s")])
def test_run_is_correct(capsys, no_compile_cache, workload, metric):
    res, _ = _run(capsys, workload)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.batch"])
def test_traced_run_reads_its_layers(capsys, no_compile_cache, workload):
    res, _ = _run(capsys, workload, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["compiles_in_window.x"]["value"] == 0
    assert res["metrics"]["router_calls_per_frame.x"]["value"] >= 1
    key = "prefix_hit_share" if workload == "tiny.chat" else "mean_occupancy"
    assert res["metrics"][key]["value"] > 0
    assert "latency_p90_ms" not in res["metrics"]


def test_altered_token_is_not_correct(capsys, no_compile_cache,
                                      monkeypatch):
    """A token altered where the engine produces it: the last token of
    every reply is moved to its neighbour in the vocabulary."""
    from repro.serve import engine
    retire = engine.ServeEngine._retire

    def altered(self, i):
        slot = self._slots[i]
        slot.generated[-1] = (slot.generated[-1] + 1) % self._cfg.vocab_size
        retire(self, i)

    monkeypatch.setattr(engine.ServeEngine, "_retire", altered)
    res, _ = _run(capsys, "tiny.chat")
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_float8_control_is_not_correct(capsys, no_compile_cache):
    """The reference in float8, put in the program's place (``--control
    1``): the same run, the same requests, comes out not correct, while
    the served tokens' own gap, read on the way, lies within the limit."""
    res, err = _run(capsys, "tiny.chat", seed=11, control=1)
    assert res["correct"] is False and res["control"] == "float8"
    check = res["checks"]["logit_gap"]
    assert check["value"] > check["limit"]
    prog = float(err.split("reading program logit_gap: ")[1].split()[0])
    assert prog <= check["limit"]


@pytest.mark.parametrize("name", ["qwen2-1.5b", "starcoder2-3b"])
def test_configuration_names_its_reference_and_weights(name):
    """The harness takes a configuration's reference and weight maker from
    the paths the configuration names, so a new architecture brings its
    own files and edits none of the harness."""
    cfg = json.loads((run.BENCH / "configs" / f"{name}.json").read_text())
    ref = run.config_module(cfg, "reference")
    maker = run.config_module(cfg, "weights")
    assert callable(ref.logits_at) and ref.Q_BLOCK > 0
    assert maker.param_count(cfg["model"]) > 1e9
    assert run.config_module(cfg, "weights") is maker
    with pytest.raises(SystemExit):
        run.config_module({k: v for k, v in cfg.items() if k != "weights"},
                          "weights")
