"""``BENCHMARK.json`` and the files it names agree, and the entry point
refuses to report a run without a TPU."""

import json
import os
import re
import subprocess
import sys

from bench_chip_helpers import ROOT, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_every_name_has_its_files():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"] and sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        assert set(cell.limits) >= {"loss_gap", "grad_gap", "update_gap"}
        assert cell.traffic["global_batch"] % cell.chips == 0
    for m in BENCH["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert "setup_s" in {e["name"] for e in BENCH["end_to_end"]}


def test_program_section_keeps_the_published_widths():
    wm = json.load(open(os.path.join(ROOT, "benchmarks/chip/configs/whisper-medium.json")))
    p = wm["program"]
    assert (p["d_model"], p["num_heads"], p["d_ff"], p["num_layers"], p["encoder_layers"]) == (
        wm["d_model"], wm["decoder_attention_heads"], wm["decoder_ffn_dim"],
        wm["decoder_layers"], wm["encoder_layers"])
    assert p["vocab_size"] == wm["vocab_size"] and p["encoder_seq"] == wm["max_source_positions"]
    sc = json.load(open(os.path.join(ROOT, "benchmarks/chip/configs/starcoder2-3b-l6.json")))
    p = sc["program"]
    assert (p["d_model"], p["num_heads"], p["num_kv_heads"], p["d_ff"], p["num_layers"]) == (
        sc["hidden_size"], sc["num_attention_heads"], sc["num_key_value_heads"],
        sc["intermediate_size"], sc["num_hidden_layers"])
    assert p["num_heads"] * p["head_dim"] == sc["hidden_size"]
    assert p["rope_theta"] == sc["rope_theta"] and p["vocab_size"] == sc["vocab_size"]


def test_peaks_are_keyed_by_device_kind():
    peaks = json.load(open(os.path.join(ROOT, "benchmarks/chip/peaks.json")))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    step_mfu = harness.load_module("metrics", "step_mfu")
    ctx = {"peaks": peaks, "device_kind": "TPU v5 lite", "model_flops_per_step": 197e12,
           "steps": 2, "window_s": 4.0, "chips": 1}
    assert step_mfu.read(ctx) == 50.0
    try:
        step_mfu.read(dict(ctx, device_kind="cpu"))
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "whisper-medium.train.1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT, env=env)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
