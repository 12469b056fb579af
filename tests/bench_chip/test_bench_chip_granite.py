"""The granite cell on the CPU at a tiny size (``granite_helpers``): the
program against ``reference/granite_hybrid.py``, the FLOP counts, the
Mamba-2 scopes in the compiled step, and the readers of ``ssm_ms`` and
``ssd_roofline_pct`` (``ssm_scopes.py``) on hand-made events and on a
small trace recorded on the chip (``record_ssm_trace.py``)."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_chip_helpers import harness
from bench_chip_scope_helpers import compiled_op_names
from benchmarks.chip import data, ssm_scopes, weights
from benchmarks.chip import trace_reduce as tr
from granite_helpers import WORKLOAD, tiny_granite
from repro.models import transformer as T
from repro.train import step as TS

SEED = 2**31 + 11  # seeds run past 32 bits
SSM_TRACE = os.path.join(harness.HERE, "testdata", "small_ssm_1chip.xplane.pb")


def test_program_loss_and_gradients_match_the_reference():
    cell = tiny_granite()
    program = cell.config["program"]
    reference = harness.load_module("reference", "granite_hybrid")
    cfg = harness.model_config(program)
    key = weights.base_key(SEED)
    abstract = TS.abstract_state(cfg)["params"]
    params = weights.tree_values(key, abstract)
    batch = {k: jnp.asarray(v) for k, v in data.host_batch(program, cell.traffic, 0, SEED).items()}
    loss, grads = jax.value_and_grad(lambda p: T.train_loss(cfg, p, batch))(params)
    flat = {weights.path_str(kp): g for kp, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    ref_params = {k: weights.leaf_value(key, k, s, jnp.float32)
                  for k, s in reference.param_spec(program).items()}
    assert set(flat) == set(ref_params)
    ein = lambda spec, a, b: jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: reference.loss_fn(program, ein, p, batch))(ref_params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    med = np.median([float(jnp.linalg.norm(g)) for g in ref_grads.values()])
    for k, g in ref_grads.items():
        gap = float(jnp.linalg.norm(flat[k] - g)) / max(float(jnp.linalg.norm(g)), med)
        assert gap < 1e-4, (k, gap)


def test_model_flops_of_the_cell():
    cell = harness.load_cell(WORKLOAD)
    program, traffic = cell.config["program"], cell.traffic
    flops = harness.load_module("flops", "granite_hybrid")
    # 2 T (L N G + L P H + 2 N P H) with T 4096, L 256, N 128, G 1, P 64, H 64
    assert flops.ssd_forward_flops_per_row(program, 4096) == 2 * 4096 * (256 * 128 + 256 * 64 * 64
                                                                          + 2 * 128 * 64 * 64)
    assert flops.model_flops(program, traffic) == pytest.approx(24.28e12, rel=1e-3)
    f, b = flops.ssd_step_work(program, traffic)
    assert f == 4 * 9 * flops.ssd_forward_flops_per_row(program, 4096)
    assert b == 4 * 9 * 2 * 4096 * (2 * 64 * 64 + 64 + 2 * 128)
    # the parameters the reference writes down are those the program counts,
    # with the layers' two norms and the final norm, which its count leaves out
    reference = harness.load_module("reference", "granite_hybrid")
    n = sum(int(np.prod(s)) for s in reference.param_spec(program).values())
    norms = (2 * program["num_layers"] + 1) * program["d_model"]
    assert n == harness.model_config(program).param_count() + norms
    assert n == pytest.approx(0.952e9, rel=1e-3)


def test_configuration_keeps_the_published_keys():
    cfg = harness.load_cell(WORKLOAD).config
    program = cfg["program"]
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == program["num_layers"]
    kinds = ["attn" if t == "attention" else "mamba2" for t in cfg["layer_types"][: program["num_layers"]]]
    assert [p["kind"] for p in program["pattern"]] == kinds
    same = {"hidden_size": "d_model", "intermediate_size": "d_ff", "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
            "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim", "mamba_d_state": "ssm_state_dim",
            "mamba_n_groups": "ssm_groups", "mamba_d_conv": "ssm_conv_width", "mamba_expand": "ssm_expand",
            "mamba_chunk_size": "ssm_chunk", "rms_norm_eps": "norm_eps",
            "embedding_multiplier": "embedding_multiplier", "residual_multiplier": "residual_multiplier",
            "logits_scaling": "logits_divisor", "attention_multiplier": "attention_scale",
            "tie_word_embeddings": "tie_embeddings"}
    assert {k: cfg[k] for k in same} == {k: program[v] for k, v in same.items()}
    assert program["d_model"] // program["num_heads"] == program["head_dim"]
    assert cfg["position_embedding_type"] == "nope" and program["rope"] == "none"


def test_ssm_scopes_reach_the_compiled_step():
    names = compiled_op_names(tiny_granite())
    seen = {s: set() for s in ("ssm", "ssd")}
    for n in names:
        segments = n.split("/")
        for s in seen:
            if s in segments:
                seen[s].add("backward" if any(x.startswith("transpose(") for x in segments) else "forward")
        if "ssd" in segments:
            assert segments.index("ssm") < segments.index("ssd"), n  # nested in the mixer's scope
        if "ssm" in segments:
            assert not {"attention", "mlp", "optimizer"} & set(segments), n
    assert seen == {"ssm": {"backward", "forward"}, "ssd": {"backward", "forward"}}
    assert ssm_scopes.SCOPES == TS.SSM_SCOPES  # the reader's copy of the program's names


def _ops(events):
    """(in ssm, in ssd, start, end) arrays of (ssm, ssd, start, end) tuples."""
    flags = np.array([(a, b) for a, b, _, _ in events], dtype=bool)
    return (flags, np.array([s for *_, s, _ in events], float), np.array([e for *_, e in events], float))


def test_reduce_on_hand_made_events():
    chips = {0: _ops([(True, False, -100, 50),  # clipped to the window
                      (True, True, 100, 300),
                      (True, True, 250, 400),  # overlaps: the union counts it once
                      (False, False, 400, 500),
                      (True, False, 600, 700)]),
             1: _ops([(True, True, 0, 100)]),
             2: _ops([(True, True, 0, 1000)])}  # a chip the cell does not use
    windows = [("bench/window", -5000, -4000), ("bench/window", 0, 1000)]
    r = ssm_scopes.reduce(chips, windows, n_chips=2, steps=1)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["ssm_ms"] == pytest.approx((50 + 300 + 100 + 100) / 2 * 1e-6)
    assert r["ssd_ms"] == pytest.approx((300 + 100) / 2 * 1e-6)
    assert ssm_scopes.reduce(chips, windows, n_chips=2, steps=2)["ssd_ms"] == pytest.approx(r["ssd_ms"] / 2)
    none = {0: _ops([(False, False, 0, 500)])}
    assert ssm_scopes.reduce(none, windows, 1, 1) == {"window_s": pytest.approx(1e-6),
                                                       "ssm_ms": None, "ssd_ms": None}
    assert ssm_scopes.reduce(chips, [], 1, 1) is None


def test_readers_on_a_recorded_chip_trace(tmp_path, monkeypatch):
    chips, windows = ssm_scopes.load(SSM_TRACE)
    r = ssm_scopes.reduce(chips, windows, n_chips=1, steps=2)
    busy = tr.reduce(*tr.load(SSM_TRACE), n_chips=1)["busy_s"] / 2 * 1e3
    assert 0 < r["ssd_ms"] < r["ssm_ms"] < busy
    # independently: jaxlib's reader for the times, XProf's trace viewer for each op's tf_op
    from jax._src.profiler import ProfileData
    from xprof.convert import raw_to_tool_data

    viewer, _ = raw_to_tool_data.xspace_to_tool_data([SSM_TRACE], "trace_viewer", {})
    tf_op = {e["args"].get("long_name", e["name"]): e["args"]["tf_op"]
             for e in json.loads(viewer)["traceEvents"] if "tf_op" in e.get("args", {})}
    planes = list(ProfileData.from_file(SSM_TRACE).planes)
    host = [e for p in planes if p.name == "/host:CPU" for line in p.lines for e in line.events]
    lo, hi = [(e.start_ns, e.start_ns + e.duration_ns) for e in host if e.name == "bench/window"][-1]
    for scope in ("ssm", "ssd"):
        ivs = sorted((max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
                     for p in planes if p.name.startswith("/device:TPU:") for line in p.lines
                     if line.name in ("XLA Ops", "Async XLA Ops") for e in line.events
                     if scope in tf_op.get(e.name, "").split("/")
                     and e.name.split(" = ")[0].lstrip("%").split(".")[0] not in tr.CONTAINERS)
        total, end = 0.0, -np.inf
        for a, b in ivs:
            total += max(0.0, b - max(a, end))
            end = max(end, b)
        # jaxlib rounds each event to whole nanoseconds; the reduction keeps picoseconds
        assert r[scope + "_ms"] == pytest.approx(total / 2 * 1e-6, abs=len(ivs) * 1e-6 / 2), scope
    # read as a run reads it: the newest trace under the harness's directory, its own window
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    shutil.copy(SSM_TRACE, tmp_path / "run.xplane.pb")
    ctx = {"trace": {"window_s": r["window_s"]}, "chips": 1, "trace_steps": 2}
    assert harness.load_module("metrics", "ssm_ms").read(ctx) == r["ssm_ms"]
    ctx["trace"]["window_s"] += 2e-9
    assert harness.load_module("metrics", "ssm_ms").read(ctx) is None


def test_ssd_roofline_is_the_least_time_over_the_ssd_time(monkeypatch):
    cell = harness.load_cell(WORKLOAD)
    flops, bytes_ = harness.load_module("flops", "granite_hybrid").ssd_step_work(
        cell.config["program"], cell.traffic)
    peaks = harness._json("peaks.json")
    kind = "TPU v5 lite"
    least_ms = max(flops / peaks[kind]["bf16_flops_per_s"], bytes_ / peaks[kind]["hbm_bytes_per_s"]) * 1e3
    assert least_ms == pytest.approx(3.19, rel=0.01)  # compute-bound: 0.628 TFLOP at 197 TFLOP/s
    reader = harness.load_module("metrics", "ssd_roofline_pct")
    ctx = {"peaks": peaks, "device_kind": kind, "chips": 1}
    monkeypatch.setattr(ssm_scopes, "reading", lambda ctx, name: {"ssd_ms": 4 * least_ms}[name])
    assert reader.read(ctx) == pytest.approx(25.0)
    monkeypatch.setattr(ssm_scopes, "reading", lambda ctx, name: None)
    assert reader.read(ctx) is None
