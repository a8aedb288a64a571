"""Harness arithmetic and lookups, on the CPU and without a chip."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import base64
import json
import os
import subprocess
import sys
import types

import pytest

from harness import counts, kernels, layers, runner, trace
from harness.peaks import UnknownDevice, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def _bench():
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("workload", [w["name"] for w in _bench()
                                      ["workloads"]])
def test_cell_files_found_by_name(workload):
    found = runner.find_cell(workload)
    assert found["config"]["name"] == found["cell"]["config"]
    driver = os.path.join(runner.BENCH_DIR, "drivers",
                          found["traffic"]["driver"] + ".py")
    assert os.path.exists(driver)
    assert os.path.exists(os.path.join(
        runner.BENCH_DIR, "configs", found["config"]["name"] + ".py"))
    assert {m["name"] for m in found["end_to_end"]} >= {"setup_s"}
    assert found["per_layer"]
    for m in found["per_layer"]:
        mod = runner.load_module(os.path.join(
            runner.BENCH_DIR, "metrics", m["name"] + ".py"))
        assert callable(mod.read)
    assert found["traffic"]["limits"]


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        runner.find_cell("no.such.cell")


def test_trace_reduction_on_recorded_fixture():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as fh:
        records = json.load(fh)
    s = trace.reduce(records)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s[0] == pytest.approx(450e-9)   # [100,400] + [500,650]
    assert s.busy_s[1] == pytest.approx(1000e-9)
    assert s.busy_mean_s == pytest.approx(725e-9)
    # self time: the loop less the ops it holds
    assert s.op_s["while.1"] == pytest.approx(50e-9)
    assert s.op_s["while.7"] == pytest.approx(200e-9)
    assert s.op_s["fusion.1"] == pytest.approx(800e-9)
    assert s.op_s["all-reduce.3"] == pytest.approx(50e-9)
    calls = {"body.5": "rk_stage"}
    assert kernels.kernel_seconds(s.op_s, calls, layers.RK_STAGE_MODULE) \
        == pytest.approx(150e-9)
    gaps = dict(s.gaps)   # per-device average over the two devices
    assert gaps["bench/step"] == pytest.approx(200e-9 / 2)
    assert gaps["bench/block"] == pytest.approx(350e-9 / 2)
    assert s.top_ops(1) == [["fusion.1", pytest.approx(800e-9)]]
    ctx = {"summary": s}
    assert layers.idle_share(ctx) == pytest.approx(27.5)
    assert trace.op_name('%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)') \
        == "fusion.3"
    assert trace.op_name('%body.5 = f32[8]{0} custom-call(f32[8]{0} %p), '
                         'custom_call_target="tpu_custom_call"') \
        == "body.5[tpu_custom_call]"


def _custom_call(name, *sources):
    """One compiled-HLO line of a Pallas call whose Mosaic body names
    ``sources`` in its string table."""
    body = b"ML\xefR\x00func.func\x00" + b"".join(
        s.encode() + b"\x00" for s in sources)
    return (f'  %{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), '
            f'custom_call_target="tpu_custom_call", backend_config='
            f'{{"custom_call_config":{{"body":"'
            f'{base64.b64encode(body).decode()}"}}}}')


def test_pallas_calls_are_named_by_their_kernel_module():
    text = "\n".join([
        "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
        _custom_call("body.5", "/ck/src/repro/kernels/rk_stage.py",
                     "/ck/src/repro/kernels/ops.py",
                     "/ck/src/repro/core/stepper.py"),
        "  ROOT " + _custom_call("jvp.2", "/a/repro/kernels/rmsnorm.py")
        .strip(),
        _custom_call("body.9", "/a/repro/kernels/rk_stage.py",
                     "/a/repro/kernels/flash_attention.py"),
        _custom_call("body.11", "/a/repro/core/stepper.py"),
        # differentiated under jvp: only the callers' files, and the
        # kernel function's name
        _custom_call("jvp.4", "/a/repro/core/odeint_aca.py",
                     "_incr_batched_kernel"),
        # a name five modules give their kernels identifies none
        _custom_call("jvp.6", "/a/repro/core/odeint_aca.py", "_kernel"),
    ])
    calls = kernels.pallas_calls(text)
    assert calls == {"body.5": "rk_stage", "jvp.2": "rmsnorm",
                     "body.9": None, "body.11": None, "jvp.4": "rk_stage",
                     "jvp.6": None}
    merged = kernels.merge([{"a.1": "rk_stage", "b.1": "rmsnorm"},
                            {"a.1": "rk_stage", "b.1": "rk_stage"}])
    assert merged == {"a.1": "rk_stage", "b.1": None}


@pytest.mark.parametrize("calls, want", [
    ({"body.5": "rk_stage", "jvp.2": "rmsnorm"}, 3.0),
    ({"body.5": "rk_stage", "jvp.2": "rk_stage"}, 7.0),
    ({"body.5": "rk_stage"}, kernels.UnidentifiedKernel),
    ({"body.5": "rk_stage", "jvp.2": None}, kernels.UnidentifiedKernel),
])
def test_kernel_seconds_counts_only_the_named_module(calls, want):
    op_s = {"fusion.1": 5.0, "body.5[tpu_custom_call]": 3.0,
            "jvp.2[tpu_custom_call]": 4.0}
    if isinstance(want, float):
        assert kernels.kernel_seconds(op_s, calls, "rk_stage") == want
    else:
        with pytest.raises(want):
            kernels.kernel_seconds(op_s, calls, "rk_stage")


class _Program:
    def __init__(self, temp, out, alias):
        self.m = types.SimpleNamespace(
            temp_size_in_bytes=temp, output_size_in_bytes=out,
            alias_size_in_bytes=alias)

    def memory_analysis(self):
        return self.m


class _Device:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return {"peak_bytes_in_use": self.peak}


@pytest.mark.parametrize("peaks, want", [
    # live 100 + the larger working set (temp 50 + outputs 30 - 20 aliased)
    ((10, 20), 160),
    # the allocator's own peak where it is larger
    ((10, 500), 500),
])
def test_memory_peak_counts_the_programs_working_set(peaks, want):
    programs = [_Program(50, 30, 20), _Program(40, 10, 0)]
    got = runner.memory_peak_bytes([_Device(p) for p in peaks], 100,
                                   programs, log=lambda *_: None)
    assert got == want


def test_trace_reduction_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce([{"kind": "op", "dev": 0, "name": "x",
                       "start_ns": 0, "dur_ns": 1}])


def test_rk_byte_count_by_hand():
    # dopri5: increments read z + nonzero a_ij stages and write z_i:
    # 3+4+5+6+7+7 = 32; the combination reads z + 6 stages, writes 1: 8
    a, b, e = counts.DOPRI5_A, counts.DOPRI5_B, counts.DOPRI5_E
    assert counts.rk_trial_elements(a, b, e) == 40
    assert counts.rk_bytes(10, 256, 4, a, b, e) == 40 * 256 * 4 * 10
    # Heun-Euler: one increment (3) and a combination of z + 2 stages (4)
    assert counts.rk_trial_elements(counts.HEUN_EULER_A, counts.HEUN_EULER_B,
                                    counts.HEUN_EULER_E) == 7


def test_train_flops_by_hand():
    # one layer, d 2, d_ff 4, 1 head of 2, vocab 3, seq 8, gated:
    # matmul params 2*2*4 (q,k,v,o) + 2*4*3 + 2*3 = 16 + 24 + 6 = 46
    # attention 3*2*2*1*2*8/2 = 96
    got = counts.dense_lm_train_flops(1, 2, 4, 1, 2, 1, 3, 8)
    assert got == 6 * 46 + 96
    # the configuration at full width: ~1.19 GFLOP per token
    full = counts.dense_lm_train_flops(18, 768, 3072, 12, 64, 12, 32768, 256)
    assert full == pytest.approx(6 * (18 * (4 * 768 ** 2 + 3 * 768 * 3072)
                                      + 768 * 32768)
                                 + 18 * 6 * 768 * 256)


def test_peaks_table():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("cpu")


def test_seed_keys_accept_large_seeds():
    from harness import seeds
    a = seeds.key(2 ** 40 + 3)
    b = seeds.key(3)
    assert (a != b).any()
    with pytest.raises(ValueError):
        seeds.key(-1)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(runner.BENCH_DIR, "run.py"),
         "--workload", "solve.mlp256.heavy", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=runner.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
