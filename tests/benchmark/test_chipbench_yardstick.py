"""The on-chip benchmark's yardstick, checked on the CPU: the trace
reduction, the counts of work, the corpus generator, the benchmark's
own trainer, the stall watcher, the table of peaks, and the finding of
configurations, traffic mixes and per-layer metrics by name."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.chip import (counts, gen_data, registry, stalls,
                             trace_reduce, weights)

# A device plane with two programs and three ops; a host plane with the
# benchmark's pass annotations and two dispatch spans. Times in ps from
# the line's timestamp (1 us = 1e6 ps).
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_push_many" } }
  event_metadata { key: 2 value { id: 2 name: "jit_dense" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "push_kernel" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.encode_pass" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(dense)" } }
  event_metadata { key: 3 value { id: 3 name: "unpack" } }
  event_metadata { key: 4 value { id: 4 name: "bench.decode_pass" } }
}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return trace_reduce.from_profile(ProfileData.from_text_proto(TRACE))


def test_trace_reads_devices_programs_and_host_spans(trace):
    assert trace.devices == ["/device:TPU:0"]
    assert [e.name for e in trace.modules] == ["jit_push_many", "jit_dense"]
    assert len(trace.ops) == 3
    assert trace.spans("bench.encode_pass") == [(0.0, 10000.0)]


def test_busy_union_and_idle_share(trace):
    enc = trace.spans("bench.encode_pass")
    # ops cover 1..4 us and 6..8 us of the 0..10 us pass: 5 us busy.
    assert trace_reduce.busy_ns(trace, enc) == pytest.approx(5000.0)
    assert trace_reduce.idle_percent(trace, enc) == pytest.approx(50.0)
    # nothing runs in the decode pass: no number, never a 0 or 100.
    assert trace_reduce.idle_percent(
        trace, trace.spans("bench.decode_pass")) is None


def test_interval_union_and_overlap():
    assert trace_reduce.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace_reduce.overlap([(0, 10)], [(2, 3), (5, 20)]) == 6
    assert trace_reduce.length([(0, 2), (1, 3)]) == 3


def test_kernel_time_by_pattern(trace):
    enc = trace.spans("bench.encode_pass")
    push = trace_reduce.matching(trace.modules, [r"push", r"pop"])
    assert [e.name for e in push] == ["jit_push_many"]
    assert trace_reduce.device_seconds(push, enc) == pytest.approx(3e-6)
    ops = trace_reduce.top_ops(trace, enc)
    assert ops[0] == ["jit_push_many/push_kernel", pytest.approx(2e-6)]
    assert ["jit_dense/fusion.1", pytest.approx(2e-6)] in ops


def test_idle_gaps_named_by_the_host(trace):
    gaps = dict(trace_reduce.idle_gaps(
        trace, trace.spans("bench.encode_pass"),
        ["bench.encode_pass", "bench.decode_pass"]))
    # idle 0..1 (Python between calls), 4..6 (dispatch of dense) and
    # 8..10 (unpack)
    assert gaps == {"PjitFunction(dense)": pytest.approx(2e-6),
                    "unpack": pytest.approx(2e-6),
                    "bench.encode_pass": pytest.approx(1e-6)}


def test_short_names():
    assert trace_reduce.short(
        "%fusion.3 = u32[8]{0} fusion(u32[8] %a), kind=kCustom") == "%fusion.3"
    assert trace_reduce.short("jit_pop_many_dyn(3081055484432196907)") == \
        "jit_pop_many_dyn"
    assert trace_reduce.short("PjitFunction(dense)") == "PjitFunction(dense)"


# The paper's full-MNIST model (784-200-50, beta-binomial); it has no
# cell yet, so no configuration file.
BETABINOMIAL = {"input_dim": 784, "hidden": 200, "latent": 50,
                "likelihood": "beta_binomial", "lat_bits": 10}


@pytest.mark.parametrize("cfg, macs", [
    (registry.config("vae-bernoulli"), 168800), (BETABINOMIAL, 500400)])
def test_model_flops_at_the_paper_widths(cfg, macs):
    assert counts.vae_macs(cfg) == macs
    assert counts.vae_flops_per_image(cfg) == 2 * macs


def test_coder_bytes_and_roofline():
    cfg = registry.config("vae-bernoulli")
    # encode: 40 posterior pops over 1024 buckets (11 entries), 784 pixel
    # pushes and 40 prior pushes (2 entries each), 864 int32 symbols.
    enc = 40 * 11 + 784 * 2 + 40 * 2
    assert counts.coder_bytes_per_image(cfg, "encode", 50.0) == \
        864 * 4 + enc * 4 + 50.0
    # decode pops the 2-symbol pixels: 2 entries each.
    dec = 40 * 11 + 784 * 2 + 40 * 2
    assert counts.coder_bytes_per_image(cfg, "decode", 0.0) == \
        864 * 4 + dec * 4
    assert counts.coder_bytes_per_image(BETABINOMIAL, "decode", 0.0) == \
        (2 * 50 + 784) * 4 + (50 * 11 + 784 * 9 + 50 * 2) * 4
    peaks = registry.peaks("TPU v5 lite")
    r = counts.roofline(819e9 * 0.5, 0.0, 1.0, peaks)
    assert r == {"percent": pytest.approx(50.0), "bound": "hbm"}
    r = counts.roofline(0.0, 197e12, 4.0, peaks)
    assert r == {"percent": pytest.approx(25.0), "bound": "bf16"}


def test_throughput_arithmetic():
    # 10240 images x 784 bytes over 2 passes of 0.5 s and 1.5 s.
    assert counts.mb_per_s(10240 * 784, [0.5, 1.5]) == \
        pytest.approx(2 * 10240 * 784 / 1e6 / 2.0)
    assert counts.mb_per_s(100, []) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(registry.UnknownDevice):
        registry.peaks("TPU v99 imaginary")
    assert registry.peaks("TPU v5 lite")["bf16_flops"] == 197e12


@pytest.mark.parametrize("binarized", [True, False])
def test_corpus_is_a_function_of_the_seed(binarized):
    seed = 2**31 + 17          # seeds past 32 signed bits are drawn too
    a = np.asarray(gen_data.corpus(16, seed, binarized=binarized, block=8))
    b = np.asarray(gen_data.corpus(16, seed, binarized=binarized, block=8))
    c = np.asarray(gen_data.corpus(16, seed + 1, binarized=binarized,
                                   block=8))
    assert a.shape == (16, 784) and a.dtype == np.uint8
    assert (a == b).all() and not (a == c).all()
    assert a.max() <= (1 if binarized else 255)
    # digit-like: mostly background with a few bright strokes
    mean = a.mean() / (1 if binarized else 255)
    assert 0.03 < mean < 0.4


def test_training_set_is_apart_from_the_corpus():
    a = np.asarray(gen_data.corpus(8, 5, binarized=True, block=8))
    b = np.asarray(gen_data.corpus(8, 5, binarized=True, block=8,
                                   salt=weights.TRAIN_SALT))
    assert not (a == b).all()


def test_weights_are_trained_once_from_the_seed_and_kept(tmp_path):
    config = registry.config("vae-bernoulli")
    config["train"] = {"steps": 20, "seed": 3, "n_train": 64, "batch": 16,
                       "lr": 0.001}
    ref = registry.part("references", config["reference"])
    said = []
    a, train_s, _ = weights.load_or_train(config, ref, str(tmp_path / "a"),
                                          said.append)
    assert train_s > 0 and said and "trained 20 steps" in said[0]
    b, again_s, _ = weights.load_or_train(config, ref, str(tmp_path / "a"),
                                          said.append)
    c, _, _ = weights.load_or_train(config, ref, str(tmp_path / "c"),
                                    said.append)
    assert again_s == 0.0 and len(said) == 5
    assert said[1] == said[2] == said[4]          # the same bytes
    assert set(a) == {"enc_h", "enc_mu", "enc_logvar", "dec_h", "dec_out"}
    assert a["enc_h"]["w"].shape == (784, 100)
    assert a["dec_out"]["w"].shape == (100, 784)
    for layer in a:
        for k in a[layer]:
            assert (a[layer][k] == b[layer][k]).all()
            assert (a[layer][k] == c[layer][k]).all()
    # training moved the weights away from their initial draw
    init = ref.init(config, 3)
    assert not np.allclose(a["enc_h"]["w"], np.asarray(init["enc_h"]["w"]))
    # a coder precision does not change the weights; a width does
    assert weights.path(dict(config, precision=12), "x") == \
        weights.path(config, "x")
    assert weights.path(dict(config, hidden=50), "x") != \
        weights.path(config, "x")


def test_pass_watch_names_a_slow_pass_and_where_it_waited():
    watch = stalls.PassWatch(interval=0.01)
    try:
        for i in range(5):
            with watch.watch("encode"):
                time.sleep(0.6 if i == 3 else 0.02)
    finally:
        watch.close()
    assert [round(p["wall"], 1) for p in watch.passes][3] == 0.6
    lines = watch.report(factor=1.25, min_excess_s=0.3)
    assert len(lines) == 1 and "slow encode pass 3" in lines[0]
    assert "sleep" in lines[0] or "test_chipbench" in lines[0]
    assert not watch._thread.is_alive()


def test_discovers_a_config_traffic_and_metric_added_as_files(tmp_path):
    base = tmp_path / "chip"
    shutil.copytree(registry.HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (base / p).read_bytes() for p in
              ("configs/vae-bernoulli.json", "traffic/archive.json",
               "registry.py", "harness.py")}
    cfg = registry.config("vae-bernoulli", str(base))
    cfg["name"] = "vae-new"
    (base / "configs" / "vae-new.json").write_text(json.dumps(cfg))
    (base / "traffic" / "archive-small.json").write_text(json.dumps(
        dict(registry.traffic("archive", str(base)), images=2048)))
    (base / "metrics" / "new_metric.encode.py").write_text(
        "def read(ctx):\n    return 2 * ctx.x\n")

    assert registry.config("vae-new", str(base))["hidden"] == 100
    assert registry.traffic("archive-small", str(base))["images"] == 2048
    import types
    reader = registry.metric_reader("new_metric.encode", str(base))
    assert reader(types.SimpleNamespace(x=21)) == 42
    bench = {"per_layer": [{"name": "new_metric.encode",
                            "workloads": ["cell-new"]},
                           {"name": "everywhere"}],
             "workloads": [{"name": "cell-new", "config": "vae-new",
                            "traffic": "archive-small", "chips": 1}]}
    assert [m["name"] for m in registry.metrics_for(
        bench, "cell-new", "per_layer")] == ["new_metric.encode",
                                             "everywhere"]
    assert registry.cell(bench, "cell-new")["config"] == "vae-new"
    for p, data in before.items():
        assert (base / p).read_bytes() == data


def test_every_named_part_of_the_benchmark_exists():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cfg = registry.config(w["config"])
        traffic = registry.traffic(w["traffic"])
        registry.part("systems", cfg["system"])
        registry.part("references", cfg["reference"])
        registry.part("runners", traffic["runner"])
        assert "forward_gap" in cfg["limits"]
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))
    for c in bench["configs"]:
        assert registry.config(c["name"])["source"] == c["source"]


def test_no_tpu_means_no_result(capsys):
    from benchmarks.chip import harness
    rc = harness.run(["--workload", "archive-vae-bernoulli", "--seed", "1",
                      "--seconds", "1", "--trace", "0"],
                     compile_cache=False)
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "no TPU" in out.err


def test_a_checkout_without_the_program_fails(tmp_path):
    shutil.copytree(registry.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "archive-vae-bernoulli", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
