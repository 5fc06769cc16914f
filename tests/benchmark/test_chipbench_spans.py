"""The readers of the program's own spans (``benchmarks/chip/spans.py``
and the eight ``metrics/*`` files on it), checked on the CPU on a small
recorded trace: their values on known intervals, ``None`` where the
trace has no ``repro.*`` span, names with the profiler's ``#``
metadata, and the archive cell's traced run at a small size."""

from __future__ import annotations

import json
import types

import pytest

from benchmarks.chip import harness, registry, spans, trace_reduce

US = 1000000          # ps per us

# Times in us. Encode pass 0..10: the device runs 2..3 and 7..8, so it
# idles 0..2, 3..7 and 8..10 (8 us); the forward covers 0..2, the
# lowering 3..6 (a host read nested at 4..5), a coder dispatch 6..7 and
# a host read 7.5..8 while the device runs; 8..10 is covered by no span
# (2 us). Decode pass 10..20: the device runs 12..13, idle 9 us; the
# forward covers 10..11, the lowering 13..16 (a host read at 14..15),
# a frame 16..17; 11..12 and 17..20 are uncovered (4 us).
DEVICE = [(2, 1), (7, 1), (12, 1)]
HOST = [
    ("bench.encode_pass", 0, 10),
    ("repro.forward", 0, 2),
    ("repro.lower", 3, 3),
    ("repro.host_read", 4, 1),
    ("repro.coder", 6, 1),
    ("repro.host_read", 7.5, 0.5),
    ("bench.decode_pass", 10, 10),
    ("repro.forward", 10, 1),
    ("repro.lower", 13, 3),
    ("repro.host_read", 14, 1),
    ("repro.frame", 16, 1),
    ("PjitFunction(dense)", 18, 1),
]

EXPECTED = {
    "lower_share.encode": 30.0, "lower_share.decode": 30.0,
    "forward_share.encode": 20.0, "forward_share.decode": 10.0,
    "host_reads.encode": 2.0, "host_reads.decode": 1.0,
    "idle_unattributed.encode": 25.0,
    "idle_unattributed.decode": 100.0 * 4 / 9,
}
READERS = sorted(EXPECTED)


def _trace(host, suffix=""):
    """A trace in the profiler's text form: a device plane with the
    ops of ``DEVICE``, before it in name order a device plane with one
    op (the idle is the busier device's), one host line with ``host``
    spans; ``suffix`` follows every ``repro.*`` name, as the profiler's
    metadata does."""
    names = sorted({n + (suffix if n.startswith("repro.") else "")
                    for n, _, _ in host})
    ids = {n: i + 1 for i, n in enumerate(names)}
    ops = "".join(f"events {{ metadata_id: 1 offset_ps: {int(s * US)} "
                  f"duration_ps: {int(d * US)} }} " for s, d in DEVICE)
    spans_ = "".join(
        f"events {{ metadata_id: "
        f"{ids[n + (suffix if n.startswith('repro.') else '')]} "
        f"offset_ps: {int(s * US)} duration_ps: {int(d * US)} }} "
        for n, s, d in host)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }} ' for n, i in ids.items())
    text = f"""
planes {{ id: 3 name: "/device:CUSTOM:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {int(8.5 * US)}
              duration_ps: {US} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "sync" }} }} }}
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops}}}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "main" timestamp_ns: 0 {spans_}}}
  {meta}}}
"""
    from jax.profiler import ProfileData
    return trace_reduce.from_profile(ProfileData.from_text_proto(text))


def _ctx(trace):
    return types.SimpleNamespace(
        trace=trace, windows={"encode": "bench.encode_pass",
                              "decode": "bench.decode_pass"})


@pytest.mark.parametrize("suffix", ["", "#site=stream.msg#"])
@pytest.mark.parametrize("name", READERS)
def test_reader_on_known_intervals(name, suffix):
    value = registry.metric_reader(name)(_ctx(_trace(HOST, suffix)))
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_program_spans(name):
    bare = [h for h in HOST if not h[0].startswith("repro.")]
    assert registry.metric_reader(name)(_ctx(_trace(bare))) is None


def test_a_name_that_only_begins_alike_is_not_matched():
    assert spans.named("repro.lower#k=v#", spans.LOWER)
    assert not spans.named("repro.lowering", spans.LOWER)


def test_interval_difference():
    assert spans.minus([(0, 10)], [(2, 3), (7, 8)]) == \
        [(0, 2), (3, 7), (8, 10)]
    assert spans.minus([(0, 2), (5, 9)], [(1, 6)]) == [(0, 1), (6, 9)]
    assert spans.minus([(0, 2)], []) == [(0, 2)]
    assert spans.minus([(0, 2)], [(-1, 3)]) == []


def test_entries_name_a_layer_and_what_they_move():
    bench = registry.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in EXPECTED}
    for name in READERS:
        m = entries[name]
        assert m["source"] == "device_trace"
        assert m["workloads"] == ["archive-vae-bernoulli"]
        assert m["moves"] == f"{name.split('.')[1]}_MBps"
        assert m["layer"] in layers


# The archive cell at the size of test_chipbench_correct.py.
SMALL = {"traffic": {"images": 64, "lanes": 32, "block_symbols": 2},
         "config": {"train": {"steps": 400, "seed": 0, "n_train": 512,
                              "batch": 64, "lr": 0.001}}}


def test_traced_run_of_the_archive_cell(capsys, tmp_path):
    rc = harness.run(["--workload", "archive-vae-bernoulli",
                      "--seed", str(2**31 + 7), "--seconds", "0.5",
                      "--trace", "1"],
                     platform="cpu", overrides=SMALL,
                     cache_root=str(tmp_path), compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"], res["checks"]
    got = res["metrics"]
    # On the CPU the trace holds no device plane: no idle to attribute.
    for name in READERS:
        if name.startswith("idle_unattributed."):
            assert name not in got
        else:
            assert got[name]["value"] is not None, name
    # 2 chain steps per pass, each 12 reads in the lowering; one block
    # framed per pass, 6 reads encoding it and 2 decoding it.
    assert got["host_reads.encode"]["value"] == 2 * 12 + 6
    assert got["host_reads.decode"]["value"] == 2 * 12 + 2
    assert 0 < got["lower_share.encode"]["value"] < 100
    assert 0 < got["forward_share.decode"]["value"] < 100
