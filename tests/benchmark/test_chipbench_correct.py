"""The comparison that decides ``correct``, driven through a whole run
of the archive cell on the CPU at a small size: sound runs pass, and
the two controls (the reference forward with float8 matmul inputs in
the program's place; the program's coder at 12 bits of precision where
the configuration states 16) and each fault planted in the timed path
fail."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmarks.chip import harness

# A small corpus and a short training: what a test run can hold.
SMALL = {"traffic": {"images": 64, "lanes": 32, "block_symbols": 2},
         "config": {"train": {"steps": 400, "seed": 0, "n_train": 512,
                              "batch": 64, "lr": 0.001}}}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_cache"))


def run(capsys, cache, seed=2**31 + 3, control=None, config=None) -> dict:
    overrides = {"traffic": SMALL["traffic"],
                 "config": dict(SMALL["config"], **(config or {}))}
    rc = harness.run(["--workload", "archive-vae-bernoulli",
                      "--seed", str(seed), "--seconds", "0.5",
                      "--trace", "0"],
                     platform="cpu", control=control, overrides=overrides,
                     cache_root=cache, compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_sound_run_is_correct(capsys, cache):
    res = run(capsys, cache)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"encode_MBps", "decode_MBps",
                                   "wire_bits_per_dim", "setup_s"}
    assert list(res)[-1] == "checks"


def test_control_fails_on_the_forward(capsys, cache):
    res = run(capsys, cache, control="float8_e4m3fn")
    assert not res["correct"]
    gap = res["checks"]["forward_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_on_the_rate(capsys, cache):
    res = run(capsys, cache, config={"precision": 12})
    assert not res["correct"]
    gap = res["checks"]["rate_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["forward_gap"]["value"] <= \
        res["checks"]["forward_gap"]["limit"]


def test_fault_answer_altered_where_it_is_produced(capsys, cache,
                                                   monkeypatch):
    from repro import shard_codec
    decompress = shard_codec.decompress_dataset

    def altered(*args, **kwargs):
        out = np.array(decompress(*args, **kwargs))
        out[0, 0, 400] ^= 1
        return out

    monkeypatch.setattr(shard_codec, "decompress_dataset", altered)
    res = run(capsys, cache)
    assert not res["correct"]
    assert res["checks"]["lossless_mismatched_pixels"]["value"] > 0


def test_fault_step_returns_its_state_unchanged(capsys, cache, monkeypatch):
    import importlib
    cc = importlib.import_module("repro.codecs.compile")
    unchanged = {True: lambda stack, *a, **k: stack,
                 False: lambda stack, *a, **k: stack}
    for name in ("_PUSH_MANY", "_PUSH_GRID", "_PUSH_TABLE"):
        monkeypatch.setattr(cc, name, unchanged)
    assert not run(capsys, cache)["correct"]


def test_fault_half_the_batch_left_out(capsys, cache, monkeypatch):
    from repro import shard_codec
    compress = shard_codec.compress_dataset

    def half(codec, data, **kwargs):
        lanes = data.shape[1]
        return compress(codec, data.at[:, lanes // 2:].set(0), **kwargs)

    monkeypatch.setattr(shard_codec, "compress_dataset", half)
    res = run(capsys, cache)
    assert not res["correct"]
    assert res["checks"]["lossless_mismatched_pixels"]["value"] > 0
