"""The lowering's frequency-soundness check of coding tables
(``codecs/compile._validate_tables``). The device reduces the tables to
a three-flag verdict (``_table_faults``) and the host reads only that;
a flagged table is read whole and the host routine
(``_check_tables_host``) raises its error. Bad tables planted in each
table family raise the host routine's text, count and first index
included; a valid lowering reads a few bytes, not the table; the device
verdict is the host routine's on valid tables, single-entry mutations
of them and a lane-sharded table."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs, spans
from repro.codecs.compile import (_TABLE_FAULTS, _TableRepeat,
                                  _check_tables_host, _lower_repeat,
                                  _table_faults, _validate_tables)
from repro.core import ans

N, LANES, PRECISION = 4, 5, 16


def _family_repeat(family: str) -> codecs.Repeat:
    rng = np.random.default_rng(0)
    if family == "Bernoulli":
        lg = jnp.asarray(rng.normal(size=(LANES, N)), jnp.float32)
        return codecs.Repeat(lambda d: codecs.Bernoulli(lg[:, d]), N)
    if family == "Categorical":
        lg = jnp.asarray(rng.normal(size=(LANES, N, 6)), jnp.float32)
        return codecs.Repeat(lambda d: codecs.Categorical(lg[:, d]), N)
    al, be = (jnp.asarray(rng.uniform(0.5, 3.0, (LANES, N)), jnp.float32)
              for _ in range(2))
    return codecs.Repeat(
        lambda d: codecs.BetaBinomial(al[:, d], be[:, d], 15), N)


def _last_start(t):
    t[2, 1, -1] = (1 << PRECISION) - 1
    return 1, (2, 1)


def _decreasing_start(t):
    t[1, 3, 1] = t[1, 3, 2] + 1
    return None


def _zero_width(t):
    t[0, 0, 1] = t[0, 0, 2]
    return None


def _several(t):
    t[3, 4, 0] = 1
    t[1, 2, -1] = 7
    t[1, 0, -1] = (1 << PRECISION) + 1
    return 3, (1, 0)


@pytest.mark.parametrize("family", ["Bernoulli", "Categorical",
                                    "BetaBinomial"])
@pytest.mark.parametrize("plant, fault", [
    (_last_start, "freq-sum"),
    (_decreasing_start, "starts-monotone"),
    (_zero_width, "freq-zero"),
    (_several, "freq-sum"),
])
def test_planted_bad_table_raises_the_host_routines_error(family, plant,
                                                          fault):
    node = _lower_repeat(_family_repeat(family), donate=False)
    assert isinstance(node, _TableRepeat)
    bad = np.array(node.tables)
    expect = plant(bad)
    what = f"Repeat[{family}, n={N}]"
    with pytest.raises(ValueError) as host:
        _check_tables_host(bad, PRECISION, what)
    with pytest.raises(ValueError) as device:
        _validate_tables(jnp.asarray(bad), PRECISION, what)
    assert str(device.value) == str(host.value)
    assert f"({fault}) in {what}" in str(device.value)
    if expect is not None:
        count, first = expect
        assert f"{count} of {N * LANES} tables" in str(device.value)
        assert f"the first, at {first}," in str(device.value)


def test_valid_lowering_reads_only_the_verdict(monkeypatch):
    reads = []
    real = spans.host_read

    def recording(x, site):
        out = real(x, site)
        reads.append((site, out.nbytes))
        return out

    monkeypatch.setattr(spans, "host_read", recording)
    lg = jnp.asarray(np.random.default_rng(1).normal(size=(64, 784)),
                     jnp.float32)
    node = _lower_repeat(
        codecs.Repeat(lambda d: codecs.Bernoulli(lg[:, d]), 784),
        donate=False)
    assert isinstance(node, _TableRepeat)
    assert node.tables.nbytes == 784 * 64 * 3 * 4
    table_reads = [b for site, b in reads if site == "compile.tables"]
    assert len(table_reads) == 1 and sum(table_reads) <= 64


def _valid_tables(rng, precision, alphabet, shape=(3, 4)):
    total = 1 << precision
    inner = [np.sort(rng.choice(np.arange(1, total), alphabet - 1,
                                replace=False))
             for _ in range(int(np.prod(shape)))]
    t = np.stack([np.concatenate([[0], s, [total]]) for s in inner])
    return t.reshape(shape + (alphabet + 1,)).astype(np.uint32)


def _host_verdict(t, precision):
    try:
        _check_tables_host(t, precision, "t")
    except ValueError as e:
        return re.search(r"contract violation \(([a-z-]+)\)",
                         str(e)).group(1)
    return None


def _device_verdict(t, precision):
    flags = np.asarray(_table_faults(jnp.asarray(t), precision=precision))
    assert flags.shape == (3,) and flags.dtype == np.bool_
    assert flags[2] or not flags[1]     # a decrease is a zero width too
    return _TABLE_FAULTS[int(np.argmax(flags))] if flags.any() else None


@pytest.mark.parametrize("precision", [1, 8, 12, ans.MAX_PRECISION])
def test_device_verdict_is_the_host_routines(precision):
    rng = np.random.default_rng(precision)
    total = 1 << precision
    seen = set()
    for alphabet in sorted({2, min(5, total), min(40, total)}):
        t = _valid_tables(rng, precision, alphabet)
        assert _device_verdict(t, precision) is None
        assert _host_verdict(t, precision) is None
        for _ in range(60):
            i, j = rng.integers(0, 3), rng.integers(0, 4)
            k = rng.integers(0, alphabet + 1)
            old = int(t[i, j, k])
            value = rng.choice([0, 1, old - 1, old + 1, total - 1, total,
                                total + 1, 2 ** 32 - 1,
                                int(t[i, j, max(k - 1, 0)]),
                                int(t[i, j, min(k + 1, alphabet)])])
            m = t.copy()
            m[i, j, k] = np.uint32(int(value) % 2 ** 32)
            verdict = _host_verdict(m, precision)
            assert _device_verdict(m, precision) == verdict, (i, j, k,
                                                              value)
            seen.add(verdict)
    assert {None, "freq-sum", "freq-zero"} <= seen


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.codecs.compile import _table_faults
    from repro.sharding import api as shard_api

    spec = NamedSharding(shard_api.lane_mesh(4),
                         P(None, shard_api.LANE_AXIS, None))
    out = []
    for m in json.load(sys.stdin):
        x = jax.device_put(np.asarray(m, np.uint32), spec)
        flags = _table_faults(x, precision=16)
        out.append({"flags": np.asarray(flags).tolist(),
                    "shards": len(x.addressable_shards),
                    "replicated": flags.sharding.is_fully_replicated})
    print(json.dumps(out))
""")


def test_device_verdict_on_a_lane_sharded_table():
    t = _valid_tables(np.random.default_rng(2), 16, 4, shape=(3, 8))
    cases = [t]
    for (i, j, k), value in [((2, 7, 4), (1 << 16) - 1), ((0, 5, 2), 0),
                             ((1, 2, 1), 0), ((2, 0, 3), 1 << 16)]:
        m = t.copy()
        m[i, j, k] = value
        cases.append(m)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    res = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT],
        input=json.dumps([c.tolist() for c in cases]),
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    verdicts = [_host_verdict(c, 16) for c in cases]
    assert verdicts[0] is None and len(set(verdicts)) >= 3, verdicts
    for rec, verdict in zip(out, verdicts):
        flags = rec["flags"]
        got = _TABLE_FAULTS[int(np.argmax(flags))] if any(flags) else None
        assert got == verdict, (flags, verdict)
        assert rec["shards"] == 4 and rec["replicated"]
