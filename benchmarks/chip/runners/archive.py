"""Archive traffic: one corpus, whole encode and decode passes.

Set-up loads the model (trained by the benchmark on a checkout's
first run), makes the corpus from the seed on the device and runs one
encode and one decode pass, which compile every program the window
calls.
The window then alternates whole encode and decode passes of the
corpus, back to back, for ``seconds`` (a traced run for at most
``TRACE_SECONDS``). Each pass is timed from the call
until its result (the blob, or the decoded images) is in host memory;
nothing else runs in the window.

After the window: every decode of the window is compared with the
corpus, every blob with the first, and one more encode pass, observed,
records the model's forward; its blob must equal the window's. Then
the plain reference computes the forward on the recorded inputs and
the corpus's -ELBO, after the device's peak memory has been read: the
coded rate is compared with that -ELBO.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import counts, gen_data, stalls, weights

PHASES = ("encode", "decode")
#: A traced run's window, at most: the trace of a longer one grows by
#: some 60,000 host spans a second and takes long to write and read.
TRACE_SECONDS = 10.0


def _now() -> float:
    return time.perf_counter()


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp

    traffic, system, log = ctx.traffic, ctx.system, ctx.log
    config = system.config
    n, lanes = traffic["images"], traffic["lanes"]
    dims = n * config["input_dim"]

    # -- set-up -----------------------------------------------------------
    start_s = _now() - ctx.t0
    host_params, train_s, load_s = weights.load_or_train(
        config, ctx.reference, ctx.weights_cache, log)
    system.load(host_params)
    weights_s = _now() - ctx.t0 - start_s
    log(f"setup: weights {'trained and ' if train_s else ''}loaded "
        f"({train_s:.2f} s training, {load_s:.3f} s load)")
    t = _now()
    imgs = gen_data.corpus(n, ctx.seed, binarized=system.binarized)
    data = imgs.astype(jnp.int32).reshape(n // lanes, lanes,
                                          config["input_dim"])
    data.block_until_ready()
    data_host = np.asarray(data)
    data_s = _now() - t
    log(f"setup: corpus {n} images ({n // lanes} per lane x {lanes} "
        f"lanes) made on the device in {data_s:.3f} s")

    ref = ctx.reference
    ref_params = jax.tree_util.tree_map(jnp.asarray, host_params)
    codec = system.codec()
    coder_seed = gen_data.seed32(ctx.seed, 1)
    swap = None
    if ctx.control:
        swap = system.replaced_forward(
            lambda s: ref.encode(ref_params, config, s, ctx.control),
            lambda y: ref.decode(ref_params, config, y, ctx.control))
        swap.__enter__()
        log(f"control: the reference forward with {ctx.control} matmul "
            "inputs stands in the program's place")

    def encode(**kw):
        return system.encode(codec, data, traffic, coder_seed, **kw)

    def decode(blob):
        return system.decode(codec, blob, traffic)

    before = ctx.compiles.snapshot()
    t = _now()
    warm_blob = encode()
    decode(warm_blob)
    warm_s = _now() - t
    after = ctx.compiles.snapshot()
    setup_s = _now() - ctx.t0
    d = {k: after[k] - before[k] for k in after}
    log(f"setup: warm-up encode and decode pass "
        f"{warm_s:.2f} s, of which compiling {d['compile_s']:.2f} s "
        f"(backend {d['backend_compile_s']:.2f} s over {d['programs']} "
        f"programs, {d['cache_hits']} persistent-cache hits)")
    log(f"setup: setup_s {setup_s:.3f} from process start to window = "
        f"start-up {start_s:.2f} (imports, JAX and the chip) + weights "
        f"{weights_s:.2f} + corpus {data_s:.2f} + "
        f"warm-up {warm_s:.2f} (compiling {d['compile_s']:.2f}) + the "
        f"reference's weights and the codec "
        f"{setup_s - start_s - weights_s - data_s - warm_s:.2f}")

    # -- the window -------------------------------------------------------
    trace_dir = None
    if ctx.trace:
        trace_dir = ctx.trace_dir
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0, g0, r0 = ctx.compiles.snapshot(), ctx.gc.mark(), ctx.grows[0]
    watch = stalls.PassWatch()
    times: Dict[str, List[float]] = {p: [] for p in PHASES}
    blobs: List[bytes] = []
    outs: List[np.ndarray] = []
    errors: List[str] = []
    seconds = min(ctx.seconds, TRACE_SECONDS) if ctx.trace else ctx.seconds
    w0 = _now()
    while _now() - w0 < seconds:
        try:
            with jax.profiler.TraceAnnotation("bench.encode_pass"), \
                    watch.watch("encode"):
                t = _now()
                blob = encode()
                times["encode"].append(_now() - t)
            blobs.append(blob)
            with jax.profiler.TraceAnnotation("bench.decode_pass"), \
                    watch.watch("decode"):
                t = _now()
                out = decode(blob)
                times["decode"].append(_now() - t)
            outs.append(out)
        except Exception as e:           # a failed pass fails the run
            errors.append(f"{type(e).__name__}: {e}")
            break
    window_s = _now() - w0
    watch.close()
    c1 = ctx.compiles.snapshot()
    if ctx.trace:
        jax.profiler.stop_trace()
    gc_pauses = ctx.gc.since(g0)
    window_compiles = c1["programs"] - c0["programs"]
    all_times = times["encode"] + times["decode"]
    log(f"stalls: window {window_s:.3f} s; compiles {window_compiles} "
        f"({c1['backend_compile_s'] - c0['backend_compile_s']:.3f} s), "
        f"persistent-cache hits {c1['cache_hits'] - c0['cache_hits']}; "
        f"grow-and-retry {ctx.grows[0] - r0}; GC pauses {len(gc_pauses)} "
        f"(max {max(gc_pauses, default=0) * 1e3:.2f} ms, total "
        f"{sum(gc_pauses) * 1e3:.2f} ms); generator lateness n/a "
        "(passes run back to back); longest gap between completions "
        f"{max(all_times, default=0):.3f} s")
    for line in watch.report():
        log(line)
    for p in PHASES:
        log(f"passes: {p} {len(times[p])} x, s: "
            + " ".join(f"{t:.4f}" for t in times[p]))
    for e in errors:
        log(f"window: pass failed: {e}")

    # -- what the window produced -----------------------------------------
    per_pass = [int(np.count_nonzero(o != data_host)) for o in outs]
    mismatched = sum(per_pass)
    variants = len({warm_blob, *blobs}) - 1
    with system.recorded_forward() as calls:
        blob_v, info = encode(with_info=True)
    observed_differs = int(blob_v != warm_blob)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices())
    if swap is not None:
        swap.__exit__(None, None, None)
    del codec, outs

    # -- the plain reference ----------------------------------------------
    t = _now()
    fwd_gap, fwd_per = ref.compare_forward(ref_params, config, calls)
    neg_elbo = ref.neg_elbo_bits_per_dim(ref_params, config, imgs,
                                         gen_data.seed32(ctx.seed, 2))
    coded = info["net_bits"] / dims
    rate_gap = coded / neg_elbo - 1
    log(f"reference: {time.perf_counter() - t:.2f} s; forward gaps "
        + ", ".join(f"{k} {v:.6g}" for k, v in sorted(fwd_per.items()))
        + f"; coded {coded:.6f} bits/dim against the reference -ELBO "
        f"{neg_elbo:.6f} ({rate_gap * 100:+.3f}%)")

    wire = len(warm_blob)
    n_pass = {p: len(times[p]) for p in PHASES}
    checks = [
        ("lossless_mismatched_pixels", mismatched),
        ("blob_variants", variants),
        ("observed_pass_differs", observed_differs),
        ("forward_gap", fwd_gap),
        ("rate_gap", rate_gap),
    ]
    attempted = sum(n_pass.values()) + len(errors)
    failed = len(errors) + sum(m > 0 for m in per_pass)
    e2e = {"setup_s": setup_s,
           "wire_bits_per_dim": wire * 8 / dims}
    for p in PHASES:
        if times[p]:       # raw input bytes: one byte per pixel
            e2e[f"{p}_MBps"] = counts.mb_per_s(dims, times[p])
    return {
        "e2e": e2e, "checks": checks,
        "attempted": attempted, "failed": failed,
        "errors": errors, "memory_peak_bytes": memory_peak,
        "trace_dir": trace_dir,
        "layer": {
            "windows": {p: f"bench.{p}_pass" for p in PHASES},
            "pass_seconds": {p: sum(times[p]) for p in PHASES},
            "images": {p: n_pass[p] * n for p in PHASES},
            "wire_bytes_per_image": wire / n,
            "window_compiles": {"archive": window_compiles},
        },
    }


def cleanup(ctx) -> None:
    if ctx.trace_dir and os.path.isdir(ctx.trace_dir):
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
