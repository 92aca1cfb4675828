"""Host-collective microbenchmark worker (``bench.py --suite collectives``).

Run under the local launcher (one process per rank, loopback TCP):

    python -m rabit_tpu.tracker.launch_local -n 4 -- \
        python -m rabit_tpu.tools.collectives_bench OUT.json \
            [--sizes 4KB,64KB,1MB] [--tune-dir DIR]

Measures, per payload size, the MB/s of every applicable collective
schedule (``tree``/``ring``/``halving``/``swing``/``hier`` — forced via
the engine's schedule hook) plus the non-schedule paths ``static`` (the
tree/ring crossover dispatch), ``async`` (handle stream, fusion off)
and ``bucketed`` (handle stream, fusion on), and the headline stream
benchmark: 64 x 256 KB sum-allreduces, sequential blocking vs
bucketed/async (doc/performance.md).  Every timed pass is verified
against the exact expected sum, so a wire bug can never masquerade as a
fast run.

Rank 0 writes the JSON — stamped with a schema version and host/world
metadata, because the auto-tuner's cache format depends on it — and,
given ``--tune-dir``, persists the measured winners as a
:class:`rabit_tpu.sched.TuningCache` for ``rabit_sched=auto``.
"""
from __future__ import annotations

import argparse
import json
import socket as socket_mod
import sys
import time

import numpy as np

import rabit_tpu
from rabit_tpu import sched as sched_mod
from rabit_tpu.ops import SUM
from rabit_tpu.utils.units import parse_byte_size

#: bump when the JSON layout changes (the tuner reads the sizes table)
SCHEMA_VERSION = 2

STREAM_OPS = 64
STREAM_BYTES = 256 << 10
DEFAULT_SIZES = [4 << 10, 64 << 10, 256 << 10, 1 << 20, 4 << 20]
REPEAT = 3


def barrier() -> None:
    rabit_tpu.allreduce(np.zeros(1, np.float32), SUM)


def make_stream(nops: int, nelem: int, rank: int) -> list[np.ndarray]:
    return [np.full(nelem, float(rank + 1 + (i % 7)), np.float32)
            for i in range(nops)]


#: verification tolerance per wire codec: the classic wire must be
#: bit-exact; a lossy codec run is checked against its documented
#: accuracy envelope instead (doc/performance.md "Quantized wire
#: codecs") — a wire bug still cannot masquerade as a fast run, it
#: would blow far past one quantization step.
CODEC_TOL = {"none": 0.0, "bf16": 0.02, "int8": 0.05, "int4": 0.3,
             # fp8's error is relative to the VALUE (float format), not
             # the block absmax, but the stream payloads are constant
             # blocks whose normalized value 1.0 encodes exactly — the
             # envelope only has to absorb merge-order rounding.
             "fp8e4m3": 0.1, "fp8e5m2": 0.15}


def check_stream(arrays: list[np.ndarray], world: int,
                 tol: float = 0.0) -> None:
    for i, a in enumerate(arrays):
        expect = world * (world + 1) / 2.0 + world * (i % 7)
        if not len(a):
            continue
        err = max(abs(float(a[0]) - expect), abs(float(a[-1]) - expect))
        # `not (err <= bound)`, NEVER `err > bound`: a NaN result (an
        # overflowed scale, torn bytes decoded as NaN) compares False
        # both ways, and the inverted form keeps it a hard failure.
        if not (err <= tol * abs(expect)):
            raise AssertionError(
                f"stream op {i}: got {a[0]}/{a[-1]}, want {expect} "
                f"(tol {tol})")


def run_blocking(arrays: list[np.ndarray]) -> None:
    for a in arrays:
        rabit_tpu.allreduce(a, SUM)


def run_handles(arrays: list[np.ndarray]) -> None:
    handles = [rabit_tpu.allreduce_async(a, SUM) for a in arrays]
    for h in handles:
        h.wait()


def time_once(fn, nops: int, nelem: int, rank: int, world: int,
              tol: float = 0.0) -> float:
    """Wall seconds for ONE pass of ``nops`` ops (barrier-bracketed so
    every rank times the same window), result-verified."""
    arrays = make_stream(nops, nelem, rank)
    barrier()
    t0 = time.perf_counter()
    fn(arrays)
    dt = time.perf_counter() - t0
    barrier()
    check_stream(arrays, world, tol)
    return dt


def time_path(fn, nops: int, nelem: int, rank: int, world: int,
              tol: float = 0.0, repeat: int = REPEAT) -> float:
    """Best-of-``repeat`` wall seconds for one pass of ``nops`` ops."""
    return min(time_once(fn, nops, nelem, rank, world, tol)
               for _ in range(repeat))


def time_paths(paths, nops: int, nelem: int, rank: int,
               world: int, tol: float = 0.0,
               repeat: int = REPEAT) -> dict[str, float]:
    """Best-of-``repeat`` seconds per labeled path, with the candidates
    INTERLEAVED across trials (one full pass over all of them per
    trial) so a transient load burst perturbs every candidate instead
    of sinking whichever one it happened to land on — the same
    measurement discipline as the kmeans suite."""
    best = {label: float("inf") for label, _setup, _fn in paths}
    for _ in range(repeat):
        for label, setup, fn in paths:
            cleanup = setup() if setup is not None else None
            try:
                dt = time_once(fn, nops, nelem, rank, world, tol)
            finally:
                if cleanup is not None:
                    cleanup()
            best[label] = min(best[label], dt)
    return best


def parse_sizes(raw: str | None) -> list[int]:
    if not raw:
        return list(DEFAULT_SIZES)
    return [parse_byte_size(tok) for tok in raw.split(",") if tok.strip()]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("out", nargs="?", default=None,
                    help="JSON output path (rank 0 writes it)")
    ap.add_argument("--sizes", default=None,
                    help="comma-separated payload sizes (byte suffixes "
                         "OK, e.g. 4KB,64KB,1MB) overriding the default "
                         "ladder — tuning sweeps need not hard-code it")
    ap.add_argument("--tune-dir", default=None,
                    help="persist the measured per-size winners as a "
                         "sched tuning cache here (rabit_sched=auto "
                         "reads it via rabit_tune_dir)")
    ap.add_argument("--repeat", type=int, default=REPEAT,
                    help="interleaved best-of trials per path (default "
                         f"{REPEAT}; raise it for noisy-box A/Bs like "
                         "the paced pipeline passes)")
    ap.add_argument("--trace-ab", action="store_true",
                    help="measure the blocking stream twice, "
                         "interleaved inside ONE run: causal hop "
                         "tracing armed (the launch's "
                         "RABIT_TRACE_SAMPLE) vs disarmed — the paired "
                         "A/B the trace-overhead budget is verified "
                         "on, immune to the cross-launch baseline "
                         "jitter that dominates oversubscribed boxes "
                         "(sampling is a per-rank perf knob, "
                         "byte-stream invariant, so toggling it "
                         "mid-run is safe; same discipline as "
                         "--pipe-depths)")
    ap.add_argument("--kernel-ab", action="store_true",
                    help="measure the blocking stream twice, "
                         "interleaved inside ONE run: compiled codec "
                         "kernel bound (native) vs unbound (the numpy "
                         "reference) — the paired A/B the native-"
                         "kernel speedup is recorded from.  The impl "
                         "is a per-rank perf knob, bit-identical by "
                         "contract (codec/kernel.py), so rebinding it "
                         "mid-run is safe; same discipline as "
                         "--trace-ab.  Requires an armed block-scale "
                         "codec; degrades to a recorded skip when the "
                         "library is not built")
    ap.add_argument("--pipe-depths", default=None,
                    help="comma list of rabit_pipeline_depth values: "
                         "adds ring_dN/halving_dN/bucketed_dN per-size "
                         "paths with the hop-pipeline depth forced to "
                         "N — depth A/B stays interleaved inside ONE "
                         "run, immune to cross-launch box noise (depth "
                         "is a per-rank perf knob, byte-stream "
                         "invariant, so forcing it mid-run is safe)")
    args = ap.parse_args()

    rabit_tpu.init()
    rank = rabit_tpu.get_rank()
    world = rabit_tpu.get_world_size()
    from rabit_tpu import engine as engine_mod

    eng = engine_mod.get_engine()
    mode = eng._sched_name
    bucket = eng._bucket_bytes
    sizes_bytes = parse_sizes(args.sizes)
    tol = CODEC_TOL.get(getattr(eng, "_codec_label", "none"), 0.0)

    # ---- headline stream: 64 x 256KB, blocking vs bucketed/async ----
    nelem = STREAM_BYTES // 4
    t_block = time_path(run_blocking, STREAM_OPS, nelem, rank, world,
                        tol, args.repeat)
    t_fused = time_path(run_handles, STREAM_OPS, nelem, rank, world,
                        tol, args.repeat)
    mbs = STREAM_OPS * STREAM_BYTES / 1e6
    stream = {
        "ops": STREAM_OPS, "payload_bytes": STREAM_BYTES,
        "blocking_MBps": round(mbs / t_block, 1),
        "fused_MBps": round(mbs / t_fused, 1),
        "speedup": round(t_block / t_fused, 3),
    }
    if args.trace_ab:
        # Paired tracing A/B (doc/observability.md "Causal tracing &
        # postmortem"): the same process, sockets and stream, with the
        # per-op sampling rate toggled between trials.  trace_sampled()
        # is deterministic in the replicated op seqno, so every rank
        # flips identically and the wire stays lockstep.
        sample0 = getattr(eng, "_trace_sample", 0)

        def force_sample(v):
            eng._trace_sample = v
            return lambda: setattr(eng, "_trace_sample", sample0)

        ab = time_paths(
            [("traced", (lambda: force_sample(sample0)), run_blocking),
             ("untraced", (lambda: force_sample(0)), run_blocking)],
            STREAM_OPS, nelem, rank, world, tol, args.repeat)
        stream["blocking_MBps_traced"] = round(mbs / ab["traced"], 1)
        stream["blocking_MBps_untraced"] = round(mbs / ab["untraced"], 1)
        stream["trace_sample"] = sample0
    if args.kernel_ab:
        # Paired native-kernel A/B (doc/benchmarks.md "Codec kernel
        # A/B"): the same process, sockets and stream, with the
        # compiled hop kernel bound vs unbound between interleaved
        # trials.  Both sides are bit-identical by contract, so the
        # check_stream verification doubles as the honesty guard.
        from rabit_tpu import codec as codec_mod

        codec = getattr(eng, "_codec", None)
        kern = codec_mod.load() if hasattr(codec, "_bind_kernel") else None
        if kern is None:
            # A skip is RECORDED, never silent: a bench row that quietly
            # measured numpy-vs-numpy would report speedup 1.0 as if the
            # kernel had been tried and found worthless.
            stream["kernel_ab_skipped"] = (
                "no block-scale codec armed" if not hasattr(
                    codec, "_bind_kernel")
                else f"kernel unavailable: {codec_mod.load_error()}")
        else:
            k0 = codec._k

            def force_kernel(k):
                codec._bind_kernel(k)
                return lambda: codec._bind_kernel(k0)

            ab = time_paths(
                [("native", (lambda: force_kernel(kern)), run_blocking),
                 ("numpy", (lambda: force_kernel(None)), run_blocking)],
                STREAM_OPS, nelem, rank, world, tol, args.repeat)
            stream["blocking_MBps_native"] = round(mbs / ab["native"], 1)
            stream["blocking_MBps_numpy"] = round(mbs / ab["numpy"], 1)
            stream["kernel_speedup"] = round(
                ab["numpy"] / ab["native"], 3)

    # ---- per-size path table: every applicable schedule + the ------
    # ---- static dispatch + async/bucketed handle streams -----------
    sizes: dict[str, dict[str, float]] = {}
    sched_names = [n for n, s in sched_mod.SCHEDULES.items()
                   if s.applies(eng, 1)]
    for size in sizes_bytes:
        nelem = max(size // 4, 1)
        nops = max(8, min(64, (8 << 20) // max(size, 1)))

        def force(name):
            eng.set_schedule(name)
            return lambda: eng.set_schedule(mode)

        def nofuse():
            eng._bucket_bytes = 0  # async overlap only, no fusion

            def restore():
                eng._bucket_bytes = bucket
            return restore

        paths = ([(name, (lambda n=name: force(n)), run_blocking)
                  for name in sched_names]
                 + [("static", lambda: force("static"), run_blocking),
                    ("async", nofuse, run_handles),
                    ("bucketed", None, run_handles)])
        if args.pipe_depths:
            depth0 = eng._pipe_depth

            def force_depth(name, dd):
                eng._pipe_depth = dd
                restore_sched = force(name) if name else None

                def restore():
                    eng._pipe_depth = depth0
                    if restore_sched is not None:
                        restore_sched()
                return restore

            for dstr in args.pipe_depths.split(","):
                dd = int(dstr)
                for name in ("ring", "halving"):
                    if name in sched_names:
                        paths.append(
                            (f"{name}_d{dd}",
                             (lambda n=name, d=dd: force_depth(n, d)),
                             run_blocking))
                paths.append((f"bucketed_d{dd}",
                              (lambda d=dd: force_depth(None, d)),
                              run_handles))
        timed = time_paths(paths, nops, nelem, rank, world, tol,
                           args.repeat)
        sizes[str(size)] = {label: round(nops * size / 1e6 / dt, 1)
                            for label, dt in timed.items()}

    host = socket_mod.gethostname()
    if rank == 0:
        data = {
            "schema": SCHEMA_VERSION,
            "host": host,
            "world": world,
            "groups": list(eng._groups),
            "codec": getattr(eng, "_codec_label", "none"),
            "pipeline_depth": getattr(eng, "_pipe_depth", 1),
            "engine": type(eng).__name__,
            "schedules": sched_names,
            "stream": stream,
            "sizes": sizes,
            "engine_stats": eng.stats(),
        }
        if args.out:
            with open(args.out, "w") as f:
                json.dump(data, f, indent=2)
        if args.tune_dir:
            # The wire codec this world measured on keys the cache
            # rows (allreduce vs allreduce+int8 — sched/tuner.py
            # table_kind): schedule crossovers genuinely differ between
            # full-width and quantized wires whose per-payload bytes
            # differ 2-4x — auto picks must never bleed across.
            codec = getattr(eng, "_codec_label", "none")
            cache = sched_mod.TuningCache.from_bench(
                sizes, world, host=host,
                candidates=set(sched_names), codec=codec,
                extra_meta={"bench": "collectives",
                            "sizes": sorted(int(s) for s in sizes),
                            "pipeline_depth": getattr(eng, "_pipe_depth",
                                                      1)})
            prior = sched_mod.TuningCache.load(args.tune_dir)
            if prior is not None:
                # Merge-don't-clobber, per (kind, world): a full-width
                # pass, a codec pass and runs at other world sizes all
                # land in ONE cache file — this run's rows win only for
                # the exact (kind, world) cells it actually measured,
                # so a world-2 pass can never erase the flagship
                # world-4 rows the nearest-world fallback serves.
                merged = {k: dict(w) for k, w in prior.table.items()}
                for kind, worlds in cache.table.items():
                    merged.setdefault(kind, {}).update(worlds)
                cache.table = merged
            path = cache.save(args.tune_dir)
            print(f"collectives_bench: wrote tuning cache to {path} "
                  f"(codec={codec})",
                  file=sys.stderr, flush=True)
    rabit_tpu.finalize()


if __name__ == "__main__":
    main()
