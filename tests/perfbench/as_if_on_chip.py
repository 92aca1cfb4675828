"""The benchmark's command with the steering a rehearsal needs, kept in
the test and out of the program and the harness: the CPU backend passes
for the chip, the Pallas kernels are interpreted, the tier budgets are
shrunk so that tiny data lands in the tiers the real sizes reach, and
what XLA's CPU client ran stands in for the device plane of the trace.
Children of a several-rank cell are started with this same file."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def steer() -> None:
    import jax

    import rabit_tpu.ops.kmeans_kernel as kk
    from perfbench import harness, trace_reduce
    from rabit_tpu.learn import kmeans

    # the adapter as the harness will find it: by its path
    adapter = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "kmeans.py"))

    jax.default_backend = lambda: "tpu"
    for name in ("kmeans_stats_fused", "kmeans_ell_stats_fused"):
        def interpreted(*a, _orig=getattr(kk, name), **kw):
            kw["interpret"] = True
            return _orig(*a, **kw)

        setattr(kk, name, interpreted)
    stage = kmeans.prepare_shard
    kmeans.prepare_shard = lambda *a, **kw: stage(*a, **{**kw, "budget": 0})
    kmeans._dense16_budget = lambda: 1 << 40
    # an interpreted kernel stands for the Mosaic kernel it would be
    adapter.mosaic = lambda kwargs: True
    trace_reduce.device_ops = host_as_device
    harness.require_chip = lambda devices, chips: None
    # the CPU has no row in peaks.json (an unknown device is an error):
    # a rehearsal borrows the v5e's, and writes no metric anywhere
    from perfbench import readers

    v5e = harness.read_json(os.path.join(ROOT, "perfbench", "peaks.json"))
    readers.Observed.peaks = lambda self: v5e["TPU v5 lite"]
    broken = os.environ.get("PERFBENCH_TEST_BREAK")
    if broken == "other_tier":
        # the rows fall into another tier than the configuration names:
        # densified in float32 (the budget a tiny shard fits)
        kmeans.prepare_shard = stage
    if broken == "kernel_interpreted":
        # the step program holds no Mosaic kernel
        adapter.mosaic = lambda kwargs: not kwargs.get("interpret", False)
    if broken == "step_keeps_state":
        # the timed path broken underneath: a step that returns its
        # state unchanged (the chained program's centroid update)
        kmeans.centroid_update = lambda cent, stats: cent


def host_as_device(profile):
    """In place of ``trace_reduce.device_ops``, which refuses a trace
    with no TPU plane: the operations XLA's CPU client ran (host events
    that carry ``hlo_op``), so that the path from trace to result line
    can be exercised here.  Never a metric."""
    from perfbench.trace_reduce import _clean

    named = []
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats and not e.name.startswith("end:"):
                    named.append((
                        f"{_clean(str(stats.get('hlo_module', '')))}"
                        f"/{_clean(e.name)}:{_clean(e.name)}",
                        float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return {"host-as-device": (named, [])} if named else {}


if __name__ == "__main__":
    steer()
    from perfbench import harness

    sys.exit(harness.main(entry=os.path.abspath(__file__)))
