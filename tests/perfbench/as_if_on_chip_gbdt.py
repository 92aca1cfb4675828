"""The benchmark's command for the boosting cell with the steering a
rehearsal needs, kept in the test and out of the program and the
harness (``as_if_on_chip.py`` is k-means'): the CPU backend passes for
the chip, so ``boosting.train`` takes its device arm, and what XLA's CPU
client ran stands in for the device plane of the trace.  That the
histogram kernel is interpreted off the chip is the adapter's own doing
(``learners/gbdt.py on_chip``), because the run-x1 tests rehearse this
cell under k-means' file too.  ``PERFBENCH_TEST_BREAK`` breaks the
timed path underneath, for the tests of ``correct``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def steer() -> None:
    import jax

    from as_if_on_chip import host_as_device
    from perfbench import harness, readers, trace_reduce

    adapter = harness.load_module(os.path.join(
        ROOT, "perfbench", "learners", "gbdt.py"))

    jax.default_backend = lambda: "tpu"
    trace_reduce.device_ops = host_as_device
    harness.require_chip = lambda devices, chips: None
    v5e = harness.read_json(os.path.join(ROOT, "perfbench", "peaks.json"))
    readers.Observed.peaks = lambda self: v5e["TPU v5 lite"]
    broken = os.environ.get("PERFBENCH_TEST_BREAK")
    if broken == "kernel_interpreted":
        # the programs hold no Mosaic kernel
        adapter.mosaic = lambda kwargs: not kwargs.get("interpret", False)
    if broken == "host_arm":
        # the job stays off the device arm: nothing is staged there
        from rabit_tpu.learn import boosting

        boosting.on_tpu = lambda: False
    if broken == "leaf_ignores_rounding":
        # the timed path broken underneath: every split decided, and
        # every leaf weighed, on hessian sums 5% too large
        from rabit_tpu.learn import boosting

        split = boosting._split

        def wrong(node, tree, hist, *a):
            hist = hist.copy()
            hist[:, :, 1] *= 1.05
            return split(node, tree, hist, *a)

        boosting._split = wrong


if __name__ == "__main__":
    steer()
    from perfbench import harness

    sys.exit(harness.main(entry=os.path.abspath(__file__)))
