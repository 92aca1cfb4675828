"""The benchmark's command for the ``approx`` boosting cell with the
steering a rehearsal needs: ``as_if_on_chip_gbdt.py``'s (the CPU backend
passes for the chip, so ``boosting.train`` takes its device arm; its
``PERFBENCH_TEST_BREAK`` cases hold here too), and four ways of breaking
what this cell is there to hold the program to: cuts that are sketched
once and kept, a summary too coarse for its bound, rows that are not
binned again, and split values that are not the tree's cuts."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def steer() -> None:
    import as_if_on_chip_gbdt

    as_if_on_chip_gbdt.steer()
    broken = os.environ.get("PERFBENCH_TEST_BREAK")
    if broken == "cuts_kept_from_the_first_round":
        # every tree is grown on (and commits) the first round's cuts:
        # what tree_method="hist" does
        from rabit_tpu.learn import histogram

        cuts_program, kept = histogram.cuts_program, {}

        def first_rounds(*a, **kw):
            fn = cuts_program(*a, **kw)
            return lambda merged: kept.setdefault("cuts", fn(merged))

        histogram.cuts_program = first_rounds
    if broken == "summary_of_64_entries":
        # a sketch too coarse for its bound: 64 entries a feature where
        # 8 * nbin * 4 are stated, padded to the stated shape
        import jax.numpy as jnp

        from rabit_tpu.learn import histogram

        summary = histogram.sketch_summary

        def coarse(values_t, weights, entries):
            few = summary(values_t, weights, 64)
            return jnp.repeat(few, entries // 64, axis=1)

        histogram.sketch_summary = coarse
    if broken == "rows_binned_once":
        # the second round on keeps the first round's bins
        from rabit_tpu.learn import histogram

        rebin_program, done = histogram.rebin_program, []

        def once(*a, **kw):
            fn = rebin_program(*a, **kw)

            def rebin(bins_t, values_t, cuts):
                if done:
                    return bins_t
                done.append(1)
                return fn(bins_t, values_t, cuts)

            return rebin

        histogram.rebin_program = once
    if broken == "split_values_off_their_cuts":
        from rabit_tpu.learn import boosting

        fill = boosting._fill_splits

        def shifted(tree, cuts):
            fill(tree, cuts)
            for node in tree:
                if node.feature >= 0:
                    node.split += 1.0

        boosting._fill_splits = shifted


if __name__ == "__main__":
    steer()
    from perfbench import harness

    sys.exit(harness.main(entry=os.path.abspath(__file__)))
