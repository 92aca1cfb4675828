"""The benchmark's command for the sparse boosting cell with the
steering a rehearsal needs: ``as_if_on_chip_gbdt_missing.py``'s (the CPU
backend passes for the chip, so ``boosting.train`` takes its device arm;
its ``PERFBENCH_TEST_BREAK`` cases hold here too), and three ways of
breaking what this cell is there to hold the program to: absent rows
scored to the right only (an indicator column's one split, absent left
of its one cut, is then never seen: sending them left, the wide cell's
break, changes nothing where that is what most splits choose), the
numeric columns' cells dropped from the flat histograms, and a row move
that sends every row without the split's column right."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def steer() -> None:
    import as_if_on_chip_gbdt_missing

    as_if_on_chip_gbdt_missing.steer()
    broken = os.environ.get("PERFBENCH_TEST_BREAK")
    if broken == "absent_rows_scored_right":
        # no direction is learned: every candidate is scored with its
        # absent rows on the right
        import numpy as np

        from rabit_tpu.learn import histogram

        candidates = histogram.split_candidates

        def right_only(hist, reg_lambda=1.0, min_child_weight=None,
                       total=None):
            gain, left = candidates(hist, reg_lambda, min_child_weight,
                                    total)
            if total is None:
                return gain, left
            mass = histogram.missing_mass(hist, total)
            sent_right = np.asarray(hist, np.float64).copy()
            sent_right[:, -1] += mass
            return candidates(sent_right, reg_lambda, min_child_weight,
                              None)[0], np.zeros_like(left)

        histogram.split_candidates = right_only
    if broken == "histogram_drops_the_numeric_columns":
        # the rehearsal's six numeric columns are in no histogram:
        # their splits are never seen
        from rabit_tpu.learn import histogram

        level = histogram.level_hist_flat

        def dropped(entries, gh, node, nslots, flat, **kw):
            return level(entries, gh, node, nslots, flat, **kw).at[
                :, :int(flat.ptr[6])].set(0.0)

        histogram.level_hist_flat = dropped
    if broken == "absent_moves_right":
        # the row move ignores the committed direction: a row without
        # the split's column goes right
        import jax.numpy as jnp

        from rabit_tpu.learn import boosting

        move = boosting._move_entries

        def fixed(cells_t, node, tab):
            return move(cells_t, node, jnp.asarray(tab).at[..., 3].set(0))

        boosting._move_entries = fixed


if __name__ == "__main__":
    steer()
    from perfbench import harness

    sys.exit(harness.main(entry=os.path.abspath(__file__)))
