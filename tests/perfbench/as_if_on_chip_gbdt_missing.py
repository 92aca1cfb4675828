"""The benchmark's command for the wide boosting cell with the steering
a rehearsal needs: ``as_if_on_chip_gbdt.py``'s (the CPU backend passes
for the chip, so ``boosting.train`` takes its device arm; its
``PERFBENCH_TEST_BREAK`` cases hold here too), and two ways of breaking
what this cell is there to hold the program to: absent rows sent one
fixed way, and a histogram that drops a station."""
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
    if broken == "absent_rows_one_fixed_way":
        # no direction is learned: every split sends its absent rows
        # left, in the scan and so in the row move
        import numpy as np

        from rabit_tpu.learn import histogram

        candidates = histogram.split_candidates

        def left_only(hist, reg_lambda=1.0, min_child_weight=None,
                      total=None):
            gain, left = candidates(hist, reg_lambda, min_child_weight,
                                    total)
            if total is None:
                return gain, left
            mass = histogram.missing_mass(hist, total)
            sent_left = np.asarray(hist, np.float64).copy()
            sent_left[:, 0] += mass
            return candidates(sent_left, reg_lambda, min_child_weight,
                              None)[0], np.ones_like(left)

        histogram.split_candidates = left_only
    if broken == "histogram_drops_a_station":
        # the first two columns (the rehearsal's first station) are in
        # no histogram: their splits are never seen
        from rabit_tpu.learn import histogram

        level = histogram.level_hist

        def dropped(*a, **kw):
            return level(*a, **kw).at[:, :2].set(0.0)

        histogram.level_hist = dropped


if __name__ == "__main__":
    steer()
    from perfbench import harness

    sys.exit(harness.main(entry=os.path.abspath(__file__)))
