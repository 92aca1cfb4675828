"""The benchmark's command for the multi-class boosting cell with the
steering a rehearsal needs: ``as_if_on_chip_gbdt.py``'s (the CPU backend
passes for the chip, so ``boosting.train`` takes its device arm; its
``PERFBENCH_TEST_BREAK`` cases hold here too), and three ways of
breaking what this cell is there to hold the program to: a round whose
trees see one another's updates, a softmax without its factor of two,
and a forest that states another number of classes than it holds."""
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
    if broken == "hessian_not_doubled":
        # p (1 - p) where XGBoost's softmax objective has 2 p (1 - p)
        from rabit_tpu.learn import boosting

        program = boosting.softprob_grad_program

        def halved(*a, **kw):
            fn = program(*a, **kw)

            def grad(margin, labels, *keep):
                gh = fn(margin, labels, *keep)
                return gh.at[:, 1].multiply(0.5)

            return grad

        boosting.softprob_grad_program = halved
    if broken == "classes_from_stale_margins":
        # every round's gradients are the first round's: the margins
        # the round before left are not read
        from rabit_tpu.learn import boosting

        program, kept = boosting.softprob_grad_program, {}

        def stale(*a, **kw):
            fn = program(*a, **kw)

            def grad(margin, labels, *keep):
                if "gh" not in kept:
                    kept["gh"] = fn(margin, labels, *keep)
                return kept["gh"]

            return grad

        boosting.softprob_grad_program = stale
    if broken == "forest_states_six_classes":
        import rabit_tpu

        commit = rabit_tpu.checkpoint

        def misstated(model, *a, **kw):
            model.num_class = 6
            try:
                return commit(model, *a, **kw)
            finally:
                model.num_class = 7

        rabit_tpu.checkpoint = misstated


if __name__ == "__main__":
    steer()
    from perfbench import harness

    sys.exit(harness.main(entry=os.path.abspath(__file__)))
