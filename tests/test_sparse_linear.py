"""The wide sparse linear path: the bucketed layout and the two products
of ``rabit_tpu.ops.sparse_linear_kernel`` (XLA formulation, and the
kernels interpreted), and ``LinearObjFunction`` on ``LBFGSSolver``
through them: arrays against a libsvm file, the device arm against the
host arm, margin reuse, kill and resume, spans and counters."""
import numpy as np
import pytest

import rabit_tpu
from rabit_tpu.obs import program
from rabit_tpu.ops import sparse_linear_kernel as sk


def zipf_rows(n, k, nf, seed, zero_share=0.0):
    """Seeded rows with a skewed feature law (a few cells take most of
    the non-zeros), arbitrary float32 values."""
    rng = np.random.default_rng(seed)
    idx = np.minimum((rng.pareto(0.6, (n, k)) * 3).astype(np.int64),
                     nf - 1).astype(np.int32)
    idx = (idx * 7919 + rng.integers(0, 3, (n, k))) % nf
    val = rng.standard_normal((n, k)).astype(np.float32)
    val[rng.random((n, k)) < zero_share] = 0.0
    return idx.astype(np.int32), val


def stage(idx, val, nf):
    import jax.numpy as jnp

    n, k = idx.shape
    tiles = -(-n // sk.ROW_TILE)
    pad = tiles * sk.ROW_TILE - n
    idx = np.concatenate([idx, np.zeros((pad, k), np.int32)])
    val = np.concatenate([val, np.zeros((pad, k), np.float32)])
    packed, v, fb, real = sk.bucket_group(
        jnp.asarray(idx.reshape(-1)), jnp.asarray(val.reshape(-1)),
        nnz_row=k, num_feature=nf)
    return packed, v, fb, int(real), tiles


def interpreted(kernel):
    """The kernel as the program calls it on the chip, interpreted."""
    return lambda *a, **kw: kernel(*a, **{**kw, "interpret": True})


def dense_products(idx, val, w, g):
    m = (val.astype(np.float64) * w[idx]).sum(axis=1)
    grad = np.zeros(len(w))
    np.add.at(grad, idx.reshape(-1),
              (val.astype(np.float64) * g[:, None]).reshape(-1))
    return m, grad


def test_split3_parts_are_on_the_bfloat16_grid_and_add_up_exactly():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = (rng.standard_normal(4096) * 10.0 ** rng.integers(-20, 20, 4096)
         ).astype(np.float32)
    parts = [np.asarray(p) for p in sk.split3(jnp.asarray(x))]
    for p in parts:
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(p).astype(jnp.bfloat16).astype(
                jnp.float32)), p)
    np.testing.assert_array_equal((parts[0] + parts[1]) + parts[2], x)


# rows, non-zeros a row, features, share of explicit zeros: one tile with
# most buckets empty; buckets that fill whole sub-chunks; one row over a
# tile (a second tile that holds a single row); several feature blocks
CASES = {
    "empty_buckets": (700, 3, 3 * sk.FEAT_BLOCK, 0.0),
    "full_buckets": (sk.SUB, 4, 64, 0.0),
    "one_over_a_tile": (sk.ROW_TILE + 1, 2, 2 * sk.FEAT_BLOCK + 17, 0.2),
    "ragged_rows": (3000, 5, sk.FEAT_BLOCK - 1, 0.5),
}


@pytest.mark.parametrize("formulation", ["xla", "kernel_interpreted"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_products_match_a_dense_float64_product(case, formulation):
    import jax.numpy as jnp

    n, k, nf, zeros = CASES[case]
    idx, val = zipf_rows(n, k, nf, seed=len(case), zero_share=zeros)
    if case == "full_buckets":
        idx[:, 0] = 5                     # SUB slots of one bucket exactly
    packed, v, fb, real, tiles = stage(idx, val, nf)
    assert real == np.count_nonzero(val)
    assert packed.shape == (tiles * sk.capacity(k, nf) // sk.SUB, sk.SUB)
    rng = np.random.default_rng(1)
    w = rng.standard_normal(nf).astype(np.float32)
    g = np.zeros(tiles * sk.ROW_TILE, np.float32)
    g[:n] = rng.standard_normal(n)
    want_m, want_g = dense_products(idx, val, w.astype(np.float64),
                                    g[:n].astype(np.float64))
    if formulation == "xla":
        m = sk.margins_xla(packed, v, fb, jnp.asarray(w), tiles=tiles)
        grad = sk.gradient_xla(packed, v, fb, jnp.asarray(g), tiles=tiles,
                               num_feature=nf)
    else:
        m = sk.lbfgs_margin(packed, v, fb, jnp.asarray(w), tiles=tiles,
                            interpret=True)
        grad = sk.lbfgs_grad(packed, v, fb, jnp.asarray(g), tiles=tiles,
                             num_feature=nf, interpret=True)
    m, grad = np.asarray(m), np.asarray(grad)
    assert m.shape == (tiles * sk.ROW_TILE,) and grad.shape == (nf,)
    scale_m = np.abs(val.astype(np.float64) * w[idx]).sum(axis=1).max()
    np.testing.assert_allclose(m[:n], want_m, atol=2e-6 * scale_m)
    assert not m[n:].any()
    np.testing.assert_allclose(grad, want_g,
                               atol=2e-6 * np.abs(want_g).max() + 1e-6)


def test_a_feature_outside_the_model_and_a_zero_value_are_padding():
    idx = np.array([[0, 9, 12], [3, 3, 11]], np.int32)
    val = np.array([[1.0, 2.0, 4.0], [0.0, 0.5, 8.0]], np.float32)
    packed, v, fb, real, tiles = stage(idx, val, nf=10)
    assert real == 3                      # 12 and 11 are past the model
    import jax.numpy as jnp

    m = np.asarray(sk.margins_xla(
        packed, v, fb, jnp.arange(10, dtype=jnp.float32), tiles=tiles))
    np.testing.assert_allclose(m[:2], [0.0 + 18.0, 1.5])


# ----------------------------------------------------------------------
# the objective and the solver on top
# ----------------------------------------------------------------------
def small_problem(seed=0, n=600, k=6, nf=300):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, nf, (n, k)).astype(np.int32)
    val = (rng.integers(1, 9, (n, k)) / 8.0).astype(np.float32)
    w_true = rng.standard_normal(nf) * (rng.random(nf) < 0.2)
    p = 1 / (1 + np.exp(-(val * w_true[idx]).sum(axis=1)))
    return idx, val, (rng.random(n) < p).astype(np.float32), nf


def objective(idx, val, y, nf, **params):
    from rabit_tpu.learn import LinearObjFunction

    obj = LinearObjFunction()
    obj.load_arrays(idx, val, y, nf)
    for name, value in {"reg_L1": 0.5, "silent": 1, **params}.items():
        obj.set_param(name, str(value))
    return obj


def test_arrays_give_what_a_libsvm_file_gives(empty_engine, tmp_path):
    from rabit_tpu.learn import LinearObjFunction

    idx, val, y, nf = small_problem(n=200, k=4, nf=40)
    idx = np.sort(idx, axis=1)
    idx[:, 1:][idx[:, 1:] == idx[:, :-1]] = nf - 1   # no duplicate columns
    idx[-1, -1] = nf - 1                  # both see the same feat_dim
    path = tmp_path / "rows.libsvm"
    with open(path, "w") as f:
        for i in range(len(y)):
            f.write(f"{int(y[i])} " + " ".join(
                f"{c}:{v:.6g}" for c, v in zip(idx[i], val[i])) + "\n")
    runs = []
    for load in ("file", "arrays"):
        if rabit_tpu.version_number():    # a fresh store for each job
            rabit_tpu.finalize()
            rabit_tpu.init(rabit_engine="empty")
        obj = LinearObjFunction()
        if load == "file":
            obj.load_data(str(path))
        else:
            obj.load_arrays(idx, val, y, nf)
        for name, value in (("reg_L1", "0.5"), ("silent", "1"),
                            ("max_lbfgs_iter", "6")):
            obj.set_param(name, value)
        obj.lbfgs.run()
        runs.append((obj.lbfgs.get_weight().copy(), obj.lbfgs.old_objval))
    np.testing.assert_array_equal(runs[0][0], runs[1][0])
    assert runs[0][1] == runs[1][1]


def commits_of(obj, stop_after=None):
    """Run the solver, keeping a copy of every payload it commits;
    ``stop_after`` raises after that many commits (a kill)."""
    import copy

    kept, real = [], rabit_tpu.checkpoint

    class Killed(Exception):
        pass

    def keep(glob, local=None):
        real(glob, local)
        kept.append(copy.deepcopy((glob, local)))
        if stop_after is not None and len(kept) >= stop_after:
            raise Killed

    rabit_tpu.checkpoint = keep
    try:
        obj.lbfgs.run()
    except Killed:
        pass
    finally:
        rabit_tpu.checkpoint = real
    return kept


def test_the_device_arm_commits_what_the_host_arm_commits(
        empty_engine, monkeypatch):
    """Through the interpreted kernels against XLA's gather and
    scatter: the same float32 products summed in another order, so the
    first three iterations agree to a few float32 roundings of the
    gradient, not bit for bit (the loss's exp and log leave the
    float32-exact sizes at the first objective)."""
    from rabit_tpu.learn import linear

    idx, val, y, nf = small_problem()
    for name in ("lbfgs_margin", "lbfgs_grad"):       # the CPU interprets
        monkeypatch.setattr(sk, name, interpreted(getattr(sk, name)))
    runs = {}
    for arm in ("host", "device"):
        if rabit_tpu.version_number():
            rabit_tpu.finalize()
            rabit_tpu.init(rabit_engine="empty")
        monkeypatch.setattr(linear, "on_tpu", lambda arm=arm: arm == "device")
        runs[arm] = commits_of(objective(idx, val, y, nf, max_lbfgs_iter=3))
    assert len(runs["host"]) == len(runs["device"]) == 3
    for (gh, lh), (gd, ld) in zip(runs["host"], runs["device"]):
        np.testing.assert_allclose(gd["weight"], gh["weight"],
                                   rtol=1e-4, atol=1e-6)
        assert gd["old_objval"] == pytest.approx(gh["old_objval"], rel=1e-6)
        np.testing.assert_allclose(ld["hist"], lh["hist"],
                                   rtol=1e-3, atol=1e-4)


def test_margins_are_reused_and_never_stale(empty_engine):
    idx, val, y, nf = small_problem()
    obj = objective(idx, val, y, nf)
    obj.init_num_dim()
    w = np.zeros(nf + 1)
    obj.init_model(w)
    rng = np.random.default_rng(3)
    w = rng.standard_normal(nf + 1) * 0.1
    program.reset()
    fresh = obj.calc_grad(w)              # nothing kept: computes margins
    assert program.stats().get("lbfgs.margin_reused", 0) == 0
    obj.eval(w)
    again = obj.calc_grad(w.copy())       # same weights, another array
    assert program.stats()["lbfgs.margin_reused"] == 1
    np.testing.assert_array_equal(again, fresh)
    changed = w.copy()
    changed[7] += 0.25
    moved = obj.calc_grad(changed)        # a changed weight recomputes
    assert program.stats()["lbfgs.margin_reused"] == 1
    assert np.abs(moved - fresh).max() > 1e-4
    fresh_w = w.copy()
    obj.eval(fresh_w)
    w[7] += 0.25                          # ... also one changed in place
    np.testing.assert_array_equal(obj.calc_grad(w), moved)
    assert program.stats()["lbfgs.margin_reused"] == 1
    np.testing.assert_array_equal(obj.calc_grad(fresh_w), fresh)
    bias = changed.copy()
    bias[nf] -= 0.5                       # the bias alone moves them too
    obj.eval(changed)
    assert np.abs(obj.calc_grad(bias) - moved).max() > 1e-4
    assert program.stats()["lbfgs.margin_reused"] == 1


def test_a_gradient_started_ahead_is_used_for_its_weights_only(empty_engine):
    idx, val, y, nf = small_problem(seed=2)
    obj = objective(idx, val, y, nf)
    obj.init_num_dim()
    obj.init_model(np.zeros(nf + 1))
    rng = np.random.default_rng(8)
    w = rng.standard_normal(nf + 1) * 0.1
    want = obj.calc_grad(w)
    program.reset()
    obj.start_grad(w)
    np.testing.assert_array_equal(obj.calc_grad(w.copy()), want)
    assert program.stats()["learn.ahead"] == 1
    other = w + 0.01
    obj.start_grad(w)                      # ... and another is asked for
    got = obj.calc_grad(other)
    assert program.stats()["learn.ahead"] == 1
    assert np.abs(got - want).max() > 1e-4
    np.testing.assert_array_equal(obj.calc_grad(w), want)   # none left over
    assert program.stats()["learn.ahead"] == 1


def test_the_next_gradient_is_enqueued_before_the_commit(empty_engine):
    idx, val, y, nf = small_problem(seed=6)
    obj = objective(idx, val, y, nf, max_lbfgs_iter=3)
    order, start, commit = [], obj.start_grad, rabit_tpu.checkpoint

    def started(weight):
        order.append("start_grad")
        start(weight)

    def committed(glob, local=None):
        order.append("commit")
        commit(glob, local)

    obj.start_grad, rabit_tpu.checkpoint = started, committed
    try:
        obj.lbfgs.run()
    finally:
        rabit_tpu.checkpoint = commit
    # the last iteration has no gradient to start
    assert order == ["start_grad", "commit", "start_grad", "commit", "commit"]


def test_kill_after_a_commit_and_resume_gives_the_next_commit(empty_engine):
    idx, val, y, nf = small_problem(seed=4)
    whole = commits_of(objective(idx, val, y, nf, max_lbfgs_iter=5))
    assert len(whole) == 5
    rabit_tpu.finalize()
    rabit_tpu.init(rabit_engine="empty")
    killed = commits_of(objective(idx, val, y, nf, max_lbfgs_iter=5),
                        stop_after=3)
    assert len(killed) == 3 and rabit_tpu.version_number() == 3
    version, glob, local = rabit_tpu.load_checkpoint(with_local=True)
    assert version == 3 and local is not None
    np.testing.assert_array_equal(glob["weight"], whole[2][0]["weight"])
    np.testing.assert_array_equal(local["hist"], whole[2][1]["hist"])
    resumed = commits_of(objective(idx, val, y, nf, max_lbfgs_iter=5))
    assert len(resumed) == 2              # versions 4 and 5
    for (g, l), (wg, wl) in zip(resumed, whole[3:]):
        np.testing.assert_array_equal(g["weight"], wg["weight"])
        np.testing.assert_array_equal(g["dot_buf"], wg["dot_buf"])
        np.testing.assert_array_equal(l["hist"], wl["hist"])
        assert g["old_objval"] == wg["old_objval"]
        assert (g["num_iteration"], g["offset"], l["num_useful"]) == (
            wg["num_iteration"], wg["offset"], wl["num_useful"])


SPANS = ("lbfgs.init", "stage.put", "stage.bucket", "stage.compile",
         "learn.step", "lbfgs.grad", "learn.dispatch", "learn.fetch",
         "allreduce", "lbfgs.direction", "lbfgs.gram", "lbfgs.two_loop",
         "lbfgs.assemble", "lbfgs.linesearch", "lbfgs.eval", "commit",
         "commit.serialize")
COUNTERS = ("learn.versions", "learn.iterations", "lbfgs.evals", "lbfgs.grads",
            "lbfgs.margin_reused", "lbfgs.nnz", "lbfgs.nnz_padded")


def test_spans_and_counters_are_in_path_stats(empty_engine):
    from rabit_tpu import engine

    idx, val, y, nf = small_problem(seed=5)
    program.reset()
    obj = objective(idx, val, y, nf, max_lbfgs_iter=4)
    obj.lbfgs.run()
    stats = engine.get_engine().path_stats
    for name in SPANS:
        assert stats[name + ".n"] >= 1 and stats[name + ".total_s"] > 0, name
    for name in COUNTERS:
        assert stats[name] >= 1, name
    assert stats["learn.versions"] == stats["learn.step.n"] == 4
    assert stats["lbfgs.evals"] == stats["lbfgs.eval.n"] >= 4
    # every gradient rode the margins of the objective before it
    assert (stats["lbfgs.margin_reused"] == stats["lbfgs.grads"]
            == stats["lbfgs.grad.n"] == 4)
    # and was enqueued before the commit of the version before it (the
    # first has none before it, the last enqueues none after it)
    assert stats["learn.ahead"] == 3
    passes = stats["lbfgs.eval.n"] + 1 + stats["lbfgs.grad.n"]
    assert stats["lbfgs.nnz"] == passes * np.count_nonzero(val)
    assert stats["lbfgs.nnz_padded"] == passes * sk.capacity(6, nf)
    # the work of an iteration lies inside learn.step, set-up outside
    inside = sum(stats[n + ".total_s"] for n in (
        "lbfgs.grad", "lbfgs.direction", "lbfgs.linesearch", "commit"))
    assert inside <= stats["learn.step.total_s"]
    assert stats["stage.compile.total_s"] < stats["lbfgs.init.total_s"]
