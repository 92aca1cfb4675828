"""Histogram builder tests: numeric parity with numpy, the level's
chunking over kernel calls, and the rule between the two bodies."""
import numpy as np
import pytest

from rabit_tpu.learn import histogram


def _np_hist(bins, grad, hess, nbin):
    n, f = bins.shape
    out = np.zeros((f, nbin, 2), np.float64)
    for j in range(f):
        for b in range(nbin):
            m = bins[:, j] == b
            out[j, b, 0] = grad[m].sum()
            out[j, b, 1] = hess[m].sum()
    return out.astype(np.float32)


@pytest.mark.parametrize("n,f,nbin", [(1000, 5, 16), (513, 3, 7)])
def test_node_builder_matches_numpy(n, f, nbin):
    # the float32 per-node builder, as the level's fallback off the chip
    rng = np.random.default_rng(0)
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    got = np.asarray(histogram.build_level_local(
        bins, grad, hess, np.zeros(n, np.int32), [0], nbin,
        use_pallas=False))
    want = _np_hist(bins, grad, hess, nbin)
    assert got.shape == (1, f, nbin, 2)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-3)


def test_quantize_bounds():
    rng = np.random.default_rng(1)
    vals = rng.standard_normal((500, 4)).astype(np.float32)
    cuts = histogram.quantile_cuts(vals, 32)
    bins = histogram.apply_cuts(vals, cuts)
    assert bins.min() >= 0 and bins.max() < 32
    assert cuts.shape == (4, 31)
    # roughly uniform occupancy from quantile cuts
    counts = np.bincount(bins[:, 0], minlength=32)
    assert counts.min() > 0


@pytest.mark.parametrize("n,f,nbin", [(1000, 5, 16), (513, 3, 7),
                                      (300, 9, 256)])
def test_pallas_kernel_matches_numpy(n, f, nbin):
    from rabit_tpu.ops.histogram_kernel import hist_fused_multi

    rng = np.random.default_rng(3)
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    want = _np_hist(bins, grad, hess, nbin)
    # interpret-mode fused kernel, every row a level of one slot: f32
    # exact path, bf16 default path
    level = dict(node_of_row=np.zeros(n, np.int32), nslots=1, interpret=True)
    got = np.asarray(hist_fused_multi(
        bins.T, np.stack([grad, hess]), nbin, compute_dtype="float32",
        **level)).transpose(1, 2, 0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    got16 = np.asarray(hist_fused_multi(
        bins.T, np.stack([grad, hess]), nbin, **level)).transpose(1, 2, 0)
    np.testing.assert_allclose(got16, want, rtol=2e-2, atol=5e-2)


def test_multi_channel_kernel_matches_per_node():
    # a level's slots from the (2, n) weights and the node ids must equal
    # node-by-node builds of the masked weights
    from rabit_tpu.ops.histogram_kernel import hist_fused_multi

    rng = np.random.default_rng(4)
    n, f, nbin, m = 600, 4, 16, 4
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    node = rng.integers(0, m, n).astype(np.int32)
    out = np.asarray(hist_fused_multi(
        bins.T, np.stack([grad, hess]), nbin, interpret=True,
        compute_dtype="float32", node_of_row=node, nslots=m))
    assert out.shape == (2 * m, f, nbin)
    for v in range(m):
        want = _np_hist(bins, grad * (node == v), hess * (node == v), nbin)
        np.testing.assert_allclose(out[2 * v:2 * v + 2].transpose(1, 2, 0),
                                   want, rtol=1e-4, atol=1e-3)


def test_build_level_local_pallas_matches_fallback():
    rng = np.random.default_rng(5)
    n, f, nbin, m = 400, 3, 8, 2
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    node = rng.integers(0, m, n).astype(np.int32)
    got = np.asarray(histogram.build_level_local(
        bins, grad, hess, node, [0, 1], nbin, use_pallas=True,
        compute_dtype="float32"))
    want = np.asarray(histogram.build_level_local(
        bins, grad, hess, node, [0, 1], nbin, use_pallas=False))
    assert got.shape == (m, f, nbin, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_build_level_chunks_past_channel_budget():
    # 40 nodes -> 80 weight channels, past the widest call the kernel
    # module asks for (16): level_hist builds them in five calls
    rng = np.random.default_rng(7)
    n, f, nbin, m = 300, 2, 8, 40
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    node = rng.integers(0, m, n).astype(np.int32)
    got = np.asarray(histogram.build_level_local(
        bins, grad, hess, node, list(range(m)), nbin, use_pallas=True,
        compute_dtype="float32"))
    want = np.asarray(histogram.build_level_local(
        bins, grad, hess, node, list(range(m)), nbin, use_pallas=False))
    assert got.shape == (m, f, nbin, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_split_gain_prefers_clean_split():
    # two clusters: negative gradients in low bins, positive in high bins
    nbin = 8
    hist = np.zeros((1, nbin, 2), np.float32)
    hist[0, :4, 0] = -5.0
    hist[0, 4:, 0] = +5.0
    hist[0, :, 1] = 10.0
    gain, _left = histogram.split_candidates(hist)
    assert gain.shape == (1, nbin - 1)
    assert gain.argmax() == 3  # the boundary between the clusters


# ----------------------------------------------------------------------
# the staged layout and the level builder of the device arm
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n,chunk,nan", [(1000, 1 << 20, False),
                                         (1000, 1 << 20, True),
                                         (1000, 384, True)],
                         ids=["dense", "nan", "nan-chunks-and-a-tail"])
def test_stage_bins_equals_apply_cuts_bit_for_bit(monkeypatch, n, chunk, nan):
    rng = np.random.default_rng(11)
    f, nbin = 5, 16
    vals = rng.standard_normal((n, f)).astype(np.float32)
    vals[::7, 2] = vals[3, 2]                    # ties, on a cut or not
    if nan:
        vals[rng.random((n, f)) < 0.1] = np.nan
    cuts = histogram.quantile_cuts(vals, nbin)
    monkeypatch.setattr(histogram, "STAGE_CHUNK_ROWS", chunk)
    bins_t, seen = histogram.stage_bins(vals, cuts, nbin)
    want = histogram.apply_cuts(vals, cuts)
    fpad = histogram.staged_features(f, nbin)
    assert bins_t.shape == (fpad, n) and str(bins_t.dtype) == "int32"
    np.testing.assert_array_equal(np.asarray(bins_t)[:f].T, want)
    assert not np.asarray(bins_t)[f:].any()
    assert list(np.asarray(seen)) == [int(nan), int(want.max())]


@pytest.mark.parametrize("kw", [{"use_pallas": False},
                                {"use_pallas": True,
                                 "compute_dtype": "float32"}],
                         ids=["xla", "kernel"])
def test_level_hist_padded_slots_read_zero(kw):
    """A level always has 2^depth slots: a slot with no row reads
    zeros, a row at no slot is in no histogram, the live slots read the
    per-node histograms."""
    import jax.numpy as jnp

    rng = np.random.default_rng(12)
    n, f, nbin, slots = 700, 3, 8, 8
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    node = rng.choice([-3, -1, 0, 3, 5], n).astype(np.int32)
    fpad = histogram.staged_features(f, nbin)
    bins_t = jnp.zeros((fpad, n), jnp.int32).at[:f].set(bins.T)
    got = np.asarray(histogram.level_hist(
        bins_t, jnp.stack([grad, hess]), jnp.asarray(node), slots, f, nbin,
        **kw))
    assert got.shape == (slots, f, nbin, 2)
    for s in range(slots):
        rows = node == s
        want = _np_hist(bins[rows], grad[rows], hess[rows], nbin)
        if s not in (0, 3, 5):
            assert not got[s].any()
        np.testing.assert_allclose(got[s], want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("f,padded", [(32, False), (28, True)],
                         ids=["staged", "not-staged"])
def test_staged_input_makes_the_kernels_pad_a_noop(f, padded):
    """(fpad, n) int32 input with n a multiple of the block reaches the
    kernel as it is: the compiled program holds no pad of the bins."""
    import functools

    import jax
    import jax.numpy as jnp

    from rabit_tpu.ops.histogram_kernel import hist_fused_multi

    n, nbin = 512, 16                  # 16 bins: 32 features a group
    fn = jax.jit(functools.partial(hist_fused_multi, nbin=nbin, nslots=2,
                                   interpret=True))
    text = fn.lower(jax.ShapeDtypeStruct((f, n), jnp.int32),
                    jax.ShapeDtypeStruct((2, n), jnp.float32),
                    node_of_row=jax.ShapeDtypeStruct((n,), jnp.int32)
                    ).compile().as_text()
    pads = [ln for ln in text.splitlines()
            if " pad(" in ln and "s32[32,512]" in ln.split(" pad(")[0]]
    assert bool(pads) == padded, pads


def test_split_gain_of_a_node_of_millions_of_rows():
    """Found on the chip (PR 26): in float32 a total summed in another
    order than the prefix sums left an empty right side at hr = -1, and
    hr + lambda = 0 made that candidate's gain infinite."""
    nbin = 256
    hist = np.zeros((2, nbin, 2), np.float32)
    rng = np.random.default_rng(13)
    hist[:, :162, 1] = 29000.0 + rng.random((2, 162)).astype(np.float32)
    hist[:, :162, 0] = rng.standard_normal((2, 162)) * 1e3
    gain, _left = histogram.split_candidates(hist, 1.0)
    assert np.isfinite(gain).all()
    assert abs(gain[:, 161:]).max() < 1e-6     # nothing on the right
    # the first bin's rows again, absent from both features
    total = hist[0].sum(axis=0, dtype=np.float64) + hist[0, 0]
    gain_m, _left = histogram.split_candidates(hist, 1.0, total=total)
    assert np.isfinite(gain_m).all()


# ----------------------------------------------------------------------
# a level wider than the kernel's widest worthwhile call is built by
# several calls inside level_hist
# ----------------------------------------------------------------------
def _level_case(nslots, n=2500, f=3, nbin=8, seed=21):
    """Rows in no slot (-1, and slots beyond the level), two slots with
    no row, and a row count that is no multiple of the kernel's block
    (2048 here: two blocks, the second padded)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + nslots)
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    gh = np.stack([rng.standard_normal(n), rng.random(n)]).astype(np.float32)
    node = rng.integers(-1, nslots + 2, n).astype(np.int32)
    empty = sorted({nslots // 2, nslots - 1})
    node[np.isin(node, empty)] = -1
    fpad = histogram.staged_features(f, nbin)
    bins_t = jnp.zeros((fpad, n), jnp.int32).at[:f].set(bins.T)
    return bins_t, jnp.asarray(gh), jnp.asarray(node), empty


def _grids(jaxpr) -> list:
    """The grid of every ``pallas_call`` in a jaxpr, nested programs
    included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                grids += _grids(inner)
    return grids


def _pallas_calls(jaxpr) -> int:
    """``pallas_call`` equations in a jaxpr, nested programs included."""
    return len(_grids(jaxpr))


@pytest.fixture
def body(request, monkeypatch):
    """``two-level``: the rule's crossing out of reach, so that every
    level is the two-level body's, in calls of its widest; ``rule``:
    the rule as it stands; ``lane``: every level the lane-wide body's."""
    from rabit_tpu.ops import histogram_kernel as hk

    crossing = {"two-level": 1 << 30, "rule": hk._LANE_CROSSING,
                "lane": 2}[request.param]
    monkeypatch.setattr(hk, "_LANE_CROSSING", crossing)
    return request.param


def _mass(bins_t, gh, node, nslots, f, nbin):
    import jax.numpy as jnp

    return np.asarray(histogram.level_hist(
        bins_t, jnp.abs(gh), node, nslots, f, nbin, use_pallas=False))


@pytest.mark.parametrize("body", ["two-level", "rule"], indirect=True)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("nslots", [1, 8, 9, 16, 17, 32, 64])
def test_chunked_level_equals_the_direct_call_exactly(nslots, dtype, body):
    """The calls of a chunked level give every channel the rows of the
    direct calls in the same order: equal bit for bit under the
    two-level body (a direct call takes ``max_channels`` channels, 8
    slots here, so a wider level is held against several), under the
    rule (a wide level is one lane-wide call, the direct calls of 8
    slots lane-wide too) to the order of the float32 adds; and equal to
    the float32 XLA level to the operand's rounding."""
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    f, nbin = 3, 8
    bins_t, gh, node, empty = _level_case(nslots)
    got = np.asarray(histogram.level_hist(
        bins_t, gh, node, nslots, f, nbin, use_pallas=True,
        compute_dtype=dtype))
    assert got.shape == (nslots, f, nbin, 2)
    per = hk.max_channels(nbin, bins_t.shape[0]) // 2
    assert per == 8
    direct = jnp.concatenate([
        hk.hist_fused_multi(bins_t, gh, nbin, node_of_row=node - lo,
                            nslots=min(per, nslots - lo), compute_dtype=dtype)
        for lo in range(0, nslots, per)])
    direct = np.asarray(direct.reshape(nslots, 2, -1, nbin)
                        .transpose(0, 2, 3, 1)[:, :f])
    mass = _mass(bins_t, gh, node, nslots, f, nbin)
    if body == "two-level":
        np.testing.assert_array_equal(got, direct)
    else:
        assert (np.abs(got - direct) <= 1e-5 * mass.sum(
            axis=(1, 2), keepdims=True)).all()
    for s in empty:
        assert not got[s].any()
    want = np.asarray(histogram.level_hist(bins_t, gh, node, nslots, f, nbin,
                                           use_pallas=False))
    # a bin's sum of bf16-rounded weights is off by 2^-9 of its sum of |w|
    room = 2.0 ** -8 if dtype == "bfloat16" else 1e-5
    assert (np.abs(got - want) <= room * mass + 1e-4).all()


@pytest.mark.parametrize("body", ["two-level", "rule"], indirect=True)
@pytest.mark.parametrize("nslots", [1, 8, 9, 16, 32, 64])
def test_level_lowers_to_one_kernel_call_a_width(nslots, body):
    """Under the two-level body a level at or under the kernel's widest
    worthwhile call is one ``pallas_call`` and a wider one
    ceil(2 * nslots / width) of them in the same program; under the rule
    a level from the crossing on is one lane-wide call up to 256
    lanes."""
    import jax

    from rabit_tpu.ops import histogram_kernel as hk

    f, nbin = 3, 8
    bins_t, gh, node, _ = _level_case(nslots, n=256)
    width = hk.max_channels(nbin, bins_t.shape[0])
    assert width == 16
    jaxpr = jax.make_jaxpr(lambda b, w, nd: histogram.level_hist(
        b, w, nd, nslots, f, nbin, use_pallas=True))(bins_t, gh, node)
    calls = _pallas_calls(jaxpr.jaxpr)
    lane = body == "rule" and 2 * nslots >= hk._LANE_CROSSING
    assert calls == (1 if lane else -(-2 * nslots // width))
    assert histogram.level_calls(nslots, f, nbin, use_pallas=True) == (
        calls, int(lane))
    assert histogram.level_calls(nslots, f, nbin, use_pallas=False) == (0, 0)


@pytest.mark.parametrize("nbin,f,want", [(256, 28, 16), (256, 32, 16),
                                         (8, 3, 16), (256, 2000, 3)],
                         ids=["cell", "cell-staged", "tiny", "wide"])
def test_widest_call_is_the_smaller_of_the_line_and_the_vmem_bound(
        nbin, f, want):
    """Wide-feature shapes whose accumulator bound is under the line's
    width keep that smaller bound."""
    from rabit_tpu.ops import histogram_kernel as hk

    assert hk.max_channels(nbin, f) == want
    assert histogram.slots_per_call(nbin, f) == max(1, want // 2)


@pytest.mark.parametrize("body", ["two-level", "rule"], indirect=True)
def test_direct_caller_gets_what_the_plans_call_holds_and_no_more(body):
    """The two-level body's widest direct call is ``max_channels``
    channels (16: 8 slots), and the refusal names the caller that
    chunks; the lane-wide body's is what 256 lanes hold."""
    from rabit_tpu.ops import histogram_kernel as hk

    most = 8 if body == "two-level" else 128
    bins_t, gh, node, _ = _level_case(most, n=256)
    out = hk.hist_fused_multi(bins_t, gh, 8, node_of_row=node, nslots=most)
    assert out.shape[0] == 2 * most
    with pytest.raises(ValueError, match="out of range") as refusal:
        hk.hist_fused_multi(bins_t, gh, 8, node_of_row=node, nslots=most + 1)
    assert ("level_hist" in str(refusal.value)) == (body == "two-level")


@pytest.mark.parametrize("body,calls", [("two-level", (3, 0)),
                                        ("rule", (1, 1))], indirect=["body"])
def test_build_level_local_takes_level_hists_chunks(body, calls):
    """A ``node_ids`` list longer than a call's slots, of ids in no
    order: ``build_level_local`` is ``level_hist`` on the ids' places
    (42 channels: three calls of the two-level body, one lane-wide)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(33)
    n, f, nbin = 900, 3, 8
    ids = [int(v) for v in rng.permutation(60)[:21]]
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    node = rng.integers(-1, 60, n).astype(np.int32)
    got = np.asarray(histogram.build_level_local(
        bins, grad, hess, node, ids, nbin, use_pallas=True))
    place = np.full(n, -1, np.int32)
    for pos, v in enumerate(ids):
        place[node == v] = pos
    want = np.asarray(histogram.level_hist(
        jnp.asarray(bins.T), jnp.stack([grad, hess]), jnp.asarray(place),
        len(ids), f, nbin, use_pallas=True))
    assert histogram.level_calls(len(ids), f, nbin, use_pallas=True) == calls
    np.testing.assert_array_equal(got, want)
    exact = np.asarray(histogram.build_level_local(
        bins, grad, hess, node, ids, nbin, use_pallas=False))
    np.testing.assert_allclose(got, exact, rtol=0, atol=0.05)


# ----------------------------------------------------------------------
# the lane-wide body: channels on the MXU's lanes, a round's trees in
# one call; and the rule that shares a level out between the bodies
# ----------------------------------------------------------------------
def _forest_case(trees, nslots, n, f, nbin, seed=43):
    """A round's level: ``(fpad, n)`` bins with absent entries (code
    ``nbin``), ``(T, 2, n)`` weights, ``(T, n)`` slots with rows at -1."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + trees * nslots)
    bins = rng.integers(0, nbin + 1, (n, f)).astype(np.int32)
    gh = np.stack([rng.standard_normal((trees, n)),
                   rng.random((trees, n))], axis=1).astype(np.float32)
    node = rng.integers(-1, nslots, (trees, n)).astype(np.int32)
    fpad = histogram.staged_features(f, nbin)
    bins_t = jnp.zeros((fpad, n), jnp.int32).at[:f].set(bins.T)
    return bins, bins_t, gh, node


def _float64_level(bins, gh, node, nslots, nbin, dtype):
    """``(T * nslots, f, nbin, 2)`` in float64 from the weights rounded
    as the kernel rounds them; an absent entry in no bin, a row at -1
    in no slot."""
    import jax.numpy as jnp

    w = np.asarray(jnp.asarray(gh).astype(dtype).astype(jnp.float32),
                   np.float64)
    trees, (n, f) = node.shape[0], bins.shape
    out = np.zeros((trees, nslots, f, nbin + 1, 2))
    for t in range(trees):
        live = node[t] >= 0
        for j in range(f):
            for c in range(2):
                np.add.at(out[t, :, j, :, c],
                          (node[t][live], bins[live, j]), w[t, c][live])
    return out[:, :, :, :nbin].reshape(trees * nslots, f, nbin, 2)


def _lane_level_against_float64_and_two_level(monkeypatch, f, trees, nslots,
                                              n, dtype, totals):
    """The lane-wide body in interpret mode against numpy in float64
    and against the two-level body on the same level of ``f`` features
    of 16 bins; returns the lane-wide level."""
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    nbin = 16
    bins, bins_t, gh, node = _forest_case(trees, nslots, n, f, nbin)

    def level(crossing):
        monkeypatch.setattr(hk, "_LANE_CROSSING", crossing)
        return np.asarray(histogram.level_hist(
            bins_t, jnp.asarray(gh), jnp.asarray(node), nslots, f, nbin,
            use_pallas=True, compute_dtype=dtype, totals=totals))

    got, old = level(2), level(1 << 30)
    assert histogram.level_calls(nslots, f, nbin, True, trees)[1] == 0
    monkeypatch.setattr(hk, "_LANE_CROSSING", 2)
    calls, lane = histogram.level_calls(nslots, f, nbin, True, trees)
    assert calls == lane == -(-trees // max(1, 256 // max(16, 2 * nslots))) \
        * -(-2 * nslots // 256)
    assert got.shape == old.shape == (trees * nslots, f + totals, nbin, 2)
    want = _float64_level(bins, gh, node, nslots, nbin, dtype)
    mass = _float64_level(bins, np.abs(gh), node, nslots, nbin, dtype).sum(
        axis=(1, 2), keepdims=True)
    for hist in (got, old):
        assert (np.abs(hist[:, :f] - want) <= 1e-5 * mass).all()
    assert (np.abs(got - old) <= 1e-5 * np.concatenate(
        [mass] * (1 + totals), axis=1)[:, :f + totals].max(
            axis=1, keepdims=True)).all()
    if totals:
        # bin 0 of the extra row: the slot's (grad, hess) over all rows
        sums = _float64_level(np.zeros((n, 1), np.int32), gh, node, nslots,
                              nbin, dtype)[:, 0, 0]
        np.testing.assert_allclose(got[:, f, 0], sums, rtol=1e-5, atol=1e-4)
        assert not got[:, f, 1:].any()
    return got


@pytest.mark.parametrize("trees,nslots,n,dtype,totals", [
    (7, 1, 2500, "bfloat16", False),        # 14 channels
    (1, 16, 2500, "bfloat16", False),       # 32
    (1, 16, 2048, "float32", True),         # 32, exact operands, totals
    (4, 16, 4097, "bfloat16", True),        # 128: every lane, ragged
    (1, 64, 2500, "bfloat16", False),       # 128, one tree
    (7, 16, 1300, "bfloat16", False),       # 224: a call of 256 lanes
    (7, 16, 700, "float32", False),
    (3, 2, 900, "float32", True),           # trees narrower than a tile
    (9, 16, 600, "bfloat16", False),        # 288: two calls, 8 + 1 trees
    (1, 256, 2100, "bfloat16", False),      # 512: a tree over two calls
], ids=lambda v: str(v))
def test_lane_wide_level_is_the_float64_level_and_the_two_level_bodys(
        monkeypatch, trees, nslots, n, dtype, totals):
    """The lane-wide body in interpret mode against numpy in float64
    and against the two-level body on the same level: absent entries,
    rows at node -1, a ragged ``n``, both compute dtypes, with and
    without the slots' totals."""
    _lane_level_against_float64_and_two_level(
        monkeypatch, 5, trees, nslots, n, dtype, totals)


@pytest.fixture
def small_chunks(request, monkeypatch):
    """A lane-wide grid step holds ``request.param`` features, as if the
    accumulator's budget held no more (the real budget holds hundreds:
    minutes of interpreted kernel)."""
    from rabit_tpu.ops import histogram_kernel as hk

    import jax

    monkeypatch.setattr(hk, "lane_chunk",
                        lambda nbin, f, lanes: request.param)
    jax.clear_caches()      # the kernel's builder is traced once a shape
    yield request.param
    jax.clear_caches()


@pytest.mark.parametrize("f,small_chunks,trees,nslots,n,dtype,totals", [
    (19, 8, 1, 8, 2500, "bfloat16", False),    # 3 chunks, the last of 3
    (19, 16, 1, 16, 4097, "bfloat16", True),   # 2: the second reads past
    (21, 8, 1, 16, 2048, "float32", True),     # the 24 staged rows
    (40, 16, 1, 8, 700, "bfloat16", False),    # 3 chunks of 16: 48 > 40
    (24, 8, 3, 4, 900, "bfloat16", True),      # a forest, whole chunks
    (33, 16, 7, 16, 600, "bfloat16", False),   # 256 lanes, 3 chunks
    (9, 8, 1, 64, 4097, "bfloat16", True),     # 128 channels, 2 chunks
], indirect=["small_chunks"], ids=lambda v: str(v))
def test_lane_wide_level_chunked_over_its_features(
        monkeypatch, f, small_chunks, trees, nslots, n, dtype, totals):
    """A shard of more features than one grid step's accumulator holds:
    the lane-wide call takes them chunk by chunk on a grid axis outside
    the row blocks (a feature count that is no multiple of the chunk, a
    last chunk that reads past the staged rows, a ragged ``n``, rows at
    node -1, absent entries, totals) and reads what one chunk of all the
    features reads, bit for bit: a feature's adds are the same adds in
    the same order."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    got = _lane_level_against_float64_and_two_level(
        monkeypatch, f, trees, nslots, n, dtype, totals)
    _, bins_t, gh, node = _forest_case(trees, nslots, n, f, 16)
    args = (bins_t, jnp.asarray(gh), jnp.asarray(node))

    def level(b, w, nd):
        return histogram.level_hist(b, w, nd, nslots, f, 16, use_pallas=True,
                                    compute_dtype=dtype, totals=totals)

    blocks = -(-n // 2048)
    assert _grids(jax.make_jaxpr(level)(*args).jaxpr) == [
        (-(-f // small_chunks), blocks)]
    monkeypatch.undo()
    jax.clear_caches()
    monkeypatch.setattr(hk, "_LANE_CROSSING", 2)
    assert _grids(jax.make_jaxpr(level)(*args).jaxpr) == [(blocks,)]
    np.testing.assert_array_equal(got, np.asarray(level(*args)))


def test_a_forests_level_is_its_trees_levels_one_by_one():
    """``(T, 2, n)`` weights and ``(T, n)`` slots give, tree-major, what
    each tree's own ``level_hist`` gives: on the XLA path exactly, and
    the entry itself refuses a forest without its node ids."""
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    f, nbin, trees, nslots = 5, 16, 3, 4
    _, bins_t, gh, node = _forest_case(trees, nslots, 800, f, nbin)
    gh, node = jnp.asarray(gh), jnp.asarray(node)
    for kw in ({"use_pallas": False},
               {"use_pallas": True, "compute_dtype": "float32"}):
        whole = np.asarray(histogram.level_hist(
            bins_t, gh, node, nslots, f, nbin, totals=True, **kw))
        parts = np.concatenate([np.asarray(histogram.level_hist(
            bins_t, gh[t], node[t], nslots, f, nbin, totals=True, **kw))
            for t in range(trees)])
        np.testing.assert_allclose(whole, parts, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="node ids"):
        hk.hist_fused_multi(bins_t, gh, nbin)


@pytest.mark.parametrize("weights,level", [
    ((6, 300), {}),                                   # the per-node form
    ((6, 300), {"node_of_row": (300,), "nslots": 3}),
    ((2, 300), {}),
    ((2, 300), {"node_of_row": (300,)}),
    ((3, 2, 300), {"node_of_row": (3, 300)}),
    ((3, 4, 300), {"node_of_row": (3, 300), "nslots": 2}),
    ((300,), {"node_of_row": (300,), "nslots": 1}),
], ids=["plain-matrix", "plain-matrix-with-nodes", "pairs-without-nodes",
        "pairs-without-nslots", "forest-without-nslots", "forest-of-fours",
        "one-row"])
def test_a_call_without_node_ids_is_refused(weights, level):
    """The kernel's entry takes a tree level and nothing else: (2, n) or
    (T, 2, n) weights with node ids and ``nslots``.  The plain (nw, n)
    matrix of before PR 26 and every half-given level is a ``ValueError``
    that names what to call instead, before anything is traced."""
    from rabit_tpu.ops import histogram_kernel as hk

    bins_t = np.zeros((3, 300), np.int32)
    kw = dict(level)
    if "node_of_row" in kw:
        kw["node_of_row"] = np.zeros(kw["node_of_row"], np.int32)
    with pytest.raises(ValueError, match="node ids.*level_hist"):
        hk.hist_fused_multi(bins_t, np.ones(weights, np.float32), 8,
                            interpret=True, **kw)


def test_a_wide_shards_call_over_its_width_names_level_hist():
    """968 features of 256 bins: the accumulator holds 6 channels, so a
    direct two-level call of 4 slots is refused with the caller that
    chunks, and ``level_hist`` builds the same 4 slots in two calls of 3
    and 1 that read what direct calls of those widths read."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    f, nbin, nslots, n = 968, 256, 4, 256
    assert hk.max_channels(nbin, f) == 6
    assert hk.level_plan(nbin, f, nslots) == (False, 1, 3)
    rng = np.random.default_rng(45)
    bins_t = jnp.asarray(rng.integers(0, nbin + 1, (f, n)).astype(np.int32))
    gh = jnp.asarray(rng.standard_normal((2, n)).astype(np.float32))
    node = jnp.asarray(rng.integers(-1, nslots, n).astype(np.int32))
    with pytest.raises(ValueError, match="3 at 968 features.*level_hist"):
        hk.hist_fused_multi(bins_t, gh, nbin, node_of_row=node,
                            nslots=nslots, interpret=True)
    assert histogram.level_calls(nslots, f, nbin, True) == (2, 0)

    def level(b, w, nd):
        return histogram.level_hist(b, w, nd, nslots, f, nbin,
                                    use_pallas=True)

    assert _pallas_calls(jax.make_jaxpr(level)(bins_t, gh, node).jaxpr) == 2
    got = np.asarray(level(bins_t, gh, node))
    direct = np.concatenate([np.asarray(hk.hist_fused_multi(
        bins_t, gh, nbin, node_of_row=node - lo, nslots=ns, interpret=True))
        for lo, ns in ((0, 3), (3, 1))])
    np.testing.assert_array_equal(
        got, direct.reshape(nslots, 2, f, nbin).transpose(0, 2, 3, 1))


# (f, trees) of the four boosting configurations, 256 bins, depth 6: the
# build slots of a tree level by level are 1, 1, 2, 4, 8, 16
@pytest.mark.parametrize("f,trees,want", [
    # HIGGS and approx: 2 to 32 channels
    (28, 1, [(1, 0), (1, 0), (1, 0), (1, 0), (1, 1), (1, 1)]),
    (32, 1, [(1, 0), (1, 0), (1, 0), (1, 0), (1, 1), (1, 1)]),
    # Covertype: 14 to 224 channels of seven trees
    (54, 7, [(1, 1), (1, 1), (1, 1), (1, 1), (1, 1), (1, 1)]),
    (56, 7, [(1, 1), (1, 1), (1, 1), (1, 1), (1, 1), (1, 1)]),
    # Bosch: 3 slots a call of the two-level body, and the two widest
    # levels one lane-wide call each, 7 calls a round where 14 were
    (968, 1, [(1, 0), (1, 0), (1, 0), (2, 0), (1, 1), (1, 1)]),
], ids=["higgs-approx", "higgs-staged", "covtype", "covtype-staged", "bosch"])
def test_the_rule_at_the_four_configurations_shapes(f, trees, want):
    """Which body builds each level of a depth-6 round, in how many
    calls: ``level_calls`` as ``(calls, lane-wide among them)``."""
    from rabit_tpu.ops import histogram_kernel as hk

    levels = [1, 1, 2, 4, 8, 16]
    got = [histogram.level_calls(s, f, 256, True, trees) for s in levels]
    assert got == want
    # the staged width reads the same rule
    assert got == [histogram.level_calls(
        s, histogram.staged_features(f, 256), 256, True, trees)
        for s in levels]
    for s, (calls, lane) in zip(levels, got):
        plan = hk.level_plan(256, f, s, trees)
        assert plan.lane == bool(lane)
        assert histogram.slots_per_call(256, f, s, trees) == plan.slots
        assert calls == -(-trees // plan.trees) * -(-s // plan.slots)
    assert hk.lane_width(256, f) == 256
    # the narrow shards' features are one grid step's, at either width of
    # call; the wide one's go in four
    chunks = [-(-f // hk.lane_chunk(256, f, lanes)) for lanes in (128, 256)]
    assert chunks == ([4, 8] if f == 968 else [1, 1])


@pytest.mark.parametrize("f,lanes,want", [
    (28, 128, 32), (28, 256, 32), (56, 256, 56), (128, 256, 128),
    (129, 256, 72), (264, 128, 264), (265, 128, 136), (968, 128, 248),
    (968, 256, 128), (2000, 128, 256), (4000, 256, 128)],
    ids=lambda v: str(v))
def test_lane_chunk_is_what_the_accumulators_budget_holds(f, lanes, want):
    """A grid step's features at 256 bins: whole groups of 8, at most
    what a third of the VMEM limit holds of f32 accumulator (264 at 128
    lanes, 128 at 256), shared out evenly over the fewest chunks."""
    from rabit_tpu.ops import histogram_kernel as hk

    budget = hk._VMEM_LIMIT_BYTES // 3
    chunk = hk.lane_chunk(256, f, lanes)
    assert chunk == want and chunk % 8 == 0
    assert chunk * 256 * lanes * 4 <= budget
    most = budget // (8 * 256 * lanes * 4) * 8
    assert -(-f // chunk) == -(-f // most)


@pytest.mark.parametrize("nslots,trees,want", [
    (6, 1, (False, 1, 8)), (7, 1, (True, 1, 7)), (64, 1, (True, 1, 64)),
    (128, 1, (True, 1, 128)), (256, 1, (True, 1, 128)),
    (1, 6, (False, 1, 8)), (1, 7, (True, 7, 1)), (1, 20, (True, 16, 1)),
    (16, 9, (True, 8, 16)), (32, 7, (True, 4, 32))],
    ids=lambda v: str(v))
def test_level_plan_by_width_alone(nslots, trees, want):
    """The rule reads the level's channels and nothing else of the job:
    under the crossing the two-level body's calls, from it on the
    lane-wide body's, a tree padded to 16 lanes, 256 lanes a call."""
    from rabit_tpu.ops import histogram_kernel as hk

    assert hk._LANE_CROSSING == 14
    assert tuple(hk.level_plan(256, 28, nslots, trees)) == want


@pytest.mark.parametrize("nslots,trees,want", [
    (4, 1, (False, 1, 3)), (6, 1, (False, 1, 3)), (7, 1, (True, 1, 7)),
    (8, 1, (True, 1, 8)), (16, 1, (True, 1, 16)), (1, 7, (True, 7, 1)),
    (16, 7, (True, 7, 16)), (256, 1, (True, 1, 128))],
    ids=lambda v: str(v))
def test_level_plan_of_a_wide_shard_crosses_where_a_narrow_ones_does(
        nslots, trees, want):
    """At 968 features the same crossing in channels: both bodies' costs
    are linear in the features.  Under it the two-level body's calls of
    what its accumulator holds (3 slots), from it on one lane-wide call,
    the features chunk by chunk inside it."""
    from rabit_tpu.ops import histogram_kernel as hk

    assert tuple(hk.level_plan(256, 968, nslots, trees)) == want


def test_the_chip_check_of_the_two_bodies_rehearsed(monkeypatch):
    """``tools/hist_kernel_check.py`` at tiny shapes with the kernels
    interpreted: every comparison it would make on the chip is made and
    passes, a timing line a width follows, and off the chip ``main``
    refuses to judge."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools",
                        "hist_kernel_check.py")
    spec = importlib.util.spec_from_file_location("hist_kernel_check", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(tool, "SLICE_ROWS", 2048)
    monkeypatch.setattr(tool, "WIDTHS", (2, 32))
    lines = []
    monkeypatch.setattr(tool.hk, "lane_chunk", lambda nbin, f, lanes: 8)
    tool.jax.clear_caches()
    assert tool.run({"one": (8, 5, 1 << 12, 1, 0),
                     "forest": (8, 6, 1 << 12, 7, 0),
                     "wide": (16, 16, 3000, 1, 4)},
                    43, True, lines.append)
    tool.jax.clear_caches()
    checks = [ln for ln in lines if "check" in ln]
    assert len(checks) == 3 * (2 + 4 + 3) and all(ln["ok"] for ln in checks)
    assert {ln["shape"] for ln in checks} == {
        "one", "one-ragged", "forest", "forest-ragged", "wide",
        "wide-ragged"}
    wide = [ln for ln in checks if ln["shape"].startswith("wide")
            and ln["check"] == "lane_vs_two_level"]
    assert [(ln["slots"], ln["feature_chunks"]) for ln in wide] == [
        (8, 2), (16, 2), (8, 2)]
    # absent station by station, 81% of the entries
    assert all(0.7 < ln["entries_absent"] < 0.9 for ln in wide)
    assert all(ln["rows_at_no_node"] > 0 for ln in checks
               if ln["check"] == "lane_vs_two_level")
    assert {"bodies_agree": True} in lines
    timed = [ln for ln in lines if "timing" in ln]
    assert [(ln["trees"], ln["channels"]) for ln in timed] == [
        (1, 2), (1, 32), (1, 2), (1, 32),
        (7, 14), (7, 28), (7, 56), (7, 112), (7, 224), (1, 2), (1, 32)]
    monkeypatch.setattr("sys.argv", [path])
    assert tool.main() == 2


def test_the_chip_check_of_the_packed_body_rehearsed(monkeypatch):
    """``tools/hist_kernel_check.py --cases packed`` at a tiny shape with
    the kernels interpreted: the rule's own plan on cuts of indicator
    columns (some of one reachable code) at 128 and 256 lanes and ragged,
    plans of 2 to 16 codes by hand, each packed call equal to the
    unpacked one bit for bit and to float64, then a timing line a width
    of call."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools",
                        "hist_kernel_check.py")
    spec = importlib.util.spec_from_file_location("hist_kernel_check", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(tool, "SLICE_ROWS", 2048)
    monkeypatch.setattr(tool, "CUT_ROWS", 4096)
    lines = []
    assert tool.run_packed((24, 20, 1 << 13, 7, 0), 43, True, lines.append)
    rule = lines[0]
    assert rule["narrow"] == 10 and rule["width"] in (2, 4)
    assert min(rule["codes_a_feature"]) == 2    # 0, which no row holds, and 255
    checks = [ln for ln in lines if "check" in ln]
    assert len(checks) == 2 * (3 + 4) and all(ln["ok"] for ln in checks)
    packed = [ln for ln in checks if ln["check"] == "packed_vs_unpacked"]
    assert all(ln["equal_bitwise"] and ln["rows_at_no_node"] > 0
               and ln["entries_absent"] > 0 for ln in packed)
    assert [(ln["shape"], ln["lanes"]) for ln in packed] == [
        ("rule", 128), ("rule", 256), ("rule-ragged", 128), ("width-2", 256),
        ("width-4", 256), ("width-8", 256), ("width-16", 256)]
    assert {"packed_equals_unpacked": True} in lines
    timed = [ln for ln in lines if "timing" in ln]
    assert [ln["lanes"] for ln in timed] == [128, 256]
    assert all({"unpacked_s", "rule_s", "width_2_s", "width_16_s"} <= set(ln)
               for ln in timed)


# ----------------------------------------------------------------------
# narrow features share one product of the lane-wide body (PR 48): the
# rule from the cuts, and the packed call against the unpacked one
# ----------------------------------------------------------------------
def _packed_case(held, wide, n, trees, nslots, nbin=256, seed=48):
    """A level over ``wide`` features of every bin and one narrow
    feature a list of ``held`` (the codes it can take), absent entries
    (code ``nbin``) in all of them and rows at node -1: the staged bins,
    the plain ``(n, f)`` ones, weights, node ids and the plan with its
    codes as ``pack_plan`` lays them out."""
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    rng = np.random.default_rng(seed + n + trees * nslots)
    f = wide + len(held)
    bins = rng.integers(0, nbin + 1, (n, f)).astype(np.int32)
    width = hk._next_pow2(max(len(c) for c in held))
    codes = np.full((len(held), width), hk._NO_CODE, np.int32)
    # narrow features between the wide ones and after them
    narrow = tuple(range(1, 1 + len(held))) if wide else tuple(range(f))
    for k, (j, mine) in enumerate(zip(narrow, held)):
        codes[k, :len(mine)] = sorted(mine)
        bins[:, j] = np.asarray(sorted(mine) + [nbin])[
            rng.integers(0, len(mine) + 1, n)]
    gh = np.stack([rng.standard_normal((trees, n)),
                   rng.random((trees, n))], axis=1).astype(np.float32)
    node = rng.integers(-1, nslots, (trees, n)).astype(np.int32)
    fpad = histogram.staged_features(f, nbin)
    bins_t = jnp.zeros((fpad, n), jnp.int32).at[:f].set(bins.T)
    return bins, bins_t, gh, node, (hk.PackPlan(narrow, width), codes)


@pytest.mark.parametrize("held,wide,n,trees,nslots,dtype,totals", [
    # a column of one code, of two, of w (4), codes that are not 0..w-1
    ([[255]], 2, 2500, 7, 1, "bfloat16", False),
    ([[0, 255], [7]], 2, 2500, 7, 1, "bfloat16", False),
    ([[0, 85, 170, 255], [0, 200, 255], [3]], 2, 2500, 7, 1, "bfloat16",
     False),
    # 44 columns of at most 4 codes after 10 wide ones: 176 rows
    ([[0, 255]] * 20 + [[0, 9, 255]] * 14 + [[0, 31, 200, 255]] * 10, 10,
     1100, 7, 1, "bfloat16", False),
    # ragged n, 256 lanes, totals
    ([[0, 200, 255], [0, 255]], 3, 4097, 7, 16, "bfloat16", True),
    # one tree, three; float32 operands
    ([[0, 200, 255], [1, 2]], 2, 2100, 1, 16, "bfloat16", False),
    ([[0, 200, 255], [1, 2]], 2, 2100, 3, 4, "float32", True),
    ([[0, 200, 255], [1, 2]], 2, 700, 7, 16, "float32", False),
    # segments of 8 and 16 rows (a feature a tile, two tiles), of 1 and 2
    ([list(range(0, 256, 37)), [5, 6, 7, 8, 9]], 1, 2500, 7, 1, "bfloat16",
     False),
    ([list(range(0, 256, 17)), [0, 255]], 1, 2500, 7, 2, "bfloat16", False),
    ([[255], [0], [17]], 0, 2500, 7, 1, "bfloat16", False),
    ([[0, 255]] * 5, 1, 2500, 1, 8, "bfloat16", True),
    # more rows than one product of the segment takes: 40 x 8 = 320
    ([list(range(8))] * 40, 1, 600, 7, 1, "bfloat16", False),
], ids=["one-code", "two-codes", "w-codes-not-0-to-w", "covtype-44-of-54",
        "ragged-256-lanes-totals", "one-tree", "three-trees-f32",
        "seven-trees-256-lanes-f32", "segments-of-8", "segments-of-16",
        "segments-of-1-no-wide", "segments-of-2", "two-products"])
def test_packed_level_equals_the_unpacked_one_bit_for_bit(
        monkeypatch, held, wide, n, trees, nslots, dtype, totals):
    """The lane-wide call with a pack plan in interpret mode against the
    same call without one, bit for bit (a bin's sum is the same products
    added in the same order along the block), and against numpy in
    float64: absent entries, rows at node -1, codes no row holds."""
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    nbin = 256
    bins, bins_t, gh, node, pack = _packed_case(held, wide, n, trees, nslots)
    f = bins.shape[1]
    monkeypatch.setattr(hk, "_LANE_CROSSING", 2)
    assert histogram.level_calls(nslots, f, nbin, True, trees) == (1, 1)
    assert histogram.level_packs(nslots, f, nbin, True, trees) == 1

    def level(**kw):
        return np.asarray(histogram.level_hist(
            bins_t, jnp.asarray(gh), jnp.asarray(node), nslots, f, nbin,
            use_pallas=True, compute_dtype=dtype, totals=totals, **kw))

    got, plain = level(pack=pack), level()
    assert got.shape == (trees * nslots, f + totals, nbin, 2)
    np.testing.assert_array_equal(got, plain)
    want = _float64_level(bins, gh, node, nslots, nbin, dtype)
    mass = _float64_level(bins, np.abs(gh), node, nslots, nbin, dtype).sum(
        axis=(1, 2), keepdims=True)
    assert (np.abs(got[:, :f] - want) <= 1e-5 * mass).all()
    # a narrow feature's mass sits on its codes alone
    for k, j in enumerate(pack[0].narrow):
        off = np.setdiff1d(np.arange(nbin), held[k])
        assert not got[:, j, off].any() and got[:, j, held[k]].any()


def test_a_chunked_lane_wide_call_leaves_the_plan(monkeypatch):
    """A wide shard's call (features chunk by chunk on a second grid
    axis) builds every feature's own product: the plan is left, the
    level reads the same, and nothing counts as packed."""
    import jax
    import jax.numpy as jnp

    from rabit_tpu.ops import histogram_kernel as hk

    held = [[0, 200, 255]] * 6
    bins, bins_t, gh, node, pack = _packed_case(held, 13, 900, 1, 8)
    f = bins.shape[1]
    monkeypatch.setattr(hk, "lane_chunk", lambda nbin, f, lanes: 8)
    jax.clear_caches()

    def level(b, w, nd, **kw):
        return histogram.level_hist(b, w, nd, 8, f, 256, use_pallas=True,
                                    **kw)

    args = (bins_t, jnp.asarray(gh), jnp.asarray(node))
    assert not hk.lane_packs(256, f, 128)
    assert histogram.level_packs(8, f, 256, True, 1) == 0
    assert str(jax.make_jaxpr(level)(*args)) == str(jax.make_jaxpr(
        lambda *a: level(*a, pack=pack))(*args))
    np.testing.assert_array_equal(np.asarray(level(*args)),
                                  np.asarray(level(*args, pack=pack)))
    jax.clear_caches()


def _cuts_of(kind, nbin=256, seed=7):
    """Cuts of a few columns of one kind, as ``quantile_cuts`` makes
    them from a sample."""
    rng = np.random.default_rng(seed)
    m = 5000
    if kind == "continuous":
        sample = rng.standard_normal((m, 3))
    elif kind == "indicator":
        sample = np.stack([rng.random(m) < p for p in
                           (1e-4, 0.003, 0.02, 0.5, 0.97, 1.0)], axis=1)
    elif kind == "levels":
        sample = np.stack([rng.integers(0, k, m) * 0.25 for k in
                           (3, 5, 9, 17, 33)], axis=1)
    elif kind == "all-equal":
        sample = np.full((m, 2), 3.5)
    elif kind == "all-nan-column":
        sample = np.stack([np.full(m, np.nan), rng.integers(0, 2, m)], axis=1)
    else:                                       # duplicates: a heavy atom
        sample = np.where(rng.random((m, 3)) < 0.7, 0.0,
                          rng.standard_normal((m, 3)))
    return histogram.quantile_cuts(sample.astype(np.float32), nbin)


@pytest.mark.parametrize("kind", ["continuous", "indicator", "levels",
                                  "all-equal", "all-nan-column",
                                  "duplicates"])
def test_reachable_codes_are_the_codes_apply_cuts_gives_around_every_cut(
        kind):
    """``feature_codes``: the codes values around every cut take under
    ``apply_cuts`` and under the device's binning (the cut itself, a
    value just under and just over it (no subnormal: the device flushes
    those to zero), the midpoints, both infinities), no more and no
    fewer; a NaN takes the absent code, which is in no list."""
    from rabit_tpu.ops import histogram_kernel as hk

    nbin = 256
    cuts = _cuts_of(kind)
    held = hk.feature_codes(cuts)
    around = []
    for row in cuts:
        mid = (row[1:] + row[:-1]) / 2
        step = np.maximum(np.abs(row) * 1e-6, 1e-30)
        around.append(np.concatenate([
            row, row - step, row + step, mid,
            [-np.inf, np.inf, 0.0, 1.0, np.nan]]).astype(np.float32))
    values = np.stack(around, axis=1)                       # (draws, f)
    bins = histogram.apply_cuts(values, cuts)
    staged, _ = histogram.stage_bins(values, cuts, nbin)
    np.testing.assert_array_equal(np.asarray(staged)[:len(cuts)].T, bins)
    for j, mine in enumerate(held):
        got = np.unique(bins[:, j])
        assert got[-1] == nbin                  # the NaN's code
        np.testing.assert_array_equal(got[:-1], mine)
        assert len(mine) <= len(np.unique(cuts[j])) + 1
    plan = hk.pack_plan(cuts)
    assert hk._NARROW_CODES <= nbin // 2
    narrow = [j for j, c in enumerate(held) if len(c) <= hk._NARROW_CODES]
    if kind in ("continuous", "duplicates"):
        # an atom of 70% leaves 77 cuts of its 255 distinct: not narrow
        assert plan is None and not narrow
    else:
        assert plan[0].narrow == tuple(narrow)
        assert plan[0].width == hk._next_pow2(
            max(len(held[j]) for j in narrow))
        assert plan[1].shape == (len(narrow), plan[0].width)
        for k, j in enumerate(narrow):
            assert plan[1][k].tolist() == held[j].tolist() + [hk._NO_CODE] * (
                plan[0].width - len(held[j]))
    if kind == "indicator":
        # set in under 1/256 of the rows: every cut 0; in all: every cut 1
        assert [c.tolist() for c in held][::5] == [[0, 255], [0, 255]]
        assert max(len(c) for c in held) <= 4


def test_the_plan_is_shapes_and_its_codes_an_operand():
    """Two jobs whose cuts differ only in where the narrow features'
    codes lie share a plan, so a compiled program; the codes differ."""
    from rabit_tpu.ops import histogram_kernel as hk

    a, b = _cuts_of("indicator", seed=1), _cuts_of("indicator", seed=2)
    (plan_a, codes_a), (plan_b, codes_b) = hk.pack_plan(a), hk.pack_plan(b)
    assert plan_a == plan_b and hash(plan_a) == hash(plan_b)
    assert codes_a.dtype == np.int32 and not np.array_equal(codes_a, codes_b)


# ----------------------------------------------------------------------
# absent entries take no histogram slot: XGBoost's layout
# ----------------------------------------------------------------------
@pytest.mark.parametrize("f", [28, 968], ids=["higgs", "bosch"])
def test_plan_for_256_bins_is_the_same_with_and_without_missing_values(f):
    """A 257th slot pads to 512 (32 x 16) and doubles every product of
    the kernel; the missing code 256 needs none under the 16 x 16 plan,
    whose ``hi`` classes end at 15."""
    from rabit_tpu.ops import histogram_kernel as hk

    hi, lo, fpg, ngroups = hk.plan(256, f)
    assert (hi, lo, fpg, ngroups) == (16, 16, 8, -(-f // 8))
    assert hk.plan(257, f)[:2] == (32, 16)            # what it would cost
    assert 256 >> (lo.bit_length() - 1) >= hi         # matches no class
    assert histogram.staged_features(f, 256) == 8 * ngroups


@pytest.mark.parametrize("kw,nbin", [({"use_pallas": False}, 16),
                                     ({"use_pallas": True}, 16),
                                     ({"use_pallas": True}, 7),
                                     ({"use_pallas": True}, 256)],
                         ids=["xla", "kernel", "kernel-7-bins", "kernel-256"])
def test_an_absent_entry_adds_to_no_bin(kw, nbin):
    """Rows whose code is ``nbin`` (absent) are in no bin of that
    feature, in every builder, and in every bin they have elsewhere."""
    import jax.numpy as jnp

    rng = np.random.default_rng(41)
    n, f, nslots = 700, 3, 2
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    bins[rng.random((n, f)) < 0.3] = nbin
    bins[:, 2] = rng.integers(0, nbin, n)             # nobody misses it
    gh = np.stack([rng.standard_normal(n), rng.random(n)]).astype(np.float32)
    node = rng.integers(-1, nslots, n).astype(np.int32)
    got = np.asarray(histogram.level_hist(
        jnp.asarray(bins.T), jnp.asarray(gh), jnp.asarray(node), nslots, f,
        nbin, compute_dtype="float32", **kw))
    assert got.shape == (nslots, f, nbin, 2)
    for s in range(nslots):
        at = node == s
        for j in range(f):
            have = at & (bins[:, j] < nbin)
            want = _np_hist(bins[have][:, j:j + 1], gh[0, have], gh[1, have],
                            nbin)[0]
            np.testing.assert_allclose(got[s, j], want, rtol=0, atol=2e-4)
    built = np.asarray(histogram.build_level_local(
        bins, gh[0], gh[1], np.zeros(n, np.int32), [0], nbin, **kw, **(
            {"compute_dtype": "float32"} if kw["use_pallas"] else {})))[0]
    have = bins[:, 0] < nbin
    np.testing.assert_allclose(
        built[0], _np_hist(bins[have][:, :1], gh[0, have], gh[1, have],
                           nbin)[0], rtol=0, atol=2e-4)


def _tally_with_a_257th_slot(bins, grad, hess, rows, nbin):
    """The parent's layout: (f, nbin + 1, 2) float64, the last slot the
    (grad, hess) sums of the rows absent from the feature."""
    out = np.zeros((bins.shape[1], nbin + 1, 2))
    for j in range(bins.shape[1]):
        for c, w in enumerate((grad, hess)):
            out[j, :, c] = np.bincount(bins[rows, j], w[rows].astype(
                np.float64), nbin + 1)
    return out


@pytest.mark.parametrize("which", ["node", "sibling-by-subtraction"])
def test_missing_mass_by_subtraction_equals_the_tally_of_a_257th_slot(which):
    """Total less bins against a slot that tallies the absent rows, to
    float64 rounding: on a node, on a sibling had as parent minus built,
    and on a feature nobody misses (whose mass reads 0)."""
    rng = np.random.default_rng(43)
    n, f, nbin = 5000, 4, 16
    bins = rng.integers(0, nbin, (n, f)).astype(np.int32)
    bins[rng.random((n, f)) < 0.6] = nbin
    bins[:, 3] = rng.integers(0, nbin, n)             # nobody misses it
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32)
    parent = rng.random(n) < 0.7
    built = parent & (rng.random(n) < 0.3)
    tally = _tally_with_a_257th_slot(bins, grad, hess, parent, nbin)
    rows = parent
    if which == "sibling-by-subtraction":
        tally = tally - _tally_with_a_257th_slot(bins, grad, hess, built,
                                                 nbin)
        rows = parent & ~built
    # the new layout: the bins alone, and the node's totals
    hist, total = tally[:, :nbin], tally[0].sum(axis=0)
    mass = histogram.missing_mass(hist, total)
    scale = np.abs(grad[rows]).sum() + hess[rows].sum()
    np.testing.assert_allclose(mass, tally[:, nbin], rtol=0,
                               atol=1e-13 * scale)
    assert (mass[3] == 0).all() and not tally[3, nbin].any()
    assert np.abs(mass[:3, 1]).min() > 0.1 * hess[rows].sum()
    # and the candidates scored on either layout are the same
    gain, left = histogram.split_candidates(hist, 1.0, 1.0, total)
    gm, hm = tally[:, nbin:, 0], tally[:, nbin:, 1]
    gc, hc = np.cumsum(hist[:, :, 0], 1), np.cumsum(hist[:, :, 1], 1)
    gt, ht = gc[:, -1:] + gm, hc[:, -1:] + hm

    def score(gl, hl):
        ok = (hl >= 1.0) & (ht - hl >= 1.0)
        return np.where(ok, gl * gl / (hl + 1.0) + (gt - gl) ** 2 / (
            ht - hl + 1.0) - gt * gt / (ht + 1.0), -np.inf)

    want_left = score(gc[:, :-1] + gm, hc[:, :-1] + hm)
    want_right = score(gc[:, :-1], hc[:, :-1])
    np.testing.assert_allclose(gain, np.maximum(want_left, want_right),
                               rtol=1e-9, atol=1e-9)
    decided = np.abs(want_left - want_right) > 1e-9
    assert decided.any() and (left == (want_left >= want_right))[decided].all()
    assert {True, False} == set(left[decided].tolist())


def test_a_residue_under_the_floor_is_no_missing_mass():
    """Where nobody is absent, total less bins is the accumulation's
    residue, of either sign: it is read as 0 so that both arms (and
    every rank) take the same default direction, left."""
    hist = np.zeros((2, 4, 2))
    hist[0] = [(-3.0, 10.0), (1.0, 10.0), (2.0, 11.0), (0.5, 9.0)]
    hist[1] = [(-6.0, 12.0), (-5.0, 8.0), (6.0, 12.0), (5.5, 8.0)]
    total = hist[0].sum(axis=0)
    for residue in (3e-6, -3e-6):
        off = total + residue * total[1]
        assert not histogram.missing_mass(hist, off).any()
        gain, left = histogram.split_candidates(hist, 1.0, 1e-3, off)
        assert left.all() and np.isfinite(gain).all()
    real = total + np.array([0.4, 1.0])               # a few rows' worth
    np.testing.assert_allclose(histogram.missing_mass(hist, real),
                               [[0.4, 1.0], [0.4, 1.0]], atol=1e-12)


@pytest.mark.parametrize("case", ["dense", "holes", "all-absent-column",
                                  "one-present", "few-levels"])
def test_cuts_equal_nanquantile_bit_for_bit(case):
    import warnings

    rng = np.random.default_rng(47)
    m, f, nbin = 20000, 37, 256
    vals = rng.standard_normal((m, f)).astype(np.float32)
    if case != "dense":
        vals[rng.random((m, f)) < 0.8] = np.nan
    if case == "all-absent-column":
        vals[:, 5] = np.nan
    if case == "one-present":
        vals[:, 6] = np.nan
        vals[123, 6] = 2.5
    if case == "few-levels":
        vals = np.round(vals * 2) / 2
    qs = np.linspace(0, 1, nbin + 1)[1:-1]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        want = np.nan_to_num(np.nanquantile(vals, qs, axis=0).T,
                             nan=0.0).astype(np.float32)
    got = histogram.quantile_cuts(vals, nbin)
    assert got.dtype == np.float32 and got.shape == (f, nbin - 1)
    np.testing.assert_array_equal(got, want)
    if case == "all-absent-column":
        assert not got[5].any()
    if case == "few-levels":
        assert (np.diff(got, axis=1) == 0).any()      # duplicate cuts
    # a batch boundary and a width that is no multiple of the batch
    np.testing.assert_array_equal(
        histogram.quantile_cuts(vals[:, :histogram.CUT_BATCH_COLS + 1], 7),
        np.nan_to_num(np.nanquantile(
            vals[:, :histogram.CUT_BATCH_COLS + 1],
            np.linspace(0, 1, 8)[1:-1], axis=0).T, nan=0.0).astype(
                np.float32) if case != "all-absent-column" else
        histogram.quantile_cuts(vals[:, :histogram.CUT_BATCH_COLS + 1], 7))


def test_stage_bins_at_968_columns_holds_no_chunk_over_its_byte_budget(
        monkeypatch):
    """A chunk of float rows is bounded in bytes whatever the width (at
    2^20 rows a 968-column chunk is 4 GB, its transpose another), a
    narrow shard keeps its 2^20 rows, and the result is apply_cuts'."""
    import jax

    from rabit_tpu.obs import program

    rng = np.random.default_rng(53)
    n, f, nbin = 700, 968, 256
    vals = rng.standard_normal((n, f)).astype(np.float32)
    station = np.repeat(np.arange(121), 8)
    vals[(rng.random((n, 121)) < 0.8)[:, station]] = np.nan
    cuts = histogram.quantile_cuts(vals, nbin)
    budget = 150 * f * 4                              # 150 rows a chunk
    monkeypatch.setattr(histogram, "STAGE_CHUNK_BYTES", budget)
    put, chunks = jax.device_put, []

    def seen_put(x, *a, **kw):
        chunks.append(np.asarray(x).nbytes)
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", seen_put)
    before = program.stats()
    bins_t, seen = histogram.stage_bins(vals, cuts, nbin)
    after = program.stats()
    assert len(chunks) == 5 and max(chunks) <= budget
    want = histogram.apply_cuts(vals, cuts)
    assert bins_t.shape == (f, n)                     # 121 groups of 8
    np.testing.assert_array_equal(np.asarray(bins_t).T, want)
    assert list(np.asarray(seen)) == [1, nbin]
    assert after["gbdt.entries"] - before.get("gbdt.entries", 0) == n * f
    assert (after["gbdt.entries_missing"]
            - before.get("gbdt.entries_missing", 0)
            == np.count_nonzero(np.isnan(vals)))
    # the budgets as shipped: HIGGS keeps 2^20 rows, Bosch gets 69k
    monkeypatch.undo()
    for width, rows in ((28, 1 << 20), (968, (1 << 28) // (4 * 968))):
        assert min(histogram.STAGE_CHUNK_ROWS,
                   histogram.STAGE_CHUNK_BYTES // (4 * width)) == rows
        assert rows * width * 4 <= histogram.STAGE_CHUNK_BYTES


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_wide_shards_looped_kernel_body_equals_the_unrolled_one(
        monkeypatch, dtype):
    """Past ``_UNROLL_GROUPS`` feature groups the kernel's body is one
    group inside a loop (968 features are 121 groups: unrolled, a
    boosting job's kernels took the chip's compiler minutes); the sums
    are those of the unrolled body bit for bit, absent codes and a
    ragged last row block included."""
    import jax

    from rabit_tpu.ops import histogram_kernel as hk

    rng = np.random.default_rng(59)
    n, f, nbin = 2300, 80, 256                         # 10 groups of 8
    assert hk.plan(nbin, f)[3] > hk._UNROLL_GROUPS
    bins = rng.integers(0, nbin + 1, (f, n)).astype(np.int32)
    gh = rng.standard_normal((2, n)).astype(np.float32)
    node = rng.integers(-1, 3, n).astype(np.int32)
    kw = dict(node_of_row=node, nslots=3, compute_dtype=dtype)
    looped = np.asarray(hk.hist_fused_multi(bins, gh, nbin, **kw))
    monkeypatch.setattr(hk, "_UNROLL_GROUPS", 1 << 20)
    jax.clear_caches()
    unrolled = np.asarray(hk.hist_fused_multi(bins, gh, nbin, **kw))
    jax.clear_caches()
    np.testing.assert_array_equal(looped, unrolled)
    if dtype == "float32":
        for s in range(3):
            for j in (0, 41, 79):
                have = (node == s) & (bins[j] < nbin)
                want = _np_hist(bins[j][have][:, None], gh[0, have],
                                gh[1, have], nbin)[0]
                np.testing.assert_allclose(
                    looped[2 * s:2 * s + 2, j].T, want, rtol=0, atol=2e-4)


def test_slot_totals_are_sums_of_the_weights_as_the_kernel_rounds_them():
    """The totals ride with bins that hold sums of bf16-rounded weights:
    they must be sums of the same numbers, or every feature reads a
    missing mass of 2^-9 of the node (found on the chip, where a cast
    there and back is dropped as excess precision)."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    rng = np.random.default_rng(67)
    n, nslots = 5000, 3
    gh = rng.standard_normal((2, n)).astype(np.float32)
    slot = rng.integers(-1, nslots, n).astype(np.int32)
    fn = jax.jit(lambda g, s: histogram.slot_totals(g, s, nslots,
                                                    "bfloat16"))
    got = np.asarray(fn(jnp.asarray(gh), jnp.asarray(slot)))
    rounded = gh.astype(ml_dtypes.bfloat16).astype(np.float64)
    for s in range(nslots):
        np.testing.assert_allclose(got[s], rounded[:, slot == s].sum(axis=1),
                                   rtol=0, atol=1e-3)
    assert "reduce_precision" in str(jax.make_jaxpr(
        lambda g, s: histogram.slot_totals(g, s, nslots, "bfloat16"))(
            gh, slot))
    exact = np.asarray(histogram.slot_totals(
        jnp.asarray(gh), jnp.asarray(slot), nslots, jnp.float32))
    np.testing.assert_allclose(
        exact[1], gh[:, slot == 1].astype(np.float64).sum(axis=1), atol=1e-3)
    # a kernel level's totals and bins agree to accumulation, not to 2^-9
    bins = rng.integers(0, 16, (8, n)).astype(np.int32)
    out = np.asarray(histogram.level_hist(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(slot), nslots, 8, 16,
        use_pallas=True, totals=True), np.float64)
    np.testing.assert_allclose(out[:, :8].sum(axis=2), np.repeat(
        out[:, 8:, 0], 8, axis=1), rtol=0, atol=2e-4)


# ----------------------------------------------------------------------
# the device's float32 ranking of a slot's features (PR 34): it chooses
# which histogram rows the host looks at and decides nothing
# ----------------------------------------------------------------------
def _scored_case(kind, absent, f=6, nbin=16, seed=31):
    """A float32 ``(f, nbin, 2)`` histogram and, with ``absent``, the
    node's totals: a full node, a slot that holds no row, or a sibling
    derived as parent minus built, whose cells read an ulp of either
    sign where no row fell (feature 0's last bins, feature 1's first)."""
    rng = np.random.default_rng(seed)
    node = rng.random((f, nbin, 2))
    node[..., 0] -= 0.5
    node[0, nbin - 3:] = 0
    node[1, :2] = 0
    # every feature's bins add up to the node's totals (0.8, 9)
    node[..., 1] *= 9.0 / node[..., 1].sum(axis=1, keepdims=True)
    node[:, 5, 0] += 0.8 - node[..., 0].sum(axis=1)
    if kind == "empty":
        node[:] = 0
    hist = node.astype(np.float32)
    if kind == "sibling":
        built = (37 * rng.random((f, nbin, 2))).astype(np.float32)
        parent = (built.astype(np.float64) + node).astype(np.float32)
        # the parent's cells were added up in another order
        parent = np.nextafter(parent, np.where(
            rng.random(parent.shape) < 0.5, -np.inf, np.inf).astype(
                np.float32))
        hist = parent - built
        assert (hist[0, nbin - 3:] != 0).any() and hist[..., 1].min() < 0
    if not absent:
        return hist, None
    # features 2.. are absent from a share of the rows; nobody is absent
    # from features 0 and 1 (a residue under the floor reads as none)
    total = hist[0].sum(axis=0, dtype=np.float64)
    hist[2:] *= rng.uniform(0.3, 0.9, (f - 2, 1, 1)).astype(np.float32)
    return hist, total.astype(np.float32)


def _both_ways(hist, total, lam):
    """Float64 gains with the absent rows sent left and sent right."""
    sums = np.cumsum(hist.astype(np.float64), axis=1)
    mass = histogram.missing_mass(hist, total)
    tot = (sums[:, -1] + mass)[:, None]
    out = []
    for add in (mass[:, None], 0.0):
        left = sums[:, :-1] + add
        right = tot - left
        out.append(left[..., 0] ** 2 / (left[..., 1] + lam)
                   + right[..., 0] ** 2 / (right[..., 1] + lam)
                   - tot[..., 0] ** 2 / (tot[..., 1] + lam))
    return out


@pytest.mark.parametrize("kind", ["node", "empty", "sibling"])
@pytest.mark.parametrize("min_child_weight", [None, 1.0, 1e9],
                         ids=["any-child", "mcw-1", "bars-every-cut"])
@pytest.mark.parametrize("absent", [False, True], ids=["dense", "nan"])
def test_device_scoring_equals_split_candidates(kind, min_child_weight,
                                                absent):
    """``split_candidates_device`` (float32, traceable) against the
    host's float64: the gain to 1e-5 of the node's scale, the same
    candidates barred, the same default direction wherever the two
    directions differ by more than that."""
    import jax

    lam = 1.0
    hist, total = _scored_case(kind, absent)
    want, want_left = histogram.split_candidates(hist, lam, min_child_weight,
                                                 total)
    got, got_left = jax.jit(
        lambda h, t: histogram.split_candidates_device(
            h[..., 0], h[..., 1], lam, min_child_weight,
            None if t is None else (t[0], t[1])))(hist, total)
    # the last bin rides along, is no cut and reads -inf
    assert (np.asarray(got)[:, -1] == -np.inf).all()
    got = np.asarray(got)[:, :-1]
    got_left = None if got_left is None else np.asarray(got_left)[:, :-1]
    assert got.dtype == np.float32 and got.shape == want.shape == (6, 15)
    barred = want == -np.inf
    np.testing.assert_array_equal(got == -np.inf, barred)
    assert barred.all() == (min_child_weight == 1e9) or kind == "empty"
    if min_child_weight == 1.0 and kind != "empty":
        assert barred.any() and not barred.all()
    g_abs = np.abs(hist[..., 0].astype(np.float64)).sum(axis=1).max()
    scale = g_abs ** 2 / (hist[0, :, 1].sum(dtype=np.float64) + lam)
    np.testing.assert_allclose(got[~barred], want[~barred], rtol=0,
                               atol=1e-5 * scale)
    if not absent:
        assert got_left is None and want_left is None
        return
    left, right = _both_ways(hist, total, lam)
    clear = ~barred & (np.abs(left - right) > 1e-5 * scale)
    np.testing.assert_array_equal(np.asarray(got_left)[clear],
                                  want_left[clear])
    # nobody is absent from features 0 and 1: a tie, which goes left
    assert np.asarray(got_left)[:2].all() and want_left[:2].all()
    if kind == "node" and not barred.all():     # both ways are taken
        assert clear[2:].any() and not want_left[clear].all()


def _tied_level(absent, slots=3, f=12, nbin=16, seed=37):
    """An assembled level ``(2, slots, f [+ 1], nbin)`` whose features
    come on few levels (empty bins: consecutive cuts tie exactly) and
    in copies (features 4 and 9 repeat feature 2: whole features tie);
    slot 1 holds no row."""
    rng = np.random.default_rng(seed)
    hist = np.zeros((slots, f + absent, nbin, 2), np.float32)
    for s in (0, 2):
        for j in range(f):
            at = rng.choice(nbin, size=5, replace=False)
            g, h = rng.standard_normal(5) * 3, rng.uniform(1.5, 4, 5)
            # every feature's bins add up to the node's totals (0, 12)
            hist[s, j, at, 0] = g - g.mean()
            hist[s, j, at, 1] = h * 12 / h.sum()
        hist[s, 4] = hist[s, 9] = hist[s, 2]
        # the best gain by far, twice: features 2's copies aside, 6 == 7
        hist[s, 6] = 0
        hist[s, 6, [1, 2, 11, 12]] = [(-9, 3), (-9, 3), (9, 3), (9, 3)]
        hist[s, 7] = hist[s, 6]
        if absent:          # half of the rows skip features 6 and 7
            hist[s, f, 0] = hist[s, 6].sum(axis=0)
            hist[s, 6:8] *= np.float32(0.5)
    return np.moveaxis(hist, -1, 0).copy(), hist


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("absent", [False, True], ids=["dense", "nan"])
def test_shortlist_holds_the_float64_winner(k, absent):
    """The host's ``best_split`` on a slot's shortlist rows is its
    ``best_split`` on the whole histogram (of equals the first feature,
    the first cut, left), for a shortlist of one and of eight, on
    histograms with duplicate cuts, empty bins and repeated features;
    the rows are the level's own, in feature order, the totals row
    last."""
    import jax

    lam, mcw, f = 1.0, 1.0, 12
    level, hist = _tied_level(absent)
    feats, rows = jax.jit(lambda x: histogram.level_shortlist(
        x, f, lam, mcw, absent, k))(level)
    feats, rows = np.asarray(feats), np.moveaxis(np.asarray(rows), 0, -1)
    assert feats.shape == (3, k) and rows.shape == (3, k + absent, 16, 2)
    assert (np.diff(feats, axis=1) > 0).all()
    for s in range(3):
        np.testing.assert_array_equal(rows[s, :k], hist[s, feats[s]])
        total = hist[s, f, 0] if absent else None
        if absent:
            np.testing.assert_array_equal(rows[s, k], hist[s, f])
        gain, j, t, left = histogram.best_split(hist[s, :f], lam, mcw, total)
        got = histogram.best_split(rows[s, :k], lam, mcw, total)
        assert (got[0], int(feats[s, got[1]]), got[2], got[3]) == (
            gain, j, t, left)
        if s == 1:          # no row: every cut barred, the first of all
            assert (gain, j, t) == (-np.inf, 0, 0)
            assert feats[s].tolist() == list(range(k))
        else:               # 6 and 7 tie, cuts 2..10 tie: the first
            assert (j, t) == (6, 2) and gain > 0 and feats[s, 0] <= 6


@pytest.mark.parametrize("absent", [False, True], ids=["dense", "nan"])
@pytest.mark.parametrize("depth", [0, 3])
def test_scan_of_a_level_returns_every_slot_and_the_hosts_level(depth,
                                                                absent):
    """``assemble_level`` + ``level_shortlist`` as the scan program runs
    them: the level equals the host's (built slots as they are, siblings
    as parent minus built, in float64) to a float32 rounding of the
    parent's cell, zeros where the slot above was not split; and every
    slot has its shortlist, node or not."""
    import jax

    from rabit_tpu.learn import boosting

    f, nbin, p = 5, 8, 1 << max(depth - 1, 0)
    rng = np.random.default_rng(41 + depth)
    built = rng.random((p, f + absent, nbin, 2)).astype(np.float32)

    @jax.jit
    def scan(built, above=None, build=None):
        level = histogram.assemble_level(built, above, build)
        return (level,) + histogram.level_shortlist(level, f, 1.0, 1.0,
                                                    absent)

    if not depth:
        level, feats, rows = scan(built)
        np.testing.assert_array_equal(np.moveaxis(np.asarray(level), 0, -1),
                                      built)
        assert feats.shape == (1, f) and rows.shape == (2, 1, f + absent,
                                                        nbin)
        return
    above = (built + 3 * rng.random(built.shape)).astype(np.float32)
    build = np.array([1, 2, -1, 7], np.int32)
    level, feats, rows = scan(built, np.moveaxis(above, -1, 0), build)
    level = np.moveaxis(np.asarray(level), 0, -1)
    host = boosting._assemble({depth - 1: above.astype(np.float64)}, depth,
                              built.astype(np.float64), build, 2 * p)
    assert level.shape == host.shape == (2 * p, f + absent, nbin, 2)
    assert level.dtype == np.float32
    np.testing.assert_allclose(level, host, rtol=0,
                               atol=np.finfo(np.float32).eps * above.max())
    for s in (1, 2, 7):
        np.testing.assert_array_equal(level[s], built[s >> 1])
    assert not level[4:6].any() and level[[0, 3, 6]].all()
    assert feats.shape == (2 * p, f) and rows.shape[1:3] == (2 * p,
                                                            f + absent)
    np.testing.assert_array_equal(
        np.asarray(feats), np.tile(np.arange(f), (2 * p, 1)))
    np.testing.assert_array_equal(np.moveaxis(np.asarray(rows), 0, -1), level)


# ----------------------------------------------------------------------
# the weighted quantile sketch (tree_method="approx")
# ----------------------------------------------------------------------
def _rank_err(values, weights, cuts, nbin):
    """The largest distance from (i + 1) / nbin to cut i's exact
    weighted rank interval, float64, over features and cuts; a tie
    spans an interval; a feature without weight has no rank."""
    worst = 0.0
    w = weights.astype(np.float64)
    for j in range(values.shape[1]):
        v = values[:, j].astype(np.float64)
        present = ~np.isnan(v)
        total = w[present].sum()
        if total == 0:
            continue
        for i, cut in enumerate(cuts[j].astype(np.float64)):
            below = w[present & (v < cut)].sum() / total
            upto = w[present & (v <= cut)].sum() / total
            want = (i + 1) / nbin
            worst = max(worst, below - want, want - upto)
    return worst


def _sketch_rows(case: str, n: int = 6000, seed: int = 5):
    """Seeded rows of six columns (continuous, ties on a grid of
    halves, constant, a third absent, all absent, two values) and the
    case's weights."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 6)).astype(np.float32)
    x[:, 1] = np.round(x[:, 1] * 2) / 2
    x[:, 2] = 3.0
    x[rng.random(n) < 0.33, 3] = np.nan
    x[:, 4] = np.nan
    x[:, 5] = np.where(x[:, 5] > 1.0, 1.0, -0.0)
    w = {"uniform": np.full(n, 0.25),
         "hessians": rng.random(n) * 0.25,
         "zero-weights": np.where(rng.random(n) < 0.6, 0.0, rng.random(n)),
         "heavy-tailed": rng.pareto(1.05, n),
         "one-row-holds-half": np.where(np.arange(n) == 17, float(n), 1.0),
         }[case].astype(np.float32)
    return x, w


def _summary(x, w, nbin):
    import jax.numpy as jnp

    return histogram.sketch_summary(
        jnp.asarray(np.ascontiguousarray(x.T)), jnp.asarray(w),
        histogram.summary_entries(nbin))


@pytest.mark.parametrize("nbin", [16, 256])
@pytest.mark.parametrize("case", ["uniform", "hessians", "zero-weights",
                                  "heavy-tailed", "one-row-holds-half"])
def test_sketch_cuts_are_within_eps_of_the_exact_weighted_quantiles(case,
                                                                    nbin):
    x, w = _sketch_rows(case)
    cuts = np.asarray(histogram.sketch_cuts(_summary(x, w, nbin)[None], nbin))
    assert cuts.shape == (6, nbin - 1) and cuts.dtype == np.float32
    assert _rank_err(x, w, cuts, nbin) <= 1e-5 < histogram.sketch_eps(nbin)
    assert (np.diff(cuts, axis=1) >= 0).all()           # may repeat
    np.testing.assert_array_equal(cuts[2], 3.0)         # constant
    np.testing.assert_array_equal(cuts[4], 0.0)         # all absent
    assert set(np.unique(cuts[5])) <= {0.0, 1.0}
    # every cut is a value of the data, of a row that has weight
    for j in (0, 1, 3):
        assert np.isin(cuts[j], x[w > 0, j]).all()


def test_a_summary_holds_exact_ranks_and_its_total():
    x, w = _sketch_rows("hessians")
    s = np.asarray(_summary(x, w, 16)).astype(np.float64)
    assert s.shape == (6, histogram.summary_entries(16), 3)
    for j in (0, 1, 3, 5):
        v = x[:, j].astype(np.float64)
        present = ~np.isnan(v)
        total = w[present].sum(dtype=np.float64)
        np.testing.assert_allclose(s[j, -1, 2], total, rtol=1e-6)
        for value, rmin, rmax in s[j, ::37]:
            np.testing.assert_allclose(
                rmin, w[present & (v < value)].sum(dtype=np.float64),
                rtol=1e-5, atol=1e-6 * total)
            np.testing.assert_allclose(
                rmax, w[present & (v <= value)].sum(dtype=np.float64),
                rtol=1e-5, atol=1e-6 * total)
    assert not s[4].any()                               # no weight


@pytest.mark.parametrize("case", ["hessians", "heavy-tailed"])
@pytest.mark.parametrize("shards", [2, 3, 8])
def test_merged_summaries_are_within_eps_on_every_shard_order(shards, case):
    import jax.numpy as jnp

    nbin = 16
    x, w = _sketch_rows(case, n=6000)
    # unequal shards: the first takes half of the rows
    bounds = np.concatenate([[0], np.linspace(3000, 6000, shards).astype(int)])
    parts = [_summary(x[a:b], w[a:b], nbin)
             for a, b in zip(bounds, bounds[1:])]
    cuts = np.asarray(histogram.sketch_cuts(jnp.stack(parts), nbin))
    assert _rank_err(x, w, cuts, nbin) <= histogram.sketch_eps(nbin)
    for order in (list(range(shards))[::-1],
                  list(np.random.default_rng(1).permutation(shards))):
        again = np.asarray(histogram.sketch_cuts(
            jnp.stack([parts[k] for k in order]), nbin))
        np.testing.assert_array_equal(again, cuts)
    # one rank's merge is its own sketch
    whole = np.asarray(histogram.sketch_cuts(
        _summary(x, w, nbin)[None], nbin))
    assert _rank_err(x, w, whole, nbin) <= 1e-5


def test_sketch_payload_is_the_ranks_slot_and_zeros_elsewhere():
    x, w = _sketch_rows("hessians", n=512)
    gh = np.stack([np.zeros_like(w), w])
    mine = np.asarray(histogram.sketch_program(512, 6, 16, 3, 1)(
        np.ascontiguousarray(x.T), gh))
    assert mine.shape == (3, 6, histogram.summary_entries(16), 3)
    assert not mine[0].any() and not mine[2].any()
    np.testing.assert_array_equal(mine[1], np.asarray(_summary(x, w, 16)))
    alone = np.asarray(histogram.sketch_program(512, 6, 16)(
        np.ascontiguousarray(x.T), gh))
    np.testing.assert_array_equal(alone[0], mine[1])


@pytest.mark.parametrize("case", ["uniform", "hessians", "zero-weights",
                                  "heavy-tailed", "one-row-holds-half"])
def test_a_summary_does_not_read_the_order_of_the_rows(case):
    """The sketch's sort is not stable, so rows of equal value reach
    the prefix sums in whatever order the sort leaves them: on the
    columns that are mostly ties, the rows shuffled, every entry holds
    the same value, its ranks move by float32 rounding alone, and the
    cuts stay exact."""
    nbin, tied = 16, [1, 2, 3, 5]   # halves, constant, a third absent, two
    x, w = _sketch_rows(case)
    x = x[:, tied]
    s = np.asarray(_summary(x, w, nbin))
    for seed in (0, 1):
        order = np.random.default_rng(seed).permutation(len(x))
        xs, ws = x[order], w[order]
        again = np.asarray(_summary(xs, ws, nbin))
        np.testing.assert_array_equal(again[:, :, 0], s[:, :, 0])
        for mine, other in zip(s, again):
            np.testing.assert_allclose(other[:, 1:], mine[:, 1:], rtol=1e-5,
                                       atol=1e-6 * mine[-1, 2])
        cuts = np.asarray(histogram.sketch_cuts(again[None], nbin))
        assert _rank_err(xs, ws, cuts, nbin) <= 1e-5


def test_the_sketch_sorts_two_operands_and_not_stably():
    """What the per-feature sort carries is the key and the weight: a
    stable sort would carry the row's index as a third operand on the
    chip (and say ``is_stable=true`` on any backend)."""
    import re

    text = histogram.sketch_program(512, 6, 16).as_text()
    sorts = [line for line in text.splitlines()
             if re.search(r"\bsort\(", line)]
    assert len(sorts) == 1, sorts
    operands = re.search(r"\bsort\(([^)]*)\)", sorts[0]).group(1).split(",")
    assert len(operands) == 2, sorts[0]
    assert "is_stable=true" not in sorts[0]


@pytest.mark.parametrize("nan", [False, True], ids=["dense", "nan"])
def test_rebin_equals_apply_cuts_bit_for_bit_and_writes_in_place(nan):
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    n, f, nbin = 3000, 5, 16
    x = rng.standard_normal((n, f)).astype(np.float32)
    if nan:
        x[rng.random((n, f)) < 0.2] = np.nan
    values_t, bins_t, seen = histogram.stage_values(x, nbin)
    np.testing.assert_array_equal(np.asarray(values_t), x.T)
    assert bins_t.shape == (histogram.staged_features(f, nbin), n)
    assert list(np.asarray(seen)) == [int(nan), nbin * int(nan)]
    cuts = histogram.quantile_cuts(x, nbin)
    fn = histogram.rebin_program(n, f, bins_t.shape[0], nbin - 1)
    out = fn(bins_t, values_t, jnp.asarray(cuts))
    assert bins_t.is_deleted()                          # donated
    np.testing.assert_array_equal(np.asarray(out)[:f].T,
                                  histogram.apply_cuts(x, cuts))
    assert not np.asarray(out)[f:].any()
    # again, on other cuts, over the same buffer
    cuts2 = cuts + np.float32(0.1)
    out2 = fn(out, values_t, jnp.asarray(cuts2))
    np.testing.assert_array_equal(np.asarray(out2)[:f].T,
                                  histogram.apply_cuts(x, cuts2))
