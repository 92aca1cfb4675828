"""The main path's kernels compiled for a DESCRIBED TPU v5e 2x2 at real
widths (on-chip-measurement guide, section 2): the chip's compiler runs
here without the chip, so what Mosaic refuses — a slice off the tiling,
too much scoped VMEM, a program that does not fit HBM — fails a test
instead of a chip call.  Interpret mode shows none of that: the Pallas
ring passed every interpret-mode test while no chip could compile it.

Nothing runs: a compile that passes is not a chip run (that is
``chip_smoke.py``).  The library's own defaults ask ``rabit_tpu.ops.
on_tpu``, which says "no" in this sandbox, so the backend name is faked
for the lowering — steering in the test, not an option of the program.
The file's name sorts it early in a suite that is cut at its time limit.
"""
import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# memory_stats()["bytes_limit"] of the chip tool's v5e (chip run, PR 21)
V5E_BYTES_LIMIT = 16909336064


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def chip_compiler(monkeypatch):
    """Library defaults take their TPU branch; the persistent cache is
    off (a described-device compile is written to it but cannot be read
    back without a chip, and warns on the next run)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _one_chip(topo, *shaped):
    s = SingleDeviceSharding(topo.devices[0])
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=s)
            for shape, dtype in shaped]


def _kmeans_dense(topo):
    from rabit_tpu.ops.kmeans_kernel import kmeans_stats_fused

    fn = jax.jit(functools.partial(kmeans_stats_fused, interpret=False))
    return fn.lower(*_one_chip(topo, ((64, 256), jnp.float32),
                               ((1 << 19, 256), jnp.bfloat16),
                               ((1 << 19,), jnp.float32))).compile()


def _hist_level(topo):
    from rabit_tpu.ops.histogram_kernel import hist_fused_multi, level_plan

    # a level of 4 slots, 8 channels: under the rule's crossing, so the
    # two-level body, its 8 feature groups unrolled (what the narrow
    # levels of the HIGGS and Covertype cells run, and no other id
    # compiles); 64 features, 256 bins, 262k rows
    n = 1 << 18
    assert level_plan(256, 64, 4) == (False, 1, 8)
    fn = jax.jit(functools.partial(hist_fused_multi, nbin=256, nslots=4,
                                   interpret=False))
    (bins, gh, node) = _one_chip(topo, ((64, n), jnp.int32),
                                 ((2, n), jnp.float32), ((n,), jnp.int32))
    return fn.lower(bins, gh, node_of_row=node).compile()


def _hist_level_staged(nslots, topo):
    from rabit_tpu.ops.histogram_kernel import hist_fused_multi

    # one lane-wide call of 128 lanes: 32 node slots x (grad, hess)
    # folded in inside the kernel, 28 features staged as (32, n) int32,
    # 256 bins, one chip's 33.6M rows; the bins are not copied (the
    # temporaries are the bf16 grad and hess)
    n = 32 << 20
    fn = jax.jit(functools.partial(hist_fused_multi, nbin=256, nslots=nslots,
                                   interpret=False))
    (bins, gh, node) = _one_chip(topo, ((32, n), jnp.int32),
                                 ((2, n), jnp.float32), ((n,), jnp.int32))
    compiled = fn.lower(bins, gh, node_of_row=node).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= n * 2 * 2 + (1 << 20)
    return compiled


def _hist_level_chunked(nslots, topo):
    from rabit_tpu.learn import histogram

    # the benchmark cell's widest level as level_hist builds it (16
    # slots since a level builds one child of every split node): 32
    # channels, one call of the lane-wide body (two calls of 8 slots of
    # the two-level body before the rule); beside the bf16 grad and hess
    # no temporary of a row's length
    n = 32 << 20
    assert histogram.level_calls(nslots, 28, 256, True) == (1, 1)
    fn = jax.jit(lambda bins, gh, node: histogram.level_hist(
        bins, gh, node, nslots, 28, 256, use_pallas=True))
    compiled = fn.lower(*_one_chip(topo, ((32, n), jnp.int32),
                                   ((2, n), jnp.float32),
                                   ((n,), jnp.int32))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= n * 2 * 2 + n * 4 + (2 << 20))
    return compiled


def _hist_level_wide(topo):
    from rabit_tpu.learn import histogram

    # the wide boosting cell's widest kernel call: 968 features staged
    # as (968, n) int32 (121 groups of 8), 256 bins with absent entries
    # coded 256, 3 slots (6 channels: what the VMEM accumulator holds at
    # this width) and the slots' totals; 1,183,747 rows are no multiple
    # of the kernel's row block, and the bins are still not copied
    n, f = 1183747, 968
    assert histogram.slots_per_call(256, f) == 3
    fn = jax.jit(lambda bins, gh, node: histogram.level_hist(
        bins, gh, node, 3, f, 256, use_pallas=True, totals=True))
    compiled = fn.lower(*_one_chip(topo, ((f, n), jnp.int32),
                                   ((2, n), jnp.float32),
                                   ((n,), jnp.int32))).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes <= 64 << 20, m
    assert m.output_size_in_bytes == 3 * (f + 1) * 256 * 2 * 4
    return compiled


def _hist_level_wide_lanes(nslots, topo):
    from rabit_tpu.learn import histogram
    from rabit_tpu.ops import histogram_kernel as hk

    # the wide boosting cell's two widest levels (8 and 16 built slots)
    # as level_hist builds them since the lane-wide body takes a wide
    # shard's features chunk by chunk: one call of 128 lanes, its
    # (248, 256, 128) f32 accumulator a quarter of the features, on a
    # grid of 4 chunks x 579 row blocks; looped bodies only (PR 33 paid
    # 433 s for 121 unrolled groups).  The bins are still not copied:
    # beside them the kernel's raw (992, 256, 128) output and its slice
    n, f = 1183747, 968
    assert hk.lane_chunk(256, f, 128) == 248
    assert histogram.level_calls(nslots, f, 256, True) == (1, 1)
    fn = jax.jit(lambda bins, gh, node: histogram.level_hist(
        bins, gh, node, nslots, f, 256, use_pallas=True, totals=True))
    compiled = fn.lower(*_one_chip(topo, ((f, n), jnp.int32),
                                   ((2, n), jnp.float32),
                                   ((n,), jnp.int32))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes <= 992 * 256 * 128 * 4 * 2, m
    assert m.output_size_in_bytes == nslots * (f + 1) * 256 * 2 * 4
    return compiled


def _kmeans_ell_chain(topo):
    from rabit_tpu.learn import kmeans

    # the chained fused-ELL program kmeans.run() builds at d=512,
    # 32-nnz, 4M rows staged grouped (n/4, 4*nnz)
    n, nnz, g = 4 << 20, 32, kmeans._ELL_FUSED_GROUP
    fn = kmeans._ell_chain_fn(8, 64, 512, 512, nnz)
    return fn.lower(*_one_chip(topo, ((64, 512), jnp.float32),
                               ((n // g, g * nnz), jnp.int32),
                               ((n // g, g * nnz), jnp.float32),
                               ((n,), jnp.float32))).compile()


def _kmeans_ell_chunk(topo):
    from rabit_tpu.learn import kmeans

    # the call of a shard that streams (tier ell_stream): the kernel on
    # one chunk of 2^20 rows, its statistics added to the pass's sum
    n, nnz, g = kmeans._STAGE_CHUNK_ROWS, 32, kmeans._ELL_FUSED_GROUP
    fn = kmeans._ell_chunk_fn(64, 512, 512, nnz)
    return fn.lower(*_one_chip(topo, ((64, 513), jnp.float32),
                               ((64, 512), jnp.float32),
                               ((n // g, g * nnz), jnp.int32),
                               ((n // g, g * nnz), jnp.float32),
                               ((n,), jnp.float32))).compile()


def test_stage_slice_writer_updates_the_shard_in_place_on_v5e(topo):
    """The fused ELL tier's staging at the sparse cell's shape (33.5M
    rows grouped (n/4, 128), slices of 2^20 rows): the donated array is
    the output, so the device holds the array and the slice."""
    from rabit_tpu.learn import kmeans

    n_g, rows = (32 << 20) // kmeans._ELL_FUSED_GROUP, 1 << 18
    x, c, start = _one_chip(topo, ((n_g, 128), jnp.int32),
                            ((rows, 128), jnp.int32), ((), jnp.int32))
    m = kmeans._stage_slice_fns()[1].lower(x, c, start).compile(
        ).memory_analysis()
    assert m.alias_size_in_bytes == m.output_size_in_bytes == n_g * 128 * 4
    assert m.temp_size_in_bytes <= 1 << 20


def test_approx_rebin_writes_over_the_old_bins_on_v5e(topo):
    """``tree_method="approx"`` bins the resident values again every
    round at the cell's shape (28 x 33.5M float32 into (32, n) int32):
    the donated bins are the output and the program holds a feature's
    temporaries beside them, not a second array of 4.29 GB."""
    from rabit_tpu.learn import histogram

    n, f = 32 << 20, 28
    fpad = histogram.staged_features(f, 256)
    m = jax.jit(histogram.rebin, donate_argnums=(0,)).lower(*_one_chip(
        topo, ((fpad, n), jnp.int32), ((f, n), jnp.float32),
        ((f, 255), jnp.float32))).compile().memory_analysis()
    assert m.alias_size_in_bytes == m.output_size_in_bytes == fpad * n * 4
    assert m.temp_size_in_bytes <= 3 * n * 4, m


def test_approx_cuts_program_of_four_ranks_compiles_for_v5e(topo):
    """The cuts of four ranks' merged summaries (28 x 8,192 entries a
    rank): kilobytes out of 11 MB, in about their own size."""
    from rabit_tpu.learn import histogram

    entries = histogram.summary_entries(256)
    m = jax.jit(lambda s: histogram.sketch_cuts(s, 256)).lower(*_one_chip(
        topo, ((4, 28, entries, 3), jnp.float32))).compile(
        ).memory_analysis()
    assert m.output_size_in_bytes <= 28 * 256 * 4 + 4096
    assert m.temp_size_in_bytes <= 8 * 4 * 28 * entries * 3 * 4, m


def test_approx_sketch_sorts_pairs_by_one_compare_on_v5e(topo):
    """``gbdt_sketch`` at the cell's shape (28 features of 2^25 rows):
    the chip's sort carries the key and the weight and compares the
    keys once.  A stable sort is rewritten here to carry an iota of the
    rows as a third operand and to compare it too (five operations),
    and compiles for three times as long.  The program's temporaries
    stay a feature's five arrays (keys and weights before and after the
    sort, the prefix sums): buffer assignment gives the same figure with
    and without the iota."""
    import re

    from rabit_tpu.learn import histogram

    n, f = 32 << 20, 28
    entries = histogram.summary_entries(256)

    def gbdt_sketch(values_t, gh):
        return histogram.sketch_summary(values_t, gh[1], entries)[None]

    compiled = jax.jit(gbdt_sketch).lower(*_one_chip(
        topo, ((f, n), jnp.float32), ((2, n), jnp.float32))).compile()
    text = compiled.as_text()
    (sort,) = [line for line in text.splitlines()
               if re.search(r"\bsort\(", line)]
    assert len(re.search(r"\bsort\(([^)]*)\)", sort).group(1).split(",")) == 2
    assert "is_stable=true" not in sort
    assert not re.search(rf"s32\[{n}\]\S* iota\(", text)
    comparator = re.search(r"to_apply=(%[\w.]+)", sort).group(1)
    body = text.split(f"\n{comparator} (")[1].split("\n}")[0]
    assert body.count(" compare(") == 1 and " select(" not in body, body
    assert compiled.memory_analysis().temp_size_in_bytes <= 6 * n * 4


def test_wide_level_scan_holds_a_level_and_hands_over_kilobytes_on_v5e(topo):
    """The wide boosting cell's deepest ``gbdt/scan`` as ``boosting.
    _DeviceShard`` builds it: 16 built slots of 968 features and the
    totals row, the 16 slots above, a level of 32.  Its arguments are
    the two levels as they are (no padded copy of the built slots'
    minor dimension of 2), it returns the level for the next depth and
    a shortlist a slot, and works in about the level's own size."""
    from rabit_tpu.learn import histogram

    f, nbin, p = 968, 256, 16

    def gbdt_scan(built, above, build):
        level = histogram.assemble_level(built, above, build)
        return (level,) + histogram.level_shortlist(level, f, 1.0, 1.0, True)

    m = jax.jit(gbdt_scan).lower(*_one_chip(
        topo, ((p, f + 1, nbin, 2), jnp.float32),
        ((2, p, f + 1, nbin), jnp.float32), ((p,), jnp.int32))).compile(
        ).memory_analysis()
    level = 2 * p * (f + 1) * nbin * 2 * 4
    k = histogram.SHORTLIST
    assert m.argument_size_in_bytes <= level + (1 << 20)
    assert level <= m.output_size_in_bytes <= level + (1 << 20)
    assert m.output_size_in_bytes - level >= 2 * p * (k + 1) * nbin * 2 * 4
    assert m.temp_size_in_bytes <= 2 * level, m


def _covtype_cuts(f=54, wide=10, nbin=256):
    """Cuts of the multi-class cell's kind: ``wide`` continuous columns,
    then indicator columns: all zeros (set in under 1/256 of the rows),
    zeros then ones, and zeros, one interpolated quantile, ones."""
    cuts = np.tile(np.linspace(-2, 2, nbin - 1, dtype=np.float32), (f, 1))
    for j in range(wide, f):
        ones = (0, 3, 40, 129, 250)[j % 5]
        cuts[j] = 0.0
        if ones:
            cuts[j, -ones:] = 1.0
            if j % 2:
                cuts[j, -ones - 1] = 0.625
    return cuts


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
def test_forest_level_programs_of_seven_classes_compile_for_v5e(
        topo, monkeypatch, packed):
    """The multi-class boosting cell's programs as ``boosting.
    _DeviceShard`` builds them (8,388,608 rows of 54 columns staged as
    (56, n), 7 classes, depth 6): the softmax gradient, a level program
    a width holding every tree's slots (one lane-wide kernel call for
    the seven trees at every width), a row move a depth and the
    leaf update of the (7, n) node ids and margins in place, and the
    scans over 7 times the slots.  ``packed``: with the pack plan of
    cuts of the cell's kind (44 indicator columns of 2 to 4 codes share
    one product: its codes the level programs' fifth operand, its
    scatter back inside them, still one kernel call a level).
    The shapes are handed a described device here (steering in the
    test: the shard builds them from ``jax.ShapeDtypeStruct``)."""
    from rabit_tpu.learn import boosting, histogram
    from rabit_tpu.ops import histogram_kernel as hk

    n, f, k, nbin, depth = 8 << 20, 54, 7, 256, 6
    real = jax.ShapeDtypeStruct
    s = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(jax, "ShapeDtypeStruct",
                        lambda shape, dtype: real(shape, dtype, sharding=s))
    monkeypatch.setattr(boosting, "_PROGRAMS", {})
    shard = object.__new__(boosting._DeviceShard)
    shard.model = boosting.BoostedModel(
        cuts=np.zeros((f, nbin - 1), np.float32), base_score=0.5,
        loss="softprob", num_class=k)
    shard.n, shard.f, shard.nbin, shard.max_depth = n, f, nbin, depth
    shard.trees, shard.lead = k, (k,)
    shard.subsample, shard.seed = 1.0, 0
    shard.use_pallas, shard.compute_dtype = True, None
    shard.scan_by, shard.has_missing = (1.0, 1.0), False
    fpad = histogram.staged_features(f, nbin)
    shard.bins_t = real((fpad, n), jnp.int32)
    assert fpad == 56 and histogram.slots_per_call(nbin, fpad) == 8
    shard.pack = hk.pack_plan(_covtype_cuts()) if packed else None
    if packed:
        plan, codes = shard.pack
        assert plan == (tuple(range(10, 54)), 4) and codes.shape == (44, 4)
        assert sorted({int((c < nbin).sum()) for c in codes}) == [2, 3, 4]
    prog = shard._programs()

    def fits(compiled):
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        assert need <= V5E_BYTES_LIMIT // 2, m
        return m

    assert sorted(prog["level"]) == [1, 2, 4, 8, 16]
    for p, level in prog["level"].items():
        # 14 to 224 channels: one lane-wide call for the seven trees
        # (256 lanes at 16 slots)
        calls = histogram.level_calls(p, f, nbin, True, k)
        assert calls == (1, 1)
        assert level.as_text().count("tpu_custom_call") == calls[0], p
        assert histogram.level_packs(p, f, nbin, True, k) == 1
        m = fits(level)
        assert m.argument_size_in_bytes >= packed * 44 * 4 * 4
        assert m.output_size_in_bytes == k * p * f * nbin * 2 * 4
        # beside the bins: the trees' (2, n) weight pairs, their bf16
        # operands and a second call's slot codes
        assert m.temp_size_in_bytes <= k * n * (8 + 4 + 4) + (8 << 20), m
    grad = fits(prog["grad"])
    assert grad.output_size_in_bytes == k * 2 * n * 4
    # (7, n) is laid out in tiles of 8 rows: the donated array is the
    # output, at 8 rows' size, of every depth's move
    assert sorted(prog["partition"]) == list(range(depth))
    for move in map(fits, prog["partition"].values()):
        assert move.alias_size_in_bytes == move.output_size_in_bytes \
            == 8 * n * 4
    leaf = fits(prog["leaf"])
    assert leaf.alias_size_in_bytes == 2 * 8 * n * 4
    assert leaf.output_size_in_bytes <= 2 * 8 * n * 4 + 4096   # the tuple
    assert sorted(prog["scan"]) == [1, 2, 4, 8, 16, 32]
    widest = fits(prog["scan"][32])
    # the level for the next depth and 8 shortlisted rows a slot
    level = 2 * k * 32 * f * nbin * 4
    short = 2 * k * 32 * histogram.SHORTLIST * nbin * 4
    assert level + short <= widest.output_size_in_bytes \
        <= level + short + (1 << 20)


@pytest.mark.parametrize("n,f,k,sliced", [
    (8 << 20, 54, 7, []), (32 << 20, 28, 1, [0, 1]),
    (1183747, 968, 1, [0, 1, 2, 3, 4, 5]), (1183747, 968, 7, [0, 1, 2, 3, 4]),
], ids=["covtype-7x56x8m", "higgs-32x33m", "bosch-968x1183747",
        "seven-trees-968x1183747"])
def test_every_depths_row_move_compiles_for_v5e(topo, monkeypatch, n, f, k,
                                                sliced):
    """``boosting.partition_program`` of each of six depths at the three
    boosting cells' shapes (and seven trees on the wide one's rows, the
    one that slices several trees' rows): the donated node ids are the
    output (a round of seven trees: in tiles of 8 rows; of one: n ids);
    a depth that slices (``_move_slices``: where 8 rows a slice read
    less than the staged array) holds one dynamic slice a tree and level
    node and no temporary but the trees' ids; one that passes over the
    array whole holds none, and the trees' split features and bins
    beside the ids."""
    from rabit_tpu.learn import boosting, histogram

    nbin, depth = 256, 6
    real = jax.ShapeDtypeStruct
    s = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(jax, "ShapeDtypeStruct",
                        lambda shape, dtype: real(shape, dtype, sharding=s))
    monkeypatch.setattr(boosting, "_PROGRAMS", {})
    fpad = histogram.staged_features(f, nbin)
    # (7, n) in tiles of 8 x 128, (n,) in tiles of 1,024
    ids = (8 * 128 * -(-n // 128) if k > 1 else 1024 * -(-n // 1024)) * 4
    assert [d for d in range(depth)
            if boosting._move_slices(k, 1 << d, fpad)] == sliced
    for d in range(depth):
        move = boosting.partition_program(n, fpad, k, 1 << d, nbin)
        m, text = move.memory_analysis(), move.as_text()
        assert m.alias_size_in_bytes == m.output_size_in_bytes == ids, (d, m)
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        assert need <= V5E_BYTES_LIMIT // 2, (d, m)
        assert m.argument_size_in_bytes >= fpad * n * 4
        if d in sliced:
            assert text.count(" dynamic-slice(") == k << d, d
            assert m.temp_size_in_bytes <= 2 * ids + (1 << 20), (d, m)
        else:
            assert " dynamic-slice(" not in text, d
            assert m.temp_size_in_bytes <= 2 * ids + (8 << 20), (d, m)
        assert m.temp_size_in_bytes < fpad * n * 4 // 2


def _allstate_flat(nbin=256):
    """The sparse boosting cell's flat bin space: 15 columns of 255
    cuts, 4,212 indicator columns of one."""
    from rabit_tpu.learn import histogram

    cuts = np.array([nbin - 1] * 15 + [1] * 4212)
    cut_ptr = np.concatenate([[0], np.cumsum(cuts)])
    return histogram.FlatBins(cut_ptr, np.zeros(cut_ptr[-1], np.float32),
                              nbin)


@pytest.mark.parametrize("nslots", [1, 2, 4, 8, 16])
def test_sparse_level_kernel_compiles_for_v5e_at_every_width(topo, nslots):
    """``ops.sparse_hist_kernel.hist_sparse`` at the sparse boosting
    cell's shapes (2^25 rows of 32 slots bucketed over 24 blocks of 512
    cells, a level of 1 to 16 build slots): Mosaic takes the two one-hot
    products, the resident ``(24, 128, 128)`` accumulator, a tile's 280
    sub-chunks as one block and the loop over its live steps, whose trip
    count is a scalar read from SMEM (PR 52: a ``while`` in what Mosaic
    is given, and one branch, the call's first tile); the program's
    temporaries are the table of (grad, hess, slot) a row, in float32
    and in bfloat16, the tiles' SMEM rows (4 KB a tile) and little else
    (24 bytes a row)."""
    from rabit_tpu.ops import sparse_hist_kernel as sk

    flat, n, width = _allstate_flat(), 1 << 25, 32
    assert flat.cells == 12265 and flat.size == 24 * sk.CELL_BLOCK
    tiles, cap = n // sk.ROW_TILE, sk.capacity(width, flat.cells)
    assert cap == 35 * sk.STEP
    traced = sk.hist_sparse.trace(*_one_chip(
        topo, ((tiles * cap // sk.SUB, sk.SUB), jnp.int32),
        ((tiles * cap // sk.STEP, sk.SUBS), jnp.int32),
        ((2, n), jnp.float32), ((n,), jnp.int32)),
        tiles=tiles, nslots=nslots, cells=flat.cells, interpret=False)
    kernel = str(traced.jaxpr)
    assert kernel.count(" cond[") == 1 and " while[" in kernel
    m = traced.lower().compile().memory_analysis()
    assert m.argument_size_in_bytes >= tiles * cap * 4
    assert m.output_size_in_bytes == nslots * flat.size * 2 * 4
    assert m.temp_size_in_bytes <= 24 * n, m


def test_sparse_staging_and_row_move_compile_for_v5e(topo, monkeypatch):
    """The sparse shard's other programs at the cell's shapes: the
    bucketing of a group of 32 tiles (a sort of 32 x 143,360 slots) and
    the count of the steps its marks leave the kernel, the
    row move of every depth (the (32, n) cells searched for the split's
    column, the donated node ids the output, no temporary of the cells'
    size) and the deepest ``gbdt/scan`` on the flat axis (16 built slots
    of 12,288 cells, the 16 above: it hands over the level and a
    shortlist of windows, 9 x 256 a slot)."""
    from rabit_tpu.learn import boosting, histogram
    from rabit_tpu.ops import sparse_hist_kernel as sk

    flat, n, width = _allstate_flat(), 1 << 25, 32
    s = SingleDeviceSharding(topo.devices[0])
    real = jax.ShapeDtypeStruct
    monkeypatch.setattr(jax, "ShapeDtypeStruct",
                        lambda shape, dtype: real(shape, dtype, sharding=s))
    monkeypatch.setattr(histogram, "_CACHE", {})
    bucket = histogram._bucket_program(n, width, sk.GROUP_TILES, flat.cells)
    m = bucket.memory_analysis()
    group = sk.GROUP_TILES * sk.capacity(width, flat.cells) * 4
    assert group <= m.output_size_in_bytes <= 1.1 * group
    # the count of the grid steps the kernel works, from the staged marks
    steps = n // sk.ROW_TILE * sk.capacity(width, flat.cells) // sk.STEP
    m = sk.steps_worked.lower(real((steps, sk.SUBS), jnp.int32, sharding=s),
                              cells=flat.cells).compile().memory_analysis()
    assert m.temp_size_in_bytes <= 4 * steps, m
    cells = real((width, n), jnp.int32, sharding=s)
    for depth in range(6):
        move = jax.jit(boosting._move_entries, donate_argnums=(1,)).lower(
            cells, real((n,), jnp.int32, sharding=s),
            real((1 << depth, 5), jnp.int32, sharding=s)).compile()
        m = move.memory_analysis()
        assert m.alias_size_in_bytes == m.output_size_in_bytes == n * 4
        assert m.temp_size_in_bytes <= 16 * n * 4, (depth, m)

    def gbdt_scan(built, above, build):
        level = histogram.assemble_level(built[:, None], above, build)
        return (level,) + histogram.level_shortlist_flat(level, flat, 1.0,
                                                         1.0)

    p = 16
    m = jax.jit(gbdt_scan).lower(
        real((p, flat.size, 2), jnp.float32, sharding=s),
        real((2, p, 1, flat.size), jnp.float32, sharding=s),
        real((p,), jnp.int32, sharding=s)).compile().memory_analysis()
    level = 2 * 2 * p * flat.size * 4
    k = histogram.SHORTLIST
    assert level <= m.output_size_in_bytes <= level + (1 << 20)
    assert m.output_size_in_bytes - level >= 2 * 2 * p * (k + 1) * 256 * 4
    assert m.temp_size_in_bytes <= 64 * level, m


def test_sparse_shards_programs_compile_for_v5e(topo, monkeypatch):
    """Every program of a sparse boosting job as ``boosting._SparseShard``
    builds them at the cell's shapes (2^25 rows, depth 6, the levels'
    histograms kept on the device): the gradient, a level program a
    width (the slots of a row, ``hist_sparse``, the totals), a row move
    a depth, the leaf update and a scan a level.  A round's programs
    need the entries' two copies and 1.1 GB beside them at the widest
    level (the chip's peak reads 10.25 GB: PERF.md section 5)."""
    from rabit_tpu.learn import boosting
    from rabit_tpu.ops import sparse_hist_kernel as sk

    flat, n, width = _allstate_flat(), 1 << 25, 32
    s = SingleDeviceSharding(topo.devices[0])
    real = jax.ShapeDtypeStruct
    monkeypatch.setattr(jax, "ShapeDtypeStruct",
                        lambda shape, dtype: real(shape, dtype, sharding=s))
    monkeypatch.setattr(boosting, "_PROGRAMS", {})
    slots = n // sk.ROW_TILE * sk.capacity(width, flat.cells)
    shard = object.__new__(boosting._SparseShard)
    shard.__dict__.update(
        n=n, rows=n, f=flat.f, width=width, flat=flat, nbin=256,
        max_depth=6, trees=1, lead=(), subsample=1.0, seed=0,
        use_pallas=True, compute_dtype=None, scan_by=(1.0, 1.0),
        has_missing=True, approx=False, pack=None,
        model=boosting.BoostedModel(cuts=flat.cut_vals,
                                    cut_ptr=flat.cut_ptr),
        entries=(real((width, n), jnp.int32),
                 real((slots // sk.SUB, sk.SUB), jnp.int32),
                 real((slots // sk.STEP, sk.SUBS), jnp.int32)))
    prog = shard._programs()
    assert sorted(prog["level"]) == [1, 2, 4, 8, 16]
    assert sorted(prog["partition"]) == list(range(6))
    assert sorted(prog["scan"]) == [1, 2, 4, 8, 16, 32]
    for p, level in prog["level"].items():
        m = level.memory_analysis()
        # the kernel's road reads the bucketed copy, not the move's
        assert slots * 4 <= m.argument_size_in_bytes < (
            width * n + slots) * 4, p
        assert m.output_size_in_bytes == p * flat.size * 2 * 4, p
        assert m.temp_size_in_bytes <= 5 << 28, (p, m)
        assert "hist_sparse" in level.as_text()
    for d, move in prog["partition"].items():
        m = move.memory_analysis()
        assert m.alias_size_in_bytes == m.output_size_in_bytes == n * 4, d


def _dense16_loop(topo):
    from rabit_tpu.learn import kmeans

    # tools/big_kmeans.py dense: ~24M x 256 bf16 resident, chained
    n = 23 << 20
    fn = kmeans._device_loop_fn(8, True, kmeans._DENSE16_ROW_TILE,
                                "bfloat16")
    compiled = fn.lower(*_one_chip(topo, ((64, 256), jnp.float32),
                                   ((n, 256), jnp.bfloat16),
                                   ((n,), jnp.float32))).compile()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert need <= V5E_BYTES_LIMIT, (need, m)
    return compiled


def _lbfgs_product(which, topo):
    from rabit_tpu.ops import sparse_linear_kernel as sk

    # the benchmark cell's shard as linear.stage_rows leaves it: 1024
    # row tiles of 16,384 rows x 39 non-zeros over a million weights,
    # both kernels with the whole weight (or gradient) table in VMEM
    nf, tiles = 1_000_000, 1024
    subs = tiles * sk.capacity(39, nf) // sk.SUB
    packed, val, fb, w, g = _one_chip(
        topo, ((subs, sk.SUB), jnp.int32), ((subs, sk.SUB), jnp.float32),
        ((subs // sk.SUBS, sk.SUBS), jnp.int32), ((nf,), jnp.float32),
        ((tiles * sk.ROW_TILE,), jnp.float32))
    if which == "margin":
        return sk.lbfgs_margin.lower(packed, val, fb, w, tiles=tiles,
                                     interpret=False).compile()
    return sk.lbfgs_grad.lower(packed, val, fb, g, tiles=tiles,
                               num_feature=nf, interpret=False).compile()


def _mesh(topo):
    return Mesh(np.array(topo.devices), ("dp",))


def _mesh_kmeans_step(topo):
    """The data-parallel step of __graft_entry__ / chip_smoke --chips 4:
    fused kernel -> framework allreduce -> centroid update."""
    from rabit_tpu.learn import kmeans as km
    from rabit_tpu.ops import ReduceOp
    from rabit_tpu.ops.kmeans_kernel import kmeans_stats_fused
    from rabit_tpu.parallel import collectives as C

    mesh = _mesh(topo)

    def step(cent, x, v):
        stats = kmeans_stats_fused(cent, x, v, interpret=False)
        return km.centroid_update(
            cent, C.allreduce(stats, "dp", ReduceOp.SUM))

    fn = C.shard_collective(
        mesh, step, in_specs=(P(), P("dp", None), P("dp")), out_specs=P(),
        check_vma=False)
    n = 4 << 20
    compiled = fn.lower(
        jax.ShapeDtypeStruct((64, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct((n, 256), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp", None))),
        jax.ShapeDtypeStruct((n,), jnp.float32,
                             sharding=NamedSharding(mesh, P("dp")))
    ).compile()
    assert "all-reduce" in compiled.as_text()
    return compiled


def _ring(nbytes, topo):
    from rabit_tpu.ops.ring_allreduce import ring_allreduce_pallas

    mesh = _mesh(topo)
    fn = jax.jit(jax.shard_map(
        lambda s: ring_allreduce_pallas(s[0], "dp", interpret=False)[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
    return fn.lower(jax.ShapeDtypeStruct(
        (4, nbytes // 4), jnp.float32,
        sharding=NamedSharding(mesh, P("dp")))).compile()


def test_distributed_update_program_compiles_for_v5e(topo):
    """The per-iteration loop's two device programs at the x4 cell's
    shapes, as the loop hands them over (operands committed to one
    chip): the update of the allreduced (64, 257) statistics, plain XLA
    that leaves float32 centroids and the counts column, and the stats
    program that takes those centroids with a rank's 24.1M rows."""
    from rabit_tpu.learn import kmeans as km

    (stats,) = _one_chip(topo, ((64, 257), jnp.float32))
    update = km._update_fn().lower(stats).compile()
    assert "tpu_custom_call" not in update.as_text()
    cent, counts = update.output_shardings
    assert cent == counts == stats.sharding
    shapes = jax.eval_shape(km._update_fn(), stats)
    assert [(o.shape, o.dtype) for o in shapes] == [
        ((64, 256), jnp.float32), ((64, 1), jnp.float32)]
    n = 23 << 20
    step = km._dense16_stats_fn(64, 256, 256).lower(*_one_chip(
        topo, ((64, 256), jnp.float32), ((n, 256), jnp.bfloat16),
        ((n,), jnp.float32))).compile()
    assert "tpu_custom_call" in step.as_text()
    assert step.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("build", [
    _kmeans_dense, _hist_level, functools.partial(_hist_level_staged, 32),
    functools.partial(_hist_level_chunked, 16), _hist_level_wide,
    functools.partial(_hist_level_wide_lanes, 8),
    functools.partial(_hist_level_wide_lanes, 16),
    _kmeans_ell_chain, _kmeans_ell_chunk, _dense16_loop,
    functools.partial(_lbfgs_product, "margin"),
    functools.partial(_lbfgs_product, "grad"),
    _mesh_kmeans_step,
    # latency-sized, one VMEM segment, and past the segmentation
    # threshold (_VMEM_BUDGET_BYTES): the three fail at the parent commit
    functools.partial(_ring, 64 << 10), functools.partial(_ring, 4 << 20),
    functools.partial(_ring, 64 << 20),
], ids=["kmeans_stats_fused-bf16-512k", "hist_fused_multi-4slots-64x256x262k",
        "hist_fused_multi-32slots-28x256x33.6M",
        "hist_fused_multi-16slots-28x256x33.6M",
        "hist_fused_multi-3slots-968x256x1.18M-ragged",
        "hist_fused_multi-8slots-lanes-968x256x1.18M-ragged",
        "hist_fused_multi-16slots-lanes-968x256x1.18M-ragged",
        "kmeans_ell_chain-d512-4M", "kmeans_ell_chunk_stats-d512-1M",
        "dense16_loop-24M",
        "lbfgs_margin-16.8Mx39-1M", "lbfgs_grad-16.8Mx39-1M", "mesh_kmeans_step",
        "ring-64KB", "ring-4MB", "ring-64MB"])
def test_compiles_for_v5e(topo, build):
    assert "tpu_custom_call" in build(topo).as_text()
