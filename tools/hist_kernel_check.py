#!/usr/bin/env python3
"""The histogram kernel's two bodies held against each other ON THE
CHIP, before any timing, and then timed width by width.

``ops/histogram_kernel.py`` builds a tree level's histograms by one of
two bodies (``level_plan``): the two-level one-hot product, a call's
cost linear in its channels, and the lane-wide one, channels on the
MXU's lanes.  Two earlier re-plans of that kernel passed every
interpret-mode test and were wrong on the chip, so this script is the
judge: at the boosting cells' own shapes it builds the same level with
both bodies from the same inputs and prints

* the largest ``|lane - two-level|`` of any (channel, feature, bin) as a
  share of the channel's absolute mass (sum of ``|w|`` over its rows;
  the limit is 1e-5: both round the weights to bf16 once and add exact
  products in float32, only the order of the adds differs), and, where
  they part, the first place: channel (tree, slot, grad/hess), feature,
  bin and, by bisection over the rows with everything else masked to
  node -1, the row block;
* both against a float64 numpy histogram of the first 2^20 rows;
* a ragged ``n`` (no multiple of the row block) with rows at node -1;
* then, unless ``--no-timing``, seconds a call of each body at 2 to 128
  channels of one tree at each row shape, and the forest's levels.

``packed`` (PR 48) holds the lane-wide body with a ``pack_plan`` against
the same body without one, BIT FOR BIT (a bin's sum is the same
products added in the same order along the block): at the multi-class
cell's shape with cuts of its kind (10 continuous columns, 44 indicator
columns set in a ten-thousandth to nine tenths of the rows, so that
some hold one reachable code, the rule ``hk.pack_plan`` deciding), at
128 and 256 lanes, ragged, rows at node -1, absent entries, both
against float64; then seconds a call of each, and of a packed call whose
44 narrow features hold 2, 4, 8 and 16 codes (the table
``_NARROW_CODES`` rests on).

``bosch`` is the wide boosting cell's shape (968 features, 1,183,747
rows, ragged as they are, 81% of the entries absent station by station,
8 and 16 slots): the lane-wide body there takes the features chunk by
chunk on a second grid axis (``lane_chunk``).

``sparse`` (PR 49) holds the sparse-row kernel (``ops/
sparse_hist_kernel.py``: entries bucketed by cell block, a level's
histograms as two one-hot products an entry) against XLA's
``segment_sum`` over the same entries at the sparse boosting cell's
shape (2^24 rows of up to 32 entries over a flat bin space of 12,265
cells: 15 columns of 256 bins in nearly every row, the rest indicator
columns by a power law, ragged rows, rows at node -1, and uneven tiles:
the second holds no entry, the third every slot), every level's width,
and against float64 on the first 2^20 rows; then seconds a call of each
width and of the bucketing.  Since PR 52 the kernel does nothing on the
steps of a tile's dead tail (``bucket_group`` marks them, the kernel's
loop over a tile's steps ends before them): at every width its sums must
EQUAL BIT FOR BIT those it gives with the marks taken out (every step
worked: the sums of before PR 52), and each timing line holds both
calls' seconds beside the share of the staged slots worked.

Not on any cell's path.  Run it through the chip tool:

    chiprun --timeout 1800 -- python3 tools/hist_kernel_check.py
    chiprun -- python3 tools/hist_kernel_check.py --cases packed
    chiprun -- python3 tools/hist_kernel_check.py --cases sparse

It prints one JSON object a line and writes the same lines to
``chiprun_out/hist_kernel_check/report.jsonl``; exit code 1 if the
bodies part anywhere.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from rabit_tpu.ops import histogram_kernel as hk  # noqa: E402

NBIN = 256
LIMIT = 1e-5
SLICE_ROWS = 1 << 20
# (name, staged feature rows, features, rows, trees, stations): the
# cells' shapes; with stations, a row's entries are absent (code 256)
# station by station, ``PRESENT`` of them present
SHAPES = {"higgs": (32, 28, 1 << 25, 1, 0), "covtype": (56, 54, 1 << 23, 7, 0),
          "bosch": (968, 968, 1183747, 1, 52)}
WIDTHS = (2, 4, 8, 16, 32, 64, 128)
PRESENT = 0.19


CDT = jnp.dtype(hk.DEFAULT_COMPUTE_DTYPE).name


def _mix(x):
    for shift, mult in ((15, 2246822519), (13, 3266489917), (16, 1)):
        x = (x ^ (x >> shift)) * jnp.uint32(mult)
    return x


def visiting_shares(stations: int) -> np.ndarray:
    """A ladder from the rarest station (0.4% of the rows visit it) to
    the commonest (99.5%), bent until ``PRESENT`` of all visits are
    made: the wide cell's own recipe, stations of one width here."""
    rank = np.arange(stations) / (stations - 1.0)
    a, b = 0.02, 50.0
    for _ in range(80):
        bend = (a * b) ** 0.5
        share = 0.004 * (0.995 / 0.004) ** (rank ** bend)
        a, b = (a, bend) if share.mean() < PRESENT else (bend, b)
    return share


@functools.partial(jax.jit, static_argnames=("fpad", "n", "stations"))
def make_bins(seed, fpad: int, n: int, stations: int = 0):
    """Made on the device in one fused pass (a hash of the place: no
    generator's temporaries beside 4.3 GB of bins): uniform over 0..256,
    256 being the absent code; with ``stations``, uniform over 0..255
    where the row visits the feature's station and 256 where not."""
    row = lax.broadcasted_iota(jnp.uint32, (fpad, n), 1)
    feat = lax.broadcasted_iota(jnp.uint32, (fpad, n), 0)
    seed = seed.astype(jnp.uint32)
    x = _mix(row * jnp.uint32(2654435761) + feat * jnp.uint32(40503) + seed)
    if not stations:
        return (x % jnp.uint32(NBIN + 1)).astype(jnp.int32)
    station = np.arange(fpad) * stations // fpad
    visit = _mix(row * jnp.uint32(2246822519) + seed
                 + jnp.asarray(station, jnp.uint32)[:, None]
                 * jnp.uint32(374761393))
    bar = jnp.asarray(visiting_shares(stations)[station] * 2.0 ** 32,
                      jnp.float32).astype(jnp.uint32)[:, None]
    return jnp.where(visit < bar, x % jnp.uint32(NBIN),
                     jnp.uint32(NBIN)).astype(jnp.int32)


def make_rows(key, n: int, trees: int, nslots: int):
    """Grad normal, hess uniform, nodes uniform over -1 and the level's
    slots."""
    kg, kh, kn = jax.random.split(key, 3)
    gh = jnp.stack([jax.random.normal(kg, (trees, n), jnp.float32),
                    0.25 * jax.random.uniform(kh, (trees, n), jnp.float32)],
                   axis=1)
    return gh, jax.random.randint(kn, (trees, n), -1, nslots, jnp.int32)


def lane(bins_t, gh, node, nslots: int, f: int, pack=None):
    """The lane-wide body whatever the rule says of the shape; with
    ``pack`` (a plan and its codes) the narrow features in one product."""
    trees, n = node.shape
    packed = {} if pack is None else {"pack": pack[0],
                                      "codes": jnp.asarray(pack[1])}
    return hk._hist_multi(bins_t, gh, node, NBIN, hk.default_block(n),
                          not hk.on_tpu(), CDT, nslots=nslots, features=f,
                          lanes=hk.call_lanes(trees, nslots), **packed)


def two_level(bins_t, gh, node, nslots: int, f: int):
    """The two-level body as ``level_hist`` calls it under the rule's
    width: a tree a call, ``max_channels`` channels a call."""
    trees, n = node.shape
    per = max(1, hk.max_channels(NBIN, f) // 2)
    return jnp.concatenate([
        hk._hist_multi(bins_t, gh[t], node[t] - lo, NBIN,
                       hk.default_block(n), not hk.on_tpu(), CDT,
                       nslots=min(per, nslots - lo))[:, :f]
        for t in range(trees) for lo in range(0, nslots, per)])


def masses(gh, node, nslots: int):
    """(trees * nslots * 2,) the sum of |w| (rounded as the kernel
    rounds it) over each channel's rows."""
    w = jnp.abs(gh.astype(hk.DEFAULT_COMPUTE_DTYPE).astype(jnp.float32))
    at = node[:, None, :] == jnp.arange(nslots, dtype=jnp.int32)[None, :, None]
    out = [jnp.sum(jnp.where(at[t][:, None, :], w[t][None], 0.0), axis=-1)
           for t in range(node.shape[0])]          # (nslots, 2) a tree
    return jnp.stack(out).reshape(-1)


def float64_hist(bins_t, gh, node, nslots: int, f: int):
    """numpy, float64, of weights rounded as the kernel rounds them."""
    bins_t, node = np.asarray(bins_t[:f]), np.asarray(node)
    w = np.asarray(gh.astype(hk.DEFAULT_COMPUTE_DTYPE).astype(jnp.float32),
                   np.float64)
    trees = node.shape[0]
    out = np.zeros((trees, nslots, 2, f, NBIN + 1))
    for t in range(trees):
        live = node[t] >= 0
        for j in range(f):
            flat = node[t][live] * (NBIN + 1) + bins_t[j][live]
            for c in range(2):
                out[t, :, c, j] = np.bincount(
                    flat, w[t, c][live], nslots * (NBIN + 1)).reshape(
                        nslots, NBIN + 1)
    return out[..., :NBIN].reshape(trees * nslots * 2, f, NBIN)


def worst(a, b, mass):
    """(largest |a - b| / mass of its channel, its index)."""
    rel = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)) \
        / np.maximum(np.asarray(mass, np.float64), 1e-30)[:, None, None]
    at = np.unravel_index(int(np.argmax(rel)), rel.shape)
    return float(rel[at]), tuple(int(v) for v in at)


def first_block(bins_t, gh, node, nslots, f, at, mass):
    """The first row block on which the bodies part at ``at``, by
    bisection: rows outside the half under test sit at node -1, so
    every shape (and program) stays."""
    n = node.shape[1]
    block = hk.default_block(n)
    rows = jnp.arange(n, dtype=jnp.int32)
    lo, hi = 0, -(-n // block)

    def parts(a, b):
        keep = (rows >= a * block) & (rows < b * block)
        nd = jnp.where(keep[None], node, -1)
        x = np.asarray(lane(bins_t, gh, nd, nslots, f))[at]
        y = np.asarray(two_level(bins_t, gh, nd, nslots, f))[at]
        return abs(float(x) - float(y)) > LIMIT * float(mass[at[0]])

    if not parts(lo, hi):
        return None         # only the whole sum parts: an order of adds
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (lo, mid) if parts(lo, mid) else (mid, hi)
    return lo


def compare(name: str, key, fpad, f, n, trees, nslots, emit,
            stations: int = 0) -> bool:
    bins_t = make_bins(key[-1], fpad, n, stations)
    gh, node = make_rows(key, n, trees, nslots)
    mass = np.asarray(masses(gh, node, nslots))
    a = np.asarray(lane(bins_t, gh, node, nslots, f))
    b = np.asarray(two_level(bins_t, gh, node, nslots, f))
    rel, at = worst(a, b, mass)
    line = {"check": "lane_vs_two_level", "shape": name, "rows": n,
            "features": f, "trees": trees, "slots": nslots,
            "channels": 2 * trees * nslots, "max_rel_to_mass": rel,
            "limit": LIMIT, "ok": rel <= LIMIT,
            "rows_at_no_node": int(np.asarray(jnp.sum(node < 0))),
            "entries_absent": float(np.asarray(jnp.mean(bins_t[:f] == NBIN))),
            "feature_chunks": -(-f // hk.lane_chunk(
                NBIN, f, hk._round_up(trees * hk.lane_rows(nslots), 128))),
            "equal_bitwise": bool(np.array_equal(a, b))}
    if rel > LIMIT:
        ch, feat, cls = at
        line["parts_at"] = {
            "tree": ch // (2 * nslots), "slot": ch % (2 * nslots) // 2,
            "grad_or_hess": ch % 2, "feature": feat, "bin": cls,
            "lane": (ch // (2 * nslots)) * hk.lane_rows(nslots)
            + ch % (2 * nslots),
            "lane_reads": float(a[at]), "two_level_reads": float(b[at]),
            "row_block": first_block(bins_t, gh, node, nslots, f, at, mass)}
    emit(line)
    # both against float64 on the first 2^20 rows
    m = min(SLICE_ROWS, n)
    sb, sg, sn = bins_t[:, :m], gh[:, :, :m], node[:, :m]
    want = float64_hist(sb, sg, sn, nslots, f)
    smass = np.asarray(masses(sg, sn, nslots))
    ok = line["ok"]
    for body, fn in (("lane", lane), ("two_level", two_level)):
        rel, at = worst(np.asarray(fn(sb, sg, sn, nslots, f)), want, smass)
        emit({"check": f"{body}_vs_float64", "shape": name, "rows": m,
              "trees": trees, "slots": nslots, "max_rel_to_mass": rel,
              "at": at, "ok": rel <= LIMIT})
        ok = ok and rel <= LIMIT
    return ok


def seconds(fn, *args) -> float:
    """The quicker of two calls after the compiling one, host clock
    around ``block_until_ready``."""
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return min(took)


def timing(name: str, key, fpad, f, n, trees, emit,
           stations: int = 0) -> None:
    """One tree at every width, then (``trees`` > 1) the forest's own
    levels; the two-level body as the rule's calls of its widest."""
    levels = [(1, w // 2) for w in WIDTHS]
    if trees > 1:
        levels += [(trees, s) for s in (1, 2, 4, 8, 16)]
    bins_t = make_bins(key[-1], fpad, n, stations)
    for nt, nslots in levels:
        gh, node = make_rows(key, n, nt, nslots)
        emit({"timing": name, "rows": n, "features": f, "trees": nt,
              "slots": nslots, "channels": 2 * nt * nslots,
              "lane_s": seconds(lane, bins_t, gh, node, nslots, f),
              "two_level_s": seconds(two_level, bins_t, gh, node, nslots, f),
              "two_level_calls": nt * -(-nslots // max(
                  1, hk.max_channels(NBIN, f) // 2))})


# the multi-class cell's columns: continuous ones first, then indicator
# columns set in these shares of the rows (under 1/256: every cut is 0
# and one code is reachable)
PACKED_WIDE = 10
PACKED_SHARES = np.geomspace(1e-4, 0.9, 44)
PACKED_WIDTHS = (2, 4, 8, 16)
CUT_ROWS = 1 << 16


def indicator_cuts(seed: int, f: int) -> np.ndarray:
    """Quantile cuts of a sample of the packed case's columns."""
    from rabit_tpu.learn.histogram import quantile_cuts

    rng = np.random.default_rng(seed)
    sample = rng.standard_normal((CUT_ROWS, f)).astype(np.float32)
    for j, share in enumerate(PACKED_SHARES[:f - PACKED_WIDE]):
        sample[:, PACKED_WIDE + j] = rng.random(CUT_ROWS) < share
    return quantile_cuts(sample, NBIN)


def width_pack(f: int, width: int):
    """A plan by hand: every column from ``PACKED_WIDE`` on holds
    ``width`` codes spread over the bins."""
    narrow = tuple(range(PACKED_WIDE, f))
    codes = np.tile(np.linspace(0, NBIN - 1, width).astype(np.int32),
                    (len(narrow), 1))
    return hk.PackPlan(narrow, width), codes


@functools.partial(jax.jit, static_argnames=("fpad", "n"))
def coded_bins(seed, table, count, fpad: int, n: int):
    """Bins a narrow feature of which holds its own codes and the absent
    one, uniformly (``table (fpad, w + 1)``, ``count`` of them a
    feature); a feature of ``count`` 0 holds 0..256 as ``make_bins``."""
    row = lax.broadcasted_iota(jnp.uint32, (fpad, n), 1)
    feat = lax.broadcasted_iota(jnp.uint32, (fpad, n), 0)
    x = _mix(row * jnp.uint32(2654435761) + feat * jnp.uint32(40503)
             + seed.astype(jnp.uint32))
    pick = x % jnp.maximum(count, 1).astype(jnp.uint32)[:, None]
    held = jnp.zeros((fpad, n), jnp.int32)
    for i in range(table.shape[1]):
        held = jnp.where(pick == i, table[:, i:i + 1], held)
    return jnp.where(count[:, None] > 0, held,
                     (x % jnp.uint32(NBIN + 1)).astype(jnp.int32))


def pack_bins(seed, pack, fpad: int, n: int):
    plan, codes = pack
    table = np.full((fpad, plan.width + 1), NBIN, np.int32)
    count = np.zeros((fpad,), np.int32)
    for k, j in enumerate(plan.narrow):
        mine = codes[k][codes[k] < NBIN]
        table[j, :len(mine)] = mine
        count[j] = len(mine) + 1                     # and the absent code
    return coded_bins(seed, jnp.asarray(table), jnp.asarray(count), fpad, n)


def compare_packed(name: str, key, pack, fpad, f, n, trees, nslots,
                   emit) -> bool:
    """The packed call against the unpacked one, bit for bit, and both
    against float64 on the first 2^20 rows."""
    bins_t = pack_bins(key[-1], pack, fpad, n)
    gh, node = make_rows(key, n, trees, nslots)
    a = np.asarray(lane(bins_t, gh, node, nslots, f, pack))
    b = np.asarray(lane(bins_t, gh, node, nslots, f))
    equal = bool(np.array_equal(a, b))
    line = {"check": "packed_vs_unpacked", "shape": name, "rows": n,
            "features": f, "narrow": len(pack[0].narrow),
            "width": pack[0].width, "trees": trees, "slots": nslots,
            "lanes": hk.call_lanes(trees, nslots), "equal_bitwise": equal,
            "ok": equal,
            "rows_at_no_node": int(np.asarray(jnp.sum(node < 0))),
            "entries_absent": float(np.asarray(jnp.mean(bins_t[:f] == NBIN)))}
    if not equal:
        ch, feat, cls = (int(v[0]) for v in np.nonzero(a != b))
        line["parts_at"] = {"channel": ch, "feature": feat, "bin": cls,
                            "packed_reads": float(a[ch, feat, cls]),
                            "unpacked_reads": float(b[ch, feat, cls])}
    emit(line)
    m = min(SLICE_ROWS, n)
    sb, sg, sn = bins_t[:, :m], gh[:, :, :m], node[:, :m]
    rel, at = worst(np.asarray(lane(sb, sg, sn, nslots, f, pack)),
                    float64_hist(sb, sg, sn, nslots, f),
                    np.asarray(masses(sg, sn, nslots)))
    emit({"check": "packed_vs_float64", "shape": name, "rows": m,
          "trees": trees, "slots": nslots, "max_rel_to_mass": rel, "at": at,
          "ok": rel <= LIMIT})
    return equal and rel <= LIMIT


def run_packed(shape, seed: int, timed: bool, emit,
               widths=PACKED_WIDTHS) -> bool:
    """The rule's own plan at the multi-class cell's shape, then plans of
    every width by hand; seconds a call of each beside the unpacked
    one."""
    fpad, f, n, trees, _ = shape
    key = jax.random.PRNGKey(seed)
    pack = hk.pack_plan(indicator_cuts(seed, f))
    held = [int(np.count_nonzero(c < NBIN)) for c in pack[1]]
    emit({"packed": "rule", "narrow": len(pack[0].narrow),
          "width": pack[0].width, "codes_a_feature": held,
          "narrow_codes": hk._NARROW_CODES})
    ok = True
    for nslots in (1, 16):                      # 128 and 256 lanes
        ok &= compare_packed("rule", key, pack, fpad, f, n, trees, nslots,
                             emit)
    ok &= compare_packed("rule-ragged", key, pack, fpad, f, n // 8 - 77,
                         trees, 8, emit)
    packs = {w: width_pack(f, w) for w in widths}
    for w, by_hand in packs.items():
        ok &= compare_packed(f"width-{w}", key, by_hand, fpad, f,
                             n // 8 - 77, trees, 16, emit)
    emit({"packed_equals_unpacked": bool(ok)})
    if ok and timed:
        bins_t = pack_bins(key[-1], pack, fpad, n)
        for nslots in (1, 16):
            gh, node = make_rows(key, n, trees, nslots)
            line = {"timing": "packed", "rows": n, "features": f,
                    "trees": trees, "slots": nslots,
                    "lanes": hk.call_lanes(trees, nslots),
                    "unpacked_s": seconds(lane, bins_t, gh, node, nslots, f),
                    "rule_s": seconds(lane, bins_t, gh, node, nslots, f,
                                      pack)}
            for w, by_hand in packs.items():
                line[f"width_{w}_s"] = seconds(lane, bins_t, gh, node,
                                               nslots, f, by_hand)
            emit(line)
    return ok


# (rows, ELL width, columns of NBIN bins, indicator columns): the sparse
# boosting cell's shard
SPARSE_SHAPE = (1 << 24, 32, 15, 4212)
SPARSE_WIDTHS = (1, 2, 4, 8, 16)


def sparse_cells(key, n: int, width: int, wide: int, narrow: int):
    """``(width, n)`` int32 cells on the device, -1 where a row has no
    entry (6% of the slots, and every slot of the last 1,000 rows: a
    ragged shard; every slot of the second tile of the kernel's, and
    none of the third: uneven tiles): slot ``j < wide`` an entry of
    column ``j``, any of its NBIN bins; the others the one occupied cell
    of an indicator column drawn by a power law."""
    from rabit_tpu.ops.sparse_hist_kernel import ROW_TILE

    ks = jax.random.split(key, 4)
    first = jnp.arange(wide, dtype=jnp.int32)[:, None] * NBIN \
        + jax.random.randint(ks[0], (wide, n), 0, NBIN)
    u = jax.random.uniform(ks[1], (width - wide, n))
    rest = wide * NBIN + 2 * jnp.minimum(
        (narrow * u ** 3).astype(jnp.int32), narrow - 1) + 1
    cells = jnp.concatenate([first, rest])
    held = jax.random.uniform(ks[2], (width, n)) > 0.06
    held &= (jnp.arange(n) < n - 1000)[None, :]
    tile = (jnp.arange(n) // ROW_TILE)[None, :]
    held = (held | (tile == 2)) & (tile != 1)
    return jnp.where(held, cells, -1)


def run_sparse(shape, seed: int, timed: bool, emit,
               widths=SPARSE_WIDTHS) -> bool:
    from rabit_tpu.learn import histogram
    from rabit_tpu.ops import sparse_hist_kernel as sk
    from rabit_tpu.ops.sparse_linear_kernel import place

    n, width, wide, narrow = shape
    cells = wide * NBIN + 2 * narrow + 1
    tiles = n // sk.ROW_TILE
    key = jax.random.PRNGKey(seed)
    cells_t = sparse_cells(key, n, width, wide, narrow)
    cap = sk.capacity(width, cells)
    packed = jnp.zeros((tiles * cap // sk.SUB, sk.SUB), jnp.int32)
    fb = jnp.zeros((tiles * cap // sk.STEP, sk.SUBS), jnp.int32)
    t0 = time.perf_counter()
    real = 0
    for t in range(0, tiles, sk.GROUP_TILES):
        g = min(sk.GROUP_TILES, tiles - t)
        part, part_fb, count = histogram._bucket_program(
            n, width, g, cells)(cells_t, np.int32(t * sk.ROW_TILE))
        packed = place(packed, part, np.int32(t * cap // sk.SUB))
        fb = place(fb, part_fb, np.int32(t * cap // sk.STEP))
        real += int(count)
    jax.block_until_ready((packed, fb))
    took = time.perf_counter() - t0
    nblk = sk.num_blocks(cells)
    live_sub = np.asarray(fb) < nblk
    live = live_sub[:, 0].reshape(tiles, -1)
    worked = int(sk.steps_worked(fb, cells=cells)) * sk.STEP
    assert worked == int(live.sum()) * sk.STEP
    emit({"sparse": "bucketed", "rows": n, "entries": real,
          "slots": tiles * cap, "padding": tiles * cap / real - 1.0,
          "worked": worked, "worked_share": worked / (tiles * cap),
          "worked_over_entries": worked / real,
          "steps_a_tile": live.shape[1],
          "live_steps_a_tile": sorted(set(live.sum(axis=1).tolist())),
          "live_sub_chunks": int(live_sub.sum()),
          "seconds_with_compile": took})
    # the marks taken out, as before PR 52: every step is then worked
    unmarked = jnp.where(fb >= nblk, 0, fb)
    ok = True
    kernel = jax.jit(functools.partial(
        sk.hist_sparse, tiles=tiles, cells=cells,
        interpret=jax.default_backend() != "tpu"),
        static_argnames=("nslots",))
    xla = jax.jit(sk.hist_sparse_xla, static_argnums=(3, 4))
    # the kernel's operand, as float32: by reduce_precision, because the
    # chip's compiler drops a cast to bfloat16 and back (excess precision
    # is allowed it) and the reference would then add unrounded weights,
    # 2^-9 of each apart (found on the chip, PR 49: 3e-5 of a channel's
    # mass at every width, the same with and without the compensated join)
    grid = jnp.finfo(hk.DEFAULT_COMPUTE_DTYPE)
    rounded = jax.jit(lambda gh: jax.lax.reduce_precision(
        gh, grid.nexp, grid.nmant))
    head = min(n, SLICE_ROWS) // sk.ROW_TILE * sk.ROW_TILE
    for nslots in widths:
        k1, k2 = jax.random.split(jax.random.fold_in(key, nslots))
        gh = jax.random.normal(k1, (2, n), jnp.float32)
        slot = jax.random.randint(k2, (n,), -1, nslots)   # -1: at no node
        got = kernel(packed, fb, gh, slot, nslots=nslots)
        mass = np.asarray(masses(gh[None], slot[None], nslots)).reshape(
            nslots, 1, 2)
        want = xla(cells_t, rounded(gh), slot, nslots, cells)
        rel = np.abs(np.asarray(got, np.float64) - np.asarray(
            want, np.float64)) / np.maximum(mass, 1e-30)
        at = np.unravel_index(int(rel.argmax()), rel.shape)
        line = {"check": "sparse_vs_segment_sum", "slots": nslots,
                "worst": float(rel[at]), "at": [int(v) for v in at],
                "ok": bool(rel[at] <= LIMIT),
                "skip_equal": bool(jnp.array_equal(got, kernel(
                    packed, unmarked, gh, slot, nslots=nslots)))}
        # float64 on the first rows: the others masked to node -1
        part = jnp.where(jnp.arange(n) < head, slot, -1)
        got = np.asarray(kernel(packed, fb, gh, part, nslots=nslots),
                         np.float64)
        c, w, s = (np.asarray(a[..., :head]) for a in (
            cells_t, rounded(gh), part))
        want = np.zeros((nslots, got.shape[1], 2))
        live = (c >= 0) & (s >= 0)[None, :]
        flat = (s[None, :] * got.shape[1] + c)[live]
        for ch in range(2):
            want[:, :, ch] = np.bincount(
                flat, np.broadcast_to(w[ch].astype(np.float64),
                                      c.shape)[live],
                nslots * got.shape[1]).reshape(nslots, -1)
        mass = np.asarray(masses(gh[None], part[None], nslots)).reshape(
            nslots, 1, 2)
        rel = float((np.abs(got - want) / np.maximum(mass, 1e-30)).max())
        line.update(float64_worst=rel, float64_ok=bool(rel <= LIMIT))
        ok &= line["ok"] and line["float64_ok"] and line["skip_equal"]
        emit(line)
        if timed:
            emit({"timing": "hist_sparse", "slots": nslots,
                  "channels": 2 * nslots,
                  "seconds": seconds(functools.partial(
                      kernel, nslots=nslots), packed, fb, gh, slot),
                  "seconds_every_step": seconds(functools.partial(
                      kernel, nslots=nslots), packed, unmarked, gh, slot),
                  "worked_share": worked / (tiles * cap),
                  "segment_sum_seconds": seconds(
                      lambda *a: xla(*a, nslots, cells), cells_t, gh, slot)
                  if nslots == widths[-1] else None})
    emit({"sparse_agrees": bool(ok)})
    return ok


def run(shapes: dict, seed: int, timed: bool, emit) -> bool:
    key = jax.random.PRNGKey(seed)
    ok = True
    for name, (fpad, f, n, trees, stations) in shapes.items():
        # a wide shard's two lane-wide levels; one tree's widest; a
        # forest's narrowest, and two more
        for nslots in ((8, 16) if stations else (16,) if trees == 1
                       else (1, 8, 16)):
            ok &= compare(name, key, fpad, f, n, trees, nslots, emit,
                          stations)
        # ragged: the last block reads past the rows
        ok &= compare(name + "-ragged", key, fpad, f, n // 8 - 77, trees, 8,
                      emit, stations)
    emit({"bodies_agree": bool(ok)})
    if ok and timed:
        for name, (fpad, f, n, trees, stations) in shapes.items():
            timing(name, key, fpad, f, n, trees, emit, stations)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="higgs,covtype,bosch")
    ap.add_argument("--seed", type=int, default=43)
    ap.add_argument("--no-timing", action="store_true")
    ap.add_argument("--cases", default="bodies,packed",
                    help="bodies: lane-wide against two-level; packed: "
                    "the lane-wide body with a pack plan against without; "
                    "sparse: the sparse-row kernel against segment_sum")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("hist_kernel_check: no TPU here; the chip is the judge",
              file=sys.stderr)
        return 2
    out_dir = os.path.join("chiprun_out", "hist_kernel_check")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "report.jsonl"), "a") as report:
        def emit(line: dict) -> None:
            text = json.dumps(line)
            print(text, flush=True)
            report.write(text + "\n")
            report.flush()

        emit({"device": device.device_kind, "seed": args.seed,
              "crossing": hk._LANE_CROSSING, "cases": args.cases})
        ok, cases = True, args.cases.split(",")
        if "sparse" in cases:
            ok &= run_sparse(SPARSE_SHAPE, args.seed, not args.no_timing,
                             emit)
        if "packed" in cases:
            ok &= run_packed(SHAPES["covtype"], args.seed,
                             not args.no_timing, emit)
        if "bodies" in cases:
            ok &= run({name: SHAPES[name]
                       for name in args.shapes.split(",")},
                      args.seed, not args.no_timing, emit)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
