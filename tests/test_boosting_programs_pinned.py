"""The dense boosting jobs' device programs, pinned: what ``train``
traces for HIGGS-, Bosch- and Covertype-shaped rows (every level, scan,
row-move, gradient and leaf program of the job) hashes to what the
parent of PR 49 traced.  That PR split ``_DeviceShard._programs`` into
methods a second shard class overrides; the dense cells must run the
programs they ran before (ISSUE 49: "the dense cells' programs do not
change").

A program is taken as its jaxpr (the Pallas kernels' bodies included),
source positions and object addresses stripped, so a line moved changes
nothing and an operation changed does.  A PR that means to change one of
these programs records the new hashes:

    JAX_PLATFORMS=cpu python tests/test_boosting_programs_pinned.py

prints the table to paste below.
"""
from __future__ import annotations

import hashlib
import re

import numpy as np
import pytest

N = 8192


def _rows(case: str):
    rng = np.random.default_rng(49)
    if case == "higgs":                     # 28 floats, nothing absent
        x = rng.standard_normal((N, 28)).astype(np.float32)
        return x, (rng.random(N) < 0.5).astype(np.float32), {}
    if case == "bosch":                     # 968 columns, 81% absent
        x = rng.standard_normal((N, 968)).astype(np.float32)
        x[rng.random(x.shape) < 0.81] = np.nan
        return x, (rng.random(N) < 0.01).astype(np.float32), {}
    # covtype: 10 gridded columns, 44 indicators, 7 classes
    x = np.concatenate([
        rng.integers(0, 200, (N, 10)).astype(np.float32),
        (rng.random((N, 44)) < 0.1).astype(np.float32)], axis=1)
    return x, rng.integers(0, 7, N).astype(np.float32), {
        "loss": "softprob", "num_class": 7}


class _Built(Exception):
    """Raised once the job's programs are all traced."""


def traced_programs(case: str, monkeypatch) -> dict:
    """name -> sha256 of the stripped jaxpr of every program the job of
    ``case`` builds before its first round, in the order built."""
    import jax

    import rabit_tpu
    from rabit_tpu.learn import boosting, histogram

    seen: dict = {}
    jit = jax.jit

    class Recorder:
        def __init__(self, fn, **kw):
            self.fn, self.kw = fn, kw

        def __call__(self, *a, **kw):
            return jit(self.fn, **self.kw)(*a, **kw)

        def lower(self, *shapes):
            text = str(jax.make_jaxpr(self.fn)(*shapes))
            text = re.sub(r" at [^\s:]+:\d+", "", text)
            text = re.sub(r"0x[0-9a-f]+", "0x", text)
            name = getattr(self.fn, "__name__", "fn")
            seen[f"{name}.{sum(k.startswith(name + '.') for k in seen)}"] = \
                hashlib.sha256(text.encode()).hexdigest()[:16]
            return self

        def compile(self):
            return None

    def programs(shard):
        # only the job's own programs: the staging's run for real
        with monkeypatch.context() as m:
            m.setattr(jax, "jit", lambda fn, **k: Recorder(fn, **k))
            built(shard)
        raise _Built

    built = boosting._DeviceShard._programs
    values, labels, kw = _rows(case)
    monkeypatch.setattr(boosting, "on_tpu", lambda: True)
    monkeypatch.setattr(histogram, "on_tpu", lambda: True)
    monkeypatch.setattr(boosting._DeviceShard, "_programs", programs)
    monkeypatch.setattr(boosting, "_PROGRAMS", {})
    rabit_tpu.init([], rabit_engine="empty")
    try:
        with pytest.raises(_Built):
            boosting.train(values, labels, num_round=1, max_depth=6,
                           nbin=256, **kw)
    finally:
        rabit_tpu.finalize()
    return seen


# what the parent of PR 49 (commit 235d131) traces, by this file's recipe
PINNED: dict = {
    "higgs": {
        "gbdt_grad.0": "16e9d54edd0104ae",
        "gbdt_level.0": "1c5578359fe93a09",
        "gbdt_level.1": "3cb7277e36c9b1c9",
        "gbdt_level.2": "98e37d2617de6a12",
        "gbdt_level.3": "63e43b0019687679",
        "gbdt_level.4": "eb4dc45cd04a8464",
        "gbdt_partition.0": "13f049a7caa5595c",
        "gbdt_partition.1": "71236833b5066e63",
        "gbdt_partition.2": "d1c65db7b5de4e93",
        "gbdt_partition.3": "e2b782d0ec550c42",
        "gbdt_partition.4": "034b4b72723ebfaa",
        "gbdt_partition.5": "360fb5173347556e",
        "gbdt_leaf.0": "f98759b90a19cb38",
        "gbdt_scan.0": "82a68b872935b589",
        "gbdt_scan.1": "0fe24192d8551678",
        "gbdt_scan.2": "46db8dae1f2dd19b",
        "gbdt_scan.3": "984505ee87a638b6",
        "gbdt_scan.4": "fbdbd40b4032d5cb",
        "gbdt_scan.5": "12ba39cf90c05fb1",
    },
    "bosch": {
        "gbdt_grad.0": "16e9d54edd0104ae",
        "gbdt_level.0": "8411f3a0c9365ecd",
        "gbdt_level.1": "e5bb7d58bcb4f84a",
        "gbdt_level.2": "55ce1d1ac644ac9e",
        "gbdt_level.3": "39e471b892fb51a9",
        "gbdt_level.4": "cb174fe99deb0e11",
        "gbdt_partition.0": "db5b58825a180106",
        "gbdt_partition.1": "4e3ff918d38395ab",
        "gbdt_partition.2": "8c925103da350c72",
        "gbdt_partition.3": "ab2bec62a7acc5d1",
        "gbdt_partition.4": "ec597586722ee25d",
        "gbdt_partition.5": "818e79ba122537c1",
        "gbdt_leaf.0": "f98759b90a19cb38",
        "gbdt_scan.0": "c94e73ef62743270",
        "gbdt_scan.1": "4c9b07ad4fd1ea0d",
        "gbdt_scan.2": "da4c090021591982",
        "gbdt_scan.3": "d05d587a7f9472a5",
        "gbdt_scan.4": "ef5b382887e626b0",
        "gbdt_scan.5": "9bd2918b18c1ae0e",
    },
    "covtype": {
        "gbdt_grad_softmax.0": "0084c97bd3f545dc",
        "gbdt_level.0": "555d2f65f4956a54",
        "gbdt_level.1": "58cbcdbb44e9fc6d",
        "gbdt_level.2": "a7329c68c046b220",
        "gbdt_level.3": "6671c0a41076c05f",
        "gbdt_level.4": "a2f74f5e22a0be9b",
        "gbdt_partition.0": "bab33520d605dd64",
        "gbdt_partition.1": "c6a66613347ab822",
        "gbdt_partition.2": "7dc64763648b44dc",
        "gbdt_partition.3": "fab4c112cefec4d9",
        "gbdt_partition.4": "9c4fa2ea9e60c6d0",
        "gbdt_partition.5": "23f824a9d66c7be0",
        "gbdt_leaf.0": "e303c1becc140c73",
        "gbdt_scan.0": "749ebbdcbc1584be",
        "gbdt_scan.1": "ee97b55dfe163c87",
        "gbdt_scan.2": "33887afceb135db4",
        "gbdt_scan.3": "05599d39471816d8",
        "gbdt_scan.4": "42e3efe470791591",
        "gbdt_scan.5": "f9a90ca8bed136f3",
    },
}


@pytest.mark.parametrize("case", ["higgs", "bosch", "covtype"])
def test_dense_job_traces_the_programs_it_traced_before(case, monkeypatch):
    got = traced_programs(case, monkeypatch)
    assert {k.split(".")[0] for k in got} >= {
        "gbdt_level", "gbdt_scan", "gbdt_partition"}, sorted(got)
    assert got == PINNED[case], (
        "the dense job's programs changed; if meant, record:\n"
        f'    "{case}": {got!r},')


if __name__ == "__main__":
    mp = pytest.MonkeyPatch()
    for case in ("higgs", "bosch", "covtype"):
        with mp.context() as m:
            print(f'    "{case}": {traced_programs(case, m)!r},')
