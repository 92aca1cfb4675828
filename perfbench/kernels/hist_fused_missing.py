"""Operations and bytes one call of the fused level-histogram kernel
needs on rows with absent entries (``rabit_tpu.ops.histogram_kernel.
hist_fused_multi`` as a boosting round calls it, one or more calls a
level), from its shapes.

What the algorithm needs, whatever implements it: a row adds its grad
and its hess into one bin of each feature **it has**, 2 adds a present
entry (``present_entries``, counted from the rows by the adapter, not
from the program), whatever the level's width; an absent entry is added
nowhere.  The kernel's one-hot products of every entry, present or not,
against every node slot are its choice and are not counted.  A call
reads the staged bins once (int32, as staged: the absent entries' codes
are read too, the layout holds them), the node, grad and hess of every
row (12 bytes), and writes histograms; the output is that of the mean
level of a round, as ``hist_fused_multi.py`` has it."""


def cost(shape: dict) -> dict:
    n, f, nbin = shape["rows"], shape["features"], shape["nbin"]
    depth = shape["max_depth"]
    slots = ((1 << depth) - 1) / depth          # 1 + 2 + ... a round
    return {"ops": 2.0 * shape["present_entries"],
            "bytes": float(n) * (4 * f + 12) + slots * f * nbin * 2 * 4,
            "ops_dtype": shape["ops_dtype"]}
