"""Operations and bytes one call of the sparse level-histogram kernel
needs (``rabit_tpu.ops.sparse_hist_kernel.hist_sparse`` as a boosting
round on sparse rows calls it, once a level), from its shapes.

What the algorithm needs, whatever implements it: a row adds its grad
and its hess into the cell of each entry **it has**, 2 adds a present
entry (``present_entries``, counted from the rows by the adapter, not
from the program), whatever the level's width; an absent entry does not
exist.  The kernel's two one-hot products a slot (the pick of the row's
weights, the add into the cell's block) are its choice and are not
counted, nor are the slots its bucketing pads.  A call reads the
entries once as staged (int32 cells in ELL form, the row's unused slots
too: the layout holds them), the node, grad and hess of every row (12
bytes), and writes histograms on the flat bin space; the output is that
of the mean level of a round, as ``hist_fused_multi.py`` has it."""


def cost(shape: dict) -> dict:
    n, depth = shape["rows"], shape["max_depth"]
    slots = ((1 << depth) - 1) / depth          # 1 + 2 + ... a round
    return {"ops": 2.0 * shape["present_entries"],
            "bytes": float(n) * (4 * shape["ell_width"] + 12)
            + slots * shape["flat_bins"] * 2 * 4,
            "ops_dtype": shape["ops_dtype"]}
