"""Operations and bytes one call of the fused level-histogram kernel
needs (``rabit_tpu.ops.histogram_kernel.hist_fused_multi`` as a boosting
round calls it, once a level), from its shapes.

What the algorithm needs, not what the implementation spends: a row
adds its grad and its hess into one bin of each feature of its own
node's histogram, 2 * features adds, whatever the level's width; the
kernel's one-hot products against every node slot (32,768 FLOP a row,
feature group and channel) are its choice and are not counted.  A call
reads every bin once (int32, as staged, without the padding features),
the node, grad and hess of every row (12 bytes), and writes the
histograms of its level; the output is that of the mean level of a
round, so that six calls add up to a round's."""


def cost(shape: dict) -> dict:
    n, f, nbin = shape["rows"], shape["features"], shape["nbin"]
    depth = shape["max_depth"]
    slots = ((1 << depth) - 1) / depth          # 1 + 2 + ... a round
    return {"ops": float(n) * 2 * f,
            "bytes": float(n) * (4 * f + 12) + slots * f * nbin * 2 * 4,
            "ops_dtype": shape["ops_dtype"]}
