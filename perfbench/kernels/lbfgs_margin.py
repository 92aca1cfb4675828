"""Operations and bytes one call of the margin kernel needs
(``rabit_tpu.ops.sparse_linear_kernel.lbfgs_margin``: ``X w`` over the
whole shard, once a line-search trial), from its shapes.

What the algorithm needs, not what the implementation spends: a
non-zero is one multiply and one add; the kernel's one-hot products
(some 260,000 FLOP a slot on the MXU) and its padding slots are its
choice and are not counted.  A call reads every non-zero once (int32
index, float32 value), reads every weight once and writes a margin a
row."""


def cost(shape: dict) -> dict:
    nnz = float(shape["rows"]) * shape["nnz_per_row"]
    return {"ops": 2.0 * nnz,
            "bytes": 8.0 * nnz + 4.0 * shape["rows"] + 4.0 * shape["features"],
            "ops_dtype": shape["ops_dtype"]}
