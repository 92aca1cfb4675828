"""Operations and bytes one call of the gradient kernel needs
(``rabit_tpu.ops.sparse_linear_kernel.lbfgs_grad``: ``X^T g`` over the
whole shard, once an outer iteration), from its shapes.

What the algorithm needs, not what the implementation spends (see
``lbfgs_margin.py``): a non-zero is one multiply and one add.  A call
reads every non-zero once (int32 index, float32 value), reads the
loss's derivative of every row once and writes every gradient cell."""


def cost(shape: dict) -> dict:
    nnz = float(shape["rows"]) * shape["nnz_per_row"]
    return {"ops": 2.0 * nnz,
            "bytes": 8.0 * nnz + 4.0 * shape["rows"] + 4.0 * shape["features"],
            "ops_dtype": shape["ops_dtype"]}
