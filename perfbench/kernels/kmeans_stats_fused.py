"""Operations and bytes one call of the dense fused stats kernel needs
(``rabit_tpu.ops.kmeans_kernel.kmeans_stats_fused``), from its shapes.

What the algorithm needs, not what the implementation spends: per row
a similarity product with k centroids (2*d*k) and one add of the row
into its cluster's sum (d) — the kernel does that add as a second
one-hot matmul, which is its choice and is not counted.  Every stored
row is read once, the validity vector once, centroids in, stats out."""


def cost(shape: dict) -> dict:
    n, d, k = shape["rows"], shape["dim_staged"], shape["k"]
    return {"ops": float(n) * (2 * d * k + d),
            "bytes": float(n * d * shape["row_itemsize"] + n * 4
                           + 2 * k * (d + 1) * 4),
            "ops_dtype": shape["ops_dtype"]}
