"""Operations and bytes one call of the fused ELL stats kernel needs
(``rabit_tpu.ops.kmeans_kernel.kmeans_ell_stats_fused``), from its
shapes.

What the algorithm needs on SPARSE rows: a similarity of nnz stored
values with k centroids (2*nnz*k) and nnz adds into the cluster's sum.
The kernel rebuilds dense rows in VMEM (compares on the VPU, then dense
matmuls): that is its choice, not the algorithm's, and no VPU peak is
published to hold it against.  So by the published peaks the kernel is
bound by reading nnz*(4+4) bytes a row, and its roofline share says how
far the rebuild keeps it from that bound."""


def cost(shape: dict) -> dict:
    n, d, k, nnz = shape["rows"], shape["dim_staged"], shape["k"], shape["nnz"]
    return {"ops": float(n) * (2 * nnz * k + nnz),
            "bytes": float(n * nnz * 8 + n * 4 + 2 * k * (d + 1) * 4),
            "ops_dtype": shape["ops_dtype"]}
