"""Bytes and operations one feature of the weighted quantile sketch
needs (``rabit_tpu.learn.histogram.sketch_summary`` inside the program
``gbdt_sketch``, which orders one feature at a time: a round holds as
many such calls as the rows have features), from its shapes.

What the algorithm needs, whatever implements it: every value of the
feature read once with its weight, the weights a row are read once for
all features (4 bytes a row spread over them), one add a value into a
running weight, and the summary's entries written (value, rmin, rmax).
A sort's passes over the pairs, the prefix sums and the bisections are
the implementation's choice and are not counted: the share will read
low, as the histogram kernel's does."""


def cost(shape: dict) -> dict:
    n, f = shape["rows"], shape["features"]
    return {"ops": float(n),
            "bytes": float(n) * (4 * f + 4) / f
            + shape["summary_entries"] * 3 * 4,
            "ops_dtype": shape["ops_dtype"]}
