"""Bytes and operations one feature of the round's binning needs
(``gbdt_rebin``: the resident float values binned by the round's cuts
into the staged int32 array, a feature at a time, so a round holds as
many such calls as the rows have features), from its shapes.

What the algorithm needs, whatever implements it: every value read once
(4 bytes) and its bin written once (4 bytes as staged), 8nf bytes a
round, and a bisection of the feature's cuts a value, log2(nbin)
compares.  Comparing every value with every cut is the implementation's
choice and is not counted."""
import math


def cost(shape: dict) -> dict:
    n = shape["rows"]
    return {"ops": float(n) * math.log2(shape["nbin"]),
            "bytes": 8.0 * n,
            "ops_dtype": shape["ops_dtype"]}
