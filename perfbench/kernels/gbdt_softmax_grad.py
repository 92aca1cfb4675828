"""Bytes and operations one round's softmax gradient needs
(``gbdt_grad_softmax``: from the ``(K, n)`` margins and the labels to
the ``(K, 2, n)`` grad and hess of every class, once a round), from its
shapes.

What the algorithm needs, whatever implements it: a row's K margins and
its label read once and its K (grad, hess) pairs written once, (3K + 1)
* 4 bytes; K exponentials, a maximum and a sum over K, a division and
four more operations a class.  A second pass over the margins for the
maximum or the sum, or a copy of the result into another layout, is the
implementation's choice and is not counted.

The reader (``readers.roofline``) multiplies a call's cost by the
number of device operations its pattern matched, and the program is not
one operation: the v5e's compiler makes it ``PASSES`` a round (PR 42's
traced run: the maximum over the classes, two passes over the margins,
the result, and its copy into the ``(K, 2, n)`` layout; each runs once
a round).  ``cost`` is therefore a round's need over ``PASSES``, a
share an operation, so that the operations of a round add up to the
round's need and the share read is the need over the whole program's
time.  A compiler that makes another number of operations of it scales
the share by ``PASSES`` over that number: ``gbdt_mc_grad_per_step_s``
beside it is the program's time a round whatever their number."""

PASSES = 5


def round_cost(shape: dict) -> dict:
    """What a round's softmax gradient needs."""
    n, k = shape["rows"], shape["num_class"]
    return {"ops": float(n) * 8 * k,
            "bytes": float(n) * (3 * k + 1) * 4,
            "ops_dtype": shape["ops_dtype"]}


def cost(shape: dict) -> dict:
    whole = round_cost(shape)
    return {"ops": whole["ops"] / PASSES, "bytes": whole["bytes"] / PASSES,
            "ops_dtype": whole["ops_dtype"]}
