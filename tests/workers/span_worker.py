"""Worker: a few commits, then what the program's own spans saw.

Writes ``path_stats`` and the ``span`` events of ``Engine.events()`` to
``$SPAN_OUT/rank<r>.json`` for tests/test_program_spans.py.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import rabit_tpu
from rabit_tpu import engine as _em


def main() -> None:
    commits = int(sys.argv[1])
    rabit_tpu.init()
    rank = rabit_tpu.get_rank()
    version, _model = rabit_tpu.load_checkpoint()
    assert version == 0
    for it in range(commits):
        a = np.full(16, rank + 1.0, np.float32)
        rabit_tpu.allreduce(a, rabit_tpu.SUM)
        rabit_tpu.checkpoint({"it": it})
    eng = _em.get_engine()
    out = {"path_stats": eng.path_stats,
           "spans": [e for e in eng.events() if e["name"] == "span"]}
    with open(os.path.join(os.environ["SPAN_OUT"], f"rank{rank}.json"),
              "w") as f:
        json.dump(out, f)
    rabit_tpu.finalize()


if __name__ == "__main__":
    main()
