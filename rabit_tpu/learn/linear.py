"""Linear / logistic regression on the L-BFGS solver.

Equivalent of reference: rabit-learn/linear/{linear.h,linear.cc}.  The
objective's Eval/CalcGrad — the FLOP-heavy part the reference spreads over
OpenMP threads with per-row sparse loops (linear.cc:150-201) — are here
two compiled programs over the shard, which stays on the device for the
whole job (:class:`DeviceShard`): the margins ``X w`` with the loss summed
a tile of rows, and the gradient ``X^T g``.  On the chip both products are
the blocked one-hot kernels of ``rabit_tpu/ops/sparse_linear_kernel.py``;
off it the same staged arrays go through XLA's gather and scatter.  Model
files keep the reference's two on-disk encodings ("binf" binary and
"bs64" base64 text for text-only channels, linear.cc:76-122).
"""
from __future__ import annotations

import functools
import struct
import sys
from typing import BinaryIO

import numpy as np

import rabit_tpu
from rabit_tpu.learn.data import SparseMat, fetch, load_libsvm
from rabit_tpu.learn.lbfgs import LBFGSSolver, ObjFunction
from rabit_tpu.obs import program
from rabit_tpu.ops import MAX, on_tpu
from rabit_tpu.ops import sparse_linear_kernel as sk
from rabit_tpu.utils import compile_cache
from rabit_tpu.utils.checks import check
from rabit_tpu.utils.serial import Base64InStream, Base64OutStream

LOSS_LINEAR = 0
LOSS_LOGISTIC = 1

# on-disk param block: base_score, num_feature, loss_type + reserved pad
# (layout of reference ModelParam, linear.h:18-33; fixed little-endian here)
_PARAM_FMT = "<fQi64x"


class LinearModel:
    """Weights + param block (reference: LinearModel, linear.h:17-130).

    ``weight`` has ``num_feature + 1`` entries; the last is the bias.
    """

    def __init__(self) -> None:
        self.base_score = 0.5
        self.num_feature = 0
        self.loss_type = LOSS_LOGISTIC
        self.weight: np.ndarray | None = None

    # -- config (reference: ModelParam::SetParam, linear.h:45-62) ----------
    def set_param(self, name: str, val: str) -> None:
        if name == "base_score":
            self.base_score = float(val)
        elif name == "num_feature":
            self.num_feature = int(val)
        elif name == "objective":
            if val == "linear":
                self.loss_type = LOSS_LINEAR
            elif val == "logistic":
                self.loss_type = LOSS_LOGISTIC
            else:
                check(False, "unknown objective type %s", val)

    def init_base_score(self) -> None:
        """Fold base_score through the logit once at init
        (reference: linear.h:35-39)."""
        check(0.0 < self.base_score < 1.0,
              "base_score must be in (0,1) for logistic loss")
        self.base_score = -float(np.log(1.0 / self.base_score - 1.0))

    # -- inference ---------------------------------------------------------
    def margin(self, data: SparseMat, weight: np.ndarray | None = None
               ) -> np.ndarray:
        w = self.weight if weight is None else weight
        nf = self.num_feature
        out = np.full(data.num_row, self.base_score + w[nf], np.float64)
        for i in range(data.num_row):
            fi, fv = data.row(i)
            keep = fi < nf
            out[i] += w[fi[keep]] @ fv[keep]
        return out

    def predict(self, data: SparseMat) -> np.ndarray:
        m = self.margin(data)
        if self.loss_type == LOSS_LOGISTIC:
            return 1.0 / (1.0 + np.exp(-m))
        return m

    # -- model IO (reference: LinearModel::Load/Save, linear.h:114-126;
    #    headers written by linear.cc:76-122) ------------------------------
    def _save_stream(self, write) -> None:
        write(struct.pack(_PARAM_FMT, self.base_score, self.num_feature,
                          self.loss_type))
        write(np.asarray(self.weight, np.float32).tobytes())

    def _load_stream(self, read) -> None:
        hdr = read(struct.calcsize(_PARAM_FMT))
        self.base_score, self.num_feature, self.loss_type = struct.unpack(
            _PARAM_FMT, hdr)
        raw = read(4 * (self.num_feature + 1))
        self.weight = np.frombuffer(raw, np.float32).astype(np.float64)

    def save(self, fname: str, base64_: bool = False) -> None:
        use_stdout = fname == "stdout"
        fp: BinaryIO = sys.stdout.buffer if use_stdout else open(fname, "wb")
        try:
            if base64_ or use_stdout:
                fp.write(b"bs64\t")
                out = Base64OutStream(fp)
                self._save_stream(out.write)
                out.finish()
                fp.write(b"\n")
            else:
                fp.write(b"binf")
                self._save_stream(fp.write)
        finally:
            if not use_stdout:
                fp.close()

    def load(self, fname: str) -> None:
        with open(fname, "rb") as fp:
            header = fp.read(4)
            if header == b"bs64":
                fp.read(1)  # tab
                self._load_stream(Base64InStream(fp).read)
            elif header == b"binf":
                self._load_stream(fp.read)
            else:
                check(False, "invalid model file")


def _row_loss(loss_type: int, m, labels):
    import jax.numpy as jnp

    if loss_type == LOSS_LOGISTIC:
        # stable nlogprob (reference: MarginToLoss, linear.h:72-86)
        nlogprob = jnp.where(m > 0.0, jnp.log1p(jnp.exp(-m)),
                             -m + jnp.log1p(jnp.exp(m)))
        return labels * nlogprob + (1.0 - labels) * (m + nlogprob)
    return 0.5 * (m - labels) ** 2


def _is_for(kept, w: np.ndarray, base: np.float32) -> bool:
    """Whether ``kept`` (``(w, base, what the device holds for them)`` or
    None) was computed at these weights: by value, so that neither a copy
    nor a weight changed in place is mistaken."""
    return (kept is not None and kept[1] == base
            and np.array_equal(kept[0], w))


class DeviceShard:
    """One rank's rows on the device for the whole job: the bucketed
    non-zeros (:mod:`rabit_tpu.ops.sparse_linear_kernel`), labels and
    the valid mask, and the two programs of an iteration, compiled
    before the first.  ``evaluate`` and ``gradient`` only dispatch."""

    def __init__(self, packed, val, fb, labels, valid, tiles: int,
                 num_feature: int, nnz: int, loss_type: int) -> None:
        self.arrays = (packed, val, fb, labels, valid)
        self.tiles, self.num_feature = tiles, num_feature
        self.nnz, self.nnz_padded = nnz, int(packed.size)
        with program.span("stage.compile"):
            self._evaluate, self._gradient = self._programs(loss_type)

    def _programs(self, loss_type: int):
        import jax
        import jax.numpy as jnp

        tiles, nf = self.tiles, self.num_feature
        # the kernels on the chip; XLA's gather and scatter off it (on a
        # v5e they take 4.96 s and 4.71 s a pass at a million weights and
        # 654M non-zeros, the kernels 0.77 s and 0.79 s: PERF.md section 5)
        product, product_t = (
            (functools.partial(sk.lbfgs_margin, interpret=False),
             functools.partial(sk.lbfgs_grad, interpret=False))
            if on_tpu() else (sk.margins_xla, sk.gradient_xla))

        def lbfgs_margin(packed, val, fb, labels, valid, w, base):
            with jax.named_scope("lbfgs_margin"):
                m = product(packed, val, fb, w, tiles=tiles) + base
                loss = _row_loss(loss_type, m, labels) * valid
                return m, jnp.sum(loss.reshape(tiles, -1), axis=1)

        def lbfgs_grad(packed, val, fb, labels, valid, m):
            with jax.named_scope("lbfgs_grad"):
                pred = jax.nn.sigmoid(m) if loss_type == LOSS_LOGISTIC else m
                g = (pred - labels) * valid
                return (product_t(packed, val, fb, g, tiles=tiles,
                                  num_feature=nf),
                        jnp.sum(g.reshape(tiles, -1), axis=1))

        rows = jax.ShapeDtypeStruct((tiles * sk.ROW_TILE,), jnp.float32)
        return (jax.jit(lbfgs_margin).lower(
                    *self.arrays, jax.ShapeDtypeStruct((nf,), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32)).compile(),
                jax.jit(lbfgs_grad).lower(*self.arrays, rows).compile())

    def evaluate(self, w: np.ndarray, base: np.float32):
        """(margins on the device, the loss summed a tile)."""
        return self._evaluate(*self.arrays, w, base)

    def gradient(self, margins):
        """(``X^T g`` for the loss's g at ``margins``, g summed a tile)."""
        return self._gradient(*self.arrays, margins)


def stage_rows(indices: np.ndarray, values: np.ndarray, labels: np.ndarray,
               num_feature: int, loss_type: int = LOSS_LOGISTIC
               ) -> DeviceShard:
    """Padded-ELL rows ``(n, k)`` (a slot of value 0 is padding) to the
    device, a group of tiles at a time with the next group's host copy
    in flight, each group bucketed there (``stage.put``,
    ``stage.bucket``); then the job's programs (``stage.compile``)."""
    import jax
    import jax.numpy as jnp

    n, k = indices.shape
    tiles = max(1, -(-n // sk.ROW_TILE))
    group = min(sk.GROUP_TILES, tiles)
    groups = -(-tiles // group)
    tiles = groups * group
    cap = sk.capacity(k, num_feature)
    rows = group * sk.ROW_TILE
    with program.span("stage.compile"):
        slots = jax.ShapeDtypeStruct((rows * k,), jnp.int32)
        bucket = sk.bucket_group.lower(
            slots, jax.ShapeDtypeStruct((rows * k,), jnp.float32),
            nnz_row=k, num_feature=num_feature).compile()

    def put(i: int):
        with program.span("stage.put"):
            lo, hi = i * rows, min(n, (i + 1) * rows)
            flat = []
            for a, dtype in ((indices, np.int32), (values, np.float32)):
                a = np.ascontiguousarray(a[lo:hi], dtype).reshape(-1)
                if hi - lo < rows:
                    a = np.concatenate(
                        [a, np.zeros((rows - (hi - lo)) * k, dtype)])
                flat.append(jax.device_put(a))
            return flat

    subs = group * cap // sk.SUB
    packed = jnp.zeros((groups * subs, sk.SUB), jnp.int32)
    val = jnp.zeros((groups * subs, sk.SUB), jnp.float32)
    fb = jnp.zeros((groups * subs // sk.SUBS, sk.SUBS), jnp.int32)
    ahead, counted = put(0), []
    for i in range(groups):
        here, ahead = ahead, put(i + 1) if i + 1 < groups else None
        with program.span("stage.bucket"):
            p, v, f, real = bucket(*here)
            packed = sk.place(packed, p, i * subs)
            val = sk.place(val, v, i * subs)
            fb = sk.place(fb, f, i * subs // sk.SUBS)
            counted.append(real)
    with program.span("stage.put"):
        pad = tiles * sk.ROW_TILE - n
        y = jax.device_put(np.concatenate(
            [np.asarray(labels, np.float32), np.zeros(pad, np.float32)]))
        valid = jax.device_put(np.concatenate(
            [np.ones(n, np.float32), np.zeros(pad, np.float32)]))
    with program.span("stage.bucket"):
        nnz = sum(int(c) for c in counted)       # waits for the last group
    return DeviceShard(packed, val, fb, y, valid, tiles, num_feature, nnz,
                       loss_type)


class LinearObjFunction(ObjFunction):
    """The solver-facing objective (reference: LinearObjFunction,
    linear.cc:7-208)."""

    def __init__(self) -> None:
        self.model = LinearModel()
        self.reg_L2 = 0.0
        self.task = "train"
        self.model_in = "NULL"
        self.model_out = "final.model"
        self.name_pred = "pred.txt"
        self.save_base64 = False
        self.lbfgs = LBFGSSolver(self)
        self.dtrain: SparseMat | None = None
        self._rows = None          # (indices, values, labels), padded ELL
        self._feat_dim = 0
        self._shard: DeviceShard | None = None
        self._margins = None       # (w, base, margins on the device)
        self._ahead = None         # (w, base, a gradient under way)

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        self.model.set_param(name, val)
        self.lbfgs.set_param(name, val)
        if name == "num_feature":
            self.lbfgs.set_param("num_dim", str(int(val) + 1))
        elif name == "reg_L2":
            self.reg_L2 = float(val)
        elif name == "task":
            self.task = val
        elif name == "model_in":
            self.model_in = val
        elif name == "model_out":
            self.model_out = val
        elif name == "name_pred":
            self.name_pred = val
        elif name == "save_base64":
            self.save_base64 = bool(int(val))

    def load_data(self, fname: str) -> None:
        self.dtrain = load_libsvm(fname)
        self._feat_dim = self.dtrain.feat_dim

    def load_arrays(self, indices: np.ndarray, values: np.ndarray,
                    labels: np.ndarray, feat_dim: int) -> None:
        """This rank's shard as padded-ELL arrays ``(n, k)`` (a slot of
        value 0 is padding), what ``SparseMat.to_ell`` gives for a file;
        ``feat_dim`` is one past the largest feature index."""
        check(indices.shape == values.shape and indices.ndim == 2
              and len(labels) == len(indices),
              "load_arrays: indices and values (n, k), labels (n,)")
        self._rows = (indices, values, labels)
        self._feat_dim = int(feat_dim)

    # ------------------------------------------------------------------
    # ObjFunction contract
    def init_num_dim(self) -> int:
        """(reference: InitNumDim, linear.cc:126-133)"""
        if self.model_in == "NULL":
            ndim = int(rabit_tpu.allreduce(
                np.array([self._feat_dim], np.int64), MAX)[0])
            self.model.num_feature = max(ndim, self.model.num_feature)
        return self.model.num_feature + 1

    def init_model(self, weight: np.ndarray) -> None:
        """(reference: InitModel, linear.cc:134-142)"""
        if self.model_in == "NULL":
            weight[:] = 0.0
            if self.model.loss_type == LOSS_LOGISTIC:
                self.model.init_base_score()
        else:
            weight[:] = self.model.weight
        self.prepare()

    def save_state(self) -> object:
        return (self.model.base_score, self.model.num_feature,
                self.model.loss_type)

    def load_state(self, state: object) -> None:
        (self.model.base_score, self.model.num_feature,
         self.model.loss_type) = state
        self.prepare()

    def prepare(self) -> None:
        """Stage the shard and compile the job's programs, once: when
        the solver's ``init`` hands over a fresh model or a committed
        one, so that no iteration pays for it."""
        if self._shard is not None:
            return
        if self._rows is None:
            idx, val, _labels, _valid = self.dtrain.to_ell()
            self._rows = (idx, val, self.dtrain.labels)
        self._shard = stage_rows(*self._rows, self.model.num_feature,
                                 self.model.loss_type)

    def _split(self, weight: np.ndarray):
        """(float32 feature weights, float32 bias + base score): what the
        device is handed."""
        nf = self.model.num_feature
        return (np.asarray(weight[:nf], np.float32),
                np.float32(self.model.base_score + weight[nf]))

    def _evaluate(self, w: np.ndarray, base: np.float32):
        """Margins at ``(w, base)`` kept on the device for the gradient
        that follows an accepted trial; the loss summed a tile."""
        with program.span("learn.dispatch"):
            margins, partial = self._shard.evaluate(w, base)
            program.enqueued(partial)
        self._margins = (w, base, margins)
        program.count("lbfgs.nnz", self._shard.nnz)
        program.count("lbfgs.nnz_padded", self._shard.nnz_padded)
        return partial

    def eval(self, weight: np.ndarray) -> float:
        """Shard data loss (+L2 on rank 0 only — added once globally;
        reference: Eval, linear.cc:150-173).  Float32 within a tile of
        rows on the device, float64 across tiles here."""
        self.prepare()
        partial = self._evaluate(*self._split(weight))
        with program.span("learn.fetch"):
            sum_val = fetch(partial, lambda tiles: float(
                tiles.astype(np.float64).sum()))
        nf = self.model.num_feature
        if rabit_tpu.get_rank() == 0 and self.reg_L2 != 0.0:
            sum_val += 0.5 * self.reg_L2 * float(weight[:nf] @ weight[:nf])
        check(not np.isnan(sum_val), "nan occurs")
        return sum_val

    def _dispatch_grad(self, w: np.ndarray, base: np.float32):
        """Enqueue ``X^T g`` at ``(w, base)``.  The margins are those
        ``eval`` left on the device when it was handed these same
        weights (the accepted trial of the line search); anything else
        recomputes them."""
        program.count("lbfgs.grads")
        if _is_for(self._margins, w, base):
            program.count("lbfgs.margin_reused")
        else:
            self._evaluate(w, base)
        with program.span("learn.dispatch"):
            out = self._shard.gradient(self._margins[2])
            program.enqueued(out[1])
        program.count("lbfgs.nnz", self._shard.nnz)
        program.count("lbfgs.nnz_padded", self._shard.nnz_padded)
        return out

    def start_grad(self, weight: np.ndarray) -> None:
        """The gradient at ``weight`` enqueued, not waited for: what the
        solver asks for before it commits."""
        self.prepare()
        w, base = self._split(weight)
        self._ahead = (w, base, self._dispatch_grad(w, base))

    def calc_grad(self, weight: np.ndarray) -> np.ndarray:
        """Shard gradient (reference: CalcGrad, linear.cc:174-201): the
        one ``start_grad`` enqueued for these weights, or a new one."""
        self.prepare()
        w, base = self._split(weight)
        ahead, self._ahead = self._ahead, None
        if _is_for(ahead, w, base):
            program.count("learn.ahead")
            gw, gbias = ahead[2]
        else:
            gw, gbias = self._dispatch_grad(w, base)
        nf = self.model.num_feature

        def widen(host):
            out = np.empty(nf + 1, np.float64)
            out[:nf] = host[0]
            out[nf] = host[1].astype(np.float64).sum()
            return out

        with program.span("learn.fetch"):
            out = fetch((gw, gbias), widen)
        if rabit_tpu.get_rank() == 0 and self.reg_L2 != 0.0:
            out[:nf] += self.reg_L2 * weight[:nf]
        return out

    # ------------------------------------------------------------------
    def run(self) -> None:
        """train / pred dispatch (reference: Run, linear.cc:52-75)."""
        if self.model_in != "NULL":
            self.model.load(self.model_in)
        if self.task == "train":
            self.lbfgs.run()
            w = self.lbfgs.get_weight()
            self.model.weight = np.asarray(w, np.float64)
            if rabit_tpu.get_rank() == 0:
                self.model.save(self.model_out, self.save_base64)
        elif self.task == "pred":
            check(self.model_in != "NULL",
                  "must set model_in for task=pred")
            preds = self.predict()
            with open(self.name_pred, "w") as fp:
                for p in preds:
                    fp.write(f"{p:g}\n")
            print(f"Finishing writing to {self.name_pred}", flush=True)
        else:
            check(False, "unknown task=%s", self.task)

    def predict(self) -> np.ndarray:
        return self.model.predict(self.dtrain)


def main(argv: list[str]) -> int:
    """CLI mirroring the reference binary:
    ``linear <data_in> [name=value ...]`` (reference: linear.cc:212-239)."""
    if len(argv) < 2:
        rabit_tpu.init()
        if rabit_tpu.get_rank() == 0:
            rabit_tpu.tracker_print("Usage: <data_in> param=val")
        rabit_tpu.finalize()
        return 0
    compile_cache.enable()
    obj = LinearObjFunction()
    if argv[1] == "stdin":
        obj.load_data(argv[1])
        rabit_tpu.init(argv[2:])
    else:
        rabit_tpu.init(argv[2:])
        obj.load_data(argv[1])
    for a in argv[2:]:
        if "=" in a:
            name, val = a.split("=", 1)
            obj.set_param(name, val)
    obj.run()
    rabit_tpu.finalize()
    return 0


def cli() -> int:
    """Console-script entry point."""
    import sys

    return main(sys.argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
