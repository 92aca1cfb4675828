"""Linear / logistic regression on the L-BFGS solver.

Equivalent of reference: rabit-learn/linear/{linear.h,linear.cc}.  The
objective's Eval/CalcGrad — the FLOP-heavy part the reference spreads over
OpenMP threads with per-row sparse loops (linear.cc:150-201) — are here
single jitted XLA programs over the padded-ELL data: margins come from a
gather + row-sum, gradients from a scatter-add, both fused by XLA.  Model
files keep the reference's two on-disk encodings ("binf" binary and
"bs64" base64 text for text-only channels, linear.cc:76-122).
"""
from __future__ import annotations

import struct
import sys
from typing import BinaryIO

import numpy as np

import rabit_tpu
from rabit_tpu.learn.data import SparseMat, load_libsvm
from rabit_tpu.learn.lbfgs import LBFGSSolver, ObjFunction
from rabit_tpu.ops import MAX
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


_EVAL_CACHE: dict = {}


def _make_kernels(loss_type: int, nblocks: int, block: int, nnz: int,
                  wlen: int):
    """Jitted eval/grad over ELL blocks.

    Weights are padded with one zero slot that all ELL padding (and any
    feature ≥ num_feature, reference: linear.h:94-96) points at, so the
    gather/scatter needs no masking.
    """
    key = (loss_type, nblocks, block, nnz, wlen)
    fns = _EVAL_CACHE.get(key)
    if fns is not None:
        return fns
    import jax
    import jax.numpy as jnp

    def margins(wpad, base, idx, val):
        # (nb, B, nnz) gather → row-sum; bias wpad[wlen-2] added by caller
        return base + jnp.sum(wpad[idx] * val, axis=-1)

    @jax.jit
    def eval_fn(wpad, base, idx, val, labels, valid):
        m = margins(wpad, base, idx, val)
        if loss_type == LOSS_LOGISTIC:
            # stable nlogprob (reference: MarginToLoss, linear.h:72-86)
            nlogprob = jnp.where(
                m > 0.0,
                jnp.log1p(jnp.exp(-m)),
                -m + jnp.log1p(jnp.exp(m)))
            loss = labels * nlogprob + (1.0 - labels) * (m + nlogprob)
        else:
            loss = 0.5 * (m - labels) ** 2
        return jnp.sum(loss * valid)

    @jax.jit
    def grad_fn(wpad, base, idx, val, labels, valid):
        m = margins(wpad, base, idx, val)
        if loss_type == LOSS_LOGISTIC:
            pred = jax.nn.sigmoid(m)
        else:
            pred = m
        g = (pred - labels) * valid          # (nb, B)
        flat_idx = idx.reshape(-1)
        flat = (val * g[..., None]).reshape(-1)
        # 1-D scatter into the weight vector measures on par with a
        # one-hot contraction here (unlike the 2-D row densify in
        # kmeans, where one-hot wins 10x) — keep the simple form.
        gw = jnp.zeros(wlen, jnp.float32).at[flat_idx].add(flat)
        return gw, jnp.sum(g)

    _EVAL_CACHE[key] = (eval_fn, grad_fn)
    return _EVAL_CACHE[key]


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
        self.row_block = 1024
        self.lbfgs = LBFGSSolver(self)
        self.dtrain: SparseMat | None = None
        self._ell = None

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
        elif name == "row_block":
            self.row_block = int(val)

    def load_data(self, fname: str) -> None:
        self.dtrain = load_libsvm(fname)

    # ------------------------------------------------------------------
    # ObjFunction contract
    def init_num_dim(self) -> int:
        """(reference: InitNumDim, linear.cc:126-133)"""
        if self.model_in == "NULL":
            ndim = int(rabit_tpu.allreduce(
                np.array([self.dtrain.feat_dim], np.int64), MAX)[0])
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

    def save_state(self) -> object:
        return (self.model.base_score, self.model.num_feature,
                self.model.loss_type)

    def load_state(self, state: object) -> None:
        (self.model.base_score, self.model.num_feature,
         self.model.loss_type) = state

    def _ell_blocks(self):
        if self._ell is None:
            nf = self.model.num_feature
            idx, val, labels, valid = self.dtrain.to_ell(
                pad_index=nf + 1, row_block=self.row_block)
            # any feature ≥ num_feature routes to the zero pad slot
            idx = np.where(idx >= nf, nf + 1, idx).astype(np.int32)
            import jax

            nb = idx.shape[0] // self.row_block
            # device-resident across all solver iterations
            self._ell = tuple(jax.device_put(a) for a in (
                idx.reshape(nb, self.row_block, -1),
                val.reshape(nb, self.row_block, -1),
                labels.reshape(nb, self.row_block),
                valid.reshape(nb, self.row_block),
            ))
        return self._ell

    def _wpad(self, weight: np.ndarray) -> np.ndarray:
        # [w_0..w_{nf-1}, bias, 0-pad]
        return np.concatenate(
            [weight, [0.0]]).astype(np.float32)

    def eval(self, weight: np.ndarray) -> float:
        """Shard data loss (+L2 on rank 0 only — added once globally;
        reference: Eval, linear.cc:150-173)."""
        idx, val, labels, valid = self._ell_blocks()
        eval_fn, _ = _make_kernels(
            self.model.loss_type, *idx.shape, len(weight) + 1)
        nf = self.model.num_feature
        base = np.float32(self.model.base_score + weight[nf])
        sum_val = float(eval_fn(self._wpad(weight), base, idx, val,
                                labels, valid))
        if rabit_tpu.get_rank() == 0 and self.reg_L2 != 0.0:
            sum_val += 0.5 * self.reg_L2 * float(weight[:nf] @ weight[:nf])
        check(not np.isnan(sum_val), "nan occurs")
        return sum_val

    def calc_grad(self, weight: np.ndarray) -> np.ndarray:
        """Shard gradient (reference: CalcGrad, linear.cc:174-201)."""
        idx, val, labels, valid = self._ell_blocks()
        _, grad_fn = _make_kernels(
            self.model.loss_type, *idx.shape, len(weight) + 1)
        nf = self.model.num_feature
        base = np.float32(self.model.base_score + weight[nf])
        gw, gbias = grad_fn(self._wpad(weight), base, idx, val,
                            labels, valid)
        out = np.asarray(gw, np.float64)[:nf + 1]
        out[nf] = float(gbias)
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
