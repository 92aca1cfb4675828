"""Vector-free distributed L-BFGS (+ OWL-QN for L1) solver.

Equivalent of reference: rabit-learn/solver/lbfgs.h:55-650, keeping its
parallel decomposition — each rank owns one contiguous, 8-aligned
**parameter-range shard** (lbfgs.h:125-135); the (s, y) history lives only
as shards; the two-loop recursion runs on *dot products* (computed on
shards, summed with one allreduce, lbfgs.h:244-249) so no rank ever
materializes another rank's history; the final direction is assembled
shard-locally and completed with an allreduce (lbfgs.h:283-296).

Where the work is: the objective's two passes over the data (``eval``
and ``calc_grad``) are the device's; everything of the solver itself is
float64 numpy on the host — the Gram products and the direction
assembly are one matmul each over the (2m+1, nsub) history matrix
instead of the reference's per-pair loops (0.9 GFLOP an iteration at a
million weights and m = 10).  Cross-rank sums go through the framework
allreduce; solver state is committed with the (global, local)
checkpoint pair exactly like the reference (gstate global / history
shard local, lbfgs.h:119,192).

Spans (doc/observability.md "Program spans"): ``lbfgs.init`` at set-up;
an outer iteration is ``learn.step`` ⊃ ``lbfgs.grad``, ``allreduce``,
``lbfgs.direction`` (⊃ ``lbfgs.gram``, ``lbfgs.two_loop``,
``lbfgs.assemble``), ``lbfgs.linesearch`` (⊃ one ``lbfgs.eval`` a
trial) and ``commit``; the accepted trial's gradient is enqueued before
the commit (``ObjFunction.start_grad``), so the step after finds it
under way.
"""
from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Optional

import numpy as np

import rabit_tpu
from rabit_tpu.obs import program
from rabit_tpu.ops import SUM
from rabit_tpu.utils.checks import check


class ObjFunction(ABC):
    """Objective contract (reference: IObjFunction, lbfgs.h:21-51).

    Eval/CalcGrad see only this rank's data shard; the solver allreduces.
    ``save``/``load`` let the objective persist extra state inside the
    solver checkpoint.
    """

    @abstractmethod
    def eval(self, weight: np.ndarray) -> float: ...

    @abstractmethod
    def calc_grad(self, weight: np.ndarray) -> np.ndarray: ...

    @abstractmethod
    def init_num_dim(self) -> int: ...

    @abstractmethod
    def init_model(self, weight: np.ndarray) -> None: ...

    def start_grad(self, weight: np.ndarray) -> None:
        """Begin ``calc_grad(weight)``'s device work without waiting for
        it, where an objective can.  The solver calls it on the accepted
        iterate just before it commits; ``calc_grad`` of the same
        weights then only has to fetch."""

    def save_state(self) -> object:
        return None

    def load_state(self, state: object) -> None:
        pass


def _gram(hist: np.ndarray) -> np.ndarray:
    """Gram matrix of the history rows, in float64.

    The two-loop recursion's curvature ratios need the full float64 the
    solver state carries; a device matmul would silently downcast to f32
    without x64 mode, so this (2m+1)² product stays on host.  The
    FLOP-heavy work (the objective's eval/grad) is on device.
    """
    with program.span("lbfgs.gram"):
        return hist @ hist.T


class LBFGSSolver:
    """Reference: LBFGSSolver, lbfgs.h:55-650.

    History layout matches the reference rolling array: rows [0, m) are
    s-vectors (weight deltas), rows [m, 2m) are y-vectors (gradient
    deltas), row 2m is the current steepest-descent proposal
    (lbfgs.h:229-309).  ``dot_buf`` caches the Gram matrix of those rows
    across shifts (lbfgs.h:499-503).
    """

    def __init__(self, obj: Optional[ObjFunction] = None):
        self.obj = obj
        # hyper-parameters (defaults per reference ctor, lbfgs.h:57-67)
        self.reg_L1 = 0.0
        self.max_linesearch_iter = 100
        self.linesearch_backoff = 0.5
        self.linesearch_c1 = 1e-4
        self.min_lbfgs_iter = 5
        self.max_lbfgs_iter = 500
        self.lbfgs_stop_tol = 1e-5
        self.silent = 0
        self.size_memory = 10
        # global state (reference: GlobalState, lbfgs.h:459-545)
        self.num_dim = 0
        self.num_iteration = 0
        self.init_objval = 0.0
        self.old_objval = 0.0
        self.new_objval = 0.0
        self.weight: np.ndarray | None = None
        # rolling history (reference: HistoryArray, lbfgs.h:547-632)
        self.hist: np.ndarray | None = None     # (2m+1, nsub) float64
        self.num_useful = 0
        self.offset = 0
        self.dot_buf: np.ndarray | None = None  # (2m+1, 2m+1) float64
        self.range_begin = 0
        self.range_end = 0
        self._work: dict[str, np.ndarray] = {}

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        """Untyped name=value config (reference: lbfgs.h:74-102)."""
        if name == "num_dim":
            self.num_dim = int(val)
        elif name == "size_memory":
            self.size_memory = int(val)
        elif name == "reg_L1":
            self.reg_L1 = float(val)
        elif name == "lbfgs_stop_tol":
            self.lbfgs_stop_tol = float(val)
        elif name == "linesearch_backoff":
            self.linesearch_backoff = float(val)
        elif name == "max_linesearch_iter":
            self.max_linesearch_iter = int(val)
        elif name == "max_lbfgs_iter":
            self.max_lbfgs_iter = int(val)
        elif name == "min_lbfgs_iter":
            self.min_lbfgs_iter = int(val)
        elif name == "linesearch_c1":
            self.linesearch_c1 = float(val)
        elif name == "silent":
            self.silent = int(val)

    # ------------------------------------------------------------------
    # rolling-array indexing (reference: MapIndex, lbfgs.h:447-457)
    def _map(self, i: int) -> int:
        m = self.size_memory
        if i == 2 * m:
            return i
        if i < m:
            return (i + self.offset) % m
        return (i + self.offset) % m + m

    def _row(self, i: int) -> np.ndarray:
        return self.hist[self._map(i)]

    def _dot(self, i: int, j: int) -> float:
        return self.dot_buf[self._map(i), self._map(j)]

    def _set_dot(self, i: int, j: int, v: float) -> None:
        a, b = self._map(i), self._map(j)
        self.dot_buf[a, b] = v
        self.dot_buf[b, a] = v

    def _shift(self) -> None:
        self.offset = (self.offset + 1) % self.size_memory

    def _scratch(self, name: str, size: int) -> np.ndarray:
        """A float64 work vector that lives as long as the solver: an
        iteration at a million weights made some 25 temporaries of 8 MB,
        which the allocator maps and faults in anew each time (PERF.md
        section 6, PR 31).  Nothing committed or returned ever aliases
        one."""
        buf = self._work.get(name)
        if buf is None or buf.shape[0] != size:
            buf = self._work[name] = np.empty(size, np.float64)
        return buf

    # ------------------------------------------------------------------
    def init(self) -> None:
        """Restore-or-initialize (reference: lbfgs.h:116-152)."""
        check(self.obj is not None, "LBFGSSolver.init: set an objective first")
        with program.span("lbfgs.init"):
            self._init()

    def _init(self) -> None:
        version, gstate, hist = rabit_tpu.load_checkpoint(with_local=True)
        if version == 0:
            self.num_dim = self.obj.init_num_dim()
        else:
            self._restore_global(gstate)
        # parameter partition: contiguous, 8-aligned upper split
        # (reference: lbfgs.h:125-135)
        nproc = rabit_tpu.get_world_size()
        rank = rabit_tpu.get_rank()
        step = (self.num_dim + nproc - 1) // nproc
        step = (step + 7) // 8 * 8
        self.range_begin = min(rank * step, self.num_dim)
        self.range_end = min((rank + 1) * step, self.num_dim)
        nsub = self.range_end - self.range_begin
        if version == 0:
            m = self.size_memory
            self.dot_buf = np.zeros((2 * m + 1, 2 * m + 1), np.float64)
            self.hist = np.zeros((2 * m + 1, nsub), np.float64)
            self.weight = np.zeros(self.num_dim, np.float64)
            self.obj.init_model(self.weight)
            # all ranks adopt rank 0's initialization
            self.weight = rabit_tpu.broadcast(
                self.weight if rank == 0 else None, 0)
            self.old_objval = self._eval(self.weight)
            self.init_objval = self.old_objval
            if self.silent == 0 and rank == 0:
                rabit_tpu.tracker_print(
                    "L-BFGS solver starts, num_dim=%d, init_objval=%g, "
                    "size_memory=%d"
                    % (self.num_dim, self.init_objval, self.size_memory))
        else:
            self._restore_local(hist)
            # the one line every learner prints on a resume
            rabit_tpu.tracker_print("[%d] restart iter=%d" % (rank, version))

    # -- checkpoint payloads (reference: GlobalState/HistoryArray
    #    Load/Save, lbfgs.h:505-528,596-617) --------------------------------
    def _global_payload(self) -> dict:
        return {
            "size_memory": self.size_memory,
            "num_iteration": self.num_iteration,
            "num_dim": self.num_dim,
            "init_objval": self.init_objval,
            "old_objval": self.old_objval,
            "offset": self.offset,
            "dot_buf": self.dot_buf,
            "weight": self.weight,
            "obj_state": self.obj.save_state(),
        }

    def _restore_global(self, payload: dict) -> None:
        self.size_memory = payload["size_memory"]
        self.num_iteration = payload["num_iteration"]
        self.num_dim = payload["num_dim"]
        self.init_objval = payload["init_objval"]
        self.old_objval = payload["old_objval"]
        self.offset = payload["offset"]
        self.dot_buf = payload["dot_buf"]
        self.weight = payload["weight"]
        self.obj.load_state(payload["obj_state"])

    def _local_payload(self) -> dict:
        return {"hist": self.hist, "num_useful": self.num_useful}

    def _restore_local(self, payload: Optional[dict]) -> None:
        nsub = self.range_end - self.range_begin
        if payload is None:
            # local state lost beyond replication reach: restart history
            # (the reference would abort; we degrade to a cold history)
            self.hist = np.zeros(
                (2 * self.size_memory + 1, nsub), np.float64)
            self.num_useful = 0
            return
        self.hist = payload["hist"]
        self.num_useful = payload["num_useful"]

    # ------------------------------------------------------------------
    def update_one_iter(self) -> bool:
        """One outer iteration (reference: UpdateOneIter, lbfgs.h:166-194)."""
        with program.span("learn.step", version=rabit_tpu.version_number() + 1):
            return self._update_one_iter()

    def _update_one_iter(self) -> bool:
        with program.span("lbfgs.grad"):
            grad = np.asarray(self.obj.calc_grad(self.weight), np.float64)
        # codec=False on every solver collective: the L-BFGS direction
        # math is precision-critical (curvature ratios of near-equal
        # dots), so these ops keep exact full-width bytes even when the
        # job arms a lossy wire codec for its bulk traffic
        # (doc/performance.md "Quantized wire codecs").
        grad = rabit_tpu.allreduce(grad, SUM, codec=False)
        with program.span("lbfgs.direction"):
            dir_, vdot = self._find_change_direction(grad)
        if vdot >= -1e-15:
            # the (sub)gradient direction vanished: already at the optimum
            # (the reference asserts dotv<0, lbfgs.h:318; converging to an
            # exact stationary point is a stop, not an error, here)
            self.new_objval = self.old_objval
            return True
        with program.span("lbfgs.linesearch"):
            iters, new_weight = self._backtrack_line_search(dir_, vdot)
        check(iters < self.max_linesearch_iter, "line search failed")
        self.weight = new_weight
        if self.num_iteration > self.min_lbfgs_iter:
            if (self.old_objval - self.new_objval
                    < self.lbfgs_stop_tol * self.init_objval):
                return True
        if self.silent == 0 and rabit_tpu.get_rank() == 0:
            rabit_tpu.tracker_print(
                "[%d] L-BFGS: linesearch finishes in %d rounds, "
                "new_objval=%g, improvement=%g"
                % (self.num_iteration, iters, self.new_objval,
                   self.old_objval - self.new_objval))
        self.old_objval = self.new_objval
        # the next iteration's gradient starts on the device now, so
        # that the commit runs under it: the host's 0.2 s a commit of
        # 176 MB, and its jitter, were a tenth of an iteration with
        # nothing in flight (PERF.md section 6, PR 31)
        if self.num_iteration < self.max_lbfgs_iter:
            self.obj.start_grad(self.weight)
        rabit_tpu.checkpoint(self._global_payload(), self._local_payload())
        program.count("learn.iterations")
        program.count("learn.versions")
        return False

    def run(self) -> None:
        """Optimize to convergence (reference: Run, lbfgs.h:196-210)."""
        self.init()
        while self.num_iteration < self.max_lbfgs_iter:
            if self.update_one_iter():
                break
        if self.silent == 0 and rabit_tpu.get_rank() == 0:
            nonzero = int(np.count_nonzero(self.weight))
            rabit_tpu.tracker_print(
                "L-BFGS: finishes at iteration %d, %d/%d active weights"
                % (self.num_iteration, nonzero, self.num_dim))

    def get_weight(self) -> np.ndarray:
        return self.weight

    # ------------------------------------------------------------------
    def _find_change_direction(self, grad: np.ndarray):
        """Vector-free two-loop recursion on shard dot products
        (reference: FindChangeDirection, lbfgs.h:214-311)."""
        m = self.size_memory
        n = self.num_useful
        lo, hi = self.range_begin, self.range_end
        nsub = hi - lo
        gsub = grad[lo:hi]
        if n != 0:
            # hist[m+n-1] holds the previous gradient shard → turn it into
            # the newest y-vector (lbfgs.h:231)
            newest = self._row(m + n - 1)
            np.subtract(gsub, newest, out=newest)
            self._l1_dir(gsub, self.weight[lo:hi], out=self._row(2 * m))
            # Gram products of all history rows in one matmul, then a
            # single allreduce of the needed entries
            # (reference computes 5n dots pairwise, lbfgs.h:233-249)
            gram = _gram(self.hist)
            idxset = ([(j, 2 * m) for j in range(n)]
                      + [(j, n - 1) for j in range(n)]
                      + [(j, m + n - 1) for j in range(n)]
                      + [(m + j, 2 * m) for j in range(n)]
                      + [(m + j, m + n - 1) for j in range(n)])
            vals = np.array(
                [gram[self._map(i), self._map(j)] for i, j in idxset])
            vals = rabit_tpu.allreduce(vals, SUM, codec=False)
            for (i, j), v in zip(idxset, vals):
                self._set_dot(i, j, v)
            with program.span("lbfgs.two_loop"):
                delta = self._two_loop(n)
            # assemble shard direction: one (2m+1)-row matvec
            # (reference: AddScale loop, lbfgs.h:283-291)
            with program.span("lbfgs.assemble"):
                delta_phys = np.zeros(2 * m + 1)
                for i in range(2 * m + 1):
                    delta_phys[self._map(i)] = delta[i]
                dirsub = delta_phys @ self.hist
                steep = self._row(2 * m)
                if self.reg_L1 != 0.0:
                    against = self._scratch("against", nsub)
                    np.multiply(dirsub, steep, out=against)
                    np.putmask(dirsub, against <= 0.0, 0.0)
                vdot = -float(dirsub @ steep)
                # the direction and its slope travel as one vector
                both = self._scratch("direction", self.num_dim + 1)
                both[:lo] = 0.0
                both[lo:hi] = dirsub
                both[hi:self.num_dim] = 0.0
                both[self.num_dim] = vdot
            # The direction assembly is the big wire op of the
            # iteration (num_dim + 1 doubles): issue it async with
            # fuse=False (eager dispatch — a lone bucketed op would sit
            # unsent until wait()) and run the history-shift bookkeeping
            # below — pure local state — while it is in flight.
            both_handle = rabit_tpu.allreduce_async(
                both, SUM, fuse=False, codec=False)
        else:
            dir_ = self._l1_dir(grad, self.weight)
            vdot = -float(dir_ @ dir_)
            both_handle = None
        # shift history (lbfgs.h:302-309)
        if n < m:
            n += 1
        else:
            # rolling shift discards the oldest (s, y) pair and rotates
            # dot_buf with it (reference: GlobalState::Shift + hist.Shift)
            self._shift()
        self.num_useful = n
        self.hist[self._map(m + n - 1)] = gsub
        if both_handle is not None:
            both = both_handle.wait()
            dir_, vdot = both[:-1], float(both[-1])
        return dir_, vdot

    def _two_loop(self, n: int) -> np.ndarray:
        """The two-loop recursion in dot space (lbfgs.h:253-281): the
        coefficients of the direction over the 2m+1 history rows."""
        m = self.size_memory
        alpha = np.zeros(n)
        delta = np.zeros(2 * m + 1)
        delta[2 * m] = 1.0
        for j in range(n - 1, -1, -1):
            vsum = sum(delta[k] * self._dot(k, j)
                       for k in range(2 * m + 1))
            alpha[j] = vsum / self._dot(j, m + j)
            delta[m + j] -= alpha[j]
        scale = (self._dot(n - 1, m + n - 1)
                 / self._dot(m + n - 1, m + n - 1))
        delta *= scale
        for j in range(n):
            vsum = sum(delta[k] * self._dot(k, m + j)
                       for k in range(2 * m + 1))
            beta = vsum / self._dot(j, m + j)
            delta[j] += alpha[j] - beta
        return delta

    def _backtrack_line_search(self, dir_: np.ndarray, vdot: float):
        """Armijo backtracking (reference: BacktrackLineSearch,
        lbfgs.h:314-350); first iteration uses a unit-norm step."""
        check(vdot < 0.0, "gradient error, dotv=%g", vdot)
        alpha = 1.0
        backoff = self.linesearch_backoff
        if self.num_iteration == 0:
            alpha = 1.0 / np.sqrt(-vdot)
            backoff = 0.1
        iters = 0
        c1 = self.linesearch_c1
        new_weight = self.weight
        while True:
            iters += 1
            if iters >= self.max_linesearch_iter:
                break
            # a trial point is a new array: a committed one is kept
            new_weight = np.multiply(dir_, alpha)
            np.add(self.weight, new_weight, out=new_weight)
            if self.reg_L1 != 0.0:
                # OWL-QN: clamp sign flips (lbfgs.h:391-401)
                flipped = self._scratch("product", self.num_dim)
                np.multiply(new_weight, self.weight, out=flipped)
                np.putmask(new_weight, flipped < 0.0, 0.0)
            with program.span("lbfgs.eval"):
                new_val = self._eval(new_weight)
            program.count("lbfgs.evals")
            if new_val - self.old_objval <= c1 * vdot * alpha:
                self.new_objval = new_val
                break
            alpha *= backoff
        lo, hi = self.range_begin, self.range_end
        np.subtract(new_weight[lo:hi], self.weight[lo:hi],
                    out=self._row(self.num_useful - 1))
        self.num_iteration += 1
        return iters, new_weight

    def _l1_dir(self, grad: np.ndarray, weight: np.ndarray,
                out: np.ndarray | None = None) -> np.ndarray:
        """Steepest descent with L1 subgradient (reference: SetL1Dir,
        lbfgs.h:352-377), into ``out`` where one is given."""
        if out is None:
            out = np.empty(grad.shape, np.float64)
        np.negative(grad, out=out)
        if self.reg_L1 == 0.0:
            return out
        r = self.reg_L1
        # the penalty pushes down where the weight is positive, or is
        # zero under a gradient below -r; up the other way; and holds a
        # zero weight where it is the stronger
        down = (weight > 0.0) | ((weight == 0.0) & (grad < -r))
        up = (weight < 0.0) | ((weight == 0.0) & (grad > r))
        np.subtract(out, r, out=out, where=down)
        np.add(out, r, out=out, where=up)
        np.putmask(out, ~(down | up), 0.0)
        return out

    def _eval(self, weight: np.ndarray) -> float:
        """Global objective = allreduced data term + L1 (reference: Eval,
        lbfgs.h:402-413)."""
        val = float(self.obj.eval(weight))
        val = float(rabit_tpu.allreduce(np.array([val]), SUM,
                                        codec=False)[0])
        if self.reg_L1 != 0.0:
            val += self.reg_L1 * float(np.abs(
                weight, out=self._scratch("product", len(weight))).sum())
        check(not np.isnan(val), "nan occurs")
        return val
