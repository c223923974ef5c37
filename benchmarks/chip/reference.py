"""Plain numpy reference for a served view deployment.

It imports nothing of the system under test and takes nothing the system
made: its inputs are the table the benchmark generated and the commits the
benchmark's own sessions sent and saw acknowledged. It computes

  * the k one-vs-all hinge models by replaying every committed training row
    through plain SGD (float32 weights, float64 bias, in commit order);
  * margins F . W^T - b in float32, in blocks of rows;
  * Eq. 2 waters and the Lemma 3.1 band test, for the band kernel's need.

A (row, view) pair whose reference margin lies within TOL of zero may be
labelled either way by two correct f32 summation orders, so label and
answer comparisons count only pairs outside it.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple

import numpy as np

TOL = 1e-4          # |margin| at or below this may round to either sign
BLOCK_ROWS = 1 << 16


def sgd_replay(F: np.ndarray, truth: np.ndarray, groups: Sequence[np.ndarray],
               k: int, lr: float, l2: float, keep: Iterable[int] = ()
               ) -> Tuple[np.ndarray, np.ndarray, Dict[int, tuple]]:
    """Replay committed groups in order. Epoch e is the model after the
    first e groups; `keep` names the epochs whose (W, b) to return."""
    keep = set(keep)
    W = np.zeros((k, F.shape[1]), np.float32)
    b = np.zeros(k, np.float64)
    decay = 1.0 - lr * l2
    onehot = np.arange(k)
    snaps = {0: (W.copy(), b.copy())} if 0 in keep else {}
    for e, ids in enumerate(groups, start=1):
        for i in ids:
            f = F[int(i)]
            y = np.where(onehot == int(truth[int(i)]), 1.0, -1.0)
            z = W @ f - b.astype(np.float32)
            g = np.where(y * z.astype(np.float64) < 1.0, -y, 0.0)
            W = W * decay
            W -= (lr * g).astype(np.float32)[:, None] * f[None, :]
            b = b + lr * g
        if e in keep:
            snaps[e] = (W.copy(), b.copy())
    return W, b, snaps


def margins(F: np.ndarray, W: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n, k) float32 margins F . W^T - b."""
    Wt = np.asarray(W, np.float32).T
    b32 = np.asarray(b).astype(np.float32)
    out = np.empty((F.shape[0], Wt.shape[1]), np.float32)
    for s in range(0, F.shape[0], BLOCK_ROWS):
        out[s:s + BLOCK_ROWS] = F[s:s + BLOCK_ROWS] @ Wt - b32
    return out


def model_error(W, b, W_ref, b_ref) -> float:
    """Largest gap of weights and biases, relative to the reference's
    largest magnitude (or 1 where that is smaller)."""
    W, W_ref = np.asarray(W, np.float64), np.asarray(W_ref, np.float64)
    b, b_ref = np.asarray(b, np.float64), np.asarray(b_ref, np.float64)
    ew = np.max(np.abs(W - W_ref)) / max(1.0, float(np.max(np.abs(W_ref))))
    eb = np.max(np.abs(b - b_ref)) / max(1.0, float(np.max(np.abs(b_ref))))
    return float(max(ew, eb))


def label_mismatches(labels: np.ndarray, Z: np.ndarray) -> Tuple[int, int]:
    """labels: (k, n) in entity order; Z: (n, k). Returns (pairs that
    disagree with sign(Z) outside TOL, pairs within TOL)."""
    ref = np.where(Z.T >= 0, 1, -1)
    near = np.abs(Z.T) <= TOL
    return int(np.count_nonzero((labels != ref) & ~near)), int(near.sum())


def answer_mismatches(F: np.ndarray, answers: np.ndarray,
                      snaps: Dict[int, tuple]) -> Tuple[int, int]:
    """answers: (m, 4) rows of (entity, view, label, epoch). Each label is
    judged against the model at its own epoch. Returns (wrong outside
    TOL, within TOL)."""
    bad = near = 0
    for e in np.unique(answers[:, 3]):
        a = answers[answers[:, 3] == e]
        W, b = snaps[int(e)]
        z = np.einsum("md,md->m", F[a[:, 0]], W[a[:, 1]]) \
            - b[a[:, 1]].astype(np.float32)
        close = np.abs(z) <= TOL
        bad += int(np.count_nonzero((np.where(z >= 0, 1, -1) != a[:, 2])
                                    & ~close))
        near += int(close.sum())
    return bad, near


def row_norm_max(F: np.ndarray, q: float) -> float:
    """Hölder's M = max_t ||f(t)||_q over the table (q = 2 or 1)."""
    m = 0.0
    for s in range(0, F.shape[0], BLOCK_ROWS):
        blk = np.abs(F[s:s + BLOCK_ROWS])
        r = blk.sum(axis=1) if q == 1 else np.sqrt((blk * blk).sum(axis=1))
        m = max(m, float(r.max()))
    return m


def waters(lw, hw, W, b, W_s, b_s, M: float, p: float):
    """Eq. 2: lw never rises and hw never falls between reorganizes."""
    dw = (np.abs(np.asarray(W, np.float32) - np.asarray(W_s, np.float32))
          ** p).sum(axis=1) ** (1.0 / p)
    db = np.asarray(b, np.float64) - np.asarray(b_s, np.float32).astype(
        np.float64)
    return np.minimum(lw, -M * dw + db), np.maximum(hw, M * dw + db)


def in_band(eps, lw, hw):
    """Lemma 3.1: a row whose stored margin lies in [lw, hw) may have
    changed sign; outside it the stored label stands."""
    return (eps >= lw) & (eps < hw)
