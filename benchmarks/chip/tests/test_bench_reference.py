"""The reference against plain loops, on a seeded small stream."""
import numpy as np

import reference


def _table(rng, n=300, d=24, k=5):
    F = rng.random((n, d), dtype=np.float32)
    F /= np.linalg.norm(F, axis=1, keepdims=True)
    return F, rng.integers(0, k, n).astype(np.int32)


def _loop_sgd(F, truth, groups, k, lr, l2):
    """k scalar hinge models, one example at a time, one view at a time."""
    W = [np.zeros(F.shape[1], np.float32) for _ in range(k)]
    b = [0.0] * k
    for ids in groups:
        for i in ids:
            for v in range(k):
                y = 1.0 if truth[i] == v else -1.0
                z = float(np.float32(W[v] @ F[i]) - np.float32(b[v]))
                g = -y if y * z < 1.0 else 0.0
                W[v] = W[v] * np.float32(1.0 - lr * l2)
                W[v] = W[v] - np.float32(lr * g) * F[i]
                b[v] = b[v] + lr * g
    return np.stack(W), np.array(b)


def test_sgd_replay_matches_per_view_loop():
    rng = np.random.default_rng(7)
    F, truth = _table(rng)
    groups = [rng.integers(0, len(F), 16) for _ in range(20)]
    W, b, snaps = reference.sgd_replay(F, truth, groups, 5, 0.5, 1e-4,
                                       keep={0, 10, 20})
    Wl, bl = _loop_sgd(F, truth, groups, 5, 0.5, 1e-4)
    assert reference.model_error(W, b, Wl, bl) < 1e-6
    assert np.array_equal(snaps[20][0], W) and not snaps[0][0].any()
    W10, _, _ = reference.sgd_replay(F, truth, groups[:10], 5, 0.5, 1e-4)
    assert np.array_equal(snaps[10][0], W10)


def test_margins_and_label_comparison():
    rng = np.random.default_rng(8)
    F, _ = _table(rng, n=200)
    W = rng.normal(size=(5, F.shape[1])).astype(np.float32)
    b = rng.normal(size=5)
    Z = reference.margins(F, W, b)
    exact = F.astype(np.float64) @ W.T.astype(np.float64) - b
    assert np.max(np.abs(Z - exact)) < 1e-5
    labels = np.where(Z.T >= 0, 1, -1).astype(np.int8)
    assert reference.label_mismatches(labels, Z)[0] == 0
    far = np.argwhere(np.abs(Z.T) > reference.TOL)[:3]
    labels[far[:, 0], far[:, 1]] *= -1
    assert reference.label_mismatches(labels, Z)[0] == 3


def test_answers_are_judged_at_their_epoch():
    rng = np.random.default_rng(9)
    F, truth = _table(rng)
    groups = [rng.integers(0, len(F), 16) for _ in range(6)]
    _, _, snaps = reference.sgd_replay(F, truth, groups, 5, 0.5, 1e-4,
                                       keep={2, 6})
    rows = []
    for e in (2, 6):
        W, b = snaps[e]
        for i in range(40):
            v = i % 5
            z = F[i] @ W[v] - np.float32(b[v])
            rows.append((i, v, 1 if z >= 0 else -1, e))
    answers = np.array(rows, np.int64)
    assert reference.answer_mismatches(F, answers, snaps)[0] == 0
    swapped = answers.copy()
    swapped[:, 3] = np.where(answers[:, 3] == 2, 6, 2)
    assert reference.answer_mismatches(F, swapped, snaps)[0] > 0


def test_waters_and_band_against_loops():
    rng = np.random.default_rng(11)
    k, d = 4, 10
    W_s, W = rng.normal(size=(2, k, d)).astype(np.float32)
    b_s, b = rng.normal(size=(2, k))
    lw, hw = reference.waters(np.zeros(k), np.zeros(k), W, b, W_s, b_s,
                              M=1.5, p=2.0)
    for v in range(k):
        dw = float(np.sqrt(np.sum((W[v] - W_s[v]).astype(np.float64) ** 2)))
        db = b[v] - float(np.float32(b_s[v]))
        assert np.isclose(lw[v], min(0.0, -1.5 * dw + db), rtol=1e-6)
        assert np.isclose(hw[v], max(0.0, 1.5 * dw + db), rtol=1e-6)
    eps = np.array([-1.0, 0.0, 0.5, 2.0])
    assert reference.in_band(eps, -1.0, 0.5).tolist() == [True, True, False,
                                                          False]
