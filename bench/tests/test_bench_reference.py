import numpy as np
import pytest

from bench import reference


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_exact_topk_matches_brute_force(metric):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((300, 16)).astype(np.float32)
    q = rng.standard_normal((7, 16)).astype(np.float32)
    ids, scores = reference.exact_topk(x, q, 5, metric, block=64)
    for i in range(len(q)):
        if metric == "l2":
            s = [float(((x[j].astype(np.float64) - q[i]) ** 2).sum())
                 for j in range(len(x))]
        else:
            s = [-float(np.dot(x[j].astype(np.float64), q[i]))
                 for j in range(len(x))]
        want = sorted(range(len(x)), key=lambda j: s[j])[:5]
        assert ids[i].tolist() == want
        np.testing.assert_allclose(scores[i], [s[j] for j in want],
                                   rtol=1e-9)


def test_scores_of_agrees_with_topk():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((200, 8)).astype(np.float32)
    q = rng.standard_normal((4, 8)).astype(np.float32)
    ids, scores = reference.exact_topk(x, q, 3)
    np.testing.assert_allclose(reference.scores_of(x, q, ids), scores,
                               rtol=1e-9)
