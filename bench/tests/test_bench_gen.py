"""The traffic generator: rows are a pure function of the seed, step and
worker, every row of a step differs, and large seeds work."""
import numpy as np

from bench import gen, spec


def _rows(seed, step, traffic="b8_s4096_4w_psum"):
    t = dict(spec.traffic(traffic), seq=256)
    return gen.worker_batches(t, 32064, seed, step)


def test_same_seed_same_rows_and_large_seeds():
    a, b = _rows(3_000_000_000, 2), _rows(3_000_000_000, 2)
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


def test_rows_differ_across_workers_steps_and_seeds():
    rows = [r["tokens"] for s in (0, 1) for r in _rows(7, s)] \
        + [r["tokens"] for r in _rows(8, 0)]
    flat = np.concatenate(rows)
    assert len({row.tobytes() for row in flat}) == len(flat)


def test_targets_are_the_next_token_and_last_is_masked():
    r = _rows(5, 0)[0]
    np.testing.assert_array_equal(r["targets"][:, :-1], r["tokens"][:, 1:])
    assert np.all(r["mask"][:, -1] == 0) and np.all(r["mask"][:, :-1] == 1)
    assert r["tokens"].min() >= 0 and r["tokens"].max() < 32064
