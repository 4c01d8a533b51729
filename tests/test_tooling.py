"""Tooling correctness: data-pipeline determinism, batching runtime."""
import numpy as np

from repro.core.misd.batching import BatchAccumulator
from repro.training.data import TokenPipeline


def test_token_pipeline_deterministic_and_seekable():
    p1 = TokenPipeline(1000, 32, 4, seed=7)
    p2 = TokenPipeline(1000, 32, 4, seed=7)
    it1, it2 = p1.batches(), p2.batches()
    for _ in range(3):
        b1, b2 = next(it1), next(it2)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # seek: step 2 reproduced from a fresh iterator
    fresh = next(TokenPipeline(1000, 32, 4, seed=7).batches(start_step=2))
    np.testing.assert_array_equal(fresh["tokens"], b1["tokens"])


def test_batch_accumulator_deadline_and_target():
    acc = BatchAccumulator(target_batch=3, deadline_s=1.0)
    assert acc.add("a", now=0.0) is None
    assert acc.add("b", now=0.1) is None
    assert acc.poll(now=0.5) is None  # under deadline, under target
    got = acc.add("c", now=0.2)
    assert got == ["a", "b", "c"]  # target reached
    assert acc.add("d", now=5.0) is None
    assert acc.poll(now=6.1) == ["d"]  # deadline flush
