"""The benchmark's copy of the token hash."""
import numpy as np

from harness import tokens


def test_copy_matches_the_program_hash():
    from repro.data.pipeline import _hash_tokens

    for seed in (0, 7, 2 ** 31 + 5):
        np.testing.assert_array_equal(
            tokens.hash_tokens(seed, 3, (4, 65), 96),
            _hash_tokens(seed, 3, (4, 65), 96))


def test_same_seed_same_batch_rows_differ():
    a = tokens.batch(2 ** 31 + 11, 0, 64, 128, 96)["tokens"]
    b = tokens.batch(2 ** 31 + 11, 0, 64, 128, 96)["tokens"]
    c = tokens.batch(2 ** 31 + 11, 1, 64, 128, 96)["tokens"]
    np.testing.assert_array_equal(a, b)
    assert a.shape == (64, 129) and a.dtype == np.int32
    assert len({r.tobytes() for r in np.concatenate([a, c])}) == 128
    assert a.min() >= 0 and a.max() < 96
