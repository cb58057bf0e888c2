"""The traffic generator: seeded prompts and the open loop's arrivals."""
import numpy as np
import pytest

from bench import traffic


def test_prompts_are_seeded_printable_and_distinct():
    a = traffic.prompt(2**33 + 1, 0, 256)
    assert a == traffic.prompt(2**33 + 1, 0, 256)
    assert len(a) == 256 and all(32 <= ord(c) < 127 for c in a)
    assert a != traffic.prompt(2**33 + 1, 1, 256)
    assert a != traffic.prompt(2**33 + 2, 0, 256)


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**40])
def test_open_arrivals_same_gaps_for_every_seed(seed):
    t = traffic.open_arrivals(4.0, 30.0, seed)
    base = traffic.open_arrivals(4.0, 30.0, 7)
    assert len(t) == len(base) == 120
    assert t[0] == 0.0 and all(0 <= x < 30.0 for x in t)
    # the same set of gaps, in another order
    gaps = np.diff(t + [30.0])
    assert np.allclose(np.sort(gaps), np.sort(np.diff(base + [30.0])))
    assert not np.allclose(gaps, np.diff(base + [30.0]))


def test_validate_names_what_is_missing():
    traffic.validate({"loop": "closed", "clients": 2, "prompt_bytes": 8,
                      "max_tokens": 32})
    with pytest.raises(ValueError, match="rate_per_s"):
        traffic.validate({"loop": "open", "prompt_bytes": 8,
                          "max_tokens": 32})
    with pytest.raises(ValueError):
        traffic.validate({"loop": "bursty"})
