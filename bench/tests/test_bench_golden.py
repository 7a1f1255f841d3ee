"""Qwen3 through its architecture module gives the numbers recorded before
the benchmark looked architectures up by ``model_type``
(``data/qwen3_golden.json``, by ``record_golden.py``): the weights' leaf
checksums and the operation counts exactly, the plain reference's losses,
gradient norms and change bit for bit."""
from __future__ import annotations

import json
import os

import pytest

from bench.tests import record_golden, tiny


@pytest.fixture(scope="module")
def numbers():
    with open(os.path.join(tiny.ROOT, "bench", "tests", "data",
                           "qwen3_golden.json")) as f:
        want = json.load(f)
    return want, record_golden.numbers()


@pytest.mark.parametrize("key", ["checksums", "flops", "losses",
                                 "grad_norms", "delta_norms", "sketch_norms"])
def test_qwen3_numbers_equal_the_golden_record(numbers, key):
    want, got = numbers
    assert got[key] == want[key]
