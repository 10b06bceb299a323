"""The low-precision control fails the limits, at a size a test holds.

On the chip, ``bench/calibrate.py --control fp8`` reads the control at
each cell's own size (see PERF.md for the readings the limits were set
from).  Here the same reference functions run at the tiny test size on
the CPU, against the tiny configuration's limits, on three seeds.
"""

import numpy as np
import pytest

from bench import counters
from bench.reference import dense_transformer, gemm
from bench.tests.conftest import TINY_CONFIG


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_fp8_control_fails_logit_gap(seed):
    w = dense_transformer.make_weights(TINY_CONFIG, seed)
    rng = np.random.default_rng(seed)
    pairs = [(rng.integers(1, 256, 40).astype(np.int32),
              rng.integers(1, 256, 20).astype(np.int32)) for _ in range(3)]
    ctrl = dense_transformer.widest_gap(TINY_CONFIG, w, pairs,
                                        control="fp8")
    assert ctrl > TINY_CONFIG["limits"]["logit_gap"]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_fp8_control_fails_row_err(seed):
    classes = counters.gemm_classes(TINY_CONFIG, 32)
    triples = [(a, bs[0], None) for a, bs in gemm.make_inputs(classes, seed)]
    ctrl = gemm.worst_row_error(triples, "fp8")["row_err"]
    assert ctrl > TINY_CONFIG["limits"]["row_err"]


def test_reference_agrees_with_itself_in_float32():
    """The plain reference reads 0 against its own float32 tokens."""
    w = dense_transformer.make_weights(TINY_CONFIG, 4)
    prompt = np.arange(1, 30, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):             # greedy decode by the reference itself
        logits = dense_transformer.logits_at(
            TINY_CONFIG, w, np.array(seq, np.int32),
            np.array([len(seq) - 1]))
        seq.append(int(np.argmax(np.asarray(logits)[0])))
    served = np.array(seq[len(prompt):], np.int32)
    gaps = dense_transformer.served_gaps(TINY_CONFIG, w, prompt, served)
    assert np.max(gaps) == 0.0
