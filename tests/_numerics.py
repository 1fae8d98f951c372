"""How far one request's served log-probabilities may move with the batch
around it.

XLA:CPU picks its dot, reduction and convolution code by operand shape, so
the same request computed in a batch of 5 and in a padded bucket of 4 can
round differently in the last place (measured: up to 2.9e-6).  The
rounding happens on the logits before ``log_softmax``, which reach
``|sims / answer_temp| = 20`` for NVSA, so the bound is in ulps at the
larger of ``LOGIT_SCALE`` and the row's largest magnitude.  Answers must
still be equal; log-probabilities must agree within ``ULPS`` such ulps.
"""

import numpy as np

ULPS = 4
LOGIT_SCALE = 32.0


def assert_logprobs_close(actual, desired, ulps: int = ULPS, err_msg=""):
    a = np.asarray(actual, np.float32)
    b = np.asarray(desired, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape, err_msg)
    scale = np.maximum(np.max(np.abs(b), axis=-1, keepdims=True),
                       np.float32(LOGIT_SCALE))
    tol = ulps * np.spacing(scale)
    bad = np.abs(a - b) > tol
    assert not bad.any(), (
        f"{err_msg}: {int(bad.sum())} logprobs beyond {ulps} ulp "
        f"(max |diff| {float(np.max(np.abs(a - b))):.3g}, "
        f"tol {float(np.max(tol)):.3g})")
