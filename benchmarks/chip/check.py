"""The comparison that decides ``correct``.

Every sampled answer (``traffic.CHECK_IMAGES`` images' worth, drawn from
the seed among all requests due in the window) is compared row by row with
the plain reference's logits of the same pool images. Two numbers, each
with its limit:

- ``answers_missing``: requests sent in the window whose answer failed or
  never came (limit 0).
- ``logit_rel_l2_max``: the largest relative L2 distance of one image's
  served logits from the reference's, ||y − y_ref|| / ||y_ref||. The limit
  is the configuration's ``limits.logit_rel_l2_max``, set between the
  largest reading of sound runs and the smallest of the int4 control.
"""
from __future__ import annotations

import numpy as np

def relative_gaps(answers: dict, starts: list, ref: np.ndarray) -> np.ndarray:
    """Per image: ||y − ref|| / ||ref|| over every sampled answer's rows."""
    if not answers:
        return np.zeros(0)
    ys, rs = [], []
    for i, y in answers.items():
        y = np.asarray(y, np.float64)
        ys.append(y)
        rs.append(ref[starts[i]:starts[i] + len(y)])
    y, r = np.concatenate(ys), np.concatenate(rs).astype(np.float64)
    return np.linalg.norm(y - r, axis=1) / np.maximum(np.linalg.norm(r, axis=1), 1e-30)


def decide(win, ref: np.ndarray, limits: dict) -> tuple:
    """(correct, checks): each check ``{"value", "limit"}``."""
    missing = len(win.error)
    gaps = relative_gaps(win.answers, win.start, ref)
    worst = float(gaps.max()) if len(gaps) else float("nan")
    checks = {
        "answers_missing": {"value": missing, "limit": 0},
        "logit_rel_l2_max": {"value": worst, "limit": limits["logit_rel_l2_max"]},
    }
    correct = (missing == 0 and len(gaps) > 0 and np.isfinite(worst)
               and worst <= limits["logit_rel_l2_max"])
    return bool(correct), checks
