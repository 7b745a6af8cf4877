"""The comparison that decides ``correct``.

Every answer the window returned is compared with the configuration's plain
reference, run after the window on the same seed's corpus and query pool:

  failed       requests that never came back with a 200 (an answer that
               comes late is late, and counts in the latency, not here)
  bad_answers  answers that are not k distinct ids of stored rows
  miss_share   share of the answers' ids that the reference's progressive
               search does not return for that query
  source_miss  share of the answers to noisy copies of stored rows that
               lack the copied row, by far the copy's nearest row: a stage
               0 that scans the wrong rows (an IVF probe of the wrong or too
               few lists, members that are not the list's) loses it
  score_gap    widest gap between a served score and the reference's
               full-dimension score of the same id, over ||q||^2

A configuration's ``correct`` block names the numbers it is held to and
their limits; a number passes when it is at or under its limit.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def hits(ids: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(R, k) bool: whether each id of each row is among ``want``'s row."""
    return (ids[:, :, None] == want[:, None, :]).any(-1)


def numbers(served: Dict[str, np.ndarray], queries: np.ndarray,
            sources: np.ndarray, ref, prog_ids: np.ndarray, n_docs: int
            ) -> Dict[str, float]:
    """The compared numbers for the answers ``served`` (status, qidx, ids,
    scores) to the pool ``queries`` (``sources``: the row each query copies,
    -1 for a fresh draw), against the reference ``ref`` and its progressive
    ids."""
    ok = served["status"] == 200
    q = served["qidx"][ok]
    ids = served["ids"][ok].astype(np.int64)
    k = ids.shape[1]
    srt = np.sort(ids, axis=1)
    bad = ((ids < 0) | (ids >= n_docs)).any(1) | (srt[:, 1:] == srt[:, :-1]
                                                   ).any(1)
    out = {"failed": float(np.count_nonzero(~ok)),
           "bad_answers": float(np.count_nonzero(bad))}
    if not ids.size:
        out.update(miss_share=1.0, source_miss=1.0, score_gap=float("inf"))
        return out
    out["miss_share"] = float(1.0 - hits(ids, prog_ids[q]).mean())
    src = sources[q]
    copy = src >= 0
    out["source_miss"] = float(1.0 - (ids[copy] == src[copy, None]).any(1)
                               .mean()) if copy.any() else 0.0
    key = np.concatenate([q[:, None], ids], axis=1)
    uniq, inv = np.unique(key, axis=0, return_inverse=True)
    ref_s = ref.scores_of(queries[uniq[:, 0]], uniq[:, 1:])[inv.reshape(-1)]
    qn = np.sum(queries[q].astype(np.float64) ** 2, axis=1)[:, None]
    gap = np.abs(served["scores"][ok].astype(np.float64) - ref_s) / qn
    gap = np.where(np.isnan(ref_s), 0.0, np.where(np.isnan(gap), np.inf, gap))
    out["score_gap"] = float(gap.max()) if k else 0.0
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(all within limits, {name: {"value", "limit"}}) for the limited."""
    checks = {name: {"value": values[name], "limit": float(lim)}
              for name, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def recall(ids: np.ndarray, status: np.ndarray, exact: np.ndarray) -> float:
    """Mean share of the exact top-k in each answer; a failed request
    counts as none found."""
    found = hits(ids.astype(np.int64), exact).mean(1)
    return float(np.where(status == 200, found, 0.0).mean())
