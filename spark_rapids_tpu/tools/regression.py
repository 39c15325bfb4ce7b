"""The shared regression-detection core.

``tools history regress`` judges the latest ingested run against the
accumulated baseline; the warehouse's ingest records a failed run
without its numbers.  Both must agree on what a failed run looks like
(placeholder-zero payloads are skipped, never treated as a −100%
regression), so the thresholds and the failed-run detector live here.

Noise model: with ≥ ``min_runs`` baseline samples the band around the
baseline median is ``max(rel_threshold·|median|, band_k·1.4826·MAD)``
— the MAD term widens the band for genuinely noisy metrics (a 5% rule
on a metric that jitters 20% run-to-run cries wolf every run), the
relative floor keeps a perfectly stable metric from flagging on
femtosecond drift.  1.4826 scales the median absolute deviation to a
Gaussian sigma.  Stdlib-only, like the rest of the toolkit.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

#: the relative floor of the band: >5% the wrong way is a regression
REL_THRESHOLD = 0.05

#: baseline samples required before a verdict is trusted at all
DEFAULT_MIN_RUNS = 3

#: MAD multiplier (k·1.4826·MAD ≈ k sigma for Gaussian noise)
DEFAULT_BAND_K = 3.0


def run_failure(payload: Dict) -> Optional[str]:
    """A payload from a run that FAILED rather than measured: its
    numbers are placeholders (value 0, vs_baseline 0.0 from a run's
    failsafe), and comparing against them would report a −100%/÷0
    'regression' where the honest verdict is 'run failed'."""
    if not isinstance(payload, dict):
        return None
    # a run that produced a real primary value is a (possibly partial)
    # measurement even if a later phase tripped the budget alarm; only
    # a placeholder-zero payload is a failed run
    if payload.get("value"):
        return None
    if payload.get("budget_exceeded"):
        return str(payload.get("error") or "budget exceeded")
    if payload.get("error"):
        return str(payload["error"])
    return None


def median(xs: Sequence[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    if n % 2:
        return float(s[mid])
    return (s[mid - 1] + s[mid]) / 2.0


def mad(xs: Sequence[float]) -> float:
    """Median absolute deviation from the median."""
    if not xs:
        return 0.0
    m = median(xs)
    return median([abs(x - m) for x in xs])


def detect(history: Sequence[float], latest: float,
           higher_better: bool,
           min_runs: int = DEFAULT_MIN_RUNS,
           rel_threshold: float = REL_THRESHOLD,
           band_k: float = DEFAULT_BAND_K) -> Dict:
    """Latest sample vs baseline history, noise-aware.

    Returns a verdict dict: ``regression`` (bool), ``skipped`` (True
    when the baseline is too thin for a verdict), plus the evidence
    (baseline median, band width, the latest value and its delta)."""
    n = len(history)
    out: Dict = {"n_baseline": n, "latest": latest,
                 "regression": False, "skipped": False}
    if n < min_runs:
        out["skipped"] = True
        out["reason"] = f"baseline too thin ({n} < {min_runs} runs)"
        return out
    med = median(history)
    band = max(rel_threshold * abs(med), band_k * 1.4826 * mad(history))
    out["median"] = round(med, 6)
    out["band"] = round(band, 6)
    delta = latest - med
    out["delta"] = round(delta, 6)
    if med:
        out["delta_pct"] = round(delta / abs(med) * 100.0, 2)
    wrong_way = -delta if higher_better else delta
    if wrong_way > band:
        out["regression"] = True
        direction = "below" if higher_better else "above"
        out["reason"] = (f"latest {latest:.6g} is {direction} the "
                         f"baseline median {med:.6g} by more than the "
                         f"noise band ±{band:.6g} "
                         f"(n={n}, MAD-aware)")
    return out


def summarize(verdicts: List[Dict]) -> Dict:
    """Rollup for a batch of metric verdicts: counts + exit code."""
    regressions = [v for v in verdicts if v.get("regression")]
    skipped = [v for v in verdicts if v.get("skipped")]
    return {"checked": len(verdicts) - len(skipped),
            "skipped": len(skipped),
            "regressions": len(regressions),
            "exit_code": 1 if regressions else 0}
