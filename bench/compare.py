"""The comparison that decides ``correct``.

Both sides, the simulator's studies and the plain reference, are read as
per-replica arrays in the simulator's output layout.  From them
:func:`features` takes one number per replica for each compared output,
and :func:`z_scores` sets the program's mean of each against the
reference's, in units of the pooled standard error.  :func:`reported`
takes the numbers users read from a study's aggregated ``Replications``
(its ``stats`` and its pooled ``histograms``), and :func:`stat_zs` sets
each against the reference's arrays, in units of the reference's
sampling noise.  The exact check counts replicas that did not come back
finished.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional

import numpy as np

#: per-replica scalars compared by their means
SCALARS = ("total_time", "n_failures", "n_host_selections",
           "n_standby_swaps", "n_manual_repairs")
#: histogram channels compared by their per-replica mean binned value
CHANNELS = ("run_duration", "recovery", "waiting")
#: percentiles of the aggregated stats that are compared
PCTS = (50, 90, 99)


def midpoints(edges: np.ndarray) -> np.ndarray:
    """Value of each count slot: underflow, the bins, overflow."""
    e = np.asarray(edges, np.float64)
    return np.concatenate([[0.5 * e[0]], np.sqrt(e[:-1] * e[1:]), [e[-1]]])


def features(arrays: Dict[str, np.ndarray],
             edges: Optional[np.ndarray]) -> Dict[str, np.ndarray]:
    """One value per replica for each compared output (NaN = no value).

    The ring and the histograms give features only where the arrays
    carry them; ``edges`` is None for a config without histograms.
    """
    out = {k: np.asarray(arrays[k], np.float64) for k in SCALARS
           if k in arrays}
    with np.errstate(invalid="ignore", divide="ignore"):
        ring = np.asarray(arrays.get("run_durations", np.zeros((0, 0))),
                          np.float64)
        if ring.ndim == 2 and ring.shape[1]:
            n_valid = np.minimum(np.asarray(arrays["n_runs"], np.int64),
                                 ring.shape[1])
            valid = np.arange(ring.shape[1])[None, :] < n_valid[:, None]
            out["ring_mean"] = np.where(
                n_valid > 0, (ring * valid).sum(1) / n_valid, np.nan)
        if edges is not None:
            mid = midpoints(edges)
            for ch in CHANNELS:
                if f"hist_{ch}" in arrays:
                    counts = np.asarray(arrays[f"hist_{ch}"], np.float64)
                    n = counts.sum(1)
                    out[f"{ch}_hist_mean"] = np.where(
                        n > 0, counts @ mid / n, np.nan)
    return out


def z_score(x: np.ndarray, y: np.ndarray) -> float:
    """Pooled-variance two-sample z of mean(x) against mean(y)."""
    x = x[np.isfinite(x)]
    y = y[np.isfinite(y)]
    if len(x) < 2 or len(y) < 2:
        return math.inf
    mx, my = x.mean(), y.mean()
    var = (((x - mx) ** 2).sum() + ((y - my) ** 2).sum()) / (len(x)
                                                               + len(y) - 2)
    se = math.sqrt(var * (1.0 / len(x) + 1.0 / len(y)))
    if se == 0.0:
        return 0.0 if mx == my else math.inf
    return float((mx - my) / se)


def z_scores(prog: Dict[str, np.ndarray],
             ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """z of every output the reference gives; one the program lacks
    reads infinite."""
    return {k: z_score(prog.get(k, np.zeros(0)), ref[k]) for k in ref}


def unfinished(arrays: Dict[str, np.ndarray], replicas: int) -> int:
    """Replicas of one point that did not come back finished."""
    done = np.asarray(arrays["completed"], np.float64)
    return int(replicas - done.sum() + max(len(done) - replicas, 0))


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every compared number is within its limit."""
    return all(values[k] <= limits[k] for k in limits)


def check_lines(values: Dict[str, float],
                limits: Dict[str, float]) -> List[str]:
    return [f"check {k} {values[k]!r} limit {limits[k]!r}" for k in limits]


def worst_z(pairs: Iterable[Dict[str, float]]) -> float:
    return max((abs(z) for zs in pairs for z in zs.values()), default=math.inf)


def worst_name(pairs: Iterable[Dict[str, float]]) -> str:
    """The compared output with the largest |z|, for the log."""
    return max(((abs(z), k) for zs in pairs for k, z in zs.items()),
               default=(math.inf, "none"))[1]


def slot_bounds(edges) -> tuple:
    """[lower, upper) of each count slot: underflow from 0, the bins,
    overflow to infinity."""
    e = np.asarray(edges, np.float64)
    return np.concatenate([[0.0], e]), np.concatenate([e, [np.inf]])


def hist_percentile(edges, counts, q: float) -> float:
    """Percentile of pooled counts, linear inside its slot; the overflow
    slot reads its lower edge."""
    counts = np.asarray(counts, np.float64)
    total = counts.sum()
    if total == 0:
        return math.nan
    target = q / 100.0 * total
    cum = np.cumsum(counts)
    i = min(int(np.searchsorted(cum, target, side="left")), len(counts) - 1)
    lo, hi = slot_bounds(edges)
    if i == len(counts) - 1:
        return float(lo[i])
    below = cum[i - 1] if i else 0.0
    frac = min(max((target - below) / max(counts[i], 1e-30), 0.0), 1.0)
    return float(lo[i] + frac * (hi[i] - lo[i]))


def counts_below(edges, counts: np.ndarray, x: float) -> np.ndarray:
    """Per replica, the records below ``x``, the slot that holds ``x``
    counted in proportion: the rule :func:`hist_percentile` inverts."""
    lo, hi = slot_bounds(edges)
    i = int(np.clip(np.searchsorted(lo, x, side="right") - 1, 0,
                    len(lo) - 1))
    frac = (min(max((x - lo[i]) / (hi[i] - lo[i]), 0.0), 1.0)
            if math.isfinite(hi[i]) else 0.0)
    return counts[:, :i].sum(1) + frac * counts[:, i]


def reported(rep) -> Dict[str, float]:
    """The numbers users read from one point's aggregated study: from
    ``rep.stats`` the means of :data:`SCALARS`, the percentiles of
    ``total_time`` over replicas and each channel's pooled mean and
    percentiles; and the channel's mean and percentiles again, worked
    out from ``rep.histograms``."""
    out = {}
    for k in SCALARS:
        if k in rep.stats:
            out[f"{k}.mean"] = rep.stats[k].mean
    if "total_time" in rep.stats:
        for p in PCTS:
            out[f"total_time.p{p}"] = rep.stats["total_time"].percentiles[p]
    for ch, h in rep.histograms.items():
        st = rep.stats.get(f"{ch}_dist")
        if st is not None:
            out[f"{ch}_dist.mean"] = st.mean
            for p in PCTS:
                out[f"{ch}_dist.p{p}"] = st.percentiles[p]
        counts = np.asarray(h.counts, np.float64)
        out[f"{ch}_hist.mean"] = (float(counts @ midpoints(h.edges)
                                        / counts.sum())
                                  if counts.sum() else math.nan)
        for p in PCTS:
            out[f"{ch}_hist.p{p}"] = hist_percentile(h.edges, counts, p)
    return out


def _z(diff: float, se: float) -> float:
    if not math.isfinite(diff):
        return math.inf
    if se == 0.0:
        return 0.0 if diff == 0.0 else math.inf
    return float(diff / se)


def mean_z(x: np.ndarray, value: float, n_other: int) -> float:
    """z of a mean over ``n_other`` replicas against the reference's."""
    x = x[np.isfinite(x)]
    if len(x) < 2:
        return math.inf
    se = x.std(ddof=1) * math.sqrt(1.0 / len(x) + 1.0 / n_other)
    return _z(value - x.mean(), se)


def quantile_z(x: np.ndarray, value: float, q: float) -> float:
    """Exact binomial test of ``value`` as the ``q`` quantile of the
    reference's replicas, as a signed normal score."""
    from scipy import special, stats

    x = x[np.isfinite(x)]
    if not math.isfinite(value) or len(x) < 2:
        return math.inf
    n, k = len(x), int((x <= value).sum())
    # two-sided p-value summed in logs, so that a far tail stays finite
    logpmf = stats.binom.logpmf(np.arange(n + 1), n, q)
    log_half_p = min(special.logsumexp(logpmf[:k + 1]),
                     special.logsumexp(logpmf[k:]), math.log(0.5))
    return float(math.copysign(-special.ndtri_exp(log_half_p), k / n - q))


def ratio_z(num: np.ndarray, den: np.ndarray, value: float,
            n_other: int, share: Optional[float] = None) -> float:
    """z of a pooled ratio over ``n_other`` replicas against the
    reference's sum(num) / sum(den), with the variance of a ratio over
    replicas (records within a replica are not independent).  Where the
    ratio is a ``share`` of records, its variance is at least that of
    independent records."""
    n, tot = len(den), den.sum()
    if n < 2 or tot == 0:
        return math.inf
    r = num.sum() / tot
    var = ((num - r * den) ** 2).sum() / tot ** 2 * n / (n - 1)
    if share is not None:
        var = max(var, share * (1.0 - share) / tot)
    return _z(value - r, math.sqrt(var * (1.0 + n / n_other)))


def stat_zs(rep: Dict[str, float], ref: Dict[str, np.ndarray],
            edges: Optional[np.ndarray], n_prog: int) -> Dict[str, float]:
    """z of every aggregated number the reference's arrays give (see
    :func:`reported`); one the study lacks reads infinite."""
    zs = {}
    for k in SCALARS:
        if k in ref:
            zs[f"{k}.mean"] = mean_z(np.asarray(ref[k], np.float64),
                                     rep.get(f"{k}.mean", math.nan), n_prog)
    tt = np.asarray(ref["total_time"], np.float64)
    for p in PCTS:
        zs[f"total_time.p{p}"] = quantile_z(
            tt, rep.get(f"total_time.p{p}", math.nan), p / 100.0)
    if edges is None:
        return zs
    mid = midpoints(edges)
    for ch in CHANNELS:
        if f"hist_{ch}" not in ref:
            continue
        counts = np.asarray(ref[f"hist_{ch}"], np.float64)
        n_rec = counts.sum(1)
        for src in ("dist", "hist"):
            name = f"{ch}_{src}"
            zs[f"{name}.mean"] = ratio_z(counts @ mid, n_rec,
                                         rep.get(f"{name}.mean", math.nan),
                                         n_prog)
            for p in PCTS:
                v = rep.get(f"{name}.p{p}", math.nan)
                zs[f"{name}.p{p}"] = (
                    ratio_z(counts_below(edges, counts, v), n_rec,
                            p / 100.0, n_prog, share=p / 100.0)
                    if math.isfinite(v) else math.inf)
    return zs
