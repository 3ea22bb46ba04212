"""Window arithmetic: percentiles and the accounting of a serve window.
Pure Python on lists of numbers, so that it runs on a fake clock."""

import math


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list: the
    smallest value with at least q% of the samples at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    v = sorted(values)
    k = max(0, min(len(v) - 1, math.ceil(q / 100.0 * len(v)) - 1))
    return v[k]


def mean(values):
    if not values:
        raise ValueError("mean of no samples")
    return math.fsum(values) / len(values)


class RequestLog:
    """One request as the client saw it: when it was due, when it was
    handed to the server, and the time of every token."""

    __slots__ = ("due", "submitted", "token_times", "prompt_len",
                 "max_new", "handle", "failed")

    def __init__(self, due, prompt_len, max_new):
        self.due = due
        self.submitted = None
        self.token_times = []
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.handle = None
        self.failed = False

    @property
    def finished(self):
        return len(self.token_times) >= self.max_new


def window_samples(logs, t0, t1):
    """What a window [t0, t1) holds, from the clients' logs.

    * ``ttft``: first token minus due time, over requests DUE inside the
      window whose first token has come (a request carried in from the
      pre-roll was due before t0 and is not a sample; one still waiting
      at t1 is counted in ``unanswered``).
    * ``gaps``: every gap between consecutive tokens of one request that
      ENDS inside the window -- requests in flight at t0 carry in, those
      unfinished at t1 are cut at the edge.
    * ``late``: submit minus due, over requests due inside the window.
    """
    ttft, gaps, late, unanswered, failed = [], [], [], 0, 0
    for r in logs:
        if t0 <= r.due < t1:
            if r.failed:
                failed += 1
            elif r.token_times:
                ttft.append(r.token_times[0] - r.due)
            else:
                unanswered += 1
            if r.submitted is not None:
                late.append(r.submitted - r.due)
        tt = r.token_times
        for a, b in zip(tt, tt[1:]):
            if t0 <= b < t1:
                gaps.append(b - a)
    return dict(ttft=ttft, gaps=gaps, late=late, unanswered=unanswered,
                failed=failed,
                attempted=sum(1 for r in logs if t0 <= r.due < t1))
