"""Median, in milliseconds, of every gap between consecutive output tokens of
every request (the lead-in's too), where the later token came inside the
window: the streaming speed a user sees between admissions."""
from bench import stats


def read(run):
    w = run.window
    gaps = stats.token_gaps([r.token_times for r in w.served], w.start, w.end)
    if not gaps:
        return None
    return 1e3 * stats.percentile(gaps, 50)
