"""95th percentile of the time from each request's due time on the
open-loop schedule to its first output token, over every request due in
the window; a request never served ranks above all the others."""
from bench.lib import stats


def read(run):
    s = run.samples
    if not s["ttft_s"] and not s["ttft_missing_s"]:
        return None
    return stats.percentile_with_missing(s["ttft_s"], s["ttft_missing_s"],
                                         95) * 1e3
