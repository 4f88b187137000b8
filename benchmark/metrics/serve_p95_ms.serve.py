"""95th percentile, over ALL requests of the traced window, of the time from
the instant a request was due to the instant its answer was in the
collector's hands; a failed, shed or late request counts as 1e6 ms. Not an
end-to-end metric: between runs of one seed it read 61 and 99 ms (quartile
spread 25 %), more than any admissible bound holds (PERF.md section 6)."""


def read(run):
    return run.counters["summary"]["serve_p95_ms"]
