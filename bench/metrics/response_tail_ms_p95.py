"""Executor layer: 95th percentile of job response R (due release to
completion), in ms, over every job of every service completed with the
profiler off (the untraced rest of a traced run).
A tail at four fifths of the knee: it rises by a whole job or more each time
the host stands still and jobs queue behind the stall, so it is reported
here, beside the end-to-end median, and bounds nothing."""
import jobs


def read(record):
    r = [j.response * 1e3 for j in record["jobs"]]
    return jobs.percentile(r, 95) if r else None
