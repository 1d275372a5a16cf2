"""End to end: median job response R (due release to completion), in ms,
over every job of every service completed in the window."""
import jobs


def read(record):
    r = [j.response * 1e3 for j in record["jobs"]]
    return jobs.percentile(r, 50) if r else None
