"""Executor layer (``runtime/executor.py``): 95th percentile of the time a
job waits between its due release and its start, queued behind other jobs
or in the executor's polling sleep.  From the executor's ``EventTrace`` of
the jobs run with the profiler off: wait = response - (complete - start)."""
import jobs


def read(record):
    waits = [j.wait * 1e3 for j in record["jobs"]]
    return jobs.percentile(waits, 95) if waits else None
