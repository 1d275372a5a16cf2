"""Serving engine / device (``serving/engine.py``): the share of the time
inside ``job`` spans in which no operation runs on the device, in percent.
It is what ``generate``'s host work between device calls costs a job.
From the profiler trace."""
import tracefile


def read(record):
    r = record["trace"]
    if r is None or not r.devices or not r.jobs:
        return None
    total = sum(e - s for _, s, e in r.jobs)
    busy = sum(tracefile.covered(r.busy, s, e) for _, s, e in r.jobs)
    return 100.0 * (total - busy) / total if total > 0 else None
