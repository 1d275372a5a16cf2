"""Model, decode (``models/``): mean device time of one execution of the
decode program, from the ``XLA Modules`` line of the profiler trace."""


def read(record):
    r = record["trace"]
    steps = [m.end - m.start for m in (r.modules if r else ())
             if "decode" in m.name]
    return sum(steps) / len(steps) / 1e6 if steps else None
