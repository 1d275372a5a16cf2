"""Model, whole step: model FLOPs of every prefill and decode execution in
the trace over their device time times the chip's bf16 peak, in percent.
FLOPs come from ``bench/cost/<family>.py`` and the service's shapes; an
execution is charged to the service whose ``job`` span it ran in."""


def _flops(svc, kind):
    m, spec = svc.model, svc.spec
    b, p, n = spec["batch"], spec["prompt_len"], spec["new_tokens"]
    if kind == "prefill":
        return m.cost.prefill_flops(m.dims, b, p)
    # the i-th decode step attends to the prompt, the tokens so far and itself
    return sum(m.cost.decode_flops(m.dims, b, p + i + 1) for i in range(n)) / n


def read(record):
    r = record["trace"]
    if r is None:
        return None
    flops = secs = 0.0
    for mod in r.modules:
        kind = ("prefill" if "prefill" in mod.name
                else "decode" if "decode" in mod.name else None)
        svc = record["services"].get(mod.service)
        if kind is None or svc is None:
            continue
        flops += _flops(svc, kind)
        secs += (mod.end - mod.start) / 1e9
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * record["peak"]["bf16_flops_per_s"])
