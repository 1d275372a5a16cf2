"""A run's pieces end to end on the CPU at a tiny size, through the same
``harness.Cell`` that ``run.py`` drives, with the program's smoke config in
bfloat16 in place of the published one: set-up, admission, a short window,
the tally, and the check against the plain reference.

The control (the reference with fp8 weights in the program's place) has to
fail the limit, and so has a run whose timed path is broken underneath: a
decode step that returns its cache unchanged, a served token altered where
it is produced, and half of the batch left out."""
import dataclasses

import numpy as np
import pytest

import harness
import registry
from repro.configs import get_smoke_config
from repro.core import set_backend

LIMIT = 0.02      # at this size bf16 reads about 2e-3, the fp8 control 8e-2
SEED = 2**33 + 5  # wider than 32 bits: run.py takes any whole number

# OLMo's published keys (allenai/OLMo-1B-hf), for the family's other norm:
# a LayerNorm with no weight, and no qk-norm
OLMO = {"arch": "olmo-1b", "family": "dense", "dtype": "bfloat16",
        "config": {
            "model_type": "olmo", "vocab_size": 50304, "hidden_size": 2048,
            "intermediate_size": 8192, "num_hidden_layers": 16,
            "num_attention_heads": 16, "num_key_value_heads": 16,
            "rope_theta": 10000.0, "tie_word_embeddings": True}}


def smoke_dicts(monkeypatch, arch="qwen3-0.6b"):
    """The control cell and its configuration at the program's smoke size,
    serving ``arch``, with the program's smoke config in bf16 in place of
    the published one."""
    monkeypatch.setattr(harness, "get_config", lambda arch: dataclasses.replace(
        get_smoke_config(arch), dtype="bfloat16"))
    set_backend("numpy")
    smoke = get_smoke_config(arch)
    config = registry.config("qwen3-0.6b")
    if arch != "qwen3-0.6b":
        config["models"] = {arch: OLMO}
    config["models"][arch]["config"].update(
        vocab_size=smoke.vocab, hidden_size=smoke.d_model,
        intermediate_size=smoke.d_ff, num_hidden_layers=smoke.n_layers,
        num_attention_heads=smoke.n_heads,
        num_key_value_heads=smoke.n_kv_heads, head_dim=smoke.head_dim,
        rope_theta=smoke.rope_theta)
    cell = registry.workload("qwen3-0.6b.control")
    cell["services"][0].update(model=arch, batch=2, max_context=24,
                               prompt_len=16,
                               new_tokens=4, period_ms=250.0,
                               deadline_ms=250.0, gap_limit=LIMIT)
    cell["check_jobs"] = 4
    return cell, config


def _cell(monkeypatch, seed=SEED, arch="qwen3-0.6b"):
    cell, config = smoke_dicts(monkeypatch, arch)
    c = harness.Cell("smoke", seed, pool_jobs=32, cell=cell, config=config)
    c.build()
    c.time_warm_jobs(2)
    c.admit()
    return c


def _run(c, seconds=1.2):
    c.window(seconds)
    attempted, failed = c.tally(seconds)
    c.free_program()
    found = c.check(4, control=True)
    return attempted, failed, found


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "olmo-1b"])
def test_run_pieces_end_to_end(monkeypatch, arch):
    c = _cell(monkeypatch, arch=arch)
    svc = c.services[0]
    assert svc.bound_ms is not None and svc.step_s > 0
    attempted, failed, found = _run(c)
    assert attempted >= 4 and 0 <= failed <= attempted
    assert found["control"]["jobs"] == 4
    assert found["control"]["tokens"] == 4 * 2 * 4
    correct, compared = c.verdict(found)
    assert correct, compared
    assert compared == {"control.gap": {"value": found["control"]["gap"],
                                        "limit": LIMIT}}


def test_same_seed_same_prompts_and_weights(monkeypatch):
    a, b = _cell(monkeypatch), _cell(monkeypatch)
    np.testing.assert_array_equal(a.services[0].pool, b.services[0].pool)
    m = a.models["qwen3-0.6b"]
    wa, wb = m.weights(SEED), m.weights(SEED)
    np.testing.assert_array_equal(np.asarray(wa["wq"], np.float32),
                                  np.asarray(wb["wq"], np.float32))
    other = np.asarray(m.weights(SEED + 1)["wq"], np.float32)
    assert not np.array_equal(np.asarray(wa["wq"], np.float32), other)


def test_control_fails_the_limit(monkeypatch):
    c = _cell(monkeypatch, seed=11)
    _, _, found = _run(c)
    r = found["control"]
    assert r["gap"] <= LIMIT < r["control_gap"]
    assert r["control_gap"] >= 3 * r["gap"]
    # the control in the program's place reads not correct by the verdict
    # a run gives
    control = {n: dict(v, gap=v["control_gap"]) for n, v in found.items()}
    assert c.verdict(found)[0] and not c.verdict(control)[0]


def test_decode_that_keeps_its_cache_is_caught(monkeypatch):
    c = _cell(monkeypatch)
    eng = c.services[0].engine
    decode = eng._decode
    eng._decode = lambda p, tok, caches, n: (decode(p, tok, caches, n)[0],
                                             caches)
    _, _, found = _run(c)
    correct, compared = c.verdict(found)
    assert not correct, compared


def test_altered_token_is_caught(monkeypatch):
    c = _cell(monkeypatch)
    eng = c.services[0].engine
    generate = eng.generate

    def altered(prompts, max_new_tokens):
        out, stats = generate(prompts, max_new_tokens=max_new_tokens)
        out = out.copy()
        out[0, 1] = (out[0, 1] + 1) % eng.cfg.vocab
        return out, stats

    eng.generate = altered
    _, _, found = _run(c)
    correct, compared = c.verdict(found)
    assert not correct, compared


def test_half_the_batch_left_out_is_caught(monkeypatch):
    """Only the first half of the rows is served; the rest repeat it."""
    c = _cell(monkeypatch)
    eng = c.services[0].engine
    generate = eng.generate

    def half(prompts, max_new_tokens):
        h = len(prompts) // 2
        out, stats = generate(np.concatenate([prompts[:h], prompts[:h]]),
                              max_new_tokens=max_new_tokens)
        return out, stats

    eng.generate = half
    _, _, found = _run(c)
    correct, compared = c.verdict(found)
    assert not correct, compared
