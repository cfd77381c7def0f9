"""``correct`` at a size a test run can hold, on the CPU: a sound run
passes; the control (the reference with float8 weights in the program's
place) fails; and a run whose timed path is broken underneath fails, once
for each fault a serving cell can have.

The model is granite-3-2b's code at d_model 128, 2 layers, 4 heads over
2 KV heads, d_ff 256 and an 8192-token vocabulary; the traffic is one
closed-loop round of 8 prompts of 8 tokens and 64 decode steps, and the
sample is all 8 requests. On seed 7 the sound program's widest gap reads
0.00054 and its mean gap 2.4e-6, the control's 0.0225 and 1.6e-4; the
limits below sit between."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, harness, run, spec

SEED = 7
LIMITS = {"max_logit_gap": 0.005, "mean_logit_gap": 2e-5}
CONF = {"arch": "granite-3-2b", "reference": "dense_decoder",
        "model_type": "granite", "hidden_size": 128,
        "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 8192, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "attention_multiplier": 32 ** -0.5,
        "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
        "logits_scaling": 1.0}
TRAFFIC = {"loop": "closed_rounds", "batch": 8, "prompt_len": 8,
           "decode_steps": 64}
CELL = spec.Cell("tiny", 1, CONF, TRAFFIC,
                 {"sample_requests": 8, "reference_block": 4,
                  "limits": LIMITS})


def program_cfg():
    from repro.configs import get_config
    return dataclasses.replace(get_config("granite-3-2b"), d_model=128,
                               n_layers=2, d_ff=256, n_heads=4,
                               n_kv_heads=2, vocab=8192)


def measure(step_wrap=None):
    # --seconds 0: the window is the first round
    return run.measure(CELL, SEED, 0.0, False, jax.devices()[0],
                       time.perf_counter(), program_cfg=program_cfg(),
                       step_wrap=step_wrap)


def test_sound_run_is_correct():
    res = measure()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["rounds"] == 1
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_logit_gap"]["limit"] == 0.005


def test_control_is_not_correct():
    dims = spec.reference("dense_decoder").dims(CONF)
    server = harness.set_up(program_cfg(), TRAFFIC, SEED, dims["vocab"],
                            jax.devices()[0])
    r = server.serve_round(server.prompts(0), time.perf_counter())
    picks = check.sample(1, 8, 8, SEED)
    seqs, served = check.sequences(TRAFFIC, dims["vocab"], SEED, picks,
                                   lambda _, row: r.tokens[row])
    g = check.reference_gaps(spec.reference("dense_decoder"), dims,
                             server.params, seqs, served, 4, control=True)
    ok, _ = check.verdict(g, LIMITS)
    assert ok
    ok, checks = check.verdict(
        {"gap": g["control_gap"], "out_of_vocab": 0}, LIMITS)
    assert not ok, checks


def altered_token(step):
    calls = [0]

    def wrapped(p, t, c):
        t2, lg, c2 = step(p, t, c)
        calls[0] += 1
        if calls[0] == 10:
            t2 = (t2 + 1) % 8192
        return t2, lg, c2
    return wrapped


def state_unchanged(step):
    def wrapped(p, t, c):
        t2, lg, _ = step(p, t, jax.tree.map(jnp.copy, c))
        return t2, lg, c
    return wrapped


def half_batch(step):
    def wrapped(p, t, c):
        t2, lg, c2 = step(p, t, c)
        return jnp.concatenate([t2[:4], t2[:4]]), lg, c2
    return wrapped


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_batch])
def test_broken_timed_path_is_not_correct(fault):
    res = measure(step_wrap=fault)
    assert not res["correct"], res["checks"]
    assert np.isfinite(res["checks"]["max_logit_gap"]["value"])
