"""The hybrid cell's benchmark files on the CPU: the reference's ``dims``
carry every key the harness and the dense counts read, and they agree with
the program's configuration; `work_hybrid`'s counts against a hand count
at a small size; the new readers read nothing without a trace and the
right number from one; and ``correct`` at a size a test run can hold: a
sound run passes and the float8 control fails.

The small model is granite-4.0-h-micro's code at d_model 128, 20 layers
(two periods of ten), 4 heads over 2 KV heads, d_ff 256, Mamba-2 8 heads
of 32, state 16, chunk 8 and a 4096-token vocabulary. The traffic is one
round of 8 prompts of 20 tokens (three chunks) and 40 decode steps, and
the sample is every request. On seed 3416000022 the sound program's
widest gap reads 0.0022 and its mean gap 1.2e-5, the control's 0.030 and
3.1e-4; the limits below sit between."""

import dataclasses
import json
import time
from types import SimpleNamespace

import jax
import pytest

from chipbench import check, harness, run, spec, work, work_hybrid

CELL = "granite-4.0-h-micro.decode"
SEED = 3416000022
LIMITS = {"max_logit_gap": 0.008, "mean_logit_gap": 6e-5}
READERS = ("hybrid_decode_roofline", "hybrid_prefill_roofline",
           "mfu.hybrid")


def full_dims():
    c = spec.load_cell(CELL)
    return spec.reference(c.config["reference"]).dims(c.config)


def small_conf() -> dict:
    conf = spec.load_cell(CELL).config
    return dict(
        conf, hidden_size=128, shared_intermediate_size=256,
        num_hidden_layers=20, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=4096, mamba_n_heads=8, mamba_d_head=32, mamba_expand=2,
        mamba_d_state=16, mamba_chunk_size=8,
        attention_multiplier=32 ** -0.5,
        layer_types=conf["layer_types"][:20])


def small_program():
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("granite-4.0-h-micro"), d_model=128, n_layers=20,
        d_ff=256, n_heads=4, n_kv_heads=2, vocab=4096, ssm_heads=8,
        ssm_head_dim=32, ssm_state=16, ssm_chunk=8)


TRAFFIC = {"loop": "closed_rounds", "batch": 8, "prompt_len": 20,
           "decode_steps": 40}


# ------------------------------------------------------------ the sizes

def test_dims_carry_what_the_harness_reads():
    """``check_program_config`` and the dense counts (`work.py`, which every
    traced run's ``roofline_bound`` calls) find their keys, and the file
    agrees with the program's configuration."""
    from repro.configs import get_config

    dims = full_dims()
    run.check_program_config(get_config("granite-4.0-h-micro"), dims)
    work.prefill_work(dims, 32, 512)
    work.decode_step_work(dims, 32, 512)
    cfg = get_config("granite-4.0-h-micro")
    assert (dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_groups"],
            dims["ssm_state"], dims["ssm_conv"], dims["ssm_chunk"]) == (
        cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
        cfg.ssm_conv, cfg.ssm_chunk)
    kinds = [("mamba" if k == "mamba2" else "attention")
             for k, _ in cfg.layer_kinds()]
    assert list(dims["layer_types"]) == kinds
    assert dims["ssm_state_bytes"] == 4 and dims["rotary_dim"] == 0


def test_dims_refuse_a_config_that_does_not_add_up():
    conf = dict(small_conf(), mamba_expand=3)
    with pytest.raises(ValueError, match="mamba_expand"):
        spec.reference("granite_hybrid").dims(conf)


# ------------------------------------------------------------ the counts

# A hand count: d 8, 2 layers (mamba, attention), 2 heads / 1 KV head of 4,
# d_ff 16, vocab 32; Mamba-2 2 heads of 4 (di 8), 1 group, N 3, conv 2.
SMALL = {"d_model": 8, "n_layers": 2, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 16, "vocab": 32, "bytes_per_param": 2,
         "layer_types": ("mamba", "attention"), "ssm_heads": 2,
         "ssm_head_dim": 4, "ssm_groups": 1, "ssm_state": 3, "ssm_conv": 2,
         "ssm_state_bytes": 4, "conv_state_bytes": 2}
# conv channels C = 8 + 2*3 = 14; in_proj 8 x (8 + 14 + 2) = 192,
# out_proj 8 x 8 = 64: 256 matrix weights in the Mamba-2 mixer
M_MATRIX = 8 * 24 + 64
A_MATRIX = 8 * (8 + 2 * 4) + 8 * 8           # Q, K, V, O = 192
MLP = 3 * 8 * 16                             # 384


def test_hybrid_counts_by_hand():
    assert work_hybrid.mamba_matrix_params(SMALL) == M_MATRIX == 256
    assert work_hybrid.attn_matrix_params(SMALL) == A_MATRIX == 192
    # Mamba-2: matrices, conv taps 2x14 and bias 14, gated norm 8 (bf16);
    # dt_bias, A_log, D 3x2 float32. Both layers: MLP and two norms of 8.
    mamba = (256 + 28 + 14 + 8) * 2 + 6 * 4                  # 636
    every = (384 + 16) * 2                                   # 800
    assert work_hybrid.weight_bytes(SMALL) == (
        mamba + 192 * 2 + 2 * every + (8 + 8 * 32) * 2)     # 3148
    # state: 2 heads x 4 x 3 float32, conv 1 x 14 bf16
    assert work_hybrid.state_bytes_per_request(SMALL) == 96 + 28
    assert work_hybrid.kv_bytes_per_token(SMALL) == 2 * 4 * 2

    step = work_hybrid.decode_step_work(SMALL, 2, 5)     # 5 filled
    token = (2 * 256 + 2 * 2 * 14 + 2 * (5 * 12 + 3 * 4)   # Mamba-2 mixer
             + 2 * 192 + 2 * 2 * 384)                      # attention, MLPs
    assert step.flops == 2 * token + 4 * 2 * 2 * 4 * 6 + 2 * 2 * 8 * 32
    assert step.bytes == 3148 + 2 * 8 * 2 + 2 * 6 * 16 + 2 * 2 * 124
    pre = work_hybrid.prefill_work(SMALL, 2, 10)
    assert pre.flops == (20 * token + 4 * 2 * 2 * 4 * 50
                         + 2 * 2 * 8 * 32)
    assert pre.bytes == 3148 + 20 * 16 + 20 * 16 + 2 * 124
    rnd = work_hybrid.decode_round_work(SMALL, 2, 10, 3)
    assert rnd.bytes == sum(work_hybrid.decode_step_work(SMALL, 2, t).bytes
                            for t in (10, 11, 12))


def test_cell_decode_is_memory_bound_and_state_heavy():
    """At the cell's shapes a decode step needs 11.4 GB, of which the
    Mamba-2 state read and written is 43 %; the prefill is bound by
    compute."""
    dims, peaks = full_dims(), work.load_peaks("TPU v5 lite")
    step = work_hybrid.decode_step_work(dims, 32, 512)
    assert step.bytes == pytest.approx(11.409e9, rel=1e-3)
    share = 2 * 32 * work_hybrid.state_bytes_per_request(dims) / step.bytes
    assert share == pytest.approx(0.429, abs=1e-3)
    assert work.roofline_s(step, peaks)[1] == "memory"
    pre = work_hybrid.prefill_work(dims, 32, 512)
    assert work.roofline_s(pre, peaks)[1] == "compute"


# ----------------------------------------------------------- the readers

def _run(trace):
    return SimpleNamespace(trace=trace, dims=full_dims(),
                           peaks=work.load_peaks("TPU v5 lite"),
                           traffic=spec.load_cell(CELL).traffic)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_a_trace(name):
    assert spec.reader(name)(_run(None)) is None
    run_ = _run(SimpleNamespace(decode_s=[0.02] * 256, prefill_s=[0.7],
                                window_s=6.0))
    run_.dims = {k: v for k, v in run_.dims.items() if k != "layer_types"}
    assert spec.reader(name)(run_) is None


def test_readers_from_a_trace():
    """One traced round: 256 decode steps of 20 ms, a 700 ms prefill, a
    6 s window."""
    run_ = _run(SimpleNamespace(decode_s=[0.02] * 256, prefill_s=[0.7],
                                window_s=6.0))
    d, peaks = run_.dims, run_.peaks
    need = sum(work.roofline_s(work_hybrid.decode_step_work(d, 32, 512 + i),
                               peaks)[0] for i in range(256))
    assert spec.reader("hybrid_decode_roofline")(run_) == pytest.approx(
        need / (256 * 0.02) * 100)
    pre = work_hybrid.prefill_work(d, 32, 512)
    assert spec.reader("hybrid_prefill_roofline")(run_) == pytest.approx(
        work.roofline_s(pre, peaks)[0] / 0.7 * 100)
    flops = pre.flops + work_hybrid.decode_round_work(d, 32, 512, 256).flops
    assert spec.reader("mfu.hybrid")(run_) == pytest.approx(
        flops / (6.0 * 197e12) * 100)
    for name in READERS:
        assert 0 < spec.reader(name)(run_) < 100


# ------------------------------------------------------------- correct

def test_sound_run_is_correct_and_the_control_is_not():
    """The served round, through the harness's own set-up, passes; the
    float8 control at the same positions fails."""
    ref = spec.reference("granite_hybrid")
    dims = ref.dims(small_conf())
    server = harness.set_up(small_program(), TRAFFIC, SEED, dims["vocab"],
                            jax.devices()[0])
    r = server.serve_round(server.prompts(0), time.perf_counter())
    picks = check.sample(1, 8, 8, SEED)
    seqs, served = check.sequences(TRAFFIC, dims["vocab"], SEED, picks,
                                   lambda _, row: r.tokens[row])
    g = check.reference_gaps(ref, dims, server.params, seqs, served, 4,
                             control=True)
    ok, checks = check.verdict(g, LIMITS)
    assert ok, checks
    ok, checks = check.verdict(
        {"gap": g["control_gap"], "out_of_vocab": 0}, LIMITS)
    assert not ok, checks


def test_config_file_keeps_the_published_keys():
    """Every key of the published configuration is in the file; the four
    muP multipliers, which the program does not run, are listed in
    ``reduced`` with their published values among the departures; the
    state dtypes, which the published file does not give, under
    ``assumed``."""
    conf = spec.load_cell(CELL).config
    assert conf["num_hidden_layers"] == 40 and conf["hidden_size"] == 2048
    assert conf["layer_types"].count("mamba") == 36
    assert conf["torch_dtype"] == "bfloat16"
    assert conf["ssm_state_dtype"] == "float32"
    assumed = json.dumps(conf["assumed"])
    assert "ssm_state_dtype float32" in assumed
    assert "conv_state_dtype bfloat16" in assumed
    text = json.dumps(conf["departures"])
    for published in ("0.015625", "12", "0.22", "8"):
        assert published in text
