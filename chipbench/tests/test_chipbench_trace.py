"""Trace reduction on a small hand-made trace (data/small_trace.textproto).

Times below are in microseconds. The traced window is the host span
``round``, 100 .. 1100. Device plane 0's operations, clipped to it and
merged, cover 100-120, 150-380 (two overlapping fusions), 430-680 (a
while op enclosing a fusion and a copy), 730-900 and 910-990: 750 busy of
1000. Each idle part, put down to the program the device was inside at
its middle, or else to the innermost benchmark span the host was in:

    120-150  30  token_readback (130-400; "unrelated" is not ours)
    380-430  50  decode_call    (400-410)
    680-730  50  decode_call    (700-710)
    900-910  10  inside serve_step (730-990)
    990-1100 110 round

The decode step ran 430-680 and 730-990, so the one gap between them is
680-730, 50 of it idle. Self times: fusion.3 170 + 170, copy.1 70 + 80,
fusion.2 140, fusion.1 100, convert.1 20 (clipped at 100), while.1
250 - 170 - 70 = 10. Device plane 1 and the ops and module at 1200
(after the window) do not count."""

from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from chipbench import harness, spec, trace

DATA = Path(__file__).resolve().parent / "data" / "small_trace.textproto"
US = 1e-6


@pytest.fixture(scope="module")
def summary():
    events = trace.events_of(ProfileData.from_text_proto(DATA.read_text()),
                             harness.SPANS)
    return trace.summarize(events, prefill="prefill_step",
                           decode="serve_step")


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(1000 * US)
    assert summary.busy_s == pytest.approx(750 * US)


def test_program_times_and_decode_gap(summary):
    assert summary.prefill_s == pytest.approx([230 * US])
    assert summary.decode_s == pytest.approx([250 * US, 260 * US])
    assert summary.decode_gaps_s == pytest.approx([50 * US])


def test_top_ops(summary):
    names = [n for n, _ in summary.top_ops]
    assert names == ["fusion.3", "copy.1", "fusion.2", "fusion.1",
                     "convert.1", "while.1"]
    assert [t for _, t in summary.top_ops] == pytest.approx(
        [340 * US, 150 * US, 140 * US, 100 * US, 20 * US, 10 * US])


def test_idle_gaps_by_host_span(summary):
    assert [n for n, _ in summary.idle_gaps] == [
        "round x1", "decode_call x2", "token_readback x1",
        "inside serve_step x1"]
    assert [t for _, t in summary.idle_gaps] == pytest.approx(
        [110 * US, 100 * US, 30 * US, 10 * US])


def test_readers(summary):
    run = SimpleNamespace(trace=summary)
    assert spec.reader("device_idle_share")(run) == pytest.approx(25.0)
    assert spec.reader("decode_gap_ms")(run) == pytest.approx(0.05)


def test_work_readers(summary):
    """A one-layer toy at 1e6 FLOP/s and 1e6 B/s, worked by hand: a layer
    has 2·(2+2·2) + 2·2 + 3·2·2 = 28 weights, the head 2·4 = 8, and a
    position's K and V take 8 bytes. Prefill (B 1, P 2): 144 FLOPs, 96
    bytes; decode steps with 2 and 3 positions filled: 96 and 104 FLOPs,
    100 and 108 bytes."""
    dims = {"d_model": 2, "n_layers": 1, "n_heads": 1, "n_kv_heads": 1,
            "head_dim": 2, "d_ff": 2, "vocab": 4, "bytes_per_param": 2}
    run = SimpleNamespace(
        trace=summary, dims=dims,
        traffic={"batch": 1, "prompt_len": 2, "decode_steps": 2},
        peaks={"bf16_flop_per_s": 1e6, "hbm_bytes_per_s": 1e6})
    # prefill ran 230 us; the two decode steps 250 + 260 us
    assert spec.reader("prefill_roofline")(run) == pytest.approx(
        144 / 230 * 100)
    assert spec.reader("mfu.prefill")(run) == pytest.approx(144 / 230 * 100)
    assert spec.reader("decode_roofline")(run) == pytest.approx(
        (100 + 108) / 510 * 100)
    assert spec.reader("mfu.decode")(run) == pytest.approx(
        (96 + 104) / 510 * 100)
    assert spec.reader("mfu")(run) == pytest.approx(
        (144 + 96 + 104) / 1000 * 100)


def test_reader_with_nothing_to_read_returns_nothing():
    empty = trace.Summary(window_s=1.0, busy_s=0.5)
    run = SimpleNamespace(trace=empty)
    assert spec.reader("decode_gap_ms")(run) is None
    assert spec.reader("prefill_roofline")(run) is None
    assert spec.reader("decode_roofline")(run) is None
    assert spec.reader("mfu.prefill")(run) is None
    assert spec.reader("mfu.decode")(run) is None


def test_load_reads_the_binary_file(tmp_path):
    raw = ProfileData.text_proto_to_serialized_xspace(DATA.read_text())
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    events = trace.load(tmp_path, harness.SPANS)
    kinds = {k: sum(e.kind == k for e in events)
             for k in ("op", "module", "span")}
    assert kinds == {"op": 9, "module": 4, "span": 8}


def test_a_program_mapped_to_just_before_the_round_counts():
    ev = [trace.Event("span", "round", 1.0, 2.0),
          trace.Event("module", "jit_prefill_step(1)", 0.999, 1.2),
          trace.Event("module", "jit_serve_step(2)", 1.3, 1.4),
          trace.Event("module", "jit_serve_step(2)", 0.5, 0.6)]
    s = trace.summarize(ev, prefill="prefill_step", decode="serve_step")
    assert s.prefill_s == pytest.approx([0.201])
    assert s.decode_s == pytest.approx([0.1])


def test_short_names():
    assert trace.short_name("%copy.96 = bf16[40,64]{1,0} copy(%x)") == \
        "copy.96"
    assert trace.short_name("jit_serve_step(14224714294208494905)") == \
        "serve_step"


def test_interval_helpers():
    merged = trace.union([(5, 7), (1, 3), (2, 4)])
    assert merged == [(1, 4), (5, 7)]
    assert trace.covered(merged, 0, 6) == 4
    assert trace.idle_intervals(merged, 0, 8) == [(0, 1), (4, 5), (7, 8)]
