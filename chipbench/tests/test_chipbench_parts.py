"""The decode step and the decode gap by part, on a small hand-made trace
(data/scoped_trace.textproto) whose ops take their ``op_name`` from a
hand-made decode program (data/scoped_decode.hlo).

Times below are in microseconds. The traced window is the host span
``round``, 100 .. 1000. The prefill ran 130-280 and the decode step three
times, 320-450, 530-660 and 740-870, each the same 130 of ops:

    fusion           320-325   5  gather, no scope          unscoped
    while.3          325-430 105  layers/while: self 5      scan
      fusion.152     325-345  20  attention/dot_general     attention
      fusion.154     345-355  10  attention/kv_cache        kv_cache
      copy.63        355-360   5  kv_cache/..;attention/..  kv_cache
      copy.64        360-375  15  layers/.../dynamic_slice  scan
      fusion.161     375-415  40  mlp                       mlp
      c_d-u-s_f.4    415-425  10  layers/.../d_u_s          scan
    conv_select_f.   430-440  10  lm_head                   lm_head
    iota_reduce_f.   440-445   5  lm_head                   lm_head
    copy.94          445-450   5  no metadata               unscoped

So a step is attention 20, kv_cache 15, mlp 40, lm_head 15, scan 30 and
unscoped 10. The prefill's op (named fusion.152 too) and the step at 1200,
after the window, do not count. The turns ``serve.step`` end at 480, 700
and 900, so the gaps 450-530 and 660-740 split into readback 30 and 40,
launch 50 and 40."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import harness, parts, trace

DATA = Path(__file__).resolve().parent / "data"
US = 1e-6


@pytest.fixture(scope="module")
def profile():
    return ProfileData.from_text_proto(
        (DATA / "scoped_trace.textproto").read_text())


@pytest.fixture(scope="module")
def names():
    return parts.op_names((DATA / "scoped_decode.hlo").read_text())


@pytest.fixture(scope="module")
def events(profile):
    return trace.events_of(profile, harness.SPANS + (parts.TURN,))


@pytest.fixture(scope="module")
def reduced(events, names):
    return parts.reduce(events, names, "serve_step")


def test_op_names_from_the_program_text(names):
    assert names["copy.63"] == ("jit(serve_step)/layers/while/body/"
                                "closed_call/attention/kv_cache/squeeze")
    assert names["while.3"] == "jit(serve_step)/layers/while"
    assert "copy.94" not in names
    assert "tuple.47" not in names


@pytest.mark.parametrize("op_name, part", [
    ("jit(serve_step)/layers/while/body/closed_call/attention/kv_cache/"
     "scatter", "kv_cache"),
    ("jit(serve_step)/layers/while/body/closed_call/attention/dot_general",
     "attention"),
    ("jit(serve_step)/layers/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(serve_step)/lm_head/reduce", "lm_head"),
    ("jit(serve_step)/layers/while/body/dynamic_update_slice", "scan"),
    ("jit(serve_step)/layers/while", "scan"),
    ("jit(serve_step)/jit(_take)/gather", "unscoped"),
    ("jit(serve_step)/while/body/dynamic_slice", "unscoped"),
    (None, "unscoped"),
])
def test_part_is_the_innermost_scope(op_name, part):
    assert parts.part_of(op_name) == part


def test_the_eight_numbers(reduced):
    assert reduced.n_decode == 3
    got = parts.metrics(reduced)
    want = {"decode_ms.attention": 0.020, "decode_ms.kv_cache": 0.015,
            "decode_ms.mlp": 0.040, "decode_ms.lm_head": 0.015,
            "decode_ms.scan": 0.030, "decode_ms.unscoped": 0.010,
            "decode_gap.readback_ms": 0.035, "decode_gap.launch_ms": 0.045}
    assert got == pytest.approx(want)
    assert list(got) == list(want)
    assert reduced.decode_ops["scan"] == pytest.approx(
        {"while.3": 15 * US, "copy.64": 45 * US,
         "constant_dynamic-update-slice_fusion.4": 30 * US})


def test_parts_partition_the_decode_steps_and_gaps(events, reduced):
    summary = trace.summarize([e for e in events if e.name != parts.TURN],
                              prefill="prefill_step", decode="serve_step")
    assert sum(reduced.decode_s.values()) == pytest.approx(
        sum(summary.decode_s))
    assert [r + la for r, la in zip(reduced.readback_s, reduced.launch_s)] \
        == pytest.approx(summary.decode_gaps_s)


def test_turns_leave_the_existing_summary_as_it_was(profile, events):
    """Loading ``serve.step`` beside the benchmark's spans, and leaving it
    out of what `trace.summarize` reads, gives the summary the benchmark's
    spans alone give: every field, the idle-gap labels among them."""
    alone = trace.summarize(trace.events_of(profile, harness.SPANS),
                            prefill="prefill_step", decode="serve_step")
    beside = trace.summarize([e for e in events if e.name != parts.TURN],
                             prefill="prefill_step", decode="serve_step")
    assert beside == alone
    assert [n for n, _ in alone.idle_gaps] == [
        "round x3", "decode_call x1", "prefill_call x1"]
    assert [t for _, t in alone.idle_gaps] == pytest.approx(
        [290 * US, 40 * US, 30 * US])
    assert alone.decode_gaps_s == pytest.approx([80 * US, 80 * US])


def test_a_program_without_scopes_or_turns_gives_nothing(events, names):
    """The parent program: the same ops with no scope in their names, and
    no turns on the host."""
    unscoped = {n: "/".join(c for c in v.split("/")
                            if c not in parts.SCOPES)
                for n, v in names.items()}
    no_turns = [e for e in events if e.name != parts.TURN]
    reduced = parts.reduce(no_turns, unscoped, "serve_step")
    assert reduced.n_decode == 3
    assert parts.metrics(reduced) == {}


def test_a_negative_part_is_not_clamped():
    """A turn that ends after the next step starts on the device (the two
    clocks disagree) gives a negative launch part."""
    ev = [trace.Event("span", "round", 0.0, 10.0),
          trace.Event("module", "jit_serve_step(2)", 1.0, 2.0),
          trace.Event("module", "jit_serve_step(2)", 3.0, 4.0),
          trace.Event("span", parts.TURN, 0.5, 3.2),
          trace.Event("span", parts.TURN, 3.3, 4.5)]
    reduced = parts.reduce(ev, {}, "serve_step")
    assert parts.metrics(reduced) == pytest.approx(
        {"decode_gap.readback_ms": 1200.0, "decode_gap.launch_ms": -200.0})
    assert reduced.lead_s == pytest.approx([0.5, -0.3])


def test_turns_that_do_not_match_the_steps_are_an_error():
    ev = [trace.Event("span", "round", 0.0, 10.0),
          trace.Event("module", "jit_serve_step(2)", 1.0, 2.0),
          trace.Event("module", "jit_serve_step(2)", 3.0, 4.0),
          trace.Event("span", parts.TURN, 0.5, 2.5)]
    with pytest.raises(ValueError, match="1 serve.step spans"):
        parts.reduce(ev, {}, "serve_step")
