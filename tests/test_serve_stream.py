"""Serving-launcher runtime pieces: `WeightStream` mode selection (was
CLI-only) and the `decode_tokens` context-threading regression (the
`(A and B) or C` operator-precedence bug)."""

import numpy as np
import pytest

from repro.launch.serve import WeightStream, decode_tokens, schedule_report


def _params(sizes: dict[str, int]):
    rng = np.random.default_rng(0)
    return {name: rng.standard_normal(n // 4).astype(np.float32)
            for name, n in sizes.items()}


# --------------------------------------------------------- WeightStream

def test_zero_copy_packing_respects_half_total_cap():
    """Cold-leaf (largest-first) zero-copy packing must stay under half
    the total weight bytes, skipping leaves that would overflow the cap
    in favour of smaller ones that still fit."""
    sizes = {"big": 400 * 1024, "mid": 300 * 1024, "small": 200 * 1024,
             "tiny": 60 * 1024}
    ws = WeightStream(_params(sizes), 2, budget_frac=0.5, policy="lrf",
                      mode="zero_copy")
    zc = ws.executor._zc_leaves
    total = sum(sizes.values())
    assert sum(sizes[p] for p in zc) <= total // 2
    # greedy largest-first: 'big' fits (400k <= 480k); 'mid' would
    # overflow (700k) and is skipped; 'tiny' still fits after 'big'
    assert zc == {"big", "tiny"}
    # zero-copy leaves never migrate: their accesses are remote
    ws.step()
    assert ws.executor.mgr.n_zerocopy > 0


def test_svm_aware_skips_pinning_when_hot_leaf_dominates():
    """The pinned-full-pool deadlock guard: a hot leaf above half the
    budget is streamed, not pinned (prefetch still engages)."""
    sizes = {"embed": 400 * 1024, "l0": 40 * 1024, "l1": 40 * 1024}
    ws = WeightStream(_params(sizes), 2, budget_frac=0.5, policy="lrf",
                      mode="svm_aware")
    assert ws.executor.prefetch
    assert not ws.executor.mgr.pinned


def test_svm_aware_pins_hot_leaf_when_it_fits():
    sizes = {"embed": 100 * 1024, "l0": 60 * 1024, "l1": 60 * 1024,
             "l2": 60 * 1024}
    ws = WeightStream(_params(sizes), 2, budget_frac=0.8, policy="lrf",
                      mode="svm_aware")
    ex = ws.executor
    assert ex.prefetch
    assert set(ex.plan.leaf_ranges["embed"]) == ex.mgr.pinned


def test_report_fields_consistent_with_executor_metrics():
    sizes = {f"l{i}": 64 * 1024 for i in range(8)}
    ws = WeightStream(_params(sizes), 2, budget_frac=0.4, policy="lrf",
                      mode="naive")
    for _ in range(5):
        ws.step()
    m = ws.executor.metrics()
    rep = ws.report(5)
    assert f"{m['migrations']} migs / {m['evictions']} evicts" in rep
    assert f"e2m {m['evict_to_mig']:.2f}" in rep
    assert f"DOS {m['dos']:.0f}%" in rep
    assert f"{m['wall_s'] * 1e3:.2f}ms" in rep
    assert (f"{m['segment_cache_misses']} compiled / "
            f"{m['segment_cache_hits']} cached replays") in rep
    assert "5 tokens" in rep


# ------------------------------------------- decode_tokens context threading

class _Cfg:
    def __init__(self, *, vlm=False, encdec=False):
        self.is_vlm = vlm
        self.is_encdec = encdec


class _Step:
    """Records the context argument of every decode call."""

    def __init__(self):
        self.calls = []

    def __call__(self, params, tok, cache, ctx=_Cfg):   # sentinel default
        self.calls.append(ctx)
        return tok + 1, -tok, cache


def test_decoder_only_takes_two_arg_path():
    step = _Step()
    outs, logits, cache = decode_tokens(_Cfg(), step, {}, 0, "kv", None, 3)
    assert outs == [1, 2, 3] and cache == "kv"
    assert logits == [0, -1, -2]
    assert step.calls == [_Cfg, _Cfg, _Cfg]      # ctx never passed


def test_vlm_threads_image_context_without_encoding(monkeypatch):
    import repro.models

    def boom(*a):  # pragma: no cover — must not run for VLMs
        raise AssertionError("encode() must not run for VLM decode")

    monkeypatch.setattr(repro.models, "encode", boom)
    step = _Step()
    decode_tokens(_Cfg(vlm=True), step, {}, 0, "kv", "img", 2)
    assert step.calls == ["img", "img"]


def test_encdec_reencodes_context_each_step(monkeypatch):
    import repro.models

    monkeypatch.setattr(repro.models, "encode",
                        lambda params, cfg, ctx: ("enc", ctx))
    step = _Step()
    decode_tokens(_Cfg(encdec=True), step, {}, 0, "kv", "frames", 2)
    assert step.calls == [("enc", "frames"), ("enc", "frames")]


def test_vlm_without_context_takes_plain_path_regression():
    """The old `ctx is not None and cfg.is_encdec or cfg.is_vlm` parsed
    as `(A and B) or C`: a VLM config with no context entered the
    context branch and passed ctx=None explicitly.  The intended
    `A and (B or C)` must take the plain two-arg path."""
    step = _Step()
    decode_tokens(_Cfg(vlm=True), step, {}, 0, "kv", None, 2)
    assert step.calls == [_Cfg, _Cfg]


def test_schedule_report_mentions_key_fields():
    from repro.core import MB
    from repro.svm import ModelSpec, run_schedule

    spec = ModelSpec.synthetic("a", 4, MB, embed_bytes=MB)
    r = run_schedule([spec], 3, 2 * spec.total_bytes, policy="fifo",
                     seed=0, tokens=4)
    rep = schedule_report(r)
    assert "svm sched[fifo]" in rep
    assert f"{r['migrations']} migs / {r['evictions']} evicts" in rep
    assert f"{r['segment_shared_hits']} cross-request replays" in rep


# ------------------------------------------- the served path on the CPU

def _chip_smoke():
    """The repo-root `chip_smoke.py`, loaded by path (it is no package)."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_decode_logits_match_forward():
    """Prefill plus decode through the served path's cache gives the
    logits that `forward` gives over the same tokens. The prompt-wide
    cache the path used to build let the first decoded token overwrite
    prompt position 0 and missed by 0.11-0.23 here."""
    import jax.numpy as jnp

    from repro.launch.serve import parse_args, serve
    from repro.models import forward

    res = serve(parse_args(["--arch", "granite-3-2b", "--reduced",
                            "--batch", "2", "--prompt-len", "8",
                            "--decode", "3"]))
    cfg = res["cfg"]
    assert res["tokens"].shape == (2, 4)
    assert res["logits"].shape == (2, 4, cfg.padded_vocab)
    seq = jnp.concatenate([res["prompts"], res["tokens"][:, :-1]], axis=1)
    ref = forward(res["params"], cfg, seq)[0][:, 7:]
    np.testing.assert_allclose(
        np.asarray(res["logits"][..., :cfg.vocab], np.float32),
        np.asarray(ref[..., :cfg.vocab], np.float32), rtol=0, atol=1e-2)
    # the served tokens are the greedy picks of those logits
    np.testing.assert_array_equal(
        np.asarray(res["tokens"]),
        np.asarray(jnp.argmax(res["logits"], axis=-1)))


def test_chip_smoke_path_on_cpu_at_reduced_size():
    """`chip_smoke.py`'s own path, steered to the reduced config: the
    same serve arguments and the same logits check, without the backend
    check that keeps its `main` off the CPU."""
    cs = _chip_smoke()
    res, chk = cs.smoke(cs.SERVE_ARGS + ["--reduced"])
    assert chk["ok"], chk
    assert chk["positions"] == 33
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert res["stream"].startswith("svm stream (simulated)")
    assert res["schedule"]["n_requests"] == 8
    assert res["compile_s"] > 0 and res["prefill_s"] > 0
    assert res["tok_s"] == pytest.approx(8 * 32 / res["decode_s"])


def test_logits_check_fails_a_prompt_wide_cache(monkeypatch):
    """The check's tolerance is tight enough to catch the old cache,
    which was as wide as the prompt: decoded tokens then overwrote the
    prompt's oldest positions."""
    import repro.launch.serve as serve_mod
    from repro.launch import steps

    make = steps.make_prefill_step
    monkeypatch.setattr(serve_mod, "make_prefill_step",
                        lambda cfg, cache_len=None: make(cfg))
    cs = _chip_smoke()
    _, chk = cs.smoke(cs.SERVE_ARGS + ["--reduced"])
    assert not chk["ok"], chk


def test_chip_smoke_refuses_a_cpu_backend(capsys):
    cs = _chip_smoke()
    assert cs.main() == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
