"""Set-up, the measured window and the traced round of one cell.

The window drives the program's own serving entry: weights made by
`weights.py` from the seed, prefill and decode executables from
``repro.launch.serve.compile_steps`` and greedy decode through
``repro.launch.serve.decode_tokens``. ``decode_tokens`` is handed a thin
wrapper around the compiled decode step that reads the step's token ids
back to the host and stamps their delivery, as a streaming server does
for each token it sends. Every stamp follows a read-back, so it waits for
the device.

Host spans, written into the profiler's trace when one runs: ``round``,
``prompt_upload``, ``prefill_call``, ``decode_call`` and
``token_readback``."""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from chipbench import traffic as traffic_lib
from chipbench import weights as weights_lib

SPANS = ("round", "prompt_upload", "prefill_call", "decode_call",
         "token_readback")


@dataclass
class Round:
    start: float             # when the round's requests were due
    deliveries: np.ndarray   # (D + 1,) delivery time of each served token
    tokens: np.ndarray       # (B, D + 1) served token ids


@dataclass
class Server:
    """One cell's program, set up: weights, both executables, traffic."""
    cfg: object
    traffic: dict
    seed: int
    params: object
    prefill: object
    decode: object
    device: object
    vocab: int
    step_wrap: object = None   # tests plant faults in the decode step here
    setup_parts: dict = field(default_factory=dict)

    def serve_round(self, prompts: np.ndarray, start: float) -> Round:
        from repro.launch.serve import decode_tokens

        ann = jax.profiler.TraceAnnotation
        times, toks = [], []
        step = self.decode if self.step_wrap is None \
            else self.step_wrap(self.decode)

        def delivered(p, t, c):
            with ann("decode_call"):
                t2, lg, c2 = step(p, t, c)
            with ann("token_readback"):
                toks.append(np.asarray(t2))
            times.append(time.perf_counter())
            return t2, lg, c2

        with ann("round"):
            with ann("prompt_upload"):
                dev_prompts = jax.device_put(prompts, self.device)
            with ann("prefill_call"):
                tok, _, cache = self.prefill(self.params, dev_prompts)
            with ann("token_readback"):
                toks.append(np.asarray(tok))
            times.append(time.perf_counter())
            out = decode_tokens(self.cfg, delivered, self.params, tok, cache,
                                None, self.traffic["decode_steps"])
        del out, cache, tok       # free the round's cache and logits
        return Round(start, np.asarray(times), np.concatenate(toks, axis=1))

    def prompts(self, round_idx: int) -> np.ndarray:
        return traffic_lib.prompts(self.traffic, self.vocab, self.seed,
                                   round_idx)


def set_up(cfg, traffic: dict, seed: int, vocab: int, device,
           step_wrap=None) -> Server:
    """Weights from the seed, both programs compiled (or loaded from the
    persistent cache), and one warm call of each through the same path the
    window takes."""
    from repro.launch.serve import compile_steps
    from repro.models import init_params

    t = [time.perf_counter()]
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = weights_lib.maker(shapes)(
        jax.device_put(weights_lib.seed_words(seed), device))
    jax.block_until_ready(params)
    t.append(time.perf_counter())
    B, P = traffic["batch"], traffic["prompt_len"]
    prompts = jax.ShapeDtypeStruct((B, P), np.int32)
    prefill_c, decode_c = compile_steps(cfg, params, prompts, None,
                                        traffic_lib.cache_len(traffic))
    t.append(time.perf_counter())
    server = Server(cfg, traffic, seed, params, prefill_c, decode_c, device,
                    vocab, step_wrap)
    warm = dict(traffic, decode_steps=1)
    Server(cfg, warm, seed, params, prefill_c, decode_c, device,
           vocab).serve_round(np.zeros((B, P), np.int32), time.perf_counter())
    t.append(time.perf_counter())
    server.setup_parts = {"weights_s": t[1] - t[0], "compile_s": t[2] - t[1],
                          "warm_s": t[3] - t[2]}
    return server


@dataclass
class Window:
    rounds: list = field(default_factory=list)
    start: float = 0.0
    end: float = 0.0


def run_window(server: Server, seconds: float) -> Window:
    """Closed-loop rounds from the window's start until the round in flight
    when ``seconds`` have passed has delivered its last token."""
    w = Window(start=time.perf_counter())
    due = w.start
    while True:
        prompts = server.prompts(len(w.rounds))
        r = server.serve_round(prompts, due)
        w.rounds.append(r)
        due = float(r.deliveries[-1])
        if due - w.start >= seconds:
            break
    w.end = due
    return w
