"""How ``correct`` is decided: served tokens against the plain reference.

After the window closes, a sample of the requests it finished is drawn
from the seed: ``sample_requests`` of them, taken in turn from equal
slices of the batch (so both halves of a batch are always looked at), each
from a round drawn at random. The reference runs once over each sampled
prompt followed by its served tokens, in float32, and reads, at every
served position, how far the served token's logit lies below the
reference's best logit there. Greedy decoding serves the program's own
best token, so a sound program lies below only by rounding.

Numbers compared, each with its limit from ``workloads/<cell>.json``
(``limits``; a number without a limit there is not compared):

- ``max_logit_gap``: the widest such gap over the sample, which a single
  wrong token moves;
- ``mean_logit_gap``: the mean gap over every served position of the
  sample, which a lower precision moves at many positions at once;
- ``out_of_vocab``: served tokens outside the vocabulary (limit 0).

The control (``quantize=True``) puts the reference, with its weights in
float8, in the program's place: at the same positions it takes the token
that float8 puts first and reads that token's gap."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.weights import seed_words


def sample(n_rounds: int, batch: int, k: int, seed: int) -> list:
    """``k`` distinct (round, row) pairs drawn from the seed (all of them,
    where the window served fewer): the rows taken in turn from
    ``min(k, batch)`` equal slices of the batch, each from a random
    round."""
    rng = np.random.default_rng([*seed_words(seed), 0x5A3])
    k = min(k, n_rounds * batch)
    strata = np.array_split(np.arange(batch), min(k, batch))
    picks: list = []
    i = 0
    while len(picks) < k:
        s = strata[i % len(strata)]
        pick = (int(rng.integers(n_rounds)), int(rng.choice(s)))
        if pick not in picks:
            picks.append(pick)
            i += 1
    return picks


@functools.lru_cache(maxsize=None)
def _gap_program(ref, dims_items: tuple, first: int, control: bool):
    dm = dict(dims_items)

    @jax.jit
    def gaps(params, seqs, served):
        lg = ref.logits(params, dm, seqs, first)             # (n, T, V)
        best = jnp.max(lg, axis=-1)
        valid = served < dm["vocab"]
        got = jnp.take_along_axis(
            lg, jnp.where(valid, served, 0)[..., None], axis=-1)[..., 0]
        out = {"gap": jnp.where(valid, best - got, 0.0),
               "out_of_vocab": jnp.sum(~valid)}
        if control:
            pick = jnp.argmax(ref.logits(params, dm, seqs, first,
                                         quantize=True), axis=-1)
            out["control_gap"] = best - jnp.take_along_axis(
                lg, pick[..., None], axis=-1)[..., 0]
        return out

    return gaps


def sequences(cell_traffic: dict, vocab: int, seed: int, picks: list,
              served_of) -> tuple[np.ndarray, np.ndarray]:
    """Reference inputs for the picked requests: prompt + served tokens but
    the last (n, P + D), and the served tokens (n, D + 1) they predict.
    ``served_of(round, row)`` gives a request's served tokens."""
    seqs, served = [], []
    for r, row in picks:
        prompt = traffic_lib.prompts(cell_traffic, vocab, seed, r)[row]
        s = np.asarray(served_of(r, row), np.int32)
        seqs.append(np.concatenate([prompt, s[:-1]]))
        served.append(s)
    return np.stack(seqs), np.stack(served)


def reference_gaps(ref, dims: dict, params, seqs: np.ndarray,
                   served: np.ndarray, block: int,
                   control: bool = False) -> dict:
    """Per-position gaps (n, D + 1) of the served tokens (and of the
    control's tokens), with the reference run ``block`` sequences at a
    time."""
    first = seqs.shape[1] - served.shape[1]
    fn = _gap_program(ref, tuple(sorted(dims.items())), first, control)
    parts = []
    for i in range(0, len(seqs), block):
        out = fn(params, jnp.asarray(seqs[i:i + block]),
                 jnp.asarray(served[i:i + block]))
        parts.append(jax.tree.map(np.asarray, out))
    res = {k: np.concatenate([p[k] for p in parts])
           for k in parts[0] if k != "out_of_vocab"}
    res["out_of_vocab"] = int(sum(p["out_of_vocab"] for p in parts))
    return res


def verdict(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """The numbers compared, each with its limit, and whether all hold."""
    values = {"max_logit_gap": float(np.max(gaps["gap"])),
              "mean_logit_gap": float(np.mean(gaps["gap"]))}
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in values.items() if k in limits}
    checks["out_of_vocab"] = {"value": gaps["out_of_vocab"], "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
